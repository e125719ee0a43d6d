"""The vmapped population trainer — the framework's hot loop.

Reference call stack being replaced (SURVEY.md §3; reference unreadable,
contract from BASELINE.json): Coordinator → MPI send → N MPIWorker ranks
each train one trial → MPI gather of scores. Here the N workers ARE one
XLA program: ``jax.jit(jax.vmap(member_step))`` over a leading population
axis, scanned over steps so the whole multi-step training segment is a
single device computation — hyperparameters are *data* (one row per
member), so one compilation serves every trial the search will ever
propose.

Design notes (TPU):
- member step = loss + grad + SGD/momentum update fused in one vmapped
  function; XLA sees [P, ...] batched matmuls/convs that tile the MXU.
- the minibatch is shared across members (one gather from the on-device
  dataset per step); per-member *augmentation* decorrelates members,
  with member-folded RNG. Augmentation = per-sample horizontal flip +
  per-member-per-step circular shift (jnp.roll) — branchless, fusable.
- hyperparameters (lr, momentum, weight decay, aug strengths) enter as
  an ``OptHParams`` of [P]-vectors; inside the vmap each member sees
  scalars. PBT can therefore mutate them between segments without
  recompiling anything.
- optimizer state (momentum) lives beside params in ``PopState``; PBT
  exploit is a single ``jax.tree.map(lambda x: x[src_idx], state)`` —
  the weight copy the reference does with MPI point-to-point transfers
  becomes one on-device gather.
- datasets stay device-resident across the entire search (one host →
  device transfer per search, vs per-trial pickling over MPI).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, Callable

import flax.struct
import jax
import jax.numpy as jnp


@flax.struct.dataclass
class OptHParams:
    """Per-member hyperparameters; every field is a [P] vector."""

    lr: jax.Array
    momentum: jax.Array
    weight_decay: jax.Array
    flip_prob: jax.Array  # per-sample horizontal flip probability
    shift: jax.Array  # max augmentation shift in pixels (continuous)

    @staticmethod
    def defaults(n: int, lr: float = 0.1) -> "OptHParams":
        f = lambda v: jnp.full((n,), v, dtype=jnp.float32)
        return OptHParams(f(lr), f(0.9), f(1e-4), f(0.5), f(3.0))


@flax.struct.dataclass
class PopState:
    """Population training state: leading axis = member."""

    params: Any
    momentum: Any
    step: jax.Array  # int32[P]


def trainer_jit(static_argnames=(), donate_argnames=()):
    """``jax.jit`` for a program whose FIRST argument is the trainer.

    Jitting with the trainer as a static argument would key the
    function's process-wide jit cache on it: every trainer ever passed,
    and every executable compiled for it, then lives until the process
    exits (each XLA:CPU executable holds ~10-30 memory mappings; a
    process that had built a few hundred trainers ran into
    ``vm.max_map_count`` and died inside the next compile). Instead the
    jitted wrapper is built once per trainer around
    ``partial(fn, trainer)`` and kept ON the trainer, so a trainer's
    programs are released with it. Trainers hash by identity, so no
    cache entry was ever shared between two of them anyway.

    ``decorated.program(trainer)`` is that trainer's jitted wrapper, for
    callers that need ``.lower``.
    """

    def deco(fn):
        def program(trainer):
            jitted = trainer._programs.get(fn)
            if jitted is None:
                bound = functools.partial(fn, trainer)
                # the program's name in compile logs and device traces
                # (a bare partial reads "unknown"); NOT __wrapped__,
                # which would put ``trainer`` back into the signature
                # the argnames are resolved against
                bound.__name__ = fn.__name__
                bound.__qualname__ = fn.__qualname__
                jitted = trainer._programs[fn] = jax.jit(
                    bound,
                    static_argnames=static_argnames,
                    donate_argnames=donate_argnames,
                )
            return jitted

        @functools.wraps(fn)
        def call(trainer, *args, **kwargs):
            return program(trainer)(*args, **kwargs)

        call.program = program
        return call

    return deco


def _augment(key: jax.Array, x: jax.Array, flip_prob: jax.Array, shift: jax.Array):
    """Per-member augmentation of a shared [B, H, W, C] batch.

    Branchless: flip via a per-sample mask, translation via a circular
    roll with a traced per-member offset (wrap-around stands in for
    pad-and-crop; equally effective as regularization, far cheaper to
    compile than dynamic_slice per sample).
    """
    with jax.named_scope("augment"):
        k_flip, k_dy, k_dx = jax.random.split(key, 3)
        b = x.shape[0]
        do_flip = jax.random.bernoulli(k_flip, flip_prob, (b, 1, 1, 1))
        x = jnp.where(do_flip, x[:, :, ::-1, :], x)
        max_s = jnp.maximum(shift, 0.0)
        dy = jnp.round(jax.random.uniform(k_dy, (), minval=-max_s, maxval=max_s)).astype(jnp.int32)
        dx = jnp.round(jax.random.uniform(k_dx, (), minval=-max_s, maxval=max_s)).astype(jnp.int32)
        return jnp.roll(x, (dy, dx), axis=(1, 2))


class ClassifierMember:
    """What ONE member computes, for the image (or tabular) classifier:
    its loss on a minibatch and its score on the validation rows. The
    trainer owns everything around it (the loop nest, the optimizer,
    the scopes ``member_loss`` and ``eval_population``); a workload
    whose member is something else hands the trainer another object
    with these names (``workloads/language.py``).

    ``loss(params, hp, key, bx, by)``: float32 scalar, ``hp`` the
    member's hyperparameters as scalars, ``key`` its own key this step.
    ``counters``: names of what the member counts of its own work in a
    step (a decoder's selected keys, its routed tokens); where there
    are any, ``loss`` returns ``(loss, float32[len(counters)])`` and the
    train segment hands their mean over the members out beside the
    losses, for the ``train`` span (each name is a span attribute).
    ``score_sum(params, cx, cy)``: a chunk of validation rows' part of
    the score, added up over chunks in ``score_dtype`` (rows padded onto
    the last chunk have labels < 0 and add nothing); ``score(total,
    n_val)``: the member's score, higher is better. ``eval_chunk`` is
    the validation rows a chunk holds. ``single_unbatched``: a cohort of
    ONE member is called without a batch axis (for a member that
    branches on its own values: under a batch axis a ``lax.cond`` runs
    both branches).
    """

    eval_chunk = 1024
    score_dtype = jnp.int32
    single_unbatched = False
    counters = ()

    def __init__(self, apply_fn: Callable, augment: bool):
        self.apply_fn = apply_fn
        self.augment = augment

    def loss(self, params, hp, key, bx, by):
        if self.augment and bx.ndim == 4:
            bx = _augment(key, bx, hp.flip_prob, hp.shift)
        logits = self.apply_fn(params, bx)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, by[:, None], axis=1))

    def score_sum(self, params, cx, cy):
        logits = self.apply_fn(params, cx)
        pred = jnp.argmax(logits, axis=-1)
        return jnp.sum((pred == cy) & (cy >= 0))

    def score(self, total, n_val: int):
        return total.astype(jnp.float32) / n_val


def _where_members(mask: jax.Array, a, b):
    """Per-member choice between two population pytrees: member m of
    the result is ``a``'s where ``mask[m]``, else ``b``'s."""

    def pick(x, y):
        return jnp.where(mask.reshape((-1,) + (1,) * (x.ndim - 1)), x, y)

    return jax.tree.map(pick, a, b)


class PopulationTrainer:
    """Builds the jitted population train/eval programs for one model.

    Args:
        apply_fn: ``apply(params, x) -> logits`` (flax ``Module.apply``
            partial'd over everything but params and inputs); the
            classifier's, unused where ``member`` is given.
        init_fn: ``init(rng, sample_x) -> params``.
        batch_size: per-step minibatch size (shared across members).
        augment: whether image augmentation applies (False for tabular).
        member: what one member computes (``ClassifierMember``'s five
            names); default the classifier around ``apply_fn``.
        member_chunk: if >0, at most this many members' activations
            live at once (activation-memory relief for big
            populations; params/momentum still resident for all
            members). A train segment trains each chunk of that many
            members through ALL its steps before the next, so the
            population's state is cut and written back in place once a
            chunk a segment, not once a step (``_train_nest``);
            evaluation ``lax.map``s the chunks (``_map_members``).
        donate: donate the input state to ``train_segment`` so XLA can
            reuse its buffers for the output instead of holding old and
            new population state simultaneously — the difference between
            1x and 2x resident params+momentum, which is what caps the
            single-chip ResNet population. Callers must not touch a
            state after passing it in (``make_trainer`` turns this on;
            keep it off when comparing states across calls).
        mesh: optional ``('pop','data')`` Mesh. When set, every train/
            eval batch carries a sharding constraint over the ``data``
            axis, so within-member compute is data-parallel: each data
            shard computes grads on its slice of the shared batch and
            the SPMD partitioner inserts the gradient all-reduce over
            ``data`` — the MPI allreduce of a data-parallel rank block,
            as a layout annotation (tested by HLO inspection in
            tests/test_parallel.py). Without the constraint the batch
            is replicated and the axis does nothing.
    """

    def __init__(
        self,
        apply_fn: Callable,
        init_fn: Callable,
        batch_size: int = 256,
        augment: bool = True,
        member_chunk: int = 0,
        donate: bool = False,
        mesh=None,
        momentum_dtype=None,
        member=None,
    ):
        self.apply_fn = apply_fn
        self.init_fn = init_fn
        self.batch_size = batch_size
        self.augment = augment
        self.member = member if member is not None else ClassifierMember(apply_fn, augment)
        self.member_chunk = member_chunk
        self.donate = donate
        self.mesh = mesh
        # storage dtype for the momentum buffers (None = match params,
        # i.e. f32). The update math always runs in f32; a narrower
        # STORAGE dtype only changes the bytes the bandwidth-bound
        # optimizer fusions move (probes/probe_bf16_momentum.py measures
        # whether that's a win on this platform)
        self.momentum_dtype = momentum_dtype
        if mesh is not None and batch_size % mesh.shape["data"]:
            raise ValueError(
                f"batch_size {batch_size} not divisible by the mesh 'data' "
                f"axis ({mesh.shape['data']})"
            )
        # every jitted program of this trainer (trainer_jit) lives here
        # and is released with the trainer
        self._programs: dict = {}
        donated = ("state",) if donate else ()
        self.train_segment = jax.jit(
            self._train_segment, static_argnames=("steps",), donate_argnames=donated
        )
        self.train_segment_masked = jax.jit(
            self._train_segment_masked,
            static_argnames=("steps",),
            donate_argnames=donated,
        )

    # -- init -------------------------------------------------------------

    @trainer_jit(static_argnames=("n",))
    def init_population(self, key: jax.Array, sample_x: jax.Array, n: int) -> PopState:
        return self.init_members(jax.random.split(key, n), sample_x)

    @trainer_jit()
    def init_members(self, keys: jax.Array, sample_x: jax.Array) -> PopState:
        """Init one member per key (leading axis = member).

        The wave-sliced form of ``init_population``: member m of a
        P-member population inits from ``split(key, P)[m]`` whether it
        lands on device as part of the full resident cohort or as a
        host-staged wave (``train/staging.py``) — so wave-mode initial
        weights are bit-identical to resident mode's.
        """
        n = keys.shape[0]
        params = jax.vmap(lambda k: self.init_fn(k, sample_x))(keys)
        dt = self.momentum_dtype
        momentum = jax.tree.map(lambda p: jnp.zeros(p.shape, dt or p.dtype), params)
        return PopState(params=params, momentum=momentum, step=jnp.zeros((n,), jnp.int32))

    # -- member-level pieces (scalar hparams; vmapped below) -------------

    def _member_loss(self, params, hp: OptHParams, key, bx, by):
        # the one scope that splits forward from backward in a device
        # trace: JAX names the backward ops transpose(jvp(member_loss))
        with jax.named_scope("member_loss"):
            return self.member.loss(params, hp, key, bx, by)

    def _member_update(self, params, momentum, step, hp: OptHParams, key, bx, by):
        # ``loss`` is the member's (loss, counters) where it counts its work
        loss, grads = jax.value_and_grad(self._member_loss, has_aux=bool(self.member.counters))(
            params, hp, key, bx, by
        )
        # SGD + momentum + coupled L2 weight decay (wd*p folded into the
        # gradient, so the effective decay is lr-scaled), hparams as
        # traced scalars. Math in f32 regardless of the momentum STORAGE
        # dtype (the astype is a no-op at the default f32 storage).
        with jax.named_scope("optimizer_update"):
            m32 = jax.tree.map(
                lambda m, g, p: hp.momentum * m.astype(jnp.float32) + g + hp.weight_decay * p,
                momentum, grads, params,
            )
            params = jax.tree.map(lambda p, m: p - hp.lr * m, params, m32)
            dt = self.momentum_dtype
            momentum = m32 if dt is None else jax.tree.map(lambda m: m.astype(dt), m32)
        return params, momentum, step + 1, loss

    def _constrain_data(self, bx, by):
        """Shard a batch over the mesh 'data' axis (no-op without a mesh)."""
        if self.mesh is None:
            return bx, by
        from jax.sharding import NamedSharding, PartitionSpec

        sh = lambda a: jax.lax.with_sharding_constraint(
            a, NamedSharding(self.mesh, PartitionSpec("data"))
        )
        return sh(bx), sh(by)

    # -- population programs ---------------------------------------------

    def _per_device_chunks(self, n: int):
        """``(n_pop, k, chunk)`` where a mesh's 'pop' axis shards ``n``
        members evenly, else None: each device's ``n/n_pop`` members
        walked as ``k`` chunks. The chunk shrinks to the largest
        divisor of the per-device count, so the ``[n_pop, k, chunk]``
        view is exact; members are independent, so which of them share
        a chunk changes no result."""
        n_pop = 1 if self.mesh is None else int(self.mesh.shape["pop"])
        if n_pop == 1 or n % n_pop:
            return None
        local = n // n_pop
        chunk = max(c for c in range(1, min(self.member_chunk, local) + 1) if local % c == 0)
        return n_pop, local // chunk, chunk

    def _map_members(self, fn, xs):
        """``fn`` over the leading member axis of the pytree ``xs``:
        vmapped whole, or — with ``member_chunk`` — ``lax.map``'ed in
        chunks of that many members (activation-memory relief).
        Evaluation's loop over members: it cuts the parameters once a
        validation chunk, twice a generation. The train segment has a
        nest of its own (``_train_nest``), which cuts the whole state
        once a segment; on a 'pop' mesh both cut per device
        (``_member_chunks`` says why).
        """
        with jax.named_scope("map_members"):
            chunk = self.member_chunk
            if chunk <= 0:
                return jax.vmap(fn)(xs)
            n = jax.tree.leaves(xs)[0].shape[0]
            per_device = self._per_device_chunks(n)
            if per_device is None:
                if chunk == 1 and self.member.single_unbatched:
                    return jax.lax.map(fn, xs)
                return jax.lax.map(fn, xs, batch_size=chunk)
            from jax.sharding import NamedSharding, PartitionSpec

            n_pop, k, chunk = per_device
            by_chunk = NamedSharding(self.mesh, PartitionSpec(None, "pop"))

            def split(a):  # [n, ...] -> [k, n_pop, chunk, ...], device-local
                a = a.reshape((n_pop, k, chunk) + a.shape[1:])
                return jax.lax.with_sharding_constraint(jnp.swapaxes(a, 0, 1), by_chunk)

            def join(a):  # [k, n_pop, chunk, ...] -> [n, ...]
                a = jnp.swapaxes(a, 0, 1)
                return a.reshape((n,) + a.shape[3:])

            out = jax.lax.map(jax.vmap(jax.vmap(fn)), jax.tree.map(split, xs))
            return jax.tree.map(join, out)

    def _pop_update(self, state: PopState, hp: OptHParams, keys, bx, by):
        """One step of these members (the population or one chunk of it)
        on a shared batch: ``(state, loss[members])``."""
        fn = lambda *a: self._member_update(*a, bx, by)
        args = (state.params, state.momentum, state.step, hp, keys)
        if state.step.shape[0] == 1 and self.member.single_unbatched:
            out = fn(*jax.tree.map(lambda a: a[0], args))
            p, m, s, loss = jax.tree.map(lambda a: a[None], out)
        else:
            p, m, s, loss = jax.vmap(fn)(*args)
        return PopState(params=p, momentum=m, step=s), loss

    def _train_input(self, k, train_x, train_y, n: int, window=None):
        """One step's inputs: the carried key advanced, the ``n``
        members' augmentation keys and the shared minibatch.

        ``window=(n_total, offset)`` is the wave form: the keys are the
        wave's window of the full population's per-step split
        (``offset`` is traced, see ``_train_segment_window``).
        """
        with jax.named_scope("train_input"):
            k, k_batch, k_aug = jax.random.split(k, 3)
            idx = jax.random.randint(k_batch, (self.batch_size,), 0, train_x.shape[0])
            bx = jnp.take(train_x, idx, axis=0)
            by = jnp.take(train_y, idx, axis=0)
            bx, by = self._constrain_data(bx, by)
            if window is None:
                member_keys = jax.random.split(k_aug, n)
            else:
                n_total, offset = window
                all_keys = jax.random.split(k_aug, n_total)
                member_keys = jax.random.wrap_key_data(
                    jax.lax.dynamic_slice_in_dim(
                        jax.random.key_data(all_keys), offset, n, axis=0
                    )
                )
        return k, member_keys, bx, by

    def _member_chunks(self, n: int):
        """How a chunked train segment walks ``n`` members:
        ``(k, chunk, view, unview, cut, put)``, or None where the
        population is one vmap (no ``member_chunk``, or one that holds
        every member). ``view`` is applied once to every per-member
        array, ``cut(a, j, size=chunk)`` takes pass ``j``'s members out
        of a viewed array with a ``dynamic_slice`` and ``put(a, piece,
        j)`` writes them back with a ``dynamic_update_slice``, both on
        an axis no device shares; passes ``0..k-1`` hold ``chunk``
        members a device, and ``cut(a, k, n - k * chunk)`` is the
        remainder where the chunk does not divide ``n`` (a pass of its
        own, as ``lax.map(batch_size=)`` runs it).

        On a mesh whose 'pop' axis shards the members, the chunks are
        cut PER DEVICE: each device's ``n/n_pop`` members are viewed as
        ``[k, chunk]`` and pass ``j`` runs chunk ``j`` of every device
        at once. Chunking the global axis instead loops over the very
        dimension that is sharded: the TPU compiler then all-gathers
        the whole population's state onto every chip (433 all-gathers
        and 17 GiB a chip for a pop=128 ResNet-18 on a 2x2 v5e, where
        the per-device cut needs none — compiled for the described
        topology, PR 21). Virtual CPU devices never showed it.
        """
        chunk = self.member_chunk
        if chunk <= 0:
            return None
        per_device = self._per_device_chunks(n)
        if per_device is None:
            if chunk >= n:
                return None
            ident = lambda a: a

            def cut(a, j, size=chunk):
                return jax.lax.dynamic_slice_in_dim(a, j * chunk, size, axis=0)

            def put(a, piece, j):
                return jax.lax.dynamic_update_slice_in_dim(a, piece, j * chunk, axis=0)

            return n // chunk, chunk, ident, ident, cut, put
        from jax.sharding import NamedSharding, PartitionSpec

        n_pop, k, chunk = per_device
        by_member = NamedSharding(self.mesh, PartitionSpec("pop"))

        def view(a):  # [n, ...] -> [n_pop, k, chunk, ...], device-local
            return a.reshape((n_pop, k, chunk) + a.shape[1:])

        def unview(a):
            return a.reshape((n,) + a.shape[3:])

        def cut(a, j, size=chunk):  # -> [n_pop * chunk, ...], sharded as the members are
            a = jax.lax.dynamic_slice_in_dim(a, j, 1, axis=1)
            a = a.reshape((n_pop * size,) + a.shape[3:])
            return jax.lax.with_sharding_constraint(a, by_member)

        def put(a, piece, j):
            piece = piece.reshape((n_pop, 1, chunk) + piece.shape[1:])
            return jax.lax.dynamic_update_slice_in_dim(a, piece, j, axis=1)

        return k, chunk, view, unview, cut, put

    def _train_nest(self, state, hp, train_x, train_y, key, steps, window=None, rem=None):
        """The loop nest of the three train-segment programs:
        ``(state, mean losses [steps])``; where the member counts its
        own work (``member.counters``) the second is ``(mean losses
        [steps], mean counters [steps, len(counters)])``, every mean
        over the members. They differ in ``window`` (the
        wave form's ``(n_total, offset)`` of ``_train_input``) and in
        ``rem`` (the masked form's int32[P] budgets) alone.

        Without ``member_chunk``: scan over steps of one vmap over the
        members. With it, the chunk loop is OUTSIDE the step loop: each
        chunk is cut from the population's state once, trained through
        all ``steps`` and written back in place, so the state is cut
        and stitched once a segment (a step loop around a chunk loop
        did it every step: 17% of a ResNet-18 generation, PR 25's
        ``device_train_rest_s``). That is the same arithmetic because
        members are independent given the shared minibatch, and the
        minibatch and the augmentation keys depend on ``key`` alone,
        never on the state: every chunk walks the same key chain from
        ``key``, draws the same minibatches, and takes its own members'
        rows of the same per-step key split.
        """
        n = state.step.shape[0]
        chunks = self._member_chunks(n)
        # the scope of the loop over members: around the vmap of each
        # step, or around the whole chunk loop
        step_scope = (
            contextlib.nullcontext
            if chunks
            else functools.partial(jax.named_scope, "map_members")
        )

        def masked(active, loss):
            """What a step reports (the losses [members]; with them the
            counters [members, c] where the member has any), nought for
            the members past their budget."""
            return jax.tree.map(
                lambda a: jnp.where(jnp.expand_dims(active, tuple(range(1, a.ndim))), a, 0.0), loss
            )

        def run(st, hp, rem, pick):
            """``steps`` steps of the members of ``st``; ``pick`` takes
            their rows of the whole state's per-step keys. A member
            past its budget reads loss 0. Losses [steps, members] of a
            chunk, for the one mean over all members; whole, the mean
            is taken inside the step, where it is a scalar to reduce
            over a mesh."""

            def one_step(carry, t):
                st, k = carry
                k, member_keys, bx, by = self._train_input(k, train_x, train_y, n, window)
                with step_scope():
                    new_st, loss = self._pop_update(st, hp, pick(member_keys), bx, by)
                if rem is not None:
                    active = t < rem  # bool[members]
                    new_st = _where_members(active, new_st, st)
                    loss = masked(active, loss)
                if not chunks:
                    loss = jax.tree.map(lambda a: jnp.mean(a, axis=0), loss)
                return (new_st, k), loss

            (st, _), losses = jax.lax.scan(one_step, (st, key), jnp.arange(steps))
            return st, losses

        with jax.named_scope("train_segment"):
            if not chunks:
                return run(state, hp, rem, lambda keys: keys)
            with jax.named_scope("map_members"):
                k, chunk, view, unview, cut, put = chunks
                hp_v, rem_v = jax.tree.map(view, (hp, rem))

                def one_chunk(st, j, size=chunk):
                    take = lambda a: cut(a, j, size)
                    pick = lambda keys: jax.random.wrap_key_data(
                        take(view(jax.random.key_data(keys)))
                    )
                    if in_place:
                        return steps_in_place(st, j, take, pick)
                    piece, losses = run(*jax.tree.map(take, (st, hp_v, rem_v)), pick)
                    return jax.tree.map(lambda a, p: put(a, p, j), st, piece), losses

                # A member walked alone and without a batch axis is one
                # too large to hold twice (``single_unbatched``): cutting
                # it out for a segment would be a second copy of its
                # parameters and momentum beside the population's. Its
                # steps read and write its row of the state itself, cut
                # and put back inside every step, where the slices fuse
                # into the step's own reads and its update (the bytes of
                # a cut a step are nothing beside such a member's step).
                in_place = chunk == 1 and self.member.single_unbatched

                def steps_in_place(st, j, take, pick):
                    hp_j, rem_j = jax.tree.map(take, (hp_v, rem_v))

                    def one_step(carry, t):
                        st, k = carry
                        k, member_keys, bx, by = self._train_input(k, train_x, train_y, n, window)
                        piece = jax.tree.map(take, st)
                        new, loss = self._pop_update(piece, hp_j, pick(member_keys), bx, by)
                        if rem_j is not None:
                            active = t < rem_j
                            new = _where_members(active, new, piece)
                            loss = masked(active, loss)
                        return (jax.tree.map(lambda a, p: put(a, p, j), st, new), k), loss

                    (st, _), losses = jax.lax.scan(one_step, (st, key), jnp.arange(steps))
                    return st, losses

                st, losses = jax.lax.scan(one_chunk, jax.tree.map(view, state), jnp.arange(k))
                # [k, steps, members, ...] -> [steps, ...]
                total = jax.tree.map(lambda a: jnp.sum(a, axis=(0, 2)), losses)
                tail = n - k * jax.tree.leaves(losses)[0].shape[2]
                if tail:
                    st, losses = one_chunk(st, k, tail)
                    total = jax.tree.map(lambda t, a: t + jnp.sum(a, axis=1), total, losses)
                return jax.tree.map(unview, st), jax.tree.map(lambda t: t / n, total)

    def _train_segment(
        self,
        state: PopState,
        hp: OptHParams,
        train_x: jax.Array,
        train_y: jax.Array,
        key: jax.Array,
        steps: int,
    ) -> tuple[PopState, jax.Array]:
        """Run ``steps`` shared-batch steps; returns (state, mean losses [steps]).

        Jitted as ``self.train_segment`` in __init__ (donation is
        per-instance, and the wrapper must die with the instance: see
        ``trainer_jit``).
        """
        return self._train_nest(state, hp, train_x, train_y, key, steps)

    def _train_segment_window(
        self,
        state: PopState,
        hp: OptHParams,
        train_x: jax.Array,
        train_y: jax.Array,
        key: jax.Array,
        steps: int,
        n_total: int,  # static: full population size
        offset: jax.Array,  # int32: this wave's first member index
    ) -> tuple[PopState, jax.Array]:
        """``_train_segment`` for a WAVE of a larger population: the
        state holds members [offset, offset+W) of an ``n_total``-member
        population (host-staged wave scheduling, train/staging.py).

        Bit-identity contract with the resident program: the batch key
        chain threads exactly as in ``_train_segment`` (the minibatch is
        shared population-wide, so every wave of a generation must draw
        the SAME batch sequence — they do, by receiving the same
        ``key``), and per-member augmentation keys are the wave's WINDOW
        of the full population's per-step split — member m sees
        ``split(k_aug, n_total)[m]`` whether it trains resident or in a
        wave. ``offset`` is traced (dynamic_slice on the key data), so
        all same-sized waves share one compiled program.
        """
        return self._train_nest(
            state, hp, train_x, train_y, key, steps, window=(n_total, offset)
        )

    def _train_segment_masked(
        self,
        state: PopState,
        hp: OptHParams,
        train_x: jax.Array,
        train_y: jax.Array,
        key: jax.Array,
        steps: int,
        rem: jax.Array,  # int32[P]: per-member steps remaining
    ) -> tuple[PopState, jax.Array]:
        """``_train_segment`` with per-member step budgets: member m's
        update applies only while the scan index is < ``rem[m]``, so one
        program trains a MIXED-budget cohort (an ASHA batch spanning
        rungs) to each member's own budget. ``steps`` should be
        ``max(rem)``. Members past their budget still compute a step
        (SPMD lockstep — there is no early exit inside one program) but
        the update is discarded, trading those FLOPs for what they buy:
        ONE launch and ONE blocking score fetch per driver batch
        instead of one per rung group (whether that trade still pays on
        a locally attached chip is ROADMAP D2's measurement). RNG
        advances in lockstep too,
        so a member's trajectory depends on its cohort's step schedule —
        deterministic given the batch plan, not bit-identical to the
        grouped path.
        """
        return self._train_nest(state, hp, train_x, train_y, key, steps, rem=rem)

    @trainer_jit(static_argnames=("eval_chunk",))
    def eval_population(
        self, state: PopState, val_x: jax.Array, val_y: jax.Array, eval_chunk: int | None = None
    ) -> jax.Array:
        """The member's score on the validation rows (the classifier's:
        accuracy), per member: float32[P], higher is better.

        Scans the val set in fixed chunks (``eval_chunk`` rows; default
        the member's own) so activation memory stays
        O(P * eval_chunk) regardless of val-set size; with
        ``member_chunk`` set, members are additionally lax.map'ed in
        chunks, bounding activations at O(member_chunk * eval_chunk) —
        ResNet-scale populations OOM the forward pass without this. The
        tail chunk is masked, not dropped.
        """
        member = self.member
        eval_chunk = eval_chunk or member.eval_chunk
        with jax.named_scope("eval_population"):
            n_val = val_x.shape[0]
            n_chunks = -(-n_val // eval_chunk)
            pad = n_chunks * eval_chunk - n_val
            vx = jnp.pad(val_x, [(0, pad)] + [(0, 0)] * (val_x.ndim - 1))
            vy = jnp.pad(val_y, [(0, pad)] + [(0, 0)] * (val_y.ndim - 1), constant_values=-1)
            vx = vx.reshape((n_chunks, eval_chunk) + val_x.shape[1:])
            vy = vy.reshape((n_chunks, eval_chunk) + val_y.shape[1:])

            def chunk_step(acc, chunk):
                cx, cy = chunk
                cx, cy = self._constrain_data(cx, cy)
                part = self._map_members(lambda p: member.score_sum(p, cx, cy), state.params)
                acc = acc + part
                return acc, None

            zero = jnp.zeros((state.step.shape[0],), member.score_dtype)
            total, _ = jax.lax.scan(chunk_step, zero, (vx, vy))
            return member.score(total, n_val)

    # -- multi-objective member metrics (ISSUE 17) ------------------------

    @trainer_jit(static_argnames=("threshold",))
    def member_effective_params(
        self, state: PopState, threshold: float = 1e-3
    ) -> jax.Array:
        """Effective parameter count per member: float32[P].

        Counts weights with ``|w| > threshold`` — the model-size
        objective of the multi-objective eval path. Unlike the dense
        parameter count (identical across members — static shapes),
        this varies with each member's weight-decay trajectory, so
        "accuracy vs params" is a real trade-off the search can move
        along. Members with any non-finite weight poison to NaN, which
        is what marks a diverged member infeasible in every objective
        consumer (journal status, Pareto ok-mask, warm-start guard).
        """
        n = state.step.shape[0]
        count = jnp.zeros((n,), jnp.float32)
        bad = jnp.zeros((n,), bool)
        for leaf in jax.tree.leaves(state.params):
            axes = tuple(range(1, leaf.ndim))
            count = count + jnp.sum(
                (jnp.abs(leaf) > threshold).astype(jnp.float32), axis=axes
            )
            bad = bad | ~jnp.all(jnp.isfinite(leaf), axis=axes)
        return jnp.where(bad, jnp.nan, count)

    @trainer_jit()
    def member_latency_proxy(self, state: PopState) -> jax.Array:
        """Step-time latency proxy per member: float32[P], pseudo-ms.

        ``2 * MACs / 1e6`` over the weights a structured-sparse kernel
        could not skip (coarser prunability threshold than the params
        metric, 1e-2) — a deterministic, device-computable stand-in
        for inference step time that needs no wall-clock measurement
        (which would not be per-member attributable inside one fused
        program anyway).
        """
        return 2e-6 * self.member_effective_params(state, threshold=1e-2)

    # -- population surgery (exploit / slot management) ------------------

    @staticmethod
    @jax.jit
    def gather_members(state: PopState, src_idx: jax.Array) -> PopState:
        """Exploit/copy: member i continues from member src_idx[i].

        The MPI weight transfer of the reference, as one device gather.
        """
        with jax.named_scope("gather_members"):
            return jax.tree.map(lambda x: x[src_idx], state)

    #: a member whose parameters and momentum reach this many bytes is
    #: copied row by row at an exploit (``exploit_members``)
    ROW_COPY_BYTES = 1 << 30

    @staticmethod
    def exploit_members(state: PopState, src_idx: jax.Array) -> PopState:
        """``gather_members`` for an EXPLOIT's source map, in which every
        source keeps itself (``src_idx[src_idx[i]] == src_idx[i]``: the
        losers copy winners, the winners stay; ``ops/pbt.py`` cuts at
        most half the population, so its maps all are). Small members are
        gathered as ever. A gather writes a second population before
        the first is free, which a population of gigabyte members has
        no room for: there the replaced rows are copied one at a time
        into the state itself (a ``dynamic_update_slice`` on the loop's
        carry, no second copy), which is the same result because no
        source row is ever overwritten.
        """
        n = state.step.shape[0]
        leaves = jax.tree.leaves((state.params, state.momentum))
        if sum(x.size * x.dtype.itemsize for x in leaves) < n * PopulationTrainer.ROW_COPY_BYTES:
            return PopulationTrainer.gather_members(state, src_idx)
        with jax.named_scope("gather_members"):

            def copy_row(i, st):
                src = src_idx[i]
                take = lambda x: jax.lax.dynamic_update_index_in_dim(
                    x, jax.lax.dynamic_index_in_dim(x, src, 0, keepdims=False), i, 0
                )
                return jax.lax.cond(src == i, lambda s: s, lambda s: jax.tree.map(take, s), st)

            return jax.lax.fori_loop(0, n, copy_row, state)

    @staticmethod
    @jax.jit
    def select_members(fresh_mask: jax.Array, fresh: PopState, existing: PopState) -> PopState:
        """Per-member choice between a fresh init and existing state."""
        return _where_members(fresh_mask, fresh, existing)
