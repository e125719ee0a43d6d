"""The vmapped population trainer: learning, hparam sensitivity, surgery."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_opt_tpu.data import load_dataset
from mpi_opt_tpu.models import MLP
from mpi_opt_tpu.train import OptHParams, PopulationTrainer, PopState


@pytest.fixture(scope="module")
def setup():
    d = load_dataset("fashion_mnist", n_train=2048, n_val=512)
    model = MLP(hidden=64, n_classes=10)
    trainer = PopulationTrainer(
        apply_fn=lambda p, x: model.apply({"params": p}, x),
        init_fn=lambda r, x: model.init(r, x)["params"],
        batch_size=128,
    )
    data = {k: jnp.asarray(v) for k, v in d.items() if k != "n_classes"}
    return trainer, data


def test_population_members_differ_after_init(setup):
    trainer, data = setup
    st = trainer.init_population(jax.random.key(0), data["train_x"][:2], 4)
    leaves = jax.tree.leaves(st.params)
    assert all(l.shape[0] == 4 for l in leaves)
    kernel = next(l for l in leaves if l.ndim >= 3)  # a weight matrix, not a bias
    assert not np.allclose(np.asarray(kernel[0]), np.asarray(kernel[1]))


def test_training_improves_over_init(setup):
    trainer, data = setup
    st = trainer.init_population(jax.random.key(1), data["train_x"][:2], 4)
    acc0 = trainer.eval_population(st, data["val_x"], data["val_y"])
    hp = OptHParams.defaults(4, lr=0.1)
    st, losses = trainer.train_segment(
        st, hp, data["train_x"], data["train_y"], jax.random.key(2), 100
    )
    acc1 = trainer.eval_population(st, data["val_x"], data["val_y"])
    assert losses.shape == (100,)
    assert float(losses[-5:].mean()) < float(losses[:5].mean())
    assert float(acc1.mean()) > float(acc0.mean()) + 0.2
    assert (np.asarray(st.step) == 100).all()


def test_per_member_lr_matters(setup):
    """Members with absurd lr diverge while good members learn — the
    whole point of hparams-as-data."""
    trainer, data = setup
    st = trainer.init_population(jax.random.key(3), data["train_x"][:2], 3)
    hp = OptHParams(
        lr=jnp.array([0.1, 1e-5, 500.0]),
        momentum=jnp.array([0.9, 0.9, 0.9]),
        weight_decay=jnp.zeros(3),
        flip_prob=jnp.zeros(3),
        shift=jnp.zeros(3),
    )
    st, _ = trainer.train_segment(
        st, hp, data["train_x"], data["train_y"], jax.random.key(4), 120
    )
    acc = np.asarray(trainer.eval_population(st, data["val_x"], data["val_y"]))
    assert acc[0] > acc[1] + 0.1  # tiny lr undertrains
    assert acc[0] > acc[2]  # huge lr diverges (may be nan-level accuracy)


def test_gather_members_copies_state(setup):
    trainer, data = setup
    st = trainer.init_population(jax.random.key(5), data["train_x"][:2], 4)
    src_idx = jnp.array([3, 3, 2, 3])
    g = trainer.gather_members(st, src_idx)
    p0 = np.asarray(jax.tree.leaves(g.params)[0])
    orig = np.asarray(jax.tree.leaves(st.params)[0])
    np.testing.assert_allclose(p0[0], orig[3])
    np.testing.assert_allclose(p0[2], orig[2])


@pytest.mark.parametrize("row_copy", [False, True], ids=["gathered", "row by row"])
def test_exploit_members_is_the_gather_of_an_exploits_map(setup, monkeypatch, row_copy):
    """Losers copy winners, winners keep themselves: small members are
    gathered as ever, members of a gigabyte are copied row by row into
    the state itself; both are ``gather_members``'s result."""
    trainer, data = setup
    st = trainer.init_population(jax.random.key(8), data["train_x"][:2], 5)
    st = st.replace(momentum=jax.tree.map(lambda p: p * 2.0, st.params), step=jnp.arange(5, dtype=jnp.int32))
    src_idx = jnp.array([0, 3, 2, 3, 0])  # members 1 and 4 are replaced
    if row_copy:
        monkeypatch.setattr(PopulationTrainer, "ROW_COPY_BYTES", 0)
    exploit = jax.jit(lambda s, i: trainer.exploit_members(s, i))  # traced anew under the patch
    assert ("dynamic_update_slice" in exploit.lower(st, src_idx).as_text()) == row_copy
    got = exploit(st, src_idx)
    want = trainer.gather_members(st, src_idx)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n", [3, 4, 7, 32])
def test_an_exploits_sources_are_never_replaced(n):
    """What ``exploit_members`` copies in place by: the exploit cuts at
    most half the population, whatever the fraction asked for, so every
    source row keeps itself (at 0.5, three members round to a cut of
    two of three: top and bottom would share the middle one)."""
    from mpi_opt_tpu.ops.pbt import PBTConfig, pbt_exploit_explore

    for seed in range(4):
        scores = jax.random.normal(jax.random.key(seed), (n,))
        _, src, bottom = pbt_exploit_explore(
            jax.random.key(100 + seed), jnp.zeros((n, 2)), scores, jnp.zeros((2,), bool), PBTConfig(truncation_frac=0.5)
        )
        src, bottom = np.asarray(src), np.asarray(bottom)
        np.testing.assert_array_equal(src[src], src)
        assert bottom.sum() == n // 2 and not bottom[src].any()
    for frac in (0.0, 0.75, 1.0):
        with pytest.raises(ValueError, match="truncation_frac"):
            PBTConfig(truncation_frac=frac)


def test_select_members_mixes_fresh_and_existing(setup):
    trainer, data = setup
    a = trainer.init_population(jax.random.key(6), data["train_x"][:2], 4)
    b = trainer.init_population(jax.random.key(7), data["train_x"][:2], 4)
    mask = jnp.array([True, False, True, False])
    out = trainer.select_members(mask, a, b)
    la, lb, lo = (np.asarray(jax.tree.leaves(x.params)[0]) for x in (a, b, out))
    np.testing.assert_allclose(lo[0], la[0])
    np.testing.assert_allclose(lo[1], lb[1])


def test_member_chunk_matches_full_vmap(setup):
    trainer, data = setup
    model = MLP(hidden=64, n_classes=10)
    chunked = PopulationTrainer(
        apply_fn=trainer.apply_fn,
        init_fn=trainer.init_fn,
        batch_size=128,
        member_chunk=2,
    )
    st = trainer.init_population(jax.random.key(8), data["train_x"][:2], 4)
    hp = OptHParams.defaults(4, lr=0.05)
    a, _ = trainer.train_segment(st, hp, data["train_x"], data["train_y"], jax.random.key(9), 10)
    b, _ = chunked.train_segment(st, hp, data["train_x"], data["train_y"], jax.random.key(9), 10)
    la, lb = np.asarray(jax.tree.leaves(a.params)[0]), np.asarray(jax.tree.leaves(b.params)[0])
    np.testing.assert_allclose(la, lb, rtol=2e-2, atol=2e-5)  # bf16 tolerance


def _chunked(trainer, chunk=2):
    return PopulationTrainer(
        apply_fn=trainer.apply_fn, init_fn=trainer.init_fn, batch_size=128, member_chunk=chunk
    )


def _distinct_hparams(n):
    """A different row for every member, augmentation on."""
    r = jnp.arange(n, dtype=jnp.float32)
    return OptHParams(
        lr=0.02 + 0.01 * r,
        momentum=0.8 + 0.02 * r,
        weight_decay=1e-4 * (1.0 + r),
        flip_prob=0.1 + 0.05 * r,
        shift=1.0 + 0.5 * r,
    )


def _per_step_reference(trainer, st, hp, tx, ty, key, steps, chunk, n_total, offset, rem):
    """The train segment as the old order had it, one launch a chunk a
    step: ``for t in steps: for chunk: vmap(_member_update)``, with the
    key chain and the members' window of the per-step split spelled out
    (the contract of ``_train_input``, not a call to it)."""
    n = st.step.shape[0]
    update = jax.jit(jax.vmap(trainer._member_update, in_axes=(0, 0, 0, 0, 0, None, None)))
    k = key
    for t in range(steps):
        k, k_batch, k_aug = jax.random.split(k, 3)
        idx = jax.random.randint(k_batch, (trainer.batch_size,), 0, tx.shape[0])
        bx, by = tx[idx], ty[idx]
        keys = jax.random.split(k_aug, n_total)[offset : offset + n]
        pieces = []
        for lo in range(0, n, chunk):
            cut = lambda a: a[lo : lo + chunk]
            old = jax.tree.map(cut, st)
            p, m, s, _ = update(old.params, old.momentum, old.step, jax.tree.map(cut, hp), cut(keys), bx, by)
            new = PopState(params=p, momentum=m, step=s)
            if rem is not None:
                active = t < cut(rem)
                keep = lambda a, b: jnp.where(active.reshape((-1,) + (1,) * (a.ndim - 1)), a, b)
                new = jax.tree.map(keep, new, old)
            pieces.append(new)
        st = jax.tree.map(lambda *xs: jnp.concatenate(xs), *pieces)
    return st


@pytest.mark.parametrize("n", [4, 5], ids=["chunk_divides", "chunk_leaves_a_remainder"])
@pytest.mark.parametrize("form", ["segment", "window", "masked"])
def test_chunked_nest_matches_the_per_step_order_bit_for_bit(setup, form, n):
    """The chunk loop outside the step loop trains every member exactly
    as the step loop around the chunk loop did: same chunks, same
    minibatches, same augmentation keys, ``step + 1`` a step."""
    trainer, data = setup
    tx, ty = data["train_x"], data["train_y"]
    chunked = _chunked(trainer)
    steps, key = 4, jax.random.key(21)
    st = trainer.init_population(jax.random.key(20), tx[:2], n)
    hp = _distinct_hparams(n)
    n_total, offset, rem = n, 0, None
    if form == "segment":
        got, losses = chunked.train_segment(st, hp, tx, ty, key, steps)
    elif form == "window":
        n_total, offset = n + 6, 3
        program = jax.jit(chunked._train_segment_window, static_argnames=("steps", "n_total"))
        got, losses = program(st, hp, tx, ty, key, steps=steps, n_total=n_total, offset=jnp.int32(offset))
    else:
        rem = jnp.asarray([4, 1, 3, 0, 2][:n], jnp.int32)
        got, losses = chunked.train_segment_masked(st, hp, tx, ty, key, steps, rem)
    want = _per_step_reference(trainer, st, hp, tx, ty, key, steps, 2, n_total, offset, rem)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(got.step).tolist() == ([steps] * n if rem is None else rem.tolist())
    assert losses.shape == (steps,) and np.isfinite(np.asarray(losses)).all()


def test_chunked_losses_are_the_mean_over_all_members(setup):
    """``losses[t]`` is assembled from the chunks' rows: the mean over
    every member at step ``t``, as the unchunked program takes it."""
    trainer, data = setup
    tx, ty = data["train_x"], data["train_y"]
    st = trainer.init_population(jax.random.key(22), tx[:2], 5)
    hp = _distinct_hparams(5)
    _, whole = trainer.train_segment(st, hp, tx, ty, jax.random.key(23), 3)
    _, chunked = _chunked(trainer).train_segment(st, hp, tx, ty, jax.random.key(23), 3)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(whole), rtol=2e-2)  # bf16 members


def test_step_loop_of_a_chunked_segment_never_cuts_the_state(setup):
    """The structure, read from the compiled program: the loop over
    steps holds no ``dynamic-slice`` and no ``dynamic-update-slice`` of
    a state leaf — the population's state is cut and stitched in the
    chunk loop, once a segment. (At the parent of PR 26 the chunk loop
    sat inside the step loop and every step did both.)"""
    import hlo_loops

    trainer, data = setup
    tx, ty = data["train_x"], data["train_y"]
    chunked = _chunked(trainer)
    st = trainer.init_population(jax.random.key(24), tx[:2], 6)
    steps = 5  # three chunks of two: the two loops differ in trip count
    text = chunked.train_segment.lower(
        st, OptHParams.defaults(6), tx, ty, jax.random.key(25), steps
    ).compile().as_text()
    loops = hlo_loops.loops(text)  # a loop's body includes the loops nested in it
    assert steps in [l.trips for l in loops]
    assert [l.trips for l in loops if hlo_loops.state_cuts(l, st)] == [3]


def test_momentum_storage_dtype_knob(setup):
    """momentum_dtype=bfloat16 stores momentum narrow (the bandwidth A/B
    probe's knob) while params stay f32 and training still learns; the
    default (None) keeps momentum at the params dtype exactly."""
    _, data = setup
    model = MLP(hidden=64, n_classes=10)
    trainer = PopulationTrainer(
        apply_fn=lambda p, x: model.apply({"params": p}, x),
        init_fn=lambda r, x: model.init(r, x)["params"],
        batch_size=128,
        momentum_dtype=jnp.bfloat16,
    )
    st = trainer.init_population(jax.random.key(3), data["train_x"][:2], 4)
    assert all(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(st.momentum))
    assert all(l.dtype == jnp.float32 for l in jax.tree.leaves(st.params))
    acc0 = trainer.eval_population(st, data["val_x"], data["val_y"])
    hp = OptHParams.defaults(4, lr=0.1)
    st, _ = trainer.train_segment(
        st, hp, data["train_x"], data["train_y"], jax.random.key(4), 60
    )
    assert all(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(st.momentum))
    acc1 = trainer.eval_population(st, data["val_x"], data["val_y"])
    assert float(acc1.max()) > float(acc0.max()) + 0.1


def test_fused_pbt_gen_chunked_launches(shared_workload):
    """gen_chunk is pure launch-splitting: population state AND the
    scan-carried RNG key thread through launches, so a chunked sweep
    must be BIT-IDENTICAL to the single-launch sweep — same curves,
    same final scores, same winning hparams."""
    import numpy as np

    from mpi_opt_tpu.train.fused_pbt import fused_pbt
    from mpi_opt_tpu.workloads import get_workload

    wl = shared_workload("fashion_mlp", n_train=512, n_val=256)
    kw = dict(population=8, generations=3, steps_per_gen=10, seed=0)
    whole = fused_pbt(wl, gen_chunk=0, **kw)
    chunked = fused_pbt(wl, gen_chunk=2, **kw)  # balanced split [2, 1]
    assert chunked["best_curve"].shape == (3,)
    np.testing.assert_array_equal(chunked["best_curve"], whole["best_curve"])
    np.testing.assert_array_equal(chunked["mean_curve"], whole["mean_curve"])
    np.testing.assert_array_equal(chunked["unit"], whole["unit"])
    assert chunked["best_score"] == whole["best_score"]


def test_fused_pbt_rejects_zero_generations(shared_workload):
    import pytest

    from mpi_opt_tpu.train.fused_pbt import fused_pbt
    from mpi_opt_tpu.workloads import get_workload

    wl = shared_workload("fashion_mlp", n_train=256, n_val=128)
    with pytest.raises(ValueError, match="generations"):
        fused_pbt(wl, population=4, generations=0, steps_per_gen=5)


def test_masked_segment_matches_unmasked_when_uniform(setup):
    """With every member's rem equal to the segment length, the masked
    program threads the same RNG and applies every update — bit-identical
    to train_segment, so the merged driver path costs nothing when the
    batch isn't actually mixed-budget."""
    trainer, data = setup
    st = trainer.init_population(jax.random.key(3), data["train_x"][:2], 4)
    hp = OptHParams.defaults(4, lr=0.05)
    a, _ = trainer.train_segment(
        st, hp, data["train_x"], data["train_y"], jax.random.key(4), 7
    )
    b, _ = trainer.train_segment_masked(
        st, hp, data["train_x"], data["train_y"], jax.random.key(4), 7,
        jnp.full((4,), 7, jnp.int32),
    )
    for xa, xb in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
    assert (np.asarray(b.step) == 7).all()


def test_masked_segment_freezes_members_at_their_budget(setup):
    """A mixed-budget batch in one program: member m advances exactly
    rem[m] steps and is untouched afterwards (the merged ASHA batch's
    correctness condition — a frozen member's score must be the score AT
    its budget, not beyond it)."""
    trainer, data = setup
    st = trainer.init_population(jax.random.key(5), data["train_x"][:2], 3)
    hp = OptHParams.defaults(3, lr=0.05)
    rem = jnp.asarray([0, 2, 6], jnp.int32)
    out, _ = trainer.train_segment_masked(
        st, hp, data["train_x"], data["train_y"], jax.random.key(6), 6, rem
    )
    assert np.asarray(out.step).tolist() == [0, 2, 6]
    # member 0 (rem=0) is bit-untouched
    for xa, xb in zip(jax.tree.leaves(st.params), jax.tree.leaves(out.params)):
        np.testing.assert_array_equal(np.asarray(xa[0]), np.asarray(xb[0]))
    # members with rem>0 actually moved
    k0 = next(l for l in jax.tree.leaves(st.params) if l.ndim >= 3)
    k1 = next(l for l in jax.tree.leaves(out.params) if l.ndim >= 3)
    assert not np.allclose(np.asarray(k0[1]), np.asarray(k1[1]))
    assert not np.allclose(np.asarray(k0[2]), np.asarray(k1[2]))


# -- the image classifier is one member among others: a digest of the parent's --

# two steps of four members in chunks of two (n_train 64, n_val 32, batch
# 8; init key 3, unit key 4, train key 5) and their evaluation, recorded
# on commit eb135a3 (PR 27), before the trainer took its member from the
# workload (PR 28)
PARENT_DIGEST = {
    "cifar10_cnn": {
        "kwargs": {},
        "param_sq_norm": 15483.715519129535,
        "momentum_sq_norm": 78869.0256225158,
        "losses": [2.556962013244629, 49.37770080566406],
        "scores": [0.09375, 0.0625, 0.09375, 0.03125],
    },
    "cifar100_resnet18": {
        "kwargs": {"width": 8},
        "param_sq_norm": 5286.809479172202,
        "momentum_sq_norm": 486.21161993276115,
        "losses": [5.024986743927002, 5.368114471435547],
        "scores": [0.03125, 0.03125, 0.03125, 0.0],
    },
}


@pytest.mark.parametrize("name", sorted(PARENT_DIGEST))
def test_image_workloads_reach_the_parents_state(name):
    from mpi_opt_tpu.train.common import workload_arrays
    from mpi_opt_tpu.workloads import get_workload

    want = PARENT_DIGEST[name]
    wl = get_workload(name, n_train=64, n_val=32, **want["kwargs"])
    wl.batch_size = 8
    trainer, space, tx, ty, vx, vy = workload_arrays(wl, member_chunk=2)
    state = trainer.init_population(jax.random.key(3), tx[:2], 4)
    hp = wl.make_hparams(space.from_unit(space.sample_unit(jax.random.key(4), 4)))
    state, losses = trainer.train_segment(state, hp, tx, ty, jax.random.key(5), 2)
    scores = trainer.eval_population(state, vx, vy)
    sq = lambda tree: sum(float(np.sum(np.square(np.asarray(l, np.float64)))) for l in jax.tree.leaves(tree))
    assert sq(state.params) == pytest.approx(want["param_sq_norm"], rel=1e-9)
    assert sq(state.momentum) == pytest.approx(want["momentum_sq_norm"], rel=1e-9)
    np.testing.assert_array_equal(np.asarray(losses), np.float32(want["losses"]))
    np.testing.assert_array_equal(np.asarray(scores), np.float32(want["scores"]))
