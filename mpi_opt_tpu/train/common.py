"""Shared plumbing for the fused (whole-sweep-on-device) drivers."""

from __future__ import annotations

import weakref

import jax
import jax.numpy as jnp

from mpi_opt_tpu.obs import trace


def workload_arrays(workload, member_chunk: int = 0, mesh=None):
    """(trainer, space, train_x, train_y, val_x, val_y) for a population
    workload, cached on the workload instance.

    The trainer/space are static jit args (identity-hashed), so
    rebuilding them per call would make every fused invocation a
    guaranteed retrace; the device arrays ride along so the dataset is
    uploaded once per search. ``mesh`` is part of the cache key: a
    meshed trainer constrains its batches over the 'data' axis, which
    changes the compiled program — and with a mesh the datasets come
    back replicated across it (every shard samples the same shared
    minibatch; the trainer's in-program constraint then splits each
    batch over 'data'). This is the single placement point for fused
    sweep data — don't re-place at call sites.
    """
    from mpi_opt_tpu.workloads.base import resolve_momentum_dtype

    # the momentum-dtype knob changes the trainer make_trainer builds;
    # it must be part of the cache key or flipping it mid-process
    # silently reuses the stale-dtype trainer. Resolved ONCE and passed
    # down, so the key and the built trainer cannot disagree
    mdt = resolve_momentum_dtype()
    key = (member_chunk, mesh, mdt)
    cache = getattr(workload, "_fused_cache", None)
    if cache is None or cache[0] != key:
        # setup span: dataset load + upload + trainer build — the cold
        # pre-first-launch time the trace CLI must attribute (it is part
        # of time-to-first-trial, and invisible without a span)
        with trace.span("setup", workload=getattr(workload, "name", None)) as sp:
            # device kind keys the roofline's platform-cap calibration
            trace.note_device(sp)
            d = workload.data()
            arrays = (
                jnp.asarray(d["train_x"]),
                jnp.asarray(d["train_y"]),
                jnp.asarray(d["val_x"]),
                jnp.asarray(d["val_y"]),
            )
            if mesh is not None:
                from mpi_opt_tpu.parallel.mesh import replicate

                rep = replicate(mesh)
                arrays = tuple(jax.device_put(a, rep) for a in arrays)
            workload._fused_cache = (
                key,
                workload.make_trainer(
                    member_chunk=member_chunk, mesh=mesh, momentum_dtype=mdt
                ),
                workload.default_space(),
                *arrays,
            )
    return workload._fused_cache[1:]


def finite_winner(scores, ok=None):
    """(best_i, diverged) for a host score vector: the argmax over
    finite (and ``ok``-masked) entries, with argmax's first-NaN behavior
    gated out — the numpy-level twin of ``algorithms.base.best_finite``,
    shared by the fused SHA/PBT/TPE winner picks so the divergence rule
    lives in ONE place. An all-diverged set returns (0, True): callers
    report best_params=None and a non-finite best_score."""
    import numpy as np

    scores = np.asarray(scores)
    mask = np.isfinite(scores) if ok is None else (np.asarray(ok) & np.isfinite(scores))
    diverged = not bool(mask.any())
    best_i = 0 if diverged else int(np.where(mask, scores, -np.inf).argmax())
    return best_i, diverged


def momentum_dtype_str() -> str:
    """Checkpoint-config form of the momentum storage dtype ('float32'
    default). Part of every fused sweep's config-mismatch check: the
    dtype is carried-state STRUCTURE — resuming a bf16-momentum snapshot
    into an f32 trainer would crash in the scan carry (or silently
    change numerics) instead of refusing cleanly."""
    from mpi_opt_tpu.workloads.base import resolve_momentum_dtype

    return resolve_momentum_dtype() or "float32"


def oom_funnel(wave_size=None):
    """The fused drivers' device-OOM classification boundary (ISSUE 13):
    wrap a launch dispatch so an XLA ``RESOURCE_EXHAUSTED`` escaping it
    re-raises as ``utils.resources.DeviceOOM`` — the ONE type the CLI's
    classified exit (``EX_IOERR``) and the wave scheduler's
    ``--oom-backoff`` handler catch. All four fused drivers classify
    through this door (run_fused wraps the whole dispatch; the shared
    wave engine — train/engine.py, all algorithms — additionally
    guards each wave so backoff can catch per generation/rung/batch);
    everything else propagates raw. ``wave_size`` rides on the typed
    error so diagnostics can say what to halve."""
    from mpi_opt_tpu.utils.resources import oom_funnel as _funnel

    return _funnel(wave_size)


def launch_boundary(
    stage: str, *, final: bool, snapshot=None, state=None, **progress
) -> None:
    """The fused host loops' per-launch service point (one call at the
    end of every launch/rung/generation): write the rank heartbeat, then
    honor a pending graceful-shutdown request — flush the boundary
    snapshot via ``snapshot()`` (pass None when the cadence save already
    ran, or the sweep doesn't checkpoint) and raise ``SweepInterrupted``
    so the CLI exits EX_TEMPFAIL and the launch supervisor restarts with
    ``--resume`` for free. ``final=True`` (the sweep's last boundary)
    suppresses the drain: completing normally strictly dominates
    preempting a finished sweep.

    This is also the cooperative-slice point for the resident sweep
    service (service/scheduler.py): an installed slice hook
    (``shutdown.set_slice_hook``) gets its per-boundary look FIRST and
    may set the very drain flag checked next — so a time-sliced tenant
    parks through the identical flush-snapshot-and-raise path a
    platform SIGTERM takes, and its ledger/snapshot state cannot
    differ from a preempted run's.

    Under multi-process SPMD the same hook slot carries the coord
    plane's drain agreement (``parallel/coord.py``): the tick votes
    this rank's shutdown flag into the boundary's barrier, and the
    drain below additionally requires the AGREED verdict
    (``coord.drain_allowed``) — a SIGTERM that landed on one rank
    after this boundary's vote closed must wait for the next
    boundary's vote, or half the world drains while the other half
    issues the next collective alone. The ``resources.boundary_fault``
    seam fires first: the ``rank_kill`` chaos injector counts 1-based
    boundary ordinals here.

    ``state`` is the population state the sweep holds at this boundary
    (a ``PopState``, or None where the caller has none to show): an
    installed boundary observer (``shutdown.set_boundary_observer``)
    is handed it as ``fn(stage, state)`` before the slice hook runs.
    The state is the live one, donated to the next launch: an observer
    copies what it wants to keep.
    """
    from mpi_opt_tpu.health import heartbeat, shutdown
    from mpi_opt_tpu.parallel import coord
    from mpi_opt_tpu.utils import resources

    if coord.active_plane() is not None:
        # multi-process: label the beat (and a drain's ``at``) as a
        # boundary phase — a rank frozen HERE is waiting in the
        # agreement barrier, the exact last-beat shape launch.py's
        # collective-wedge classifier keys on; and identical labels
        # across ranks let drills assert "all ranks drained at the
        # same boundary" from the summaries alone
        stage = f"boundary:{stage}"
    resources.boundary_fault(stage)
    heartbeat.beat(stage=stage, **progress)
    observer = shutdown.get_boundary_observer()
    if observer is not None:
        observer(stage, state)
    if not final:
        shutdown.poll_slice(stage)
    if final or not shutdown.requested():
        return
    if not coord.drain_allowed():
        return
    if snapshot is not None:
        snapshot()
    raise shutdown.SweepInterrupted(shutdown.active_signal(), at=stage)


def journal_boundary(
    journal, b_local: int, members, units, scores, step: int, scores_mo=None
) -> None:
    """The fused drivers' shared ledger service point, paired with
    ``launch_boundary``: called once per natural boundary (PBT
    generation, SHA/BOHB rung, TPE batch) with the boundary's member
    identities, unit rows, and scores — BEFORE that boundary's snapshot
    is saved, so the journal never lags the snapshot (the fused twin of
    the driver path's fsync-before-report invariant). No-op without a
    journal; on a re-computed boundary (resume) it verifies against the
    journal instead of re-writing (ledger/fused.py).

    ``scores_mo`` (optional ``[n, m]`` raw objective matrix) is the
    multi-objective sweeps' vector payload: ``scores`` stays the
    authoritative scalarized score (what resume/fsck/warm-start
    verify), the vectors ride beside it as each record's ``scores``
    field."""
    if journal is None:
        return
    # one journal span per boundary (not per member record: a pop-1024
    # generation journals 1024 lines — span volume must stay
    # proportional to boundaries, not members); ``fsyncs`` is what the
    # boundary cost the store: 1 written, 0 verified on resume
    with trace.span("journal", boundary=int(b_local), n=len(members)) as sp:
        sp["fsyncs"] = journal.record_boundary(
            b_local, members, units, scores, step, scores_mo=scores_mo
        )


def journal_require_prefix(journal, n_boundaries: int) -> None:
    """Resume-time consistency gate: every boundary the restored
    snapshot records as complete must already be fully journaled
    (``FusedJournal.require_prefix``); no-op without a journal."""
    if journal is not None:
        journal.require_prefix(n_boundaries)


def make_fused_journal(ledger, space, **offsets):
    """``ledger/fused.make_journal`` re-export at the drivers' layer:
    the four fused drivers build their journal views through one door
    so offsets/construction cannot drift between them."""
    from mpi_opt_tpu.ledger.fused import make_journal

    return make_journal(ledger, space, **offsets)


#: objective metric names the population eval path can produce; the
#: ObjectiveSpec names of a fused multi-objective sweep must come from
#: this set (validated in the CLI before anything compiles)
POPULATION_METRICS = ("accuracy", "params", "latency")


def eval_population_objectives(trainer, state, val_x, val_y, names):
    """Multi-metric population eval: raw ``float32[P, m]``, one column
    per objective name (ISSUE 17).

    Jit-safe with ``names`` static (it arrives from the frozen
    ObjectiveSpec that is itself a static jit arg), so inside
    ``run_fused_pbt`` this compiles into the generation scan; called
    eagerly from the SHA rung loop it dispatches the same jitted
    programs with no extra host sync — columns stay on device until
    the driver's one per-boundary fetch.
    """
    cols = []
    with jax.named_scope("eval_population"):
        for name in names:
            if name == "accuracy":
                cols.append(trainer.eval_population(state, val_x, val_y))
            elif name == "params":
                cols.append(trainer.member_effective_params(state))
            elif name == "latency":
                cols.append(trainer.member_latency_proxy(state))
            else:
                raise ValueError(
                    f"unknown population objective {name!r}; "
                    f"supported: {POPULATION_METRICS}"
                )
        return jnp.stack(cols, axis=-1)


def segment_flops_hint(workload, population: int, steps: int):
    """Per-boundary FLOPs (one train segment of ``population`` members
    for ``steps`` steps + one eval pass) for the trace layer's achieved-
    TF/s attribution — the number that turns the 33-of-157 TF/s kernel
    gap (PERF_NOTES) into something the system REPORTS per launch.

    Only computed when tracing is enabled (the probe lowers tiny
    one-member programs through XLA's cost analysis —
    utils.flops.population_sweep_flops — which an untraced sweep must
    not pay), cached per (population, steps) on the workload instance,
    and probe compiles are span-suppressed so they don't pollute the
    very attribution they serve. None when tracing is off or the
    backend offers no cost analysis; callers then omit the ``flops``
    span attr and the trace CLI reports TF/s as unavailable. A workload
    that counts its own work from its equations
    (``segment_flops(population, steps)``) answers instead, and nothing
    is lowered: at a member of half a billion parameters the probe
    would lower its programs a second time.
    """
    if not trace.enabled():
        return None
    analytic = getattr(workload, "segment_flops", None)
    if analytic is not None:
        return analytic(int(population), int(steps))
    cache = getattr(workload, "_flops_hint_cache", None)
    if cache is None:
        cache = workload._flops_hint_cache = {}
    key = (int(population), int(steps))
    if key not in cache:
        from mpi_opt_tpu.utils.flops import population_sweep_flops

        # the probe's own wall is attributed as setup (it is real
        # pre-train time of a traced sweep); the tiny programs it
        # lowers are span-SUPPRESSED so their compiles don't count as
        # the sweep's compile phase
        with trace.span("setup", op="flops_probe", members=int(population)):
            with trace.suppressed():
                cache[key] = population_sweep_flops(
                    workload, int(population), 1, int(steps), n_evals=1
                )
    return cache[key]


class HParamsFn:
    """Hashable (space, workload)-bound unit->OptHParams mapping, usable
    as a static jit argument (identity-hashed: space/workload come from
    per-workload caches, so identity is stable across calls and a fresh
    pair correctly forces a retrace).

    The workload is held WEAKLY. A static argument lives in the jit
    cache of the trainer's program, where the cycle collector cannot
    see it; a strong reference from here would close the loop workload
    -> trainer -> program -> this -> workload through that blind spot,
    and no workload, trainer or executable would ever be freed. Every
    caller holds the workload for as long as the program can trace."""

    def __init__(self, space, workload):
        self.space = space
        self._workload = weakref.ref(workload)
        self._hash = hash((id(space), id(workload)))

    def __call__(self, unit):
        return self._workload().make_hparams(self.space.from_unit(unit))

    def __hash__(self):
        return self._hash

    def __eq__(self, other):
        if not isinstance(other, HParamsFn) or self.space is not other.space:
            return False
        workload = self._workload()
        return workload is not None and workload is other._workload()
