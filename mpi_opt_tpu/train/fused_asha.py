"""Fused successive halving: ASHA's rung reductions on-device.

Reference behavior being replaced (SURVEY.md §2 row 4; BASELINE.json
north_star: "ASHA rung reductions become lax.top_k over a device mesh
instead of MPI_Allgather"): the reference promotes trials through budget
rungs asynchronously because its workers are independent MPI ranks and
waiting for a rung to fill would idle them. On a TPU the whole cohort
trains in lockstep as one vmapped population, so the *synchronous*
variant (successive halving) is the natural execution: train every
member to the rung budget, evaluate, cut to the top 1/eta with
``ops.asha.asha_cut``, gather the survivors into a smaller population,
continue. Stragglers don't exist — every member advances in the same
XLA program — which is exactly why the async relaxation isn't needed.

Per rung there is ONE host sync (the cut indices come back to update the
tiny trial ledger); population shapes shrink eta-fold per rung, so a
sweep compiles at most len(rungs) train/eval program pairs, all cached
across sweeps.

The cut itself (`_cut_and_gather`) is a jitted kernel: ``asha_cut``
ranks the cohort, the top-k slice of its descending order picks the
survivors, and the same index vector gathers member states — the MPI
Allgather + per-rank promotion decisions + state re-dispatch of the
reference collapse into one on-device top-k + gather.
"""

from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from mpi_opt_tpu.obs import memory, trace
from mpi_opt_tpu.ops.asha import asha_cut, asha_cut_mo, asha_rungs
from mpi_opt_tpu.train.common import (
    eval_population_objectives,
    finite_winner,
    journal_boundary,
    journal_require_prefix,
    launch_boundary,
    make_fused_journal,
    momentum_dtype_str,
    segment_flops_hint,
    workload_arrays,
)

# the shared fault-tolerant wave executor (train/engine.py): wave
# scheduling, host-pool staging, OOM backoff, drain/heartbeat — this
# module supplies only SHA's boundary op (the rung cut). The private
# ``_run_wave`` alias is this module's chaos-drill seam, mirroring
# fused_pbt's.
from mpi_opt_tpu.train.engine import (
    WaveRunner,
    boundary_span,
    resolve_wave_size,
)
from mpi_opt_tpu.train.engine import run_wave as _run_wave
from mpi_opt_tpu.train.population import PopState, trainer_jit
from mpi_opt_tpu.utils import profiling


@trainer_jit(static_argnames=("eta", "k"))
def _cut_and_gather(trainer, state, unit, scores, eta: int, k: int):
    """One rung reduction: rank, keep the top k, gather their states.

    ``k`` is static (rung cohort sizes are known ahead of time), so the
    survivor population has a fixed shape for the next rung's program.
    Returns (survivor_state, survivor_unit, keep_idx, promote_mask).
    """
    promote, order = asha_cut(scores, eta)
    keep = order[:k]
    return trainer.gather_members(state, keep), unit[keep], keep, promote


@trainer_jit(static_argnames=("eta", "k"))
def _cut_and_gather_mo(trainer, state, unit, norm_scores, eta: int, k: int, norm_bounds=None):
    """The rung reduction's multi-objective twin (ISSUE 17): rank by
    ``pareto_score`` (front index, crowding tie-break, constraint
    degradation) instead of the raw scalar, then keep/gather exactly as
    the scalar cut does — the Pareto selection stays inside the same
    compiled boundary program, no extra host round-trip."""
    promote, order, _eff = asha_cut_mo(norm_scores, eta, norm_bounds=norm_bounds)
    keep = order[:k]
    return trainer.gather_members(state, keep), unit[keep], keep, promote


@functools.partial(jax.jit, static_argnames=("eta", "k"))
def _wave_cut(unit, scores, eta: int, k: int):
    """The rung cut for wave-scheduled cohorts: rank + keep exactly as
    ``_cut_and_gather`` does, minus the on-device state gather — the
    survivor-weight copy is realized LAZILY by the next rung's stage-in
    indexing the host pool with ``keep`` (train/staging.py; the
    ``fused_pbt._wave_exploit`` precedent: a separate-jit boundary op
    preserves CPU bit-identity with the fused one). Returns
    (survivor_unit, keep_idx)."""
    _promote, order = asha_cut(scores, eta)
    keep = order[:k]
    return unit[keep], keep


def sha_cohort_sizes(n_trials: int, n_rungs: int, eta: int, round_to: int = 1) -> list[int]:
    """Population size at each rung: n, ceil(n/eta), ... (>=1).

    ``round_to`` rounds survivor counts up to a multiple (a sharded
    population must stay divisible by the mesh's 'pop' axis).
    """
    sizes = [n_trials]
    for _ in range(n_rungs - 1):
        k = -(-sizes[-1] // eta)  # ceil
        k = min(sizes[-1], -(-k // round_to) * round_to)
        sizes.append(max(k, 1))
    return sizes


def fused_sha(  # sweeplint: barrier(rung host loop: gathers cohort scores for the rung cut + journal)
    workload,
    n_trials: int,
    min_budget: int = 10,
    max_budget: int = 270,
    eta: int = 3,
    seed: int = 0,
    member_chunk: int = 0,
    mesh=None,
    round_to: int = 1,
    checkpoint_dir: str = None,
    init_unit=None,
    ledger=None,
    boundary_offset: int = 0,
    trial_offset: int = 0,
    member_offset: int = 0,
    warm_obs=None,
    objectives=None,
    wave_size=0,
    oom_backoff: int = 2,
):
    """Run a whole successive-halving sweep with on-device rung cuts.

    ``wave_size`` (int or ``'auto'``; the carried PR-4 follow-up, via
    the shared engine) schedules each RUNG's cohort as resident waves
    through a host pool when it exceeds device residency — per-rung
    re-cohorting: every rung gets a fresh pool sized to its (shrinking)
    cohort, and the cut's survivor gather is realized by the next
    rung's stage-in permutation. Bit-identical to resident mode for any
    wave size on the CPU backend (tested): hparams are mapped eagerly
    over the FULL cohort exactly as the resident rung does (then sliced
    per wave — slicing is exact), member/batch RNG windows the full
    split, init keys slice the same ``split(k_init, n)``, and the cut
    sees the same (scores, eta, k). ``oom_backoff`` extends the PBT
    wave-halving contract to rungs: a device OOM during a rung's waves
    halves the cap and re-runs THAT rung from wave 0, bit-identically.

    ``ledger`` journals one record per surviving trial per rung —
    pre-cut score at the rung's budget, the trial's unit params —
    BEFORE the rung's snapshot (ledger/fused.py); the three offsets
    place this sweep's boundaries/records/trial identities inside a
    composite journal (fused hyperband/BOHB give each bracket its
    global offsets). ``warm_obs`` (prior-ledger observations,
    cross-mode) seeds cohort row 0 with the prior best point — ignored
    when the caller supplies ``init_unit`` (model-based callers own
    their cohorts).

    Returns a dict with the best trial's score/params, per-rung sizes
    and budgets, and a per-trial ledger (stop rung + last score).

    ``init_unit`` (optional float[n_trials, dim] in the unit cube)
    replaces the uniform initial cohort — fused BOHB passes
    model-sampled configurations here. The checkpoint config records a
    digest of it, so a resume under different initial configurations is
    refused (deterministic callers like fused_bohb regenerate the same
    matrix, so their resumes still match).

    ``checkpoint_dir`` makes the sweep crash-recoverable at RUNG
    granularity (same failure model as fused_pbt's launch snapshots):
    after each rung's cut the surviving cohort (state, unit, RNG key)
    and the trial ledger are orbax-saved; a fresh call with the same
    arguments resumes at the next rung and — the key being part of the
    snapshot — produces the IDENTICAL result of an uninterrupted run.
    A config-mismatched checkpoint raises ValueError.

    ``objectives`` (an ``ObjectiveSpec``, ISSUE 17) turns every rung cut
    multi-objective: each rung evaluates the spec's metrics, cuts by
    ``pareto_score`` inside the compiled boundary op, and journals the
    scalarized primary score (authoritative) plus the raw objective
    vector per record. The scalar path is untouched.
    """
    from mpi_opt_tpu.parallel.mesh import fetch_global, place_pop, shard_popstate
    from mpi_opt_tpu.train.staging import population_pool, write_rows

    trainer, space, train_x, train_y, val_x, val_y = workload_arrays(
        workload, member_chunk, mesh
    )
    # wave scheduling (cohort > residency): the shared engine door
    # resolves ``auto``, pre-clamps explicit caps, refuses multi-process
    # (train/engine.py). A cap at or above the first rung's cohort means
    # everything fits — resident mode, the bit-identical baseline.
    wave_size = resolve_wave_size(
        trainer,
        train_x[:2],
        n_trials,
        wave_size=wave_size,
        mesh=mesh,
        oom_backoff=oom_backoff,
    )
    waves = 0 < wave_size < n_trials
    if waves and objectives is not None:
        raise ValueError(
            "wave scheduling is not supported with multi-objective "
            "sweeps yet; run resident (wave_size=0) or shard the "
            "cohort over a mesh"
        )
    norm_bounds = None
    if objectives is not None:
        supported = tuple(workload.objective_metrics())
        missing = [n for n in objectives.names if n not in supported]
        if missing:
            raise ValueError(
                f"workload {getattr(workload, 'name', type(workload).__name__)!r} "
                f"cannot evaluate objectives {missing}; supported: {supported}"
            )
        if objectives.has_bounds:
            norm_bounds = objectives.norm_bounds()
    rungs = asha_rungs(min_budget, max_budget, eta)
    if mesh is not None and round_to == 1:
        round_to = mesh.shape["pop"]
    sizes = sha_cohort_sizes(n_trials, len(rungs), eta, round_to)

    if init_unit is not None:
        init_unit = np.asarray(init_unit, dtype=np.float32)
        if init_unit.shape != (n_trials, space.dim):
            raise ValueError(
                f"init_unit shape {init_unit.shape} != ({n_trials}, {space.dim})"
            )

    key = jax.random.key(seed)
    k_init, k_unit, k_run = jax.random.split(key, 3)

    # host ledger: which original trial occupies each population row
    alive = np.arange(n_trials)
    stop_rung = np.zeros(n_trials, dtype=np.int32)
    last_score = np.full(n_trials, np.nan, dtype=np.float32)
    # every (trial, budget, score) observation, one entry per rung —
    # model-based callers (fused BOHB) consume ALL of a trial's scores,
    # not just the one at its stop rung
    rung_history: list = []

    # restore BEFORE initializing: a resumed sweep must not pay (or
    # transiently hold the memory of) a full-cohort init it discards
    snap = None
    restored = None
    start_rung = 0
    scores = None
    if checkpoint_dir is not None:
        from mpi_opt_tpu.utils.checkpoint import SweepCheckpointer

        ck_config = {
            "workload": getattr(workload, "name", type(workload).__name__),
            "n_trials": n_trials,
            "rungs": rungs,
            "sizes": sizes,
            "eta": eta,
            "seed": seed,
            "member_chunk": member_chunk,
            # carried-state structure (see fused_pbt): a resumed rung
            # must find momentum in the dtype it was saved with
            "momentum_dtype": momentum_dtype_str(),
            # the initial cohort defines the sweep: a resume whose
            # caller supplies different configurations is a
            # different search and must be refused
            "init_unit_digest": (
                None
                if init_unit is None
                else hashlib.sha1(init_unit.tobytes()).hexdigest()
            ),
        }
        if waves:
            # the wave split is part of a wave-scheduled sweep's
            # identity: its snapshots resume through host pools. Resident
            # configs deliberately DON'T write the key, so every
            # pre-existing SHA snapshot keeps resuming via the
            # ``setdefault(0)`` back-compat (utils/checkpoint.py) — and
            # a wave resume of a resident snapshot refuses cleanly
            # (0 != cap) instead of crashing in pool reconstruction.
            # The REQUESTED (resolved) cap, as in fused_pbt: an OOM
            # backoff's smaller execution cap lives in meta
            # (wave_size_run) and is adopted on resume below
            ck_config["wave_size"] = wave_size
        if objectives is not None:
            # objective identity shapes every cut (see fused_pbt); the
            # key is absent on scalar sweeps so pre-existing snapshots
            # keep resuming
            ck_config["objectives"] = objectives.spec()
        snap = SweepCheckpointer(checkpoint_dir, ck_config)
        restored = snap.restore_population_sweep()
        if restored is not None:
            state, unit, k_run, scores, meta = restored
            alive = np.asarray(meta["alive"], dtype=np.int64)
            stop_rung = np.asarray(meta["stop_rung"], dtype=np.int32)
            last_score = np.asarray(meta["last_score"], dtype=np.float32)
            start_rung = int(meta["rungs_done"])
            # pre-upgrade snapshots have no history; completed rungs'
            # stop-rung observations are still in last_score, so the
            # history is marked partial rather than fabricated
            rung_history = list(meta.get("rung_history", []))
            if waves:
                # adopt a prior attempt's OOM-settled execution cap
                # (meta wave_size_run): resuming at the requested size
                # would re-OOM a rung just to re-learn the answer
                run_wave_size = int(meta.get("wave_size_run", wave_size))
                # the snapshot's survivor cohort becomes the next rung's
                # host pool; its rows are already in cohort order, so
                # the stage-in permutation starts as the identity
                pool_front = {
                    "params": jax.tree.map(np.asarray, state.params),
                    "momentum": jax.tree.map(np.asarray, state.momentum),
                    "step": np.asarray(state.step),
                }
                perm = np.arange(len(alive))
                state = None
    journal = make_fused_journal(
        ledger,
        space,
        boundary_offset=boundary_offset,
        trial_offset=trial_offset,
        member_offset=member_offset,
    )
    journal_require_prefix(journal, start_rung)
    if restored is None:
        if init_unit is not None:
            unit = jax.numpy.asarray(init_unit)
        else:
            unit = space.sample_unit(k_unit, n_trials)
            if warm_obs:
                from mpi_opt_tpu.ledger.warmstart import best_observation

                bo = best_observation(warm_obs)
                if bo is not None:
                    # sampler-family warm start (mirrors driver ASHA's
                    # seeded first suggestion): one cohort row starts at
                    # the prior best; the rung cuts keep it only if it
                    # earns survival
                    unit = np.array(unit)
                    unit[0] = np.asarray(bo.unit, dtype=unit.dtype)
                    unit = jax.numpy.asarray(unit)
        if waves:
            # rung-0 members initialize on device per wave, windows of
            # the SAME ``split(k_init, n)`` the resident
            # ``init_population`` derives — weights are bit-identical
            member_keys = jax.random.split(k_init, n_trials)
            pool_front = None
            perm = np.arange(n_trials)
            state = None
        else:
            state = trainer.init_population(k_init, train_x[:2], n_trials)
    if mesh is not None:
        # datasets were already replicated over the mesh by workload_arrays
        if not waves:
            state = shard_popstate(state, mesh)
        unit = place_pop(unit, mesh)

    def record_rung(r: int, np_scores_r) -> None:
        """Ledger update for one rung's PRE-cut cohort — the single
        source for both the eager (checkpointed) and deferred-replay
        paths, which must produce identical result ledgers."""
        stop_rung[alive] = r
        last_score[alive] = np_scores_r
        rung_history.append(
            {
                "budget": int(rungs[r]),
                "trials": [int(i) for i in alive],
                "scores": [float(v) for v in np_scores_r],
            }
        )

    # Uncheckpointed sweeps DEFER every host fetch to one barrier after
    # the last rung: the per-rung score/keep values feed only the host
    # ledger (consumed after the sweep), so the rung programs can
    # dispatch back-to-back — the wall becomes device time instead of
    # launch + blocking fetch per rung (a 4-rung config-2 sweep paid
    # ~7 of them).
    # Checkpointed sweeps keep the per-rung fetch: each snapshot needs
    # host copies of the ledger at that rung. A fused JOURNAL forces the
    # eager path too: its records must be fsync-durable per rung (the
    # journal-before-snapshot ordering), which deferral would break.
    # Wave scheduling is eager by construction: every rung's scores land
    # on host through the staging writers.
    defer = snap is None and journal is None and not waves
    runner = None
    if waves:
        # the shared wave executor (train/engine.py) owns the staging
        # engine, the execution cap, and the OOM-backoff retry; the rung
        # loop below supplies SHA's shapes and boundary op. Starts at
        # the snapshot-adopted cap when resuming past a backoff.
        runner = WaveRunner(
            n_trials,
            run_wave_size if restored is not None else wave_size,
            oom_backoff=oom_backoff,
        )
    rung_scores_dev: list = []  # device scores per rung (pre-cut rows)
    rung_keep_dev: list = []  # device survivor indices per cut
    rung_mo_dev: list = []  # device [n, m] objective matrices (MO only)
    np_final_mo = None  # last rung's raw objective matrix (MO only)
    try:
        for r in range(start_rung, len(rungs)):
            budget = rungs[r]
            prev_budget = rungs[r - 1] if r > 0 else 0
            k_run, k_seg = jax.random.split(k_run)
            profiling.launch_tick()
            # eager mode's score fetch is the rung's completion barrier,
            # so the span's duration is real and carries flops for
            # achieved TF/s; deferred mode dispatches async (the span
            # measures dispatch — no flops attr, TF/s would be bogus)
            # hint probed OUTSIDE the span (its one-time cost must not
            # inflate the first rung's measured duration)...
            f = None if defer else segment_flops_hint(
                workload, sizes[r], budget - prev_budget
            )
            if waves:
                n_r = sizes[r]
                # EAGER unit->hparams mapping over the FULL cohort — the
                # resident rung maps eagerly before train_segment, so
                # the wave path must hand the programs the SAME values
                # (sliced per wave inside run_wave; slicing is exact) to
                # be bit-identical to it. This is NOT the PBT/TPE rule
                # (their resident programs map in-scan): each wave path
                # mirrors ITS resident twin.
                hp = workload.make_hparams(space.from_unit(unit))
                # per-rung re-cohorting: a fresh pool sized to THIS
                # rung's (shrinking) cohort; the previous rung's pool is
                # read through the cut's survivor permutation
                pool_back = population_pool(trainer, train_x[:2], n_r)
                scores_host = np.full((n_r,), np.nan, np.float32)

                def _writer(off, pool_back=pool_back, scores_host=scores_host):
                    def on_host(host):  # sweeplint: barrier(stage-out landing: writes fetched wave states + scores into the rung pool)
                        write_rows(pool_back, off, host["state"])
                        w_ = len(host["scores"])
                        scores_host[off : off + w_] = np.asarray(
                            host["scores"], np.float32
                        )

                    return on_host

                def _dispatch(
                    w, off, wl_, eng, r=r, k_seg=k_seg, hp=hp, n_r=n_r,
                    pool_front=pool_front, perm=perm,
                    budget=budget, prev_budget=prev_budget,
                ):
                    # ``_run_wave`` resolved at call time (module
                    # global) so the chaos drills' monkeypatch seam
                    # keeps working
                    return _run_wave(
                        trainer,
                        pool_front,
                        perm[off : off + wl_],
                        off,
                        None,  # unit/hparams_fn unused: hp mode
                        None,
                        train_x,
                        train_y,
                        val_x,
                        val_y,
                        k_seg,
                        budget - prev_budget,
                        n_r,
                        mesh,
                        eng,
                        init_keys=member_keys[off : off + wl_] if r == 0 else None,
                        sample_x=train_x[:2],
                        hp=hp,
                    )

                def _payload(st, sc):
                    return {
                        "state": {
                            "params": st.params,
                            "momentum": st.momentum,
                            "step": st.step,
                        },
                        "scores": sc,
                    }

                wave_scores = runner.run_interval(
                    n=n_r,
                    run_wave_fn=_dispatch,
                    payload_fn=_payload,
                    writer_fn=_writer,
                    scores_host=scores_host,
                    stage_label=lambda w, nw, r=r: (
                        f"sha rung {r + 1}/{len(rungs)} wave {w + 1}/{nw}"
                    ),
                    boundary_kwargs=lambda w, nw, r=r: {
                        "rung": r + 1,
                        "of": len(rungs),
                    },
                    # no mid-rung snapshots: SHA snapshots at rung
                    # granularity (a resume re-trains the interrupted
                    # rung; the journal verifies instead of re-writing)
                    midpoint_snapshot=None,
                    span_attrs=lambda nw, r=r, n_r=n_r: {
                        "launch": boundary_offset + r + 1,
                        "rung": r + 1,
                        "members": n_r,
                        "steps": budget - prev_budget,
                        "waves": nw,
                    },
                    flops=f,
                    notify_fields=(("rung", r + 1),),
                )
                mo = None
                np_mo = None
                # same device/host score pair the resident path holds:
                # the concat feeds the cut, the landed host copy feeds
                # the ledger (f32 round-trips exactly)
                scores = jnp.concatenate([jnp.asarray(s) for s in wave_scores])
                np_scores = scores_host.copy()
                record_rung(r, np_scores)
                if journal is not None:
                    journal_boundary(
                        journal, r, alive, fetch_global(unit), np_scores,
                        step=budget,
                    )
                # fall through to the shared rung cut below
            else:
                with trace.span(
                    "train",
                    launch=boundary_offset + r + 1,
                    rung=r + 1,
                    members=sizes[r],
                    steps=budget - prev_budget,
                ) as sp:
                    if objectives is not None:
                        # registered span attr: MO rungs are visible in
                        # the trace; the cut still runs on-device (no
                        # new sync)
                        sp["objectives"] = ",".join(objectives.names)
                    hp = workload.make_hparams(space.from_unit(unit))
                    state, _ = trainer.train_segment(
                        state, hp, train_x, train_y, k_seg, budget - prev_budget
                    )
                    if objectives is None:
                        mo = None
                        scores = trainer.eval_population(state, val_x, val_y)
                    else:
                        # each metric call is its own jitted program, so
                        # the dispatches stay async — the rung still
                        # pays at most the one host fetch the eager path
                        # always paid
                        mo = eval_population_objectives(
                            trainer, state, val_x, val_y, objectives.names
                        )
                        scores = objectives.scalarize(mo)
                    if defer:
                        rung_scores_dev.append(scores)
                        if mo is not None:
                            rung_mo_dev.append(mo)
                    else:
                        np_scores = fetch_global(scores)
                        # ...and attached only AFTER the fetch barrier:
                        # a rung that raised mid-span must not report
                        # full-rung FLOPs over a partial duration
                        if f:
                            sp["flops"] = f
                        # post-barrier device-memory watermark: the
                        # rung's cohort + activations just peaked
                        memory.note(sp)
                if not defer:
                    np_mo = None if mo is None else fetch_global(mo)
                    np_final_mo = np_mo if np_mo is not None else np_final_mo
                    record_rung(r, np_scores)
                    if journal is not None:
                        # one member record per PRE-cut survivor at this
                        # rung's budget, before the rung snapshot below
                        journal_boundary(
                            journal, r, alive, fetch_global(unit), np_scores,
                            step=budget, scores_mo=np_mo,
                        )
            if r < len(rungs) - 1:
                # boundary_span (train/engine.py): heartbeats from
                # inside the op, so a stall DURING the cut is attributed
                # to "boundary:rung_cut" by launch.py's stall report
                with boundary_span("rung_cut", rung=r + 1):
                    if waves:
                        # survivor weights are NOT gathered on device:
                        # the next rung's stage-in indexes the host pool
                        # with ``keep`` (the wave path's lazy gather)
                        unit, keep = _wave_cut(unit, scores, eta, sizes[r + 1])
                        if mesh is not None:
                            unit = place_pop(unit, mesh)
                        np_keep = fetch_global(keep)
                        alive = alive[np_keep]
                        np_scores = np_scores[np_keep]
                        perm = np.asarray(np_keep)
                    elif objectives is None:
                        state, unit, keep, _ = _cut_and_gather(
                            trainer, state, unit, scores, eta, sizes[r + 1]
                        )
                    else:
                        state, unit, keep, _ = _cut_and_gather_mo(
                            trainer,
                            state,
                            unit,
                            objectives.normalize(mo),
                            eta,
                            sizes[r + 1],
                            norm_bounds=norm_bounds,
                        )
                    if not waves and mesh is not None:
                        # re-place: the gather may leave survivors
                        # unsharded/skewed
                        state = shard_popstate(state, mesh)
                        unit = place_pop(unit, mesh)
                    if defer:
                        rung_keep_dev.append(keep)
                    elif not waves:
                        np_keep = fetch_global(keep)
                        alive = alive[np_keep]
                        # post-cut survivors' scores, for a
                        # resume-at-complete result (np_scores already
                        # holds this rung's fetch — re-fetching would pay
                        # an extra cross-process allgather per rung under
                        # multi-host)
                        np_scores = np_scores[np_keep]
            if waves:
                # the trained cohort now lives in this rung's pool: it
                # becomes the next rung's stage-in source (read through
                # ``perm``, the cut's survivor map)
                pool_front = pool_back
            if snap is not None:
                save_state = state
                if waves:
                    # materialize the CURRENT cohort (post-cut survivors;
                    # the full final cohort at the last rung) from the
                    # pool — fancy indexing copies, so the async orbax
                    # write can never see later in-place pool writes
                    sel = perm if r < len(rungs) - 1 else np.arange(sizes[r])
                    save_state = PopState(
                        params=jax.tree.map(lambda l: l[sel], pool_back["params"]),
                        momentum=jax.tree.map(lambda l: l[sel], pool_back["momentum"]),
                        step=pool_back["step"][sel],
                    )
                meta_extra = {
                    "rungs_done": r + 1,
                    # ledger cross-check unit (fsck, resume gate):
                    # GLOBAL boundary count complete at this snapshot
                    "boundaries_done": boundary_offset + r + 1,
                    "alive": alive.tolist(),
                    "stop_rung": stop_rung.tolist(),
                    "last_score": [float(v) for v in last_score],
                    "rung_history": rung_history,
                }
                if waves:
                    # the OOM-settled execution cap (adopted on resume)
                    meta_extra["wave_size_run"] = runner.wave_size
                # scores saved = the CURRENT cohort rows (post-cut when cut)
                snap.save_population_sweep(
                    r + 1, save_state, unit, k_run, np_scores,
                    meta_extra=meta_extra,
                )
            # heartbeat + graceful-shutdown drain: checkpointed sweeps
            # already snapshot every rung (nothing extra to flush);
            # uncheckpointed ones have no durable state — the drain
            # still honors the preemption promptly
            launch_boundary(
                f"sha rung {r + 1}/{len(rungs)}",
                final=r + 1 == len(rungs),
                rung=r + 1,
                of=len(rungs),
            )
    finally:
        if runner is not None:
            runner.close()
        if snap is not None:
            snap.close()

    final_np_scores = None
    if defer and rung_scores_dev:
        # the single host barrier: fetch every rung's scores/cuts in one
        # batched transfer and replay the ledger updates the eager path
        # did per rung
        from mpi_opt_tpu.parallel.mesh import fetch_global_batched

        fetched = fetch_global_batched(rung_scores_dev + rung_keep_dev + rung_mo_dev)
        ns, nk = len(rung_scores_dev), len(rung_keep_dev)
        np_rung_scores = fetched[:ns]
        np_keeps = fetched[ns : ns + nk]
        if rung_mo_dev:
            np_final_mo = fetched[-1]  # last rung's objective matrix
        final_np_scores = np_rung_scores[-1]  # last rung has no cut
        for r_off, np_scores in enumerate(np_rung_scores):
            r = start_rung + r_off
            record_rung(r, np_scores)
            if r < len(rungs) - 1:
                alive = alive[np_keeps[r_off]]

    np_unit = fetch_global(unit)
    final_scores = fetch_global(scores) if final_np_scores is None else final_np_scores
    # one diverged survivor (NaN, or +/-inf from an exploded loss) must
    # not hijack the bracket's best — argmax would return the NaN/+inf
    # row. Shared rule: train.common.finite_winner; the all-diverged
    # cohort reports non-finite/None with diverged=True, so no
    # arbitrary row masquerades as a meaningful winner
    best_row, diverged = finite_winner(final_scores)
    pareto = None
    if objectives is not None and np_final_mo is not None:
        from mpi_opt_tpu.objectives import (
            hypervolume,
            pareto_front_mask,
            select_best,
        )

        # constraint-aware winner (see fused_pbt): best FEASIBLE
        # survivor, typed degradation to least-violating when nothing is
        sel = select_best(np_final_mo, objectives)
        if sel["index"] is None:
            best_row, diverged = 0, True
        else:
            best_row, diverged = int(sel["index"]), False
        norm = objectives.normalize(np_final_mo)
        mask = pareto_front_mask(norm)
        front_rows = [int(i) for i in np.flatnonzero(mask)]
        pareto = {
            "front_size": len(front_rows),
            "front_members": [int(alive[i]) for i in front_rows],
            "front_scores": [
                [float(v) for v in np_final_mo[i]] for i in front_rows
            ],
            "hypervolume": float(hypervolume(norm[mask])) if front_rows else 0.0,
            "selection": sel["kind"],
            "violation": sel["violation"],
        }
    return {
        # diverged normalizes to NaN (not a raw +/-inf row) so library
        # callers can detect it uniformly across fused SHA/PBT/TPE
        "best_score": float("nan") if diverged else float(final_scores[best_row]),
        "best_params": None if diverged else space.materialize_row(np_unit[best_row]),
        "best_trial": None if diverged else int(alive[best_row]),
        "diverged": diverged,
        "rung_budgets": rungs,
        "rung_sizes": sizes,
        "stop_rung": stop_rung,
        "last_score": last_score,
        "rung_history": rung_history,
        # per-rung diverged-member tallies (ROADMAP open item): the
        # isfinite winner pick MASKS divergence, it must not HIDE it —
        # operators need to see how many members each rung lost. From
        # rung_history, so eager and deferred paths agree by
        # construction; a pre-upgrade resume with partial history
        # reports the rungs it has
        "member_failures": [
            int(np.sum(~np.isfinite(np.asarray(rh["scores"], dtype=np.float64))))
            for rh in rung_history
        ],
        "n_trials": n_trials,
        "journal": None
        if journal is None
        else {"written": journal.written, "verified": journal.verified},
        # multi-objective extras (ISSUE 17, see fused_pbt): None on
        # scalar sweeps and on a resume that restarted past the final
        # rung (``report`` recomputes the front from the ledger then)
        "objectives": None if objectives is None else list(objectives.names),
        "pareto": pareto,
        # wave-scheduling observability (the same keys every
        # wave-scheduled driver reports — train/engine.py): settled
        # execution split, OOM halvings, staged bytes, overlap
        **({} if runner is None else runner.result_extras()),
    }


def _bracket_cohort(checkpoint_dir, b: int, n: int, tag: str, cohort_fn):  # sweeplint: barrier(bracket cohort cache: materializes suggested units to disk)
    """Sample bracket ``b``'s initial cohort — durably, when the sweep
    is checkpointed. The sampled matrix is persisted next to the
    bracket snapshots and REUSED on resume: regenerating it would
    couple resume correctness to bit-identical model-sampling replay
    across processes/JAX versions, where any numeric drift makes
    fused_sha's cohort digest permanently refuse an otherwise-valid
    checkpoint with no recovery path (ADVICE r3). The digest check
    stays as defense-in-depth — the persisted cohort always matches it.
    """
    import os

    path = None
    if checkpoint_dir:
        path = os.path.join(checkpoint_dir, f"cohort_{b}.npz")
        if os.path.exists(path):
            with np.load(path) as z:
                cohort, n_model = np.array(z["cohort"]), int(z["n_model"])
                saved_tag = str(z["tag"])
            # validated HERE, not only by fused_sha's snapshot config
            # check: a crash after the cohort write but before the first
            # rung snapshot leaves no snapshot to refuse a reused dir,
            # so the cohort file itself carries the sweep's identity
            # (workload/plan/seed tag + row count). The cohort's VALUES
            # are deliberately not part of the identity — the persisted
            # matrix IS the sweep's sampling record; model hyperparams
            # (random_fraction, TPEConfig) only shaped how it was drawn.
            if cohort.shape[0] != n or saved_tag != tag:
                raise ValueError(
                    f"persisted cohort for bracket {b} is from a different "
                    f"sweep ({cohort.shape[0]} rows, tag {saved_tag!r}; "
                    f"expected {n} rows, tag {tag!r}) — use a fresh "
                    "checkpoint dir"
                )
            return cohort, n_model
    cohort, n_model = cohort_fn(b, n)
    if path is not None:
        os.makedirs(checkpoint_dir, exist_ok=True)
        # write-then-rename: a crash mid-write must not leave a torn
        # cohort file that a resume would trust. The tmp name is
        # RANK-UNIQUE: under multi-process SPMD every rank runs this
        # host code against the SHARED checkpoint dir, and two ranks
        # sharing one tmp path race each other (one rank's os.replace
        # steals the other's half-written file; the loser's replace
        # then raises FileNotFoundError). Ranks write identical bytes
        # (the cohort is drawn by deterministic SPMD-identical host
        # code), so last-replace-wins is correct.
        tmp = f"{path}.tmp{jax.process_index()}"
        with open(tmp, "wb") as f:
            np.savez(f, cohort=cohort, n_model=n_model, tag=np.asarray(tag))
        os.replace(tmp, path)
    return cohort, n_model


def fused_hyperband(
    workload,
    max_budget: int = 270,
    eta: int = 3,
    seed: int = 0,
    member_chunk: int = 0,
    mesh=None,
    round_to: int = 1,
    checkpoint_dir: str = None,
    cohort_fn=None,
    observe_fn=None,
    ledger=None,
    warm_obs=None,
    wave_size=0,
    oom_backoff: int = 2,
):
    """Hyperband with every bracket running as a fused on-device SHA.

    ``wave_size``/``oom_backoff`` pass straight through to each
    bracket's ``fused_sha``: the cap is resolved against every
    bracket's own cohort size (a small bracket that fits resident runs
    resident), and each bracket's rungs get the engine's wave
    scheduling + OOM wave-halving (train/engine.py).

    Brackets (algorithms.hyperband.bracket_plan) execute sequentially —
    each is one ``fused_sha`` sweep, so within a bracket the whole
    cohort trains/cuts on-device; between brackets there is one host
    transition. Bracket seeds match the host-side ``Hyperband``
    algorithm's (seed + 7919*b).

    ``cohort_fn(b, n) -> (unit[n, dim], n_model)`` / ``observe_fn(b,
    cohort, res)`` are the model hooks fused BOHB plugs in (sample each
    bracket's initial configurations; feed the results back). Plain
    Hyperband is the hookless case — ONE bracket loop serves both, so
    the seed scheme, per-bracket checkpoint layout, and best-pick can
    never drift between them.

    Returns the overall best plus a per-bracket summary.

    ``checkpoint_dir`` gives each bracket its own rung-checkpointed
    subdirectory (``bracket_0``, ...): a crash resumes inside the
    interrupted bracket, and brackets already complete replay instantly
    from their final snapshot.
    """
    import os

    from mpi_opt_tpu.algorithms.base import best_finite
    from mpi_opt_tpu.algorithms.hyperband import bracket_plan

    best = None
    brackets = []
    n_total = 0
    journal_totals = {"written": 0, "verified": 0}
    # wave observability aggregated across brackets (each bracket is its
    # own fused_sha with its own resolved cap — a small bracket that
    # fits resident contributes nothing): counters sum, the reported
    # wave_size is the largest settled cap any bracket ran under
    wave_totals = {
        "wave_size": 0,
        "n_waves": 0,
        "waves_run": 0,
        "oom_backoffs": 0,
        "staged_bytes": 0,
        "stage_transfer_s": 0.0,
        "stage_wait_s": 0.0,
        "stage_overlap_s": 0.0,
    }
    any_waves = False
    # the persisted-cohort identity: workload + bracket plan + seed
    # (everything that determines which search the cohorts belong to)
    tag = (
        f"{getattr(workload, 'name', type(workload).__name__)}"
        f"|R={max_budget}|eta={eta}|seed={seed}"
    )
    plan = bracket_plan(max_budget, eta)
    # one ledger spans the brackets: each fused_sha journals under its
    # bracket's GLOBAL offsets so the whole sweep reads as one
    # contiguous boundary sequence (ledger/fused.py). The offset math
    # mirrors fused_sha's own rung/size derivation exactly.
    boundary_off = trial_off = member_off = 0
    for b, (n, r) in enumerate(plan):
        if cohort_fn is None:
            cohort, n_model = None, None
        else:
            cohort, n_model = _bracket_cohort(checkpoint_dir, b, n, tag, cohort_fn)
        res = fused_sha(
            workload,
            n_trials=n,
            min_budget=r,
            max_budget=max_budget,
            eta=eta,
            seed=seed + 7919 * b,
            member_chunk=member_chunk,
            mesh=mesh,
            round_to=round_to,
            checkpoint_dir=(
                os.path.join(checkpoint_dir, f"bracket_{b}") if checkpoint_dir else None
            ),
            init_unit=cohort,
            ledger=ledger,
            boundary_offset=boundary_off,
            trial_offset=trial_off,
            member_offset=member_off,
            # model-based callers (BOHB) own their cohorts AND their
            # prior ingestion (ObsStore); only the hookless hyperband
            # seeds bracket cohorts with the prior best
            warm_obs=warm_obs if cohort_fn is None else None,
            wave_size=wave_size,
            oom_backoff=oom_backoff,
        )
        boundary_off += len(res["rung_budgets"])
        trial_off += sum(res["rung_sizes"])
        member_off += n
        if observe_fn is not None:
            observe_fn(b, cohort, res)
        n_total += n
        if res.get("journal"):
            journal_totals["written"] += res["journal"]["written"]
            journal_totals["verified"] += res["journal"]["verified"]
        summary = {
            "bracket": b,
            "n_trials": n,
            "start_budget": r,
            "rung_sizes": res["rung_sizes"],
            "rung_budgets": res["rung_budgets"],
            # .get: minimal bracket-result stubs (tests) and any cached
            # pre-upgrade result dicts simply report no tallies
            "member_failures": res.get("member_failures", []),
            "best_score": res["best_score"],
        }
        if cohort_fn is not None:
            summary["n_model_sampled"] = n_model
        if res.get("wave_size"):
            any_waves = True
            wave_totals["wave_size"] = max(wave_totals["wave_size"], res["wave_size"])
            for k in ("n_waves", "waves_run", "oom_backoffs", "staged_bytes"):
                wave_totals[k] += res[k]
            for k in ("stage_transfer_s", "stage_wait_s", "stage_overlap_s"):
                wave_totals[k] += res[k]
            summary["wave_size"] = res["wave_size"]
            summary["n_waves"] = res["n_waves"]
            summary["oom_backoffs"] = res["oom_backoffs"]
        brackets.append(summary)
        # bracket boundary: each bracket's final rung suppresses the
        # intra-sha drain (final=True there), so the between-bracket
        # check here is what lets a preemption land between brackets —
        # completed brackets replay instantly from their snapshots
        launch_boundary(
            f"hyperband bracket {b + 1}/{len(plan)}",
            final=b + 1 == len(plan),
            bracket=b + 1,
            of=len(plan),
        )
        # diverged brackets (non-finite best_score) never stick as the
        # overall winner — the ONE best-pick rule, shared with the host
        # path (see algorithms.base.best_finite); pairwise fold keeps
        # the first bracket when everything diverged
        if best is None:
            best = res
        else:
            best = best_finite([best, res], key=lambda r: r["best_score"])
    return {
        "best_score": best["best_score"],
        "best_params": best["best_params"],
        "brackets": brackets,
        # flattened across brackets in bracket order, so the CLI summary
        # can report one per-generation-shaped list for every fused algo
        "member_failures": [
            n for s in brackets for n in s["member_failures"]
        ],
        "n_trials": n_total,
        "journal": journal_totals if ledger is not None else None,
        **(wave_totals if any_waves else {}),
    }
