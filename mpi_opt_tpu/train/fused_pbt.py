"""Fully-fused on-device PBT: whole sweeps as one XLA program.

This is the performance thesis of the framework (BASELINE.json
north_star: PBT exploit/explore "become lax.top_k/psum over a device
mesh instead of MPI_Allgather"). The generic driver path (host PBT +
TPU backend) round-trips tiny score arrays once per generation; this
module removes even that: a ``lax.scan`` over generations where each
iteration trains the population (itself a scan of vmapped steps),
evaluates it, runs exploit/explore, and gathers winner states — all
inside a single jit. The host launches one computation and gets back
the final population + per-generation score curves.

Works unchanged on a sharded population: launch with a mesh-sharded
PopState (parallel/mesh.py) and XLA partitions the whole loop,
inserting the all_gathers for the ranking/gather steps over ICI.

Why fused beats the reference's architecture (and our own host loop):
- zero host↔device sync per generation (the reference pays an
  MPI_Allgather + Python decision per rank per generation);
- XLA overlaps the next generation's first steps with the previous
  exploit gather where dependencies allow;
- hyperparameters are data, so G generations of mutated schedules cost
  one compile.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from mpi_opt_tpu.health import shutdown
from mpi_opt_tpu.obs import memory, trace
from mpi_opt_tpu.ops.pbt import PBTConfig, pbt_exploit_explore, pbt_exploit_explore_mo
from mpi_opt_tpu.train.common import (
    eval_population_objectives,
    finite_winner,
    journal_boundary,
    journal_require_prefix,
    launch_boundary,
    make_fused_journal,
    momentum_dtype_str,
    oom_funnel,
    segment_flops_hint,
)
from mpi_opt_tpu.utils import profiling, resources
from mpi_opt_tpu.train.population import (
    OptHParams,
    PopState,
    PopulationTrainer,
    trainer_jit,
)

# the shared fault-tolerant wave executor (train/engine.py): wave
# scheduling, host-pool staging, OOM backoff, drain/heartbeat — this
# module supplies only PBT's boundary op (truncation exploit/explore).
# The private aliases preserve this module's historical seams: tests
# intercept ``fused_pbt._run_wave`` for crash/OOM drills.
from mpi_opt_tpu.train.engine import (
    WaveRunner,
    boundary_span,
    resolve_wave_size,
    _wave_train_program,  # noqa: F401  (re-exported test seam)
)
from mpi_opt_tpu.train.engine import balanced_split as _balanced_split
from mpi_opt_tpu.train.engine import engine_rollover as _engine_rollover  # noqa: F401
from mpi_opt_tpu.train.engine import run_wave as _run_wave
from mpi_opt_tpu.train.engine import wave_layout as _wave_layout
from mpi_opt_tpu.train.engine import writable as _writable


@trainer_jit(
    static_argnames=(
        "hparams_fn", "discrete_mask", "generations", "steps_per_gen", "cfg",
        "objectives",
    ),
    donate_argnames=("state", "unit"),
)
def run_fused_pbt(
    trainer: PopulationTrainer,
    state: PopState,
    unit: jax.Array,  # float32[P, d] initial hparams (unit cube)
    hparams_fn: Callable,  # unit matrix -> OptHParams (static, hashable)
    train_x: jax.Array = None,
    train_y: jax.Array = None,
    val_x: jax.Array = None,
    val_y: jax.Array = None,
    key: jax.Array = None,
    discrete_mask: tuple = (),
    generations: int = 10,
    steps_per_gen: int = 100,
    cfg: PBTConfig = PBTConfig(),
    objectives=None,  # static ObjectiveSpec: multi-objective exploit (ISSUE 17)
):
    """Returns (state, unit, key', best_curve[G], mean_curve[G],
    member_fail[G], final_scores[P], pre_scores[G, P], pre_units[G, P, d]).

    ``member_fail`` counts the PRE-exploit members whose eval came back
    non-finite each generation — the divergence the exploit step then
    masks by replacing losers with winners. Tallied in-scan (one int32
    per generation) so reporting it costs no extra fetch.

    ``pre_scores``/``pre_units`` are each generation's PRE-exploit
    member scores and the unit rows those members actually trained
    with — the per-member facts the fused ledger journals (one record
    per member per generation; ledger/fused.py). They ride the scan's
    stacked outputs, so collecting them costs no extra program.

    ``key'`` is the scan-carried RNG key after ``generations`` steps of
    the chain — feeding it into a following call continues the EXACT
    trajectory one longer call would have taken, which is what makes
    ``gen_chunk`` launch-splitting bit-identical to a single launch.

    ``objectives`` (a static, hashable ``ObjectiveSpec``) switches the
    generation boundary to multi-objective selection: each generation
    evaluates the full objective matrix on device
    (``eval_population_objectives``), the exploit ranks by Pareto
    score inside the same compiled scan (``pbt_exploit_explore_mo`` —
    no host round-trip is added to the hot path), and the scan's
    scalar outputs carry the spec-scalarized primary objective so
    every scalar consumer (curves, journaling, snapshots) works
    unchanged. The return grows two trailing outputs:
    ``pre_mo[G, P, m]`` (raw pre-exploit objective matrices — the
    ledger's ``scores`` vectors) and ``final_mo[P, m]`` (the final
    post-exploit population's objectives, for the winner pick /
    front summary). Scalar calls return the original 9-tuple.

    A member that counts its own work (``trainer.member.counters``)
    adds one last output to either: ``float32[G, len(counters)]``, each
    generation's mean over its member-steps, out of the train steps
    that made them (the ``train`` span's attributes).
    """
    if generations < 1:  # static arg: raises at trace time, not opaquely later
        raise ValueError(f"generations must be >= 1, got {generations}")
    disc = jnp.asarray(discrete_mask, dtype=bool)
    norm_bounds = (
        objectives.norm_bounds()
        if objectives is not None and objectives.has_bounds
        else None
    )

    def one_generation(carry, g):
        st, u, k = carry
        k, k_train, k_pbt = jax.random.split(k, 3)
        hp = hparams_fn(u)
        st, reported = trainer.train_segment(st, hp, train_x, train_y, k_train, steps_per_gen)
        counted = (jnp.mean(reported[1], axis=0),) if trainer.member.counters else ()
        if objectives is not None:
            mo = eval_population_objectives(
                trainer, st, val_x, val_y, objectives.names
            )
            scores = objectives.scalarize(mo)
            new_u, src_idx, _, _eff = pbt_exploit_explore_mo(
                k_pbt,
                u,
                objectives.normalize(mo),
                disc,
                cfg,
                norm_bounds=norm_bounds,
            )
            st = trainer.exploit_members(st, src_idx)
            # a non-finite value in ANY objective is the member failure
            n_fail = jnp.sum(~jnp.all(jnp.isfinite(mo), axis=-1)).astype(jnp.int32)
            return (st, new_u, k), (
                scores.max(), scores.mean(), n_fail, scores[src_idx],
                scores, u, mo, mo[src_idx],
            ) + counted
        scores = trainer.eval_population(st, val_x, val_y)
        new_u, src_idx, _ = pbt_exploit_explore(k_pbt, u, scores, disc, cfg)
        st = trainer.exploit_members(st, src_idx)
        # the post-exploit population's scores are exactly the gathered
        # pre-exploit scores (weights are copied verbatim, eval is
        # deterministic) — so no final re-eval is ever needed
        n_fail = jnp.sum(~jnp.isfinite(scores)).astype(jnp.int32)
        return (st, new_u, k), (
            scores.max(), scores.mean(), n_fail, scores[src_idx], scores, u,
        ) + counted

    if objectives is not None:
        (state, unit, key), (
            best, mean, fails, gen_scores, pre_scores, pre_units, pre_mo, gen_mo, *counted
        ) = jax.lax.scan(one_generation, (state, unit, key), jnp.arange(generations))
        return (
            state, unit, key, best, mean, fails, gen_scores[-1],
            pre_scores, pre_units, pre_mo, gen_mo[-1], *counted,
        )

    (state, unit, key), (best, mean, fails, gen_scores, pre_scores, pre_units, *counted) = (
        jax.lax.scan(one_generation, (state, unit, key), jnp.arange(generations))
    )
    return state, unit, key, best, mean, fails, gen_scores[-1], pre_scores, pre_units, *counted


@trainer_jit(
    static_argnames=("discrete_mask", "cfg"), donate_argnames=("state", "unit")
)
def finish_generation(
    trainer: PopulationTrainer,
    state: PopState,
    unit: jax.Array,
    key: jax.Array,  # the generation's PBT key
    val_x: jax.Array,
    val_y: jax.Array,
    discrete_mask: tuple = (),
    cfg: PBTConfig = PBTConfig(),
):
    """The generation-boundary program for step-chunked sweeps: eval the
    population, run exploit/explore, gather winner states — the tail of
    ``run_fused_pbt.one_generation`` without the training scan (which
    ran as separate ``train_segment`` launches). Returns
    (state, unit, best, mean, n_fail, post_exploit_scores, pre_scores,
    pre_unit) — the pre-exploit scores AND the unit matrix the
    generation trained with ride along for the fused ledger's
    per-member records, mirroring ``run_fused_pbt``'s stacked outputs
    (``unit`` is donated, so the caller must take the pre-exploit view
    from the OUTPUT, not its dead input reference)."""
    disc = jnp.asarray(discrete_mask, dtype=bool)
    scores = trainer.eval_population(state, val_x, val_y)
    new_u, src_idx, _ = pbt_exploit_explore(key, unit, scores, disc, cfg)
    state = trainer.exploit_members(state, src_idx)
    n_fail = jnp.sum(~jnp.isfinite(scores)).astype(jnp.int32)
    return (
        state, new_u, scores.max(), scores.mean(), n_fail, scores[src_idx],
        scores, unit,
    )


@functools.partial(jax.jit, static_argnames=("discrete_mask", "cfg"))
def _wave_exploit(
    key: jax.Array,
    unit: jax.Array,  # float32[P, d] — the FULL population's hparams
    scores: jax.Array,  # float32[P] — all waves' pre-exploit scores
    discrete_mask: tuple = (),
    cfg: PBTConfig = PBTConfig(),
):
    """Generation-boundary decision for the wave-scheduled path: exactly
    the tail of ``run_fused_pbt.one_generation`` minus the eval (already
    done per wave) and minus the device gather — the winner-weight copy
    is realized LAZILY by the next generation's stage-in indexing the
    host pool with ``src_idx`` (train/staging.py), so exploit over a
    host-staged population still operates on full-population scores.
    Returns (new_unit, src_idx, best, mean, n_fail, post_scores)."""
    disc = jnp.asarray(discrete_mask, dtype=bool)
    new_u, src_idx, _ = pbt_exploit_explore(key, unit, scores, disc, cfg)
    n_fail = jnp.sum(~jnp.isfinite(scores)).astype(jnp.int32)
    return new_u, src_idx, scores.max(), scores.mean(), n_fail, scores[src_idx]


def _host_state(pool: dict, perm) -> PopState:
    """The post-exploit population state of a wave-scheduled sweep,
    materialized on HOST (that is where a beyond-residency population
    lives): the winners' rows of the pool via the perm."""
    return PopState(
        params=jax.tree.map(lambda l: l[perm], pool["params"]),
        momentum=jax.tree.map(lambda l: l[perm], pool["momentum"]),
        step=pool["step"][perm],
    )


def _fused_pbt_waves(  # sweeplint: barrier(wave host loop: stages pools, gathers scores, exploits at generation boundaries)
    workload,
    trainer,
    space,
    train_x,
    train_y,
    val_x,
    val_y,
    population: int,
    generations: int,
    steps_per_gen: int,
    seed: int,
    cfg: PBTConfig,
    mesh,
    member_chunk: int,
    wave_size: int,
    checkpoint_dir,
    snapshot_every: int,
    snapshot_last: bool,
    ledger=None,
    warm_obs=None,
    oom_backoff: int = 0,
):
    """Wave-scheduled fused PBT: ``population > residency``.

    ``oom_backoff`` (ISSUE 13): on a device OOM during a generation's
    wave launches, halve the wave cap and RE-RUN the generation from
    its first wave, up to ``oom_backoff`` times — everything the re-run
    needs (pool_front, unit, perm, the generation's carried key) is
    still in host memory, reads of pool_front are non-destructive, and
    wave mode is bit-identical at ANY wave size, so backoff preserves
    result identity (tested). The settled-on cap is recorded in every
    snapshot's meta (``wave_size_run``) and adopted on resume — once a
    post-backoff snapshot lands, later resumes skip straight to the
    settled cap (a crash in the backoff-to-snapshot window re-learns
    the halving with a fresh budget; it converges, just not for free).

    Each generation trains ``ceil(P/W)`` resident waves of ~``W``
    members in sequence through the SAME compiled per-wave program
    (balanced split: at most two distinct wave sizes, so at most two
    compiles), staging cold members' params+momentum on host between
    waves, while exploit/explore at the generation boundary operates
    over the FULL population: scores are gathered across waves,
    truncation selection and perturbation run on all P members at once
    (``_wave_exploit``), and winners' weights reach the next
    generation's waves through the stage-in permutation.

    Semantics: bit-identical to resident mode for ANY wave size on the
    CPU backend (tested) — batch RNG is shared population-wide, member
    RNG windows the full split (``train_segment_window``), init keys
    slice the same ``split(k_init, P)``, and the exploit op sees the
    same (key, unit, scores) triple. On an accelerator the resident
    and the wave programs are two compilations that round differently,
    and this weakens to documented-equivalent, the ``step_chunk``
    standard (a v5e, pop=256 SmallCNN, PR 21: 6 of 256 first-generation
    scores differ by one validation row, and 6 members then take
    another exploit source; the best score and curve came out equal).

    Overlap: stage-out of wave k (device→host) runs on
    ``StagingEngine``'s background thread
    while the main thread dispatches wave k+1's stage-in + compute; the
    only hard barrier is ``drain()`` at the generation boundary, where
    the full score vector is needed. Device residency: at most two
    waves (one computing, one being fetched).

    Snapshots: generation-boundary on the ``snapshot_every`` cadence
    (post-exploit pool + perm + unit + key), plus BETWEEN-WAVES
    snapshots flushed by the graceful-shutdown drain at any wave
    boundary (front+back pools, partial scores, pre-generation key) —
    a preempted sweep resumes mid-generation without re-training
    completed waves.
    """
    import time

    import numpy as np

    from mpi_opt_tpu.parallel.mesh import fetch_global, place_pop
    from mpi_opt_tpu.train.common import HParamsFn
    from mpi_opt_tpu.train.staging import population_pool, write_rows
    from mpi_opt_tpu.utils.checkpoint import SweepCheckpointer

    # the REQUESTED cap is the sweep's config identity (stable across
    # resumes under the same flag); the EXECUTION cap (WaveRunner) may
    # shrink via OOM backoff, recorded per snapshot in meta (wave_size_run)
    req_wave_size = wave_size
    wave_lens, _, _ = _wave_layout(population, wave_size)
    disc = tuple(bool(b) for b in space.discrete_mask())
    hparams_fn = HParamsFn(space, workload)
    key = jax.random.key(seed)
    k_init, k_unit, k_run = jax.random.split(key, 3)
    # the SAME per-member init keys the resident program derives inside
    # init_population — gen-0 waves slice windows of this split
    member_keys = jax.random.split(k_init, population)

    best_list: list = []
    mean_list: list = []
    fail_list: list = []
    gen_walls: list = []
    start_gen = 0
    start_wave = 0
    scores_host = np.full((population,), np.nan, np.float32)
    post_scores = None
    pool_front = pool_back = None
    perm = None
    unit = None
    k_gen = None

    snap = None
    restored = None
    if checkpoint_dir is not None:
        import dataclasses

        snap = SweepCheckpointer(
            checkpoint_dir,
            {
                "workload": getattr(workload, "name", type(workload).__name__),
                "population": population,
                "generations": generations,
                "steps_per_gen": steps_per_gen,
                "seed": seed,
                "member_chunk": member_chunk,
                "cfg": dataclasses.asdict(cfg),
                "momentum_dtype": momentum_dtype_str(),
                # the wave split is part of the sweep's identity: the
                # snapshot payload is pool+perm shaped by it, and a
                # resident run must not silently resume a wave snapshot.
                # The REQUESTED cap, deliberately: an OOM backoff's
                # smaller execution cap lives in meta (wave_size_run),
                # so a resume under the same flag matches here and
                # adopts the settled cap below
                "wave_size": req_wave_size,
                "wave_lens": list(wave_lens),
            },
        )
        restored = snap.restore_wave_sweep()
        if restored is not None:
            sweep, meta = restored
            best_list = [float(v) for v in meta["best"]]
            mean_list = [float(v) for v in meta["mean"]]
            fail_list = [int(v) for v in meta["member_fail"]]
            gen_walls = [float(v) for v in meta["gen_walls"]]
            start_gen = int(meta["gen"])
            start_wave = int(meta["waves_done"])
            # adopt a prior attempt's OOM-settled cap: waves_done counts
            # waves of the split the snapshot was taken under, and
            # resuming at the requested size would re-OOM a generation
            # just to re-learn the answer
            run_ws = int(meta.get("wave_size_run", wave_size))
            if run_ws != wave_size:
                wave_size = run_ws
            pool_front = _writable(sweep["front"])
            perm = np.asarray(sweep["perm"])
            unit = jnp.asarray(sweep["unit"])
            restored_key = jax.random.wrap_key_data(jnp.asarray(sweep["key_data"]))
            if start_wave:
                # mid-generation: the saved key is the PRE-generation
                # carried key (k_train/k_pbt re-derive from it)
                k_gen = restored_key
                pool_back = _writable(sweep["back"])
                scores_host = np.array(sweep["scores"], np.float32)
            else:
                k_run = restored_key
                post_scores = np.asarray(sweep["scores"])
    journal = make_fused_journal(ledger, space)
    journal_require_prefix(journal, start_gen)
    with trace.span("setup", op="init_population", members=int(population)):
        if restored is None:
            unit = space.sample_unit(k_unit, population)
            if warm_obs:
                from mpi_opt_tpu.ledger.warmstart import best_observation

                bo = best_observation(warm_obs)
                if bo is not None:
                    # same sampler-family seeding as the resident path
                    unit = np.array(unit)
                    unit[0] = np.asarray(bo.unit, dtype=unit.dtype)
                    unit = jnp.asarray(unit)
            perm = np.arange(population)
            # the cold population's host residence; gen 0 fills it by
            # stage-out (members init on device per wave)
            pool_front = population_pool(trainer, train_x[:2], population)
        if pool_back is None:
            pool_back = population_pool(trainer, train_x[:2], population)
        if mesh is not None:
            unit = place_pop(unit, mesh)

    snapshot_every = max(1, snapshot_every)
    # the shared wave executor (train/engine.py) owns the StagingEngine,
    # the execution cap, and the OOM-backoff retry loop; the generation
    # loop below supplies only PBT's shapes (dispatch/payload/labels)
    # and boundary op
    runner = WaveRunner(population, wave_size, oom_backoff=oom_backoff)
    # per-generation FLOPs for the trace layer's achieved-TF/s (None
    # when tracing is off — the probe is never paid untraced)
    flops_gen = segment_flops_hint(workload, population, steps_per_gen)

    def _writer(off):
        def on_host(host):  # sweeplint: barrier(stage-out landing: writes fetched wave scores into the host pool)
            write_rows(pool_back, off, host["state"])
            w = len(host["scores"])
            scores_host[off : off + w] = np.asarray(host["scores"], np.float32)

        return on_host

    try:
        for g in range(start_gen, generations):
            t_gen = time.perf_counter()
            resumed_mid = g == start_gen and start_wave > 0
            gen_partial0 = 0.0
            if resumed_mid:
                # the interrupted generation's pre-crash elapsed time,
                # so its launch wall stays the launch's real cost
                gen_partial0 = float(restored[1].get("wall_partial", 0.0))
            else:
                k_gen = k_run
                scores_host[:] = np.nan
            # the carried-key chain matches run_fused_pbt.one_generation
            # exactly: next carry, train key, exploit key
            k_run, k_train, k_pbt = jax.random.split(k_gen, 3)

            def _dispatch(w, off, wl_, eng, g=g, k_train=k_train):
                # ``_run_wave`` resolved at call time (module global) so
                # the chaos drills' monkeypatch seam keeps working
                return _run_wave(
                    trainer,
                    pool_front,
                    perm[off : off + wl_],
                    off,
                    unit,
                    hparams_fn,
                    train_x,
                    train_y,
                    val_x,
                    val_y,
                    k_train,
                    steps_per_gen,
                    population,
                    mesh,
                    eng,
                    init_keys=member_keys[off : off + wl_] if g == 0 else None,
                    sample_x=train_x[:2],
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

            def _stage_label(w, nw, g=g):
                return f"pbt gen {g + 1}/{generations} wave {w + 1}/{nw}"

            def _boundary_kwargs(w, nw, g=g):
                return {"launch": g * nw + w + 1, "of": generations * nw}

            def _midgen_snapshot(w, nw, g=g):
                def save_midgen():  # sweeplint: barrier(between-waves drain snapshot: fetches partial state for the checkpoint)
                    runner.engine.drain()  # pools must hold every completed wave
                    # COPY the pools: orbax's save is async, and the live
                    # buffers are mutated in place by later waves' stage-out
                    # writers — handing them over uncopied can tear the
                    # snapshot (same contract as the resident path's
                    # host-fetch-before-save)
                    snap.save(
                        g * nw + w + 1,
                        sweep={
                            "front": jax.tree.map(np.array, pool_front),
                            "back": jax.tree.map(np.array, pool_back),
                            "perm": np.asarray(perm),
                            "unit": fetch_global(unit),
                            "key_data": np.asarray(jax.random.key_data(k_gen)),
                            "scores": scores_host.copy(),
                        },
                        meta_extra={
                            "gen": g,
                            "waves_done": w + 1,
                            # a mid-generation snapshot completes no
                            # boundary: only g generations are journaled
                            "boundaries_done": g,
                            # the OOM-settled execution cap: waves_done
                            # counts waves of THIS split, and a resume
                            # must adopt it rather than re-OOM
                            "wave_size_run": runner.wave_size,
                            "best": best_list,
                            "mean": mean_list,
                            "member_fail": fail_list,
                            "gen_walls": gen_walls,
                            "wall_partial": time.perf_counter() - t_gen + gen_partial0,
                        },
                    )

                return save_midgen

            wave_scores = runner.run_interval(
                n=population,
                run_wave_fn=_dispatch,
                payload_fn=_payload,
                writer_fn=_writer,
                scores_host=scores_host,
                stage_label=_stage_label,
                boundary_kwargs=_boundary_kwargs,
                midpoint_snapshot=None if snap is None else _midgen_snapshot,
                span_attrs=lambda nw, g=g: {"launch": g + 1, "gens": 1, "waves": nw},
                flops=flops_gen,
                start_wave=start_wave if resumed_mid else 0,
                notify_fields=(("gen", g + 1),),
            )
            # the settled layout this generation actually ran under (an
            # absorbed OOM halved it): boundary numbering + snapshot meta
            n_waves = runner.n_waves
            # journal this generation's members (pre-exploit scores +
            # the units they trained with) BEFORE the boundary snapshot;
            # a resumed generation verifies instead of re-writing
            journal_boundary(
                journal,
                g,
                np.arange(population),
                fetch_global(unit),
                scores_host,
                step=(g + 1) * steps_per_gen,
            )
            scores_dev = jnp.concatenate([jnp.asarray(s) for s in wave_scores])
            with boundary_span("exploit", gen=g + 1):
                new_unit, src_idx, best, mean, n_fail, post = _wave_exploit(
                    k_pbt, unit, scores_dev, discrete_mask=disc, cfg=cfg
                )
                # the host conversions below ARE the exploit's completion
                # barrier — inside the span so its duration is real
                best_list.append(float(best))
                mean_list.append(float(mean))
                fail_list.append(int(n_fail))
                unit = new_unit
                perm = np.asarray(src_idx)
                post_scores = np.asarray(post)
            pool_front, pool_back = pool_back, pool_front
            gen_walls.append(time.perf_counter() - t_gen + gen_partial0)
            is_last = g + 1 == generations
            due = (g + 1) % snapshot_every == 0

            def save_boundary(g=g):  # sweeplint: barrier(generation-boundary snapshot: fetches pool + perm for the checkpoint)
                # COPY the pool: the async orbax write may still be in
                # flight when this buffer (pool_back after the swap) is
                # mutated in place by a LATER generation's stage-out
                # writers — an uncopied save can mix generations' rows
                # into one silently corrupt snapshot
                snap.save(
                    (g + 1) * n_waves,
                    sweep={
                        "front": jax.tree.map(np.array, pool_front),
                        "perm": np.asarray(perm),
                        "unit": fetch_global(unit),
                        "key_data": np.asarray(jax.random.key_data(k_run)),
                        "scores": post_scores,
                    },
                    meta_extra={
                        "gen": g + 1,
                        "waves_done": 0,
                        "boundaries_done": g + 1,
                        # the OOM-settled execution cap (adopted on resume)
                        "wave_size_run": runner.wave_size,
                        "best": best_list,
                        "mean": mean_list,
                        "member_fail": fail_list,
                        "gen_walls": gen_walls,
                    },
                )

            saved = False
            if snap is not None and ((due and not is_last) or (is_last and snapshot_last)):
                save_boundary()
                saved = True
            launch_boundary(
                f"pbt gen {g + 1}/{generations} wave {n_waves}/{n_waves}",
                final=is_last,
                snapshot=None if (snap is None or saved) else save_boundary,
                # a host copy of the whole pool: only for an observer
                state=_host_state(pool_front, perm)
                if shutdown.get_boundary_observer() is not None
                else None,
                launch=(g + 1) * n_waves,
                of=generations * n_waves,
            )
    finally:
        runner.close()
        if snap is not None:
            snap.close()

    best_i, diverged = finite_winner(post_scores)
    np_unit = fetch_global(unit)
    state = _host_state(pool_front, perm)
    return {
        "best_score": float("nan") if diverged else float(post_scores[best_i]),
        "best_params": None if diverged else space.materialize_row(np_unit[best_i]),
        "diverged": diverged,
        "best_curve": np.asarray(best_list, dtype=np.float32),
        "mean_curve": np.asarray(mean_list, dtype=np.float32),
        "member_failures": [int(v) for v in fail_list],
        "state": state,
        "unit": np_unit,
        "launch_gens": [1] * generations,
        "launch_walls": [float(v) for v in gen_walls],
        # wave-scheduling observability (acceptance: staging must be
        # visible, not inferred) from the shared runner: the settled
        # EXECUTION split (after an OOM backoff it differs from the
        # requested cap, which is the point), halvings absorbed, bytes
        # moved, and how much transfer time the double buffer hid
        # behind compute
        **runner.result_extras(),
        "journal": None
        if journal is None
        else {"written": journal.written, "verified": journal.verified},
    }


def _run_stepped_generation(
    trainer,
    state,
    unit,
    hparams_fn,
    train_x,
    train_y,
    val_x,
    val_y,
    key,
    disc,
    steps: int,
    step_chunk: int,
    cfg: PBTConfig,
):
    """One PBT generation as ceil(steps/step_chunk) train launches plus
    one boundary launch — the sub-generation analogue of gen_chunk, for
    populations whose single-generation program exceeds a platform's
    execution window (PERF_NOTES.md: pop=512 x 100 steps ~fills this
    container's 60 s kill limit). Deterministic given (seed, step_chunk)
    but NOT bit-identical to the unchunked scan: sub-segment RNG keys
    are derived by folding the generation's train key, where the fused
    scan threads one key through all ``steps``. Return shapes match one
    ``run_fused_pbt(generations=1)`` launch.
    """
    from mpi_opt_tpu.health import heartbeat

    key, k_train, k_pbt = jax.random.split(key, 3)
    hp = hparams_fn(unit)
    sub_lens = _balanced_split(steps, step_chunk)
    for i, s in enumerate(sub_lens):
        state, _ = trainer.train_segment(
            state, hp, train_x, train_y, jax.random.fold_in(k_train, i), s
        )
        # sub-launch liveness (ROADMAP follow-up): each train sub-segment
        # beats, so launch.py's --stall-timeout can be sized to one
        # step_chunk instead of a whole generation's train_segment scan
        heartbeat.beat(stage=f"pbt train sub-launch {i + 1}/{len(sub_lens)}")
    with boundary_span("exploit"):
        state, unit, best, mean, n_fail, gen_scores, pre_scores, pre_unit = (
            finish_generation(
                trainer, state, unit, k_pbt, val_x, val_y, discrete_mask=disc, cfg=cfg
            )
        )
    return (
        state, unit, key, best[None], mean[None], n_fail[None], gen_scores,
        pre_scores[None], pre_unit[None],
    )


def fused_pbt(  # sweeplint: barrier(resident host loop: launch boundaries, exploit, journal, snapshot)
    workload,
    population: int,
    generations: int,
    steps_per_gen: int,
    seed: int = 0,
    cfg: PBTConfig = PBTConfig(),
    mesh=None,
    member_chunk: int = 0,
    gen_chunk: int = 0,
    step_chunk: int = 0,
    wave_size=0,
    checkpoint_dir: str = None,
    snapshot_every: int = 1,
    snapshot_last: bool = True,
    ledger=None,
    warm_obs=None,
    oom_backoff: int = 2,
    objectives=None,
):
    """Convenience wrapper: run a whole PBT sweep for a vision-style
    workload; optionally sharded over a ``('pop','data')`` mesh.

    ``objectives`` (an ``ObjectiveSpec``, ISSUE 17) runs the sweep
    multi-objective: the exploit selects by Pareto rank + crowding
    inside the compiled generation scan, records journal raw objective
    vectors beside their scalarized score, and the result carries the
    final population's Pareto front + hypervolume with a
    constraint-aware winner (typed ``selection``: feasible /
    least_violation / diverged). Resident + ``gen_chunk`` only — wave
    scheduling and ``step_chunk`` refuse (their boundary programs are
    scalar), and the objective names must come from the workload's
    ``objective_metrics()``.

    ``oom_backoff`` (wave mode; ISSUE 13): budget of automatic
    wave-size halvings on a device OOM — each absorbed OOM re-runs its
    generation at half the wave, bit-identically (0 disables; resident
    mode and an exhausted budget raise typed ``DeviceOOM``, which the
    CLI maps to the classified exit 74). With a MEASURED device budget
    (obs/memory.py) an explicit cap above the residency estimate is
    also pre-clamped before the first launch (``wave_resized``), so the
    common case never OOMs at all.

    ``ledger`` (an open ``SweepLedger`` whose fused header the CLI has
    already committed) journals one record per member per generation —
    pre-exploit score + the unit the member trained with — BEFORE that
    generation's snapshot saves; on resume, already-journaled
    generations are verified instead of re-written (ledger/fused.py).
    ``warm_obs`` (prior-ledger ``Observation``s, cross-mode) seeds the
    initial population's row 0 with the prior best point — the
    sampler-family warm-start semantic, matching driver random/ASHA.

    Returns a result dict with the best member's hparams and curves.
    (For FLOPs/MFU accounting of a sweep, call
    ``utils.flops.population_sweep_flops`` OUTSIDE any timed window —
    it lowers tiny probe programs, which must not count against a
    measurement; see bench.py.)

    ``gen_chunk`` splits the sweep into ceil(G/gen_chunk) launches
    (0 = whole sweep in one launch), sized near-equally so at most TWO
    distinct launch lengths exist — i.e. at most two compiled programs,
    exactly one when gen_chunk divides G. The population and the
    scan-carried RNG key thread through launches on-device, so a
    chunked sweep is BIT-IDENTICAL to a single launch (tested) and the
    steady-state cost is ~ms of dispatch per chunk. This exists because
    some environments bound single-program execution time (the previous
    installation did, at ~60 s; whether this one does is ROADMAP D4's
    measurement), because per-launch curves, journal records and
    snapshots need a launch boundary to happen at, and because big-G
    scans compile slower for no runtime benefit: generations are
    identical program text.

    ``checkpoint_dir`` makes the sweep crash-recoverable (SURVEY.md §5
    failure model): after every ``snapshot_every`` completed launches the
    carried (state, unit, key) is fetched to host and orbax-saved with
    the sweep config + curves. A fresh call with the same arguments and
    directory resumes at the last snapshot and — because the RNG key is
    part of the snapshot — finishes with the IDENTICAL result the
    uninterrupted sweep would have produced (tested). A checkpoint
    whose recorded config mismatches the call's raises ValueError.
    Host-fetching before the async save (rather than saving device
    buffers) is deliberate: the next launch donates the state buffers,
    which would invalidate them under orbax's background write.

    ``step_chunk`` splits each GENERATION's training into
    ceil(steps_per_gen/step_chunk) launches plus a boundary launch
    (eval + exploit) — the sub-generation analogue of ``gen_chunk``,
    needed when even ONE generation's program exceeds a platform's
    execution window (see ``gen_chunk``). Snapshots
    stay generation-granular. Unlike gen_chunk it is deterministic but
    NOT bit-identical to the unchunked sweep (sub-segment RNG keys are
    folded, not threaded), so it is recorded in the checkpoint config
    and a resume under a different step_chunk is refused. Mutually
    exclusive with gen_chunk > 1.

    ``snapshot_last=False`` skips the unconditional final-launch save.
    The final snapshot is what makes a completed sweep re-runnable
    without recompute (tested), but a caller that consumes the returned
    result immediately gets nothing from it — and a pop=64 ResNet
    snapshot host-fetches and writes ~5.7 GB, so benches turn it off.
    """
    import numpy as np

    from mpi_opt_tpu.parallel.mesh import fetch_global, shard_popstate
    from mpi_opt_tpu.train.common import workload_arrays

    if generations < 1:  # before any data/device work
        raise ValueError(f"generations must be >= 1, got {generations}")
    if step_chunk > 0 and gen_chunk > 1:
        raise ValueError(
            "step_chunk splits within generations; combining it with "
            f"gen_chunk={gen_chunk} (grouping whole generations) is ambiguous"
        )
    if objectives is not None:
        if step_chunk > 0:
            raise ValueError(
                "step_chunk is not supported with multi-objective sweeps "
                "(the sub-segment boundary program is scalar); use gen_chunk"
            )
        if wave_size:
            raise ValueError(
                "wave scheduling is not supported with multi-objective "
                "sweeps yet; run resident (wave_size=0) or shard the "
                "population over a mesh"
            )
        supported = tuple(workload.objective_metrics())
        missing = [n for n in objectives.names if n not in supported]
        if missing:
            raise ValueError(
                f"workload {getattr(workload, 'name', '?')!r} cannot "
                f"evaluate objectives {missing}; supported: {supported}"
            )
    trainer, space, train_x, train_y, val_x, val_y = workload_arrays(
        workload, member_chunk, mesh
    )
    # wave scheduling (population > residency): resolve the cap through
    # the shared engine door (``auto`` estimation, explicit pre-clamp,
    # multi-process refusal — train/engine.py), then hand off to the
    # host-staged driver. A cap at or above the population means
    # everything fits — resident mode, the bit-identical baseline.
    if wave_size:
        wave_size = resolve_wave_size(
            trainer,
            train_x[:2],
            population,
            wave_size=wave_size,
            mesh=mesh,
            oom_backoff=oom_backoff,
        )
        if 0 < wave_size < population:
            if step_chunk > 0 or gen_chunk > 1:
                raise ValueError(
                    "wave_size schedules whole generations as resident "
                    "waves; combining it with gen_chunk/step_chunk launch "
                    "splitting is ambiguous"
                )
            return _fused_pbt_waves(
                workload,
                trainer,
                space,
                train_x,
                train_y,
                val_x,
                val_y,
                population,
                generations,
                steps_per_gen,
                seed,
                cfg,
                mesh,
                member_chunk,
                wave_size,
                checkpoint_dir,
                snapshot_every,
                snapshot_last,
                ledger,
                warm_obs,
                oom_backoff=oom_backoff,
            )
    key = jax.random.key(seed)
    k_init, k_unit, k_run = jax.random.split(key, 3)

    disc = tuple(bool(b) for b in space.discrete_mask())
    if step_chunk > 0:
        gen_chunk = 1  # every launch is (part of) exactly one generation
    g_chunk = generations if gen_chunk <= 0 else min(gen_chunk, generations)
    # balanced split (e.g. G=3, chunk=2 -> [2, 1]; G=7, chunk=3 ->
    # [3, 2, 2]): a non-dividing chunk costs one extra compile, never more
    launch_lens = _balanced_split(generations, g_chunk)
    n_launches = len(launch_lens)

    # restore BEFORE initializing: a resumed sweep must not pay (or
    # transiently hold the memory of) a full-population init it discards
    snap = None
    restored = None
    start_launch = 0
    best_parts, mean_parts = [], []
    fail_parts: list = []  # per-gen diverged-member counts per launch
    fails_complete = True  # False when resuming a pre-tally snapshot
    launch_walls: list = []  # seconds per completed launch (excl. snapshot saves)
    walls_complete = True  # False when resuming a pre-duration-recording snapshot
    scores = None
    # final [P, m] raw objective matrix (MO only); None until a launch of
    # THIS process completes — a resume that starts past the last launch
    # leaves it None and the Pareto summary falls back to the ledger
    np_final_mo = None
    if checkpoint_dir is not None:
        import dataclasses

        from mpi_opt_tpu.utils.checkpoint import SweepCheckpointer

        ck_config = {
            "workload": getattr(workload, "name", type(workload).__name__),
            "population": population,
            "generations": generations,
            "steps_per_gen": steps_per_gen,
            "seed": seed,
            "launch_lens": launch_lens,
            "member_chunk": member_chunk,
            # PBT knobs change exploit/explore behavior: resuming under
            # a different cfg would not be the continuation we promise
            "cfg": dataclasses.asdict(cfg),
            # step_chunk changes the RNG derivation (folded sub-segment
            # keys), i.e. the trajectory itself — not just the launch
            # split the way gen_chunk does
            "step_chunk": step_chunk,
            # the momentum STORAGE dtype is part of the carried state's
            # structure: resuming a bf16-momentum snapshot into an f32
            # trainer would crash in the scan carry (or silently change
            # numerics) instead of refusing cleanly here
            "momentum_dtype": momentum_dtype_str(),
            # resident mode is wave_size=0; a wave-scheduled snapshot
            # (different payload: host pools + perm) must be refused
            # here, not crash in PopState reconstruction
            "wave_size": 0,
        }
        if objectives is not None:
            # objective identity is part of the trajectory (selection
            # pressure differs per spec); scalar sweeps never write the
            # key, so every pre-existing snapshot still resumes
            ck_config["objectives"] = objectives.spec()
        snap = SweepCheckpointer(checkpoint_dir, ck_config)
        restored = snap.restore_population_sweep()
        if restored is not None:
            state, unit, k_run, scores, meta = restored
            best_parts = [np.asarray(v, dtype=np.float32) for v in meta["best"]]
            mean_parts = [np.asarray(v, dtype=np.float32) for v in meta["mean"]]
            start_launch = int(meta["launches_done"])
            # per-launch durations (not cumulative timestamps): they stay
            # meaningful across a crash/resume, where the sweep's wall
            # clock is discontinuous but each launch's cost is real. A
            # snapshot from before durations were recorded has none for
            # its completed launches; mark the set incomplete rather
            # than inventing values (the result then reports
            # launch_walls=None and consumers fall back to whole-sweep
            # prorating)
            if "launch_walls" in meta:
                launch_walls = [float(w) for w in meta["launch_walls"]]
            else:
                walls_complete = False
            # same pre-upgrade rule as launch_walls: a snapshot written
            # before member-failure tallies existed cannot supply the
            # completed launches' counts — report None, never invent
            if "member_fail" in meta:
                fail_parts = [np.asarray(v, dtype=np.int32) for v in meta["member_fail"]]
            else:
                fails_complete = False
    journal = make_fused_journal(ledger, space)
    # resume gate: every generation the snapshot records complete must
    # already be journaled (journal-before-snapshot ordering); the
    # re-trained generations past the snapshot verify against their
    # records instead of re-writing
    journal_require_prefix(journal, sum(launch_lens[:start_launch]))
    with trace.span("setup", op="init_population", members=int(population)):
        if restored is None:
            unit = space.sample_unit(k_unit, population)
            if warm_obs:
                from mpi_opt_tpu.ledger.warmstart import best_observation

                bo = best_observation(warm_obs)
                if bo is not None:
                    # sampler-family warm start: one population row starts
                    # at the prior sweep's best point; PBT's exploit/explore
                    # spreads it if it earns its keep
                    unit = np.array(unit)
                    unit[0] = np.asarray(bo.unit, dtype=unit.dtype)
                    unit = jax.numpy.asarray(unit)
            state = trainer.init_population(k_init, train_x[:2], population)
        if mesh is not None:
            from mpi_opt_tpu.parallel.mesh import place_pop

            # datasets were already replicated over the mesh by workload_arrays
            state = shard_popstate(state, mesh)
            unit = place_pop(unit, mesh)

    # hparams_fn must be hashable-static; space comes from the per-
    # workload cache above so its identity is stable across calls
    from mpi_opt_tpu.train.common import HParamsFn

    hparams_fn = HParamsFn(space, workload)

    snapshot_every = max(1, snapshot_every)
    import time

    # per-generation FLOPs for the trace layer's achieved-TF/s spans
    # (None when tracing is off — the probe is never paid untraced)
    flops_gen = segment_flops_hint(workload, population, steps_per_gen)
    try:
        for i in range(start_launch, n_launches):
            profiling.launch_tick()
            t_launch = time.perf_counter()
            # the launch's train span covers dispatch AND the curve
            # fetches (the launch completion barrier), so dur_s is the
            # launch's real wall and flops/dur_s is achieved TF/s.
            # Resident mode has no wave to halve: the funnel's DeviceOOM
            # propagates to the CLI's classified exit (74) instead of an
            # unclassified traceback launch.py would burn retries on
            with oom_funnel(), trace.span(
                "train", launch=i + 1, gens=launch_lens[i]
            ) as _sp:
                if objectives is not None:
                    # mark MO launches in the trace (registered span
                    # attr); selection still runs inside this same
                    # program — no extra host-sync span appears
                    _sp["objectives"] = ",".join(objectives.names)
                # chaos seam (inject_oom): one guarded launch ordinal; a
                # synthetic RESOURCE_EXHAUSTED here classifies exactly
                # like a real warmup OOM (the staging.py docstring's
                # pop=1024 death shape) — typed via the funnel above
                resources.launch_fault("launch")
                counted = ()  # the members' own counters (one-program launches)
                if step_chunk > 0:
                    # one generation as k sub-segment launches + a boundary
                    # launch; the carried key advances exactly once per gen
                    state, unit, k_run, best, mean, fails, final_scores, pre_s, pre_u = _run_stepped_generation(
                        trainer,
                        state,
                        unit,
                        hparams_fn,
                        train_x,
                        train_y,
                        val_x,
                        val_y,
                        k_run,
                        disc,
                        steps_per_gen,
                        step_chunk,
                        cfg,
                    )
                elif objectives is not None:
                    # the MO program journals the raw objective matrix per
                    # generation besides the scalarized curve; selection
                    # already happened on-device via pareto_score
                    state, unit, k_run, best, mean, fails, final_scores, pre_s, pre_u, pre_mo, final_mo, *counted = run_fused_pbt(
                        trainer,
                        state,
                        unit,
                        hparams_fn,
                        train_x=train_x,
                        train_y=train_y,
                        val_x=val_x,
                        val_y=val_y,
                        key=k_run,
                        discrete_mask=disc,
                        generations=launch_lens[i],
                        steps_per_gen=steps_per_gen,
                        cfg=cfg,
                        objectives=objectives,
                    )
                else:
                    # k_run is the scan-carried key returned by the previous
                    # launch: the chain continues exactly as one longer scan
                    # would
                    state, unit, k_run, best, mean, fails, final_scores, pre_s, pre_u, *counted = run_fused_pbt(
                        trainer,
                        state,
                        unit,
                        hparams_fn,
                        train_x=train_x,
                        train_y=train_y,
                        val_x=val_x,
                        val_y=val_y,
                        key=k_run,
                        discrete_mask=disc,
                        generations=launch_lens[i],
                        steps_per_gen=steps_per_gen,
                        cfg=cfg,
                    )
                # curves to host eagerly: they are tiny, and a later crash
                # must not lose completed launches' history (fetch_global:
                # under multi-process SPMD these are global arrays)
                best_parts.append(fetch_global(best))
                mean_parts.append(fetch_global(mean))
                fail_parts.append(fetch_global(fails))
                scores = fetch_global(final_scores)
                if objectives is not None:
                    np_final_mo = fetch_global(final_mo)
                # flops only after the fetch barrier completed: a launch
                # that raised mid-span emits its partial duration
                # WITHOUT the attr (no inflated TF/s from partial work)
                if flops_gen:
                    _sp["flops"] = flops_gen * launch_lens[i]
                # what the members counted of their own work in this
                # launch's train steps
                if counted:
                    means = fetch_global(counted[0]).mean(axis=0)
                    _sp.update(zip(trainer.member.counters, map(float, means)))
                # post-barrier device-memory watermark (obs/memory.py):
                # resident population + activations just peaked
                memory.note(_sp)
            # the fetches above are the launch's completion barrier: the
            # curves are outputs of the launch's one program, so having
            # them on the host means it ran to its end — no separate
            # block_until_ready is needed (the two agree on the chip to
            # well under a millisecond, CHANGES.md PR 21). The duration
            # is measured AFTER them and BEFORE any snapshot save
            launch_walls.append(time.perf_counter() - t_launch)
            if journal is not None:
                # journal this launch's generations BEFORE its snapshot
                # (the boundary ordering contract); re-trained
                # generations of a resume verify instead of re-writing
                np_pre_s = fetch_global(pre_s)
                np_pre_u = fetch_global(pre_u)
                np_pre_mo = (
                    fetch_global(pre_mo) if objectives is not None else None
                )
                gens_before = sum(launch_lens[:i])
                for j in range(launch_lens[i]):
                    g = gens_before + j
                    journal_boundary(
                        journal,
                        g,
                        np.arange(population),
                        np_pre_u[j],
                        np_pre_s[j],
                        step=(g + 1) * steps_per_gen,
                        scores_mo=None if np_pre_mo is None else np_pre_mo[j],
                    )
            is_last = i + 1 == n_launches
            due = (i + 1) % snapshot_every == 0

            def save_now(i=i):
                meta_extra = {
                    "launches_done": i + 1,
                    # the ledger cross-check unit (fsck, resume gate):
                    # generations complete at this snapshot
                    "boundaries_done": sum(launch_lens[: i + 1]),
                    "best": [v.tolist() for v in best_parts],
                    "mean": [v.tolist() for v in mean_parts],
                }
                if fails_complete:
                    # an incomplete set must stay absent (see launch_walls)
                    meta_extra["member_fail"] = [v.tolist() for v in fail_parts]
                if walls_complete:
                    # an incomplete set must stay absent: writing the
                    # post-resume tail alone would misalign the NEXT
                    # resume's restore
                    meta_extra["launch_walls"] = [float(w) for w in launch_walls]
                snap.save_population_sweep(
                    i + 1, state, unit, k_run, scores, meta_extra=meta_extra
                )

            # save when a mid-sweep save comes due, or at the final
            # launch when the caller wants the completed-sweep snapshot
            saved = False
            if snap is not None and ((due and not is_last) or (is_last and snapshot_last)):
                save_now()
                saved = True
            # heartbeat + graceful-shutdown drain: a preemption flushes
            # an off-cadence snapshot (if checkpointing and the cadence
            # save didn't just run) so --resume loses no launches
            launch_boundary(
                f"pbt launch {i + 1}/{n_launches}",
                final=is_last,
                snapshot=None if (snap is None or saved) else save_now,
                state=state,
                launch=i + 1,
                of=n_launches,
            )
    finally:
        if snap is not None:
            snap.close()
    best = np.concatenate(best_parts)
    mean = np.concatenate(mean_parts)
    # a diverged member (NaN, or +/-inf from an exploded loss) must not
    # hijack the winner via argmax's first-NaN behavior — shared rule:
    # train.common.finite_winner; an all-diverged population reports
    # best_params=None with diverged=True
    best_i, diverged = finite_winner(scores)
    np_unit = fetch_global(unit)
    pareto = None
    if objectives is not None and np_final_mo is not None:
        from mpi_opt_tpu.objectives import (
            hypervolume,
            pareto_front_mask,
            select_best,
        )

        # constraint-aware winner override: "best" under objectives is
        # the best FEASIBLE member (typed degradation to the
        # least-violating one when none is feasible — never a crash)
        sel = select_best(np_final_mo, objectives)
        if sel["index"] is None:
            best_i, diverged = 0, True
        else:
            best_i, diverged = int(sel["index"]), False
        norm = objectives.normalize(np_final_mo)
        mask = pareto_front_mask(norm)
        front_members = [int(i) for i in np.flatnonzero(mask)]
        pareto = {
            "front_size": len(front_members),
            "front_members": front_members,
            "front_scores": [
                [float(v) for v in np_final_mo[i]] for i in front_members
            ],
            "hypervolume": float(hypervolume(norm[mask])) if front_members else 0.0,
            "selection": sel["kind"],
            "violation": sel["violation"],
        }
    return {
        # diverged normalizes to NaN (not a raw +/-inf row) so library
        # callers can detect it uniformly across fused SHA/PBT/TPE
        "best_score": float("nan") if diverged else float(scores[best_i]),
        "best_params": None if diverged else space.materialize_row(np_unit[best_i]),
        "diverged": diverged,
        "best_curve": np.asarray(best),
        "mean_curve": np.asarray(mean),
        # per-generation diverged-member tallies (ROADMAP open item):
        # how many members each exploit step silently replaced for
        # non-finite scores. None when a pre-upgrade snapshot left the
        # completed launches' counts unknown
        "member_failures": (
            [int(v) for v in np.concatenate(fail_parts)] if fails_complete else None
        ),
        "state": state,
        "unit": np_unit,
        # measured per-launch durations + generation split, for
        # launch-granular wall-to-target (utils.metrics); on a resumed
        # sweep, pre-crash launches' durations come from the snapshot.
        # None when a pre-upgrade snapshot left earlier durations
        # unknown — callers fall back to wall_to_target
        "launch_gens": launch_lens,
        "launch_walls": [float(w) for w in launch_walls] if walls_complete else None,
        # ledger observability: how many member records this run
        # appended vs re-verified on resume (None = no ledger active)
        "journal": None
        if journal is None
        else {"written": journal.written, "verified": journal.verified},
        # multi-objective extras (ISSUE 17): the final population's
        # non-dominated front + hypervolume and how the winner was
        # selected (feasible / least_violation / diverged). None on
        # scalar sweeps, and on a resume that restarted past the final
        # launch (the final objective matrix lives in the ledger then —
        # ``report`` recomputes the front from journaled vectors)
        "objectives": None if objectives is None else list(objectives.names),
        "pareto": pareto,
    }
