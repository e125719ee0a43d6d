"""Fused generational TPE: suggest → train → report without the host.

The driver path (algorithms/tpe.py + the TPU backend) already runs the
vectorized acquisition kernel on-device, but observations round-trip
through the host trial ledger between batches. Here the ring buffer of
observations IS device state: each generation is one XLA program that
draws a batch of suggestions from the buffer (ops.tpe.tpe_suggest, with
diversified batched top-k), initializes that many FRESH members, trains
them for the trial budget, evaluates, and writes (units, scores) back
into the buffer in place. The host sees one tiny per-generation fetch
(the generation's scores, for the progress curve) — the config-4
"surrogate-model sweep" with the surrogate fully resident on-chip.

Unlike PBT/SHA there is no population carried between generations —
every trial trains from scratch (TPE semantics) — so the recovery
snapshot is just the buffer + RNG key, making crash recovery
(``checkpoint_dir``) nearly free at generation granularity.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from mpi_opt_tpu.obs import memory, trace
from mpi_opt_tpu.ops.tpe import TPEConfig, tpe_suggest
from mpi_opt_tpu.train.common import (
    finite_winner,
    journal_boundary,
    journal_require_prefix,
    launch_boundary,
    make_fused_journal,
    momentum_dtype_str,
    segment_flops_hint,
    workload_arrays,
)
from mpi_opt_tpu.train.engine import (
    WaveRunner,
    boundary_span,
    resolve_wave_size,
)
from mpi_opt_tpu.train.engine import run_wave as _run_wave  # chaos-drill seam
from mpi_opt_tpu.train.population import trainer_jit
from mpi_opt_tpu.utils import profiling


@trainer_jit(
    static_argnames=("hparams_fn", "n_suggest", "budget", "cfg"),
    donate_argnames=("obs_unit", "obs_scores", "valid"),
)
def tpe_generation(
    trainer,
    obs_unit,  # float32[M, d] ring buffer (donated, updated in place)
    obs_scores,  # float32[M]
    valid,  # bool[M]
    hparams_fn,
    train_x,
    train_y,
    val_x,
    val_y,
    key,
    write_pos,  # int32[] — first buffer row this generation writes
    n_suggest: int,
    budget: int,
    cfg: TPEConfig,
):
    """One fused generation. Returns (obs_unit, obs_scores, valid,
    key', gen_scores[n_suggest], gen_units[n_suggest, d])."""
    from mpi_opt_tpu.parallel.mesh import constrain_pop

    key, k_sug, k_init, k_train = jax.random.split(key, 4)
    sugg, _ = tpe_suggest(k_sug, obs_unit, obs_scores, valid, n_suggest, cfg)
    # the generation's cohort is born inside this program: constrain it
    # over 'pop' so training shards instead of inheriting the (replicated)
    # buffer layout. trainer.mesh is static, so this traces to a no-op
    # without a mesh.
    state = constrain_pop(
        trainer.init_population(k_init, train_x[:2], n_suggest), trainer.mesh
    )
    hp = hparams_fn(sugg)
    state, _ = trainer.train_segment(state, hp, train_x, train_y, k_train, budget)
    scores = trainer.eval_population(state, val_x, val_y)
    obs_unit = jax.lax.dynamic_update_slice(obs_unit, sugg, (write_pos, 0))
    obs_scores = jax.lax.dynamic_update_slice(obs_scores, scores, (write_pos,))
    valid = jax.lax.dynamic_update_slice(
        valid, jnp.ones((n_suggest,), bool), (write_pos,)
    )
    return obs_unit, obs_scores, valid, key, scores, sugg


@functools.partial(jax.jit, static_argnames=("n_suggest", "cfg"))
def _tpe_suggest_program(obs_unit, obs_scores, valid, key, n_suggest: int, cfg):
    """Wave mode's suggest boundary op: the SAME key split + acquisition
    call ``tpe_generation`` opens with, as its own program. The buffers
    are NOT donated — the ring is updated only after the batch's waves
    have all landed (``_tpe_ring_update``), and an OOM-backoff re-run
    must be able to replay the batch from these exact suggestions.
    Separate-jit boundary ops preserve CPU bit-identity with the fused
    program (the engine's ``_wave_exploit`` precedent), so wave-mode
    suggestions equal resident-mode ones bit for bit."""
    key, k_sug, k_init, k_train = jax.random.split(key, 4)
    sugg, _ = tpe_suggest(k_sug, obs_unit, obs_scores, valid, n_suggest, cfg)
    return key, k_init, k_train, sugg


@functools.partial(
    jax.jit,
    static_argnames=("n_suggest",),
    donate_argnames=("obs_unit", "obs_scores", "valid"),
)
def _tpe_ring_update(obs_unit, obs_scores, valid, sugg, scores, write_pos, n_suggest: int):
    """The tail of ``tpe_generation`` — writing a completed batch's
    (units, scores) into the observation ring — split out so wave mode
    runs it once per batch, after the wave scores are gathered. f32
    scores round-trip host staging exactly, so the buffer after this
    equals the resident program's in-place update bit for bit."""
    obs_unit = jax.lax.dynamic_update_slice(obs_unit, sugg, (write_pos, 0))
    obs_scores = jax.lax.dynamic_update_slice(obs_scores, scores, (write_pos,))
    valid = jax.lax.dynamic_update_slice(
        valid, jnp.ones((n_suggest,), bool), (write_pos,)
    )
    return obs_unit, obs_scores, valid


def fused_tpe(  # sweeplint: barrier(batch host loop: fetches obs ring for snapshot/journal at batch boundaries)
    workload,
    n_trials: int,
    batch: int = 32,
    budget: int = 100,
    seed: int = 0,
    cfg: TPEConfig = TPEConfig(),
    member_chunk: int = 0,
    mesh=None,
    wave_size=0,
    oom_backoff: int = 2,
    checkpoint_dir: str = None,
    ledger=None,
    warm_obs=None,
):
    """Run an n_trials TPE sweep as ceil(n_trials/batch) fused
    generations (the last one sized to the remainder).

    ``wave_size`` (int or ``'auto'``) runs each generation's cohort as
    resident waves through the shared engine (train/engine.py) when the
    batch exceeds device residency: the suggest step runs as its own
    boundary program, each wave initializes its members from the SAME
    ``split(k_init, batch)`` key window the resident program would use,
    and only scores stage back out (TPE carries no state between
    generations) — bit-identical to resident mode at any wave size.
    ``oom_backoff`` halves the wave cap and replays the generation from
    its already-drawn suggestions on a classified device OOM.

    ``ledger`` journals one record per suggestion per generation batch
    (unit params + score at the trial budget) before the generation's
    snapshot saves; resume verifies already-journaled batches
    (ledger/fused.py). ``warm_obs`` (prior-ledger observations,
    cross-mode) PRE-FILLS the on-device observation ring: the buffer
    grows by the finite-scored prior count and the acquisition kernel
    sees the priors from its first suggestion — the fused equivalent of
    driver TPE's surrogate warm start. Warm rows are facts, not trials:
    they are barred from the best pick and the curve, and ``n_warm`` is
    part of the checkpoint identity (the buffer shape depends on it).

    Returns best score/params, the per-generation cumulative-best curve,
    and the full observation history. ``checkpoint_dir`` makes the sweep
    crash-recoverable at generation granularity; the RNG key snapshots
    with the buffer, so a resumed sweep finishes with the IDENTICAL
    result of an uninterrupted one (tested).

    ``mesh``: optional ``('pop','data')`` mesh. The observation buffer
    (tiny) replicates; each generation's cohort trains sharded over
    'pop' (constraint applied inside ``tpe_generation``) with the batch
    data-parallel over 'data' — the suggest step reads the replicated
    buffer identically on every device, so no collective is needed
    beyond what the partitioner inserts for training.
    """
    if n_trials < 1:
        raise ValueError(f"n_trials must be >= 1, got {n_trials}")
    trainer, space, train_x, train_y, val_x, val_y = workload_arrays(
        workload, member_chunk, mesh
    )
    d = len(space.discrete_mask())
    sizes = [batch] * (n_trials // batch)
    if n_trials % batch:
        sizes.append(n_trials % batch)
    # the residency question is about the LARGEST generation cohort;
    # the engine re-lays out smaller (remainder) generations per batch
    wave_size = resolve_wave_size(
        trainer,
        train_x[:2],
        max(sizes),
        wave_size=wave_size,
        mesh=mesh,
        oom_backoff=oom_backoff,
    )
    waves = 0 < wave_size < max(sizes)
    # finite-scored priors only: a diverged prior point carries no
    # evidence the model should build on (same rule as driver ingest)
    warm = [o for o in (warm_obs or []) if np.isfinite(float(o.score))]
    n_warm = len(warm)
    M = n_trials + n_warm  # buffer fits the sweep plus the priors

    def place_buffers(obs_unit, obs_scores, valid):
        """The obs buffer replicates over the mesh (single placement
        point for both the fresh-init and checkpoint-restore paths)."""
        if mesh is None:
            return obs_unit, obs_scores, valid
        from mpi_opt_tpu.parallel.mesh import replicate

        rep = replicate(mesh)
        return tuple(jax.device_put(a, rep) for a in (obs_unit, obs_scores, valid))

    key = jax.random.key(seed)
    unit0 = np.zeros((M, d), np.float32)
    scores0 = np.zeros((M,), np.float32)
    valid0 = np.zeros((M,), bool)
    if n_warm:
        unit0[:n_warm] = np.stack([np.asarray(o.unit, np.float32) for o in warm])
        scores0[:n_warm] = np.array([float(o.score) for o in warm], np.float32)
        valid0[:n_warm] = True
    obs_unit, obs_scores, valid = place_buffers(
        jnp.asarray(unit0), jnp.asarray(scores0), jnp.asarray(valid0)
    )
    from mpi_opt_tpu.train.common import HParamsFn

    hparams_fn = HParamsFn(space, workload)

    snap = None
    restored = None
    start_gen = 0
    run_wave_size = wave_size  # execution cap; adopted from snapshot meta
    done = n_warm  # write position: live trials append after the priors
    best_curve = []
    member_fail: list = []  # per-gen diverged-suggestion counts
    fails_complete = True
    if checkpoint_dir is not None:
        import dataclasses

        from mpi_opt_tpu.utils.checkpoint import SweepCheckpointer

        snap = SweepCheckpointer(
            checkpoint_dir,
            {
                "workload": getattr(workload, "name", type(workload).__name__),
                "n_trials": n_trials,
                "batch": batch,
                "budget": budget,
                "seed": seed,
                "member_chunk": member_chunk,
                # acquisition knobs change suggest behavior: a resumed
                # sweep must continue under the SAME cfg
                "cfg": dataclasses.asdict(cfg),
                # carried-state structure (see fused_pbt)
                "momentum_dtype": momentum_dtype_str(),
                # the warm prefix is buffer STRUCTURE (its rows shift
                # every live write position): resuming under a
                # different prior set must refuse, not corrupt
                "n_warm": n_warm,
                # wave mode's REQUESTED cap is config identity (the
                # OOM-settled execution cap travels in per-snapshot
                # meta); resident configs deliberately DON'T write the
                # key, so pre-wave snapshots keep resuming via the
                # checkpointer's setdefault back-compat
                **({"wave_size": wave_size} if waves else {}),
            },
        )
        restored = snap.restore()
        if restored is not None:
            sweep, meta = restored
            obs_unit, obs_scores, valid = place_buffers(
                jnp.asarray(sweep["obs_unit"]),
                jnp.asarray(sweep["obs_scores"]),
                jnp.asarray(sweep["valid"]),
            )
            key = jax.random.wrap_key_data(jnp.asarray(sweep["key_data"]))
            start_gen = int(meta["gens_done"])
            done = n_warm + sum(sizes[:start_gen])
            best_curve = [float(v) for v in meta["best_curve"]]
            # pre-upgrade snapshots have no per-gen failure tallies for
            # the completed generations: report None, never invent
            if "member_fail" in meta:
                member_fail = [int(v) for v in meta["member_fail"]]
            else:
                fails_complete = False
            if waves:
                run_wave_size = int(meta.get("wave_size_run", wave_size))

    from mpi_opt_tpu.parallel.mesh import fetch_global

    # uncheckpointed sweeps defer the per-generation running-best fetch
    # (one blocking device->host sync each) to a single batched barrier at the
    # end — the same deferral train/fused_asha.py's fused_sha applies
    # to its rung ledger; checkpointed sweeps keep it eager (each
    # snapshot records the curve so far). fused_pbt deliberately does
    # NOT defer: its per-launch fetch doubles as the launch-duration
    # barrier that launch-granular wall-to-target accounting needs.
    journal = make_fused_journal(ledger, space)
    journal_require_prefix(journal, start_gen)
    # a fused journal forces the eager path (its per-batch records must
    # be fsync-durable before the batch's snapshot — deferral breaks
    # the ordering contract), same as a checkpoint does; wave mode's
    # scores land on the host per batch anyway, so its curve is eager
    defer = snap is None and journal is None and not waves
    runner = None
    if waves:
        runner = WaveRunner(max(sizes), run_wave_size, oom_backoff=oom_backoff)
    # warm prior rows are facts, not trials of THIS sweep: bar them
    # from the running-best curve and the final winner pick
    live = jnp.arange(M) >= n_warm
    curve_dev: list = []
    fail_dev: list = []
    try:
        for g in range(start_gen, len(sizes)):
            n_g = sizes[g]
            if waves:
                # engine path: suggest as its own boundary program, the
                # cohort as resident waves (scores-only stage-out — TPE
                # carries no state between generations), the ring update
                # once the batch's scores have all landed. The runner
                # owns launch_tick, the train span, the per-wave
                # heartbeats, the drain barrier, and the OOM-backoff
                # replay (the replay re-trains from the SAME suggestions
                # and init keys, so it is bit-identical).
                with boundary_span("suggest", generation=g + 1, n=n_g):
                    key, k_init, k_train, sugg = _tpe_suggest_program(
                        obs_unit, obs_scores, valid, key, n_g, cfg
                    )
                member_keys = jax.random.split(k_init, n_g)
                scores_host = np.full((n_g,), np.nan, np.float32)

                def _dispatch(
                    w, off, wl_, eng,
                    k_train=k_train, sugg=sugg, member_keys=member_keys, n_g=n_g,
                ):
                    return _run_wave(
                        trainer,
                        None,
                        np.arange(off, off + wl_),
                        off,
                        sugg,
                        hparams_fn,
                        train_x,
                        train_y,
                        val_x,
                        val_y,
                        k_train,
                        budget,
                        n_g,
                        mesh,
                        eng,
                        init_keys=member_keys[off : off + wl_],
                        sample_x=train_x[:2],
                    )

                def _payload(st, sc):
                    return {"scores": sc}

                def _writer(off, scores_host=scores_host):
                    def _write(host_tree):  # sweeplint: barrier(stage-out landing: writes fetched wave scores into the batch accumulator)
                        s = host_tree["scores"]
                        scores_host[off : off + len(s)] = s

                    return _write

                f = segment_flops_hint(workload, n_g, budget)
                runner.run_interval(
                    n=n_g,
                    run_wave_fn=_dispatch,
                    payload_fn=_payload,
                    writer_fn=_writer,
                    scores_host=scores_host,
                    stage_label=lambda w, nw, g=g: (
                        f"tpe generation {g + 1}/{len(sizes)} wave {w + 1}/{nw}"
                    ),
                    boundary_kwargs=lambda w, nw, g=g: {
                        "generation": g + 1,
                        "of": len(sizes),
                    },
                    span_attrs=lambda nw, g=g, n_g=n_g: {
                        "launch": g + 1,
                        "members": n_g,
                        "steps": budget,
                        "waves": nw,
                    },
                    flops=f,
                    notify_fields=(("generation", g + 1),),
                )
                # f32 round-trips host staging exactly: this equals the
                # device scores tpe_generation would have produced
                scores = jnp.asarray(scores_host.copy())
                with boundary_span("observe", generation=g + 1):
                    obs_unit, obs_scores, valid = _tpe_ring_update(
                        obs_unit, obs_scores, valid, sugg, scores,
                        jnp.int32(done), n_g,
                    )
                done += n_g
                running_dev = jnp.max(
                    jnp.where(
                        valid & jnp.isfinite(obs_scores) & live, obs_scores, -jnp.inf
                    )
                )
                fail_dev_g = jnp.sum(~jnp.isfinite(scores)).astype(jnp.int32)
                best_curve.append(float(fetch_global(running_dev)))
                member_fail.append(int(fetch_global(fail_dev_g)))
            else:
                profiling.launch_tick()
                # eager mode's curve fetch is the batch's completion barrier
                # (real duration -> flops attr for achieved TF/s); deferred
                # mode dispatches async, so the span carries no flops. The
                # hint probes OUTSIDE the span (one-time cost must not
                # inflate the first batch's duration), attaches only after
                # the barrier (a crashed batch must not report full-batch
                # FLOPs over a partial duration).
                f = None if defer else segment_flops_hint(workload, sizes[g], budget)
                with trace.span(
                    "train", launch=g + 1, members=sizes[g], steps=budget
                ) as sp:
                    obs_unit, obs_scores, valid, key, scores, sugg = tpe_generation(
                        trainer,
                        obs_unit,
                        obs_scores,
                        valid,
                        hparams_fn,
                        train_x,
                        train_y,
                        val_x,
                        val_y,
                        key,
                        jnp.int32(done),
                        n_suggest=sizes[g],
                        budget=budget,
                        cfg=cfg,
                    )
                    done += sizes[g]
                    # valid alone is not enough: one valid-but-NaN observation
                    # would propagate through jnp.max into every later curve
                    # point — gate on finiteness too (same rule as best_i below)
                    running_dev = jnp.max(
                        jnp.where(
                            valid & jnp.isfinite(obs_scores) & live, obs_scores, -jnp.inf
                        )
                    )
                    # this generation's diverged-suggestion count (ROADMAP open
                    # item): the obs ring masks non-finite scores from the model,
                    # but operators need the tally the masking hides
                    fail_dev_g = jnp.sum(~jnp.isfinite(scores)).astype(jnp.int32)
                    if defer:
                        curve_dev.append(running_dev)
                        fail_dev.append(fail_dev_g)
                    else:
                        # fetch_global: under multi-process SPMD the buffer is a
                        # process-spanning (replicated) global array
                        best_curve.append(float(fetch_global(running_dev)))
                        member_fail.append(int(fetch_global(fail_dev_g)))
                        if f:
                            sp["flops"] = f
                        # post-barrier device-memory watermark: batch cohort
                        # + obs ring resident
                        memory.note(sp)
            if journal is not None:
                # one record per suggestion of this batch (members are
                # the sweep's global trial indices), journaled BEFORE
                # the generation snapshot below
                first = sum(sizes[:g])
                journal_boundary(
                    journal,
                    g,
                    np.arange(first, first + sizes[g]),
                    fetch_global(sugg),
                    fetch_global(scores),
                    step=budget,
                )
            if snap is not None:
                # fetch_global for the payload too — np.asarray on the
                # process-spanning buffers raises, killing the sweep at
                # its first snapshot exactly where the mesh needs it
                snap.save(
                    g + 1,
                    sweep={
                        "obs_unit": fetch_global(obs_unit),
                        "obs_scores": fetch_global(obs_scores),
                        "valid": fetch_global(valid),
                        "key_data": np.asarray(jax.random.key_data(key)),
                    },
                    meta_extra={
                        "gens_done": g + 1,
                        "boundaries_done": g + 1,
                        "best_curve": best_curve,
                        **({"member_fail": member_fail} if fails_complete else {}),
                        # the OOM-settled execution cap: a resume adopts
                        # it instead of re-paying the halvings
                        **({"wave_size_run": runner.wave_size} if waves else {}),
                    },
                )
            # heartbeat + graceful-shutdown drain: checkpointed sweeps
            # snapshot every generation, so a preemption here resumes
            # at exactly the next generation
            launch_boundary(
                f"tpe generation {g + 1}/{len(sizes)}",
                final=g + 1 == len(sizes),
                generation=g + 1,
                of=len(sizes),
            )
    finally:
        if runner is not None:
            runner.close()
        if snap is not None:
            snap.close()

    if curve_dev or fail_dev:
        from mpi_opt_tpu.parallel.mesh import fetch_global_batched

        fetched = fetch_global_batched(curve_dev + fail_dev)
        best_curve.extend(float(v) for v in fetched[: len(curve_dev)])
        member_fail.extend(int(v) for v in fetched[len(curve_dev):])
    # warm prior rows are sliced off the returned history: callers get
    # exactly this sweep's n_trials observations, warm-started or not
    np_unit = np.asarray(fetch_global(obs_unit))[n_warm:]
    raw_scores = np.asarray(fetch_global(obs_scores))[n_warm:]
    np_scores = np.asarray(raw_scores)
    np_valid = np.asarray(fetch_global(valid))[n_warm:]
    # invalid rows AND non-finite scores are barred from the winner
    # pick: a valid-but-NaN observation must not win argmax (NaN sorts
    # first). Shared rule: train.common.finite_winner; an all-diverged
    # sweep reports best_params=None / best_score NaN with
    # diverged=True, matching fused SHA/PBT
    best_i, diverged = finite_winner(np_scores, ok=np_valid)
    return {
        "best_score": float("nan") if diverged else float(np_scores[best_i]),
        "best_params": None if diverged else space.materialize_row(np_unit[best_i]),
        "diverged": diverged,
        "best_curve": np.asarray(best_curve, dtype=np.float32),
        # per-generation diverged-suggestion tallies; None when a
        # pre-upgrade snapshot left completed generations' counts unknown
        "member_failures": member_fail if fails_complete else None,
        "obs_unit": np_unit,
        "obs_scores": raw_scores,
        "n_trials": n_trials,
        "n_warm": n_warm,
        "journal": None
        if journal is None
        else {"written": journal.written, "verified": journal.verified},
        **({} if runner is None else runner.result_extras()),
    }
