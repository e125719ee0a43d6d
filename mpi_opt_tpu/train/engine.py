"""One fault-tolerant fused engine: the wave/stage/drain/OOM skeleton.

Every fused driver (PBT, SHA, TPE, BOHB — ``train/fused_*.py``) used to
hand-copy the same robustness machinery: wave scheduling through host
pools when the population exceeds device residency, double-buffered
async stage-out, the generation/rung/batch retry loop that halves the
wave cap on a device OOM (``--oom-backoff``), per-wave heartbeats,
between-waves graceful-drain service points, and the drain barrier at
every algorithm boundary. This module is that skeleton written ONCE,
parameterized by the algorithm's boundary op — PBT truncation-exploit,
SHA/BOHB rung cut, TPE/BOHB batch re-suggest — so a robustness contract
(bit-identical backoff re-runs, boundary-granular journaling, verified
snapshot resume, sub-launch liveness) lands for all four algorithms the
day it is written instead of four diverging times.

The division of labor:

- ``WaveRunner.run_interval`` owns ONE algorithm interval (a PBT
  generation, an SHA rung, a TPE batch) executed as resident waves:
  the wave loop, per-wave heartbeat + stage-out, between-waves
  ``launch_boundary`` drain points, the interval-ending drain barrier,
  and the DeviceOOM wave-halving retry. The caller supplies closures
  for everything algorithm-shaped: how to dispatch a wave, what to
  stage out, where scores land, how labels/snapshots are built.
- ``run_wave`` stages in + trains + evals one wave — the one function
  the chaos drills intercept (``resources.launch_fault("wave")`` is its
  first line, so OOM/crash injection covers every algorithm for free).
- ``resolve_wave_size`` is the single sizing door: ``auto`` estimation,
  the uniform pre-clamp of explicit caps against the measured residency
  estimate, and the multi-process refusal — identical behavior for
  every ``--wave-size``-capable algorithm.
- ``boundary_span`` wraps an algorithm's boundary op in a traced span
  that ALSO heartbeats from inside it, so ``launch.py`` stall events
  can say "stalled during boundary:rung_cut" instead of naming the
  last train phase.

Bit-identity contract (the PERF_NOTES round-6 moral): every transform
feeding an RNG decision stays inside jit. ``_wave_train_program``
applies the unit→hparams mapping IN-program for the drivers whose
resident path does (PBT, TPE); ``_wave_train_hp_program`` accepts
pre-mapped hparams for SHA, whose resident rung loop maps them eagerly
— each wave path reproduces ITS resident twin bit-for-bit on the CPU
backend for any wave size (tested).
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from mpi_opt_tpu.obs import memory, trace
from mpi_opt_tpu.train.common import launch_boundary, oom_funnel
from mpi_opt_tpu.train.population import PopState, trainer_jit
from mpi_opt_tpu.utils import profiling, resources


def balanced_split(total: int, chunk: int) -> list[int]:
    """Split ``total`` into ceil(total/chunk) near-equal parts (lengths
    differ by at most 1, so at most two distinct compiled program
    lengths exist). Shared by wave scheduling and the PBT gen_chunk /
    step_chunk launch splitting; total=0 yields [0] — one empty part,
    matching the unchunked path's empty-scan behavior."""
    if total <= 0:
        return [0]
    n_parts = -(-total // chunk)
    base, rem = divmod(total, n_parts)
    return [base + 1] * rem + [base] * (n_parts - rem)


def wave_layout(population: int, wave_size: int):
    """(wave_lens, offs, n_waves) for a wave cap — recomputed in place
    when the OOM backoff halves the cap mid-run."""
    wave_lens = balanced_split(population, wave_size)
    offs = [0]
    for w in wave_lens[:-1]:
        offs.append(offs[-1] + w)
    return wave_lens, offs, len(wave_lens)


def engine_rollover(old):
    """Fresh StagingEngine carrying the old one's cumulative accounting
    (results and trace attrs report RUN totals): after a device OOM the
    old engine may hold a latched transfer error — ``device_get`` of a
    never-materialized wave fails on the worker thread — which would
    refuse every later ``stage_out`` on sight."""
    from mpi_opt_tpu.train.staging import StagingEngine

    old.close()
    new = StagingEngine()
    new.staged_bytes = old.staged_bytes
    new.transfers = old.transfers
    new.transfer_s = old.transfer_s
    new.wait_s = old.wait_s
    return new


def writable(tree):
    """Orbax restores may hand back read-only numpy arrays; the pools
    are written in place per wave, so copy only the leaves that need it."""
    import numpy as np

    return jax.tree.map(
        lambda l: l if isinstance(l, np.ndarray) and l.flags.writeable else np.array(l),
        tree,
    )


@contextlib.contextmanager
def boundary_span(op: str, **attrs):
    """Trace an algorithm's boundary op (exploit / rung_cut / suggest)
    AND heartbeat from inside it: the beat records the span's phase
    (``boundary:<op>``, obs/trace.py), so a rank that stalls inside the
    boundary — a wedged cross-host gather during the cut, a hung
    acquisition — is attributed to THAT op by launch.py's stall report
    instead of to whatever train phase beat last."""
    from mpi_opt_tpu.health import heartbeat

    with trace.span("boundary", op=op, **attrs) as sp:
        heartbeat.beat(stage=f"boundary {op}")
        yield sp


def resolve_wave_size(trainer, sample_x, population: int, *, wave_size, mesh=None, oom_backoff: int = 0) -> int:
    """Resolve a requested wave cap (``'auto'`` or int) for a
    ``population``-member fused sweep — the ONE sizing door every
    wave-capable driver goes through, so ``auto`` estimation, the
    pre-clamp of explicit caps, and the multi-process cap agreement
    cannot drift between algorithms.

    Returns the resolved integer cap; 0 (or a cap >= population) means
    resident mode, the bit-identical baseline. With ``oom_backoff``
    enabled and a MEASURED device budget (obs/memory.py), an explicit
    cap above the residency estimate is pre-clamped (``wave_resized``
    event) so the common case never pays an OOM to learn the answer.

    Under multi-process SPMD (an active ``parallel/coord.py`` plane),
    each rank sizes against ITS host's budget and then the settled cap
    is min-agreed through the control plane — all ranks must run the
    same wave schedule or their collectives diverge, and the most
    memory-constrained host is the binding one.
    """
    if not wave_size:
        return 0
    from mpi_opt_tpu.train.staging import estimate_wave_size

    was_auto = wave_size == "auto"
    if was_auto:
        wave_size = estimate_wave_size(trainer, sample_x, population, mesh)
        if wave_size < population:
            # the pre-launch headroom clamp engaged: auto sized the
            # wave from the measured budget (or its fallbacks)
            # BEFORE the first OOM — record it as an event, not a
            # silent number (ISSUE 13)
            resources.notify(
                "wave_resized",
                requested="auto",
                wave_size=int(wave_size),
                population=population,
            )
    wave_size = int(wave_size)
    if wave_size < 0:
        raise ValueError(f"wave_size must be >= 0, got {wave_size}")
    if oom_backoff and not was_auto and 0 < wave_size < population:
        from mpi_opt_tpu.obs import memory as obs_memory

        # EXPLICIT cap vs MEASURED headroom (auto already sized from
        # the estimate — re-deriving it here would compare the estimate
        # against itself for a wasted eval_shape pass; and never clamp
        # against the 8 GiB default — shrinking a hand-picked cap on a
        # guess would surprise, the measured bytes_limit is evidence):
        # shrink before the first OOM instead of paying one
        if obs_memory.measured_budget() is not None:
            est = estimate_wave_size(trainer, sample_x, population, mesh)
            if est < wave_size:
                resources.notify(
                    "wave_resized",
                    requested=wave_size,
                    wave_size=est,
                    population=population,
                )
                wave_size = est
    from mpi_opt_tpu.parallel import coord

    plane = coord.active_plane()
    if plane is not None and 0 < wave_size:
        # every rank proposes its locally-settled cap (a cap at or
        # above the population still constrains a peer that sized
        # smaller, so it votes its true value, clamped to resident);
        # min-agreement picks the most constrained host's answer.
        # Without a plane a multi-process run still proceeds — SPMD
        # ranks derive identical caps from identical code on
        # homogeneous hosts — but heterogeneous budgets and OOM
        # absorption need the agreement (the backoff handler refuses
        # to halve unilaterally).
        agreed = plane.agree_cap("wave_cap", min(wave_size, population))
        if agreed and agreed != wave_size:
            resources.notify(
                "wave_resized",
                requested=wave_size,
                wave_size=agreed,
                population=population,
                agreed=True,
            )
            wave_size = agreed
    return wave_size


@trainer_jit(
    static_argnames=("hparams_fn", "steps", "n_total"), donate_argnames=("state",)
)
def _wave_train_program(
    trainer, state, unit_slice, hparams_fn, train_x, train_y, key, steps, n_total, offset
):
    """One wave's training launch, with the unit->hparams mapping
    applied IN-program. Applying it eagerly instead looks harmless but
    is not: eager op-by-op kernels and fused XLA codegen disagree by
    ~1e-7 relative on the log-uniform transforms, and the augmentation's
    DISCRETE decisions (rounded shift offsets, bernoulli flips) amplify
    an ulp of hparam difference into entirely different batches —
    measured as 1e-2 param divergence within 4 steps. In-program hp is
    what makes wave mode reproduce the resident scan bit-for-bit for
    the drivers (PBT, TPE) whose resident program maps in-scan."""
    hp = hparams_fn(unit_slice)
    return type(trainer)._train_segment_window(
        trainer, state, hp, train_x, train_y, key, steps, n_total, offset
    )


@trainer_jit(static_argnames=("steps", "n_total"), donate_argnames=("state",))
def _wave_train_hp_program(
    trainer, state, hp_slice, train_x, train_y, key, steps, n_total, offset
):
    """The eager-hparams twin of ``_wave_train_program``, for SHA: the
    resident rung loop maps unit->hparams EAGERLY before its
    ``train_segment`` call, so the wave path must hand this program the
    SAME eagerly-mapped values (sliced to the wave's rows — slicing is
    exact) to be bit-identical to it. Mapping in-program here would
    reproduce a program the resident SHA never ran."""
    return type(trainer)._train_segment_window(
        trainer, state, hp_slice, train_x, train_y, key, steps, n_total, offset
    )


def run_wave(
    trainer,
    pool,
    rows,
    offset: int,
    unit,
    hparams_fn,
    train_x,
    train_y,
    val_x,
    val_y,
    k_train,
    steps: int,
    population: int,
    mesh,
    engine,
    init_keys=None,
    sample_x=None,
    hp=None,
):
    """Stage in + train + eval ONE wave: members [offset, offset+W) of
    the interval's cohort. ``rows`` is the host-pool row index array and
    already carries the previous boundary's gather map (PBT's exploit
    sources, SHA's rung survivors), so staging in IS the winner gather.
    A cohort's first interval passes ``init_keys`` instead (members
    don't exist yet — initializing on device skips a pointless host
    round trip; the keys are the same ``split(k_init, P)`` window the
    resident program would use, so the weights are bit-identical).

    ``hp`` switches to the eager-hparams program (SHA parity, see
    ``_wave_train_hp_program``); the default maps ``unit`` rows
    in-program (PBT/TPE parity). Module-level so crash-injection tests
    can intercept it — the adapters re-export it as ``_run_wave``."""
    from mpi_opt_tpu.train.staging import stage_in, tree_bytes

    # chaos seam (inject_oom): one guarded launch ordinal per wave —
    # raises a synthetic RESOURCE_EXHAUSTED at the drilled wave, which
    # the interval's oom_funnel classifies exactly like a real one.
    # Living HERE means every algorithm's waves inherit the drill seam.
    resources.launch_fault("wave")
    w = len(rows)
    if init_keys is not None:
        st = trainer.init_members(init_keys, sample_x)
        if mesh is not None:
            from mpi_opt_tpu.parallel.mesh import shard_popstate

            st = shard_popstate(st, mesh)
    else:
        with trace.span("stage_in", members=w) as sp:
            dev = stage_in(pool, rows, mesh)
            n_bytes = tree_bytes(dev)
            sp["bytes"] = n_bytes
            memory.note(sp)
        engine.note_bytes(n_bytes)
        st = PopState(params=dev["params"], momentum=dev["momentum"], step=dev["step"])
    if hp is not None:
        hp_slice = jax.tree.map(lambda v: v[offset : offset + w], hp)
        st, _ = _wave_train_hp_program(
            trainer,
            st,
            hp_slice,
            train_x,
            train_y,
            k_train,
            steps,
            population,
            jnp.int32(offset),
        )
    else:
        st, _ = _wave_train_program(
            trainer,
            st,
            unit[offset : offset + w],
            hparams_fn,
            train_x,
            train_y,
            k_train,
            steps,
            population,
            jnp.int32(offset),
        )
    scores = trainer.eval_population(st, val_x, val_y)
    return st, scores


class WaveRunner:
    """The shared wave-scheduling executor: owns the StagingEngine
    lifecycle, the current (possibly OOM-halved) wave cap, and the
    backoff budget, and runs each algorithm interval — a PBT
    generation, an SHA rung, a TPE batch — through the one wave loop.

    ``wave_size`` here is the EXECUTION cap: it starts at the resolved
    request (or a snapshot's adopted ``wave_size_run``) and halves on
    absorbed OOMs; the REQUESTED cap stays the sweep's config identity
    in each driver's checkpoint config. After ``run_interval`` returns,
    ``wave_size`` / ``wave_lens`` / ``offs`` / ``n_waves`` reflect the
    settled layout the interval actually ran under — callers read them
    for snapshot meta (``wave_size_run``), step numbering, and result
    reporting.
    """

    def __init__(self, population: int, wave_size: int, *, oom_backoff: int = 0):
        from mpi_opt_tpu.train.staging import StagingEngine

        self.population = int(population)
        self.wave_size = int(wave_size)
        self.oom_budget = max(0, int(oom_backoff))
        self.oom_backoffs = 0
        self.waves_run = 0  # cumulative across intervals AND retries
        self.engine = StagingEngine()
        self.wave_lens, self.offs, self.n_waves = wave_layout(
            self.population, self.wave_size
        )

    def adopt(self, wave_size_run) -> None:
        """Adopt a snapshot's OOM-settled execution cap (meta
        ``wave_size_run``): waves_done in that snapshot counts waves of
        the settled split, and resuming at the requested size would
        re-OOM an interval just to re-learn the answer."""
        self.wave_size = int(wave_size_run)
        self.wave_lens, self.offs, self.n_waves = wave_layout(
            self.population, self.wave_size
        )

    def close(self) -> None:
        self.engine.close()

    def run_interval(  # sweeplint: barrier(wave interval loop: stages pools, gathers wave scores, drains at the algorithm boundary)
        self,
        *,
        n: int,
        run_wave_fn,
        payload_fn,
        writer_fn,
        scores_host,
        stage_label,
        boundary_kwargs=None,
        midpoint_snapshot=None,
        span_attrs=None,
        flops=None,
        start_wave: int = 0,
        notify_fields=(),
    ):
        """Run ONE algorithm interval (``n`` cohort members) as resident
        waves; returns the per-wave device score arrays in wave order.

        The caller parameterizes the algorithm-shaped parts:

        - ``run_wave_fn(w, off, wl, engine) -> (state, scores)``
          dispatches wave ``w`` (usually a closure over the adapter
          module's patchable ``_run_wave`` seam);
        - ``payload_fn(state, scores) -> tree`` is what the background
          thread stages out (PBT/SHA fetch the trained states into the
          back pool; TPE discards states and fetches scores only);
        - ``writer_fn(off) -> callback`` lands a fetched payload into
          host memory — it MUST fill ``scores_host[off:off+w]``, the
          NaN-initialized accumulator mid-interval resume and the OOM
          re-run both reset and re-read;
        - ``stage_label(w, n_waves)`` / ``boundary_kwargs(w, n_waves)``
          / ``midpoint_snapshot(w, n_waves)`` shape the per-wave
          heartbeat, the between-waves ``launch_boundary`` progress
          fields, and the optional graceful-drain snapshot closure;
        - ``span_attrs(n_waves)`` shapes the interval's train span.

        ``start_wave`` (mid-interval snapshot resume) skips completed
        waves, reconstituting their scores from ``scores_host`` — f32
        round-trips host storage exactly, so the reconstructed device
        arrays equal the originals.

        On a classified DeviceOOM with budget remaining, the interval
        re-runs from wave 0 under a halved cap (``oom_backoff``): pool
        reads are non-destructive, the caller's interval keys are
        already derived, and wave scheduling is bit-identical at ANY
        wave size, so the re-run reproduces the interval exactly — the
        engine is rolled over (a latched transfer error would refuse
        every later stage-out) and an ``oom_backoff`` event is
        notified with the caller's ``notify_fields`` identifying the
        interval. Budget exhausted (or cap already 1) re-raises for the
        CLI's classified exit.
        """
        import numpy as np

        from mpi_opt_tpu.health import heartbeat
        from mpi_opt_tpu.parallel import coord

        while True:  # one iteration per OOM-backoff attempt
            wave_lens, offs, n_waves = wave_layout(n, self.wave_size)
            self.wave_lens, self.offs, self.n_waves = wave_lens, offs, n_waves
            wave_scores: list = [None] * n_waves
            w0 = start_wave
            for w in range(w0):
                off, wl = offs[w], wave_lens[w]
                # completed waves' scores round-trip exactly (f32)
                wave_scores[w] = jnp.asarray(scores_host[off : off + wl])

            def _train_interval(
                w0=w0, wave_scores=wave_scores, wave_lens=wave_lens,
                offs=offs, n_waves=n_waves,
            ):
                for w in range(w0, n_waves):
                    off, wl = offs[w], wave_lens[w]
                    st, sc = run_wave_fn(w, off, wl, self.engine)
                    wave_scores[w] = sc
                    self.waves_run += 1
                    # per-wave liveness: beat as soon as the wave's
                    # programs are dispatched, so a stall timeout sized
                    # to one wave also covers the interval's LAST wave
                    # (whose next boundary beat waits on the full drain
                    # + boundary op)
                    heartbeat.beat(stage=f"{stage_label(w, n_waves)} dispatched")
                    # async stage-out: the background fetch blocks on
                    # THIS wave's compute while the loop dispatches the
                    # next wave
                    self.engine.stage_out(payload_fn(st, sc), writer_fn(off))
                    if w + 1 < n_waves:
                        # between-waves service point: heartbeat +
                        # graceful drain, with a mid-interval snapshot
                        # when the algorithm supports one (completed
                        # waves are never re-trained on resume)
                        launch_boundary(
                            stage_label(w, n_waves),
                            final=False,
                            snapshot=(
                                None
                                if midpoint_snapshot is None
                                else midpoint_snapshot(w, n_waves)
                            ),
                            **(
                                {}
                                if boundary_kwargs is None
                                else boundary_kwargs(w, n_waves)
                            ),
                        )
                # interval boundary: the ONLY hard transfer barrier —
                # the boundary op needs the full score vector and a
                # settled pool
                self.engine.drain()

            # the interval's train span covers every wave dispatch AND
            # the drain barrier, so its duration is the interval's real
            # compute+transfer wall; nested stage_in/stage_out/
            # stage_wait/save spans subtract from its self time.
            # ``flops`` makes the trace CLI report achieved TF/s per
            # interval. The oom_funnel classifies an XLA
            # RESOURCE_EXHAUSTED escaping any wave into typed DeviceOOM
            # for the backoff below.
            profiling.launch_tick()
            try:
                with oom_funnel(self.wave_size):
                    with trace.span(
                        "train",
                        **({"waves": n_waves} if span_attrs is None else span_attrs(n_waves)),
                    ) as sp:
                        _train_interval()
                        # flops only AFTER the drain barrier completed:
                        # an interval interrupted between waves emits
                        # its real partial duration WITHOUT the attr, so
                        # the trace CLI never divides full-interval
                        # FLOPs by partial wall
                        if flops:
                            sp["flops"] = flops
                        # post-drain device-memory watermark: the
                        # interval's peak residency (two waves +
                        # activations) just happened
                        memory.note(sp)
                local_oom = None
            except resources.DeviceOOM as e:
                if self.oom_budget <= 0 or self.wave_size <= 1:
                    # no wave left to halve (or backoff disabled):
                    # the classified answer propagates — CLI exit 74.
                    # Under a coord plane the peers waiting at this
                    # interval's agreement barrier wedge out on their
                    # timeout and exit too — the supervisor's
                    # coordinated restart is the recovery either way
                    raise
                if coord.active_plane() is None and jax.process_count() > 1:
                    # halving unilaterally would put this rank on a
                    # different wave schedule than its peers; without
                    # the control plane the only coordinated recovery
                    # is a job-level restart
                    raise
                local_oom = e

            # OOM agreement (multi-process SPMD): one barrier per
            # interval attempt on EVERY rank — a clean rank votes cap 0
            # ("no local constraint"), an OOMed rank votes its halved
            # cap; min-agreement means the whole cohort absorbs the
            # most constrained rank's halving together, so budgets and
            # wave schedules stay lockstep. Without a plane the local
            # proposal stands (single-process: local IS global).
            proposed = 0 if local_oom is None else max(1, self.wave_size // 2)
            plane = coord.active_plane()
            agreed = plane.agree_cap("oom", proposed) if plane is not None else proposed
            if not agreed:
                return wave_scores
            self.oom_budget -= 1
            self.oom_backoffs += 1
            # settle what completed; a transfer that died WITH
            # the OOM latched its error in the engine — roll it
            # over (accounting carried) so re-run stage-outs
            # aren't refused on sight
            try:
                self.engine.drain()
            # sweeplint: disable=drain-swallow -- settling in-flight transfers before the backoff re-run: the error here is the same already-classified OOM this handler is absorbing, and the engine is rolled over fresh below
            except BaseException:
                pass
            self.engine = engine_rollover(self.engine)
            self.wave_size = agreed
            # re-run THIS interval from wave 0 under the new split:
            # pool reads are non-destructive, the interval's keys
            # are already derived, and rewritten pool rows carry
            # identical values — bit-identity is preserved
            scores_host[:] = np.nan
            start_wave = 0
            resources.notify(
                "oom_backoff",
                **dict(notify_fields),
                wave_size=self.wave_size,
                remaining=self.oom_budget,
                error=(
                    str(local_oom)[:300]
                    if local_oom is not None
                    else "agreed backoff: device OOM on a peer rank"
                ),
            )
            continue

    def result_extras(self) -> dict:
        """The wave-observability result fields every wave-scheduled
        driver reports (acceptance: staging must be visible, not
        inferred): the settled execution split, absorbed OOM halvings,
        bytes moved, and how much of the transfer time the double
        buffer hid behind compute."""
        return {
            "wave_size": self.wave_size,
            "wave_lens": list(self.wave_lens),
            "n_waves": self.n_waves,
            # n_waves/wave_lens are the LAST interval's settled layout
            # (SHA's rungs shrink); waves_run counts every wave actually
            # dispatched, backoff re-runs included
            "waves_run": self.waves_run,
            "oom_backoffs": self.oom_backoffs,
            "staged_bytes": int(self.engine.staged_bytes),
            "stage_transfer_s": float(self.engine.transfer_s),
            "stage_wait_s": float(self.engine.wait_s),
            "stage_overlap_s": float(self.engine.overlap_s),
        }
