"""Results/reporting (SURVEY.md §2 row 12): JSONL metrics + throughput.

Emits one JSON object per event to a stream and/or file, and accounts
the metric of record (BASELINE.json): trials/sec/chip and wall-clock.
"""

from __future__ import annotations

import json
import sys
import time
from typing import IO, Optional


class MetricsLogger:
    def __init__(self, path: Optional[str] = None, stream: Optional[IO] = None, n_chips: int = 1):
        import threading

        self._file = open(path, "a") if path else None
        self._stream = stream
        # records arrive from more than one thread once span tracing is
        # wired (obs/trace.py: StagingEngine's transfer thread emits
        # stage_out spans concurrently with the main loop) — serialize
        # the sink writes so two records can never interleave mid-line
        self._sink_lock = threading.Lock()
        self.n_chips = max(1, n_chips)
        self.t_start = time.perf_counter()
        self.trials_done = 0
        # failure-lifecycle counters (driver.FailurePolicy feeds these):
        # trials_failed/trials_timeout count FINAL non-ok results (after
        # retries, disjoint by status); trials_retried counts retry
        # ATTEMPTS, so retried-then-recovered trials stay visible
        self.trials_failed = 0
        self.trials_timeout = 0
        self.trials_retried = 0
        # ledger-layer counters: evaluations SKIPPED (served from the
        # journal on resume / from the exact-match cache), disjoint from
        # trials_done so throughput never counts un-run work
        self.cache_hits = 0
        self.replayed = 0
        # health-layer counters (health/): preempted counts graceful-
        # shutdown drains this process honored (0 or 1 per run — summed
        # across restarts by log aggregation); stalls_detected counts
        # wedged evaluations this process detected and killed (the
        # driver feeds every reaped trial deadline into it — the
        # trial-level twin of launch.py's rank watchdog, whose own
        # kills appear in the supervisor's stall/done/failed events)
        self.preempted = 0
        self.stalls_detected = 0
        # integrity-layer counter (utils/integrity.py): snapshot steps
        # that failed digest/decode verification on restore and were
        # quarantined (renamed <step>.corrupt) before last-good fallback
        self.snapshots_quarantined = 0
        # staging-layer counters (train/staging.py, wave-scheduled fused
        # sweeps): staged_bytes counts host<->device bytes moved by the
        # background transfer engine; stage_overlap_s is how much of the
        # transfer time was hidden behind wave compute (transfer busy
        # time minus the main thread's barrier waits — the double
        # buffer's whole point, so it must be observable)
        self.staged_bytes = 0
        self.stage_overlap_s = 0.0
        # fused-ledger counter (ledger/fused.py): member records this
        # process appended to the boundary-granular journal (verified
        # re-computations on resume deliberately excluded — they are the
        # fused twin of `replayed`, carried in the summary's journal dict)
        self.members_journaled = 0
        # service-layer counters (service/scheduler.py, the resident
        # multi-tenant server): slices is scheduling quanta executed;
        # program_cache_hits/misses is the compiled-program reuse layer's
        # accounting — hits are slices whose (workload, pop-shape,
        # chunking) programs were already compiled in this process, the
        # observable form of "tenant N+1's cost is dispatch, not compile"
        self.slices = 0
        self.tenants_done = 0
        self.program_cache_hits = 0
        self.program_cache_misses = 0
        # fleet-federation counter (service/leases.py): orphaned jobs
        # this server claimed from a dead/expired peer's lease and
        # resumed — the observable form of "a dead host strands nothing"
        self.takeovers = 0
        # resource-exhaustion counters (utils/resources.py):
        # oom_backoffs = device-OOM wave halvings the fused scheduler
        # absorbed (each one re-ran a generation at half the wave and
        # kept the result bit-identical); wave_resized = pre-launch
        # headroom clamps of --wave-size against the measured budget;
        # snapshots_pruned = superseded retained steps deleted by the
        # ENOSPC retention-prune retry (never the newest verified step)
        self.oom_backoffs = 0
        self.wave_resized = 0
        self.snapshots_pruned = 0

    def log(self, event: str, **fields) -> dict:
        # `t` is relative (this process's clock, for intra-run deltas);
        # `ts` is absolute unix epoch so multi-process/multi-host streams
        # can be correlated after the fact
        rec = {
            "event": event,
            "t": round(time.perf_counter() - self.t_start, 4),
            "ts": round(time.time(), 4),
            **fields,
        }
        if self._file or self._stream:  # null_logger: no sink, no json cost
            line = json.dumps(rec)
            with self._sink_lock:
                if self._file:
                    self._file.write(line + "\n")
                    self._file.flush()
                if self._stream:
                    print(line, file=self._stream, flush=True)
        return rec

    def count_trials(self, n: int):
        self.trials_done += n

    def count_failure(self, status: str = "failed"):
        """One FINAL non-ok trial result (post-retry)."""
        if status == "timeout":
            self.trials_timeout += 1
        else:
            self.trials_failed += 1

    def count_retries(self, n: int = 1):
        self.trials_retried += n

    def count_cache_hits(self, n: int = 1):
        """Evaluations skipped by the exact-match ledger cache."""
        self.cache_hits += n

    def count_replayed(self, n: int = 1):
        """FINAL results served from the journal on replay-resume."""
        self.replayed += n

    def count_preempted(self, n: int = 1):
        """Graceful-shutdown drains honored (exit EX_TEMPFAIL follows)."""
        self.preempted += n

    def count_stalls(self, n: int = 1):
        """Stalled (hung-but-alive) executions detected and killed."""
        self.stalls_detected += n

    def count_quarantined(self, n: int = 1):
        """Corrupt snapshot steps quarantined during restore."""
        self.snapshots_quarantined += n

    def count_staging(self, staged_bytes: int = 0, overlap_s: float = 0.0):
        """Host-staging traffic from a wave-scheduled fused sweep."""
        self.staged_bytes += int(staged_bytes)
        self.stage_overlap_s += float(overlap_s)

    def count_journaled(self, n: int = 1):
        """Fused member records appended to the sweep ledger."""
        self.members_journaled += int(n)

    def count_slices(self, n: int = 1):
        """Service scheduling quanta (tenant slices) executed."""
        self.slices += int(n)

    def count_tenants_done(self, n: int = 1):
        """Service tenants that reached the done state."""
        self.tenants_done += int(n)

    def count_program_cache(self, hits: int = 0, misses: int = 0):
        """Compiled-program reuse accounting (service/programs.py)."""
        self.program_cache_hits += int(hits)
        self.program_cache_misses += int(misses)

    def count_takeovers(self, n: int = 1):
        """Expired-lease tenant takeovers this server performed."""
        self.takeovers += int(n)

    def count_oom_backoffs(self, n: int = 1):
        """Device-OOM wave halvings absorbed by the fused scheduler."""
        self.oom_backoffs += int(n)

    def count_wave_resized(self, n: int = 1):
        """Pre-launch wave-size headroom clamps (estimate vs budget)."""
        self.wave_resized += int(n)

    def count_pruned(self, n: int = 1):
        """Superseded snapshot steps pruned by the ENOSPC retry."""
        self.snapshots_pruned += int(n)

    @property
    def wall(self) -> float:
        return time.perf_counter() - self.t_start

    def trials_per_sec_per_chip(self) -> float:
        return self.trials_done / max(self.wall, 1e-9) / self.n_chips

    def summary(self, **extra) -> dict:
        return self.log(
            "summary",
            trials=self.trials_done,
            trials_failed=self.trials_failed,
            trials_retried=self.trials_retried,
            trials_timeout=self.trials_timeout,
            cache_hits=self.cache_hits,
            replayed=self.replayed,
            preempted=self.preempted,
            stalls_detected=self.stalls_detected,
            snapshots_quarantined=self.snapshots_quarantined,
            staged_bytes=self.staged_bytes,
            stage_overlap_s=round(self.stage_overlap_s, 3),
            members_journaled=self.members_journaled,
            slices=self.slices,
            tenants_done=self.tenants_done,
            program_cache_hits=self.program_cache_hits,
            program_cache_misses=self.program_cache_misses,
            takeovers=self.takeovers,
            oom_backoffs=self.oom_backoffs,
            wave_resized=self.wave_resized,
            snapshots_pruned=self.snapshots_pruned,
            wall_s=round(self.wall, 3),
            trials_per_sec_per_chip=round(self.trials_per_sec_per_chip(), 4),
            **extra,
        )

    def close(self):
        if self._file:
            self._file.close()
            self._file = None


def null_logger() -> MetricsLogger:
    return MetricsLogger()


def stdout_logger(path: Optional[str] = None, n_chips: int = 1) -> MetricsLogger:
    return MetricsLogger(path=path, stream=sys.stdout, n_chips=n_chips)


def wall_to_target(curve, wall_s: float, target: float):
    """Prorated wall-clock (seconds) until a per-generation best-score
    curve first reaches ``target``; None if it never does.

    The metric-of-record definition (BASELINE.json: "wall-clock to
    target validation accuracy"): generations are uniform work, so
    reaching the target at generation g costs (g+1)/G of the sweep's
    wall. Single-sourced here so every bench compares raw float curve
    values against the target identically.
    """
    curve = [float(v) for v in curve]
    for g, v in enumerate(curve):
        if v >= target:
            return wall_s * (g + 1) / len(curve)
    return None


def wall_to_target_launchwise(curve, launch_gens, launch_walls, target: float):
    """``wall_to_target`` with MEASURED per-launch wall times.

    A gen-chunked fused sweep runs as N launches of ``launch_gens[i]``
    generations taking ``launch_walls[i]`` seconds each (fused_pbt
    returns both). Whole-sweep prorating assumes every generation costs
    the same; here only generations *within* one launch are prorated
    (the scan's iterations really are identical programs), and launch
    boundaries use their measured times — tightening the granularity
    error from one sweep-fraction to at most one launch's interior.
    None if the curve never reaches target.
    """
    if len(launch_gens) != len(launch_walls):
        raise ValueError(
            f"launch_gens ({len(launch_gens)}) and launch_walls "
            f"({len(launch_walls)}) must align"
        )
    if sum(launch_gens) != len(curve):
        raise ValueError(
            f"launch_gens sums to {sum(launch_gens)} but curve has "
            f"{len(curve)} generations"
        )
    curve = [float(v) for v in curve]
    g0 = 0  # first generation index of the current launch
    done = 0.0  # wall of all completed launches before it
    for n_g, w in zip(launch_gens, launch_walls):
        for j in range(n_g):
            if curve[g0 + j] >= target:
                return done + w * (j + 1) / n_g
        g0 += n_g
        done += w
    return None


def sweep_wall_to_target(result: dict, wall_s: float, target: float):
    """Launch-granular when the sweep result carries measured launch
    durations (fused_pbt always does for fresh sweeps), whole-sweep
    prorating otherwise (``launch_walls`` is None when a resume from a
    pre-upgrade snapshot left early durations unknown).

    Semantics note: ``launch_walls`` deliberately excludes checkpoint-
    save time (the metric measures the sweep's compute-to-target),
    while the fallback's ``wall_s`` is the caller's
    clock and usually includes it. Records should carry the total wall
    alongside (benches record both) so the difference is visible."""
    if result.get("launch_walls") is not None:
        return wall_to_target_launchwise(
            result["best_curve"], result["launch_gens"], result["launch_walls"], target
        )
    return wall_to_target(result["best_curve"], wall_s, target)
