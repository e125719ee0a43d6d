"""The search driver: suggest → evaluate → report (SURVEY.md §3).

Reference call stack (contract from BASELINE.json; reference
unreadable): CLI → driver loop { algorithm.suggest → backend.evaluate
(Coordinator → MPI → MPIWorker ranks) → collect scores → algorithm
.report }. Here the loop is identical in shape, but the batch size is
pulled from the backend (``capacity``) so a TPU population backend
receives device-shaped batches, and a generational algorithm (PBT) can
hold the loop between generations without extra driver modes.
"""

from __future__ import annotations

import dataclasses
import random
import time
from typing import Optional, Sequence

from mpi_opt_tpu.algorithms.base import Algorithm
from mpi_opt_tpu.backends.base import Backend
from mpi_opt_tpu.health import heartbeat, shutdown
from mpi_opt_tpu.health.shutdown import SweepInterrupted
from mpi_opt_tpu.ledger.store import result_from_record
from mpi_opt_tpu.obs import trace
from mpi_opt_tpu.utils import profiling
from mpi_opt_tpu.trial import Trial, TrialResult
from mpi_opt_tpu.utils.metrics import MetricsLogger, null_logger


@dataclasses.dataclass
class SearchResult:
    best: Optional[Trial]
    n_trials: int
    wall_s: float
    trials_per_sec_per_chip: float
    # evaluations actually run by this call: >= n_trials for multi-rung
    # algorithms (each ASHA promotion re-enters the backend), and the
    # numerator of trials_per_sec_per_chip
    n_evals: int = 0
    # final per-status failure tallies for this call (post-retry)
    n_failed: int = 0
    n_timeout: int = 0
    n_retried: int = 0
    # ledger-layer tallies: results served without touching the backend
    # (journal replay on resume / exact-match cache), disjoint from
    # n_evals so throughput never counts un-run work
    n_replayed: int = 0
    n_cache_hits: int = 0


@dataclasses.dataclass(frozen=True)
class FailurePolicy:
    """How the driver treats non-ok trial results.

    Retries re-enter ``backend.evaluate`` for just the failed trials, up
    to ``max_retries`` times per trial, sleeping a jittered exponential
    backoff between rounds (attempt k waits ``backoff_s * 2**(k-1)``,
    scaled by up to ``backoff_jitter`` of random extra — the jitter
    keeps a fleet of retrying drivers from synchronizing against a
    shared resource). Trials still failing after the retries are
    reported to the algorithm as FINAL failures.

    ``max_failure_rate`` is the systemic-bug circuit breaker: when the
    fraction of final failures over all evaluations exceeds it (checked
    only once ``min_evals_for_abort`` evaluations exist, so a tiny
    denominator can't trip it), the sweep raises ``SweepAborted``
    instead of grinding through thousands of doomed trials. 1.0
    disables the breaker (some sweeps legitimately fail a lot).
    """

    max_retries: int = 0
    backoff_s: float = 0.1
    backoff_jitter: float = 0.5
    max_failure_rate: float = 1.0
    min_evals_for_abort: int = 20
    seed: int = 0

    def __post_init__(self):
        if self.max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {self.max_retries}")
        if not 0.0 < self.max_failure_rate <= 1.0:
            raise ValueError(
                f"max_failure_rate must be in (0, 1], got {self.max_failure_rate}"
            )

    def backoff(self, attempt: int, rng: random.Random) -> float:
        """Seconds to sleep before retry ``attempt`` (1-based)."""
        return self.backoff_s * (2 ** (attempt - 1)) * (1.0 + self.backoff_jitter * rng.random())


class SweepAborted(RuntimeError):
    """Raised when the failure fraction crosses FailurePolicy.max_failure_rate."""


class _FailureTracker:
    """Per-search retry/abort bookkeeping for one run_search call."""

    def __init__(self, policy: FailurePolicy, metrics: MetricsLogger):
        self.policy = policy
        self.metrics = metrics
        self.rng = random.Random(policy.seed)
        self.evaluated = 0  # final results seen (ok + failed)
        self.failed = 0  # final non-ok results
        self.timeout = 0
        self.retried = 0

    def evaluate(
        self, backend: Backend, batch: Sequence[Trial], on_final=None
    ) -> list[TrialResult]:
        """backend.evaluate with per-trial retries; returns FINAL results
        aligned with ``batch`` order.

        ``on_final(trial, result, attempts)`` fires once per trial with
        its post-retry FINAL result, BEFORE the abort check can raise —
        the ledger's journaling hook: an aborting batch's evaluations
        must be durable even though run_search never returns them.
        ``attempts`` is 1 + the retry rounds the trial re-entered.
        """
        results = backend.evaluate(batch)
        final = {r.trial_id: r for r in results}
        attempts = {t.trial_id: 1 for t in batch}
        if self.policy.max_retries > 0:
            by_id = {t.trial_id: t for t in batch}
            for attempt in range(1, self.policy.max_retries + 1):
                retry = [by_id[tid] for tid, r in final.items() if not r.ok]
                if not retry:
                    break
                delay = self.policy.backoff(attempt, self.rng)
                if delay > 0:
                    time.sleep(delay)
                self.retried += len(retry)
                self.metrics.count_retries(len(retry))
                self.metrics.log(
                    "trial_retry",
                    attempt=attempt,
                    of=self.policy.max_retries,
                    trials=[t.trial_id for t in retry],
                    backoff_s=round(delay, 3),
                )
                for t in retry:
                    attempts[t.trial_id] += 1
                for r in backend.evaluate(retry):
                    final[r.trial_id] = r
        out = [final[t.trial_id] for t in batch]
        if on_final is not None:
            for t, r in zip(batch, out):
                on_final(t, r, attempts[t.trial_id])
        self._account(out)
        return out

    def _account(self, results: Sequence[TrialResult]) -> None:
        self.evaluated += len(results)
        # count the batch HERE, before the abort check can raise: an
        # aborting batch's failures must not appear in the summary's
        # failure counters with their evaluations missing from `trials`
        # (operators compute failure fractions from that pair)
        self.metrics.count_trials(len(results))
        for r in results:
            if r.ok:
                continue
            self.failed += 1
            if r.status == "timeout":
                self.timeout += 1
                # a reaped deadline IS a detected stall: the evaluation
                # wedged (or its worker died) and was killed — the
                # trial-level twin of the supervisor's rank watchdog,
                # and the producer behind the summary's stalls_detected
                self.metrics.count_stalls()
            self.metrics.log(
                "trial_failed",
                trial_id=r.trial_id,
                status=r.status,
                error=r.error,
                step=r.step,
                # the phase the driver was in when the failure was
                # accounted (the stall satellite: "stalled during X",
                # not a bare reap) — None outside any span
                phase=trace.current_phase(),
            )
            self.metrics.count_failure(r.status)
        if (
            self.policy.max_failure_rate < 1.0
            and self.evaluated >= self.policy.min_evals_for_abort
            and self.failed / self.evaluated > self.policy.max_failure_rate
        ):
            msg = (
                f"sweep aborted: {self.failed}/{self.evaluated} trial "
                f"evaluations failed ({self.failed / self.evaluated:.0%} > "
                f"max_failure_rate {self.policy.max_failure_rate:.0%}) — "
                "a systemic failure, not unlucky hyperparameters"
            )
            self.metrics.log("sweep_aborted", error=msg)
            raise SweepAborted(msg)


def run_search(
    algorithm: Algorithm,
    backend: Backend,
    metrics: Optional[MetricsLogger] = None,
    max_batches: Optional[int] = None,
    checkpointer=None,
    policy: Optional[FailurePolicy] = None,
    ledger=None,
    cache=None,
) -> SearchResult:
    """Drive the suggest→evaluate→report loop to completion.

    ``checkpointer`` (utils.checkpoint.SearchCheckpointer) snapshots
    algorithm + backend state after report_batch on its cadence, so a
    killed process resumes at the last completed batch instead of
    restarting the sweep.

    ``policy`` (FailurePolicy) governs non-ok trial results: retries
    with jittered backoff first, then the FINAL result — ok or failed —
    is reported to the algorithm, and the failure-rate circuit breaker
    raises ``SweepAborted`` on systemic failure. The default policy is
    no retries and no breaker, so failed trials flow straight through
    as FAILED reports.

    ``ledger`` (ledger.store.SweepLedger, header already ensured)
    journals every FINAL result fsync-durably before it is reported,
    and REPLAYS the journal on resume: a suggested trial whose id holds
    a final record is served from the journal (params-verified) without
    touching the backend, so a killed driver resumes at the exact last
    completed trial — finer-grained than, and composable with, the
    batch-cadence ``checkpointer``. ``cache`` (ledger.cache.EvalCache)
    is the exact-match params→result memo consulted before
    ``backend.evaluate``; when a ledger is given and no cache, one is
    built from the ledger's ok records automatically. Replay beats
    cache: replay preserves the trial's recorded identity (including a
    FINAL failure), the cache only ever serves ok results to NEW points.
    """
    metrics = metrics or null_logger()
    tracker = _FailureTracker(policy or FailurePolicy(), metrics)
    replay: dict[tuple, dict] = (
        {} if ledger is None else ledger.completed_evaluations()
    )
    if cache is None and ledger is not None:
        from mpi_opt_tpu.ledger.cache import EvalCache

        cache = EvalCache(algorithm.space)
        cache.seed_from(ledger.ok_records())
    if replay:
        metrics.log("ledger_replay", completed=len(replay))

    def on_final(trial: Trial, result: TrialResult, attempts: int) -> None:
        # journal BEFORE report/abort so the record can never lag the
        # search state it will be replayed into
        if ledger is not None:
            ledger.record_trial(
                result,
                algorithm.space.canonical_params(trial.params),
                attempts=attempts,
            )
        if cache is not None:
            cache.put(trial.params, result)

    t0 = time.perf_counter()
    batches = 0
    n_run = 0  # trials evaluated by THIS run (metrics may be shared/reused)
    n_replayed = 0
    n_cache_hits = 0
    while not algorithm.finished():
        batch = algorithm.next_batch(backend.capacity)
        if not batch:
            if algorithm.finished():
                break
            raise RuntimeError(
                f"{algorithm.name}: no trials to run but search not finished "
                "(algorithm is waiting on results that were never reported)"
            )
        served: dict[int, TrialResult] = {}
        pending: list[Trial] = []
        for t in batch:
            rec = replay.pop((t.trial_id, int(t.budget)), None)
            if rec is not None:
                _verify_replay(algorithm.space, t, rec, ledger)
                served[t.trial_id] = result_from_record(rec)
                n_replayed += 1
                metrics.count_replayed()
                continue
            if cache is not None:
                hit = cache.get(t.params, t.budget, t.trial_id)
                if hit is not None:
                    served[t.trial_id] = hit
                    n_cache_hits += 1
                    metrics.count_cache_hits()
                    # the hit is a FINAL ok result of THIS sweep too:
                    # journal it (cached=True, attempts=0) so a later
                    # resume replays it instead of re-consulting fate
                    if ledger is not None:
                        ledger.record_trial(
                            hit,
                            algorithm.space.canonical_params(t.params),
                            attempts=0,
                            cached=True,
                        )
                    continue
            pending.append(t)
        if pending:
            profiling.launch_tick()
            # tracker.evaluate owns metrics.count_trials for the batch
            # (it must tally even a batch whose abort check raises) and
            # fires on_final per trial before that check. The train span
            # is the driver path's launch-equivalent: backend.evaluate
            # blocks until the batch's results exist, so dur_s is real
            # batch wall (retries included); per-trial journal spans
            # nest inside it via on_final
            with trace.span("train", batch=batches + 1, members=len(pending)):
                for r in tracker.evaluate(backend, pending, on_final=on_final):
                    served[r.trial_id] = r
        algorithm.report_batch([served[t.trial_id] for t in batch])
        n_run += len(pending)
        best = algorithm.best()
        metrics.log(
            "batch",
            algo=algorithm.name,
            backend=backend.name,
            size=len(batch),
            evaluated=len(pending),
            best_score=None if best is None else round(best.score, 6),
        )
        batches += 1
        saved = False
        if checkpointer is not None:
            saved = checkpointer.maybe_save(batches, algorithm, backend)
        # the rank's liveness pulse: one beat per completed batch (the
        # launch supervisor's stall watchdog times the gaps between
        # these). No-op unless the process configured --heartbeat-file.
        heartbeat.beat(stage="driver", batches=batches, trials=algorithm.n_trials)
        # cooperative-slice point (the driver-path twin of the fused
        # launch_boundary's): a service slice hook may set the drain
        # flag this very boundary honors. Only batches that EVALUATED
        # something tick the hook — a replay/cache-served batch costs no
        # device time, and counting it would livelock a resumed slice
        # (every slice re-replays the journal, spends its whole budget
        # on free batches, and parks with zero new progress, forever).
        # A finished sweep never drains, matching the fused final=True
        # rule below.
        if pending and not algorithm.finished():
            shutdown.poll_slice(f"batch {batches}")
        if shutdown.requested() and not algorithm.finished():
            # graceful-shutdown drain point: the in-flight batch is done
            # and journaled (the ledger fsyncs per record); force an
            # off-cadence snapshot so --resume loses nothing, then hand
            # the preemption up to the CLI's EX_TEMPFAIL exit. A batch
            # that COMPLETED the sweep exits normally instead — same
            # rule as the fused launch_boundary's final=True: finishing
            # strictly dominates preempting a finished sweep
            if checkpointer is not None and not saved:
                checkpointer.save(batches, algorithm, backend)
            metrics.log(
                "preempt_drain",
                signal=shutdown.active_signal(),
                batches=batches,
                trials=algorithm.n_trials,
            )
            raise SweepInterrupted(
                shutdown.active_signal(), at=f"batch {batches}"
            )
        if max_batches is not None and batches >= max_batches:
            break
    if replay and algorithm.finished():
        # journal records the resumed algorithm never re-suggested: not
        # fatal (the search completed), but operators should know the
        # ledger holds trials this configuration no longer produces
        metrics.log(
            "ledger_replay_unconsumed", trials=sorted({tid for tid, _step in replay})
        )
    wall = time.perf_counter() - t0
    return SearchResult(
        best=algorithm.best(),
        n_trials=algorithm.n_trials,
        wall_s=wall,
        trials_per_sec_per_chip=n_run / max(wall, 1e-9) / metrics.n_chips,
        n_evals=n_run,
        n_failed=tracker.failed - tracker.timeout,
        n_timeout=tracker.timeout,
        n_retried=tracker.retried,
        n_replayed=n_replayed,
        n_cache_hits=n_cache_hits,
    )


def _verify_replay(space, trial: Trial, rec: dict, ledger) -> None:
    """A replayed record must describe the SAME point the resumed
    algorithm re-suggested under that trial id — algorithms re-derive
    their suggestion streams deterministically from (seed, reports), so
    a mismatch means the ledger belongs to a different configuration
    than the header check could see (e.g. a code change shifted the
    stream) and replaying it would corrupt the search."""
    if space.params_key(trial.params) != space.params_key(rec["params"]):
        from mpi_opt_tpu.ledger.store import LedgerError

        raise LedgerError(
            f"ledger replay diverged at trial {trial.trial_id}: journal "
            f"records params {rec['params']} but the resumed search "
            f"suggested {space.canonical_params(trial.params)} — the "
            f"ledger{'' if ledger is None else ' ' + ledger.path} was "
            "written by a different suggestion stream; resume with the "
            "original configuration or start a fresh ledger"
        )
