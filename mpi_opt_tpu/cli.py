"""CLI + config system (SURVEY.md §2 row 1).

Reference contract (BASELINE.json north_star): named algorithm
selection, ``--backend=tpu`` opt-in with the CPU path as default,
population/trial counts, workload selection.

Example (config 1, the minimum end-to-end slice):
    python -m mpi_opt_tpu --workload digits --algorithm random \
        --trials 16 --budget 100 --backend cpu --workers 4
"""

from __future__ import annotations

import argparse
import json
import sys

from mpi_opt_tpu.algorithms import ALGORITHMS, get_algorithm
from mpi_opt_tpu.backends import available_backends, get_backend
from mpi_opt_tpu.driver import run_search
from mpi_opt_tpu.health import SweepInterrupted
from mpi_opt_tpu.health import heartbeat as _heartbeat
from mpi_opt_tpu.health import shutdown as _shutdown
from mpi_opt_tpu.obs import trace as _trace
from mpi_opt_tpu.ops.pbt import PBTConfig
from mpi_opt_tpu.utils import integrity, resources
from mpi_opt_tpu.utils.compile_cache import keyed_by_names, wire_compile_cache
from mpi_opt_tpu.utils.exitcodes import EX_DATAERR, EX_IOERR, EX_TEMPFAIL
from mpi_opt_tpu.utils.integrity import NoVerifiedSnapshotError
from mpi_opt_tpu.utils.metrics import stdout_logger
from mpi_opt_tpu.workloads import available, get_workload


def _wire_integrity_observer(metrics):
    """Route snapshot-corruption events (utils/integrity.py) into this
    run's metrics stream: each ``snapshot_corrupt`` becomes a logged
    event plus one tick of the ``snapshots_quarantined`` counter. The
    observer is process-global (fused trainers build checkpointers deep
    inside the sweep, far from any metrics handle); main() clears it on
    the way out so in-process callers see no residue."""

    def observe(event, **fields):
        metrics.log(event, **fields)
        if event == "snapshot_corrupt":
            metrics.count_quarantined()

    integrity.set_observer(observe)


def _wire_resource_observer(metrics):
    """Route resource-exhaustion events (utils/resources.py) into this
    run's metrics stream: oom_backoff / wave_resized / snapshot_pruned
    become logged events plus their summary counters. Process-global
    like the integrity observer (the wave scheduler and checkpoint
    layer run deep inside fused sweeps, far from any metrics handle);
    main() clears it on the way out."""

    def observe(event, **fields):
        metrics.log(event, **fields)
        if event == "oom_backoff":
            metrics.count_oom_backoffs()
        elif event == "wave_resized":
            metrics.count_wave_resized()
        elif event == "snapshot_pruned":
            metrics.count_pruned()

    resources.set_observer(observe)


def _resource_exit(e, metrics, kind: str, **summary_fields) -> int:
    """The resource-exhaustion park (utils/resources.py): a device OOM
    with no wave left to halve, or a disk still full after the one
    retention-prune retry. Durable state is INTACT (unlike exit 65 —
    the failed write never landed and the newest verified step was
    never touched), but a retry without operator action re-fails
    identically — so exit EX_IOERR (74): launch.py aborts with
    diagnostics, budget untouched; the service scheduler PARKS the
    tenant, and freeing the resource + ``--resume`` recovers."""
    metrics.summary(final=True)
    print(json.dumps({"resource_exhausted": str(e), "kind": kind, **summary_fields}))
    hint = (
        "free disk space, then relaunch with --resume"
        if kind == "storage_full"
        else "reduce residency: --wave-size auto (wave mode backs off "
        "automatically via --oom-backoff), smaller --population, or "
        "--member-chunk"
    )
    print(f"{e}\n({hint}; exit {EX_IOERR})", file=sys.stderr)
    return EX_IOERR


def _data_error_exit(e, metrics, **summary_fields) -> int:
    """The corruption-dead-end exit: no verified snapshot remains, so a
    retry would re-read the same poisoned state. Summarize, print the
    single-JSON-line shape, and exit EX_DATAERR (65) — the code
    launch.py classifies as NON-retryable (abort with diagnostics
    instead of burning the restart budget)."""
    metrics.summary(final=True)
    print(json.dumps({"data_error": str(e), **summary_fields}))
    print(
        f"{e}\n(no retry can help: exit {EX_DATAERR})",
        file=sys.stderr,
    )
    return EX_DATAERR


def pin_platform(platform, local_devices, error) -> None:
    """Validate and apply the pre-backend-init platform pin — the ONE
    implementation for the flat CLI and ``serve`` bring-up (``error`` is
    ``parser.error``-shaped: prints usage and exits 2). Must run before
    anything touches the XLA backend."""
    if platform is None and local_devices is None:
        return
    if local_devices is not None:
        if platform != "cpu":
            error("--local-devices requires --platform cpu")
        if local_devices < 1:
            error(f"--local-devices must be >= 1, got {local_devices}")
    import jax

    try:
        jax.config.update("jax_platforms", platform)
        if local_devices is not None:
            jax.config.update("jax_num_cpu_devices", local_devices)
    except RuntimeError as e:
        error(
            f"--platform/--local-devices must be set before any JAX "
            f"use in this process: {e}"
        )


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="mpi_opt_tpu",
        description="TPU-native hyperparameter optimization",
    )
    p.add_argument("--workload", required=True, choices=available())
    p.add_argument("--algorithm", default="random", choices=sorted(ALGORITHMS))
    p.add_argument(
        "--backend",
        default="cpu",
        choices=available_backends(),
        help="execution backend (cpu is the default path; tpu is opt-in)",
    )
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--trials", type=int, default=16, help="total trials (random/tpe/asha)")
    p.add_argument("--budget", type=int, default=100, help="steps per trial (random/tpe)")
    p.add_argument("--workers", type=int, default=0, help="cpu backend: processes (0=auto)")
    p.add_argument("--metrics-file", default=None, help="JSONL metrics output path")
    # durable sweep ledger (ledger/ package; see README: sweep ledger)
    p.add_argument(
        "--ledger",
        default=None,
        metavar="PATH",
        help="journal every FINAL result to this JSONL file (fsync'd "
        "per record). Driver path: one record per completed trial; "
        "--fused: one record per population member at every natural "
        "boundary (PBT generation, SHA/BOHB rung, TPE batch), written "
        "before the boundary's snapshot. With --resume, completed "
        "records are replayed (driver) or verified against the "
        "re-trained boundaries (fused) so a killed sweep resumes with "
        "an identical journal, and the driver's exact-match params "
        "cache skips re-evaluating recorded-ok points",
    )
    p.add_argument(
        "--warm-start",
        default=None,
        metavar="PATH|auto:DIR",
        help="feed PRIOR sweep evidence into this sweep as observations "
        "before the search starts (TPE/BOHB build surrogate priors — "
        "fused TPE pre-fills its on-device ring; random/asha/pbt seed "
        "with the prior best). A PATH names one prior ledger (CROSS-"
        "MODE: a fused ledger warm-starts a driver sweep and vice "
        "versa; the only gate is the space hash). 'auto:DIR' resolves "
        "through DIR's corpus index instead (`corpus index DIR`): "
        "every exact-space-hash ledger merges in (dedup by canonical "
        "params, newest wins) and fuzzy-matched same-workload ledgers "
        "enter down-weighted at budget 0; stale index entries degrade "
        "to corpus_skip events, never errors",
    )
    # checkpoint/resume (SURVEY.md §2 row 13, §5)
    p.add_argument(
        "--checkpoint-dir",
        default=None,
        help="durable search checkpoints (orbax) written here after each batch",
    )
    p.add_argument(
        "--checkpoint-every", type=int, default=1, help="batches between checkpoints"
    )
    p.add_argument(
        "--resume",
        action="store_true",
        help="resume from the latest checkpoint in --checkpoint-dir "
        "(starts fresh if the directory is empty)",
    )
    p.add_argument(
        "--profile-dir",
        default=None,
        help="capture a jax.profiler trace of the search loop here "
        "(TensorBoard-loadable)",
    )
    p.add_argument(
        "--profile-launches",
        default=None,
        metavar="N|A:B",
        help="with --profile-dir: profile only this launch window "
        "(1-based, inclusive — fused launches/rungs/generations, or "
        "driver batches) instead of the whole run; e.g. 2:3 skips the "
        "cold-compile first launch so the XLA trace shows steady state",
    )
    # span tracing (obs/; see README: Observability)
    p.add_argument(
        "--trace",
        action="store_true",
        help="emit span records (compile/train/staging/boundary/save/"
        "journal phase durations, obs/trace.py) into the metrics stream "
        "— give --metrics-file and render with `mpi_opt_tpu trace FILE`. "
        "Off by default: an untraced sweep does zero tracing work",
    )
    # ASHA
    p.add_argument("--min-budget", type=int, default=10)
    p.add_argument("--max-budget", type=int, default=270)
    p.add_argument("--eta", type=int, default=3)
    # PBT
    p.add_argument("--population", type=int, default=32)
    p.add_argument("--generations", type=int, default=10)
    p.add_argument("--steps-per-generation", type=int, default=200)
    p.add_argument("--truncation", type=float, default=0.25)
    # fused on-device sweeps (train/fused_pbt.py, train/fused_asha.py)
    p.add_argument(
        "--fused",
        action="store_true",
        help="run the whole sweep on-device (random/pbt/asha/hyperband/"
        "bohb/tpe): no driver round-trips, population never leaves the "
        "device; --checkpoint-dir makes it crash-recoverable (pbt: "
        "launch granularity, asha/hyperband/bohb: rung granularity, "
        "tpe: generation granularity)",
    )
    p.add_argument(
        "--member-chunk",
        type=int,
        default=0,
        help="fused: process members in chunks of this size "
        "(activation-memory relief for big populations)",
    )
    p.add_argument(
        "--gen-chunk",
        type=int,
        default=0,
        help="fused pbt: generations per program launch (bit-identical "
        "split; needed where single programs are time-limited)",
    )
    p.add_argument(
        "--step-chunk",
        type=int,
        default=0,
        help="fused pbt: max training steps per launch WITHIN a "
        "generation (for populations whose single-generation program "
        "exceeds the platform's execution window; deterministic, "
        "checkpoint-guarded, not bit-identical to unchunked)",
    )
    p.add_argument(
        "--wave-size",
        default="0",
        metavar="N|auto",
        help="fused sweeps (any algorithm): cohort > device residency — "
        "train resident waves of N members per generation/rung/batch, "
        "staging cold members on host between waves (double-buffered "
        "async transfers overlap wave compute); the boundary op "
        "(exploit, rung cut, re-suggest) still runs over the FULL "
        "cohort. 'auto' sizes the wave from a residency estimate; 0 "
        "disables (fully resident). Bit-identical to resident mode on "
        "the CPU backend (tested); see README 'Wave scheduling'",
    )
    p.add_argument(
        "--oom-backoff",
        type=int,
        default=2,
        metavar="N",
        help="fused wave mode (any algorithm): on a device OOM (XLA "
        "RESOURCE_EXHAUSTED), automatically halve the wave size and "
        "re-run the generation/rung/batch — bit-identical at any wave "
        "size — up to N times (0 disables). Also pre-clamps an "
        "explicit --wave-size against the measured device budget. "
        "Resident-mode and post-budget OOMs exit 74 (classified, "
        "non-retryable)",
    )
    p.add_argument(
        "--objectives",
        default=None,
        metavar="SPEC",
        help="fused pbt/asha: multi-objective search, e.g. "
        '"accuracy:max,params:min<=2e4" — comma-separated '
        "name:direction terms, each optionally constrained (<= for min, "
        ">= for max). Boundary selection runs on the Pareto front "
        "(non-dominated sort + crowding) inside the compiled boundary "
        "op; constrained sweeps pick the best FEASIBLE member, "
        "degrading (typed, never a crash) to the least-violating one "
        "when nothing is feasible. The ledger journals each member's "
        "objective vector beside the scalarized primary score; see "
        "README 'Multi-objective search'",
    )
    # multi-host bring-up (SURVEY.md §2 row 1 + §5): the reference's
    # ``mpirun`` launch WAS its user surface; the CLI owns SPMD bring-up
    # the same way — one OS process per host, each invoking this CLI
    # with its rank, called BEFORE any backend/mesh construction
    # (jax.distributed must initialize before the XLA backend exists)
    p.add_argument(
        "--coordinator",
        default=None,
        metavar="HOST:PORT",
        help="multi-process SPMD: the rank-0 coordinator address. Give "
        "together with --num-processes/--process-id on every rank "
        "(the mpirun-equivalent launch); on TPU pods --multihost alone "
        "auto-detects all three from pod metadata",
    )
    p.add_argument(
        "--num-processes",
        type=int,
        default=None,
        help="multi-process SPMD: total process count (with --coordinator)",
    )
    p.add_argument(
        "--process-id",
        type=int,
        default=None,
        help="multi-process SPMD: this process's rank (with --coordinator)",
    )
    p.add_argument(
        "--multihost",
        action="store_true",
        help="bring up jax.distributed via cluster auto-detection (TPU "
        "pod metadata); fails rather than silently running "
        "single-process. Implied by --coordinator",
    )
    p.add_argument(
        "--platform",
        default=None,
        choices=["cpu", "tpu"],
        help="pin the jax platform (the JAX_PLATFORMS environment "
        "variable does the same); cpu + --local-devices N gives an "
        "N-device virtual host for debugging SPMD launches off-pod",
    )
    p.add_argument(
        "--local-devices",
        type=int,
        default=None,
        help="with --platform cpu: virtual device count for this process",
    )
    # mesh / multi-chip (SURVEY.md §2 row 9: the communication layer,
    # reachable from the user surface)
    p.add_argument(
        "--n-data",
        type=int,
        default=1,
        help="mesh 'data' axis size: within-member data parallelism "
        "(gradient all-reduce over ICI). Devices are split as "
        "(devices/n_data) x n_data",
    )
    p.add_argument(
        "--n-pop",
        type=int,
        default=0,
        help="mesh 'pop' axis size (0 = all remaining devices). "
        "Population/trial parallelism axis",
    )
    p.add_argument(
        "--no-mesh",
        action="store_true",
        help="disable the automatic ('pop','data') mesh on multi-device "
        "hosts (run single-device)",
    )
    # failure recovery (SURVEY.md §5): accelerator runtimes can die
    # mid-sweep (worker crash, preemption); fused sweeps are
    # crash-recoverable via --checkpoint-dir, and --retries closes the
    # loop by resuming automatically
    p.add_argument(
        "--retries",
        type=int,
        default=0,
        help="fused: auto-retry the sweep this many times on a TRANSIENT "
        "runtime failure (worker crash/restart, unavailable, deadline). "
        "With --checkpoint-dir each retry resumes at the last snapshot; "
        "without, it restarts the (deterministic) sweep from scratch",
    )
    # per-trial failure policy (driver path; SURVEY.md §5): --retries
    # above recovers whole-SWEEP platform deaths, these recover
    # individual trials — the normal HPO failure mode (extreme
    # hyperparameters are part of the search space)
    p.add_argument(
        "--trial-retries",
        type=int,
        default=0,
        help="driver path: re-evaluate a failed/timed-out trial up to "
        "this many times (jittered exponential backoff between "
        "attempts) before reporting it as failed",
    )
    p.add_argument(
        "--trial-timeout",
        type=float,
        default=None,
        metavar="SECONDS",
        help="cpu backend: per-trial evaluation deadline; a trial still "
        "running past it is reaped as a 'timeout' result and its worker "
        "pool recycled (unset = wait forever)",
    )
    p.add_argument(
        "--max-failure-rate",
        type=float,
        default=1.0,
        metavar="FRAC",
        help="driver path: abort the sweep once more than this fraction "
        "of trial evaluations has failed (checked after 20 evaluations; "
        "1.0 disables). Catches systemic bugs fast instead of grinding "
        "through thousands of doomed trials",
    )
    p.add_argument(
        "--chaos",
        default=None,
        metavar="SPEC",
        help="fault-injection drill (driver path): wrap the workload in "
        "seeded chaos, e.g. 'exc=0.1,nan=0.05,hang=0.02,slow=0.1,seed=7' "
        "(probabilities per fault; preempt= drills the graceful-shutdown "
        "protocol; hang_s=/slow_s= tune durations). Faults are a "
        "deterministic function of (seed, trial params)",
    )
    # rank health (health/): graceful preemption + hang detection
    p.add_argument(
        "--isolate-stateful",
        action="store_true",
        help="cpu backend: evaluate STATEFUL workloads (PBT inheritance, "
        "ASHA warm resume) in a dedicated spawned worker holding the "
        "state store, instead of in-parent — makes --trial-timeout "
        "enforceable there (a hung trial is reaped as status=timeout "
        "and the worker respawned; its state store resets, so "
        "inheritors of lost states retrain from scratch)",
    )
    p.add_argument(
        "--heartbeat-file",
        default=None,
        metavar="PATH",
        help="write a monotonic progress beat (atomic JSON rewrite) to "
        "this file at every completed batch/launch — the liveness "
        "signal launch.py's --stall-timeout watchdog reads. The "
        "supervisor wires this per rank automatically; set manually "
        "for external watchdogs",
    )
    # multi-process SPMD boundary agreement (parallel/coord.py): every
    # rank-divergent decision (drain, wave cap, OOM halving) votes
    # through a filesystem control plane and becomes unanimous before
    # the next collective. launch.py owns these per rank, like
    # --coordinator/--heartbeat-file
    p.add_argument(
        "--coord-dir",
        default=None,
        metavar="DIR",
        help="multi-process SPMD: directory of the boundary-agreement "
        "control plane (per-rank vote files, rank-0 decisions). "
        "launch.py wires this per rank automatically; set manually "
        "only for external supervisors. Single-process runs may set it "
        "too (a world-of-1 plane agrees with itself — useful for "
        "protocol drills)",
    )
    p.add_argument(
        "--coord-epoch",
        type=int,
        default=0,
        metavar="N",
        help="with --coord-dir: the job attempt's vote namespace. Each "
        "coordinated restart must use a FRESH epoch (launch.py passes "
        "its relaunch counter) — a reused epoch is refused, stale "
        "votes from a killed attempt must be unreadable",
    )
    p.add_argument(
        "--coord-timeout",
        type=float,
        default=300.0,
        metavar="SECONDS",
        help="with --coord-dir: how long a rank waits at an agreement "
        "boundary for its peers before declaring the collective wedged "
        "(CoordWedged -> nonzero exit -> the supervisor's coordinated "
        "restart). Size above the longest legitimate gap between "
        "boundaries, like --stall-timeout",
    )
    p.add_argument(
        "--rank-kill",
        default=None,
        metavar="SPEC",
        help="chaos drill: SIGKILL a chosen rank at a chosen boundary "
        "— 'rank=R,at=K[,n=N][,marker=PATH]' dies hard at the K-th "
        "(1-based) launch/rung/generation boundary on the rank whose "
        "process index is R. marker makes the kill one-shot across "
        "coordinated restarts (fire only if PATH does not exist). "
        "Exercises the collective-wedge escalation end to end",
    )
    # the suggestion service (corpus/serve.py): instead of running a
    # sweep, answer suggest/report/lookup traffic for EXTERNAL sweeps
    p.add_argument(
        "--suggest-serve",
        default=None,
        metavar="DIR",
        help="run as a resident suggestion server over this filesystem "
        "spool instead of sweeping: answers suggest/report/lookup "
        "requests (`suggest-client`) from the batched TPE acquisition "
        "kernel over --workload's space, warm-started via --warm-start "
        "(incl. auto:DIR). Submittable to the sweep service unchanged "
        "— every served request is a natural boundary, so `serve` "
        "time-slices it like a sweep; with --ledger every report "
        "journals and --resume rebuilds the ring",
    )
    p.add_argument(
        "--suggest-idle-timeout",
        type=float,
        default=None,
        metavar="S",
        help="with --suggest-serve: exit 0 (done) after S seconds with "
        "no requests (unset = stay resident until `suggest-client stop` "
        "or a drain)",
    )
    # the HTTP front door (service/http.py): put a batched, overload-
    # safe REST endpoint in front of the suggestion server
    p.add_argument(
        "--http-port",
        type=int,
        default=None,
        metavar="PORT",
        help="with --suggest-serve: serve the HTTP front door on this "
        "port instead of the filesystem request spool (0 = ephemeral; "
        "the bound port publishes atomically to DIR/control/http.json). "
        "Batched ops share one journal fsync; overload sheds with typed "
        "503s; idempotency keys make client retries exactly-once",
    )
    p.add_argument(
        "--http-queue",
        type=int,
        default=64,
        metavar="N",
        help="with --http-port: admission-queue bound — requests beyond "
        "it shed with 503 + Retry-After instead of queueing unboundedly",
    )
    p.add_argument(
        "--http-state-dir",
        default=None,
        metavar="DIR",
        help="with --http-port: also expose the sweep service's "
        "submit/status/cancel ops over HTTP against this service state "
        "dir (the spool stays the durability layer; fencing tokens "
        "stay the authority)",
    )
    return p


_TRANSIENT_MARKERS = (
    "crashed",
    "restarted",
    "unavailable",
    "deadline",
    "socket closed",
    "connection reset",
    # NOT "cancelled": when an async op fails, the runtime reports its
    # dependents as CANCELLED — retrying one of those secondary errors
    # would re-run a genuine program bug N times
)


def _is_transient(e: BaseException) -> bool:
    """Platform-failure heuristic: retry-worthy errors name the runtime
    dying, not the program being wrong (a shape error or OOM retried N
    times is N identical failures).

    Two gates, both required: the exception TYPE must be one the
    accelerator runtime actually raises (JaxRuntimeError — the class a
    worker's crash/unavailable/deadline errors arrive as — or a
    transport-layer OSError), and its message must name the runtime
    dying. Type-first keeps a program error that merely QUOTES a marker
    (a dataset path containing 'unavailable', a user exception citing a
    'deadline') from being retried N times (ADVICE r4)."""
    import jax.errors

    # sweeplint: disable=resource-funnel -- deliberate: this is the TRANSIENT platform-death classifier (crashed/unavailable/deadline), disjoint from the OOM funnel — its markers exclude RESOURCE_EXHAUSTED, and DeviceOOM never reaches here (classified before the retry loop)
    if not isinstance(e, (jax.errors.JaxRuntimeError, OSError)):
        return False
    return any(m in str(e).lower() for m in _TRANSIENT_MARKERS)


def _wire_trace(args, metrics):
    """Install this run's MetricsLogger as the span sink (obs/trace.py)
    when --trace is set; returns the prior trace state (restored by
    main's finally) or None when tracing is off. Rank tags come from
    jax.process_index() under SPMD so multi-rank streams merge
    attributably; the tenant tag comes from the service scheduler's
    ``MPI_OPT_TPU_TRACE_TAG`` env around each slice."""
    if not args.trace:
        return None
    import os

    rank = 0
    if args.multihost or args.coordinator is not None:
        import jax

        rank = jax.process_index()
    return _trace.configure(
        metrics, rank=rank, tenant=os.environ.get("MPI_OPT_TPU_TRACE_TAG")
    )


def _run_with_retries(launch, retries: int, metrics):
    """Run ``launch()``; on a transient runtime failure, retry up to
    ``retries`` times. Callers pass a closure over a fused sweep whose
    checkpoint machinery (if enabled) turns each retry into a resume —
    the automatic form of the kill-and-rerun recovery the snapshot
    tests prove by hand."""
    attempt = 0
    while True:
        try:
            return launch()
        except Exception as e:
            if attempt >= retries or not _is_transient(e):
                if attempt:  # the retries were burned: record what won
                    metrics.log(
                        "retry_exhausted",
                        attempts=attempt,
                        error=f"{type(e).__name__}: {e}"[:1000],
                    )
                raise
            attempt += 1
            metrics.log(
                "retry",
                attempt=attempt,
                of=retries,
                error=f"{type(e).__name__}: {e}"[:300],
            )


def build_mesh(args):
    """The run's device mesh, or None for plain single-device execution.

    Auto-meshes whenever more than one device is visible (a v4-32 user
    typing ``--fused`` gets all 32 chips without extra flags); explicit
    ``--n-data``/``--n-pop`` force a mesh shape, ``--no-mesh`` opts out.
    """
    if args.no_mesh:
        if args.n_data > 1 or args.n_pop > 0:
            raise SystemExit("--no-mesh contradicts --n-data/--n-pop")
        return None
    import jax

    if jax.device_count() > 1 or args.n_data > 1 or args.n_pop > 0:
        from mpi_opt_tpu.parallel.mesh import make_mesh

        return make_mesh(n_pop=args.n_pop or None, n_data=args.n_data)
    return None


def make_algorithm(args, space):
    cls = get_algorithm(args.algorithm)
    if args.algorithm == "random":
        return cls(space, seed=args.seed, max_trials=args.trials, budget=args.budget)
    if args.algorithm == "tpe":
        return cls(space, seed=args.seed, max_trials=args.trials, budget=args.budget)
    if args.algorithm == "asha":
        return cls(
            space,
            seed=args.seed,
            max_trials=args.trials,
            min_budget=args.min_budget,
            max_budget=args.max_budget,
            eta=args.eta,
        )
    if args.algorithm in ("hyperband", "bohb"):
        return cls(space, seed=args.seed, max_budget=args.max_budget, eta=args.eta)
    if args.algorithm == "pbt":
        return cls(
            space,
            seed=args.seed,
            population=args.population,
            generations=args.generations,
            steps_per_generation=args.steps_per_generation,
            config=PBTConfig(truncation_frac=args.truncation),
        )
    raise AssertionError(args.algorithm)


def _device_record() -> dict:
    """The devices this process's programs ran on, as jax reports them.
    Every sweep summary carries it: ``--backend tpu`` and ``--fused``
    run on whatever jax finds (XLA:CPU on a machine without a chip, and
    tests rely on that), so the record itself must say which it was —
    a CPU run can then never be read as a chip record."""
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}


def _finite_or_null(obj):
    """Summary-layer JSON hygiene: ``json.dumps`` emits bare ``NaN`` /
    ``Infinity`` tokens for non-finite floats — invalid JSON per the
    spec, breaking the documented single-JSON-line contract for strict
    (non-Python) parsers. An all-diverged fused sweep produces exactly
    that: best_score NaN, and NaN entries in the curves (a generation
    whose every member diverged has ``scores.max() == NaN``). Replace
    non-finite floats with None recursively HERE, at the serialization
    boundary — the result dicts keep their NaNs so library callers can
    still detect divergence numerically."""
    import math

    if isinstance(obj, float):
        return obj if math.isfinite(obj) else None
    if isinstance(obj, dict):
        return {k: _finite_or_null(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_finite_or_null(v) for v in obj]
    return obj


def _has_snapshot(directory) -> bool:
    """Does an orbax sweep snapshot already live under ``directory``?

    Orbax lays out one numeric step directory per save (hyperband nests
    them under per-bracket dirs), each holding a ``_CHECKPOINT_METADATA``
    file once the save committed. Requiring BOTH the digit name and the
    metadata marker keeps unrelated numeric directories sharing the tree
    (e.g. profiler output ``plugins/profile/2026_07_30/``) from
    false-positiving a fresh sweep into a hard "pass --resume" error.
    """
    import os

    if not directory or not os.path.isdir(directory):
        return False
    for root, dirs, _files in os.walk(directory):
        for d in dirs:
            if d.isdigit() and os.path.exists(
                os.path.join(root, d, integrity.COMMIT_MARKER)
            ):
                return True
    return False


def _resolve_warm_start(args, space, metrics, parser):
    """ONE home for ``--warm-start`` resolution (ISSUE 14 satellite:
    the load/validate block used to be written twice — fused and driver
    — and the realpath self-warm-start guard protected only the flat
    main() flow; now every path, the ``auto:`` corpus resolution and
    the suggestion tenant included, flows through here).

    Returns ``(warm_obs, warm_info)``: the observations to ingest and
    the event-payload dict (``sources`` naming every contributing
    ledger with its match kind, ``skips`` counting per-record losses).
    Usage errors (bad path, space-hash mismatch, self-warm-start,
    malformed auto spec) surface as ``parser.error`` — exit 2, before
    any durable state is touched."""
    import os

    from mpi_opt_tpu.ledger import LedgerError

    spec = args.warm_start
    if spec == "auto" or spec.startswith("auto:"):
        corpus_dir = spec[len("auto:"):] if spec.startswith("auto:") else ""
        if not corpus_dir:
            parser.error(
                "--warm-start auto needs a corpus root: --warm-start auto:DIR"
            )
        if not os.path.isdir(corpus_dir):
            parser.error(
                f"--warm-start auto: {corpus_dir!r} is not a directory"
            )
        from mpi_opt_tpu.corpus.resolve import resolve

        # exclude= is the auto-path self-warm-start guard: this run's
        # own --ledger may already live under the corpus root
        res = resolve(
            space,
            corpus_dir,
            workload=args.workload,
            exclude=args.ledger,
            metrics=metrics,
        )
        # degraded whole entries already surfaced as corpus_skip events
        # inside resolve(); the warm_start payload carries the sources
        # that DID contribute plus the per-record loss counters
        return res.observations, {
            "sources": res.sources,
            "skips": res.skips or None,
        }
    # plain path: one PRIOR ledger. realpath: './sweep.jsonl' vs
    # 'sweep.jsonl' (or a symlink) is still self-feeding — this run's
    # journal is not a prior sweep
    if args.ledger and os.path.realpath(spec) == os.path.realpath(args.ledger):
        parser.error(
            "--warm-start must name a PRIOR sweep's ledger, not this "
            "run's --ledger (resuming this sweep is --ledger --resume)"
        )
    from mpi_opt_tpu.ledger.warmstart import load_observations

    try:
        obs, skips = load_observations(spec, space)
    except (LedgerError, OSError) as e:
        parser.error(f"--warm-start: {e}")
    return obs, {
        "sources": [{"path": spec, "match": "exact", "records": len(obs)}],
        "skips": skips or None,
    }


def _log_warm_start(metrics, args, warm_info, observations: int) -> None:
    """The one ``warm_start`` event shape, shared by every path:
    ``observations`` is what actually informed the search (the
    algorithm's own count where one exists), ``sources`` names the
    chosen ledgers, ``skipped`` carries the per-record loss counters
    instead of letting the list silently shrink."""
    metrics.log(
        "warm_start",
        path=args.warm_start,
        observations=observations,
        sources=(warm_info or {}).get("sources"),
        skipped=(warm_info or {}).get("skips"),
    )


def run_fused(args, parser, workload) -> int:
    """--fused: the whole sweep as on-device programs, no driver loop.

    PBT maps to train.fused_pbt (generation scan, exploit/explore and
    winner gathers on-device, optional crash-recovery snapshots); ASHA
    maps to train.fused_asha (synchronous successive halving, rung cuts
    as on-device top_k). Emits the same summary JSON shape as the
    driver path so downstream tooling doesn't care which path ran.
    """
    import time

    from mpi_opt_tpu.utils.profiling import profile_window
    from mpi_opt_tpu.workloads.base import PopulationWorkload

    if not isinstance(workload, PopulationWorkload):
        parser.error(f"--fused requires a population workload, not {args.workload!r}")
    # getattr: main() parsed --objectives; direct in-process callers
    # (tests) may hand a namespace without it
    objectives = getattr(args, "objective_spec", None)
    if objectives is not None:
        supported = tuple(workload.objective_metrics())
        missing = [n for n in objectives.names if n not in supported]
        if missing:
            parser.error(
                f"--objectives: workload {args.workload!r} cannot evaluate "
                f"{missing}; supported metrics: {list(supported)}"
            )
    if args.retries:
        import jax

        if jax.process_count() > 1:
            # a per-process retry under multi-process SPMD is unsound:
            # one process restoring a snapshot while its peers sit in a
            # collective issues mismatched programs and hangs the job.
            # Recovery there is job-level: rerun (snapshots resume it).
            parser.error(
                "--retries requires a single-process run; under "
                "multi-process SPMD recovery is a coordinated job "
                "restart — run under `python -m mpi_opt_tpu.launch "
                "--retries N`, which relaunches ALL ranks with "
                "--resume and a fresh --coord-epoch"
            )
    # resuming is explicit opt-in, matching the driver path: a stale
    # checkpoint dir must not silently replay an old sweep (ADVICE r2)
    if args.checkpoint_dir and not args.resume and _has_snapshot(args.checkpoint_dir):
        parser.error(
            f"--checkpoint-dir {args.checkpoint_dir!r} already holds a sweep "
            "snapshot; pass --resume to continue it, or point at a fresh "
            "directory"
        )

    mesh = build_mesh(args)
    # PBT/TPE keep a standing --population cohort for the whole sweep:
    # a non-dividing population would replicate on every device (see
    # parallel.mesh.shard_popstate) and silently run effectively
    # single-device — fail up front with the fix spelled out. SHA-family
    # sweeps instead round their shrinking cohorts to the mesh
    # (round_to), so only their first cohort may warn.
    if mesh is not None and args.algorithm in ("pbt", "tpe"):
        n_pop = int(mesh.shape["pop"])
        # only the population-exceeds-axis case is refused: sharding was
        # possible and the user plausibly expected it. A population
        # SMALLER than the axis (debug-sized run on a big mesh) can only
        # replicate, and gets the runtime warning instead of a hard stop.
        if args.population % n_pop and args.population > n_pop:
            lo = (args.population // n_pop) * n_pop
            parser.error(
                f"--population {args.population} does not divide the mesh "
                f"'pop' axis ({n_pop}); the population would be replicated "
                "on every device instead of sharded. Use --population "
                f"{lo} or {lo + n_pop}, reshape the mesh with "
                "--n-pop/--n-data, or pass --no-mesh."
            )
    # per-chip accounting divides by the devices the sweep ACTUALLY runs
    # on: the mesh's GLOBAL device count when sharded, exactly 1
    # otherwise (local_device_count would overstate the denominator on a
    # multi-chip host running --no-mesh; ADVICE round 2). Global, not
    # this process's share: under multi-host SPMD every process drives
    # the same global sweep and counts the same global trial total, so a
    # local divisor would overstate per-chip throughput by the host count.
    n_chips = int(mesh.devices.size) if mesh is not None else 1
    metrics = stdout_logger(path=args.metrics_file, n_chips=n_chips)
    _wire_integrity_observer(metrics)
    _wire_resource_observer(metrics)
    _wire_trace(args, metrics)  # restored by main's finally
    # boundary-agreement control plane (multi-process SPMD): activate
    # the plane and chain its drain agreement onto the slice hook
    # BEFORE any boundary runs; torn down in the finally below so no
    # hook/plane leaks into in-process callers' next sweep
    from mpi_opt_tpu.parallel import coord as _coord

    coord_uninstall = None
    if getattr(args, "coord_dir", None):
        import jax

        plane = _coord.CoordPlane(
            args.coord_dir,
            jax.process_index(),
            jax.process_count(),
            epoch=getattr(args, "coord_epoch", 0) or 0,
            timeout_s=getattr(args, "coord_timeout", None) or 300.0,
        )
        coord_uninstall = _coord.install_hook(plane)
    rank_kill_uninstall = None
    if getattr(args, "rank_kill", None):
        from mpi_opt_tpu.workloads.chaos import inject_rank_kill, parse_rank_kill_spec

        _, rank_kill_uninstall = inject_rank_kill(
            **parse_rank_kill_spec(args.rank_kill)
        )
    from mpi_opt_tpu.ledger import LedgerError

    space = workload.default_space()
    # the prior ledger validates BEFORE this run's own ledger header
    # commits, same rule as the driver path: a typo'd --warm-start must
    # not be journaled into a fresh ledger's identity
    warm_obs = None
    if args.warm_start:
        warm_obs, warm_info = _resolve_warm_start(args, space, metrics, parser)
        _log_warm_start(metrics, args, warm_info, len(warm_obs))
    ledger = _open_fused_ledger(args, parser, space, metrics)
    t0 = time.perf_counter()
    try:
        # the fused launch path's device-OOM classification boundary:
        # any driver's XLA RESOURCE_EXHAUSTED arrives here as ONE type
        with resources.oom_funnel():
            return _run_fused_dispatch(
                args,
                parser,
                workload,
                mesh,
                n_chips,
                metrics,
                t0,
                ledger,
                warm_obs,
                objectives=objectives,
            )
    except resources.DeviceOOM as e:
        # deterministic for this program+population: retrying the same
        # shape re-OOMs (wave mode already spent its --oom-backoff
        # budget before this propagates) — park classified, exit 74
        return _resource_exit(
            e,
            metrics,
            "device_oom",
            workload=args.workload,
            algorithm=args.algorithm,
            backend="fused",
        )
    except resources.StorageFull as e:
        # the disk filled mid-snapshot/journal after the one
        # retention-prune retry: durable state intact, free disk +
        # --resume recovers — park classified, exit 74
        return _resource_exit(
            e,
            metrics,
            "storage_full",
            workload=args.workload,
            algorithm=args.algorithm,
            backend="fused",
        )
    except (NoVerifiedSnapshotError, LedgerError) as e:
        # both are data dead-ends: an unverifiable snapshot tree, or a
        # journal that diverges from / lags the sweep it claims to
        # record — no restart re-reads either into health, so exit 65
        # (launch.py classifies it as non-retryable)
        return _data_error_exit(
            e,
            metrics,
            workload=args.workload,
            algorithm=args.algorithm,
            backend="fused",
        )
    except SweepInterrupted as e:
        # graceful preemption: the drained launch's snapshot is flushed
        # (fused trainers force an off-cadence save before raising);
        # exit EX_TEMPFAIL so a supervisor restarts with --resume
        # without billing its --retries budget
        metrics.count_preempted()
        metrics.summary(final=True)
        print(
            json.dumps(
                {
                    "preempted": True,
                    "signal": e.signal,
                    "at": e.at,
                    "workload": args.workload,
                    "algorithm": args.algorithm,
                    "backend": "fused",
                }
            )
        )
        print(
            f"graceful shutdown ({e.signal}) at {e.at}: snapshot flushed; "
            f"relaunch with --resume to continue (exit {EX_TEMPFAIL})",
            file=sys.stderr,
        )
        return EX_TEMPFAIL
    except _coord.CoordWedged as e:
        # a peer never reached this rank's agreement boundary — the
        # collective is wedged, and only a COORDINATED restart (the
        # launch.py supervisor relaunching every rank with --resume and
        # a fresh epoch) can recover. Exit nonzero-generic so the
        # supervisor funds exactly that from its retry budget.
        metrics.summary(final=True)
        print(f"collective wedge: {e}", file=sys.stderr)
        return 1
    finally:
        if rank_kill_uninstall is not None:
            rank_kill_uninstall()
        if coord_uninstall is not None:
            coord_uninstall()
        if ledger is not None:
            ledger.close()


def _open_fused_ledger(args, parser, space, metrics):
    """Open + identity-check the fused sweep's ledger (None without
    --ledger). Mirrors the driver path's rules — rank-0-only journaling
    under multi-process SPMD, stale journals need explicit --resume —
    and commits a FUSED header: ``mode``/``granularity`` mark the
    boundary-granular record stream, and the config carries everything
    that shapes the deterministic trajectory the journal will be
    verified against on resume."""
    if not args.ledger:
        return None
    from mpi_opt_tpu.ledger import LedgerError, SweepLedger

    ledger_rank = 0
    if args.multihost or args.coordinator is not None:
        import jax

        ledger_rank = jax.process_index()
    try:
        ledger = SweepLedger(args.ledger, read_only=ledger_rank != 0)
    except LedgerError as e:
        parser.error(f"--ledger: {e}")
    if ledger.read_only:
        metrics.log("ledger_rank_gated", rank=ledger_rank)
    if ledger.records and not args.resume:
        parser.error(
            f"--ledger {args.ledger!r} already holds "
            f"{len(ledger.records)} member records; pass --resume to "
            "verify and continue them, or point at a fresh path"
        )
    config = {
        "mode": "fused",
        "granularity": {"pbt": "generation", "tpe": "batch"}.get(
            args.algorithm, "rung"
        ),
        "algorithm": args.algorithm,
        "workload": args.workload,
        "backend": "fused",
        "seed": args.seed,
        "space_hash": space.space_hash(),
        "warm_start": args.warm_start,
    }
    objectives = getattr(args, "objective_spec", None)
    if objectives is not None:
        # objective identity (names + directions + bounds) IS config:
        # resuming a ledger under different objectives would journal a
        # different selection trajectory. Scalar sweeps never write the
        # key, so every pre-existing ledger keeps resuming byte-for-byte
        config["objectives"] = args.objectives
    # the knobs that shape each algorithm's boundary/member structure
    if args.algorithm == "pbt":
        # wave_size is deliberately NOT ledger identity: wave scheduling
        # is bit-identical to resident mode, so the journal records the
        # same trajectory either way (snapshots still refuse the
        # cross-resume — that's state shape, not history)
        config.update(
            population=args.population,
            generations=args.generations,
            steps_per_generation=args.steps_per_generation,
        )
    elif args.algorithm == "tpe":
        config.update(
            trials=args.trials, batch=args.population, budget=args.budget
        )
    elif args.algorithm == "random":
        config.update(trials=args.trials, budget=args.budget)
    elif args.algorithm == "asha":
        config.update(
            trials=args.trials,
            min_budget=args.min_budget,
            max_budget=args.max_budget,
            eta=args.eta,
        )
    else:  # hyperband / bohb
        config.update(max_budget=args.max_budget, eta=args.eta)
    try:
        # space_spec rides the header top-level (not identity): the
        # corpus index fuzzy-fingerprints ledgers from it, and
        # objective_spec (ISSUE 17) rides the same way so report/corpus
        # consumers render fronts without re-parsing the config string
        ledger.ensure_header(
            config,
            space_spec=space.spec(),
            objective_spec=None if objectives is None else objectives.spec(),
        )
    except LedgerError as e:
        parser.error(f"--ledger: {e}")
    if ledger.n_torn:
        metrics.log("ledger_torn_tail_dropped", path=args.ledger)
    if ledger.n_torn_boundary:
        metrics.log(
            "ledger_torn_boundary_dropped",
            path=args.ledger,
            records=ledger.n_torn_boundary,
        )
    return ledger


def _wave_extras(res: dict) -> dict:
    """Wave-scheduling observability fields for the fused summary —
    the staging traffic and how much of it the double buffer hid
    behind compute. Empty when the sweep ran resident; shared across
    all wave-capable algorithms so the summary shape cannot drift."""
    if not res.get("wave_size"):
        return {}
    return dict(
        wave_size=res["wave_size"],
        n_waves=res["n_waves"],
        staged_bytes=res["staged_bytes"],
        stage_overlap_s=round(res["stage_overlap_s"], 3),
        stage_wait_s=round(res["stage_wait_s"], 3),
        oom_backoffs=res.get("oom_backoffs", 0),
    )


def _run_fused_dispatch(
    args,
    parser,
    workload,
    mesh,
    n_chips,
    metrics,
    t0,
    ledger=None,
    warm_obs=None,
    objectives=None,
) -> int:
    """The fused algorithm dispatch + summary (run_fused's tail, split
    out so the graceful-shutdown catch wraps every fused path)."""
    import time

    from mpi_opt_tpu.utils.profiling import profile_window

    # getattr: main() parses the window; direct in-process callers of
    # run_fused (tests) may hand an argparse namespace without it
    with profile_window(args.profile_dir, launches=getattr(args, "profile_window", None)):
        if args.algorithm == "pbt":
            from mpi_opt_tpu.train.fused_pbt import fused_pbt

            res = _run_with_retries(lambda: fused_pbt(
                workload,
                population=args.population,
                generations=args.generations,
                steps_per_gen=args.steps_per_generation,
                seed=args.seed,
                cfg=PBTConfig(truncation_frac=args.truncation),
                mesh=mesh,
                member_chunk=args.member_chunk,
                gen_chunk=args.gen_chunk,
                step_chunk=args.step_chunk,
                wave_size=args.wave_size,
                checkpoint_dir=args.checkpoint_dir,
                snapshot_every=args.checkpoint_every,
                ledger=ledger,
                warm_obs=warm_obs,
                oom_backoff=args.oom_backoff,
                objectives=objectives,
            ), args.retries, metrics)
            n_trials = args.population * args.generations
            extra = {"best_curve": [round(float(v), 4) for v in res["best_curve"]]}
            extra.update(_wave_extras(res))
        elif args.algorithm in ("asha", "random"):
            from mpi_opt_tpu.train.fused_asha import fused_sha

            # fused random search IS the single-rung case of fused SHA:
            # one cohort of --trials members trains to --budget in
            # lockstep, no cuts — so one code path serves both
            if args.algorithm == "random":
                lo = hi = args.budget
            else:
                lo, hi = args.min_budget, args.max_budget
            res = _run_with_retries(lambda: fused_sha(
                workload,
                n_trials=args.trials,
                min_budget=lo,
                max_budget=hi,
                eta=args.eta,
                seed=args.seed,
                member_chunk=args.member_chunk,
                mesh=mesh,
                wave_size=args.wave_size,
                oom_backoff=args.oom_backoff,
                checkpoint_dir=args.checkpoint_dir,
                ledger=ledger,
                warm_obs=warm_obs,
                objectives=objectives,
            ), args.retries, metrics)
            n_trials = res["n_trials"]
            extra = {"rung_sizes": res["rung_sizes"], "rung_budgets": res["rung_budgets"]}
            extra.update(_wave_extras(res))
        elif args.algorithm == "tpe":
            from mpi_opt_tpu.train.fused_tpe import fused_tpe

            res = _run_with_retries(lambda: fused_tpe(
                workload,
                n_trials=args.trials,
                batch=args.population,
                budget=args.budget,
                seed=args.seed,
                member_chunk=args.member_chunk,
                mesh=mesh,
                wave_size=args.wave_size,
                oom_backoff=args.oom_backoff,
                checkpoint_dir=args.checkpoint_dir,
                ledger=ledger,
                warm_obs=warm_obs,
            ), args.retries, metrics)
            n_trials = res["n_trials"]
            extra = {"best_curve": [round(float(v), 4) for v in res["best_curve"]]}
            extra.update(_wave_extras(res))
        elif args.algorithm == "hyperband":
            from mpi_opt_tpu.train.fused_asha import fused_hyperband

            res = _run_with_retries(lambda: fused_hyperband(
                workload,
                max_budget=args.max_budget,
                eta=args.eta,
                seed=args.seed,
                member_chunk=args.member_chunk,
                mesh=mesh,
                wave_size=args.wave_size,
                oom_backoff=args.oom_backoff,
                checkpoint_dir=args.checkpoint_dir,
                ledger=ledger,
                warm_obs=warm_obs,
            ), args.retries, metrics)
            n_trials = res["n_trials"]
            extra = {"brackets": res["brackets"]}
            extra.update(_wave_extras(res))
        elif args.algorithm == "bohb":
            from mpi_opt_tpu.train.fused_bohb import fused_bohb

            res = _run_with_retries(lambda: fused_bohb(
                workload,
                max_budget=args.max_budget,
                eta=args.eta,
                seed=args.seed,
                member_chunk=args.member_chunk,
                mesh=mesh,
                wave_size=args.wave_size,
                oom_backoff=args.oom_backoff,
                checkpoint_dir=args.checkpoint_dir,
                ledger=ledger,
                warm_obs=warm_obs,
            ), args.retries, metrics)
            n_trials = res["n_trials"]
            extra = {"brackets": res["brackets"]}
            extra.update(_wave_extras(res))
        else:
            # registry-drift guard: unreachable while every registered
            # algorithm has a fused branch above (argparse's choices
            # rejects unknown names first); a NEW algorithm added to the
            # registry without fused support lands here with a clear
            # error instead of an UnboundLocalError
            parser.error(
                f"--fused supports random/pbt/asha/hyperband/bohb/tpe, "
                f"not {args.algorithm!r}"
            )
    wall = time.perf_counter() - t0
    metrics.count_trials(n_trials)
    # per-member failure visibility (ROADMAP open item): every fused
    # sweep reports how many member evaluations came back non-finite
    # per generation/rung — the divergence its isfinite winner picks
    # mask. None only when a pre-upgrade snapshot hid the counts
    member_failures = res.get("member_failures")
    summary = {
        "workload": args.workload,
        "algorithm": args.algorithm,
        "backend": "fused",
        "device": _device_record(),
        "mesh": None if mesh is None else dict(mesh.shape),
        "n_chips": n_chips,
        "n_trials": n_trials,
        "member_failures": member_failures,
        "wall_s": round(wall, 3),
        "trials_per_sec_per_chip": round(n_trials / max(wall, 1e-9) / n_chips, 4),
        # best_params is None when the whole sweep diverged (all scores
        # non-finite) — mirror the driver path's no-best summary shape,
        # including best_score: null (json.dumps would otherwise emit
        # the non-standard NaN token and break strict parsers)
        "best_score": None
        if res["best_params"] is None
        else round(res["best_score"], 6),
        "best_params": None
        if res["best_params"] is None
        else {k: v for k, v in res["best_params"].items() if not k.startswith("__")},
        **extra,
    }
    # staging traffic (wave-scheduled sweeps): feed the counters BEFORE
    # the summary so staged_bytes/stage_overlap_s appear in it
    if res.get("staged_bytes") is not None:
        metrics.count_staging(res["staged_bytes"], res.get("stage_overlap_s", 0.0))
    # fused ledger observability: member records appended this run vs
    # re-verified on resume (parity with the driver path's replayed)
    if res.get("journal") is not None:
        metrics.count_journaled(res["journal"]["written"])
        summary["journal"] = dict(res["journal"])
    # multi-objective extras (ISSUE 17): the final front + how the
    # winner was picked. A constrained sweep that found nothing feasible
    # reports selection="least_violation" AND emits the typed
    # objective_degraded event — degradation is an outcome operators
    # page on, never a silent argmax
    if objectives is not None:
        summary["objectives"] = res.get("objectives")
        pareto = res.get("pareto")
        summary["pareto"] = pareto
        if pareto is not None:
            metrics.log(
                "pareto_front",
                front_size=pareto["front_size"],
                hypervolume=pareto["hypervolume"],
                selection=pareto["selection"],
                objectives=",".join(objectives.names),
            )
            if pareto["selection"] != "feasible":
                metrics.log(
                    "objective_degraded",
                    selection=pareto["selection"],
                    violation=pareto["violation"],
                    objectives=",".join(objectives.names),
                )
    metrics.summary(
        final=True,
        member_failures=(
            None if member_failures is None else int(sum(member_failures))
        ),
    )
    print(json.dumps(_finite_or_null(summary)))
    return 0


def run_suggest_serve(args, parser, workload) -> int:
    """--suggest-serve DIR: the suggestion-service tenant (corpus/serve).

    Instead of sweeping, this process answers suggest/report/lookup
    traffic over DIR at acquisition-kernel speed. Lifecycle mirrors a
    sweep's exactly so the sweep service can own it: a drain request
    (slice budget, SIGTERM, cancel) parks it with EX_TEMPFAIL — every
    report is already fsync-journaled, so nothing is lost — and
    ``--ledger --resume`` rebuilds the observation ring on the next
    slice; the stop flag / idle timeout completes it (exit 0)."""
    from mpi_opt_tpu.corpus.serve import SuggestServer, serve_loop
    from mpi_opt_tpu.ledger import LedgerError, SweepLedger

    space = workload.default_space()
    metrics = stdout_logger(path=args.metrics_file, n_chips=1)
    _wire_trace(args, metrics)  # restored by main's finally
    server = SuggestServer(space, seed=args.seed)
    # corpus warm start resolves BEFORE the ledger header commits, the
    # same ordering rule as the sweep paths
    warm_obs = warm_info = None
    if args.warm_start:
        warm_obs, warm_info = _resolve_warm_start(args, space, metrics, parser)
    ledger = None
    if args.ledger:
        try:
            # the suggestion server is single-process by construction
            # (it owns its spool dir; SPMD bring-up never reaches this
            # branch), so the rank gate is constantly writable
            ledger = SweepLedger(args.ledger, read_only=False)
        except LedgerError as e:
            parser.error(f"--ledger: {e}")
        if ledger.records and not args.resume:
            parser.error(
                f"--ledger {args.ledger!r} already holds "
                f"{len(ledger.records)} report records; pass --resume to "
                "rebuild the ring from them, or point at a fresh path"
            )
        try:
            ledger.ensure_header(
                {
                    "mode": "suggest",
                    "algorithm": "tpe",
                    "workload": args.workload,
                    "backend": "suggest",
                    "seed": args.seed,
                    "space_hash": space.space_hash(),
                    "warm_start": args.warm_start,
                },
                space_spec=space.spec(),
            )
        except LedgerError as e:
            parser.error(f"--ledger: {e}")
        if ledger.n_torn:
            metrics.log("ledger_torn_tail_dropped", path=args.ledger)
        if ledger.records:
            # resume: the server's own journaled reports rebuild the
            # ring + exact cache (and the report serial continues past
            # them, so records never alias across slices)
            server.seed_from_ledger(ledger.records)
            metrics.log("ledger_replay", completed=len(ledger.records))
    if warm_obs is not None:
        n_warm = server.ingest(warm_obs)
        _log_warm_start(metrics, args, warm_info, n_warm)
    metrics.log(
        "suggest_serve",
        workload=args.workload,
        n_obs=server._n_obs,
    )
    try:
        if args.http_port is not None:
            # the HTTP front door: handler threads admit, THIS thread
            # executes (so drain/heartbeat semantics stay identical to
            # serve_loop's); the spool dir still hosts the stop flag,
            # the heartbeat and the endpoint file
            from mpi_opt_tpu.service.http import FrontDoor, serve_http

            spool = None
            if args.http_state_dir:
                from mpi_opt_tpu.service.spool import Spool

                spool = Spool(args.http_state_dir)
            front = FrontDoor(
                suggest=server,
                ledger=ledger,
                spool=spool,
                metrics=metrics,
                queue_depth=args.http_queue,
            )
            summary = serve_http(
                front,
                args.suggest_serve,
                metrics,
                port=args.http_port,
                idle_timeout=args.suggest_idle_timeout,
            )
        else:
            summary = serve_loop(
                server,
                args.suggest_serve,
                metrics,
                ledger=ledger,
                idle_timeout=args.suggest_idle_timeout,
            )
    except SweepInterrupted as e:
        # the drain park: every report the clients saw acked is already
        # fsync-journaled, so the park is free — EX_TEMPFAIL tells the
        # scheduler/supervisor "resume me" exactly like a sweep
        metrics.count_preempted()
        metrics.summary(final=True)
        print(
            json.dumps(
                {
                    "preempted": True,
                    "signal": e.signal,
                    "at": e.at,
                    "workload": args.workload,
                    "backend": "suggest",
                }
            )
        )
        print(
            f"graceful shutdown ({e.signal}) at {e.at}: reports journaled; "
            f"relaunch with --resume to continue (exit {EX_TEMPFAIL})",
            file=sys.stderr,
        )
        return EX_TEMPFAIL
    finally:
        if ledger is not None:
            ledger.close()
    metrics.summary(final=True)
    print(
        json.dumps(
            _finite_or_null(
                {
                    "workload": args.workload,
                    "algorithm": "suggest",
                    "backend": "suggest",
                    **summary,
                }
            )
        )
    )
    return 0


def main(argv=None, *, _workload=None) -> int:
    """CLI entrypoint. ``_workload`` is the sweep service's injection
    seam (service/programs.py): a resident server passes its cached
    workload instance so back-to-back tenants share trainers — and with
    them jax's in-process jit cache, making a shape-matching tenant's
    marginal cost dispatch instead of compile. None (every normal
    invocation) resolves the workload from the registry as always."""
    if argv is None:
        argv = sys.argv[1:]
    # subcommand dispatch: `mpi_opt_tpu report ...` renders/validates
    # ledgers and never touches jax; the flat sweep interface (the
    # reference's mpirun-style surface) stays exactly as it was
    if argv and argv[0] == "report":
        from mpi_opt_tpu.ledger.report import report_main

        return report_main(argv[1:])
    # `mpi_opt_tpu fsck DIR` audits a sweep's durable snapshot state
    # (verify manifests, surface torn saves, --repair quarantines) —
    # same subcommand surface as report, see utils/integrity.py
    if argv and argv[0] == "fsck":
        from mpi_opt_tpu.utils.integrity import fsck_main

        return fsck_main(argv[1:])
    # `mpi_opt_tpu lint [PATHS]` machine-checks the engine's invariants
    # (analysis/ sweeplint suite); never touches jax
    if argv and argv[0] == "lint":
        from mpi_opt_tpu.analysis.cli import lint_main

        return lint_main(argv[1:])
    # `mpi_opt_tpu trace FILE|DIR` renders phase-time attribution over
    # JSONL metrics streams (obs/report.py); `trace --diff BASE NEW
    # [--gate TOL.json]` compares two attributions and gates perf
    # regressions (obs/diff.py). Never touches jax
    if argv and argv[0] == "trace":
        from mpi_opt_tpu.obs.report import trace_main

        return trace_main(argv[1:])
    # the resident multi-tenant sweep service (service/): `serve` is the
    # long-lived device-owning server, `submit`/`status`/`cancel`/`drain`
    # are the thin filesystem-spool clients (no network dependency)
    if argv and argv[0] in ("serve", "submit", "status", "cancel", "drain"):
        from mpi_opt_tpu.service import service_main

        return service_main(argv)
    # `mpi_opt_tpu corpus index|resolve` maintains/audits the ledger-
    # corpus knowledge layer (corpus/); `index` never touches jax
    if argv and argv[0] == "corpus":
        from mpi_opt_tpu.corpus.cli import corpus_main

        return corpus_main(argv[1:])
    # `mpi_opt_tpu suggest-client` drives a --suggest-serve server over
    # its filesystem spool; jax-free like every service client
    if argv and argv[0] == "suggest-client":
        from mpi_opt_tpu.corpus.client import client_main

        return client_main(argv[1:])
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.resume and not (args.checkpoint_dir or args.ledger):
        parser.error("--resume requires --checkpoint-dir or --ledger")
    # validate the failure-policy flags HERE so a bad value is a usage
    # error (exit 2), not a ValueError traceback from FailurePolicy or
    # the backend constructor deep in the run
    if args.trial_retries < 0:
        parser.error(f"--trial-retries must be >= 0, got {args.trial_retries}")
    if not 0.0 < args.max_failure_rate <= 1.0:
        parser.error(
            f"--max-failure-rate must be in (0, 1], got {args.max_failure_rate}"
        )
    if args.trial_timeout is not None and args.trial_timeout <= 0:
        parser.error(f"--trial-timeout must be > 0, got {args.trial_timeout}")
    # --wave-size: parse + validate as a usage error (exit 2), not a
    # ValueError traceback from fused_pbt deep in the run
    if args.wave_size != "auto":
        try:
            args.wave_size = int(args.wave_size)
        except ValueError:
            parser.error(
                f"--wave-size must be an integer or 'auto', got {args.wave_size!r}"
            )
        if args.wave_size < 0:
            parser.error(f"--wave-size must be >= 0, got {args.wave_size}")
    if args.oom_backoff < 0:
        parser.error(f"--oom-backoff must be >= 0, got {args.oom_backoff}")
    if args.wave_size:
        if not args.fused:
            parser.error(
                "--wave-size schedules a fused cohort through host-staged "
                "waves (engine); it requires --fused (any algorithm: "
                "pbt/asha/random/tpe/hyperband/bohb)"
            )
        if args.gen_chunk > 1 or args.step_chunk > 0:
            parser.error(
                "--wave-size schedules whole generations as resident "
                "waves; combining it with --gen-chunk/--step-chunk "
                "launch splitting is ambiguous"
            )
    # --objectives: parse + cross-validate as a usage error (exit 2),
    # not a ValueError deep in the fused driver. The parsed spec rides
    # args.objective_spec for run_fused's ledger/dispatch wiring.
    args.objective_spec = None
    if args.objectives:
        if not args.fused or args.algorithm not in ("pbt", "asha"):
            parser.error(
                "--objectives runs multi-objective selection inside the "
                "fused boundary ops; it requires --fused --algorithm "
                "pbt|asha"
            )
        if args.wave_size:
            parser.error(
                "--objectives is not supported with --wave-size yet; run "
                "resident (--wave-size 0) or shard over a mesh"
            )
        if args.step_chunk > 0:
            parser.error(
                "--objectives is not supported with --step-chunk (the "
                "sub-segment boundary program is scalar); use --gen-chunk"
            )
        from mpi_opt_tpu.objectives import ObjectiveSpec

        try:
            args.objective_spec = ObjectiveSpec.parse(args.objectives)
        except ValueError as e:
            parser.error(f"--objectives: {e}")
    # --profile-launches: parse + validate as a usage error, and carry
    # the parsed window on args for the profile_window call sites
    args.profile_window = None
    if args.profile_launches is not None:
        if not args.profile_dir:
            parser.error("--profile-launches requires --profile-dir")
        from mpi_opt_tpu.utils.profiling import parse_launch_window

        try:
            args.profile_window = parse_launch_window(args.profile_launches)
        except ValueError as e:
            parser.error(f"--profile-launches: {e}")
    if args.isolate_stateful and (args.fused or args.backend != "cpu"):
        parser.error(
            "--isolate-stateful moves the cpu backend's in-parent "
            "stateful path into a worker process; fused/TPU sweeps "
            "have no such path"
        )
    # --ledger/--warm-start work on BOTH paths: the driver journals per
    # trial, fused sweeps journal per population member at every
    # launch/rung/generation boundary (ledger/fused.py) — and warm-start
    # is cross-mode (the records share space_hash/canonical params).
    # Resolution — including the realpath self-warm-start guard and the
    # auto: corpus path — lives in _resolve_warm_start, ONE helper every
    # execution path (driver, fused, suggestion tenant) flows through.
    if args.suggest_serve:
        if args.fused:
            parser.error(
                "--suggest-serve answers suggestion traffic instead of "
                "sweeping; it cannot combine with --fused"
            )
        if args.chaos is not None:
            parser.error(
                "--chaos injects faults into trial evaluation; a "
                "--suggest-serve server evaluates nothing"
            )
    if args.suggest_idle_timeout is not None:
        if not args.suggest_serve:
            parser.error("--suggest-idle-timeout requires --suggest-serve")
        if args.suggest_idle_timeout <= 0:
            parser.error(
                f"--suggest-idle-timeout must be > 0, got "
                f"{args.suggest_idle_timeout}"
            )
    if args.http_port is not None:
        if not args.suggest_serve:
            parser.error("--http-port requires --suggest-serve DIR")
        if not 0 <= args.http_port <= 65535:
            parser.error(f"--http-port must be in [0, 65535], got {args.http_port}")
        if args.http_queue < 1:
            parser.error(f"--http-queue must be >= 1, got {args.http_queue}")
    elif args.http_state_dir is not None:
        parser.error("--http-state-dir requires --http-port")
    # persistent compile cache, then platform pinning, then
    # multi-host bring-up, BEFORE anything touches the XLA backend
    # (build_mesh, workload data, backend construction all do)
    wire_compile_cache()
    pin_platform(args.platform, args.local_devices, parser.error)
    explicit = (args.coordinator, args.num_processes, args.process_id)
    if any(v is not None for v in explicit) and not all(
        v is not None for v in explicit
    ):
        parser.error(
            "--coordinator, --num-processes and --process-id must be "
            "given together (or use --multihost alone for TPU-pod "
            "auto-detection)"
        )
    if args.multihost or args.coordinator is not None:
        from mpi_opt_tpu.parallel.mesh import initialize_multihost

        try:
            initialize_multihost(
                coordinator_address=args.coordinator,
                num_processes=args.num_processes,
                process_id=args.process_id,
                require=True,
            )
        except (ValueError, RuntimeError) as e:
            # loud but actionable, matching every other user-input
            # failure's parser.error surface — not a raw jax traceback
            parser.error(
                f"multi-host bring-up failed: {e}\n(--multihost needs "
                "TPU-pod metadata; off-pod, pass --coordinator "
                "HOST:PORT --num-processes N --process-id RANK on every "
                "rank, and note bring-up must happen before any other "
                "JAX use in the process)"
            )
    # everything from here RUNS the sweep: arm the graceful-shutdown
    # protocol (SIGTERM/SIGINT set a drain flag; batch/launch boundaries
    # flush and exit EX_TEMPFAIL) and the optional progress heartbeat.
    # All three are scoped: handlers restored, heartbeat dropped, and
    # the trace sink RESTORED to its entry state on the way out — a
    # service tenant slice (in-process cli.main under serve --trace)
    # must hand the server back its own sink, not a cleared one.
    trace_entry = _trace.save()
    try:
        # a profiled run is read by the program's own names: they join
        # the persistent cache's key for its compiles
        with _shutdown.ShutdownGuard(), keyed_by_names(bool(args.profile_dir)):
            if args.heartbeat_file:
                _heartbeat.configure(args.heartbeat_file)
            return _run_sweep(args, parser, _workload=_workload)
    finally:
        _heartbeat.deconfigure()
        integrity.clear_observer()
        resources.clear_observer()
        _trace.deconfigure(trace_entry)


def _run_sweep(args, parser, _workload=None) -> int:
    """The sweep body of ``main`` (split out so the shutdown guard and
    heartbeat lifecycle wrap every path)."""
    # the service's shared instance when injected; --chaos still wraps
    # below (the wrapper is built fresh by name, so injection never
    # leaks one tenant's fault schedule into another)
    workload = _workload if _workload is not None else get_workload(args.workload)
    chaos_kwargs = None
    if args.chaos is not None:
        if args.fused or args.backend != "cpu":
            parser.error(
                "--chaos exercises the host driver's trial-level failure "
                "policy through the cpu backend; fused/TPU sweeps have no "
                "per-trial injection point (their divergence masking is "
                "always on)"
            )
        from mpi_opt_tpu.workloads.chaos import parse_chaos_spec

        try:
            chaos_kwargs = {"inner": args.workload, **parse_chaos_spec(args.chaos)}
            workload = get_workload("chaos", **chaos_kwargs)
        except ValueError as e:
            parser.error(f"--chaos: {e}")
    if args.suggest_serve:
        return run_suggest_serve(args, parser, workload)
    if args.fused:
        return run_fused(args, parser, workload)
    space = workload.default_space()
    algorithm = make_algorithm(args, space)
    mesh = None
    backend_kwargs = {}
    if args.backend == "cpu":
        backend_kwargs = {
            "n_workers": args.workers,
            "seed": args.seed,
            "trial_timeout": args.trial_timeout,
            "isolate_stateful": args.isolate_stateful,
        }
        if chaos_kwargs is not None:
            # pool workers rebuild the workload from (name, kwargs);
            # without this they would reconstruct a default (fault-free)
            # chaos wrapper and the drill would silently inject nothing
            backend_kwargs["workload_kwargs"] = chaos_kwargs
    elif args.backend == "tpu":
        mesh = build_mesh(args)
        backend_kwargs = {"population": args.population, "seed": args.seed, "mesh": mesh}
    # the metric of record is trials/sec/CHIP; normalizing by 1 on a
    # multi-chip TPU run would overstate it by the chip count, and by
    # the device count on a --no-mesh run that only uses one device —
    # so count the devices the slot pool is actually sharded over: the
    # mesh's GLOBAL size (every SPMD process drives and counts the same
    # global batches, so a per-process share would overstate per-chip
    # throughput by the host count).
    n_chips = 1
    if args.backend == "tpu" and mesh is not None:
        n_chips = int(mesh.devices.size)
    # metrics + tracing wire BEFORE backend construction so the pool
    # bring-up (dataset load, worker spawn, device upload) lands in a
    # setup span — it is most of a driver sweep's time-to-first-trial
    metrics = stdout_logger(path=args.metrics_file, n_chips=n_chips)
    _wire_integrity_observer(metrics)
    _wire_resource_observer(metrics)
    _wire_trace(args, metrics)  # restored by main's finally
    with _trace.span("setup", backend=args.backend) as _setup_sp:
        # device kind keys the roofline's platform-cap calibration
        _trace.note_device(_setup_sp)
        backend = get_backend(args.backend, workload, **backend_kwargs)
    checkpointer = None
    restored_step = None
    if args.checkpoint_dir:
        from mpi_opt_tpu.utils.checkpoint import SearchCheckpointer

        checkpointer = SearchCheckpointer(args.checkpoint_dir, every=args.checkpoint_every)
        if args.resume:
            try:
                restored_step = checkpointer.restore_into(algorithm, backend)
            except NoVerifiedSnapshotError as e:
                # every retained step failed verification: a retry (or a
                # supervisor's --resume restart) would re-read the same
                # poisoned state — abort with the distinct data-error code
                checkpointer.close()
                backend.close()
                return _data_error_exit(
                    e,
                    metrics,
                    workload=args.workload,
                    algorithm=args.algorithm,
                    backend=args.backend,
                )
            metrics.log("resume", step=restored_step)
    from mpi_opt_tpu.driver import FailurePolicy, SweepAborted
    from mpi_opt_tpu.utils.profiling import profile_window

    # the prior ledger is VALIDATED (loaded, space-hash checked) before
    # this run's own ledger header commits: a typo'd --warm-start path
    # must fail before it is journaled into a fresh ledger's identity,
    # which would refuse the corrected re-run
    warm_obs = warm_info = None
    if args.warm_start:
        warm_obs, warm_info = _resolve_warm_start(args, space, metrics, parser)
    ledger = None
    if args.ledger:
        from mpi_opt_tpu.ledger import LedgerError, SweepLedger

        # rank-0-only journaling under multi-process SPMD: every rank
        # runs the same deterministic driver loop and must replay the
        # SHARED journal identically, but N ranks fsync-appending one
        # file would interleave records and corrupt it — non-zero ranks
        # open read-only (in-memory bookkeeping only)
        ledger_rank = 0
        if args.multihost or args.coordinator is not None:
            import jax

            ledger_rank = jax.process_index()
        try:
            ledger = SweepLedger(args.ledger, read_only=ledger_rank != 0)
        except LedgerError as e:
            parser.error(f"--ledger: {e}")
        if ledger.read_only:
            metrics.log("ledger_rank_gated", rank=ledger_rank)
        if ledger.records and not args.resume:
            # explicit opt-in, same rule as --checkpoint-dir (ADVICE r2):
            # a stale journal must not silently replay an old sweep
            parser.error(
                f"--ledger {args.ledger!r} already holds "
                f"{len(ledger.records)} trial records; pass --resume to "
                "replay them, or point at a fresh path"
            )
        try:
            # the sweep's identity: everything that shapes the
            # deterministic suggestion stream the replay relies on
            # (space_spec rides top-level — corpus metadata, not identity)
            ledger.ensure_header(
                {
                    "algorithm": args.algorithm,
                    "workload": args.workload,
                    "backend": args.backend,
                    "seed": args.seed,
                    "space_hash": space.space_hash(),
                    "capacity": backend.capacity,
                    "trials": args.trials,
                    "budget": args.budget,
                    "chaos": args.chaos,
                    "warm_start": args.warm_start,
                },
                space_spec=space.spec(),
            )
        except LedgerError as e:
            parser.error(f"--ledger: {e}")
        if ledger.n_torn:
            metrics.log("ledger_torn_tail_dropped", path=args.ledger)
    if warm_obs is not None:
        if restored_step is not None:
            # the priors were ingested before that checkpoint was taken
            # and live inside the restored state (TPE/BOHB ring buffers
            # are checkpointed) — re-ingesting would double-weight them
            # in the model and re-queue already-consumed seed points
            metrics.log(
                "warm_start_skipped",
                reason="checkpoint restored (priors already in state)",
                step=restored_step,
            )
        else:
            n_warm = algorithm.ingest_observations(warm_obs)
            _log_warm_start(metrics, args, warm_info, n_warm)
    policy = FailurePolicy(
        max_retries=args.trial_retries,
        max_failure_rate=args.max_failure_rate,
        seed=args.seed,
    )
    try:
        with profile_window(
            args.profile_dir, launches=getattr(args, "profile_window", None)
        ):
            result = run_search(
                algorithm,
                backend,
                metrics=metrics,
                checkpointer=checkpointer,
                policy=policy,
                ledger=ledger,
            )
    except resources.StorageFull as e:
        # classified disk-full during a ledger fsync or checkpoint save
        # (after its one retention-prune retry): durable state intact,
        # exit 74 — free disk + --resume recovers
        return _resource_exit(
            e,
            metrics,
            "storage_full",
            workload=args.workload,
            algorithm=args.algorithm,
            backend=args.backend,
        )
    except SweepAborted as e:
        # the circuit breaker tripping is an OPERATOR outcome, not a
        # crash: summarize the counters that tripped it and exit nonzero
        # (launch.py supervisors see a retryable rc=1, not a usage error)
        metrics.summary(**{"final": True, "aborted": True})
        print(json.dumps({"aborted": str(e)}))
        print(str(e), file=sys.stderr)
        return 1
    except SweepInterrupted as e:
        # graceful preemption: run_search drained at a batch boundary —
        # every completed trial is journaled (ledger fsyncs per record)
        # and an off-cadence checkpoint was forced. EX_TEMPFAIL tells
        # the launch supervisor "restart me with --resume, for free"
        metrics.count_preempted()
        metrics.summary(final=True)
        print(
            json.dumps(
                {
                    "preempted": True,
                    "signal": e.signal,
                    "at": e.at,
                    "trials_done": metrics.trials_done,
                }
            )
        )
        print(
            f"graceful shutdown ({e.signal}): checkpoint + ledger "
            f"flushed; relaunch with --resume to continue "
            f"(exit {EX_TEMPFAIL})",
            file=sys.stderr,
        )
        return EX_TEMPFAIL
    finally:
        backend.close()
        if checkpointer is not None:
            checkpointer.close()
        if ledger is not None:
            ledger.close()
    best = result.best
    summary = {
        "workload": args.workload,
        "algorithm": args.algorithm,
        "backend": args.backend,
        # the cpu backend evaluates in CPU-pinned pool workers; asking
        # jax here would initialize a backend in this process just to
        # report it (and take the chip, where there is one)
        "device": {"platform": "cpu", "kind": "cpu", "count": backend.n_workers}
        if args.backend == "cpu"
        else _device_record(),
        "n_trials": result.n_trials,
        "wall_s": round(result.wall_s, 3),
        "trials_per_sec_per_chip": round(result.trials_per_sec_per_chip, 4),
        "trials_failed": metrics.trials_failed,
        "trials_retried": metrics.trials_retried,
        "trials_timeout": metrics.trials_timeout,
        "cache_hits": metrics.cache_hits,
        "replayed": metrics.replayed,
        "best_score": None if best is None else round(best.score, 6),
        "best_params": None
        if best is None
        else {k: v for k, v in best.params.items() if not k.startswith("__")},
    }
    metrics.summary(**{"final": True})
    print(json.dumps(_finite_or_null(summary)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
