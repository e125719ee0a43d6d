"""Seeded fault-injection harness: wrap any workload in configured chaos.

The trial-level fault-tolerance layer (TrialResult.status, the CPU
backend's per-job reaping, driver.FailurePolicy) is only trustworthy if
it can be EXERCISED on demand — HPO's whole premise is that some trials
fail (extreme hyperparameters are part of the search space), but
organic failures are rare and unseeded. ``ChaosWorkload`` injects the
production failure shapes at configured probabilities:

- ``exc``:  the evaluation raises (bad hyperparameter -> OOM, sklearn
  convergence error, assertion in user code)
- ``nan``:  training "succeeds" but the score is NaN (diverged loss)
- ``hang``: the evaluation blocks (deadlocked worker, wedged I/O) —
  reaped by the CPU backend's per-trial timeout
- ``crash``: the WORKER PROCESS dies hard (os._exit: segfault/OOM-kill
  stand-in) — its queued result never arrives, so this too is reaped
  by the per-trial timeout, and the backend recycles the pool
- ``slow``: the evaluation takes extra wall time (straggler rank)
- ``preempt``: delivers SIGTERM to the evaluating process itself
  mid-evaluation — the platform-preemption stand-in that makes the
  graceful-shutdown protocol (health/shutdown.py) fault-injectable.
  Where evaluation runs in the DRIVER process (inline / in-parent
  stateful paths) the installed handler turns it into a graceful
  drain: the trial completes, the sweep flushes and exits
  EX_TEMPFAIL (75). In a pool / isolated worker the signal simply
  kills that worker (default disposition) — a crash-shaped outcome,
  reaped like ``crash``.

Determinism contract: whether a trial is faulted is a pure function of
``(chaos_seed, params)`` via a SHA-256 draw — stable across processes
(pool workers reconstruct the wrapper by registry name), across runs,
and independent of scheduling. A faulted trial is therefore faulted on
every retry too: chaos models DETERMINISTIC failures (the
hyperparameters themselves are poison). Clean trials score exactly what
the inner workload scores, so a chaos sweep's best trial matches the
clean sweep's best whenever the clean winner isn't in the faulted
fraction — the property the determinism test pins.

Registry shape: ``get_workload("chaos", inner="quadratic", exc=0.2)``.
The CPU backend's pool workers rebuild workloads from
``(name, workload_kwargs)``, so the CLI passes the same kwargs dict to
both the wrapper construction and the backend (see cli.main).
"""

from __future__ import annotations

import hashlib
import json
import os
import signal
import time

from mpi_opt_tpu.space import SearchSpace
from mpi_opt_tpu.workloads import get_workload, register
from mpi_opt_tpu.workloads.base import Workload


class ChaosInjectedError(RuntimeError):
    """The exception ``exc`` faults raise — distinct so tests and log
    readers can tell injected failures from organic ones."""


def parse_chaos_spec(spec: str) -> dict:
    """``"exc=0.1,nan=0.05,hang=0.02,slow=0.1,seed=7"`` -> kwargs for
    ChaosWorkload. Unknown keys are rejected loudly (a typoed fault name
    silently injecting nothing would fake a green chaos drill)."""
    out: dict = {}
    numeric = {
        "exc": float, "nan": float, "hang": float, "crash": float,
        "slow": float, "preempt": float, "hang_s": float, "slow_s": float,
        "seed": int,
    }
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"chaos spec entry {part!r} is not key=value "
                f"(known keys: {sorted(numeric)})"
            )
        k, v = part.split("=", 1)
        k = k.strip().replace("-", "_")
        if k not in numeric:
            raise ValueError(
                f"unknown chaos key {k!r} (known: {sorted(numeric)})"
            )
        out[k] = numeric[k](v)
    for p in ("exc", "nan", "hang", "crash", "slow", "preempt"):
        if not 0.0 <= out.get(p, 0.0) <= 1.0:
            raise ValueError(f"chaos probability {p}={out[p]} outside [0, 1]")
    return out


# -- snapshot-corruption injectors (torn_save / corrupt_save faults) --------
#
# The per-trial faults above exercise the TRIAL failure layer; these two
# exercise the SNAPSHOT integrity layer (utils/integrity.py): what a
# SIGKILL mid-async-save (torn_save) or silent bit-rot (corrupt_save)
# leaves inside the latest orbax step directory. They are direct-call
# helpers, not probability faults — corruption strikes the durable
# state between runs, not an evaluation — and deterministic given
# (directory contents, seed) so resume drills can pin exact outcomes.


def _committed_step_dirs(checkpoint_dir: str) -> list:
    """(step, path) for every committed orbax step under
    ``checkpoint_dir`` (recursive: hyperband nests per-bracket roots).
    Enumeration is delegated to utils.integrity so the injectors strike
    exactly the steps fsck audits — one home for the orbax commit-marker
    convention."""
    from mpi_opt_tpu.utils.integrity import _committed_steps, find_checkpoint_roots

    out = []
    for root in find_checkpoint_roots(checkpoint_dir):
        out.extend(
            (s, os.path.join(root, str(s))) for s in _committed_steps(root)
        )
    return sorted(out)


def _corruption_target(step_dir: str) -> str:
    """The file a fault strikes: the LARGEST file orbax wrote in the
    step (ties broken by path) — in any real snapshot that is array
    data, the payload whose rot matters most; in toy snapshots it is
    orbax's own ``_CHECKPOINT_METADATA``, which the installed orbax
    would read past — exactly the file only the step's seal protects.
    The seal itself is never the target (tests tear it directly)."""
    from mpi_opt_tpu.utils.integrity import SEAL_FILE

    candidates = []
    for root, _dirs, files in os.walk(step_dir):
        for f in files:
            p = os.path.join(root, f)
            if os.path.relpath(p, step_dir) != SEAL_FILE:
                candidates.append((os.path.getsize(p), p))
    if not candidates:
        raise ValueError(f"no files to corrupt under {step_dir}")
    # largest first; the path tiebreak keeps the pick stable when sizes
    # collide (sort ascending, take last => greatest (size, path))
    return sorted(candidates)[-1][1]


def _resolve_step_dir(checkpoint_dir: str, step) -> str:
    steps = _committed_step_dirs(checkpoint_dir)
    if not steps:
        raise ValueError(f"no committed snapshot steps under {checkpoint_dir}")
    if step is None:
        return steps[-1][1]
    for s, path in steps:
        if s == int(step):
            return path
    raise ValueError(f"step {step} not found under {checkpoint_dir}")


def inject_torn_save(checkpoint_dir: str, seed: int = 0, step=None) -> str:
    """Truncate a file inside the latest (or given) committed step dir —
    the shape a SIGKILL mid-async-save leaves behind. The cut point is a
    seeded draw over the file's interior so repeated drills vary the
    tear without losing determinism. Returns the mangled path."""
    path = _corruption_target(_resolve_step_dir(checkpoint_dir, step))
    size = os.path.getsize(path)
    h = hashlib.sha256(f"torn:{seed}".encode()).digest()
    cut = 1 + int.from_bytes(h[:8], "big") % max(size - 1, 1)
    with open(path, "r+b") as f:
        f.truncate(cut)
    return path


def inject_corrupt_save(checkpoint_dir: str, seed: int = 0, step=None) -> str:
    """Flip one bit inside the latest (or given) committed step dir —
    the silent bit-rot shape only content digests can catch. Seeded
    offset/bit, deterministic per (directory contents, seed). Returns
    the mangled path."""
    path = _corruption_target(_resolve_step_dir(checkpoint_dir, step))
    size = os.path.getsize(path)
    h = hashlib.sha256(f"corrupt:{seed}".encode()).digest()
    off = int.from_bytes(h[:8], "big") % size
    bit = h[8] % 8
    with open(path, "r+b") as f:
        f.seek(off)
        byte = f.read(1)
        f.seek(off)
        f.write(bytes([byte[0] ^ (1 << bit)]))
    return path


# -- resource-exhaustion injectors (device OOM / disk full, ISSUE 13) -------
#
# Two more direct-call injectors in the inject_torn_save style: install
# a seeded deterministic schedule, drive the drill, uninstall in a
# finally. ``inject_enospc`` strikes the atomic-write/fsync paths of
# the DURABLE layers (snapshot save enqueue, ledger journal fsync) via
# the resource layer's disk-fault seam — the shape a filling disk
# presents; ``inject_oom`` raises a synthetic XLA RESOURCE_EXHAUSTED at
# a chosen guarded fused-launch ordinal (resident launch or wave) via
# the launch seam, exercising the REAL classification path
# (utils/resources.py type gate included) and the wave scheduler's
# --oom-backoff re-run.


class DiskFullInjector:
    """The schedule ``inject_enospc`` installs into
    ``utils.resources``' disk-fault seam. Counts every seam op per kind
    ("snapshot_save" / "ledger_fsync") and raises a classified
    ``StorageFull`` (ENOSPC) on the scheduled ordinals; ``fail_from``
    makes every op at/after that ordinal fail — the disk-stays-full
    shape drill B needs (the prune retry must ALSO hit the wall).
    Thread-safe (orbax save enqueues and the main loop share the
    seam)."""

    def __init__(
        self,
        fail: int = 0,
        seed: int = 0,
        ops_window: int | None = None,
        fail_from: int | None = None,
        op: str | None = None,
    ):
        import threading

        self._lock = threading.Lock()
        self._counts: dict = {}
        self._op = op  # None = every seam kind
        self._fail_from = fail_from
        self._fail = SpoolFaultInjector._schedule("disk", fail, seed, ops_window)
        self.faults_fired = 0

    def __call__(self, op: str, path: str) -> None:
        if self._op is not None and op != self._op:
            return
        with self._lock:
            ordinal = self._counts.get(op, 0)
            self._counts[op] = ordinal + 1
            fire = ordinal in self._fail or (
                self._fail_from is not None and ordinal >= self._fail_from
            )
            if fire:
                self.faults_fired += 1
        if fire:
            from mpi_opt_tpu.utils.resources import storage_full_error

            raise storage_full_error(path, op=f"chaos-injected {op} (op {ordinal})")


def inject_enospc(
    fail: int = 0,
    seed: int = 0,
    ops_window: int | None = None,
    fail_from: int | None = None,
    op: str | None = None,
):
    """Install a seeded, deterministic ENOSPC schedule on the durable
    layers' atomic-write/fsync seam (``utils.resources.disk_fault``:
    snapshot saves + ledger fsyncs). Returns ``(injector, uninstall)``
    — call ``uninstall()`` when the drill is over (tests in a finally).
    ``fail_from=N`` fails every op at/after ordinal N (disk fills and
    STAYS full — the prune-then-park drill); ``fail=n`` fails the first
    n (or a seeded sample of ``ops_window``); ``op`` restricts the
    schedule to one seam kind."""
    from mpi_opt_tpu.utils import resources

    injector = DiskFullInjector(
        fail=fail, seed=seed, ops_window=ops_window, fail_from=fail_from, op=op
    )
    resources.set_disk_fault_injector(injector)

    def uninstall() -> None:
        resources.set_disk_fault_injector(None)

    return injector, uninstall


class OOMInjector:
    """The schedule ``inject_oom`` installs into ``utils.resources``'
    launch seam: every guarded fused launch (resident launch / one
    wave) ticks one ordinal; the scheduled ordinals (1-based, matching
    "OOM at wave k") raise a synthetic RESOURCE_EXHAUSTED through the
    real classification funnel."""

    def __init__(self, at_launch: int = 1, n: int = 1, kind: str | None = None):
        import threading

        if at_launch < 1:
            raise ValueError(f"at_launch is 1-based, got {at_launch}")
        self._lock = threading.Lock()
        self._kind = kind  # None = any guarded launch ("launch"/"wave")
        self._fire_at = frozenset(range(at_launch, at_launch + max(1, n)))
        self.launches = 0
        self.faults_fired = 0

    def __call__(self, kind: str) -> None:
        if self._kind is not None and kind != self._kind:
            return
        with self._lock:
            self.launches += 1
            ordinal = self.launches
            fire = ordinal in self._fire_at
            if fire:
                self.faults_fired += 1
        if fire:
            from mpi_opt_tpu.utils.resources import synthetic_resource_exhausted

            raise synthetic_resource_exhausted(
                f"chaos: injected device OOM at {kind} ordinal {ordinal}"
            )


def inject_oom(at_launch: int = 1, n: int = 1, kind: str | None = None):
    """Install a deterministic device-OOM schedule on the fused launch
    seam: the ``at_launch``-th guarded launch (1-based; ``n``
    consecutive ordinals — n>1 drills repeated backoff) raises a
    synthetic XLA RESOURCE_EXHAUSTED. Returns ``(injector,
    uninstall)``. ``kind`` restricts to "launch" (resident) or "wave"."""
    from mpi_opt_tpu.utils import resources

    injector = OOMInjector(at_launch=at_launch, n=n, kind=kind)
    resources.set_launch_fault_injector(injector)

    def uninstall() -> None:
        resources.set_launch_fault_injector(None)

    return injector, uninstall


class RankKillInjector:
    """The schedule ``inject_rank_kill`` installs into
    ``utils.resources``' boundary seam (``train.common.launch_boundary``
    ticks it once per launch/rung/generation boundary): on the
    scheduled 1-based boundary ordinals, IF this process is the chosen
    rank, die by SIGKILL — no handlers, no atexit, no flushes, exactly
    the hard rank death that wedges an SPMD cohort's survivors in their
    next collective. Other ranks count the same ordinals and do
    nothing, so the drill is deterministic across the whole world.

    ``once_marker``: path of a sentinel file created (O_EXCL) just
    before dying. A coordinated ``--resume`` relaunch re-runs the same
    boundaries with the same injector spec — without the marker the
    restarted rank would be killed at the same ordinal forever, burning
    the retry budget on the drill itself. Marker present = already
    fired = don't fire again.
    """

    def __init__(
        self,
        rank: int = 0,
        at_boundary: int = 1,
        n: int = 1,
        once_marker: str | None = None,
    ):
        import threading

        if at_boundary < 1:
            raise ValueError(f"at_boundary is 1-based, got {at_boundary}")
        self._lock = threading.Lock()
        self._rank = int(rank)
        self._fire_at = frozenset(range(at_boundary, at_boundary + max(1, n)))
        self._once_marker = once_marker
        self.boundaries = 0
        self.faults_fired = 0

    def __call__(self, stage: str) -> None:
        with self._lock:
            self.boundaries += 1
            fire = self.boundaries in self._fire_at
        if not fire:
            return
        import jax

        if jax.process_index() != self._rank:
            return
        if self._once_marker is not None:
            try:
                fd = os.open(
                    self._once_marker, os.O_CREAT | os.O_EXCL | os.O_WRONLY
                )
                os.close(fd)
            except FileExistsError:
                return  # already fired in a previous attempt
        with self._lock:
            self.faults_fired += 1
        os.kill(os.getpid(), signal.SIGKILL)


def parse_rank_kill_spec(spec: str) -> dict:
    """``"rank=1,at=3,n=1,marker=/tmp/m"`` -> ``inject_rank_kill``
    kwargs. Unknown keys are rejected loudly, same contract as
    ``parse_chaos_spec`` — a typoed drill spec injecting nothing would
    fake a green wedge drill."""
    out: dict = {}
    keys = {"rank": int, "at": int, "n": int, "marker": str}
    names = {"rank": "rank", "at": "at_boundary", "n": "n", "marker": "once_marker"}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"rank-kill spec entry {part!r} is not key=value "
                f"(known keys: {sorted(keys)})"
            )
        k, v = part.split("=", 1)
        k = k.strip()
        if k not in keys:
            raise ValueError(f"unknown rank-kill key {k!r} (known: {sorted(keys)})")
        out[names[k]] = keys[k](v)
    return out


def inject_rank_kill(
    rank: int = 0,
    at_boundary: int = 1,
    n: int = 1,
    once_marker: str | None = None,
):
    """Install a deterministic rank-death schedule on the boundary
    seam: at the ``at_boundary``-th launch/rung/generation boundary
    (1-based; ``n`` consecutive ordinals), the process whose
    ``jax.process_index()`` equals ``rank`` SIGKILLs itself. Returns
    ``(injector, uninstall)`` like ``inject_oom``; ``once_marker``
    makes the kill one-shot across coordinated restarts."""
    from mpi_opt_tpu.utils import resources

    injector = RankKillInjector(
        rank=rank, at_boundary=at_boundary, n=n, once_marker=once_marker
    )
    resources.set_boundary_fault_injector(injector)

    def uninstall() -> None:
        resources.set_boundary_fault_injector(None)

    return injector, uninstall


# -- spool-fault injectors (fleet federation, ISSUE 12) ---------------------
#
# The two injectors above strike durable state BETWEEN runs; these
# strike the service spool's metadata primitives WHILE a scheduler (or
# a whole fleet of them) is working: delayed/failed ``os.replace`` on
# status/lease/queue writes and EIO on status reads — the weather of a
# slow or contended shared filesystem, which is exactly the substrate a
# multi-server spool runs on. Direct-call style like ``inject_torn_save``
# (install, drive the drill, uninstall), deterministic by construction:
# faults fire on exact op ordinals, optionally chosen by a seeded draw
# over a window, never by wall clock or scheduling.


class SpoolFaultInjector:
    """The schedule ``inject_spool_faults`` installs into
    ``service.spool``'s fault seam. Counts every op per kind
    ("replace" / "read" / "list") and raises ``OSError(EIO)`` on the
    scheduled ordinals (read faults only strike status.json reads —
    the ISSUE's "EIO on status reads" shape — so job-spec parsing
    stays out of scope); ``replace_delay_s`` sleeps before every
    replace while installed (the slow-NFS shape). Thread-safe: the
    scheduler's staging/heartbeat threads share the seam."""

    def __init__(
        self,
        replace_fail: int = 0,
        read_fail: int = 0,
        replace_delay_s: float = 0.0,
        seed: int = 0,
        ops_window: int | None = None,
    ):
        import threading

        self.replace_delay_s = float(replace_delay_s)
        self._lock = threading.Lock()
        self._counts = {"replace": 0, "read": 0, "list": 0}
        self._fail = {
            "replace": self._schedule("replace", replace_fail, seed, ops_window),
            "read": self._schedule("read", read_fail, seed, ops_window),
        }
        self.faults_fired = {"replace": 0, "read": 0}

    @staticmethod
    def _schedule(kind: str, n: int, seed: int, window: int | None) -> frozenset:
        """Which op ordinals (0-based) fault: the first ``n`` when no
        window is given, else a seeded SHA-draw sample of ``n`` distinct
        ordinals from ``range(window)`` — deterministic per (kind,
        seed, n, window), independent of scheduling."""
        if n <= 0:
            return frozenset()
        if window is None or window <= n:
            return frozenset(range(n))
        picked: set = set()
        i = 0
        while len(picked) < n:
            h = hashlib.sha256(f"spool:{kind}:{seed}:{i}".encode()).digest()
            picked.add(int.from_bytes(h[:8], "big") % window)
            i += 1
        return frozenset(picked)

    def __call__(self, op: str, path: str) -> None:
        import errno
        import time as _time

        if op == "replace" and self.replace_delay_s > 0:
            _time.sleep(self.replace_delay_s)
        if op == "read" and not path.endswith("status.json"):
            return
        with self._lock:
            ordinal = self._counts.get(op, 0)
            self._counts[op] = ordinal + 1
            fire = ordinal in self._fail.get(op, ())
            if fire:
                self.faults_fired[op] += 1
        if fire:
            raise OSError(
                errno.EIO, f"chaos: injected spool {op} fault (op {ordinal})", path
            )


def inject_spool_faults(
    replace_fail: int = 0,
    read_fail: int = 0,
    replace_delay_s: float = 0.0,
    seed: int = 0,
    ops_window: int | None = None,
):
    """Install a seeded, deterministic fault schedule on the service
    spool's metadata ops. Returns ``(injector, uninstall)`` — call
    ``uninstall()`` when the drill is over (tests do it in a finally).
    The spool's bounded retry-with-jittered-backoff (spool.retry_io)
    absorbs schedules shorter than its attempt budget — the drill for
    "a contended shared filesystem degrades to latency, not crashes" —
    while a schedule longer than the budget surfaces the OSError, the
    drill for the failure path."""
    from mpi_opt_tpu.service import spool as spool_mod

    injector = SpoolFaultInjector(
        replace_fail=replace_fail,
        read_fail=read_fail,
        replace_delay_s=replace_delay_s,
        seed=seed,
        ops_window=ops_window,
    )
    spool_mod.set_fault_injector(injector)

    def uninstall() -> None:
        spool_mod.set_fault_injector(None)

    return injector, uninstall


# -- network-fault injectors (HTTP front door, ISSUE 16) --------------------
#
# The spool injectors above strike filesystem metadata; this one
# strikes the WIRE: the HTTP client transport's chaos seam
# (corpus/transport.net_fault) fires at the three places a real network
# fails — before the TCP connect ("connect": refused/reset), before the
# request body is written ("send": peer died between accept and read),
# and before the response is read ("read": torn reply, the
# did-it-execute ambiguity the idempotency key exists for). Same
# direct-call discipline: install, drive the drill, uninstall in a
# finally; faults fire on exact per-stage op ordinals from a seeded
# draw, never by wall clock.


class NetFaultInjector:
    """The schedule ``inject_net`` installs into
    ``corpus.transport``'s net-fault seam. Counts every transport op
    per stage ("connect" / "send" / "read") and fires the scheduled
    ordinals: connect/send ordinals raise :class:`transport.Unreachable`
    (connection refused), read ordinals raise
    :class:`transport.TornResponse` (reply died mid-flight — the
    request MAY have executed), and ``delay_s`` sleeps before every
    faulted-read's raise is decided, on its own seeded schedule
    (``delay`` ordinals), modeling the slow-reply shape. Thread-safe:
    bench/drill clients retry from many threads through one seam."""

    def __init__(
        self,
        refuse: int = 0,
        torn: int = 0,
        delay: int = 0,
        delay_s: float = 0.05,
        seed: int = 0,
        ops_window: int | None = None,
    ):
        import threading

        self.delay_s = float(delay_s)
        self._lock = threading.Lock()
        self._counts = {"connect": 0, "send": 0, "read": 0}
        self._fail = {
            "connect": SpoolFaultInjector._schedule("net-refuse", refuse, seed, ops_window),
            "read": SpoolFaultInjector._schedule("net-torn", torn, seed, ops_window),
        }
        self._delay = SpoolFaultInjector._schedule("net-delay", delay, seed, ops_window)
        self.faults_fired = {"refuse": 0, "torn": 0, "delay": 0}

    def __call__(self, stage: str, url: str) -> None:
        from mpi_opt_tpu.corpus.transport import TornResponse, Unreachable

        with self._lock:
            ordinal = self._counts.get(stage, 0)
            self._counts[stage] = ordinal + 1
            fire = ordinal in self._fail.get(stage, ())
            delay = stage == "read" and ordinal in self._delay
            if fire:
                self.faults_fired["refuse" if stage == "connect" else "torn"] += 1
            if delay:
                self.faults_fired["delay"] += 1
        if delay:
            time.sleep(self.delay_s)
        if not fire:
            return
        if stage == "connect":
            raise Unreachable(
                f"chaos: injected connection refused (op {ordinal}) to {url}"
            )
        raise TornResponse(
            f"chaos: injected torn response (op {ordinal}) from {url}"
        )


def inject_net(
    refuse: int = 0,
    torn: int = 0,
    delay: int = 0,
    delay_s: float = 0.05,
    seed: int = 0,
    ops_window: int | None = None,
):
    """Install a seeded, deterministic network-fault schedule on the
    HTTP transport seam. Returns ``(injector, uninstall)`` — call
    ``uninstall()`` when the drill is over (tests in a finally).
    ``refuse`` connect ordinals are refused, ``torn`` read ordinals
    tear the reply, ``delay`` read ordinals sleep ``delay_s`` first;
    with ``ops_window`` each schedule is a seeded sample of that window
    instead of the first n. The client's capped jittered retry absorbs
    schedules shorter than its attempt budget — and because every retry
    reuses its idempotency key, a torn-but-executed request is answered
    from the server's dedup window, which is exactly what the
    exactly-once drill pins."""
    from mpi_opt_tpu.corpus import transport

    injector = NetFaultInjector(
        refuse=refuse,
        torn=torn,
        delay=delay,
        delay_s=delay_s,
        seed=seed,
        ops_window=ops_window,
    )
    transport.set_net_fault_injector(injector)

    def uninstall() -> None:
        transport.set_net_fault_injector(None)

    return injector, uninstall


@register
class ChaosWorkload(Workload):
    name = "chaos"

    def __init__(
        self,
        inner: str = "quadratic",
        exc: float = 0.0,
        nan: float = 0.0,
        hang: float = 0.0,
        crash: float = 0.0,
        slow: float = 0.0,
        preempt: float = 0.0,
        hang_s: float = 600.0,
        slow_s: float = 0.25,
        seed: int = 0,
        inner_kwargs: dict | None = None,
    ):
        total = exc + nan + hang + crash + slow + preempt
        if total > 1.0:
            raise ValueError(
                f"chaos probabilities sum to {total} > 1 "
                "(exc+nan+hang+crash+slow+preempt)"
            )
        self.inner = get_workload(inner, **(inner_kwargs or {}))
        self.p_exc = exc
        self.p_nan = nan
        self.p_hang = hang
        self.p_crash = crash
        self.p_slow = slow
        self.p_preempt = preempt
        self.hang_s = hang_s
        self.slow_s = slow_s
        self.chaos_seed = seed

    def default_space(self) -> SearchSpace:
        return self.inner.default_space()

    # -- the seeded draw ---------------------------------------------------

    def fault_for(self, params: dict) -> str | None:
        """Which fault (if any) this trial draws: a pure function of
        (chaos_seed, cleaned params). SHA-256, not hash(): stable across
        processes regardless of PYTHONHASHSEED."""
        payload = json.dumps(
            [
                self.chaos_seed,
                sorted(
                    (k, repr(v))
                    for k, v in params.items()
                    if not k.startswith("__")
                ),
            ]
        )
        h = hashlib.sha256(payload.encode()).digest()
        u = int.from_bytes(h[:8], "big") / 2**64  # uniform [0, 1)
        edge = 0.0
        # preempt is LAST in the cascade on purpose: appending a new
        # fault keeps every existing (seed, params) draw identical when
        # its probability is 0, so the pinned counts in the determinism
        # drills survive the addition
        for fault, p in (
            ("exc", self.p_exc),
            ("nan", self.p_nan),
            ("hang", self.p_hang),
            ("crash", self.p_crash),
            ("slow", self.p_slow),
            ("preempt", self.p_preempt),
        ):
            edge += p
            if u < edge:
                return fault
        return None

    def _apply(self, fault: str | None, params: dict) -> None:
        """Pre-evaluation faults (exceptions and stalls)."""
        if fault == "exc":
            raise ChaosInjectedError(
                f"chaos: injected trial failure (seed={self.chaos_seed})"
            )
        if fault == "preempt":
            # the platform-preemption stand-in: SIGTERM to SELF. Under a
            # ShutdownGuard (driver process) this only sets the drain
            # flag and the evaluation CONTINUES — the trial completes,
            # gets journaled, and the sweep drains at the batch
            # boundary, so after a --resume the same trial replays
            # instead of re-preempting (the restart loop converges).
            os.kill(os.getpid(), signal.SIGTERM)
        elif fault == "hang":
            time.sleep(self.hang_s)
        elif fault == "crash":
            # the hard-death stand-in: no exception to catch, no result
            # queued — exactly what a segfaulted/OOM-killed worker looks
            # like to the parent
            os._exit(13)
        elif fault == "slow":
            time.sleep(self.slow_s)

    # -- stateless protocol ------------------------------------------------

    def evaluate(self, params: dict, budget: int, seed: int) -> float:
        fault = self.fault_for(params)
        self._apply(fault, params)
        score = self.inner.evaluate(params, budget, seed)
        return float("nan") if fault == "nan" else score

    # -- stateful protocol (delegated; faults fire in train) ---------------

    @property
    def stateful(self) -> bool:
        # NOT the base class's "did the subclass override train" probe:
        # this wrapper always defines train, but it is only genuinely
        # stateful when the inner workload is
        return self.inner.stateful

    def init_state(self, params: dict, seed: int):
        return self.inner.init_state(params, seed)

    def train(self, state, params: dict, steps: int, seed: int):
        fault = self.fault_for(params)
        self._apply(fault, params)
        state, score = self.inner.train(state, params, steps, seed)
        return state, (float("nan") if fault == "nan" else score)
