"""Runtime sanitizers: per-test leak checks for process-global state.

Three recurring review-round bug classes — a background thread left
running, a signal handler left installed (the ShutdownGuard
scope/restore contract), a metrics/trace/heartbeat sink left configured
by an in-process CLI run — turn into hard test failures here instead of
flaky cross-test contamination three files later. The check is
snapshot-based: whatever global state a test STARTED with is the
baseline (a prior test's accepted leak must not cascade-fail every
test after it); only state the test itself added and failed to clean up
fails it.

The lock-order sanitizer (ISSUE 15) is racelint's runtime twin: at
session start, ``install_lock_order_tracker`` patches
``threading.Lock``/``RLock`` so locks CREATED FROM mpi_opt_tpu code
(judged by the creating frame's module — exactly the named locks the
static symbol table discovers, tagged with the same creation site) come
back wrapped. Every successful acquisition is recorded against the
per-thread held set; acquiring B while holding A registers the edge
A->B, and an acquisition whose reverse edge was already observed in
this test's window is an ORDER INVERSION — the statically-invisible
half of the lock-order checker, because runtime order flows through
callbacks and dynamic dispatch the AST cannot follow. ``snapshot()``
opens the per-test window (edges reset — two tests may legitimately
use opposite orders on fresh lock instances); ``leaks()`` reports any
inversion observed since. Locks created outside mpi_opt_tpu (jax,
orbax, stdlib internals) get the real primitive: zero overhead, zero
false positives from library internals.

Wired as an autouse fixture in tests/conftest.py. Opt out per test with
``@pytest.mark.leaks_ok`` (registered in pytest.ini) for drills that
intentionally leave state — e.g. SIGKILL-shaped subprocess kills whose
in-process twin deliberately abandons a wedged worker thread.
"""

from __future__ import annotations

import contextlib
import signal
import sys
import threading

import pytest

#: signals the ShutdownGuard contract covers (install-on-enter,
#: restore-on-exit); SIGINT also guards against tests clobbering
#: pytest's own KeyboardInterrupt handling
_GUARDED_SIGNALS = ("SIGTERM", "SIGINT")

#: grace given to teardown-in-flight threads (an orbax async-save or a
#: pool shutdown may still be unwinding when the test body returns;
#: joining briefly separates "slow teardown" from "leaked forever")
_JOIN_GRACE_S = 2.0


#: seconds a test's call phase may take, for every test alike: more than
#: four times the dearest test of a serial tier-1 run on 8 cores (25 s)
#: and twice the dearest before PR 30 (52 s; CHANGES.md). There is no
#: marker, option or environment variable that raises it: a test that
#: needs longer gets smaller, or is marked `slow`.
TEST_LIMIT_S = 120.0


@contextlib.contextmanager
def time_limit(seconds: float, name: str):
    """Fail ``name`` once the block has run for ``seconds``.

    An interval timer on the main thread: ``SIGALRM``'s handler raises
    pytest's failure where the test stands, so its ``finally`` clauses
    and fixtures unwind as after any failed assertion (a child a test
    runs under its own shorter ``timeout=`` still fails by that first; a
    longer one is cut here, and ``subprocess.run`` kills the child on the
    way out). Python runs a handler between two bytecodes, so a call
    that never comes back from C is not interrupted: that is the
    command's own ``timeout``. The timer and the previous handler are
    put back on every exit. ``SIGALRM`` is not one of the signals the
    leak check watches (``_GUARDED_SIGNALS``), and nothing under
    ``mpi_opt_tpu/`` installs a handler for it.
    """

    def expired(signum, frame):
        pytest.fail(f"{name} ran past the limit of {seconds:g} s a test has", pytrace=True)

    previous = signal.signal(signal.SIGALRM, expired)
    was = signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, *was)
        signal.signal(signal.SIGALRM, previous)


def _live_threads() -> dict:
    return {t.ident: t for t in threading.enumerate() if t.is_alive()}


def _handlers() -> dict:
    return {
        name: signal.getsignal(getattr(signal, name)) for name in _GUARDED_SIGNALS
    }


def snapshot() -> dict:
    """The process-global state a test is allowed to return to."""
    from mpi_opt_tpu.health import heartbeat, shutdown
    from mpi_opt_tpu.obs import trace
    from mpi_opt_tpu.utils import integrity

    return {
        "threads": set(_live_threads()),
        "handlers": _handlers(),
        "trace": trace.save(),
        "heartbeat": heartbeat.active(),
        "observer": integrity._OBSERVER,
        "guard": shutdown._ACTIVE,
        "slice_hook": shutdown._SLICE_HOOK,
        "boundary_observer": shutdown._BOUNDARY_OBSERVER,
        "beat_listener": heartbeat._LISTENER,
        "spool_faults": _spool_faults(),
        "resource_state": _resource_state(),
        # opens the per-test lock-order window (edges reset, violation
        # count snapshotted) — the one snapshot field that is also a
        # boundary marker, because acquisition order is an OBSERVATION
        # stream, not a restorable state
        "lock_order": _TRACKER.begin_window(),
    }


def _spool_faults():
    # lazy import: the sanitizer must not drag the service package into
    # every test module's import graph
    from mpi_opt_tpu.service import spool

    return spool._FAULTS


def _resource_state():
    # the resource-exhaustion layer's process globals (ISSUE 13): the
    # event observer plus the two chaos seams (inject_enospc /
    # inject_oom) — a leaked injector would fault every later test's
    # snapshot saves or launches
    from mpi_opt_tpu.utils import resources

    return (resources._OBSERVER, resources._DISK_FAULTS, resources._LAUNCH_FAULTS)


def leaks(before: dict) -> list:
    """Human-readable leak descriptions vs the ``before`` snapshot
    (empty = clean). Pure check — mutates nothing, so a failing test's
    OWN exception stays the headline and the leak report rides along."""
    from mpi_opt_tpu.health import heartbeat, shutdown
    from mpi_opt_tpu.obs import trace
    from mpi_opt_tpu.utils import integrity

    problems = []

    # -- non-daemon thread leaks (daemon threads die with the process
    # and jax/tensorstore own long-lived internal ones; NON-daemon
    # threads a test started and never joined hang the interpreter at
    # exit and poison every later test's timing)
    fresh = [
        t
        for ident, t in _live_threads().items()
        if ident not in before["threads"] and not t.daemon
    ]
    deadline_each = _JOIN_GRACE_S / max(1, len(fresh))
    for t in fresh:
        t.join(deadline_each)
        if t.is_alive():
            problems.append(
                f"leaked non-daemon thread {t.name!r} (still alive "
                f"{_JOIN_GRACE_S:.0f}s after the test) — join/close it "
                "(StagingEngine.close, backend.close, server shutdown)"
            )

    # -- signal-handler restore (the ShutdownGuard contract: handlers
    # installed on enter are restored on exit, even on error paths)
    for name, prev in before["handlers"].items():
        now = signal.getsignal(getattr(signal, name))
        if now is not prev and now != prev:
            problems.append(
                f"{name} handler changed across the test "
                f"({prev!r} -> {now!r}) — a ShutdownGuard (or raw "
                "signal.signal call) was not scoped/restored"
            )

    # -- process-global sinks (an in-process cli.main/serve run must
    # deconfigure on every exit path; a leftover sink makes later tests
    # emit into a dead logger's closed file)
    if trace.save() != before["trace"]:
        problems.append(
            "trace sink left configured — obs.trace.deconfigure(prior) "
            "missing on an exit path (cli.main's finally is the pattern)"
        )
    if heartbeat.active() is not before["heartbeat"]:
        problems.append(
            "heartbeat left configured — health.heartbeat.deconfigure() "
            "missing on an exit path"
        )
    if integrity._OBSERVER is not before["observer"]:
        problems.append(
            "integrity observer left installed — "
            "utils.integrity.clear_observer() missing on an exit path"
        )
    if shutdown._ACTIVE is not before["guard"]:
        problems.append(
            "ShutdownGuard left active — the guard's __exit__ never ran "
            "(use `with ShutdownGuard():`, never enter it bare)"
        )
    if shutdown._SLICE_HOOK is not before["slice_hook"]:
        problems.append(
            "slice hook left installed — shutdown.clear_slice_hook() "
            "missing on a scheduler exit path"
        )
    if shutdown._BOUNDARY_OBSERVER is not before["boundary_observer"]:
        problems.append(
            "boundary observer left installed — "
            "shutdown.set_boundary_observer(None) missing on an exit path"
        )
    if heartbeat._LISTENER is not before["beat_listener"]:
        problems.append(
            "heartbeat beat listener left installed — "
            "heartbeat.clear_beat_listener() missing on a slice exit "
            "path (the lease Refresher must die with its slice)"
        )
    if _spool_faults() is not before["spool_faults"]:
        problems.append(
            "spool fault injector left installed — the uninstall() from "
            "chaos.inject_spool_faults must run in a finally"
        )
    if _resource_state() != before["resource_state"]:
        problems.append(
            "resource-layer state left installed (observer or "
            "inject_enospc/inject_oom seam) — clear_observer() / the "
            "injector's uninstall() must run in a finally"
        )
    problems.extend(_TRACKER.violations[before.get("lock_order", 0):])
    return problems


# -- lock-order tracker (ISSUE 15) ----------------------------------------

#: the REAL primitives, captured before any patching so the wrappers
#: (and the tracker's own internal lock) never recurse into themselves
_REAL_LOCK = threading.Lock
_REAL_RLOCK = threading.RLock


class _OrderTracker:
    """Per-thread acquisition order + the observed edge graph.

    Fast path: acquiring with an empty held set only appends to a
    thread-local list. Edges/inversions are only computed when locks
    actually nest, under a raw (untracked) internal lock.
    """

    def __init__(self):
        self._local = threading.local()
        self._mu = _REAL_LOCK()
        self.edges = {}  # (id_a) -> {id_b: site}  meaning a held before b
        self.names = {}  # lock id -> display name
        self.violations = []  # human-readable, append-only

    def _held(self):
        h = getattr(self._local, "held", None)
        if h is None:
            h = self._local.held = []
        return h

    def begin_window(self) -> int:
        """Open a per-test observation window: the edge graph resets
        (fresh lock instances may legitimately order differently in
        different tests) and the current violation count is the
        baseline ``leaks`` judges against."""
        with self._mu:
            self.edges = {}
        return len(self.violations)

    def note_acquire(self, lock_id: int, name: str, blocking: bool = True) -> None:
        held = self._held()
        if held and blocking:
            # a NON-blocking acquisition records no edge and judges no
            # inversion — a trylock never waits, so it cannot close a
            # deadlock cycle (the same rule the static lock-order
            # checker applies); it still enters the held list below,
            # because blocking acquisitions made UNDER it do wait
            with self._mu:
                self.names[lock_id] = name
                for outer_id, outer_name in held:
                    if outer_id == lock_id:
                        continue  # reentrant RLock acquire
                    rev = self.edges.get(lock_id, {})
                    if outer_id in rev:
                        self.violations.append(
                            f"lock-order inversion: {name!r} acquired "
                            f"while holding {outer_name!r}, but the "
                            f"opposite nesting was observed at "
                            f"{rev[outer_id]} — two threads taking these "
                            "paths concurrently deadlock"
                        )
                    self.edges.setdefault(outer_id, {})[lock_id] = _site()
        held.append((lock_id, name))

    def note_release(self, lock_id: int) -> None:
        held = self._held()
        for i in range(len(held) - 1, -1, -1):
            if held[i][0] == lock_id:
                del held[i]
                return


def _site() -> str:
    """The acquiring CALLER's file:line — the first frame above the
    tracker/wrapper machinery AND threading.py (Condition-mediated
    acquisitions enter via Condition.__enter__/wait), so the edge's
    recorded site points at engine (or test) code."""
    depth = 2
    while True:
        try:
            f = sys._getframe(depth)
        except ValueError:  # pragma: no cover - shallow stack
            return "?"
        fname = f.f_code.co_filename
        if not fname.endswith(("sanitizers.py", "threading.py")):
            return f"{fname.rsplit('/', 1)[-1]}:{f.f_lineno}"
        depth += 1


_TRACKER = _OrderTracker()

#: monotonic TrackedLock identity — NOT id(): a garbage-collected
#: lock's address is immediately reused by CPython's freelist, and a
#: fresh lock inheriting a dead lock's edges would fabricate
#: inversions between unrelated locks
_SERIAL_MU = _REAL_LOCK()
_SERIAL = [0]


class TrackedLock:
    """A Lock/RLock proxy that reports successful acquisitions and
    releases to the order tracker. Supports the full surface the
    engine's code (and threading.Condition wrapping one) uses:
    context manager, ``acquire(blocking=, timeout=)``, ``release``,
    ``locked``."""

    __slots__ = ("_inner", "name", "_serial")

    def __init__(self, inner, name: str):
        self._inner = inner
        self.name = name
        with _SERIAL_MU:
            _SERIAL[0] += 1
            self._serial = _SERIAL[0]

    def acquire(self, blocking=True, timeout=-1):
        got = self._inner.acquire(blocking, timeout)
        if got:
            _TRACKER.note_acquire(self._serial, self.name, bool(blocking))
        return got

    def release(self):
        self._inner.release()
        _TRACKER.note_release(self._serial)

    def locked(self):
        return self._inner.locked()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def __repr__(self):  # pragma: no cover - debugging aid
        return f"<TrackedLock {self.name} {self._inner!r}>"


def is_tracked(lock) -> bool:
    return isinstance(lock, TrackedLock)


def tracked_lock(name: str) -> TrackedLock:
    """A tracked lock by explicit request — the seeded-inversion drill
    and the sanitizer's own unit tests."""
    return TrackedLock(_REAL_LOCK(), name)


_INSTALLED = False


def install_lock_order_tracker() -> None:
    """Patch ``threading.Lock``/``RLock`` for the session: creations
    whose calling frame lives in mpi_opt_tpu come back tracked, tagged
    with their creation site (module:line — the same identity the
    static symbol table records); every other caller gets the real
    primitive untouched. Idempotent; test-session-only by design (the
    production CLI never imports this module)."""
    global _INSTALLED
    if _INSTALLED:
        return
    _INSTALLED = True

    def _factory(real, kind):
        def make():
            f = sys._getframe(1)
            mod = f.f_globals.get("__name__", "")
            if mod.startswith("mpi_opt_tpu"):
                name = f"{mod}:{f.f_lineno} ({kind})"
                return TrackedLock(real(), name)
            return real()

        return make

    threading.Lock = _factory(_REAL_LOCK, "Lock")
    threading.RLock = _factory(_REAL_RLOCK, "RLock")
