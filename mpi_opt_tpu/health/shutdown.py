"""Graceful-shutdown signal protocol (preemption-safe sweeps).

TPU/cloud platforms preempt workers with SIGTERM and only escalate to
SIGKILL after a grace window. A sweep that treats SIGTERM as death
loses the in-flight batch and makes the supervisor burn a retry on a
non-failure; a sweep that ignores it gets SIGKILLed mid-checkpoint.
The protocol here is the middle path:

1. ``ShutdownGuard`` installs SIGTERM/SIGINT handlers that only SET A
   FLAG — nothing is interrupted, no async-unsafe work happens in the
   handler.
2. Drain points (the driver's batch boundary, the fused trainers'
   launch/rung/generation boundaries) poll ``requested()``: when set,
   they finish the in-flight unit, flush durable state (checkpoint
   snapshot, ledger records are already fsync'd), and raise
   ``SweepInterrupted``.
3. The CLI catches it and exits ``EX_TEMPFAIL`` (75, sysexits.h's
   "temporary failure; retry"), the dedicated code ``launch.py``
   classifies as PREEMPTION: coordinated restart with ``--resume``
   that does NOT consume the ``--retries`` budget.

A second SIGINT escalates to an immediate ``KeyboardInterrupt`` (the
interactive convention: first Ctrl-C drains, second aborts). Repeated
SIGTERM stays graceful on purpose — a supervisor forwarding SIGTERM to
a process group whose members already received the platform's signal
must not turn the drain into an abort.
"""

from __future__ import annotations

import signal
import threading
from typing import Callable, Optional

# re-export (utils/exitcodes.py is the one home for the code values;
# the historical import surface `health.shutdown.EX_TEMPFAIL` stays)
from mpi_opt_tpu.utils.exitcodes import EX_TEMPFAIL  # noqa: F401


class SweepInterrupted(RuntimeError):
    """Raised at a drain point after a graceful-shutdown request.

    By construction the in-flight batch/launch has completed and durable
    state (checkpoint snapshot, ledger journal) is flushed; the catcher
    should summarize and exit ``EX_TEMPFAIL``.
    """

    def __init__(self, signal_name: Optional[str] = None, at: str = ""):
        self.signal = signal_name or "SIGTERM"
        self.at = at
        super().__init__(
            f"graceful shutdown ({self.signal})" + (f" at {at}" if at else "")
        )


_ACTIVE: Optional["ShutdownGuard"] = None


class ShutdownGuard:
    """Context manager owning the process's graceful-shutdown flag.

    Installs the flag-setting handlers on enter (main thread only —
    elsewhere the poll API still works, signal delivery is the host
    application's concern) and restores the previous handlers on exit,
    so in-process callers (tests, library embedders) never leak a
    changed SIGINT disposition.
    """

    def __init__(self):
        self.requested = False
        self.signal_name: Optional[str] = None
        self.installed = False
        self._prev: dict = {}
        self._outer: Optional[ShutdownGuard] = None
        self._signal_seen = False

    def _handle(self, signum, frame):
        global _DELIVERED
        name = signal.Signals(signum).name
        # record every REAL signal delivery at module level: nested
        # guards (the sweep service runs each tenant slice under its
        # own guard inside the server's) consume the flag with the
        # inner guard, but the server still needs to know, after the
        # slice returns, whether the drain it observed was its own
        # cooperative time-slice or the platform telling the whole
        # process to die
        _DELIVERED = name
        if self._signal_seen and signum == signal.SIGINT:
            # a REAL signal already arrived and now Ctrl-C: the user
            # wants out NOW, not after the batch. Keyed on delivered
            # signals, NOT self.requested — a programmatic slice/cancel
            # request() must not turn the user's FIRST Ctrl-C into a
            # mid-step KeyboardInterrupt that skips the drain
            raise KeyboardInterrupt
        self._signal_seen = True
        self.requested = True
        # a real signal outranks a programmatic slice request: the
        # supervisor/platform asked the PROCESS to stop, and the exit
        # summary should say so even if a slice fired first
        if self.signal_name is None or self.signal_name == SLICE:
            self.signal_name = name

    def __enter__(self) -> "ShutdownGuard":
        global _ACTIVE
        if threading.current_thread() is threading.main_thread():
            for sig in (signal.SIGTERM, signal.SIGINT):
                self._prev[sig] = signal.signal(sig, self._handle)
            self.installed = True
        self._outer = _ACTIVE
        _ACTIVE = self
        return self

    def __exit__(self, *exc):
        global _ACTIVE
        if self.installed:
            for sig, prev in self._prev.items():
                signal.signal(sig, prev)
            self.installed = False
        _ACTIVE = self._outer
        return False


def requested() -> bool:
    """Is a graceful shutdown pending? (False when no guard is active.)"""
    return _ACTIVE is not None and _ACTIVE.requested


def active_signal() -> Optional[str]:
    return None if _ACTIVE is None else _ACTIVE.signal_name


# -- scoped programmatic drain requests (the sweep service's time-slice) --
#
# The service preempts a running tenant by the SAME mechanism a platform
# SIGTERM uses: set the active guard's drain flag and let the sweep's
# next natural boundary (gen_chunk / rung / TPE batch / wave — the
# launch_boundary call sites; the driver's batch boundary) flush its
# snapshot and raise SweepInterrupted. A time-sliced sweep therefore
# leaves EXACTLY the durable state a preempted one does, which is why a
# parked tenant's ledger is bit-identical to an uninterrupted run's.
# The request is scoped to the active guard: when the slice's guard
# exits, the flag dies with it and nothing leaks to the next tenant.

#: the pseudo-signal name a cooperative time-slice drain reports
SLICE = "SLICE"

#: the most recent REAL signal delivered to a guard's handler in this
#: process (None until one arrives); survives guard exit so a scheduler
#: can distinguish "my slice expired" from "the platform killed us".
#: Written from the signal handler, so it MUST stay a bare GIL-atomic
#: store: a handler that takes a lock can interrupt that lock's own
#: holder on the same thread and self-deadlock (the signal-safety rule)
# sweeplint: disable=guarded-by -- signal handlers may only flag-set; a lock in a handler can self-deadlock against its interrupted holder
_DELIVERED: Optional[str] = None

#: scheduler-installed per-boundary callback (see set_slice_hook)
_SLICE_HOOK: Optional[Callable[[str], None]] = None


def request(source: str = SLICE) -> bool:
    """Programmatically request a graceful drain on the active guard.

    Returns False (no-op) when no guard is active. A real signal name
    already recorded is never overwritten — the platform's SIGTERM
    outranks a slice."""
    if _ACTIVE is None:
        return False
    if _ACTIVE.signal_name is None:
        _ACTIVE.signal_name = source
    _ACTIVE.requested = True
    return True


def delivered_signal() -> Optional[str]:
    """The most recent REAL signal a guard handler received in this
    process, or None. Unlike ``active_signal`` this survives guard
    exit; clear it with ``clear_delivered`` before the window you want
    to observe."""
    return _DELIVERED


def clear_delivered() -> None:
    global _DELIVERED
    _DELIVERED = None


def set_slice_hook(fn: Optional[Callable[[str], None]]) -> None:
    """Install the scheduler's cooperative-slice callback.

    ``fn(stage)`` is invoked from every non-final drain point
    (``train.common.launch_boundary``, the driver's batch boundary)
    BEFORE the drain flag is checked, so a hook that decides the slice
    budget is spent can ``request()`` and have the very same boundary
    honor it. The hook must be cheap and must not raise — it runs on
    the sweep's hot host path — with ONE sanctioned exception:
    ``parallel/coord.py``'s boundary agreement chains onto this hook
    and may raise ``CoordWedged`` when a peer rank never reaches the
    boundary; that is a deliberate process-fatal verdict (exit, let
    the supervisor restart the world), not hot-path work."""
    global _SLICE_HOOK
    _SLICE_HOOK = fn


def get_slice_hook() -> Optional[Callable[[str], None]]:
    """The currently installed slice hook (None without one) — for
    wrappers like the coord plane's drain agreement that chain onto an
    existing scheduler hook instead of displacing it."""
    return _SLICE_HOOK


def clear_slice_hook() -> None:
    set_slice_hook(None)


#: the installed boundary observer (see set_boundary_observer)
_BOUNDARY_OBSERVER: Optional[Callable] = None


def set_boundary_observer(fn: Optional[Callable]) -> None:
    """Install (or, with None, remove) the boundary observer:
    ``fn(stage, state)`` is called from every
    ``train.common.launch_boundary``, final ones included, before the
    slice hook, with the population state the sweep holds there (None
    where the call site passes none). A reader of the state at a
    boundary — a benchmark's comparison, a debugger — needs no frame
    walk. Same rules as the slice hook: cheap, and it does not raise."""
    global _BOUNDARY_OBSERVER
    _BOUNDARY_OBSERVER = fn


def get_boundary_observer() -> Optional[Callable]:
    return _BOUNDARY_OBSERVER


def poll_slice(stage: str) -> None:
    """Drain points' service call: give an installed slice hook its
    per-boundary look (no-op without one)."""
    if _SLICE_HOOK is not None:
        _SLICE_HOOK(stage)
