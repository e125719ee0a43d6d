"""Tracing/profiling hooks (SURVEY.md §5).

``profile_window(dir)`` wraps a measured region in a ``jax.profiler``
trace when a directory is given and is a zero-cost no-op otherwise, so
callers sprinkle it unconditionally:

    with profile_window(args.profile_dir):
        run_search(...)

``profile_window(dir, launches=(A, B))`` defers the trace to a LAUNCH
WINDOW: the profiler starts when launch A begins and stops after launch
B completes (1-based, inclusive — ``--profile-launches`` on the CLI).
The fused drivers (and the driver loop, per batch) call ``launch_tick``
at the top of every launch; profiling a steady-state launch without the
cold-compile wall is what makes an XLA trace of the hot path readable.

``active()`` reports whether a jax profiler trace is CURRENTLY
recording — obs/trace.py gates its ``jax.profiler.TraceAnnotation``
wrappers on it, so span names ("train", "stage_in") appear on the XLA
timeline exactly when a trace is being taken and cost nothing
otherwise.

The dump is TensorBoard-loadable (``xplane.pb`` under
``<dir>/plugins/profile/<run>/``); where the device-side trace is
unavailable, the host-side trace (dispatch gaps, transfer waits) still
lands and a warning is printed rather than failing the run being
measured.
"""

from __future__ import annotations

import contextlib
import sys

_ACTIVE = False  # a jax profiler trace is currently recording
_DIRECTORY = None  # where the recording trace will be written
_WINDOW = None  # the installed _LaunchWindow, if any


def active() -> bool:
    return _ACTIVE


def _span(op: str, directory):
    """The ``profile`` span around a profiler start or stop. Opened
    while ``_ACTIVE`` is false on both sides, so it is never a
    ``TraceAnnotation`` itself; ``dir`` tells a reader of the stream
    where the trace lies. (obs/trace.py imports this module, hence the
    import here.)"""
    from mpi_opt_tpu.obs import trace

    return trace.span("profile", op=op, dir=str(directory))


def _start(directory) -> bool:
    global _ACTIVE, _DIRECTORY
    import jax

    with _span("start", directory):
        try:
            jax.profiler.start_trace(str(directory))
        except Exception as e:
            print(
                f"[profile] trace start failed ({type(e).__name__}: {e}); "
                "continuing unprofiled",
                file=sys.stderr,
            )
            return False
    _ACTIVE = True
    _DIRECTORY = directory
    return True


def _stop() -> None:
    global _ACTIVE
    if not _ACTIVE:
        return
    _ACTIVE = False
    import jax

    with _span("stop", _DIRECTORY):  # the stop writes the trace: seconds
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            print(
                f"[profile] trace stop failed ({type(e).__name__}: {e})",
                file=sys.stderr,
            )


class _LaunchWindow:
    """Deferred profiler start/stop driven by launch ticks."""

    def __init__(self, directory, start: int, stop: int):
        self.directory = directory
        self.start = int(start)  # first profiled launch (1-based)
        self.stop = int(stop)  # last profiled launch (inclusive)
        self.n = 0

    def tick(self) -> None:
        self.n += 1
        if self.n == self.start:
            _start(self.directory)
        elif self.n == self.stop + 1:
            _stop()


def launch_tick() -> None:
    """Called at the top of every launch/batch; no-op unless a launch
    window is installed (the common case — one branch on a global)."""
    if _WINDOW is not None:
        _WINDOW.tick()


def parse_launch_window(spec: str):
    """``"A"`` or ``"A:B"`` -> (A, B), 1-based inclusive; ValueError on
    malformed/inverted input (the CLI maps it to a usage error)."""
    parts = spec.split(":")
    if len(parts) == 1:
        a = b = int(parts[0])
    elif len(parts) == 2:
        a, b = int(parts[0]), int(parts[1])
    else:
        raise ValueError(f"expected N or A:B, got {spec!r}")
    if a < 1 or b < a:
        raise ValueError(
            f"launch window must be 1-based and non-inverted, got {spec!r}"
        )
    return a, b


@contextlib.contextmanager
def profile_window(directory=None, launches=None):
    global _WINDOW
    if not directory:
        yield
        return
    if launches is not None:
        # guard only the install/teardown bookkeeping: profiling must
        # never kill (or mask an exception from) the run being measured
        _WINDOW = _LaunchWindow(directory, *launches)
        try:
            yield
        finally:
            _WINDOW = None
            _stop()  # window still open (fewer launches than B): close it
        return
    started = _start(directory)
    try:
        yield
    finally:
        if started:
            _stop()
