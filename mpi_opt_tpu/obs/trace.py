"""Span tracing: where a sweep's seconds go, from the system itself.

``span("phase", **attrs)`` wraps a region of host code; on exit it
emits ONE duration record into the configured metrics sink::

    {"event": "span", "span": "train", "dur_s": 1.23, "self_s": 1.01,
     "t": ..., "ts": <end, epoch>, "rank": 0, "tid": 0, ...attrs}

Design rules:

- **Null mode costs nothing.** With no sink configured (``configure``
  never called — every library/test entry point), a span does zero JSON
  work: it only pushes/pops a thread-local frame, which the heartbeat's
  ``phase`` field (health/heartbeat.py) needs even untraced. This is
  the ``null_logger`` contract extended to tracing.
- **Thread-safe.** Each thread owns its own span stack (StagingEngine's
  background transfer thread traces its fetches concurrently with the
  main loop); records carry a small ``tid`` so a consumer can rebuild
  per-thread nesting. The sink itself (MetricsLogger) serializes
  writes under its own lock.
- **Self time is computed at exit, not reconstructed.** Every span
  accumulates its direct children's durations in its stack frame;
  ``self_s = dur_s - children``. Attribution (obs/report.py) sums
  ``self_s``, so nested spans never double-count wall.
- **Tracing must never kill the run being traced**: sink failures warn
  once and go quiet (the heartbeat rule).
- **Correlatable**: ``ts`` is absolute epoch (MetricsLogger stamps it),
  so multi-rank launch.py streams and multi-tenant service streams
  merge by timestamp after the fact. ``rank``/``tenant`` tags are set
  at ``configure`` time.

Compile visibility rides jax's own monitoring events: a registered
duration listener turns every XLA backend compile into a ``compile``
span (``cache="cold"``) and every persistent-compilation-cache load
into one with ``cache="persistent"`` — an in-process jit-cache hit
emits nothing, which is itself the signal (a launch span with no
compile span inside it hit the jit cache). The listener charges the
duration to the enclosing span's child accumulator so self times stay
exclusive.

When a ``jax.profiler`` trace is active (utils/profiling.py), each
span additionally enters a ``jax.profiler.TraceAnnotation`` of the same
name, so XLA timelines carry sweep semantics ("train", "stage_in")
instead of bare op names.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Optional

from mpi_opt_tpu.utils import profiling

# -- process-global sink + tags ------------------------------------------

_SINK = None  # the MetricsLogger spans emit through (None = disabled)
_TAGS: dict = {}  # rank/tenant labels stamped into every record
# warn-once latch, deliberately unlocked: the race window is two
# threads both observing False and both warning — a duplicate warning,
# never a lost error; a lock on the emission failure path buys nothing
# sweeplint: disable=guarded-by -- idempotent warn-once latch: worst race outcome is a duplicate warning
_WARNED = False
_LOCAL = threading.local()  # .stack: list[[name, child_dur]]; .tid; .off
_TID_LOCK = threading.Lock()
_NEXT_TID = [0]
# best-effort cross-thread "most recently entered, still active" span
# name: the heartbeat's fallback when the BEATING thread holds no span
# (boundary beats happen between spans). Plain assignment — GIL-atomic,
# approximate under races, which is fine for a diagnostic label; a lock
# here would put a contention point inside EVERY span enter/exit.
# sweeplint: disable=guarded-by -- GIL-atomic store of a best-effort diagnostic label; approximate-under-races is the documented contract
_LAST_PHASE: Optional[str] = None


def _stack() -> list:
    st = getattr(_LOCAL, "stack", None)
    if st is None:
        st = _LOCAL.stack = []
        with _TID_LOCK:
            _LOCAL.tid = _NEXT_TID[0]
            _NEXT_TID[0] += 1
    return st


def configure(metrics, rank: int = 0, tenant: Optional[str] = None):
    """Install ``metrics`` (a MetricsLogger) as the span sink; returns
    the PRIOR (sink, tags) state for ``deconfigure`` — the service
    scheduler traces through its own stream while each tenant slice
    re-configures to the tenant's, so configuration must nest."""
    global _SINK, _TAGS
    prior = (_SINK, _TAGS)
    _SINK = metrics
    tags = {"rank": int(rank)}
    if tenant:
        tags["tenant"] = str(tenant)
    _TAGS = tags
    _install_compile_listener()
    _emit_startup()
    return prior


# the ``setup op=startup`` span was emitted (once a process: a later
# configure — a tenant slice, a second in-process CLI run — is not a
# process start)
_STARTUP_EMITTED = False


def _process_age_s() -> Optional[float]:
    """Seconds since the OS started this process (Linux: the start time
    in /proc/self/stat against the boot clock), None where the OS does
    not say."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return None


def _emit_startup() -> None:
    """The span no ``with`` can open: process start to the moment a
    sink exists (interpreter, imports of jax and flax, argument
    parsing, backend start), emitted with its true duration so the
    seconds before the first launch carry a name."""
    global _STARTUP_EMITTED
    if _STARTUP_EMITTED or _SINK is None:
        return
    _STARTUP_EMITTED = True
    age = _process_age_s()
    if age is not None and age > 0:
        _emit("setup", age, age, {"op": "startup"})


def deconfigure(prior=None) -> None:
    """Drop (or restore) the span sink. ``prior`` is ``configure``'s
    return value; None restores the disabled state."""
    global _SINK, _TAGS
    if prior is None:
        _SINK, _TAGS = None, {}
    else:
        _SINK, _TAGS = prior


def save():
    """The current (sink, tags) state, shaped like ``configure``'s
    return value: capture at the top of an in-process CLI run and
    ``deconfigure(saved)`` in its finally, so a tenant slice that exits
    through ANY path (usage error included) restores the server's own
    sink instead of clobbering it."""
    return (_SINK, _TAGS)


def enabled() -> bool:
    return _SINK is not None


def note_device(sp: dict) -> None:
    """Attach the local device kind to an active span's attr dict (the
    setup spans carry it so obs/bubbles.py can default the roofline's
    platform cap from its calibration table without the operator
    passing --peak-tflops). No-op untraced, never raises — a telemetry
    attr must not kill the run."""
    if _SINK is None:
        return
    try:
        import jax

        sp["device"] = str(jax.local_devices()[0].device_kind)
    except Exception:
        pass


def current_phase() -> Optional[str]:
    """The calling thread's innermost active span name, else the most
    recently entered still-active span on any thread (best effort),
    else None. Feeds the heartbeat's ``phase`` field so a stall report
    can say "stalled during stage_in" instead of a bare kill."""
    st = getattr(_LOCAL, "stack", None)
    if st:
        return st[-1][0]
    return _LAST_PHASE


@contextlib.contextmanager
def suppressed():
    """Silence span emission on THIS thread for the body (the flops
    probe lowers tiny programs whose compile spans would pollute the
    sweep's own attribution)."""
    prev = getattr(_LOCAL, "suppress", False)
    _LOCAL.suppress = True
    try:
        yield
    finally:
        _LOCAL.suppress = prev


def _emit(name: str, dur_s: float, self_s: float, attrs: dict) -> None:
    global _WARNED
    sink = _SINK
    if sink is None or getattr(_LOCAL, "suppress", False):
        return
    try:
        sink.log(
            "span",
            span=name,
            dur_s=round(dur_s, 6),
            self_s=round(self_s, 6),
            tid=getattr(_LOCAL, "tid", 0),
            **_TAGS,
            **attrs,
        )
    except Exception as e:
        if not _WARNED:
            _WARNED = True
            import warnings

            warnings.warn(
                f"span emission failed ({type(e).__name__}: {e}); tracing "
                "records may be incomplete for this process",
                stacklevel=3,
            )


@contextlib.contextmanager
def span(name: str, **attrs):
    """Trace one phase of host work; yields a mutable dict for attrs
    only known at exit (``sp["bytes"] = n``). Exceptions propagate
    untouched — the span still emits, so a crashed phase is visible in
    the attribution rather than vanishing from it."""
    st = _stack()
    # an ``op`` attr joins the phase name (``boundary:rung_cut``): the
    # phase feeds heartbeat records and stall attribution, where "which
    # boundary op" is the question — the emitted span keeps the bare
    # name so per-kind aggregation is unchanged
    phase = f"{name}:{attrs['op']}" if "op" in attrs else name
    frame = [phase, 0.0]
    st.append(frame)
    global _LAST_PHASE
    _LAST_PHASE = phase
    ann = None
    if profiling.active():  # TraceAnnotation only under a live profiler
        try:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        except Exception:
            ann = None
    t0 = time.perf_counter()
    try:
        yield attrs
    finally:
        dur = time.perf_counter() - t0
        if ann is not None:
            try:
                ann.__exit__(None, None, None)
            except Exception:
                pass
        st.pop()
        _LAST_PHASE = st[-1][0] if st else None
        if st:
            st[-1][1] += dur  # credit the parent's child accumulator
        _emit(name, dur, max(0.0, dur - frame[1]), attrs)


def traced(name: Optional[str] = None, **attrs):
    """Decorator form of ``span``: ``@traced("save")`` (defaults to the
    function's own name)."""

    def deco(fn):
        import functools

        label = name or fn.__name__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with span(label, **attrs):
                return fn(*args, **kwargs)

        return wrapper

    return deco


# -- compile visibility (jax.monitoring) ---------------------------------

# event key -> how the compile was satisfied. A cold compile records
# the backend_compile duration; a persistent-cache hit records only the
# retrieval time; an in-process jit-cache hit records neither.
_COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "cold",
    "/jax/compilation_cache/cache_retrieval_time_sec": "persistent",
}
_LISTENER_INSTALLED = False


def _on_event_duration(event: str, duration: float, **_kw) -> None:
    kind = _COMPILE_EVENTS.get(event)
    if kind is None or _SINK is None or getattr(_LOCAL, "suppress", False):
        return
    # leaf span synthesized from jax's own measurement: charge it to the
    # enclosing span's children so that span's self time stays exclusive
    st = getattr(_LOCAL, "stack", None)
    during = None
    if st:
        st[-1][1] += float(duration)
        during = st[-1][0]
    _emit("compile", float(duration), float(duration), {"cache": kind, "during": during})


def _install_compile_listener() -> None:
    """Register the jax.monitoring duration listener ONCE per process.
    jax offers no single-listener removal, so the callback stays
    registered and goes inert (``_SINK is None`` check) when tracing is
    deconfigured."""
    global _LISTENER_INSTALLED
    if _LISTENER_INSTALLED:
        return
    _LISTENER_INSTALLED = True
    try:
        import jax

        jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
    except Exception:  # pragma: no cover - jax-less environments
        pass
