"""Pinning tiny host-side jax ops to the host CPU backend.

The host search layer (algorithms' sampling, the space's typed-value
materialization) runs scalar-to-few-KB jax ops between device
evaluations. Run on the DEFAULT device, each one is a dispatch to the
accelerator and a fetch back, for a result the host needs at once.
jax.random is platform-invariant (threefry), so CPU-pinning changes no
sampled value — only where the op runs. What the pin is worth on a
locally attached chip has not been measured (ROADMAP D4); on the
previous installation, whose chip sat behind a slow remote connection,
it was most of a driver-tier search's wall (not re-measured).
"""

from __future__ import annotations

import contextlib

import jax

_CPU = None
_CHECKED = False


def host_ops():
    """Context manager: run enclosed jax ops on the host CPU device.

    No-op where this process has no CPU backend: a pure-CPU process
    already defaults there, and one started with ``JAX_PLATFORMS=tpu``
    initializes nothing else, so its host ops run on the chip — same
    values, one dispatch each. That is accepted, not hidden: leave
    JAX_PLATFORMS unset (jax then brings the CPU backend up beside the
    TPU one) to get the pin.
    """
    global _CPU, _CHECKED
    if not _CHECKED:
        _CHECKED = True
        try:
            # local_devices, not devices: in a multi-process world
            # jax.devices() spans every process, and devices("cpu")[0]
            # is PROCESS 0's device — pinning another process's host
            # ops to it commits tiny arrays to a remote device and
            # kills that process (found by the 2-process fused-SHA
            # test: rank 1 died exactly there)
            _CPU = jax.local_devices(backend="cpu")[0]
        except RuntimeError:
            _CPU = None
    if _CPU is None:
        return contextlib.nullcontext()
    return jax.default_device(_CPU)
