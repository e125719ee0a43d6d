"""Test harness: force CPU with 8 virtual devices.

The sandbox has no accelerator; sharding and mesh code is validated on
a virtual 8-device CPU mesh (the same mesh code runs unchanged on real
chips). The driver's tier-1 command sets ``JAX_PLATFORMS=cpu``, which
jax honours; the config pin below makes a bare ``pytest`` do the same.
"""

# -- lock-order runtime sanitizer (ISSUE 15) ------------------------------
# Installed BEFORE any mpi_opt_tpu import so module-level locks
# (leases._TOKEN_LOCK, trace._TID_LOCK, ...) are created through the
# patched threading.Lock factory and come back order-tracked; locks
# created by jax/orbax/stdlib frames stay the real primitive.
import sanitizers  # tests/ is on sys.path (pytest's conftest-dir rule)

sanitizers.install_lock_order_tracker()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", False)
# Persistent compilation cache: OFF in the pytest process, whatever an
# in-process ``cli.main`` run asks for (utils/compile_cache.py only
# places the directory; this switch is what jax consults). A run is
# then the same whether or not an earlier one left entries behind, and
# the topology compiles of test_chip_compile.py stay silent
# (on-chip-measurement guide, section 2). Subprocess drills are CLI processes
# and do use the cache, in the checkout's own .jax_cache.
jax.config.update("jax_enable_compilation_cache", False)


# The `setup op=startup` span (obs/trace.py) is emitted once a process,
# with the process's age as its duration: in this long-lived pytest
# process it would put minutes of "setup" into whichever test traces
# first. Latched as already emitted here; tests/test_obs.py re-arms it
# where it is the subject.
from mpi_opt_tpu.obs import trace as _trace  # noqa: E402

_trace._STARTUP_EMITTED = True


# -- runtime sanitizers (ISSUE 9 + 15; tests/sanitizers.py) ---------------
#
# Every test is followed by a leak check over process-global state:
# non-daemon threads, SIGTERM/SIGINT dispositions, the trace sink,
# heartbeat, integrity observer, shutdown guard + slice hook — plus any
# lock-order inversion the tracker observed during the test (racelint's
# runtime twin: per-thread acquisition order over the tracked locks,
# reset per test). Snapshot-based (only state THIS test added fails it)
# so an accepted leak never cascades. Opt out with @pytest.mark.leaks_ok
# for drills that leave state on purpose.

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _runtime_sanitizers(request):
    import sanitizers  # tests/ is on sys.path via pytest's conftest rule

    before = sanitizers.snapshot()
    yield
    if request.node.get_closest_marker("leaks_ok") is not None:
        return
    problems = sanitizers.leaks(before)
    if problems:
        pytest.fail(
            "runtime sanitizers: leaked process-global state:\n  - "
            + "\n  - ".join(problems),
            pytrace=False,
        )
