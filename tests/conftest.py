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


# -- a limit on every test (ISSUE 30; tests/sanitizers.py time_limit) ------
#
# Around the call phase only: a session- or module-scoped fixture builds
# for every test that follows and is not one test's time.


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    with sanitizers.time_limit(sanitizers.TEST_LIMIT_S, item.nodeid):
        yield


# -- one workload a session (ISSUE 30) --------------------------------------

_WORKLOADS: dict = {}  # (name, label, attrs, kwargs) -> the session's instance


@pytest.fixture(scope="session")
def shared_workload():
    """``shared_workload(name, label=None, attrs=None, **kwargs)``: the
    session's ONE ``get_workload(name, **kwargs)`` for those arguments.

    ``train/common.py workload_arrays`` keeps the trainer on the
    workload instance and ``train/population.py trainer_jit`` keeps the
    compiled programs on the trainer, so the tests of a file that share
    the instance trace, lower and compile each program once and not
    once a test. The instance and its data stay for the session; the
    trainer on it goes when the file's last test has run
    (``_release_trainers`` below). Three rules for a test that takes one:

    - the instance holds ONE trainer, keyed ``(member_chunk, mesh,
      momentum dtype)``: a test that asks for another chunk, a mesh or
      another momentum dtype passes a ``label`` naming the variant and
      gets an instance of its own, or it would evict the others' trainer
      (and they its);
    - it sets no attribute on the instance, and patches nothing on its
      trainer that a traced program reads. What a test would set
      (``batch_size = 16``) goes into ``attrs={...}``: part of the key,
      set once on a new instance. A test that must patch the trainer
      makes its own ``get_workload(...)``;
    - it runs in the process that collected it: ``cli.main`` and child
      processes make their own workloads, and that is their subject.
    """
    from mpi_opt_tpu.workloads import get_workload

    def workload(name, label=None, attrs=None, **kwargs):
        # repr: an attribute may be a dict (the decoder's ``dims``)
        key = (name, label, repr(sorted((attrs or {}).items())), repr(sorted(kwargs.items())))
        if key not in _WORKLOADS:
            wl = get_workload(name, **kwargs)
            for attr, value in (attrs or {}).items():
                setattr(wl, attr, value)
            _WORKLOADS[key] = wl
        return _WORKLOADS[key]

    return workload


@pytest.fixture(scope="module", autouse=True)
def _release_trainers():
    """A file's trainers and their compiled programs go with the file.

    Every XLA:CPU executable holds 10-30 memory mappings, and a process
    may hold ``vm.max_map_count`` (65530) of them: with every trainer
    kept to the end of the session the suite died inside a compile at
    its 720th test (PR 21's fault, CHANGES.md PR 30). Programs repeat
    within a file, where the arguments do (a module's ``KW``), and hardly
    across files, so little is built twice for this.
    """
    yield
    for wl in _WORKLOADS.values():
        for cache in ("_fused_cache", "_eval_cache"):
            for held in getattr(wl, cache, ()):
                # a trainer's programs close a cycle through it (``partial(fn,
                # trainer)``): emptied here they go now, by count, and no
                # collection of the whole heap (a second a file) is needed
                if hasattr(held, "_programs"):
                    held._programs.clear()
            if hasattr(wl, cache):
                delattr(wl, cache)
