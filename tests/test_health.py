"""health/: graceful-shutdown protocol, heartbeats, stall detection.

The headline is the driver drain drill: a chaos-injected SIGTERM
(``preempt`` fault) mid-sweep lets the in-flight batch FINISH, forces an
off-cadence checkpoint save, and surfaces as ``SweepInterrupted`` — the
exception the CLI maps to exit 75 (EX_TEMPFAIL), which launch.py
classifies as a free (non-retry-consuming) coordinated restart.
"""

import os
import shutil
import signal
import warnings

import pytest

from mpi_opt_tpu.health import (
    EX_TEMPFAIL,
    Heartbeat,
    ShutdownGuard,
    StallDetector,
    SweepInterrupted,
    read_beat,
)
from mpi_opt_tpu.health import shutdown as shutdown_mod


# -- heartbeat -------------------------------------------------------------


def test_heartbeat_monotonic_and_atomic(tmp_path):
    path = str(tmp_path / "r0.hb")
    h = Heartbeat(path)
    r1 = h.beat(stage="driver", batches=1)
    r2 = h.beat(stage="driver", batches=2)
    assert (r1["beats"], r2["beats"]) == (1, 2)
    rec = read_beat(path)
    assert rec["beats"] == 2 and rec["pid"] == os.getpid()
    assert rec["progress"] == {"stage": "driver", "batches": 2}
    # write-tmp-then-rename leaves no litter a reader could mistake
    assert os.listdir(tmp_path) == ["r0.hb"]


def test_read_beat_missing_or_torn_returns_none(tmp_path):
    assert read_beat(str(tmp_path / "nope.hb")) is None
    torn = tmp_path / "torn.hb"
    torn.write_text('{"beats": ')
    assert read_beat(str(torn)) is None
    notdict = tmp_path / "list.hb"
    notdict.write_text("[1, 2]")
    assert read_beat(str(notdict)) is None


def test_heartbeat_write_failure_warns_once_never_raises(tmp_path):
    h = Heartbeat(str(tmp_path / "d" / "r.hb"))
    shutil.rmtree(tmp_path / "d")  # the directory vanishes under the rank
    with pytest.warns(UserWarning, match="heartbeat write"):
        assert h.beat() is None
    with warnings.catch_warnings():  # quiet (and still harmless) after
        warnings.simplefilter("error")
        assert h.beat() is None


# -- stall detection -------------------------------------------------------


def _write_beat(path, beats):
    import json

    with open(path, "w") as f:
        f.write(json.dumps({"pid": 1, "beats": beats, "ts": 0.0, "progress": {}}))


def test_stall_detector_watches_only_after_first_beat(tmp_path):
    p = str(tmp_path / "r0.hb")
    d = StallDetector([p], stall_timeout=10.0)
    # no heartbeat file yet = rank still compiling: NOT watched, no
    # matter how long (the engagement rule that keeps conservative
    # timeouts from killing legitimate cold starts)
    assert d.poll(now=0.0) == []
    assert d.poll(now=10_000.0) == []
    _write_beat(p, 1)
    assert d.poll(now=10_000.0) == []  # first beat: the clock starts here
    assert d.poll(now=10_009.0) == []  # within timeout
    assert d.poll(now=10_011.0) == [0]  # frozen past it: stalled
    _write_beat(p, 2)
    assert d.poll(now=10_012.0) == []  # advanced: watch resets
    assert d.poll(now=10_023.0) == [0]


def test_stall_detector_validates_timeout():
    with pytest.raises(ValueError, match="stall_timeout"):
        StallDetector([], 0.0)


# -- shutdown guard --------------------------------------------------------


def test_shutdown_guard_sets_flag_and_restores_handlers():
    prev_term = signal.getsignal(signal.SIGTERM)
    prev_int = signal.getsignal(signal.SIGINT)
    assert not shutdown_mod.requested()  # no active guard
    with ShutdownGuard() as g:
        assert not g.requested
        os.kill(os.getpid(), signal.SIGTERM)
        assert g.requested and g.signal_name == "SIGTERM"
        assert shutdown_mod.requested()
        assert shutdown_mod.active_signal() == "SIGTERM"
        # repeated SIGTERM stays graceful: a supervisor forwarding the
        # platform's signal must not turn the drain into an abort
        os.kill(os.getpid(), signal.SIGTERM)
        assert g.requested
    assert not shutdown_mod.requested()
    assert signal.getsignal(signal.SIGTERM) is prev_term
    assert signal.getsignal(signal.SIGINT) is prev_int


def test_second_sigint_escalates_to_keyboard_interrupt():
    with ShutdownGuard() as g:
        g._handle(signal.SIGINT, None)
        assert g.requested and g.signal_name == "SIGINT"
        with pytest.raises(KeyboardInterrupt):
            g._handle(signal.SIGINT, None)


def test_ex_tempfail_is_sysexits_value():
    assert EX_TEMPFAIL == 75  # launch.py's preemption classification key


# -- the driver drain drill ------------------------------------------------


@pytest.mark.chaos
def test_driver_drains_at_batch_boundary_and_forces_checkpoint():
    """chaos ``preempt`` delivers SIGTERM mid-evaluation: the guard
    absorbs it, the batch completes (its trial reports normally), and
    run_search drains — forcing an off-cadence checkpoint save so
    --resume loses nothing. Chaos seed 13 puts the one preempt draw at
    trial index 6 of this 12-trial seed-0 stream."""
    from mpi_opt_tpu.algorithms import RandomSearch
    from mpi_opt_tpu.backends.cpu import CPUBackend
    from mpi_opt_tpu.driver import run_search
    from mpi_opt_tpu.workloads import get_workload

    kw = {"inner": "quadratic", "preempt": 0.15, "seed": 13}
    wl = get_workload("chaos", **kw)
    algo = RandomSearch(wl.default_space(), seed=0, max_trials=12, budget=10)

    class SpyCheckpointer:
        def __init__(self):
            self.forced = []

        def maybe_save(self, step, algorithm, backend):
            return False  # never on cadence: any save below is the forced one

        def save(self, step, algorithm, backend):
            self.forced.append(step)

    ck = SpyCheckpointer()
    b = CPUBackend(wl, n_workers=1, workload_kwargs=kw)
    try:
        with ShutdownGuard():
            with pytest.raises(SweepInterrupted) as ei:
                run_search(algo, b, checkpointer=ck)
    finally:
        b.close()
    # trial 6 (0-based) preempted -> its batch still COMPLETED: 7 trials
    assert algo.n_trials == 7
    assert ck.forced == [7]  # the off-cadence flush
    assert ei.value.signal == "SIGTERM"
    assert "batch 7" in ei.value.at


@pytest.mark.chaos
def test_driver_completes_when_preempted_on_the_final_batch():
    """A SIGTERM landing during the batch that FINISHES the sweep must
    not turn success into exit 75: finishing strictly dominates
    preempting a finished sweep (same rule as the fused paths'
    final=True boundary)."""
    from mpi_opt_tpu.algorithms import RandomSearch
    from mpi_opt_tpu.backends.cpu import CPUBackend
    from mpi_opt_tpu.driver import run_search
    from mpi_opt_tpu.workloads import get_workload

    kw = {"inner": "quadratic", "preempt": 1.0}
    wl = get_workload("chaos", **kw)
    algo = RandomSearch(wl.default_space(), seed=0, max_trials=1, budget=10)
    b = CPUBackend(wl, n_workers=1, workload_kwargs=kw)
    try:
        with ShutdownGuard() as g:
            res = run_search(algo, b)  # must return, not raise
            assert g.requested  # the signal really was delivered
    finally:
        b.close()
    assert res.n_trials == 1 and res.best is not None


def test_fused_step_chunk_sub_launches_beat(shared_workload, tmp_path):
    """Sub-launch heartbeat granularity (ROADMAP follow-up): a
    step-chunked fused PBT generation beats once per train sub-segment,
    so --stall-timeout can be sized to one step_chunk instead of a
    whole generation's train_segment scan."""
    from mpi_opt_tpu.health import heartbeat
    from mpi_opt_tpu.train.fused_pbt import fused_pbt

    wl = shared_workload("fashion_mlp", n_train=256, n_val=128)
    hb_path = str(tmp_path / "rank.hb")
    hb = heartbeat.configure(hb_path)
    try:
        fused_pbt(
            wl,
            population=4,
            generations=2,
            steps_per_gen=4,
            seed=0,
            step_chunk=2,  # 2 sub-launches per generation
        )
    finally:
        heartbeat.deconfigure()
    # per generation: 2 sub-launch beats, the shared engine's
    # wave-dispatched beat (resident mode is the one-wave case of
    # train/engine.py's interval loop), and the exploit boundary_span
    # beat — so --stall-timeout can still be sized to one step_chunk
    assert hb.beats == 2 * (2 + 1 + 1)
    rec = heartbeat.read_beat(hb_path)
    assert rec is not None and rec["beats"] == hb.beats
