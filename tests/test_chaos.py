"""Fault-injection drills through the REAL CPU backend (workloads/chaos.py).

The headline is the determinism drill: a seeded random-search sweep
with ~20-30% injected trial failures (exceptions + NaN scores) must
complete, report the injected failures in the summary counters, and
return the SAME best trial as the clean run — failures cost coverage,
never correctness. The constants (algorithm seed 0, chaos seed 19,
30 trials, capacity 2) were chosen so the injection hits 9 trials
(5 exceptions + 4 NaNs) and the clean winner is not among them; chaos
faults are a pure function of (chaos_seed, params), so these counts are
stable across machines and runs — for ONE jax.random stream. The
sampled params are jax's to define: the partitionable threefry that
became the default after jax 0.4 draws a different 30-trial stream
(chaos seed 10 gave these same counts on the old one, and 3 + 2 here).
"""

import math

import pytest

from mpi_opt_tpu.algorithms import RandomSearch
from mpi_opt_tpu.backends.cpu import CPUBackend
from mpi_opt_tpu.driver import FailurePolicy, run_search
from mpi_opt_tpu.trial import TrialStatus
from mpi_opt_tpu.utils.metrics import MetricsLogger
from mpi_opt_tpu.workloads import get_workload
from mpi_opt_tpu.workloads.chaos import ChaosInjectedError, parse_chaos_spec

pytestmark = pytest.mark.chaos

# the determinism drill's injection mix: ~20% of trials faulted
CHAOS = {"inner": "quadratic", "exc": 0.12, "nan": 0.08, "seed": 19}
N_INJECTED = 9  # 5 exc + 4 nan over the 30-trial seed-0 stream


def _sweep(workload, workload_kwargs=None, **policy_kw):
    algo = RandomSearch(
        workload.default_space(), seed=0, max_trials=30, budget=20
    )
    b = CPUBackend(workload, n_workers=2, workload_kwargs=workload_kwargs)
    m = MetricsLogger()
    try:
        res = run_search(algo, b, metrics=m, **policy_kw)
    finally:
        b.close()
    return algo, res, m


# -- spec parsing ----------------------------------------------------------


def test_parse_chaos_spec():
    assert parse_chaos_spec("exc=0.1,nan=0.05,seed=7") == {
        "exc": 0.1, "nan": 0.05, "seed": 7,
    }
    assert parse_chaos_spec("hang=1.0,hang_s=30") == {"hang": 1.0, "hang_s": 30.0}
    assert parse_chaos_spec("preempt=0.2,seed=3") == {"preempt": 0.2, "seed": 3}
    with pytest.raises(ValueError, match="unknown chaos key"):
        parse_chaos_spec("explode=0.5")
    with pytest.raises(ValueError, match="key=value"):
        parse_chaos_spec("exc")
    with pytest.raises(ValueError, match="outside"):
        parse_chaos_spec("exc=1.5")
    with pytest.raises(ValueError, match="outside"):
        parse_chaos_spec("preempt=-0.1")


def test_chaos_probabilities_must_sum_to_one_or_less():
    with pytest.raises(ValueError, match="sum"):
        get_workload("chaos", inner="quadratic", exc=0.7, nan=0.6)


def test_fault_draw_is_deterministic():
    wl = get_workload("chaos", **CHAOS)
    wl2 = get_workload("chaos", **CHAOS)
    params = {"lr": 0.5, "reg": 0.3}
    assert wl.fault_for(params) == wl2.fault_for(params)
    # internal keys never change the draw (pool workers see cleaned
    # params, the in-parent stateful path sees raw ones)
    assert wl.fault_for({**params, "__slot__": 3}) == wl.fault_for(params)
    # a different chaos seed redraws
    wl3 = get_workload("chaos", **{**CHAOS, "seed": 11})
    draws = [
        (wl.fault_for({"lr": float(i), "reg": 0.1}), wl3.fault_for({"lr": float(i), "reg": 0.1}))
        for i in range(50)
    ]
    assert any(a != b for a, b in draws)


def test_injected_exception_is_distinct():
    wl = get_workload("chaos", inner="quadratic", exc=1.0)
    with pytest.raises(ChaosInjectedError):
        wl.evaluate({"lr": 0.5, "reg": 0.3}, 10, 0)


# -- the determinism drill (acceptance criterion) --------------------------


def test_chaos_sweep_matches_clean_best_and_counts_failures():
    clean_algo, clean_res, _ = _sweep(get_workload("quadratic"))
    chaos_algo, chaos_res, m = _sweep(
        get_workload("chaos", **CHAOS), workload_kwargs=CHAOS
    )

    # the sweep completed despite the injection, and counted it
    assert chaos_algo.finished()
    assert m.trials_failed == N_INJECTED
    assert chaos_res.n_failed == N_INJECTED
    n_failed_trials = sum(
        t.status == TrialStatus.FAILED for t in chaos_algo.trials.values()
    )
    assert n_failed_trials == N_INJECTED

    # the counters reach the summary record operators actually read
    s = m.summary()
    assert s["trials_failed"] == N_INJECTED
    assert s["trials_retried"] == 0 and s["trials_timeout"] == 0

    # same best trial as the clean run: failures cost coverage, never
    # correctness of the surviving results
    cb, xb = clean_res.best, chaos_res.best
    assert xb is not None
    assert xb.params == cb.params
    assert xb.score == pytest.approx(cb.score, abs=1e-12)


def test_chaos_retries_are_deterministic_too():
    """Chaos faults model poison hyperparameters: a faulted trial fails
    on every retry, so retries are burned (and counted) but the final
    outcome matches the no-retry drill."""
    algo, res, m = _sweep(
        get_workload("chaos", **CHAOS),
        workload_kwargs=CHAOS,
        policy=FailurePolicy(max_retries=1, backoff_s=0.0),
    )
    assert m.trials_failed == N_INJECTED
    assert m.trials_retried == N_INJECTED  # each failure retried once
    assert res.best is not None


# -- hang/crash reaping through the pool path ------------------------------


def test_injected_hang_is_reaped_as_timeout():
    """An injected hang must come back as a 'timeout' result instead of
    blocking evaluate() forever — the acceptance criterion for
    --trial-timeout. digits (stateless) routes through the process
    pool, where the deadline is enforceable."""
    kw = {"inner": "digits", "hang": 1.0, "hang_s": 120.0}
    wl = get_workload("chaos", **kw)
    b = CPUBackend(wl, n_workers=1, trial_timeout=1.5, workload_kwargs=kw)
    algo = RandomSearch(wl.default_space(), seed=0, max_trials=1, budget=20)
    try:
        results = b.evaluate(algo.next_batch(1))
    finally:
        b.close()
    (r,) = results
    assert r.status == "timeout"
    assert math.isnan(r.score)
    assert "within 1.5s" in r.error
    # the hung worker's pool was recycled so the next batch starts clean
    assert b._pool is None


def test_injected_crash_is_reaped_and_pool_rebuilt():
    """A worker dying HARD (os._exit) queues no result at all: the
    per-trial deadline reaps it and the backend recycles the pool."""
    kw = {"inner": "digits", "crash": 1.0}
    wl = get_workload("chaos", **kw)
    b = CPUBackend(wl, n_workers=1, trial_timeout=2.0, workload_kwargs=kw)
    algo = RandomSearch(wl.default_space(), seed=0, max_trials=1, budget=20)
    try:
        results = b.evaluate(algo.next_batch(1))
    finally:
        b.close()
    (r,) = results
    assert r.status in ("timeout", "failed")
    assert not r.ok
    assert b._pool is None  # recycled after the reap


def test_timeout_spares_innocent_trials_in_the_batch():
    """One hung trial must not eat the whole batch's deadline budget:
    trials queued behind it still get their own window and report real
    scores."""
    # chaos seed 16 puts the ONE hang at batch position 0 (scanned, on
    # this jax's sample stream — see the module docstring):
    # the worst position — every innocent trial queues behind it. With
    # 2+ hangs on 2 workers the whole pool wedges and reaping all of
    # them as timeouts is the correct outcome, which is why this test
    # pins a single-hang draw.
    kw = {"inner": "digits", "hang": 0.3, "hang_s": 120.0, "seed": 16}
    wl = get_workload("chaos", **kw)
    algo = RandomSearch(wl.default_space(), seed=0, max_trials=6, budget=20)
    batch = algo.next_batch(6)
    faults = [wl.fault_for(t.params) for t in batch]
    assert faults.count("hang") == 1 and faults[0] == "hang"
    b = CPUBackend(wl, n_workers=2, workload_kwargs=kw)
    try:
        # warm the pool on clean trials with NO deadline: worker
        # cold-start (spawn + jax/sklearn imports) is seconds of wall
        # this test must not conflate with trial runtime
        warm = [t for t, f in zip(batch, faults) if f is None][:2]
        assert all(r.ok for r in b.evaluate(warm))
        b.trial_timeout = 4.0
        results = b.evaluate(batch)
    finally:
        b.close()
    by_status = {t.trial_id: r for t, r in zip(batch, results)}
    for t, f in zip(batch, faults):
        r = by_status[t.trial_id]
        if f == "hang":
            assert r.status == "timeout"
        else:
            assert r.ok and 0.0 <= r.score <= 1.0


# -- the preemption + stateful-hang drills (health/ + --isolate-stateful) --


def test_preempt_fault_is_graceful_on_in_parent_paths():
    """chaos ``preempt`` SIGTERMs the evaluating process itself. Where
    evaluation runs in the DRIVER process (the stateful in-parent path
    here), an installed ShutdownGuard absorbs it: the trial COMPLETES
    with its real score and only the drain flag is raised — the
    graceful-shutdown protocol, not a crash."""
    from mpi_opt_tpu.health import ShutdownGuard
    from mpi_opt_tpu.health import shutdown as shutdown_mod

    wl = get_workload("chaos", inner="quadratic", preempt=1.0)
    algo = RandomSearch(wl.default_space(), seed=0, max_trials=1, budget=10)
    b = CPUBackend(wl, n_workers=1)
    try:
        with ShutdownGuard() as g:
            (r,) = b.evaluate(algo.next_batch(1))
            assert r.ok and math.isfinite(r.score)  # the trial finished
            assert g.requested and g.signal_name == "SIGTERM"
        assert not shutdown_mod.requested()  # scoped: nothing leaks
    finally:
        b.close()


def test_preempt_draw_deterministic_and_appended_last():
    """preempt joins the cascade LAST: with preempt=0 every existing
    (seed, params) draw is unchanged (the pinned counts in the
    determinism drills depend on this), and with it on, the draw is a
    pure function of (chaos_seed, params) like every other fault."""
    base = get_workload("chaos", **CHAOS)
    plus = get_workload("chaos", **{**CHAOS, "preempt": 0.0})
    params = [{"lr": 0.1 * i + 0.01, "reg": 0.4} for i in range(40)]
    assert [base.fault_for(p) for p in params] == [plus.fault_for(p) for p in params]
    pre = get_workload("chaos", inner="quadratic", preempt=0.3, seed=5)
    draws = [pre.fault_for(p) for p in params]
    assert "preempt" in draws
    assert [pre.fault_for(p) for p in params] == draws  # stable


def test_timeout_reap_counts_as_stall_detected():
    """Every reaped trial deadline feeds the summary's stalls_detected
    counter (the trial-level stall producer; supervisor-level rank
    stalls are counted in launch.py's own events)."""
    from mpi_opt_tpu.driver import run_search

    kw = {"inner": "digits", "hang": 1.0, "hang_s": 120.0}
    wl = get_workload("chaos", **kw)
    algo = RandomSearch(wl.default_space(), seed=0, max_trials=1, budget=20)
    b = CPUBackend(wl, n_workers=1, trial_timeout=1.5, workload_kwargs=kw)
    m = MetricsLogger()
    try:
        run_search(algo, b, metrics=m)
    finally:
        b.close()
    s = m.summary()
    assert s["trials_timeout"] == 1
    assert s["stalls_detected"] == 1


def test_injected_hang_on_stateful_path_times_out_under_isolation():
    """The acceptance criterion that closes the ROADMAP open item: a
    chaos ``hang`` on a STATEFUL workload — in-parent, this blocks
    forever by construction — terminates as status=timeout within ~2x
    --trial-timeout under --isolate-stateful, because the state store
    now lives in a killable worker process."""
    import time

    kw = {"inner": "quadratic", "hang": 1.0, "hang_s": 120.0}
    wl = get_workload("chaos", **kw)
    assert wl.stateful  # quadratic is stateful: the in-parent path
    b = CPUBackend(
        wl, n_workers=1, trial_timeout=1.5, isolate_stateful=True,
        workload_kwargs=kw,
    )
    algo = RandomSearch(wl.default_space(), seed=0, max_trials=1, budget=10)
    try:
        (r,) = b.evaluate(algo.next_batch(1))
    finally:
        b.close()
    assert r.status == "timeout"
    assert math.isnan(r.score)
    assert "hung" in r.error
    # wall_time excludes worker bring-up (the ready handshake): the
    # reap itself lands within ~2x the deadline
    assert r.wall_time < 2 * 1.5


# -- snapshot-corruption injectors (torn_save / corrupt_save) ---------------


def _snapshot_dir(tmp_path):
    """A real 2-step orbax snapshot tree to corrupt."""
    import numpy as np

    from mpi_opt_tpu.utils.checkpoint import SweepCheckpointer

    d = str(tmp_path / "ck")
    ck = SweepCheckpointer(d, {"seed": 0, "momentum_dtype": "float32"})
    for s in (1, 2):
        ck.save(
            s,
            sweep={"state": {"p": np.arange(64, dtype=np.float32) * s}},
            meta_extra={"gen": s},
        )
    ck.close()
    return d


def test_corrupt_save_is_deterministic_and_flips_one_bit(tmp_path):
    """Same (directory contents, seed) -> same file, same bit: drills
    that pin exact outcomes stay reproducible across machines."""
    import os

    from mpi_opt_tpu.workloads import chaos

    d = _snapshot_dir(tmp_path)
    target = chaos._corruption_target(os.path.join(d, "2"))
    before = open(target, "rb").read()
    path = chaos.inject_corrupt_save(d, seed=3)
    assert path == target  # strikes the latest step's largest file
    after = open(path, "rb").read()
    assert len(after) == len(before)
    diff = [i for i, (a, b) in enumerate(zip(before, after)) if a != b]
    assert len(diff) == 1  # exactly one byte
    assert bin(before[diff[0]] ^ after[diff[0]]).count("1") == 1  # one bit
    # flipping again with the same seed restores the original byte —
    # the draw is a pure function of (contents, seed)
    chaos.inject_corrupt_save(d, seed=3)
    assert open(path, "rb").read() == before


def test_torn_save_truncates_inside_the_step(tmp_path):
    import os

    from mpi_opt_tpu.workloads import chaos

    d = _snapshot_dir(tmp_path)
    size_before = os.path.getsize(chaos._corruption_target(os.path.join(d, "2")))
    path = chaos.inject_torn_save(d, seed=0)
    assert f"{os.sep}2{os.sep}" in path  # the LATEST step, not an older one
    assert 0 < os.path.getsize(path) < size_before


def test_injectors_target_explicit_step_and_refuse_empty_dirs(tmp_path):
    import os

    import pytest

    from mpi_opt_tpu.workloads import chaos

    d = _snapshot_dir(tmp_path)
    path = chaos.inject_corrupt_save(d, step=1)
    assert f"{os.sep}1{os.sep}" in path
    with pytest.raises(ValueError, match="step 9 not found"):
        chaos.inject_corrupt_save(d, step=9)
    empty = str(tmp_path / "empty")
    os.makedirs(empty)
    with pytest.raises(ValueError, match="no committed snapshot steps"):
        chaos.inject_torn_save(empty)


# -- rank-death injector (multi-process SPMD wedge drills, ISSUE 20) --------


def test_rank_kill_counts_boundaries_and_spares_other_ranks(monkeypatch):
    """The injector counts every boundary tick on every rank, but only
    the CHOSEN rank dies — peers tick the same ordinals and keep going,
    which is what makes the wedge drill deterministic world-wide. Here
    the process plays rank 0 while the schedule targets rank 1: the
    scheduled ordinal must be a no-op."""
    from mpi_opt_tpu.train.common import launch_boundary
    from mpi_opt_tpu.workloads.chaos import inject_rank_kill

    kills = []
    monkeypatch.setattr(
        "mpi_opt_tpu.workloads.chaos.os.kill",
        lambda pid, sig: kills.append((pid, sig)),
    )
    inj, uninstall = inject_rank_kill(rank=1, at_boundary=2)
    try:
        for i in range(3):
            launch_boundary(f"gen {i + 1}/3", final=i == 2)
    finally:
        uninstall()
    assert inj.boundaries == 3
    assert inj.faults_fired == 0 and kills == []
    # uninstalled: the seam is inert again
    launch_boundary("gen 1/1", final=True)
    assert inj.boundaries == 3


def test_rank_kill_fires_on_own_rank_once_marker_suppresses(
    tmp_path, monkeypatch
):
    """On the chosen rank the scheduled ordinal kills with SIGKILL —
    after creating the once-marker, so a coordinated --resume rerun of
    the same boundaries with the same spec does NOT re-fire (the drill
    must cost the supervisor exactly one restart)."""
    import os
    import signal as _signal

    from mpi_opt_tpu.workloads.chaos import RankKillInjector

    kills = []
    monkeypatch.setattr(
        "mpi_opt_tpu.workloads.chaos.os.kill",
        lambda pid, sig: kills.append((pid, sig)),
    )
    marker = str(tmp_path / "fired.once")
    inj = RankKillInjector(rank=0, at_boundary=2, once_marker=marker)
    inj("b1")
    assert kills == []
    inj("b2")
    assert kills == [(os.getpid(), _signal.SIGKILL)]
    assert inj.faults_fired == 1 and os.path.exists(marker)
    # the restarted attempt replays the same ordinals: marker holds
    again = RankKillInjector(rank=0, at_boundary=2, once_marker=marker)
    again("b1")
    again("b2")
    assert kills == [(os.getpid(), _signal.SIGKILL)]  # no second kill
    assert again.faults_fired == 0


def test_rank_kill_spec_parses_and_rejects_unknown_keys(tmp_path):
    from mpi_opt_tpu.workloads.chaos import parse_rank_kill_spec

    assert parse_rank_kill_spec("rank=1,at=3") == {
        "rank": 1,
        "at_boundary": 3,
    }
    assert parse_rank_kill_spec("rank=0,at=2,n=2,marker=/tmp/m") == {
        "rank": 0,
        "at_boundary": 2,
        "n": 2,
        "once_marker": "/tmp/m",
    }
    with pytest.raises(ValueError, match="unknown rank-kill key"):
        parse_rank_kill_spec("rank=1,boom=3")
    with pytest.raises(ValueError, match="not key=value"):
        parse_rank_kill_spec("rank")
    from mpi_opt_tpu.workloads.chaos import RankKillInjector

    with pytest.raises(ValueError, match="1-based"):
        RankKillInjector(at_boundary=0)
