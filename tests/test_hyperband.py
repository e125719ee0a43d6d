"""Hyperband: bracket plan math, driver loop, checkpoint, fused path."""

import numpy as np
import pytest

from mpi_opt_tpu.algorithms import Hyperband, get_algorithm
from mpi_opt_tpu.algorithms.hyperband import bracket_plan
from mpi_opt_tpu.backends.cpu import CPUBackend
from mpi_opt_tpu.driver import run_search
from mpi_opt_tpu.workloads import get_workload


def test_bracket_plan_matches_paper_table():
    # Li et al. 2018, Table 1: R=81, eta=3
    assert bracket_plan(81, 3) == [(81, 1), (34, 3), (15, 9), (8, 27), (5, 81)]
    # degenerate: R < eta -> single bracket of full-budget trials
    assert bracket_plan(2, 3) == [(1, 2)]
    # exact eta powers must NOT lose a bracket to float log error:
    # log3(243) computes as 4.999... -> naive floor drops the 243@1 bracket
    plan = bracket_plan(243, 3)
    assert len(plan) == 6
    assert plan[0] == (243, 1)
    assert plan[-1] == (6, 243)


def test_hyperband_driver_loop_completes():
    wl = get_workload("quadratic")
    algo = Hyperband(wl.default_space(), seed=0, max_budget=27, eta=3)
    be = CPUBackend(wl, n_workers=1)
    try:
        res = run_search(algo, be)
    finally:
        be.close()
    assert algo.finished()
    # R=27: brackets (27@1, 12@3, 6@9, 4@27) -> 49 configurations total
    assert res.n_trials == 27 + 12 + 6 + 4
    assert res.best is not None and res.best.score is not None
    # the all-exploit bracket trains every survivor to max budget
    tops = [t for b in algo.brackets for t in b.trials.values() if t.budget == 27]
    assert tops, "no trial ever reached max budget"


def test_hyperband_checkpoint_roundtrip():
    wl = get_workload("quadratic")
    space = wl.default_space()
    algo = Hyperband(space, seed=3, max_budget=27, eta=3)
    be = CPUBackend(wl, n_workers=1)
    try:
        # run partway: a few driver batches into the first bracket
        run_search(algo, be, max_batches=3)
        mid_state = algo.state_dict()

        resumed = Hyperband(space, seed=3, max_budget=27, eta=3)
        resumed.load_state_dict(mid_state)
        r1 = run_search(algo, be)
        r2 = run_search(resumed, be)
    finally:
        be.close()
    # NOTE: exact score equality is NOT guaranteed — the async promotion
    # rule depends on result arrival order, and resume re-dispatches
    # recovered in-flight trials first. The invariants are structural:
    # both searches complete, visit the same configuration count (the
    # bracket plan fixes suggestion counts), and produce a scored best.
    assert algo.finished() and resumed.finished()
    assert algo.n_trials == resumed.n_trials
    assert r1.best is not None and r2.best is not None
    from mpi_opt_tpu.trial import TrialStatus

    for hb in (algo, resumed):
        for b in hb.brackets:
            assert all(
                t.status in (TrialStatus.DONE, TrialStatus.STOPPED)
                for t in b.trials.values()
            )


def test_hyperband_checkpoint_rejects_mismatched_config():
    wl = get_workload("quadratic")
    space = wl.default_space()
    a = Hyperband(space, seed=0, max_budget=27, eta=3)
    b = Hyperband(space, seed=0, max_budget=81, eta=3)
    with pytest.raises(ValueError, match="hyperband"):
        b.load_state_dict(a.state_dict())


def test_fused_hyperband(shared_workload):
    from mpi_opt_tpu.train.fused_asha import fused_hyperband

    wl = shared_workload("fashion_mlp", n_train=256, n_val=128)
    res = fused_hyperband(wl, max_budget=12, eta=3, seed=0)
    # R=12: brackets (6@1(rounded), ...) — just check structural contract
    assert res["n_trials"] == sum(b["n_trials"] for b in res["brackets"])
    assert 0.0 <= res["best_score"] <= 1.0
    assert res["best_params"]
    assert res["brackets"][0]["start_budget"] < res["brackets"][-1]["start_budget"]
    # overall best is the max over brackets
    assert res["best_score"] == max(b["best_score"] for b in res["brackets"])


def test_fused_hyperband_checkpoint_resume(shared_workload, tmp_path, monkeypatch):
    """Bracket-granular recovery: each bracket checkpoints its rungs in
    its own subdirectory; completed brackets replay without re-running."""
    import mpi_opt_tpu.train.fused_asha as fa
    from mpi_opt_tpu.train.fused_asha import fused_hyperband

    wl = shared_workload("fashion_mlp", n_train=256, n_val=128)
    kw = dict(max_budget=6, eta=3, seed=2)
    whole = fused_hyperband(wl, **kw)

    real = fa.fused_sha
    calls = {"n": 0}

    def crashing(*a, **k):
        calls["n"] += 1
        if calls["n"] == 2:  # die inside the second bracket
            raise RuntimeError("simulated crash")
        return real(*a, **k)

    ckpt = str(tmp_path / "hb")
    monkeypatch.setattr(fa, "fused_sha", crashing)
    with pytest.raises(RuntimeError, match="simulated"):
        fused_hyperband(wl, checkpoint_dir=ckpt, **kw)
    monkeypatch.setattr(fa, "fused_sha", real)

    resumed = fused_hyperband(wl, checkpoint_dir=ckpt, **kw)
    assert resumed["best_score"] == whole["best_score"]
    assert resumed["n_trials"] == whole["n_trials"]
    assert [b["best_score"] for b in resumed["brackets"]] == [
        b["best_score"] for b in whole["brackets"]
    ]


def test_hyperband_best_ignores_nan_bracket():
    """A bracket whose trials all diverged reports a NaN-scored best;
    the cross-bracket aggregation must pick the finite bracket even when
    the NaN one comes first (VERDICT r3 — host-path parity with the
    fused bracket loop's NaN-safe pick)."""
    import numpy as np

    from mpi_opt_tpu.workloads import get_workload

    space = get_workload("quadratic").default_space()
    hb = Hyperband(space, seed=0, max_budget=3, eta=3)  # 2 brackets
    t_nan = hb.brackets[0]._new_trial(np.zeros(space.dim, np.float32))
    t_nan.score = float("nan")
    t_ok = hb.brackets[1]._new_trial(np.zeros(space.dim, np.float32))
    t_ok.score = 0.5
    best = hb.best()
    assert best.trial_id == t_ok.trial_id
    assert best.score == 0.5
