"""Fused successive halving: cohort math, end-to-end sweep, sharded run."""

import numpy as np
import pytest

from mpi_opt_tpu.train.fused_asha import fused_sha, sha_cohort_sizes
from mpi_opt_tpu.workloads import get_workload


def test_sha_cohort_sizes_exact():
    assert sha_cohort_sizes(64, 4, eta=3) == [64, 22, 8, 3]
    assert sha_cohort_sizes(9, 3, eta=3) == [9, 3, 1]
    assert sha_cohort_sizes(2, 3, eta=3) == [2, 1, 1]


def test_sha_cohort_sizes_mesh_rounding():
    # survivor counts round UP to the mesh 'pop' axis size
    assert sha_cohort_sizes(64, 4, eta=3, round_to=4) == [64, 24, 8, 4]
    assert sha_cohort_sizes(8, 3, eta=3, round_to=4) == [8, 4, 4]


@pytest.fixture(scope="module")
def workload(shared_workload):
    wl = shared_workload("fashion_mlp", n_train=512, n_val=256, attrs={"batch_size": 32})
    return wl


def test_fused_sha_end_to_end(workload):
    r = fused_sha(workload, n_trials=9, min_budget=2, max_budget=8, eta=2, seed=0)
    assert r["rung_budgets"] == [2, 4, 8]
    assert r["rung_sizes"] == [9, 5, 3]
    assert 0.0 <= r["best_score"] <= 1.0
    assert set(r["best_params"]) == set(workload.default_space().names)
    # ledger: every trial got a score; exactly the final cohort reached
    # the last rung; the best trial is one of them
    assert np.isfinite(r["last_score"]).all()
    reached_last = (r["stop_rung"] == 2).sum()
    assert reached_last == 3
    assert r["stop_rung"][r["best_trial"]] == 2
    assert np.isclose(r["last_score"][r["best_trial"]], r["best_score"])


def test_fused_sha_survivors_beat_stopped(workload):
    """The cut keeps the rung's top scorers: every survivor's rung-0
    score must be >= every stopped trial's rung-0 score."""
    r = fused_sha(workload, n_trials=8, min_budget=3, max_budget=6, eta=2, seed=1)
    stopped = r["last_score"][r["stop_rung"] == 0]
    survived_rung0 = r["stop_rung"] >= 1
    assert survived_rung0.sum() == 4
    # survivors' recorded scores are from rung>=1, so compare via the
    # promote rule indirectly: the worst survivor trained further; what
    # we can assert exactly is the cut count
    assert stopped.shape[0] == 4


def test_fused_sha_sharded_matches_structure(shared_workload):
    from mpi_opt_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(n_pop=4, n_data=2)
    workload = shared_workload(
        "fashion_mlp", label="pop4 data2 mesh", n_train=512, n_val=256, attrs={"batch_size": 32}
    )
    r = fused_sha(
        workload, n_trials=8, min_budget=2, max_budget=4, eta=2, seed=2, mesh=mesh
    )
    assert r["rung_sizes"] == [8, 4]
    assert 0.0 <= r["best_score"] <= 1.0


def test_fused_sha_all_nan_cohort_reports_diverged(monkeypatch):
    """An all-diverged cohort must not dress an arbitrary row up as a
    winner: best_params/best_trial are None and diverged=True, with the
    NaN best_score left visible as the flag upstream best-picks key on
    (ADVICE r3)."""
    import jax.numpy as jnp

    from mpi_opt_tpu.train.common import workload_arrays

    # its own instance, not the session's: a program traced around the
    # patched eval_population stays on the trainer
    wl = get_workload("fashion_mlp", n_train=256, n_val=128)
    trainer, *_ = workload_arrays(wl)
    monkeypatch.setattr(trainer, "eval_population", lambda *a, **k: jnp.full(4, jnp.nan))
    r = fused_sha(wl, n_trials=4, min_budget=1, max_budget=1, eta=3, seed=0)
    assert r["diverged"] is True
    assert r["best_params"] is None and r["best_trial"] is None
    assert np.isnan(r["best_score"])


def test_fused_sha_one_nan_does_not_hijack(monkeypatch):
    """One diverged member in an otherwise healthy cohort: the winner is
    the best FINITE score, diverged stays False."""
    import jax.numpy as jnp

    from mpi_opt_tpu.train.common import workload_arrays

    wl = get_workload("fashion_mlp", n_train=256, n_val=128)  # its own, as above
    trainer, *_ = workload_arrays(wl)
    scores = jnp.asarray([jnp.nan, 0.2, 0.9, 0.4])
    monkeypatch.setattr(trainer, "eval_population", lambda *a, **k: scores)
    r = fused_sha(wl, n_trials=4, min_budget=1, max_budget=1, eta=3, seed=0)
    assert r["diverged"] is False
    assert r["best_trial"] == 2
    assert r["best_score"] == pytest.approx(0.9)


def test_deferred_fetch_matches_checkpointed_ledger(tmp_path, workload):
    """Uncheckpointed sweeps defer all host fetches to one end-of-sweep
    barrier; the replayed ledger must be IDENTICAL to the eager
    (checkpointed) path's — same rung history, stop rungs, and best."""
    kw = dict(n_trials=9, min_budget=2, max_budget=8, eta=2, seed=3)
    deferred = fused_sha(workload, **kw)
    eager = fused_sha(workload, checkpoint_dir=str(tmp_path / "ck"), **kw)
    assert deferred["best_score"] == eager["best_score"]
    assert deferred["best_trial"] == eager["best_trial"]
    assert deferred["rung_history"] == eager["rung_history"]
    np.testing.assert_array_equal(deferred["stop_rung"], eager["stop_rung"])
    np.testing.assert_array_equal(deferred["last_score"], eager["last_score"])
