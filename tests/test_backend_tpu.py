"""TPU population backend: slot pool, grouping, inheritance, eviction.

Runs on the CPU-simulated device (conftest) — identical code path to a
real chip modulo the platform.
"""

import numpy as np
import pytest

from mpi_opt_tpu.algorithms import ASHA, PBT, RandomSearch
from mpi_opt_tpu.backends import get_backend
from mpi_opt_tpu.driver import run_search
from mpi_opt_tpu.trial import Trial
from mpi_opt_tpu.workloads import get_workload


MLP = dict(n_train=2048, n_val=512)


@pytest.fixture(scope="module")
def workload(shared_workload):
    return shared_workload("fashion_mlp", **MLP)


def _trial(space, tid, budget, seed=0, **extra):
    import jax

    unit = np.asarray(space.sample_unit(jax.random.fold_in(jax.random.key(seed), tid), 1))[0]
    params = space.materialize_row(unit)
    params.update(extra)
    return Trial(trial_id=tid, params=params, unit=unit, budget=budget)


def test_rejects_workload_without_population_protocol():
    wl = get_workload("digits")
    with pytest.raises(ValueError, match="population protocol"):
        get_backend("tpu", wl, population=4)


def test_batch_evaluation_returns_ordered_results(workload):
    be = get_backend("tpu", workload, population=4, seed=0)
    space = workload.default_space()
    trials = [_trial(space, i, budget=20) for i in range(4)]
    results = be.evaluate(trials)
    assert [r.trial_id for r in results] == [0, 1, 2, 3]
    assert all(0.0 <= r.score <= 1.0 for r in results)


def test_mixed_budget_batch_grouping(workload):
    """ASHA hands the backend a batch mixing rung budgets; each group
    trains only its remaining steps."""
    be = get_backend("tpu", workload, population=4, seed=1)
    space = workload.default_space()
    a = _trial(space, 10, budget=10)
    be.evaluate([a])
    assert be._trained[10] == 10
    # promoted trial (budget 30, 20 remaining) + fresh trial (budget 10)
    a.budget = 30
    b = _trial(space, 11, budget=10)
    results = be.evaluate([a, b])
    assert be._trained[10] == 30 and be._trained[11] == 10
    assert {r.trial_id for r in results} == {10, 11}


def test_warm_resume_preserves_learning(workload):
    """Resuming 40+40 steps must beat a fresh member trained 40."""
    be = get_backend("tpu", workload, population=2, seed=2)
    space = workload.default_space()
    t = _trial(space, 20, budget=40, seed=5)
    r1 = be.evaluate([t])[0]
    t.budget = 80
    r2 = be.evaluate([t])[0]
    # same member, more cumulative budget: should not get materially worse
    assert r2.score > r1.score - 0.05


def test_pbt_inheritance_gathers_weights(workload):
    be = get_backend("tpu", workload, population=2, seed=3)
    space = workload.default_space()
    parent = _trial(space, 30, budget=60, seed=7, __inherit_from__=None, __slot__=0)
    rp = be.evaluate([parent])[0]
    # child inherits parent's trained weights; 0 extra steps (same budget)
    child = _trial(space, 31, budget=60, seed=8, __inherit_from__=30, __slot__=0)
    rc = be.evaluate([child])[0]
    # inherited state ≈ parent's accuracy (no training in between)
    assert abs(rc.score - rp.score) < 0.08


def test_eviction_falls_back_to_retrain(workload):
    be = get_backend("tpu", workload, population=2, seed=4, slot_slack=2)
    space = workload.default_space()
    # pool has 4 usable slots; run 6 distinct trials to force eviction
    trials = [_trial(space, 40 + i, budget=15, seed=i) for i in range(6)]
    for t in trials:
        be.evaluate([t])
    assert len(be._slot_of) <= 4
    # evicted trial returns: retrains from scratch to its full budget
    t0 = trials[0]
    t0.budget = 30
    r = be.evaluate([t0])[0]
    assert be._trained[40] == 30
    assert 0.0 <= r.score <= 1.0


def test_batch_pressure_cannot_evict_in_batch_sources(workload):
    """Regression: fresh trials filling the pool in the same batch as a
    warm resume must not evict the resume's source slot mid-plan."""
    be = get_backend("tpu", workload, population=4, seed=11, slot_slack=2)
    space = workload.default_space()
    warm = _trial(space, 60, budget=20, seed=1)
    be.evaluate([warm])
    assert be._trained[60] == 20
    # fill every free slot with older trials so the batch below must evict
    fillers = [_trial(space, 70 + i, budget=10, seed=i) for i in range(7)]
    for f in fillers:
        be.evaluate([f])
    # batch: the warm resume + fresh trials forcing allocations
    warm.budget = 40
    batch = [warm] + [_trial(space, 80 + i, budget=10, seed=i) for i in range(3)]
    results = be.evaluate(batch)
    assert be._trained[60] == 40
    # warm trial stayed warm: its slot survived and results are ordered
    assert results[0].trial_id == 60
    assert 60 in be._slot_of


def test_full_search_pbt_on_tpu_backend(workload):
    algo = PBT(
        workload.default_space(), seed=9, population=8, generations=3, steps_per_generation=25
    )
    be = get_backend("tpu", workload, population=8, seed=9)
    res = run_search(algo, be)
    assert res.n_trials == 24
    assert res.best.score > 0.3  # actually learned something


def test_full_search_asha_on_tpu_backend(workload):
    algo = ASHA(
        workload.default_space(), seed=10, max_trials=12, min_budget=10, max_budget=90, eta=3
    )
    be = get_backend("tpu", workload, population=8, seed=10)
    res = run_search(algo, be)
    assert res.n_trials == 12
    assert res.best.score > 0.3


def test_reset_is_bit_identical_to_fresh_backend(workload):
    """reset() between searches must make a reused backend behave exactly
    like a new one. Regression: trial ids restart at 0 per algorithm, so
    WITHOUT reset a second search's ids alias the old ledger and are
    silently treated as rem=0 warm resumes of the previous search's
    states (this contaminated round-2's config-4 driver measurement)."""
    space = workload.default_space()
    first = [_trial(space, i, budget=15, seed=100 + i) for i in range(3)]
    second = [_trial(space, i, budget=15, seed=200 + i) for i in range(3)]

    be = get_backend("tpu", workload, population=4, seed=6)
    be.evaluate(first)
    be.reset()
    assert not be._slot_of and not be._trained and be._step_counter == 0
    r_reused = be.evaluate(second)
    # every post-reset trial resolved as fresh and trained its full budget
    assert all(be._trained[t.trial_id] == 15 for t in second)

    be_fresh = get_backend("tpu", workload, population=4, seed=6)
    r_fresh = be_fresh.evaluate(second)
    assert [r.score for r in r_reused] == [r.score for r in r_fresh]

    # and the aliasing hazard reset() exists for: without it, a repeated
    # id warm-resumes at rem=0 — no training happens, so two "different"
    # trials (different hparams) score identically off the stored state
    r_a = be.evaluate([_trial(space, 0, budget=15, seed=300)])[0]
    r_b = be.evaluate([_trial(space, 0, budget=15, seed=301)])[0]
    assert r_a.score == r_b.score


def test_meshed_slot_pool_shards_and_matches_unmeshed(workload, shared_workload):
    """A mesh-aware slot pool (driver path, VERDICT r2 item 1) keeps the
    pool sharded over 'pop' across evaluate() scatters, and scores agree
    with the single-device pool (sharding is a layout, not semantics)."""
    import jax

    from mpi_opt_tpu.parallel import make_mesh

    mesh = make_mesh(n_pop=8, n_data=1)
    space = workload.default_space()
    trials = [_trial(space, 100 + i, budget=10, seed=i) for i in range(8)]
    # the meshed pool's trainer stays on an instance of its own
    on_mesh = shared_workload("fashion_mlp", label="pop8 data1 mesh", **MLP)
    be_mesh = get_backend("tpu", on_mesh, population=8, seed=5, mesh=mesh)
    r_mesh = be_mesh.evaluate(trials)
    for leaf in jax.tree.leaves(be_mesh._pool.params):
        assert len(leaf.devices()) == 8, leaf.sharding
        assert not leaf.sharding.is_fully_replicated
    be_plain = get_backend("tpu", workload, population=8, seed=5)
    r_plain = be_plain.evaluate(trials)
    for m, p in zip(r_mesh, r_plain):
        assert m.trial_id == p.trial_id
        assert m.score == pytest.approx(p.score, abs=0.02)


def test_nonfinite_score_reports_failed_result(workload, monkeypatch):
    """A diverged member (NaN/inf eval score) comes back as a FAILED
    result — the driver-path contract matching the CPU backend — not as
    an 'ok' result whose poison score every consumer must gate. The
    divergence is injected at the eval boundary (real divergence needs
    an exploding LR and many steps; the contract is what's under test)."""
    be = get_backend("tpu", workload, population=4, seed=5)
    space = workload.default_space()
    trials = [_trial(space, 50 + i, budget=5, seed=5) for i in range(3)]
    be._setup()
    real = be._trainer.eval_population

    def poisoned(*a, **k):
        scores = np.asarray(real(*a, **k)).copy()
        scores[0] = np.nan
        return scores

    monkeypatch.setattr(be._trainer, "eval_population", poisoned)
    results = be.evaluate(trials)
    assert results[0].status == "failed"
    assert np.isnan(results[0].score)
    assert "diverged" in results[0].error
    assert all(r.ok and 0.0 <= r.score <= 1.0 for r in results[1:])


def test_failed_trial_evicted_so_retry_retrains(workload, monkeypatch):
    """A failed (diverged) trial must leave the ledger: a driver retry
    resolves it as FRESH and retrains from scratch, instead of warm-
    resuming the diverged state for 0 remaining steps and failing
    identically on every attempt."""
    be = get_backend("tpu", workload, population=4, seed=6)
    space = workload.default_space()
    t = _trial(space, 60, budget=5, seed=6)
    be._setup()
    real = be._trainer.eval_population
    calls = {"n": 0}

    def poison_first(*a, **k):
        calls["n"] += 1
        scores = np.asarray(real(*a, **k)).copy()
        if calls["n"] == 1:
            scores[0] = np.nan
        return scores

    monkeypatch.setattr(be._trainer, "eval_population", poison_first)
    (r1,) = be.evaluate([t])
    assert r1.status == "failed"
    assert 60 not in be._trained and 60 not in be._slot_of  # evicted
    (r2,) = be.evaluate([t])  # the driver's retry
    assert r2.ok and 0.0 <= r2.score <= 1.0
    assert be._trained[60] == 5  # genuinely retrained to budget
