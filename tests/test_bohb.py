"""BOHB: model-based Hyperband — bracket composition, model gating,
id-space partitioning, checkpoint roundtrip, end-to-end search."""

import jax
import numpy as np
import pytest

from mpi_opt_tpu.algorithms import BOHB, Hyperband, get_algorithm
from mpi_opt_tpu.backends.cpu import CPUBackend
from mpi_opt_tpu.driver import run_search
from mpi_opt_tpu.workloads import get_workload


def _space():
    return get_workload("quadratic").default_space()


def test_registered():
    assert get_algorithm("bohb") is BOHB


def test_uniform_until_model_qualifies():
    """Before any budget accumulates n_min observations, every draw is
    uniform; after feeding one budget past n_min, non-random draws come
    from the acquisition kernel (deterministically, given the key)."""
    space = _space()
    algo = BOHB(space, seed=0, max_budget=9, eta=3, random_fraction=0.0)
    assert algo._model_budget() is None
    key = jax.random.key(1)
    u = algo._model_sample(key)
    assert u.shape == (space.dim,)

    # feed a discriminative history at budget 9: high scores cluster at
    # 0.2, low scores at 0.8 (every dim), well past n_min points
    s = algo.obs.ring(9)
    rng = np.random.default_rng(0)
    n = max(4 * algo.n_min, 24)
    for i in range(n):
        good = i % 2 == 0
        center = 0.2 if good else 0.8
        s["unit"][i] = np.clip(center + 0.03 * rng.standard_normal(algo.space.dim), 0, 1)
        s["score"][i] = (1.0 if good else 0.0) + 0.01 * rng.standard_normal()
        s["valid"][i] = True
        s["n"] += 1
    assert algo._model_budget() == 9
    draws = np.stack([algo._model_sample(jax.random.fold_in(key, i)) for i in range(16)])
    # the model concentrates samples toward the good cluster
    m = float(draws[:, 0].mean())
    assert abs(m - 0.2) < abs(m - 0.8), f"model samples not biased to the good cluster: {m}"


def test_model_prefers_highest_qualified_budget():
    algo = BOHB(_space(), seed=0, max_budget=27, eta=3)
    for b in (1, 3, 9):
        s = algo.obs.ring(b)
        s["n"] = algo.n_min + 1
    assert algo._model_budget() == 9


def test_bracket_ids_are_disjoint():
    """Brackets share one (possibly stateful) backend; their trial-id
    ranges must never overlap or bracket 2's fresh trials would warm-
    resume bracket 1's ledger entries (Backend.reset's hazard, in its
    multi-Algorithm form). Applies to Hyperband and BOHB alike."""
    for cls in (Hyperband, BOHB):
        algo = cls(_space(), seed=0, max_budget=27, eta=3)
        seen = set()
        for b in algo.brackets:
            batch = b.next_batch(1000)
            ids = {t.trial_id for t in batch}
            assert not (ids & seen), f"{cls.name}: overlapping trial ids"
            seen |= ids


def test_bohb_driver_loop_completes_and_uses_model():
    wl = get_workload("quadratic")
    algo = BOHB(wl.default_space(), seed=0, max_budget=27, eta=3)
    be = CPUBackend(wl, n_workers=1)
    try:
        res = run_search(algo, be)
    finally:
        be.close()
    assert algo.finished()
    assert res.n_trials == 27 + 12 + 6 + 4  # same plan as hyperband R=27
    assert res.best is not None and res.best.score is not None
    # the later brackets ran with a qualified model (enough budget-1
    # observations exist after bracket 0's first rung alone)
    assert algo._model_budget() is not None


def test_obsstore_drops_nan_scores():
    """Diverged trials (NaN scores) must not enter the model or count
    toward n_min — filtered in ObsStore.add so the host and fused paths
    cannot disagree."""
    from mpi_opt_tpu.algorithms.bohb import ObsStore

    st = ObsStore(dim=2, buffer_size=4, n_min=2)
    st.add(1, np.array([0.1, 0.2], np.float32), float("nan"))
    assert 1 not in st.budgets  # nothing stored at all
    st.add(1, np.array([0.1, 0.2], np.float32), 0.5)
    st.add(1, np.array([0.3, 0.2], np.float32), 0.6)
    assert st.model_budget() == 1


def test_fused_hyperband_nan_bracket_never_sticks(monkeypatch):
    """A diverged bracket (best_score NaN) must not freeze as the
    overall winner — `x > nan` is False for every x, so the naive
    best-pick would return the NaN bracket forever."""
    import mpi_opt_tpu.train.fused_asha as fa

    def fake(best):
        return {
            "best_score": best,
            "best_params": {"marker": best},
            "rung_sizes": [1],
            "rung_budgets": [1],
            "stop_rung": np.zeros(1, np.int32),
            "last_score": np.array([best], np.float32),
            "rung_history": [],
            "n_trials": 1,
        }

    results = iter([fake(float("nan")), fake(0.9)])
    monkeypatch.setattr(fa, "fused_sha", lambda *a, **k: next(results))
    res = fa.fused_hyperband(None, max_budget=3, eta=3, seed=0)  # 2 brackets
    assert res["best_score"] == pytest.approx(0.9)


def test_fused_bohb_runs_and_uses_model(shared_workload):
    """Fused BOHB: every bracket executes as a fused on-device SHA; by
    the later brackets the model store has qualified, so cohorts carry
    model-sampled rows (random_fraction=0 makes the count exact)."""
    from mpi_opt_tpu.train.fused_bohb import fused_bohb

    wl = shared_workload("fashion_mlp", n_train=512, n_val=256)
    # bracket 0's first rung alone contributes 9 observations at budget
    # 1 (the FULL cohort scores, not just stop-rung ones), clearing the
    # 5-dim space's default n_min = d+3 = 8 — so the model qualifies for
    # every later bracket, same as the host algorithm would
    res = fused_bohb(wl, max_budget=9, eta=3, seed=0, random_fraction=0.0)
    # R=9: brackets (9@1, 5@3, 3@9) from bracket_plan
    assert res["n_trials"] == 9 + 5 + 3
    assert 0.0 <= res["best_score"] <= 1.0
    assert res["brackets"][0]["n_model_sampled"] == 0  # nothing to fit yet
    assert res["brackets"][1]["n_model_sampled"] == 5
    assert res["brackets"][2]["n_model_sampled"] == 3


def test_fused_sha_init_unit_digest_guards_resume(shared_workload, tmp_path):
    """A fused SHA resumed under DIFFERENT initial configurations is a
    different search: the checkpoint's cohort digest must refuse it."""
    import jax

    from mpi_opt_tpu.train.fused_asha import fused_sha

    wl = shared_workload("fashion_mlp", n_train=512, n_val=256)
    space = wl.default_space()
    ck = str(tmp_path / "ck")
    unit_a = np.asarray(space.sample_unit(jax.random.key(1), 6))
    fused_sha(wl, n_trials=6, min_budget=2, max_budget=6, eta=3,
              seed=0, checkpoint_dir=ck, init_unit=unit_a)
    unit_b = np.asarray(space.sample_unit(jax.random.key(2), 6))
    with pytest.raises(ValueError, match="different sweep"):
        fused_sha(wl, n_trials=6, min_budget=2, max_budget=6, eta=3,
                  seed=0, checkpoint_dir=ck, init_unit=unit_b)
    # the SAME cohort resumes fine (replays from the final snapshot)
    res = fused_sha(wl, n_trials=6, min_budget=2, max_budget=6, eta=3,
                    seed=0, checkpoint_dir=ck, init_unit=unit_a)
    assert 0.0 <= res["best_score"] <= 1.0


def test_bohb_checkpoint_roundtrip():
    wl = get_workload("quadratic")
    space = wl.default_space()
    algo = BOHB(space, seed=3, max_budget=27, eta=3)
    be = CPUBackend(wl, n_workers=1)
    try:
        run_search(algo, be, max_batches=3)
        mid = algo.state_dict()
        resumed = BOHB(space, seed=3, max_budget=27, eta=3)
        resumed.load_state_dict(mid)
        assert resumed._samples == algo._samples
        for b in algo.obs.budgets:
            np.testing.assert_array_equal(resumed.obs.budgets[b]["unit"], algo.obs.budgets[b]["unit"])
            assert resumed.obs.budgets[b]["n"] == algo.obs.budgets[b]["n"]
        r1 = run_search(algo, be)
        be.reset()
        r2 = run_search(resumed, be)
    finally:
        be.close()
    assert r1.best is not None and r2.best is not None
    # both complete the full plan (arrival-order effects can differ, as
    # with hyperband's resume; completion and a sane best are the contract)
    assert algo.finished() and resumed.finished()


def test_bohb_checkpoint_validates_n_min():
    """n_min is the model-qualification threshold: a checkpoint written
    under a different value must be refused (silently resuming under a
    changed threshold changes WHEN the model engages) — while a
    pre-upgrade checkpoint with no recorded n_min stays loadable
    (ADVICE r4)."""
    space = _space()
    st = BOHB(space, seed=0, max_budget=9, eta=3, n_min=5).state_dict()
    algo = BOHB(space, seed=0, max_budget=9, eta=3, n_min=7)
    with pytest.raises(ValueError, match=r"n_min=5.*not n_min=7"):
        algo.load_state_dict(st)
    # pre-upgrade checkpoints carry no n_min: setdefault to the
    # instance's value, matching the momentum_dtype pattern
    del st["bohb"]["n_min"]
    BOHB(space, seed=0, max_budget=9, eta=3, n_min=7).load_state_dict(st)


def test_obsstore_drops_inf_scores():
    """+/-inf scores (exploded losses) are as model-poisoning as NaN:
    they'd blow up the KDE moments/bandwidths. Same isfinite gate, same
    single filtering point (ADVICE r3)."""
    from mpi_opt_tpu.algorithms.bohb import ObsStore

    st = ObsStore(dim=2, buffer_size=4, n_min=2)
    st.add(1, np.array([0.1, 0.2], np.float32), float("inf"))
    st.add(1, np.array([0.3, 0.4], np.float32), float("-inf"))
    assert 1 not in st.budgets


def test_bohb_refuses_hyperband_checkpoint():
    """Restoring a plain-hyperband checkpoint into BOHB must be the
    clear ValueError refusal the R/eta and buffer-size mismatches give,
    not a bare KeyError (ADVICE r3)."""
    space = _space()
    hb_state = Hyperband(space, seed=0, max_budget=9, eta=3).state_dict()
    algo = BOHB(space, seed=0, max_budget=9, eta=3)
    with pytest.raises(ValueError, match="hyperband, not bohb"):
        algo.load_state_dict(hb_state)


def test_fused_hyperband_persists_cohorts_for_resume(shared_workload, tmp_path):
    """Resume correctness must not depend on the model regenerating
    bit-identical cohorts: each bracket's sampled cohort is persisted
    (cohort_b.npz) and reused, so a resumed sweep whose sampler would
    drift numerically still replays — the drifted sampler is never even
    consulted (ADVICE r3)."""
    import jax

    from mpi_opt_tpu.train.fused_asha import fused_hyperband

    wl = shared_workload("fashion_mlp", n_train=512, n_val=256)
    space = wl.default_space()
    ck = str(tmp_path / "ck")

    def cohort_a(b, n):
        u = np.array(space.sample_unit(jax.random.fold_in(jax.random.key(7), b), n))
        return u, 0

    r1 = fused_hyperband(wl, max_budget=3, eta=3, seed=0,
                         checkpoint_dir=ck, cohort_fn=cohort_a)

    def cohort_drifted(b, n):
        raise AssertionError("resume must reuse the persisted cohort, "
                             "not regenerate it")

    r2 = fused_hyperband(wl, max_budget=3, eta=3, seed=0,
                         checkpoint_dir=ck, cohort_fn=cohort_drifted)
    assert r2["best_score"] == pytest.approx(r1["best_score"])
    assert r2["best_params"] == r1["best_params"]


def test_persisted_cohort_refuses_different_sweep(tmp_path):
    """A cohort file left by a crashed run of a DIFFERENT sweep (other
    seed/workload/plan) must be refused even when no bracket snapshot
    exists yet to trigger fused_sha's config check — the cohort npz
    carries its own sweep-identity tag."""
    from mpi_opt_tpu.train.fused_asha import _bracket_cohort

    ck = str(tmp_path / "ck")

    def cohort(b, n):
        return np.full((n, 2), 0.5, np.float32), 0

    tag_a = "fashion_mlp|R=9|eta=3|seed=0"
    _bracket_cohort(ck, 0, 3, tag_a, cohort)  # first run writes cohort_0.npz
    for other in ("fashion_mlp|R=9|eta=3|seed=1",   # different seed
                  "cifar_cnn|R=9|eta=3|seed=0",      # different workload
                  "fashion_mlp|R=27|eta=3|seed=0"):  # different plan
        with pytest.raises(ValueError, match="different sweep"):
            _bracket_cohort(ck, 0, 3, other, cohort)
    # the matching sweep still reuses it, without consulting the sampler
    c, m = _bracket_cohort(ck, 0, 3, tag_a,
                           lambda b, n: (_ for _ in ()).throw(AssertionError))
    assert c.shape == (3, 2) and m == 0
