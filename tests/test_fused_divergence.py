"""Non-finite-score hardening for fused PBT and fused TPE (ADVICE r4).

Fused SHA/Hyperband/BOHB and the host algorithms already gate their
winner-pick on isfinite; these tests pin the same contract onto the two
remaining fused paths: a diverged member (NaN score) must never hijack
best_score via argmax's first-NaN behavior, and an all-diverged sweep
must report best_params=None with diverged=True instead of dressing an
arbitrary row up as a winner.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import mpi_opt_tpu.train.fused_tpe as ft
from mpi_opt_tpu.train.common import workload_arrays
from mpi_opt_tpu.train.fused_pbt import fused_pbt
from mpi_opt_tpu.workloads import get_workload


@pytest.fixture
def wl(shared_workload):
    return shared_workload("fashion_mlp", n_train=256, n_val=128)


@pytest.fixture
def own_wl():
    """An instance of this test's own, NOT the session's: the test puts
    its scores in place of the trainer's ``eval_population``, and a
    program traced around them stays on the trainer for whoever asks for
    the same sizes next."""
    return get_workload("fashion_mlp", n_train=256, n_val=128)


def test_fused_pbt_nan_survivor_does_not_hijack(own_wl, monkeypatch):
    """Two NaN members, truncation cut of 1: exactly one gets exploited
    (replaced by a top member's score via the src_idx gather), the other
    SURVIVES into final_scores as NaN — the scenario where a bare
    argmax would crown the NaN row. The winner must be the best finite
    score."""
    trainer, *_ = workload_arrays(own_wl)
    scores = jnp.asarray([0.9, jnp.nan, jnp.nan, 0.4])
    monkeypatch.setattr(trainer, "eval_population", lambda *a, **k: scores)
    r = fused_pbt(own_wl, population=4, generations=1, steps_per_gen=1, seed=0)
    assert r["diverged"] is False
    assert r["best_score"] == pytest.approx(0.9)
    assert r["best_params"] is not None
    # the divergence the exploit step masked is REPORTED, not hidden:
    # both NaN members count in the per-generation tally (ROADMAP item)
    assert r["member_failures"] == [2]


def test_fused_pbt_all_nan_reports_diverged(own_wl, monkeypatch):
    trainer, *_ = workload_arrays(own_wl)
    monkeypatch.setattr(
        trainer, "eval_population", lambda *a, **k: jnp.full(4, jnp.nan)
    )
    r = fused_pbt(own_wl, population=4, generations=1, steps_per_gen=1, seed=0)
    assert r["diverged"] is True
    assert r["best_params"] is None
    assert np.isnan(r["best_score"])
    assert r["member_failures"] == [4]


def test_fused_sha_counts_member_failures_per_rung(own_wl, monkeypatch):
    """The single-rung (fused random) case: diverged members are tallied
    per rung in the result, exactly what the isfinite winner pick
    masks. Shared rung_history sourcing keeps the eager and deferred
    fetch paths in agreement by construction."""
    from mpi_opt_tpu.train.fused_asha import fused_sha

    trainer, *_ = workload_arrays(own_wl)
    scores = jnp.asarray([0.9, jnp.nan, jnp.nan, 0.4])
    monkeypatch.setattr(trainer, "eval_population", lambda *a, **k: scores)
    r = fused_sha(own_wl, n_trials=4, min_budget=2, max_budget=2, seed=0)
    assert r["member_failures"] == [2]
    assert r["best_score"] == pytest.approx(0.9)


def _nan_row_injector(real, rows):
    """Wrap tpe_generation, overwriting observation rows with NaN scores
    after each generation — a valid-but-diverged trial."""

    def wrapped(*a, **k):
        obs_unit, obs_scores, valid, key, scores, extra = real(*a, **k)
        for i in rows:
            obs_scores = obs_scores.at[i].set(jnp.nan)
        return obs_unit, obs_scores, valid, key, scores, extra

    return wrapped


def test_fused_tpe_valid_nan_does_not_hijack(wl, monkeypatch):
    """A valid-but-NaN observation must not win argmax (the old code
    masked only ~valid rows) and must not poison the running
    best_curve (jnp.max propagates NaN into every later point)."""
    monkeypatch.setattr(
        ft, "tpe_generation", _nan_row_injector(ft.tpe_generation, rows=[0])
    )
    r = ft.fused_tpe(wl, n_trials=8, batch=4, budget=3, seed=0)
    assert r["diverged"] is False
    assert np.isfinite(r["best_score"])
    assert r["best_params"] is not None
    assert np.isfinite(r["best_curve"]).all()
    # the NaN observation is reported raw in obs_scores (visibility),
    # only the winner-pick and curve mask it
    assert np.isnan(r["obs_scores"][0])


def test_fused_tpe_all_nan_reports_diverged(wl, monkeypatch):
    monkeypatch.setattr(
        ft,
        "tpe_generation",
        _nan_row_injector(ft.tpe_generation, rows=range(8)),
    )
    r = ft.fused_tpe(wl, n_trials=8, batch=4, budget=3, seed=0)
    assert r["diverged"] is True
    assert r["best_params"] is None
