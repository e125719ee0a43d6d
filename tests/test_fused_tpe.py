"""Fused generational TPE: on-device ring buffer, suggest, train, report."""

import numpy as np
import pytest

import mpi_opt_tpu.train.fused_tpe as ft


@pytest.fixture
def wl(shared_workload):
    return shared_workload("fashion_mlp", n_train=256, n_val=128)


def test_fused_tpe_structure_and_determinism(wl):
    kw = dict(n_trials=10, batch=4, budget=5, seed=0)
    r1 = ft.fused_tpe(wl, **kw)
    # ceil(10/4) = 3 generations: 4 + 4 + 2
    assert r1["best_curve"].shape == (3,)
    assert r1["n_trials"] == 10
    assert 0.0 <= r1["best_score"] <= 1.0
    assert set(r1["best_params"]) == set(wl.default_space().domains)
    # cumulative best is monotone nondecreasing by construction
    assert all(b >= a - 1e-7 for a, b in zip(r1["best_curve"], r1["best_curve"][1:]))
    # deterministic per seed
    r2 = ft.fused_tpe(wl, **kw)
    assert r2["best_score"] == r1["best_score"]
    np.testing.assert_array_equal(r2["obs_scores"], r1["obs_scores"])


def test_fused_tpe_crash_resume_bit_identical(wl, tmp_path, monkeypatch):
    kw = dict(n_trials=8, batch=4, budget=5, seed=3)
    whole = ft.fused_tpe(wl, **kw)

    real = ft.tpe_generation
    calls = {"n": 0}

    def crashing(*a, **k):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("simulated crash")
        return real(*a, **k)

    ckpt = str(tmp_path / "tpe")
    monkeypatch.setattr(ft, "tpe_generation", crashing)
    with pytest.raises(RuntimeError, match="simulated"):
        ft.fused_tpe(wl, checkpoint_dir=ckpt, **kw)
    monkeypatch.setattr(ft, "tpe_generation", real)

    resumed = ft.fused_tpe(wl, checkpoint_dir=ckpt, **kw)
    assert resumed["best_score"] == whole["best_score"]
    np.testing.assert_array_equal(resumed["obs_scores"], whole["obs_scores"])
    np.testing.assert_array_equal(resumed["best_curve"], whole["best_curve"])
    assert resumed["best_params"] == whole["best_params"]


def test_fused_tpe_rejects_zero_trials(wl):
    with pytest.raises(ValueError, match="n_trials"):
        ft.fused_tpe(wl, n_trials=0)


def test_fused_tpe_checkpoint_cfg_mismatch_raises(wl, tmp_path):
    from mpi_opt_tpu.ops.tpe import TPEConfig

    ckpt = str(tmp_path / "tpe")
    ft.fused_tpe(wl, n_trials=4, batch=4, budget=3, seed=1, checkpoint_dir=ckpt)
    with pytest.raises(ValueError, match="different sweep"):
        ft.fused_tpe(wl, n_trials=4, batch=4, budget=3, seed=1,
                     cfg=TPEConfig(gamma=0.5), checkpoint_dir=ckpt)
