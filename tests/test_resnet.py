"""ResNet-18 (config 5): structure, dtype conventions, population path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_opt_tpu.models import ResNet18
from mpi_opt_tpu.workloads import get_workload

# Full ResNet-18 programs compiled AND run on XLA:CPU: 12 + 26 + 38 +
# 51 s here (measured 2026-09-26, 8 cores) — out of the tier-1 time
# limit; run explicitly or with ``-m slow``
pytestmark = pytest.mark.slow


def _n_params(params):
    return sum(p.size for p in jax.tree.leaves(params))


def test_resnet18_param_count_and_dtypes():
    """Full-width model is the real ResNet-18 (~11.2M params)."""
    m = ResNet18(n_classes=100)
    x = jnp.zeros((1, 32, 32, 3), jnp.float32)
    params = m.init(jax.random.key(0), x)["params"]
    n = _n_params(params)
    assert 11.0e6 < n < 11.5e6, n
    # f32 params (models package convention)
    assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(params))
    out = m.apply({"params": params}, x)
    assert out.shape == (1, 100)
    assert out.dtype == jnp.float32


def test_resnet_remat_matches_no_remat():
    """remat changes the memory schedule, never the function."""
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    a = ResNet18(n_classes=10, width=8, remat=False)
    b = ResNet18(n_classes=10, width=8, remat=True)
    params = a.init(jax.random.key(2), x)["params"]
    ya = a.apply({"params": params}, x)
    yb = b.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(ya), np.asarray(yb), atol=1e-6)


@pytest.fixture(scope="module")
def tiny_workload():
    # tiny width keeps the CPU test fast; identical program structure
    return get_workload("cifar100_resnet18", n_train=256, n_val=128, width=8)


def test_resnet_population_trains_and_gathers(tiny_workload):
    """The config-5 model runs the full population protocol: vmapped
    init/train/eval plus the exploit gather over a deep pytree."""
    wl = tiny_workload
    d = wl.data()
    assert d["n_classes"] == 100
    trainer = wl.make_trainer(member_chunk=2)
    tx, ty = jnp.asarray(d["train_x"]), jnp.asarray(d["train_y"])
    vx, vy = jnp.asarray(d["val_x"]), jnp.asarray(d["val_y"])
    state = trainer.init_population(jax.random.key(0), tx[:2], 4)
    space = wl.default_space()
    unit = space.sample_unit(jax.random.key(1), 4)
    hp = wl.make_hparams(space.from_unit(unit))
    state, losses = trainer.train_segment(state, hp, tx, ty, jax.random.key(2), 3)
    assert losses.shape == (3,)
    assert np.isfinite(np.asarray(losses)).all()
    scores = trainer.eval_population(state, vx, vy)
    assert scores.shape == (4,)
    assert np.isfinite(np.asarray(scores)).all()
    # exploit: everyone continues from member 2
    gathered = trainer.gather_members(state, jnp.array([2, 2, 2, 2]))
    k0 = jax.tree.leaves(gathered.params)[0]
    np.testing.assert_array_equal(np.asarray(k0[0]), np.asarray(k0[3]))


def test_resnet_fused_pbt_generation(tiny_workload):
    """One fused PBT generation end-to-end on the config-5 model."""
    from mpi_opt_tpu.train.fused_pbt import fused_pbt

    result = fused_pbt(
        tiny_workload, population=4, generations=2, steps_per_gen=2, seed=0
    )
    assert result["best_curve"].shape == (2,)
    assert 0.0 <= result["best_score"] <= 1.0
