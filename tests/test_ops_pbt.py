import jax
import jax.numpy as jnp
import numpy as np

from mpi_opt_tpu.ops import PBTConfig, pbt_exploit_explore

# as the fused programs run it: one program, not a compile an operation
exploit_explore = jax.jit(pbt_exploit_explore, static_argnames="cfg")


def _setup(n=16, d=3, seed=0):
    key = jax.random.key(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    unit = jax.random.uniform(k1, (n, d))
    scores = jax.random.uniform(k2, (n,))
    disc = jnp.array([False, False, True])
    return k3, unit, scores, disc


def test_survivors_untouched():
    key, unit, scores, disc = _setup()
    cfg = PBTConfig(truncation_frac=0.25)
    new_unit, src_idx, exploited = exploit_explore(key, unit, scores, disc, cfg)
    n_cut = 4
    assert int(exploited.sum()) == n_cut
    keep = ~np.asarray(exploited)
    np.testing.assert_allclose(np.asarray(new_unit)[keep], np.asarray(unit)[keep])
    np.testing.assert_array_equal(np.asarray(src_idx)[keep], np.arange(16)[keep])


def test_losers_copy_from_top():
    key, unit, scores, disc = _setup(n=32)
    cfg = PBTConfig(truncation_frac=0.25)
    _, src_idx, exploited = exploit_explore(key, unit, scores, disc, cfg)
    order = np.argsort(-np.asarray(scores))
    top = set(order[:8].tolist())
    bottom = set(order[-8:].tolist())
    for i in np.where(np.asarray(exploited))[0]:
        assert i in bottom
        assert int(src_idx[i]) in top


def test_explored_values_near_source():
    key, unit, scores, disc = _setup(n=64, d=2, seed=1)
    disc = jnp.array([False, False])
    cfg = PBTConfig(truncation_frac=0.25, perturb_scale=0.05)
    new_unit, src_idx, exploited = exploit_explore(key, unit, scores, disc, cfg)
    src = np.asarray(unit)[np.asarray(src_idx)]
    diff = np.abs(np.asarray(new_unit) - src)[np.asarray(exploited)]
    # perturbation is small Gaussian, clipped; 5 sigma bound
    assert diff.max() < 0.25
    assert diff.max() > 0  # but nonzero: explore actually happened


def test_bounds_respected():
    key, unit, scores, disc = _setup(n=128, d=4, seed=2)
    disc = jnp.array([False, True, False, True])
    new_unit, _, _ = exploit_explore(key, unit, scores, disc, PBTConfig(perturb_scale=0.5))
    arr = np.asarray(new_unit)
    assert arr.min() >= 0.0 and arr.max() <= 1.0


def test_jittable_and_deterministic():
    key, unit, scores, disc = _setup()
    f = jax.jit(pbt_exploit_explore, static_argnames="cfg")
    a = f(key, unit, scores, disc, PBTConfig())
    b = f(key, unit, scores, disc, PBTConfig())
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
