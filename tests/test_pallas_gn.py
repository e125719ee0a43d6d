"""Fused GroupNorm(+ReLU) Pallas kernel vs the jnp reference.

Interpret mode on CPU (the kernel's Mosaic lowering runs on real TPU in
the config-5 probes/bench); correctness here covers fwd, the custom
VJP, the no-relu form, vmap batching (the population path), and the
flax module's param-tree compatibility with nn.GroupNorm.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi_opt_tpu.ops.pallas_gn as pg


@pytest.fixture(autouse=True)
def interpret_mode(monkeypatch):
    monkeypatch.setattr(pg, "_INTERPRET", True)


def _setup(c, groups, b=2, hw=4, seed=0):
    k = jax.random.fold_in(jax.random.key(seed), c)
    kx, kg, kb, kd = jax.random.split(k, 4)
    x = jax.random.normal(kx, (b, hw, hw, c), jnp.float32)
    gamma = jax.random.normal(kg, (c,)) * 0.5 + 1.0
    beta = jax.random.normal(kb, (c,)) * 0.1
    dy = jax.random.normal(kd, x.shape)
    return x, gamma, beta, dy


@pytest.mark.parametrize("c,groups", [(64, 32), (128, 32), (8, 4)])
@pytest.mark.parametrize("relu", [True, False])
def test_forward_and_grads_match_reference(c, groups, relu):
    x, gamma, beta, dy = _setup(c, groups)
    y = pg.group_norm_relu(x, gamma, beta, groups, 1e-6, relu)
    yr = pg.reference_group_norm_relu(x, gamma, beta, groups, 1e-6, relu)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4)

    f = lambda x, g, b: jnp.sum(pg.group_norm_relu(x, g, b, groups, 1e-6, relu) * dy)
    fr = lambda x, g, b: jnp.sum(
        pg.reference_group_norm_relu(x, g, b, groups, 1e-6, relu) * dy
    )
    got = jax.grad(f, argnums=(0, 1, 2))(x, gamma, beta)
    want = jax.grad(fr, argnums=(0, 1, 2))(x, gamma, beta)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-3)


def test_vmap_matches_per_member(interpret_mode):
    """The population trainer vmaps members over the kernel; pallas's
    batching rule must agree with a per-member loop."""
    x = jax.random.normal(jax.random.key(1), (3, 2, 4, 4, 64))
    gamma = jnp.ones((3, 64))
    beta = jnp.zeros((3, 64))
    yv = jax.vmap(lambda x, g, b: pg.group_norm_relu(x, g, b, 32, 1e-6, True))(
        x, gamma, beta
    )
    yr = jnp.stack(
        [pg.reference_group_norm_relu(x[i], gamma[i], beta[i], 32) for i in range(3)]
    )
    np.testing.assert_allclose(np.asarray(yv), np.asarray(yr), atol=1e-4)


def test_resnet_param_tree_identical_across_gn_variants():
    """PallasGN keeps nn.GroupNorm's param names/shapes, so population
    states (and checkpoints) swap between the two model variants."""
    from mpi_opt_tpu.models.resnet import ResNet

    x = jnp.zeros((2, 8, 8, 3))
    kw = dict(n_classes=10, stage_sizes=(1, 1), width=8)
    # names and shapes are the subject: the init is traced, not run
    # (op by op, the interpreted kernel made this the file's dearest test)
    init = lambda pallas_gn: jax.eval_shape(ResNet(**kw, pallas_gn=pallas_gn).init, jax.random.key(0), x)["params"]
    p_xla, p_pal = init(False), init(True)
    assert jax.tree.structure(p_xla) == jax.tree.structure(p_pal)
    assert [tuple(l.shape) for l in jax.tree.leaves(p_xla)] == [
        tuple(l.shape) for l in jax.tree.leaves(p_pal)
    ]


def test_bf16_activation_dtype_roundtrip():
    x, gamma, beta, _ = _setup(64, 32)
    y = pg.group_norm_relu(x.astype(jnp.bfloat16), gamma, beta, 32, 1e-6, True)
    assert y.dtype == jnp.bfloat16
    yr = pg.reference_group_norm_relu(x.astype(jnp.bfloat16), gamma, beta, 32)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32), atol=3e-2
    )


def test_non_dividing_group_count_raises():
    """C % num_groups != 0 must raise (flax parity): _group_matrices
    floor-divides, so a non-dividing count would silently normalize over
    a WRONG group membership instead of failing."""
    x, gamma, beta, _ = _setup(48, 32)
    with pytest.raises(ValueError, match="divisible"):
        pg.group_norm_relu(x, gamma, beta, groups=32)
    # the gradient path funnels through the same forward check
    with pytest.raises(ValueError, match="divisible"):
        jax.grad(lambda v: pg.group_norm_relu(v, gamma, beta, groups=5).sum())(x)
