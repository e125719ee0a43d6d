"""Attention over a run-time selection as TPU kernels
(ops/selected_attention.py), in the Pallas interpreter on the CPU:
against the plain products, alone, in one query tile of the decoder
and in the whole decoder (models/sparse_moe_decoder.py takes them on a
TPU: ``use_kernels``, patched here) against the benchmark's reference.
What the chip's compiler makes of the kernels at the real shape is
tests/test_chip_compile.py's."""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_opt_tpu.models import sparse_moe_decoder as smd
from mpi_opt_tpu.ops import selected_attention as sa

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    monkeypatch.setattr(sa, "_INTERPRET", True)


def _plain(q, k, v, mask):
    group = q.shape[0] // k.shape[0]
    kk, vv = jnp.repeat(k, group, 0), jnp.repeat(v, group, 0)
    s = jnp.einsum("hqd,hkd->hqk", q, kk, preferred_element_type=jnp.float32)
    s = jnp.where(mask[None], s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None]).astype(jnp.bfloat16)
    return jnp.einsum("hqk,hkd->hqd", p, vv, preferred_element_type=jnp.float32), lse


def test_masked_attention_is_the_plain_products():
    """Context, log-sum-exp and the three gradients, grouped-query heads
    sharing ONE mask that leaves whole blocks empty, full and partial;
    more keys than queries (a query tile against all its causal keys)."""
    heads, kv_heads, rows, keys, d = 4, 2, 128, 256, 128
    ks = jax.random.split(jax.random.key(0), 5)
    q = (jax.random.normal(ks[0], (heads, rows, d)) * d**-0.5).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (kv_heads, keys, d)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (kv_heads, keys, d)).astype(jnp.bfloat16)
    row, col = jnp.arange(rows)[:, None] + (keys - rows), jnp.arange(keys)[None, :]
    mask = (col <= row) & ((col < 128) | (jax.random.uniform(ks[3], (rows, keys)) < 0.5) | (col == row))
    out, lse = sa.masked_attention(q, k, v, mask, 128)
    want, want_lse = _plain(q, k, v, mask)
    assert out.shape == (heads, rows, d) and lse.shape == (heads, rows) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse), atol=1e-5)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want), atol=0.03)
    w = jax.random.normal(ks[4], (heads, rows, d))
    got = jax.grad(lambda *a: jnp.sum(sa.masked_attention(*a, mask, 128)[0].astype(jnp.float32) * w), (0, 1, 2))(q, k, v)
    ref = jax.grad(lambda *a: jnp.sum(_plain(*a, mask)[0] * w), (0, 1, 2))(q, k, v)
    for a, b in zip(got, ref):
        b = np.asarray(b, np.float32)
        np.testing.assert_allclose(np.asarray(a, np.float32), b, atol=0.01 * np.abs(b).max())
    assert sa.supported(512, 128) and not sa.supported(64, 128) and not sa.supported(512, 64)


def test_a_query_tile_is_the_same_with_and_without_the_kernels():
    """One tile of the decoder's sparse attention (128 queries against
    their 256 causal keys, head size 128, top-k 96): context, the
    indexer's loss, the keys counted, and the gradient of every input —
    the indexer's through the probabilities rebuilt from the kernels'
    log-sum-exp — the kernels against XLA's own products."""
    dims = smd.DecoderDims(heads=4, kv_heads=2, head_dim=128, index_heads=2, index_dim=16, top_k_keys=96, q_chunk=128)
    assert not smd.use_kernels(dims, 256)  # off the chip: XLA's own products
    with pytest.MonkeyPatch.context() as on_tpu:
        on_tpu.setattr(jax, "default_backend", lambda: "tpu")
        assert smd.use_kernels(dims, 256)
        assert not smd.use_kernels(dims, 16)  # too short for a tile
        assert not smd.use_kernels(smd.DecoderDims(head_dim=64), 256)  # no whole lanes
    ks = jax.random.split(jax.random.key(0), 7)
    bf = lambda key, *shape: jax.random.normal(key, shape).astype(jnp.bfloat16)
    args = (
        bf(ks[0], 128, 2, 2, 128), bf(ks[1], 256, 2, 128), bf(ks[2], 256, 2, 128),
        bf(ks[3], 128, 2, 16), bf(ks[4], 256, 16), jax.random.normal(ks[5], (128, 2)),
    )
    weight = jax.random.normal(ks[6], (128, 2, 2, 128))

    def run(kernels):
        def f(*a):
            ctx, kl, n = smd._attention_tile(*a, first_row=128, dims=dims, index_loss=True, kernels=kernels)
            return jnp.sum(ctx.astype(jnp.float32) * weight) + kl, (kl, n)

        return jax.jit(jax.value_and_grad(f, argnums=tuple(range(6)), has_aux=True))(*args)

    ((want, (want_kl, want_n)), want_grads), ((got, (got_kl, got_n)), got_grads) = run(False), run(True)
    assert int(got_n) == int(want_n) >= 128 * 96  # more where index scores tie at the threshold
    assert float(got_kl) == pytest.approx(float(want_kl), rel=2e-3)
    assert float(got) == pytest.approx(float(want), rel=2e-2, abs=0.5)
    for a, b in zip(got_grads, want_grads):
        b = np.asarray(b, np.float32)
        np.testing.assert_allclose(np.asarray(a, np.float32), b, atol=0.02 * np.abs(b).max())


def test_the_decoder_on_the_kernels_is_the_reference(monkeypatch):
    """The whole member on its kernel path (one layer, two query tiles of
    128, head size 128, top-k 64 of 256 positions, bfloat16): the loss
    against the benchmark reference's bfloat16 witness, and the loss and
    every leaf's gradient against the same decoder on XLA's own products
    (which tests/test_sparse_moe_decoder.py holds to the reference leaf
    by leaf) — the indexer's three leaves through the probabilities
    rebuilt from the kernels' log-sum-exp."""
    sys.path.insert(0, BENCH)
    import check  # benchmarks/check.py
    import run as harness  # benchmarks/run.py

    cfg = harness.resolve_cell(
        "keye_vl2_30b_a3b.pbt_pop4_seq8k", True, os.path.join(BENCH, "tests", "data", "rehearse_limits.json")
    )[2]
    cfg = dict(
        cfg, head_dim=128, num_hidden_layers=1, positions=256,
        sa_config=dict(cfg["sa_config"], q_chunk_size=128, kv_chunk_size=128, topk=64),
        data=dict(cfg["data"], positions=256, n_train=2, n_val=1),
    )
    ref = check.Reference(cfg, 2, 1, 11)
    flat, _ = ref.init_member(0)
    params = {}
    for path, leaf in flat.items():
        node = params
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = leaf
    bx, by = ref.data["train_x"][:1], ref.data["train_y"][:1]
    wl = harness.rehearse_workload(cfg)
    dims = smd.DecoderDims(**dict(wl.dims, head_dim=128, layers=1, q_chunk=128, top_k_keys=64, loss_rows=128))
    model = smd.SparseMoEDecoder(dims)

    def loss(p):
        ce, index_loss, _ = model.apply({"params": p}, bx[0], by[0])
        return ce / bx.shape[1] + index_loss

    want, want_grads = jax.jit(jax.value_and_grad(loss))(params)
    monkeypatch.setattr(smd, "use_kernels", lambda dims, positions: True)
    got, got_grads = jax.jit(jax.value_and_grad(loss))(params)
    witness = ref.model.loss(flat, None, None, bx, by, "bf16", cfg)
    assert float(got) == pytest.approx(float(witness), rel=1e-3)
    assert float(got) == pytest.approx(float(want), rel=5e-4)
    got_grads, want_grads = check._flatten(got_grads), check._flatten(want_grads)
    for leaf, g in want_grads.items():
        g = np.asarray(g, np.float32)
        assert np.abs(g).max() > 0, leaf
        np.testing.assert_allclose(
            np.asarray(got_grads[leaf], np.float32), g, rtol=0, atol=0.02 * np.abs(g).max(), err_msg=str(leaf)
        )
