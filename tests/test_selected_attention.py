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
            ctx, kl, n, _ = smd._attention_tile(*a, first_row=128, dims=dims, index_loss=True, kernels=kernels)
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


def _census(jaxpr, counts=None):
    """{kernel name | "selection loop": equations} of a jaxpr and every
    jaxpr inside it (remat bodies, custom-derivative calls, loops): the
    splash kernels by their names' stems, and the selection's bisection
    (``kth_largest``: the one loop of 32 passes the member has)."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        key = None
        if eqn.primitive.name == "pallas_call":
            key = next(s for s in ("fwd", "dq", "dkv") if f"splash_mha_{s}" in str(eqn.params["name"]))
        elif eqn.primitive.name == "scan" and eqn.params["length"] == 32:
            key = "selection loop"
        if key is not None:
            counts[key] = counts.get(key, 0) + 1
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _census(inner, counts)
    return counts


@pytest.mark.parametrize(
    "kernels, dtype, layers",
    # the nest of two remats is one code for both paths: two layers of it on
    # XLA's own products, one where the interpreted kernels make a layer dear
    [(True, jnp.float32, 1), (True, jnp.bfloat16, 1), (False, jnp.float32, 2)],
    ids=["kernels-float32", "kernels-bfloat16", "xla-float32"],
)
def test_a_train_step_runs_each_tiles_kernel_and_selection_once(monkeypatch, kernels, dtype, layers):
    """Layers of two query tiles under ``grad``: the layer's remat
    and the tile's checkpoint both save the selection mask and the
    forward kernel's context and log-sum-exp by name
    (``saved_for_backward``), so the program holds ONE forward kernel
    and ONE selection loop a tile, and one dq and one dkv; with nothing
    saved (both policies ``None``: the nest as it was) it holds three of
    each forward, and gives the same loss and gradients: the saved
    values are the ones it makes again. (Compiled without XLA's excess
    precision, which skips roundings to bfloat16 inside a fusion and so
    rounds two programs of the same arithmetic differently: by up to 7%
    of a small leaf's gradient here.)"""
    monkeypatch.setattr(smd, "COMPUTE_DTYPE", dtype)
    monkeypatch.setattr(smd, "use_kernels", lambda dims, positions: kernels)
    dims = smd.DecoderDims(
        vocab=64, hidden=32, layers=layers, heads=2, kv_heads=1, head_dim=128, index_heads=2, index_dim=16,
        top_k_keys=96, q_chunk=128, experts_published=8, experts_held=2, experts_per_token=2,
        expert_width=16, expert_capacity=0, loss_rows=128,
    )
    model = smd.SparseMoEDecoder(dims)
    tokens = jax.random.randint(jax.random.key(0), (257,), 0, dims.vocab)
    x, y = tokens[:-1], tokens[1:]
    # one program: op by op, the interpreted kernels make the forward pass of an init dear
    params = jax.jit(model.init)(jax.random.key(1), x, y)["params"]

    def loss(p):
        ce, index_loss, counts = model.apply({"params": p}, x, y)
        return ce / x.shape[0] + index_loss, counts[:, 3]

    def trace():
        step = jax.value_and_grad(loss, has_aux=True)
        # traced once: the census is of the program that then runs
        traced = jax.jit(step).trace(params)
        exact = traced.lower().compile(compiler_options={"xla_allow_excess_precision": False})
        return _census(traced.jaxpr.jaxpr), exact(params)

    tiles = dims.layers * (x.shape[0] // dims.q_chunk)
    once = {"selection loop": tiles, **({"fwd": tiles, "dq": tiles, "dkv": tiles} if kernels else {})}
    census, ((got, held), got_grads) = trace()
    assert census == once
    # a tile's mask [128, K] for K = 128, 256 and, from the kernel, 2 heads'
    # context [128, 128] in the compute dtype and log-sum-exp [128] in float32
    a_layer = 128 * (128 + 256) + (2 * 2 * 128 * (128 * jnp.dtype(dtype).itemsize + 4) if kernels else 0)
    assert [float(b) for b in held] == [a_layer] * dims.layers
    monkeypatch.setattr(smd, "saved_for_backward", lambda: None)
    census, ((want, _), want_grads) = trace()
    assert census == {name: n if name in ("dq", "dkv") else 3 * n for name, n in once.items()}
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_grads), jax.tree.leaves(want_grads)):
        w = np.asarray(w, np.float32)
        assert np.abs(w).max() > 0, path
        np.testing.assert_allclose(np.asarray(g, np.float32), w, rtol=0, atol=1e-5 * np.abs(w).max(), err_msg=str(path))
