"""Decoder with latent attention, shared experts beside a held share of
sigmoid-routed experts, and leading dense layers — the block of the
DeepSeek-V3 family (Kimi-VL-A3B's language model, Moonlight).

One row of ``T`` tokens at a time, as models/sparse_moe_decoder.py. A
layer, for its input ``x [T, d]`` (RMS eps ``eps``, RoPE theta
``rope_theta``, pairs ``(2i, 2i + 1)``):

    h   = rms(x; g_attn)
    q   = h Wq                  -> [T, H, nope + rope] = [q_nope | q_pe];
                                   q_pe <- RoPE(q_pe)
    c, k_pe = h Wkv_a           -> [T, latent], [T, rope];  c <- rms(c; g_kv);
                                   k_pe <- RoPE(k_pe)  (one head, shared by all)
    k_nope, v = c Wkv_b         -> [T, H, nope], [T, H, v_dim]
    k   = [k_nope | k_pe]       -> [T, H, nope + rope]
    o[t, n] = sum_{s <= t} softmax_s(q[t, n] . k[s, n] / sqrt(nope + rope)) v[s, n]
    x1  = x + o Wo
    h2  = rms(x1; g_mlp)
    a dense layer (the first ``dense_layers``):
          x2 = x1 + (silu(h2 Wg) * (h2 Wu)) Wd                    width dense_width
    an expert layer:
          s  = sigmoid(h2 Wr) over all PUBLISHED experts
          E_t = top experts_per_token of s_t;
          gate = routed_scaling * s_t[E_t] / sum s_t[E_t]
          y  = sum over e in E_t that this chip HOLDS (experts 0 .. held-1)
               of gate_e (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e          width expert_width
             + (silu(h2 Sg) * (h2 Su)) Sd    the shared experts, one SwiGLU
                                             of their summed width, on every chip
          x2 = x1 + y        (what the absent experts would add is left out)

and after the last layer ``rms``, the head over the held slice of the
vocabulary and the next-token cross-entropy. The member's loss is its
mean plus, for every expert layer, the sequence-wise balance loss
``balance_weight * sum_i f_i P_i`` over the published experts, with
``f_i = published / (top * T) * #{t : i in E_t}`` (no gradient) and
``P_i = mean_t s_t[i] / sum_j s_t[j]``.

How it is computed: the expert layer, the router, the norms, RoPE, the
products and the loss head are sparse_moe_decoder's, the one
implementation for both decoders. Every layer is rematerialised
(``nn.remat``) under the same policy (``saved_for_backward``). On a TPU
the attention runs as the splash kernels under a static causal mask
(ops/selected_attention.py ``causal_attention``): no score leaves the
core, the blocks above the diagonal are skipped, the value width stays
its own, and the forward kernel's context and log-sum-exp are saved by
name, so the backward pass runs no forward kernel. Elsewhere, and at
sizes that are no whole tiles, it runs as XLA's own products over query
tiles of ``q_chunk`` rows against the keys up to the tile's last row,
each tile made again in the backward pass. Compute is bfloat16 with
float32 accumulation; parameters, norm statistics, softmax, router
scores and logits are float32.
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from mpi_opt_tpu.models import sparse_moe_decoder as smd
from mpi_opt_tpu.models.sparse_moe_decoder import F32, _dot, held_experts, loss_head, rms_norm, rope, route


@dataclasses.dataclass(frozen=True)
class LatentDims:
    """The sizes of one member: ``dense_layers`` dense layers, then
    ``expert_layers`` expert layers that hold ``experts_held`` of the
    router's ``experts_published`` experts. ``vocab`` is the held slice."""

    vocab: int = 20480
    hidden: int = 2048
    dense_layers: int = 1
    expert_layers: int = 4
    heads: int = 16
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128
    kv_latent: int = 512
    dense_width: int = 11264
    experts_published: int = 64
    experts_held: int = 8
    experts_per_token: int = 6
    expert_width: int = 1408
    shared_width: int = 2816  # two shared experts of expert_width, as one SwiGLU
    # slots an expert in the gathered expert path (sparse_moe_decoder.held_experts)
    expert_capacity: int = 1536
    router_scoring: str = "sigmoid"
    routed_scaling: float = 2.446
    balance_weight: float = 1e-4
    rope_theta: float = 800000.0
    eps: float = 1e-5
    q_chunk: int = 512  # query rows a tile on XLA's own path
    loss_rows: int = 1024

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim


def use_kernels(dims: LatentDims, positions: int) -> bool:
    """Whether the attention runs as TPU kernels: on a TPU, in
    bfloat16, where the rows and the value width are whole tiles and
    the rows whole kernel blocks of ``q_chunk``."""
    from mpi_opt_tpu.ops import selected_attention

    return (
        jax.default_backend() == "tpu"
        and smd.COMPUTE_DTYPE == jnp.bfloat16
        and selected_attention.supported(positions, dims.v_dim)
        and positions % min(dims.q_chunk, positions) == 0
    )


def _causal_tile(qt, k, v, first_row: int):
    """Queries ``first_row ..`` (scaled) against keys ``0 .. K-1``:
    context float32 [R, H, Dv]."""
    r, kk = qt.shape[0], k.shape[0]
    s = _dot("qnd,knd->nqk", qt, k)
    causal = (first_row + jnp.arange(r))[:, None] >= jnp.arange(kk)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    e = jnp.exp(s - jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True)))
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    return _dot("nqk,knd->qnd", p, v)


def causal_attention(q, k, v, dims: LatentDims):
    """(context [T, H * v_dim], bytes saved by name for the backward
    pass) of one row: ``q``, ``k`` [T, H, qk_dim], ``v`` [T, H, v_dim]."""
    t = q.shape[0]
    q = (q * (1.0 / math.sqrt(dims.qk_dim))).astype(q.dtype)
    if use_kernels(dims, t):
        from mpi_opt_tpu.ops.selected_attention import causal_attention as kernel

        heads = lambda a: jnp.transpose(a, (1, 0, 2))
        out = kernel(heads(q), heads(k), heads(v), min(dims.q_chunk, t))
        held = out.nbytes + dims.heads * t * 4  # the context and its log-sum-exp
        return jnp.transpose(out, (1, 0, 2)).reshape(t, -1), held
    step = min(dims.q_chunk, t)
    ctxs = [
        jax.checkpoint(lambda *a, lo=lo: _causal_tile(*a, first_row=lo))(
            q[lo : lo + step], k[: lo + step], v[: lo + step]
        )
        for lo in range(0, t, step)
    ]
    ctx = jnp.concatenate(ctxs, axis=0) if len(ctxs) > 1 else ctxs[0]
    return ctx.reshape(t, -1), 0


def balance_loss(scores, chosen, dims: LatentDims):
    """The sequence-wise balance loss of one row over the published
    experts (without its weight): sum_i f_i P_i."""
    n, t = dims.experts_published, scores.shape[0]
    picked = jnp.sum(jax.nn.one_hot(chosen, n, dtype=F32), axis=(0, 1))
    f = jax.lax.stop_gradient(picked * (n / (dims.experts_per_token * t)))
    p = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=0)
    return jnp.sum(f * p)


def swiglu(h, wg, wu, wd):
    a = jax.nn.silu(_dot("td,df->tf", h, wg)) * _dot("td,df->tf", h, wu)
    return _dot("tf,fd->td", a, wd)


class LatentLayer(nn.Module):
    dims: LatentDims
    dense: bool

    @nn.compact
    def __call__(self, x):
        """x [T, d] -> (x2, the layer's balance loss, float32[2] counts:
        tokens routed to held experts, the fullest held expert's tokens
        (nought in a dense layer), bytes saved by name for the backward
        pass)."""
        m = self.dims
        d, t, n = m.hidden, x.shape[0], m.heads
        lecun, ones = nn.initializers.lecun_normal(), nn.initializers.ones
        p = lambda name, init, *shape: self.param(name, init, shape, F32)
        # creation order is the reference's param_table order
        g_attn = p("g_attn", ones, d)
        wq = p("wq", lecun, d, n * m.qk_dim)
        wkv_a = p("wkv_a", lecun, d, m.kv_latent + m.qk_rope_dim)
        g_kv = p("g_kv", ones, m.kv_latent)
        wkv_b = p("wkv_b", lecun, m.kv_latent, n * (m.qk_nope_dim + m.v_dim))
        wo = p("wo", lecun, n * m.v_dim, d)
        g_mlp = p("g_mlp", ones, d)

        pos = jnp.arange(t)
        cd = smd.COMPUTE_DTYPE
        with jax.named_scope("latent_attention"):
            h = rms_norm(x, g_attn, m.eps)
            q = _dot("td,de->te", h, wq).reshape(t, n, m.qk_dim)
            q_pe = rope(q[..., m.qk_nope_dim :], pos, m.rope_theta, interleaved=True)
            ckv = _dot("td,de->te", h, wkv_a)
            c = rms_norm(ckv[:, : m.kv_latent], g_kv, m.eps)
            k_pe = rope(ckv[:, m.kv_latent :], pos, m.rope_theta, interleaved=True)
            kv = _dot("tc,ce->te", c, wkv_b).reshape(t, n, m.qk_nope_dim + m.v_dim)
            q = jnp.concatenate([q[..., : m.qk_nope_dim], q_pe], axis=-1).astype(cd)
            k_pe = jnp.broadcast_to(k_pe[:, None, :], (t, n, m.qk_rope_dim))
            k = jnp.concatenate([kv[..., : m.qk_nope_dim], k_pe], axis=-1).astype(cd)
            v = kv[..., m.qk_nope_dim :].astype(cd)
            ctx, held = causal_attention(q, k, v, m)
            x1 = x + _dot("te,ed->td", ctx, wo).astype(x.dtype)

        if self.dense:
            wg = p("w_gate", lecun, d, m.dense_width)
            wu = p("w_up", lecun, d, m.dense_width)
            wd = p("w_down", lecun, m.dense_width, d)
            with jax.named_scope("dense_mlp"):
                h2 = rms_norm(x1, g_mlp, m.eps)
                x2 = x1 + swiglu(h2, wg, wu, wd).astype(x.dtype)
            return x2, jnp.zeros((), F32), jnp.zeros((2,), F32), jnp.asarray(held, F32)

        w_router = p("router", lecun, d, m.experts_published)
        wg = p("w_gate", smd._expert_init, m.experts_held, d, m.expert_width)
        wu = p("w_up", smd._expert_init, m.experts_held, d, m.expert_width)
        wd = p("w_down", smd._expert_init, m.experts_held, m.expert_width, d)
        sg = p("shared_gate", lecun, d, m.shared_width)
        su = p("shared_up", lecun, d, m.shared_width)
        sd = p("shared_down", lecun, m.shared_width, d)
        with jax.named_scope("router"):
            h2 = rms_norm(x1, g_mlp, m.eps)
            gates, scores, chosen = route(h2, w_router, m, with_choice=True)
            load = jnp.sum(gates > 0.0, axis=0, dtype=jnp.int32)
            balance = balance_loss(scores, chosen, m)
        y = held_experts(h2, gates, wg, wu, wd, m)
        with jax.named_scope("shared_experts"):
            y = y + swiglu(h2, sg, su, sd)
        x2 = x1 + y.astype(x.dtype)
        counts = jnp.stack([jnp.sum(load).astype(F32), jnp.max(load).astype(F32)])
        return x2, balance, counts, jnp.asarray(held, F32)


class LatentMoEDecoder(nn.Module):
    """One row of tokens -> (sum over positions of the next-token
    cross-entropy over the held vocabulary slice, the weighted balance
    loss summed over the expert layers, float32 [expert layers, 2]
    counts, the bytes every layer saves by name for the backward
    pass)."""

    dims: LatentDims

    @nn.compact
    def __call__(self, tokens, targets):
        m = self.dims
        table = self.param("embed", smd._embed_init, (m.vocab, m.hidden), F32)
        x = table[tokens].astype(smd.COMPUTE_DTYPE)
        layer = nn.remat(LatentLayer, policy=smd.saved_for_backward())
        balance, counts, saved = jnp.zeros((), F32), [], jnp.zeros((), F32)
        for i in range(m.dense_layers + m.expert_layers):
            dense = i < m.dense_layers
            x, b, c, held = layer(m, dense, name=f"layer_{i}")(x)
            saved = saved + held
            if not dense:
                balance = balance + b
                counts.append(c)
        g_out = self.param("g_out", nn.initializers.ones, (m.hidden,), F32)
        head = self.param("head", nn.initializers.lecun_normal(), (m.hidden, m.vocab), F32)
        ce = loss_head(x, targets, g_out, head, m)
        return ce, m.balance_weight * balance, jnp.stack(counts), saved


def forward_macs(dims: LatentDims, positions: int) -> float:
    """Multiply-adds of one row's forward pass at the least work the
    equations need: causal attention over (T + 1) / 2 keys a query, each
    held expert its expected tokens (experts_per_token x held /
    published of them), the router's and the head's products."""
    m, t = dims, positions
    attention = (
        m.hidden * m.heads * m.qk_dim
        + m.hidden * (m.kv_latent + m.qk_rope_dim)
        + m.kv_latent * m.heads * (m.qk_nope_dim + m.v_dim)
        + m.heads * m.v_dim * m.hidden
        + (t + 1) / 2 * m.heads * (m.qk_dim + m.v_dim)
    )
    dense = 3 * m.hidden * m.dense_width
    experts = (
        m.hidden * m.experts_published
        + m.experts_per_token * m.experts_held / m.experts_published * 3 * m.hidden * m.expert_width
        + 3 * m.hidden * m.shared_width
    )
    per_position = (
        (m.dense_layers + m.expert_layers) * attention
        + m.dense_layers * dense
        + m.expert_layers * experts
        + m.hidden * m.vocab
    )
    return t * per_position
