"""Decoder with learned sparse attention and a held share of a mixture
of experts — the first population member that reads token rows.

One row of ``T`` tokens at a time (a member's batch is a handful of
rows). A layer, for its input ``x [T, d]``:

    h  = rms(x; g_attn)
    q, k, v = h Wq, h Wk, h Wv            32 / 4 / 4 heads of 128 (GQA);
                                          per-head rms on q and k, RoPE
    index scorer, on stop_gradient(h):    qI = h WqI (16 heads of 64),
        kI = h WkI (one shared head), w = h Ww; RoPE on qI, kI;
        I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])       (s <= t)
        S_t = the min(top_k, t + 1) positions s <= t of largest I[t, s]
    a[t, n, :] = softmax over S_t of q[t, n] . k[s, n // 8] / sqrt(128)
    x1 = x + (a v) Wo
    h2 = rms(x1; g_moe);  p = softmax(h2 Wr) over ALL published experts,
        E_t its top 8, c = p / sum over E_t;  y[t] = sum over e in E_t
        that this chip HOLDS (experts 0 .. held-1) of
        c[t, e] (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e
    x2 = x1 + y          (what the absent experts would add is left out)

and after the last layer ``rms`` and the head over the held slice of the
vocabulary. The member's loss is the mean next-token cross-entropy plus,
for every layer, ``L_I = mean_t KL(stop_gradient(pbar_t) || softmax over
S_t of I[t, :])`` with ``pbar`` the head-mean of ``a``: with the two
stop-gradients the indexer's three leaves train on ``L_I`` alone and
every other leaf on the cross-entropy alone; no gradient passes through
the selection.

For text tokens the three M-RoPE sections carry the same position, so
the rotation is plain RoPE (half-split pairing over all dims).

How it is computed. Query tiles of ``q_chunk`` rows, each against the
keys up to its last row only (the causal half is never formed). A
layer is rematerialised (``nn.remat``) and so is every tile inside it
(``jax.checkpoint``), so that no score is ever stored; both save, by
name, the three values that cost most to make again: the tile's
selection mask and, on the kernel path, the forward kernel's context
and log-sum-exp (``saved_for_backward``). So the selection and the
forward kernel run once a train step, the backward pass makes only
the index scores and the probabilities again, and a member-step holds
``saved_residual_mib`` from its forward to its backward. The selection
is a threshold mask: the k-th largest index score of a row is found
exactly by a bitwise bisection over the float's order-preserving
integer image (32 counting passes over the tile; no sort, no gather),
and ``I >= threshold`` is the set — where index scores tie at the
threshold it holds every tied key. On a
TPU the attention over that mask runs as kernels
(ops/selected_attention.py: no score leaves the core; the head-mean
probabilities the indexer's loss needs are rebuilt from the kernels'
log-sum-exp), elsewhere and at sizes that are no whole tiles as XLA's
own products. The expert layer computes each held expert for the
tokens routed to it: tokens are ranked within their expert and gathered
into ``expert_capacity`` slots an expert; a layer whose fullest expert
overflows the slots computes every held expert for every token instead
(``lax.cond``), so no token is ever dropped. Compute is bfloat16 with
float32 accumulation; parameters, softmax and norm statistics, index
scores, router probabilities and logits are float32.
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

F32 = jnp.float32
# operands of every product and the activations between them. Tests set
# float32 (products at `highest`) to hold the program to the benchmark's
# float32 reference leaf by leaf
COMPUTE_DTYPE = jnp.bfloat16


@dataclasses.dataclass(frozen=True)
class DecoderDims:
    """The sizes of one member. ``experts_held`` of ``experts_published``
    live here (experts ``0 .. held-1``); the router keeps its published
    width and its experts a token. ``vocab`` is the held slice."""

    vocab: int = 18992
    hidden: int = 2048
    layers: int = 4
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    index_heads: int = 16
    index_dim: int = 64
    top_k_keys: int = 2048
    q_chunk: int = 512
    experts_published: int = 128
    experts_held: int = 8
    experts_per_token: int = 8
    expert_width: int = 768
    # slots an expert in the gathered expert path; 0 computes every held
    # expert for every token (small sizes, and the overflow path)
    expert_capacity: int = 1024
    rope_theta: float = 1e7
    eps: float = 1e-6
    loss_rows: int = 1024  # rows of the head's logits formed at once


def _dot(eq: str, a, b):
    """Operands in the compute dtype, float32 result."""
    dt = COMPUTE_DTYPE
    precision = jax.lax.Precision.HIGHEST if dt == F32 else None
    return jnp.einsum(eq, a.astype(dt), b.astype(dt), preferred_element_type=F32, precision=precision)


def rms_norm(x, gain, eps):
    x32 = x.astype(F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps) * gain
    return y.astype(x.dtype)


def rope(x, positions, theta):
    """Rotate ``x [T, ..., D]`` by its positions: pair ``i`` is dims
    ``(i, i + D/2)``, frequency ``theta ** (-i / (D/2))``."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(F32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


# -- the selection -------------------------------------------------------------


def order_key(x):
    """float32 -> uint32 with the same order (larger float, larger key)."""
    bits = jax.lax.bitcast_convert_type(x.astype(F32), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(0x80000000))


def kth_largest(keys, k):
    """Per row of ``keys`` (uint32 [R, K]) the ``k[r]``-th largest value,
    exactly: the largest ``v`` with ``count(keys >= v) >= k``, built bit
    by bit from the top (32 counting passes, no sort)."""

    def body(i, prefix):
        cand = prefix | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        count = jnp.sum(keys >= cand[:, None], axis=-1, dtype=jnp.int32)
        return jnp.where(count >= k, cand, prefix)

    return jax.lax.fori_loop(0, 32, body, jnp.zeros(keys.shape[:1], jnp.uint32))


def select_keys(index_scores, first_row: int, top_k: int):
    """bool [R, K]: for query row ``first_row + r`` the ``min(top_k,
    t + 1)`` keys ``s <= t`` of largest index score (every key tied with
    the last of them too)."""
    r, k = index_scores.shape
    t = first_row + jnp.arange(r)
    causal = t[:, None] >= jnp.arange(k)[None, :]
    keys = jnp.where(causal, order_key(jax.lax.stop_gradient(index_scores)), jnp.uint32(0))
    threshold = kth_largest(keys, jnp.minimum(top_k, t + 1))
    return (keys >= threshold[:, None]) & causal


# -- one query tile of the sparse attention ----------------------------------------

# the tile's selection mask, for a remat policy to save
SELECTION = "attention_selection"


def saved_for_backward():
    """The policy of both remats (the layer's, the query tile's): save
    the selection mask and the forward kernel's context and log-sum-exp
    (on XLA's own path only the mask exists), make everything else
    again."""
    from mpi_opt_tpu.ops.selected_attention import RESIDUALS

    return jax.checkpoint_policies.save_only_these_names(SELECTION, RESIDUALS)


def use_kernels(dims: DecoderDims, positions: int) -> bool:
    """Whether the attention over the selection runs as TPU kernels
    (ops/selected_attention.py): on a TPU, where the shapes are whole
    tiles; else as XLA's own products."""
    from mpi_opt_tpu.ops import selected_attention

    return (
        jax.default_backend() == "tpu"
        and COMPUTE_DTYPE == jnp.bfloat16
        and selected_attention.supported(min(dims.q_chunk, positions), dims.head_dim)
    )


def _attention_tile(qt, k, v, qi, ki, w, first_row: int, dims: DecoderDims, index_loss: bool, kernels: bool):
    """Queries ``first_row ..`` against keys ``0 .. K-1`` (``K`` = one
    past the tile's last row): (context [R, kv, group, D], the
    tile's sum over rows of KL(pbar || softmax over S of I), the
    selected keys counted, the bytes of the values named for
    ``saved_for_backward``)."""
    scale = 1.0 / math.sqrt(dims.head_dim)
    with jax.named_scope("indexer"):
        # I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])
        dots = _dot("qjd,kd->jqk", qi, ki)
        scores_i = jnp.sum(jax.nn.relu(dots) * jnp.transpose(w)[:, :, None], axis=0)
        sel = checkpoint_name(select_keys(scores_i, first_row, dims.top_k_keys), SELECTION)
    held = sel.nbytes
    if kernels:
        from mpi_opt_tpu.ops.selected_attention import masked_attention

        with jax.named_scope("attention"):
            r, kv, group, d = qt.shape
            qh = jnp.transpose((qt * scale).astype(qt.dtype).reshape(r, kv * group, d), (1, 0, 2))
            kh, vh = jnp.transpose(k, (1, 0, 2)), jnp.transpose(v, (1, 0, 2))
            out, lse = masked_attention(qh, kh, vh, sel, min(dims.q_chunk, r))  # named there
            held += out.nbytes + lse.nbytes
            ctx = jnp.transpose(out, (1, 0, 2)).reshape(r, kv, group, d).astype(COMPUTE_DTYPE)
        if index_loss:
            with jax.named_scope("indexer"):
                # the kernels keep no probability: every head's are rebuilt from
                # its row's log-sum-exp for their mean over the heads
                qh, kh, lse = jax.lax.stop_gradient((qh, kh, lse))
                s = _dot("gnqd,gkd->gnqk", qh.reshape(kv, group, r, d), kh)
                p = jnp.exp(s - lse.reshape(kv, group, r, 1))
                pbar = jnp.where(sel, jnp.mean(p, axis=(0, 1)), 0.0)
    else:
        with jax.named_scope("attention"):
            s = _dot("qgnd,kgd->gnqk", qt, k) * scale
            s = jnp.where(sel[None, None], s, -jnp.inf)
            e = jnp.exp(s - jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True)))
            p = e / jnp.sum(e, axis=-1, keepdims=True)
            ctx = _dot("gnqk,kgd->qgnd", p, v).astype(COMPUTE_DTYPE)
            pbar = jax.lax.stop_gradient(jnp.mean(p, axis=(0, 1)))
    with jax.named_scope("indexer"):
        n_sel = jnp.sum(sel, dtype=jnp.int32)
        held = jnp.asarray(held, F32)
        if not index_loss:
            return ctx, jnp.zeros((), F32), n_sel, held
        logq = jax.nn.log_softmax(jnp.where(sel, scores_i, -jnp.inf), axis=-1)
        kl = jnp.sum(jax.scipy.special.xlogy(pbar, pbar)) - jnp.sum(
            jnp.where(sel, pbar * logq, 0.0)
        )
        return ctx, kl, n_sel, held


def sparse_attention(q, k, v, qi, ki, w, dims: DecoderDims, index_loss: bool):
    """(context [T, heads * D], sum over rows of the indexer's KL,
    selected keys counted, bytes saved for the backward pass) of one
    row's layer. Tiles of ``q_chunk`` queries, each made again in the
    backward pass but for what ``saved_for_backward`` names."""
    t = q.shape[0]
    step = min(dims.q_chunk, t)
    kernels = use_kernels(dims, t)
    ctxs, kl, n_sel, held = [], jnp.zeros((), F32), jnp.zeros((), jnp.int32), jnp.zeros((), F32)
    policy = saved_for_backward()
    for lo in range(0, t, step):
        hi = min(t, lo + step)
        tile = jax.checkpoint(
            lambda *a, lo=lo: _attention_tile(
                *a, first_row=lo, dims=dims, index_loss=index_loss, kernels=kernels
            ),
            policy=policy,
        )
        c, kl_t, n_t, held_t = tile(q[lo:hi], k[:hi], v[:hi], qi[lo:hi], ki[:hi], w[lo:hi])
        ctxs.append(c)
        kl, n_sel, held = kl + kl_t, n_sel + n_t, held + held_t
    ctx = jnp.concatenate(ctxs, axis=0) if len(ctxs) > 1 else ctxs[0]
    return ctx.reshape(t, dims.heads * dims.head_dim), kl, n_sel, held


# -- the held experts ----------------------------------------------------------------


def route(h2, w_router, dims: DecoderDims):
    """float32 [T, held]: each token's combine weight for every held
    expert (0 where the expert is not among the token's top choices).
    Softmax over all PUBLISHED experts, top ``experts_per_token``,
    weights renormalised over the chosen."""
    probs = jax.nn.softmax(_dot("td,de->te", h2, w_router), axis=-1)
    top, idx = jax.lax.top_k(probs, dims.experts_per_token)
    c = top / jnp.sum(top, axis=-1, keepdims=True)
    held = jnp.arange(dims.experts_held)
    return jnp.sum(jnp.where(idx[:, :, None] == held, c[:, :, None], 0.0), axis=1)


def _experts_every_token(h2, gates, wg, wu, wd):
    """Every held expert for every token, weighted by the gate (0 for a
    token not routed to it): exact whatever the router did. One expert
    at a time, recomputed in the backward pass: this is the path of
    small sizes and of an overflowing layer, and must not cost the
    gathered path its memory."""

    @jax.checkpoint
    def one(y, expert):
        g, wg_e, wu_e, wd_e = expert
        a = jax.nn.silu(_dot("td,df->tf", h2, wg_e)) * _dot("td,df->tf", h2, wu_e)
        return y + _dot("tf,fd->td", a * g[:, None], wd_e), None

    y0 = jnp.zeros((h2.shape[0], wd.shape[-1]), F32)
    return jax.lax.scan(one, y0, (jnp.transpose(gates), wg, wu, wd))[0]


def _experts_gathered(h2, gates, wg, wu, wd, dims):
    """Each held expert for the tokens routed to it, gathered into
    ``capacity`` slots (the caller has checked that no expert has more):
    slot ``c`` of expert ``e`` is its ``c``-th routed token in row
    order."""
    (t, held), capacity = gates.shape, dims.expert_capacity
    with jax.named_scope("router"):  # dispatch
        routed = jnp.transpose(gates > 0.0)  # [held, T]
        # routed tokens first, in row order (a stable sort of the flags)
        order = jnp.argsort(~routed, axis=-1, stable=True)[:, :capacity]
        filled = jnp.take_along_axis(routed, order, axis=-1)
        weight = jnp.where(filled, jnp.take_along_axis(jnp.transpose(gates), order, axis=-1), 0.0)
        x = h2[order]  # [held, capacity, d]
    with jax.named_scope("experts"):
        a = jax.nn.silu(_dot("ecd,edf->ecf", x, wg)) * _dot("ecd,edf->ecf", x, wu)
        y = _dot("ecf,efd->ecd", a, wd)
    with jax.named_scope("router"):  # combine
        y = (y * weight[:, :, None]).reshape(held * capacity, -1)
        return jnp.zeros((t, h2.shape[-1]), F32).at[order.reshape(-1)].add(y)


def held_experts(h2, gates, wg, wu, wd, dims: DecoderDims):
    """sum over held e of gates[t, e] (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e,
    float32 [T, d]."""
    capacity = dims.expert_capacity

    def every_token():
        with jax.named_scope("experts"):
            return _experts_every_token(h2, gates, wg, wu, wd)

    if capacity <= 0 or capacity >= h2.shape[0]:
        return every_token()
    with jax.named_scope("router"):
        fits = jnp.max(jnp.sum(gates > 0.0, axis=0)) <= capacity
    return jax.lax.cond(
        fits, lambda: _experts_gathered(h2, gates, wg, wu, wd, dims), every_token
    )


# -- the modules ------------------------------------------------------------------------


# every expert its own fan-in scaling: lecun-normal with the experts a batch axis
_expert_init = nn.initializers.variance_scaling(
    1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1, batch_axis=(0,)
)


def _embed_init(key, shape, dtype=F32):
    return jax.random.normal(key, shape, dtype)


class DecoderLayer(nn.Module):
    dims: DecoderDims
    index_loss: bool = True

    @nn.compact
    def __call__(self, x):
        """x [T, d] -> (x2, the layer's indexer loss L_I,
        float32[4] counts: selected keys, tokens routed to held experts,
        the fullest held expert's tokens, bytes saved by name for the
        backward pass)."""
        m = self.dims
        d, t = m.hidden, x.shape[0]
        group = m.heads // m.kv_heads
        lecun, ones = nn.initializers.lecun_normal(), nn.initializers.ones
        p = lambda name, init, *shape: self.param(name, init, shape, F32)
        # creation order is the reference's param_table order
        g_attn = p("g_attn", ones, d)
        wq = p("wq", lecun, d, m.heads * m.head_dim)
        wk = p("wk", lecun, d, m.kv_heads * m.head_dim)
        wv = p("wv", lecun, d, m.kv_heads * m.head_dim)
        wo = p("wo", lecun, m.heads * m.head_dim, d)
        g_q = p("g_q", ones, m.head_dim)
        g_k = p("g_k", ones, m.head_dim)
        wqi = p("wq_index", lecun, d, m.index_heads * m.index_dim)
        wki = p("wk_index", lecun, d, m.index_dim)
        wwi = p("ww_index", lecun, d, m.index_heads)
        g_moe = p("g_moe", ones, d)
        w_router = p("router", lecun, d, m.experts_published)
        wg = p("w_gate", _expert_init, m.experts_held, d, m.expert_width)
        wu = p("w_up", _expert_init, m.experts_held, d, m.expert_width)
        wd = p("w_down", _expert_init, m.experts_held, m.expert_width, d)

        pos = jnp.arange(t)
        with jax.named_scope("attention"):
            h = rms_norm(x, g_attn, m.eps)
            q = _dot("td,de->te", h, wq).reshape(t, m.kv_heads, group, m.head_dim)
            k = _dot("td,de->te", h, wk).reshape(t, m.kv_heads, m.head_dim)
            v = _dot("td,de->te", h, wv).reshape(t, m.kv_heads, m.head_dim).astype(COMPUTE_DTYPE)
            q = rope(rms_norm(q, g_q, m.eps), pos, m.rope_theta).astype(COMPUTE_DTYPE)
            k = rope(rms_norm(k, g_k, m.eps), pos, m.rope_theta).astype(COMPUTE_DTYPE)
        with jax.named_scope("indexer"):
            hi = jax.lax.stop_gradient(h)
            qi = _dot("td,de->te", hi, wqi).reshape(t, m.index_heads, m.index_dim)
            qi = rope(qi, pos, m.rope_theta).astype(COMPUTE_DTYPE)
            ki = rope(_dot("td,de->te", hi, wki), pos, m.rope_theta).astype(COMPUTE_DTYPE)
            w = _dot("td,dj->tj", hi, wwi)
        ctx, kl, n_sel, held = sparse_attention(q, k, v, qi, ki, w, m, self.index_loss)
        with jax.named_scope("attention"):
            x1 = x + _dot("te,ed->td", ctx, wo).astype(x.dtype)
        with jax.named_scope("router"):
            h2 = rms_norm(x1, g_moe, m.eps)
            gates = route(h2, w_router, m)
            load = jnp.sum(gates > 0.0, axis=0, dtype=jnp.int32)
        y = held_experts(h2, gates, wg, wu, wd, m)
        x2 = x1 + y.astype(x.dtype)
        counts = jnp.stack([n_sel.astype(F32), jnp.sum(load).astype(F32), jnp.max(load).astype(F32), held])
        return x2, kl / t, counts


class SparseMoEDecoder(nn.Module):
    """One row of tokens -> (sum over positions of the next-token
    cross-entropy over the held vocabulary slice, sum over layers of the
    indexer's loss, float32 [layers, 4] counts)."""

    dims: DecoderDims
    index_loss: bool = True  # evaluation needs the selection, not its loss

    @nn.compact
    def __call__(self, tokens, targets):
        m = self.dims
        table = self.param("embed", _embed_init, (m.vocab, m.hidden), F32)
        x = table[tokens].astype(COMPUTE_DTYPE)
        layer = nn.remat(DecoderLayer, policy=saved_for_backward())
        index_loss, counts = jnp.zeros((), F32), []
        for i in range(m.layers):
            x, kl, c = layer(m, self.index_loss, name=f"layer_{i}")(x)
            index_loss = index_loss + kl
            counts.append(c)
        g_out = self.param("g_out", nn.initializers.ones, (m.hidden,), F32)
        head = self.param("head", nn.initializers.lecun_normal(), (m.hidden, m.vocab), F32)
        with jax.named_scope("loss_head"):
            x = rms_norm(x, g_out, m.eps)

            @jax.checkpoint
            def block(xb, yb):
                logits = _dot("td,dv->tv", xb, head)
                picked = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
                return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

            t = tokens.shape[0]
            ce = sum(
                block(x[lo : lo + m.loss_rows], targets[lo : lo + m.loss_rows])
                for lo in range(0, t, m.loss_rows)
            )
        return ce, index_loss, jnp.stack(counts)
