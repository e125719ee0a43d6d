"""Plain reference of the `keye_vl2_30b_a3b` configuration: the decoder
block of Keye-VL-2.0-30B-A3B (learned sparse attention + mixture of
experts), one chip's share, one member, token rows in, next-token loss
out. Sizes come from the configuration's file, under the keys of the
model's own `config.json`.

For a row of T tokens, x = table[tokens] and, for every layer (all
projections without bias, eps from `rms_norm_eps`):

    h = rms(x; g_attn)
    q = h Wq -> [T, 32, 128], k = h Wk -> [T, 4, 128], v = h Wv likewise;
        rms over the 128 dims of every head of q and k (gains g_q, g_k),
        then RoPE (theta `rope_theta`, pair i = dims (i, i + 64), all
        128 dims). For text tokens the three M-RoPE sections [16, 24,
        24] carry the same position, so this is plain RoPE.
    index scorer, on hI = stop_gradient(h):
        qI = hI WqI -> [T, 16, 64], kI = hI WkI -> [T, 64] (one shared
        key head), w = hI Ww -> [T, 16]; RoPE on qI and kI;
        I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])   for s <= t
        S_t = the min(topk, t + 1) positions s <= t with the largest
        I[t, s]: one selection a query token, for all 32 heads
    a[t, n, s] = softmax over s in S_t of q[t, n] . k[s, n // 8] / sqrt(128)
    x1 = x + concat_n(sum_s a[t, n, s] v[s, n // 8]) Wo
    h2 = rms(x1; g_moe); p = softmax(h2 Wr) over all 128 router outputs;
        E_t = its top 8; c[t, e] = p[t, e] / sum over E_t of p
        y[t] = sum over e in E_t that this chip HOLDS (experts 0 ..
        `num_experts` - 1 of the published 128) of
        c[t, e] (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e
    x2 = x1 + y       (what the absent experts would add is left out)

then rms(x; g_out) and logits = x Whead over the held slice of the
vocabulary. Member loss = mean next-token cross-entropy + sum over
layers of L_I = mean_t KL(stop_gradient(pbar_t) || softmax over S_t of
I[t, :]), pbar_t[s] = (1/32) sum_n a[t, n, s]: the indexer's three
leaves are trained by L_I alone and every other leaf by the
cross-entropy alone; no gradient passes through the selection. Member
score = minus the mean held-out next-token cross-entropy.

Blocked so that it fits: queries in blocks of `sa_config.q_chunk_size`
(each against the keys up to its last row, recomputed in the backward pass),
the head's logits in blocks of rows; neither changes a value. The
selection is taken from each row's sorted index scores; where scores
tie at the last selected one, every tied key is in the set.

`cfg["control"]` (absent in every cell) plants what the comparison has
to catch: {"selection": "off"} attends to every causal key, {"topk": n}
selects n, {"index_loss": "off"} leaves L_I out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# -- data: the recipe of mpi_opt_tpu/data/tokens.py, restated ------------------


def make_data(d: dict) -> dict:
    """Every token has `len(weights)` candidate successors drawn once
    from the seed; a row is a walk over that table, candidate j taken
    with probability weights[j]. x is a row of `positions` + 1 tokens
    without its last, y without its first."""
    vocab, t, seed = d["vocab"], d["positions"], d["seed"]
    rng = np.random.Generator(np.random.Philox(seed))
    table = rng.integers(0, vocab, size=(vocab, len(d["weights"])), dtype=np.int64)
    edges = np.cumsum(np.asarray(d["weights"], np.float64))

    def split(n, salt):
        r = np.random.Generator(np.random.Philox([seed, salt]))
        rows = np.zeros((n, t + 1), np.int32)
        rows[:, 0] = r.integers(0, vocab, size=n)
        u = r.random((n, t))
        for pos in range(t):
            j = np.minimum((u[:, pos, None] > edges).sum(axis=1), len(edges) - 1)
            rows[:, pos + 1] = table[rows[:, pos], j]
        return rows[:, :-1], rows[:, 1:]

    train_x, train_y = split(d["n_train"], 1)
    val_x, val_y = split(d["n_val"], 2)
    return {"train_x": train_x, "train_y": train_y, "val_x": val_x, "val_y": val_y}


# -- parameters ---------------------------------------------------------------------


def param_table(cfg: dict) -> list:
    """[(path, creation counter in its module, kind, shape)]."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    held, width = cfg["num_experts"], cfg["moe_intermediate_size"]
    table = [(("embed",), 1, "embedding", (cfg["vocab_size"], d))]
    for i in range(cfg["num_hidden_layers"]):
        layer = [
            ("g_attn", "ones", (d,)),
            ("wq", "kernel", (d, nq * dh)),
            ("wk", "kernel", (d, nkv * dh)),
            ("wv", "kernel", (d, nkv * dh)),
            ("wo", "kernel", (nq * dh, d)),
            ("g_q", "ones", (dh,)),
            ("g_k", "ones", (dh,)),
            ("wq_index", "kernel", (d, sa["indexer_num_heads"] * sa["indexer_head_dim"])),
            ("wk_index", "kernel", (d, sa["indexer_num_kv_heads"] * sa["indexer_head_dim"])),
            ("ww_index", "kernel", (d, sa["indexer_num_heads"])),
            ("g_moe", "ones", (d,)),
            ("router", "kernel", (d, cfg["published"]["num_experts"])),
            ("w_gate", "expert_kernel", (held, d, width)),
            ("w_up", "expert_kernel", (held, d, width)),
            ("w_down", "expert_kernel", (held, width, d)),
        ]
        table += [((f"layer_{i}", n), c + 1, kind, shape) for c, (n, kind, shape) in enumerate(layer)]
    table.append((("g_out",), 2, "ones", (d,)))
    table.append((("head",), 3, "kernel", (d, cfg["vocab_size"])))
    return table


def init_leaf(kind: str, key, shape):
    if kind == "embedding":  # unit normal rows
        return jax.random.normal(key, shape, jnp.float32)
    if kind == "expert_kernel":  # lecun-normal, every expert its own fan-in (the experts a batch axis)
        init = jax.nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1, batch_axis=(0,)
        )
        return init(key, shape, jnp.float32)
    raise ValueError(f"keye_vl2_30b_a3b has no leaf kind {kind!r}")


# -- arithmetic ----------------------------------------------------------------------


def _mm(eq, a, b, mode):
    """A product in the mode's arithmetic; the result is float32."""
    if mode == "f32":
        return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32), precision=HIGHEST)
    if mode == "fp8":
        a, b = a.astype(jnp.float8_e4m3fn), b.astype(jnp.float8_e4m3fn)
    return jnp.einsum(
        eq, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), preferred_element_type=jnp.float32
    )


def _act(x, mode):
    """Activations are kept in bfloat16 where the configuration says
    the program computes in it."""
    return x.astype(jnp.float32 if mode == "f32" else jnp.bfloat16)


def _rms(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """x [T, ..., D] float32, position = row index."""
    t, half = x.shape[0], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    angle = angle.reshape((t,) + (1,) * (x.ndim - 2) + (half,))
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [lo * jnp.cos(angle) - hi * jnp.sin(angle), hi * jnp.cos(angle) + lo * jnp.sin(angle)],
        axis=-1,
    )


def _selection(index_scores, first, topk):
    """bool [R, T]: S_t for the query rows first .. first + R - 1."""
    r, t = index_scores.shape
    row = first + jnp.arange(r)
    causal = jnp.arange(t)[None, :] <= row[:, None]
    masked = jnp.where(causal, jax.lax.stop_gradient(index_scores), -jnp.inf)
    descending = -jnp.sort(-masked, axis=-1)
    last = jnp.minimum(topk, row + 1) - 1  # rank of the last selected score
    threshold = jnp.take_along_axis(descending, last[:, None], axis=-1)
    return causal & (masked >= threshold)


def _attend_block(q, k, v, qi, ki, w, first, mode, cfg, ctl):
    """One block of queries against the keys up to its last row: (context [R, 32, 128],
    sum over the block's rows of KL(pbar || softmax over S of I))."""
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    r, t = q.shape[0], k.shape[0]
    index_scores = jnp.einsum("rj,rjs->rs", w, jax.nn.relu(_mm("rjd,sd->rjs", qi, ki, mode)), precision=HIGHEST)
    if ctl.get("selection") == "off":
        sel = jnp.arange(t)[None, :] <= (first + jnp.arange(r))[:, None]
    else:
        sel = _selection(index_scores, first, ctl.get("topk", cfg["sa_config"]["topk"]))
    kk, vv = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)  # head n reads n // group
    scores = _mm("rnd,snd->rns", q, kk, mode) / np.sqrt(cfg["head_dim"])
    a = jax.nn.softmax(jnp.where(sel[:, None, :], scores, -jnp.inf), axis=-1)
    context = _mm("rns,snd->rnd", a, vv, mode)
    if ctl.get("index_loss") == "off":
        return context, jnp.zeros((), jnp.float32)
    pbar = jax.lax.stop_gradient(jnp.mean(a, axis=1))
    logq = jax.nn.log_softmax(jnp.where(sel, index_scores, -jnp.inf), axis=-1)
    terms = jnp.where(pbar > 0, pbar * (jnp.log(jnp.where(pbar > 0, pbar, 1.0)) - logq), 0.0)
    return context, jnp.sum(terms)


def _layer(p, i, x, mode, cfg, ctl):
    """(x2, L_I) of layer i for one row x [T, d]."""
    name = f"layer_{i}"
    eps, theta, sa = cfg["rms_norm_eps"], cfg["rope_theta"], cfg["sa_config"]
    t = x.shape[0]
    nq, nkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    h = _act(_rms(x, p[(name, "g_attn")], eps), mode)
    q = _mm("td,de->te", h, p[(name, "wq")], mode).reshape(t, nq, dh)
    k = _mm("td,de->te", h, p[(name, "wk")], mode).reshape(t, nkv, dh)
    v = _act(_mm("td,de->te", h, p[(name, "wv")], mode).reshape(t, nkv, dh), mode)
    q = _act(_rope(_rms(q, p[(name, "g_q")], eps), theta), mode)
    k = _act(_rope(_rms(k, p[(name, "g_k")], eps), theta), mode)
    hi = jax.lax.stop_gradient(h)
    qi = _mm("td,de->te", hi, p[(name, "wq_index")], mode)
    qi = _act(_rope(qi.reshape(t, sa["indexer_num_heads"], sa["indexer_head_dim"]), theta), mode)
    ki = _act(_rope(_mm("td,de->te", hi, p[(name, "wk_index")], mode), theta), mode)
    w = _mm("td,dj->tj", hi, p[(name, "ww_index")], mode)

    block = min(sa["q_chunk_size"], t)
    attend = jax.checkpoint(
        lambda qb, k, v, qib, ki, wb, first: _attend_block(qb, k, v, qib, ki, wb, first, mode, cfg, ctl)
    )
    contexts, kl = [], 0.0
    for first in range(0, t, block):
        rows, seen = slice(first, first + block), min(t, first + block)  # no key lies past the block
        c, kl_b = attend(q[rows], k[:seen], v[:seen], qi[rows], ki[:seen], w[rows], first)
        contexts.append(c)
        kl = kl + kl_b
    context = _act(jnp.concatenate(contexts, axis=0).reshape(t, nq * dh), mode)
    x1 = x + _act(_mm("te,ed->td", context, p[(name, "wo")], mode), mode)

    h2 = _act(_rms(x1, p[(name, "g_moe")], eps), mode)
    return x1 + _act(_experts(p, name, h2, mode, cfg), mode), kl / t


def _experts(p, name, h2, mode, cfg):
    """This chip's part of the expert layer's result for h2 [T, d]:
    the router over all PUBLISHED experts, and of each token's top
    `num_experts_per_tok` the ones held here (the first `num_experts`)."""
    prob = jax.nn.softmax(_mm("td,de->te", h2, p[(name, "router")], mode), axis=-1)
    top, chosen = jax.lax.top_k(prob, cfg["num_experts_per_tok"])
    share = top / jnp.sum(top, axis=-1, keepdims=True)
    y = jnp.zeros(h2.shape, jnp.float32)
    for e in range(cfg["num_experts"]):
        c = jnp.sum(jnp.where(chosen == e, share, 0.0), axis=-1)
        gate = _mm("td,df->tf", h2, p[(name, "w_gate")][e], mode)
        up = _mm("td,df->tf", h2, p[(name, "w_up")][e], mode)
        y = y + c[:, None] * _mm("tf,fd->td", jax.nn.silu(gate) * up, p[(name, "w_down")][e], mode)
    return y


def _row(params, tokens, targets, mode, cfg):
    """(mean next-token cross-entropy, sum over layers of L_I) of one row."""
    ctl = cfg.get("control", {})
    x = _act(params[("embed",)][tokens], mode)
    index_loss = 0.0
    for i in range(cfg["num_hidden_layers"]):
        layer = jax.checkpoint(lambda p, x, i=i: _layer(p, i, x, mode, cfg, ctl))
        x, kl = layer(params, x)
        index_loss = index_loss + kl
    x = _act(_rms(x, params[("g_out",)], cfg["rms_norm_eps"]), mode)

    @jax.checkpoint
    def block_ce(xb, yb, head):
        logits = _mm("td,dv->tv", xb, head, mode)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, yb[:, None], axis=-1))

    rows, t = cfg.get("head_rows", 1024), tokens.shape[0]
    ce = sum(
        block_ce(x[lo : lo + rows], targets[lo : lo + rows], params[("head",)])
        for lo in range(0, t, rows)
    )
    return ce / t, index_loss


def loss(params, hp, key, bx, by, mode, cfg):
    """Mean over the batch's rows of cross-entropy + indexer loss; no
    augmentation, so the member's key is not drawn from."""
    ce, index_loss = jax.lax.map(lambda row: _row(params, row[0], row[1], mode, cfg), (bx, by))
    return jnp.mean(ce) + jnp.mean(index_loss)


def score(params, val_x, val_y, mode, cfg):
    """Higher is better: minus the mean held-out next-token
    cross-entropy (the selection as trained, the indexer's loss apart)."""
    quiet = dict(cfg, control=dict(cfg.get("control", {}), index_loss="off"))
    ce, _ = jax.lax.map(lambda row: _row(params, row[0], row[1], mode, quiet), (val_x, val_y))
    return -jnp.mean(ce)


def journaled_score(value: float, cfg: dict) -> float:
    return value  # the ledger holds what `score` returns
