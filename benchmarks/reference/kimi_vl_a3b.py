"""Plain reference of the `kimi_vl_a3b` configuration: the language
model of Kimi-VL-A3B (latent attention, two shared experts beside
sigmoid-routed experts, a leading dense layer), one chip's share, one
member, token rows in, next-token loss out. Sizes come from the
configuration's file, under the keys of the model's own `config.json`.

For a row of T tokens, x = table[tokens] and, for every layer (all
projections without bias, eps `rms_norm_eps`, RoPE theta `rope_theta`
on pairs of dims (2i, 2i + 1), H = `num_attention_heads`):

    h = rms(x; g_attn)
    q = h Wq -> [T, H, 128 + 64] = [q_nope | q_pe]; q_pe <- RoPE(q_pe)
    c, k_pe = h Wkv_a -> [T, 512], [T, 64]; c <- rms(c; g_kv);
        k_pe <- RoPE(k_pe), one key head shared by all H
    k_nope, v = c Wkv_b -> [T, H, 128], [T, H, 128]; k = [k_nope | k_pe]
    a[t, n, s] = softmax over s <= t of q[t, n] . k[s, n] / sqrt(192)
    x1 = x + concat_n(sum_s a[t, n, s] v[s, n]) Wo
    h2 = rms(x1; g_mlp)
    layer 0 (`first_k_dense_replace`): x2 = x1 + (silu(h2 Wg) * (h2 Wu)) Wd
    layers 1..: s = sigmoid(h2 Wr) over all 64 published experts;
        E_t = its top 6; g[t, e] = 2.446 s[t, e] / sum over E_t of s
        y[t] = sum over e in E_t that this chip HOLDS (experts 0 ..
        `n_routed_experts` - 1) of g[t, e] (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e
             + (silu(h2 Sg) * (h2 Su)) Sd     (the shared experts, width 2 x 1408)
        x2 = x1 + y       (what the absent experts would add is left out)

then rms(x; g_out) and logits = x Whead over the held slice of the
vocabulary. Member loss = mean next-token cross-entropy + sum over the
expert layers of a * sum_i f_i P_i over the 64 published experts
(`seq_aux`; a = `aux_loss_alpha`, an assumed value), f_i = 64 / (6 T) #{t : i in
E_t} without gradient, P_i = mean_t s[t, i] / sum_j s[t, j]. Member
score = minus the mean held-out next-token cross-entropy.

Blocked so that it fits and compiles once a kind of block: queries in
blocks of `q_chunk_rows` (each against every key, the later ones
masked), the dense and shared experts' products in blocks of `mlp_rows`
rows, the head's logits in blocks of `head_rows`, each block made again
in the backward pass; the held experts and the layers of each kind
walked by `lax.scan` over their stacked leaves. None changes a value.

`cfg["control"]` (absent in every cell) plants what the comparison has
to catch: {"shared_experts": "off"} leaves the shared experts out,
{"latent_norm": "off"} leaves out the norm of c, {"scoring": "softmax"}
scores the router by a softmax in place of the sigmoid.
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


def _attention_leaves(cfg):
    d, n = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd, latent = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"]
    return [
        ("g_attn", "ones", (d,)),
        ("wq", "kernel", (d, n * (nope + rope))),
        ("wkv_a", "kernel", (d, latent + rope)),
        ("g_kv", "ones", (latent,)),
        ("wkv_b", "kernel", (latent, n * (nope + vd))),
        ("wo", "kernel", (n * vd, d)),
        ("g_mlp", "ones", (d,)),
    ]


def _expert_leaves(cfg):
    d, width, held = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    shared = cfg["n_shared_experts"] * width
    return [
        ("router", "kernel", (d, cfg["published"]["n_routed_experts"])),
        ("w_gate", "expert_kernel", (held, d, width)),
        ("w_up", "expert_kernel", (held, d, width)),
        ("w_down", "expert_kernel", (held, width, d)),
        ("shared_gate", "kernel", (d, shared)),
        ("shared_up", "kernel", (d, shared)),
        ("shared_down", "kernel", (shared, d)),
    ]


def param_table(cfg: dict) -> list:
    """[(path, creation counter in its module, kind, shape)]."""
    d, dense = cfg["hidden_size"], cfg["intermediate_size"]
    table = [(("embed",), 1, "embedding", (cfg["vocab_size"], d))]
    for i in range(cfg["num_hidden_layers"]):
        if i < cfg["first_k_dense_replace"]:
            mlp = [("w_gate", "kernel", (d, dense)), ("w_up", "kernel", (d, dense)), ("w_down", "kernel", (dense, d))]
        else:
            mlp = _expert_leaves(cfg)
        layer = _attention_leaves(cfg) + mlp
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
    raise ValueError(f"kimi_vl_a3b has no leaf kind {kind!r}")


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
    """x [T, ..., D] float32, position = row index; pair i is dims
    (2i, 2i + 1) at frequency theta ** (-2i / D), left in that order."""
    t, half = x.shape[0], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    angle = angle.reshape((t,) + (1,) * (x.ndim - 2) + (half,))
    even, odd = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def _swiglu(h, wg, wu, wd, mode):
    return _mm("tf,fd->td", jax.nn.silu(_mm("td,df->tf", h, wg, mode)) * _mm("td,df->tf", h, wu, mode), wd, mode)


def _by_rows(fn, rows, *xs):
    """fn over blocks of `rows` rows of each x (the leading axis), one
    block at a time and each made again in the backward pass; the
    blocks' results put back together."""
    t = xs[0].shape[0]
    blocks = tuple(x.reshape((t // rows, rows) + x.shape[1:]) for x in xs)
    out = jax.lax.map(jax.checkpoint(lambda b: fn(*b)), blocks)
    return out.reshape((t,) + out.shape[2:])


def _attention(p, x, mode, cfg, ctl):
    """x1 of one layer: x + the latent attention's output."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    t, n = x.shape[0], cfg["num_attention_heads"]
    nope, rope, latent = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["kv_lora_rank"]
    h = _act(_rms(x, p["g_attn"], eps), mode)
    q = _mm("td,de->te", h, p["wq"], mode).reshape(t, n, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], axis=-1)
    ckv = _mm("td,de->te", h, p["wkv_a"], mode)
    c = ckv[:, :latent] if ctl.get("latent_norm") == "off" else _rms(ckv[:, :latent], p["g_kv"], eps)
    k_pe = _rope(ckv[:, latent:], theta)
    kv = _mm("tc,ce->te", _act(c, mode), p["wkv_b"], mode).reshape(t, n, -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe[:, None, :], (t, n, rope))], axis=-1)
    v = _act(kv[..., nope:], mode)
    q = _act(q / np.sqrt(nope + rope), mode)
    k = _act(k, mode)

    def attend(qb, rows):
        """A block of queries (at positions `rows`) against every key, the later ones masked."""
        scores = _mm("rnd,snd->rns", qb, k, mode)
        causal = jnp.arange(t)[None, :] <= rows[:, None]
        a = jax.nn.softmax(jnp.where(causal[:, None, :], scores, -jnp.inf), axis=-1)
        return _mm("rns,snd->rnd", a, v, mode)

    context = _by_rows(attend, cfg["q_chunk_rows"], q, jnp.arange(t))
    context = _act(context.reshape(t, -1), mode)
    return x + _act(_mm("te,ed->td", context, p["wo"], mode), mode)


def _experts(p, h2, mode, cfg, ctl):
    """(this chip's part of the expert layer's result for h2 [T, d],
    sum_i f_i P_i): the router over all PUBLISHED experts, and of each
    token's top `num_experts_per_tok` the ones held here (the first
    `n_routed_experts`), plus the shared experts."""
    logits = _mm("td,de->te", h2, p["router"], mode)
    if ctl.get("scoring") == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    else:
        s = jax.nn.sigmoid(logits)
    k, published = cfg["num_experts_per_tok"], s.shape[-1]
    top, chosen = jax.lax.top_k(s, k)
    share = cfg["routed_scaling_factor"] * top / jnp.sum(top, axis=-1, keepdims=True)

    @jax.checkpoint
    def one_expert(y, expert):
        e, wg, wu, wd = expert
        g = jnp.sum(jnp.where(chosen == e, share, 0.0), axis=-1)
        return y + g[:, None] * _swiglu(h2, wg, wu, wd, mode), None

    held = (jnp.arange(cfg["n_routed_experts"]), p["w_gate"], p["w_up"], p["w_down"])
    y = jax.lax.scan(one_expert, jnp.zeros(h2.shape, jnp.float32), held)[0]
    if ctl.get("shared_experts") != "off":
        shared = lambda hb: _swiglu(hb, p["shared_gate"], p["shared_up"], p["shared_down"], mode)
        y = y + _by_rows(shared, cfg["mlp_rows"], h2)
    t = s.shape[0]
    f = jnp.sum(jax.nn.one_hot(chosen, published), axis=(0, 1)) * published / (k * t)
    pbar = jnp.mean(s / jnp.sum(s, axis=-1, keepdims=True), axis=0)
    return y, jnp.sum(jax.lax.stop_gradient(f) * pbar)


def _row(params, tokens, targets, mode, cfg):
    """(mean next-token cross-entropy, the weighted balance loss summed
    over the expert layers) of one row."""
    ctl = cfg.get("control", {})
    eps = cfg["rms_norm_eps"]
    x = _act(params[("embed",)][tokens], mode)
    n_dense, n_layers = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]

    def stack(layers):
        leaves = [{path[1]: v for path, v in params.items() if path[0] == f"layer_{i}"} for i in layers]
        return {name: jnp.stack([l[name] for l in leaves]) for name in leaves[0]}

    @jax.checkpoint
    def dense_layer(x, p):
        x1 = _attention(p, x, mode, cfg, ctl)
        h2 = _act(_rms(x1, p["g_mlp"], eps), mode)
        mlp = lambda hb: _swiglu(hb, p["w_gate"], p["w_up"], p["w_down"], mode)
        return x1 + _act(_by_rows(mlp, cfg["mlp_rows"], h2), mode), None

    @jax.checkpoint
    def expert_layer(x, p):
        x1 = _attention(p, x, mode, cfg, ctl)
        h2 = _act(_rms(x1, p["g_mlp"], eps), mode)
        y, balance = _experts(p, h2, mode, cfg, ctl)
        return x1 + _act(y, mode), balance

    x = jax.lax.scan(dense_layer, x, stack(range(n_dense)))[0]
    x, balances = jax.lax.scan(expert_layer, x, stack(range(n_dense, n_layers)))
    x = _act(_rms(x, params[("g_out",)], eps), mode)

    def block_ce(xb, yb):
        logp = jax.nn.log_softmax(_mm("td,dv->tv", xb, params[("head",)], mode), axis=-1)
        return -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]

    ce = jnp.sum(_by_rows(block_ce, cfg["head_rows"], x, targets))
    return ce / tokens.shape[0], cfg["aux_loss_alpha"] * jnp.sum(balances)


def loss(params, hp, key, bx, by, mode, cfg):
    """Mean over the batch's rows of cross-entropy + balance loss; no
    augmentation, so the member's key is not drawn from."""
    ce, balance = jax.lax.map(lambda row: _row(params, row[0], row[1], mode, cfg), (bx, by))
    return jnp.mean(ce) + jnp.mean(balance)


def score(params, val_x, val_y, mode, cfg):
    """Higher is better: minus the mean held-out next-token cross-entropy."""
    ce, _ = jax.lax.map(lambda row: _row(params, row[0], row[1], mode, cfg), (val_x, val_y))
    return -jnp.mean(ce)


def journaled_score(value: float, cfg: dict) -> float:
    return value  # the ledger holds what `score` returns
