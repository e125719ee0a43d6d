"""Plain reference of one fused-PBT member, shared by the configurations.

What a sweep does to ONE member, written out in straightforward
`jax.numpy`: the seeded data, the member's initial weights, the
minibatch and augmentation draws, forward, loss, backward, the
SGD+momentum update with the member's own hyperparameters, the
evaluation that scores it, and the PBT exploit/explore that decides the
next generation's rows. No `vmap`, no chunking, no donation, float32 at
`highest` matmul precision. Nothing of `mpi_opt_tpu` is imported: the
key chain, the data recipe and the layer equations are restated here
(the data recipe is a copy of `mpi_opt_tpu/data/synthetic.py`, see
PERF.md Open questions).

A configuration's own file (`<configuration>.py`, beside this one) gives
`param_table(cfg)` (name path, kind, shape of every leaf, in creation
order) and `apply(params, x, mode, cfg)`; everything else is here.

`mode` is the arithmetic the forward/backward runs in:
  "f32"   float32 operands, `highest` precision (THE reference)
  "bf16"  operands cast to bfloat16, as the configuration states the
          program computes (a witness, not the reference)
  "fp8"   operands rounded to float8_e4m3fn first (the control: the
          nearest precision below bfloat16)
`store` is the dtype parameters and momentum are kept in between steps:
float32 as the configuration states, or bfloat16 (the second control).
"""

from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# -- data (copy of the program's seeded generator) ---------------------------


def _upsample_bilinear(x, h, w):
    n, ch, cw, c = x.shape
    ys = np.linspace(0, ch - 1, h)
    xs = np.linspace(0, cw - 1, w)
    y0 = np.clip(np.floor(ys).astype(int), 0, ch - 2)
    x0 = np.clip(np.floor(xs).astype(int), 0, cw - 2)
    wy = (ys - y0)[None, :, None, None]
    wx = (xs - x0)[None, None, :, None]
    a = x[:, y0][:, :, x0]
    b = x[:, y0 + 1][:, :, x0]
    cc = x[:, y0][:, :, x0 + 1]
    d = x[:, y0 + 1][:, :, x0 + 1]
    return (
        a * (1 - wy) * (1 - wx) + b * wy * (1 - wx) + cc * (1 - wy) * wx + d * wy * wx
    ).astype(np.float32)


def make_data(d: dict) -> dict:
    """The configuration's `data` group -> train/val arrays (numpy)."""
    h, w, c, k = d["h"], d["w"], d["c"], d["n_classes"]
    seed, coarse, protos = d["seed"], d["coarse"], d["protos"]
    rng = np.random.Generator(np.random.Philox(seed))
    up = lambda z: _upsample_bilinear(z.astype(np.float32), h, w)
    common = up(rng.normal(size=(1, coarse, coarse, c)))
    class_sig = up(rng.normal(size=(k, coarse, coarse, c)))
    proto_var = up(rng.normal(size=(k * protos, coarse, coarse, c))).reshape(
        k, protos, h, w, c
    )
    templates = common[:, None] + d["delta"] * class_sig[:, None] + 0.5 * proto_var

    def split(n, salt):
        r = np.random.Generator(np.random.Philox([seed, salt]))
        y = r.integers(0, k, size=n)
        p = r.integers(0, protos, size=n)
        x = templates[y, p]
        x = x + r.normal(scale=d["noise"], size=x.shape).astype(np.float32)
        x = x * (1.0 + 0.1 * r.normal(size=(n, 1, 1, 1)).astype(np.float32))
        x = (x - x.mean()) / (x.std() + 1e-8)
        if d["label_noise"] > 0.0:
            flip = r.random(n) < d["label_noise"]
            y = np.where(flip, r.integers(0, k, size=n), y)
        return x.astype(np.float32), y.astype(np.int32)

    train_x, train_y = split(d["n_train"], 1)
    val_x, val_y = split(d["n_val"], 2)
    return {"train_x": train_x, "train_y": train_y, "val_x": val_x, "val_y": val_y}


# -- hyperparameter space ----------------------------------------------------


def from_unit(space: list, u):
    """Unit-cube rows [..., d] -> {name: values}, float32 throughout."""
    u = jnp.asarray(u, jnp.float32)
    out = {}
    for i, dom in enumerate(space):
        lo, hi = dom["low"], dom["high"]
        if dom["kind"] == "LogUniform":
            out[dom["name"]] = jnp.exp(np.log(lo) + (np.log(hi) - np.log(lo)) * u[..., i])
        elif dom["kind"] == "Uniform":
            out[dom["name"]] = lo + (hi - lo) * u[..., i]
        else:
            raise ValueError(f"reference has no domain kind {dom['kind']!r}")
    return out


def to_unit(space: list, name: str, value: float) -> float:
    """One hyperparameter's value -> its unit-cube coordinate (host)."""
    dom = next(d for d in space if d["name"] == name)
    lo, hi = dom["low"], dom["high"]
    if dom["kind"] == "LogUniform":
        return float((np.log(value) - np.log(lo)) / (np.log(hi) - np.log(lo)))
    return float((value - lo) / (hi - lo))


# -- the key chain -----------------------------------------------------------


def sweep_keys(seed: int):
    """(k_init, k_unit, k_run) as the sweep derives them from --seed."""
    return jax.random.split(jax.random.key(seed), 3)


def generation_keys(k_run, g: int):
    """(k_train, k_pbt) of generation g (0-based) on the carried chain."""
    k = k_run
    for _ in range(g + 1):
        k, k_train, k_pbt = jax.random.split(k, 3)
    return k_train, k_pbt


def param_key(rng, path: tuple, count: int):
    """The key the model library (flax linen) hands the `count`-th
    parameter created in the module at `path`: the SHA-1 of the module
    names and the counter, folded into the member's init key."""
    m = hashlib.sha1()
    for x in path + (count,):
        if isinstance(x, str):
            m.update(x.encode("utf-8"))
        else:
            m.update(x.to_bytes((x.bit_length() + 7) // 8, byteorder="big"))
    h = int.from_bytes(m.digest()[:4], byteorder="big")
    return jax.random.fold_in(rng, jnp.uint32(h))


def init_member(table: list, k_init, population: int, member: int) -> dict:
    """Member `member`'s initial parameters: {path: float32 array}."""
    rng = jax.random.split(k_init, population)[member]
    lecun = jax.nn.initializers.lecun_normal()
    params = {}
    for path, count, kind, shape in table:
        if kind == "kernel":
            params[path] = lecun(param_key(rng, path[:-1], count), shape, jnp.float32)
        elif kind == "ones":
            params[path] = jnp.ones(shape, jnp.float32)
        else:
            params[path] = jnp.zeros(shape, jnp.float32)
    return params


# -- layers ------------------------------------------------------------------


def _operands(a, b, mode):
    if mode == "f32":
        return a.astype(jnp.float32), b.astype(jnp.float32), HIGHEST, jnp.float32
    if mode == "fp8":
        a = a.astype(jnp.float8_e4m3fn)
        b = b.astype(jnp.float8_e4m3fn)
    return a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), None, jnp.bfloat16


def conv(x, kernel, mode, stride=1):
    x, kernel, prec, dt = _operands(x, kernel, mode)
    y = jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec,
    )
    return y.astype(dt)


def dense(x, kernel, mode):
    x, kernel, prec, dt = _operands(x, kernel, mode)
    return jnp.dot(x, kernel, precision=prec).astype(dt)


def act_dtype(mode):
    return jnp.float32 if mode == "f32" else jnp.bfloat16


def group_norm(x, scale, bias, groups, mode, eps=1e-6):
    """GroupNorm over (H, W, C/groups); statistics in float32,
    Var = E[x^2] - E[x]^2 clipped at 0, as the model library computes."""
    n, h, w, c = x.shape
    g = x.astype(jnp.float32).reshape(n, h, w, groups, c // groups)
    mean = g.mean(axis=(1, 2, 4))
    var = jnp.maximum((g * g).mean(axis=(1, 2, 4)) - mean * mean, 0.0)
    mean = jnp.repeat(mean, c // groups, axis=-1)[:, None, None, :]
    var = jnp.repeat(var, c // groups, axis=-1)[:, None, None, :]
    y = (x - mean) * (jax.lax.rsqrt(var + eps) * scale.reshape(1, 1, 1, c))
    return (y + bias.reshape(1, 1, 1, c)).astype(act_dtype(mode))


def max_pool2(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
    )


# -- one member's generation -------------------------------------------------


def augment(key, x, flip_prob, shift):
    k_flip, k_dy, k_dx = jax.random.split(key, 3)
    do_flip = jax.random.bernoulli(k_flip, flip_prob, (x.shape[0], 1, 1, 1))
    x = jnp.where(do_flip, x[:, :, ::-1, :], x)
    max_s = jnp.maximum(shift, 0.0)
    dy = jnp.round(jax.random.uniform(k_dy, (), minval=-max_s, maxval=max_s)).astype(jnp.int32)
    dx = jnp.round(jax.random.uniform(k_dx, (), minval=-max_s, maxval=max_s)).astype(jnp.int32)
    return jnp.roll(x, (dy, dx), axis=(1, 2))


def make_member_programs(
    apply, cfg: dict, population: int, steps: int, batch: int, keep_rows: int = 0
):
    """(train_generation, score) for one member of a `population`-member
    sweep. `train_generation(params, mom, hp, k_train, member, train_x,
    train_y, mode, store)` runs the generation's `steps` steps on the
    shared minibatch sequence with the member's own augmentation keys;
    `score(params, val_x, val_y, mode)` counts correct validation rows.
    `keep_rows` plants a fault for the control tests: only that many
    rows of each drawn minibatch are used, the mean taken over them.
    """

    def loss_fn(params, hp, key, bx, by, mode):
        bx = augment(key, bx, hp["flip_prob"], hp["shift"])
        logits = apply(params, bx, mode, cfg).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, by[:, None], axis=1))

    @functools.partial(jax.jit, static_argnames=("mode", "store"))
    def train_generation(params, mom, hp, k_train, member, train_x, train_y, mode, store):
        sdt = jnp.dtype(store)

        def one_step(carry, _):
            p, m, k = carry
            k, k_batch, k_aug = jax.random.split(k, 3)
            idx = jax.random.randint(k_batch, (batch,), 0, train_x.shape[0])
            bx, by = train_x[idx], train_y[idx]
            if keep_rows:
                bx, by = bx[:keep_rows], by[:keep_rows]
            key = jax.random.split(k_aug, population)[member]
            p32 = {n: v.astype(jnp.float32) for n, v in p.items()}
            loss, grads = jax.value_and_grad(loss_fn)(p32, hp, key, bx, by, mode)
            new_p, new_m = {}, {}
            for n in p32:
                m32 = (
                    hp["momentum"] * m[n].astype(jnp.float32)
                    + grads[n]
                    + hp["weight_decay"] * p32[n]
                )
                new_p[n] = (p32[n] - hp["lr"] * m32).astype(sdt)
                new_m[n] = m32.astype(sdt)
            return (new_p, new_m, k), loss

        p0 = {n: v.astype(sdt) for n, v in params.items()}
        m0 = {n: v.astype(sdt) for n, v in mom.items()}
        (p, m, _), losses = jax.lax.scan(one_step, (p0, m0, k_train), None, length=steps)
        return p, m, losses

    @functools.partial(jax.jit, static_argnames=("mode",))
    def score(params, val_x, val_y, mode):
        p32 = {n: v.astype(jnp.float32) for n, v in params.items()}
        correct = 0
        for lo in range(0, val_x.shape[0], 512):
            logits = apply(p32, val_x[lo : lo + 512], mode, cfg)
            correct = correct + jnp.sum(jnp.argmax(logits, axis=-1) == val_y[lo : lo + 512])
        return correct

    return train_generation, score


# -- the boundary decision ---------------------------------------------------


def exploit_explore(k_pbt, unit, scores, truncation=0.25, perturb=0.15):
    """PBT's generation boundary on journaled scores: (new_unit,
    src_idx). The bottom `truncation` of the ranking copies a uniformly
    drawn member of the top `truncation` and jitters its row in the unit
    cube; every space dimension here is continuous."""
    unit = jnp.asarray(unit, jnp.float32)
    scores = jnp.asarray(scores, jnp.float32)
    n, d = unit.shape
    k_src, k_noise, _k_resample, _k_val = jax.random.split(k_pbt, 4)
    n_cut = max(1, int(round(n * truncation)))
    order = jnp.argsort(-scores)
    rank = jnp.argsort(order)
    bottom = rank >= (n - n_cut)
    src_choice = order[jax.random.randint(k_src, (n,), 0, n_cut)]
    src_idx = jnp.where(bottom, src_choice, jnp.arange(n))
    copied = unit[src_idx]
    noise = jax.random.normal(k_noise, (n, d)) * perturb
    perturbed = jnp.clip(copied + noise, 0.0, 1.0)
    new_unit = jnp.where(bottom[:, None], perturbed, unit)
    return new_unit, src_idx
