"""Plain reference of the `cifar10_cnn` configuration (SmallCNN):
conv32-conv32-pool-conv64-conv64-pool-dense128-dense10, GroupNorm(8)
and ReLU after every convolution. Sizes come from the configuration's
file; see common.py for everything that is not the layer equations."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from common import act_dtype, conv, dense, group_norm, max_pool2


def _channels(cfg):
    w = cfg["model"]["width"]
    return (w, w, 2 * w, 2 * w)


def param_table(cfg: dict) -> list:
    """[(path, creation counter in its module, kind, shape)]."""
    d, m = cfg["data"], cfg["model"]
    table, cin = [], d["c"]
    for i, ch in enumerate(_channels(cfg)):
        table.append(((f"conv{i}", "kernel"), 1, "kernel", (3, 3, cin, ch)))
        table.append(((f"conv{i}", "bias"), 2, "zeros", (ch,)))
        table.append(((f"gn{i}", "scale"), 1, "ones", (ch,)))
        table.append(((f"gn{i}", "bias"), 2, "zeros", (ch,)))
        cin = ch
    flat = (d["h"] // 4) * (d["w"] // 4) * cin
    table.append((("fc1", "kernel"), 1, "kernel", (flat, m["dense"])))
    table.append((("fc1", "bias"), 2, "zeros", (m["dense"],)))
    table.append((("fc2", "kernel"), 1, "kernel", (m["dense"], d["n_classes"])))
    table.append((("fc2", "bias"), 2, "zeros", (d["n_classes"],)))
    return table


def apply(params: dict, x, mode: str, cfg: dict):
    dt = act_dtype(mode)
    x = x.astype(dt)
    for i in range(4):
        x = conv(x, params[(f"conv{i}", "kernel")], mode) + params[(f"conv{i}", "bias")].astype(dt)
        x = group_norm(
            x, params[(f"gn{i}", "scale")], params[(f"gn{i}", "bias")],
            cfg["model"]["groups"], mode,
        )
        x = jax.nn.relu(x)
        if i % 2 == 1:
            x = max_pool2(x)
    x = x.reshape((x.shape[0], -1))
    x = jax.nn.relu(dense(x, params[("fc1", "kernel")], mode) + params[("fc1", "bias")].astype(dt))
    x = dense(x, params[("fc2", "kernel")], mode) + params[("fc2", "bias")].astype(dt)
    return x.astype(jnp.float32)
