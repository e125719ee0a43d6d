"""Plain reference of the `cifar100_resnet18` configuration: ResNet-18
for 32x32 inputs (3x3 stem, no max-pool, stages 2-2-2-2 of basic
blocks, widths 64/128/256/512), GroupNorm(32) for BatchNorm, global
average pool, one dense head. Sizes come from the configuration's file;
see common.py for everything that is not the layer equations."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from common import act_dtype, conv, dense, group_norm


def _blocks(cfg):
    """[(name, in channels, channels, stride)] of every basic block."""
    m = cfg["model"]
    out, cin = [], m["width"]
    for stage, n_blocks in enumerate(m["stage_sizes"]):
        ch = m["width"] * 2**stage
        for b in range(n_blocks):
            out.append((f"stage{stage}_block{b}", cin, ch, 2 if stage > 0 and b == 0 else 1))
            cin = ch
    return out


def param_table(cfg: dict) -> list:
    """[(path, creation counter in its module, kind, shape)]."""
    d, w = cfg["data"], cfg["model"]["width"]
    gn = lambda path, ch: [
        (path + ("scale",), 1, "ones", (ch,)),
        (path + ("bias",), 2, "zeros", (ch,)),
    ]
    table = [(("stem", "kernel"), 1, "kernel", (3, 3, d["c"], w))]
    table += gn(("gn_stem",), w)
    for name, cin, ch, stride in _blocks(cfg):
        table.append(((name, "conv1", "kernel"), 1, "kernel", (3, 3, cin, ch)))
        table += gn((name, "gn1"), ch)
        table.append(((name, "conv2", "kernel"), 1, "kernel", (3, 3, ch, ch)))
        table += gn((name, "gn2"), ch)
        if cin != ch or stride != 1:
            table.append(((name, "proj", "kernel"), 1, "kernel", (1, 1, cin, ch)))
            table += gn((name, "gn_proj"), ch)
    top = w * 2 ** (len(cfg["model"]["stage_sizes"]) - 1)
    table.append((("head", "kernel"), 1, "kernel", (top, d["n_classes"])))
    table.append((("head", "bias"), 2, "zeros", (d["n_classes"],)))
    return table


def apply(params: dict, x, mode: str, cfg: dict):
    dt = act_dtype(mode)
    max_groups = cfg["model"]["groups"]

    def gn(path, v):
        ch = v.shape[-1]
        return group_norm(
            v, params[path + ("scale",)], params[path + ("bias",)], min(max_groups, ch), mode
        )

    x = conv(x.astype(dt), params[("stem", "kernel")], mode)
    x = jax.nn.relu(gn(("gn_stem",), x))
    for name, cin, ch, stride in _blocks(cfg):
        y = conv(x, params[(name, "conv1", "kernel")], mode, stride)
        y = jax.nn.relu(gn((name, "gn1"), y))
        y = conv(y, params[(name, "conv2", "kernel")], mode)
        y = gn((name, "gn2"), y)
        if cin != ch or stride != 1:
            x = conv(x, params[(name, "proj", "kernel")], mode, stride)
            x = gn((name, "gn_proj"), x)
        x = jax.nn.relu(x + y)
    x = jnp.mean(x, axis=(1, 2))
    x = dense(x, params[("head", "kernel")], mode) + params[("head", "bias")].astype(dt)
    return x.astype(jnp.float32)
