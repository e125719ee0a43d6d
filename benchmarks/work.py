"""Operations and bytes of a member-step, from the configuration's
layer table — the work the algorithm needs, whatever implements it.

Nothing here asks XLA: a change to the program (remat, fusion, a
kernel) does not change the count it is judged by.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in benchmarks/peaks.json")
    return table[device_kind]


def forward_macs_per_row(layers: list) -> int:
    """Multiply-accumulates of one input row's forward pass
    (convolutions and dense layers; normalisation, activation, pooling
    and the loss are not matrix work and are not counted)."""
    total = 0
    for l in layers:
        rep = l.get("repeat", 1)
        if l["op"] == "conv":
            total += rep * l["hw"] * l["hw"] * l["k"] * l["k"] * l["cin"] * l["cout"]
        elif l["op"] == "dense":
            total += rep * l["cin"] * l["cout"]
    return total


def n_params(layers: list) -> int:
    total = 0
    for l in layers:
        rep = l.get("repeat", 1)
        if l["op"] == "conv":
            total += rep * (l["k"] * l["k"] * l["cin"] * l["cout"] + (l["cout"] if l.get("bias") else 0))
        elif l["op"] == "dense":
            total += rep * (l["cin"] * l["cout"] + (l["cout"] if l.get("bias") else 0))
        elif l["op"] == "gn":
            total += rep * 2 * l["c"]
    return total


def stored_activations_per_row(layers: list) -> int:
    """Elements a row's forward pass leaves for its backward pass: the
    output of every convolution, normalisation and dense layer."""
    total = 0
    for l in layers:
        rep = l.get("repeat", 1)
        if l["op"] == "conv":
            total += rep * l["hw"] * l["hw"] * l["cout"]
        elif l["op"] == "gn":
            total += rep * l["hw"] * l["hw"] * l["c"]
        elif l["op"] == "dense":
            total += rep * l["cout"]
    return total


def member_step_flops(cfg: dict) -> float:
    """Forward + backward FLOPs of one member's step on one batch:
    2 x MACs forward, and twice that again for the two backward
    products (by the input, by the weights). No recomputation counted."""
    return 3.0 * 2.0 * forward_macs_per_row(cfg["layers"]) * cfg["batch_size"]


def eval_flops(cfg: dict) -> float:
    """Forward FLOPs of scoring one member on the validation set."""
    return 2.0 * forward_macs_per_row(cfg["layers"]) * cfg["data"]["n_val"]


def member_step_bytes(cfg: dict) -> float:
    """The least bytes one member's step must move through HBM: read
    and write parameters and momentum in float32 (16 B a parameter), and
    write each stored activation once and read it once in bfloat16 (4 B
    an element a row). The shared minibatch and the gradients that can
    stay on chip are left out: this is a floor."""
    return 16.0 * n_params(cfg["layers"]) + 4.0 * stored_activations_per_row(
        cfg["layers"]
    ) * cfg["batch_size"]


def generation_work(cfg: dict, population: int, steps: int) -> tuple:
    """(FLOPs, least bytes) of one generation: every member's steps and
    its evaluation (parameters read once per evaluation)."""
    flops = population * (steps * member_step_flops(cfg) + eval_flops(cfg))
    nbytes = population * (steps * member_step_bytes(cfg) + 4.0 * n_params(cfg["layers"]))
    return flops, nbytes
