"""`correct` has to come out false for the control and for each fault.

At rehearse sizes on the CPU (the chip's readings, at the cells' own
sizes, are `control_chip.py`'s and stand in PERF.md). These tests skip
the harness's look for a chip (`--rehearse`) and drive the rest of a
run in-process with the timed path broken underneath (faults.py).
"""

import contextlib
import io
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(BENCH))

import check  # noqa: E402
import faults  # noqa: E402
import run as harness  # noqa: E402

LIMITS = os.path.join(HERE, "data", "rehearse_limits.json")
CELL = "cifar10_cnn.pbt_pop512"
SEED = 11


def drive(fault=None):
    ctx = faults.FAULTS[fault]() if fault else contextlib.nullcontext()
    buf = io.StringIO()
    with ctx, contextlib.redirect_stdout(buf):
        rc = harness.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "6", "--trace", "0",
                           "--rehearse", "--limits", LIMITS])
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_sound_run_is_correct():
    res = drive()
    assert res["correct"] is True
    assert all(v <= lim for v, lim in res["compared"].values())


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_under_the_timed_path_is_not_correct(fault):
    res = drive(fault)
    assert res["correct"] is False
    failing = [k for k, (v, lim) in res["compared"].items() if v is None or not v <= lim]
    assert failing, res["compared"]


def test_lower_precision_control_is_not_correct():
    """The control: the reference in the program's place, its operands
    rounded to float8_e4m3fn, read by the same norm gaps against the
    float32 reference."""
    _, cell, cfg, traffic, _ = harness.resolve_cell(CELL, True)
    with open(LIMITS) as f:
        limits = json.load(f)["limits"]
    ref = check.Reference(cfg, traffic["population"], traffic["steps_per_generation"], SEED)
    slots = list(range(traffic["population"]))
    units = [ref.initial_unit()]
    srcs = [slots]  # every slot keeps its own member
    base = check.follow_sources(ref, slots, units, srcs)
    low = check.follow_sources(ref, slots, units, srcs, mode="fp8")
    worst = max(
        check.norm_gaps(ref, s, low[s][0], low[s][1], base[s][:2])["update"][0] for s in slots
    )
    assert worst > limits["update_gap_median_leaf"]


def test_a_state_out_of_reach_is_not_correct(monkeypatch):
    """Where the harness cannot find the sweep's state, the norm gaps
    cannot be read, and a number that cannot be read fails."""
    monkeypatch.setattr(check, "find_population_state", lambda: None)
    res = drive()
    assert res["correct"] is False
    assert res["compared"]["update_gap_median_leaf"][0] is None
