"""Bench record schema: the bench-record drift gate (ISSUE 10).

The trajectory comparison (`trace --diff` on embedded attributions,
bench_all's --gate-base verdict) depends on bench records keeping a
declared shape. This gate: version-2 records must carry
``schema_version``/``trace``/``device_memory``; pre-schema records and
the committed BENCH_ALL.json must stay valid as the legacy shape; and
the whole-trajectory ``bench_gate`` honors each metric's
better-direction.
"""

from __future__ import annotations

import json
import os

import pytest

from mpi_opt_tpu.obs.diff import (
    BENCH_SCHEMA_VERSION,
    bench_gate,
    validate_bench_record,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _v2(**over):
    rec = {
        "schema_version": BENCH_SCHEMA_VERSION,
        "metric": "pbt_cifar10_cnn_member_generations_per_sec_per_chip",
        "value": 8.8,
        "unit": "trials/sec/chip",
        "trace": None,
        "device_memory": None,
    }
    rec.update(over)
    return rec


def _phases(train_p50, n=4):
    return {
        "train": {
            "count": n,
            "total_s": train_p50 * n,
            "self_s": train_p50 * n,
            "p50_s": train_p50,
            "p95_s": train_p50 * 1.01,
            "mean_self_s": train_p50,
            "sd_self_s": train_p50 * 0.01,
            "p50_self_s": train_p50,
            "p95_self_s": train_p50 * 1.01,
        }
    }


def _attribution(train_p50):
    return {
        "wall_s": train_p50 * 5,
        "phases": _phases(train_p50),
        "compile": {
            "cold": {"count": 1, "total_s": 2.0},
            "persistent": {"count": 0, "total_s": 0.0},
        },
        "train": {"tflops_per_sec": 33.0},
        "time_to_first_trial_s": 3.0,
        "memory": {"peak_bytes": 1 << 30},
    }


# -- the record validator -------------------------------------------------


def test_v2_record_validates_and_requires_new_keys():
    assert validate_bench_record(_v2()) == []
    # trace/device_memory may be null but must be PRESENT
    rec = _v2()
    del rec["trace"]
    assert any("trace" in p for p in validate_bench_record(rec))
    rec = _v2()
    del rec["device_memory"]
    assert any("device_memory" in p for p in validate_bench_record(rec))
    # populated shapes are checked too
    assert validate_bench_record(
        _v2(trace=_attribution(1.0), device_memory={"bytes_in_use": 1, "source": "live_arrays"})
    ) == []
    assert any(
        "phases" in p or "trace" in p
        for p in validate_bench_record(_v2(trace={"not": "an attribution"}))
    )
    assert any(
        "device_memory" in p
        for p in validate_bench_record(_v2(device_memory={"bogus": 1}))
    )
    # drift in the core keys is always caught
    rec = _v2()
    del rec["unit"]
    assert validate_bench_record(rec)
    assert any(
        "newer" in p
        for p in validate_bench_record(_v2(schema_version=BENCH_SCHEMA_VERSION + 1))
    )


def test_v2_trace_intra_phase_sections_are_optional():
    """ISSUE 11: bubbles/staging/roofline ride in round-8+ embedded
    attributions, but they are OPTIONAL — a round-7 embed (or --no-trace
    record) without them must keep validating forever, and a present
    section must be an object."""
    # absent: valid (the round-7 shape)
    assert validate_bench_record(_v2(trace=_attribution(1.0))) == []
    # present and well-shaped: valid
    tr = _attribution(1.0)
    tr["bubbles"] = {"idle_frac": 0.1, "idle_s": 0.5, "by_cause": {"compile": 0.5}}
    tr["staging"] = {"overlap_frac": 0.76, "overlap_s": 3.0, "wait_s": 1.0}
    tr["roofline"] = {"bound": "compute-bound", "mxu_frac": 0.21, "peak_tflops": 157.0}
    assert validate_bench_record(_v2(trace=tr)) == []
    # explicit null: valid (an untraced-memory environment)
    tr2 = _attribution(1.0)
    tr2["bubbles"] = tr2["staging"] = tr2["roofline"] = None
    assert validate_bench_record(_v2(trace=tr2)) == []
    # present but mis-typed: flagged
    for key in ("bubbles", "staging", "roofline"):
        bad = _attribution(1.0)
        bad[key] = "not an object"
        assert any(
            key in p for p in validate_bench_record(_v2(trace=bad))
        ), key


def test_optional_scores_field_absent_valid_mistyped_flagged():
    """ISSUE 17: bench config 8's multi-objective summary rides an
    OPTIONAL ``scores`` object ({objective: number}) beside the scalar
    metric. Absent is valid forever (the whole scalar history); present
    it must keep the declared shape."""
    # absent: valid (every pre-17 record)
    assert validate_bench_record(_v2()) == []
    # explicit null and a well-typed object: valid
    assert validate_bench_record(_v2(scores=None)) == []
    assert validate_bench_record(
        _v2(scores={"accuracy": 0.93, "hypervolume_at_budget": 12.5})
    ) == []
    # mis-typed shapes are each flagged
    for bad in (
        [0.93, 12.5],  # a bare vector loses the objective names
        {},  # present-but-empty says nothing
        {"accuracy": "high"},
        {"accuracy": True},  # JSON true is drift, not a score
        "0.93",
    ):
        assert any(
            "scores" in p for p in validate_bench_record(_v2(scores=bad))
        ), bad
    # legacy records (no schema_version) never grew the field; the gate
    # only applies to v2 shapes, so history cannot be flagged
    legacy = {"metric": "m", "value": 1.0, "unit": "trials/sec"}
    assert validate_bench_record(legacy) == []


def test_wave_sha_config_record_shape_validates():
    """ISSUE 18: bench config 9 (wave-scheduled fused SHA) rides the
    v2 shape with the engine's staging counters as plain extra keys —
    the validator must accept them (extras are informational, never
    drift) and the gate must judge the headline like any throughput
    metric."""
    rec = _v2(
        config=9,
        metric="wave_sha64_fashion_mlp_trials_per_sec_per_chip",
        value=12.0,
        wave_size=16,
        n_waves=4,
        staged_bytes=1 << 26,
        stage_transfer_s=1.25,
        stage_wait_s=0.2,
        stage_overlap_s=1.0,
    )
    assert validate_bench_record(rec) == []
    # throughput direction: a big drop in trials/s gates
    worse = dict(rec, value=6.0)
    rep = bench_gate([rec], [worse], {})
    assert not rep["ok"]
    rep = bench_gate([rec], [rec], {})
    assert rep["ok"], rep["violations"]


# the two shapes bench.py printed before records carried schema_version
# (the first round's four keys; the later rounds' full set) — the field
# SET is the fixture, the values are placeholders, not measurements
_LEGACY_MINIMAL = {
    "metric": "pbt_cifar10_cnn_member_generations_per_sec_per_chip",
    "value": 1.0,
    "unit": "trials/sec/chip",
    "vs_baseline": 1.0,
}
_LEGACY_FULL = dict(
    _LEGACY_MINIMAL,
    population=256,
    generations=4,
    steps_per_gen=100,
    device="TPU v5 lite",
    best_val_acc=0.5,
    target_acc=0.7,
    wall_to_target_s=1.0,
    flops_total=1.0e15,
    tflops_per_sec=1.0,
    mfu=0.1,
    platform_matmul_tflops=1.0,
    mfu_vs_platform_cap=0.1,
    cpu_rank_trials_per_sec=0.01,
    vs_one_rank=1.0,
    vs_8rank_equiv=1.0,
    baseline="8-rank equivalent = 8 x single-rank rate",
)


def test_committed_bench_history_stays_valid():
    """Records from before the schema_version field must validate as
    the legacy shape forever (old records are history, not drift), and
    so must every record of the committed BENCH_ALL.json."""
    for legacy in (_LEGACY_MINIMAL, _LEGACY_FULL):
        assert validate_bench_record(legacy) == [], legacy
    with open(os.path.join(REPO_ROOT, "BENCH_ALL.json")) as f:
        records = json.load(f)
    for rec in records:
        if "error" in rec:  # a failed config records the error, not a metric
            continue
        problems = validate_bench_record(rec)
        assert problems == [], (rec.get("config"), problems)


def test_bench_all_finish_record_stamps_schema_and_watermark():
    import bench_all

    rec = bench_all._finish_record({"config": 1, "metric": "m", "value": 1.0, "unit": "trials/sec"})
    assert rec["schema_version"] == BENCH_SCHEMA_VERSION
    assert "trace" in rec and "device_memory" in rec
    # on this CPU container the watermark comes from live-array
    # accounting; either way the validator passes the stamped record
    assert validate_bench_record(rec) == []


# -- the whole-trajectory gate -------------------------------------------


def test_bench_gate_value_direction_per_unit():
    base = [
        {"config": 2, "metric": "asha", "value": 50.0, "unit": "trials/sec/chip"},
        {"config": 3, "metric": "wtt", "value": 100.0, "unit": "seconds_to_target_val_acc"},
    ]
    # throughput down 40% + wall-to-target up 60%: both regress
    worse = [
        {"config": 2, "metric": "asha", "value": 30.0, "unit": "trials/sec/chip"},
        {"config": 3, "metric": "wtt", "value": 160.0, "unit": "seconds_to_target_val_acc"},
    ]
    rep = bench_gate(base, worse, {})
    assert not rep["ok"] and len(rep["violations"]) == 2
    # throughput UP and wall-to-target DOWN are improvements, not gated
    better = [
        {"config": 2, "metric": "asha", "value": 80.0, "unit": "trials/sec/chip"},
        {"config": 3, "metric": "wtt", "value": 60.0, "unit": "seconds_to_target_val_acc"},
    ]
    rep = bench_gate(base, better, {})
    assert rep["ok"], rep["violations"]
    assert rep["configs"]["config2"]["value"]["ok"]


def test_bench_gate_diffs_embedded_traces():
    base = [_v2(config=3, trace=_attribution(1.0))]
    new = [_v2(config=3, trace=_attribution(2.0))]
    rep = bench_gate(base, new, {"phases": {"train": 0.25}})
    assert not rep["ok"]
    assert any("train" in v for v in rep["violations"])
    assert rep["configs"]["config3"]["trace_gate"]["ok"] is False
    # same trace both sides: clean
    rep = bench_gate(base, base, {"phases": {"train": 0.25}})
    assert rep["ok"], rep["violations"]
    assert rep["configs"]["config3"]["trace_gate"]["ok"] is True


def test_bench_gate_flags_config_that_lost_its_value():
    """A config whose new-round bench crashed (error record, no value)
    or whose target was never reached is the WORST regression shape —
    it must gate 1, not shrug as unjudgeable."""
    base = [{"config": 5, "metric": "resnet", "value": 2.5, "unit": "trials/sec/chip"}]
    crashed = [{"config": 5, "error": "RESOURCE_EXHAUSTED: oom"}]
    rep = bench_gate(base, crashed, {})
    assert not rep["ok"]
    assert any("RESOURCE_EXHAUSTED" in v for v in rep["violations"])
    assert rep["configs"]["config5"]["value"]["ok"] is False
    # the reverse (base never measured it) stays unjudgeable, not a fail
    rep = bench_gate(crashed, base, {})
    assert rep["ok"]
    assert rep["configs"]["config5"]["value"]["ok"] is None


def test_bench_gate_empty_or_garbage_base_is_a_failure():
    """An empty list or non-record JSON as --gate-base must fail, not
    vacuously pass with nothing gated."""
    new = [{"config": 1, "metric": "a", "value": 1.0, "unit": "trials/sec"}]
    for bad_base in ([], ["oops"], [{"no": "keys"}]):
        rep = bench_gate(bad_base, new, {})
        assert not rep["ok"], bad_base
        assert any("no bench records" in v for v in rep["violations"]), bad_base


def test_bench_gate_zero_overlap_is_a_failure_not_a_pass():
    """A --gate-base file sharing NO keys with this run's records gates
    nothing — that must be rc 1 (wrong file, wrong configs), never a
    vacuous clean verdict."""
    base = [{"config": 1, "metric": "a", "value": 1.0, "unit": "trials/sec"}]
    new = [{"config": 2, "metric": "b", "value": 1.0, "unit": "trials/sec"}]
    rep = bench_gate(base, new, {})
    assert rep["unmatched_base"] == ["config1"]
    assert rep["unmatched_new"] == ["config2"]
    assert not rep["ok"]
    assert any("no comparable records" in v for v in rep["violations"])
    # partial overlap still judges the matched pair and stays ok when
    # that pair is clean (the unmatched rest is reported, not failed)
    base.append({"config": 2, "metric": "b", "value": 1.0, "unit": "trials/sec"})
    rep = bench_gate(base, new, {})
    assert rep["ok"] and rep["unmatched_base"] == ["config1"]


def test_bench_gate_accepts_bench_r0_wrapper_shape():
    """A BENCH_r0*.json driver wrapper (record under 'parsed') gates
    directly against a flat record set — the trajectory files are the
    gate's native input."""
    base = [{"n": 5, "rc": 0, "parsed": {"metric": "m", "value": 8.81, "unit": "trials/sec/chip"}}]
    new = [{"metric": "m", "value": 4.0, "unit": "trials/sec/chip"}]
    rep = bench_gate(base, new, {})
    assert not rep["ok"]
    with pytest.raises(ValueError, match="unknown tolerance keys"):
        bench_gate(base, new, {"bogus": 1})
