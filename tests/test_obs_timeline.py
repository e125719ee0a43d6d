"""Timeline export, bubble attribution, and the roofline verdict
(ISSUE 11: obs/timeline.py + obs/bubbles.py).

Covers: the Chrome trace-event schema gate (what the tier-1
TIMELINE_DRILL asserts), bubble edge cases (single-span streams,
overlapping threads on one rank, clock-skewed multi-rank merges with
gaps clamped >= 0, legacy embeds without the new sections), the
staging-overlap promotion from StagingEngine counters to trace attrs,
roofline classification + platform-cap resolution, the new absolute
gate keys (idle_frac / min_overlap / min_mxu_frac), and the end-to-end
acceptance drill: a traced wave sweep whose bubble attribution
reproduces the engine's measured staging overlap within 5%.
"""

from __future__ import annotations

import json
import os

import pytest

from mpi_opt_tpu.obs import bubbles, events, timeline, trace
from mpi_opt_tpu.obs.report import attribute, trace_main


@pytest.fixture(autouse=True)
def _clean_trace_state():
    saved = trace.save()
    trace.deconfigure()
    yield
    trace.deconfigure(saved)


def _rec(span, ts, dur, **attrs):
    return {
        "event": "span",
        "span": span,
        "ts": ts,
        "dur_s": dur,
        "self_s": attrs.pop("self_s", dur),
        "tid": attrs.pop("tid", 0),
        **attrs,
    }


def _write_stream(path, records):
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    return str(path)


# -- bubble analysis edge cases ------------------------------------------


def test_single_busy_span_has_zero_idle():
    rep = bubbles.analyze([_rec("train", 101.0, 1.0)])
    assert rep["busy_s"] == pytest.approx(1.0)
    assert rep["idle_s"] == 0.0 and rep["gaps"] == 0
    assert rep["idle_frac"] == 0.0
    # the invariant the drill asserts: busy + idle == wall exactly
    assert rep["busy_s"] + rep["idle_s"] == pytest.approx(rep["wall_s"])


def test_single_nonbusy_span_is_all_idle_attributed():
    """A stream holding only a compile span: its whole window is one
    gap, fully attributed to compile."""
    rep = bubbles.analyze([_rec("compile", 102.0, 2.0, cache="cold")])
    assert rep["idle_s"] == pytest.approx(2.0)
    assert rep["by_cause"] == {"compile": 2.0}
    assert rep["idle_frac"] == pytest.approx(1.0)


def test_gap_attribution_by_cause_and_unattributed():
    recs = [
        _rec("train", 101.0, 1.0),  # busy [100, 101]
        _rec("compile", 102.0, 1.0, cache="cold"),  # covers gap [101, 102]
        _rec("save", 102.5, 0.5),  # checkpoint [102, 102.5]
        _rec("train", 104.0, 1.0),  # busy [103, 104]
    ]
    rep = bubbles.analyze(recs)
    # gaps: [101, 103] = 2s; compile covers 1s, save 0.5s, 0.5s uncovered
    assert rep["idle_s"] == pytest.approx(2.0)
    assert rep["by_cause"]["compile"] == pytest.approx(1.0)
    assert rep["by_cause"]["checkpoint"] == pytest.approx(0.5)
    assert rep["by_cause"]["unattributed"] == pytest.approx(0.5)
    assert rep["largest_gap_s"] == pytest.approx(2.0)


def test_overlapping_threads_on_one_rank_merge_busy():
    """The staging worker's stage_out overlapping the main thread's
    train is ONE continuous busy region — overlap is not idle."""
    recs = [
        _rec("train", 102.0, 2.0, tid=0),  # [100, 102]
        _rec("stage_out", 103.0, 2.0, tid=1),  # [101, 103] overlaps
    ]
    rep = bubbles.analyze(recs)
    assert rep["idle_s"] == 0.0
    assert rep["busy_s"] == pytest.approx(3.0)
    assert rep["wall_s"] == pytest.approx(3.0)


def test_clock_skewed_multi_rank_never_negative_idle():
    """Ranks are judged on their OWN clocks: a rank whose timestamps sit
    minutes away from another's cannot manufacture (negative) idle in
    the merge — per-rank windows, gaps clamped >= 0 by construction."""
    recs = [
        _rec("train", 101.0, 1.0, rank=0),
        _rec("train", 103.0, 1.0, rank=0),
        # rank 1's clock is ~10 minutes skewed; identical local shape
        _rec("train", 701.0, 1.0, rank=1),
        _rec("train", 703.0, 1.0, rank=1),
    ]
    rep = bubbles.analyze(recs)
    assert set(rep["per_rank"]) == {"rank0", "rank1"}
    for entry in rep["per_rank"].values():
        assert entry["idle_s"] >= 0.0
        assert entry["idle_s"] == pytest.approx(1.0)  # the local [end, begin] gap
        assert entry["wall_s"] == pytest.approx(3.0)
    # totals are per-rank sums, not a skew-spanning merged window
    assert rep["wall_s"] == pytest.approx(6.0)
    assert rep["idle_s"] == pytest.approx(2.0)
    assert rep["busy_s"] + rep["idle_s"] == pytest.approx(rep["wall_s"])


def test_tenant_groups_are_separate():
    recs = [
        _rec("train", 101.0, 1.0, tenant="alice"),
        _rec("train", 103.0, 1.0, tenant="bob"),
    ]
    rep = bubbles.analyze(recs)
    assert set(rep["per_rank"]) == {"alice:rank0", "bob:rank0"}
    assert rep["idle_s"] == 0.0  # each tenant's window is just its span


def test_analyze_empty_returns_none():
    assert bubbles.analyze([]) is None
    assert bubbles.stream_idle_frac("/nonexistent/path.jsonl") is None


def test_stream_idle_frac_reads_a_file(tmp_path):
    path = _write_stream(
        tmp_path / "m.jsonl",
        [_rec("train", 101.0, 1.0), _rec("train", 103.0, 1.0)],
    )
    assert bubbles.stream_idle_frac(path) == pytest.approx(1.0 / 3.0, abs=1e-3)


# -- staging-overlap promotion -------------------------------------------


def test_staging_summary_prefers_engine_attrs():
    """The newest stage span's cumulative overlap_s/wait_s attrs ARE the
    engine's accounting — exact, not re-derived from durations."""
    recs = [
        _rec("stage_out", 10.5, 0.4, tid=1, bytes=1000, overlap_s=0.3, wait_s=0.05),
        _rec("stage_wait", 11.0, 0.1, overlap_s=0.35, wait_s=0.15),
    ]
    s = bubbles.staging_summary(recs)
    assert s["overlap_s"] == pytest.approx(0.35)
    assert s["wait_s"] == pytest.approx(0.15)
    assert s["transfer_s"] == pytest.approx(0.4)
    assert s["overlap_frac"] == pytest.approx(0.875)
    assert s["staged_bytes"] == 1000 and s["drains"] == 1


def test_staging_summary_mid_generation_kill_evidence():
    """A wave run killed before any drain still carries overlap
    evidence: the last stage_out's cumulative attrs (the satellite fix
    — summary counters alone die with the process)."""
    recs = [
        _rec("stage_out", 10.5, 0.4, tid=1, bytes=500, overlap_s=0.2, wait_s=0.0),
        _rec("stage_out", 11.0, 0.4, tid=1, bytes=500, overlap_s=0.6, wait_s=0.0),
    ]
    s = bubbles.staging_summary(recs)
    assert s["overlap_s"] == pytest.approx(0.6)
    assert s["wait_s"] == 0.0 and s["drains"] == 0


def test_staging_summary_sums_per_rank_engines():
    """Each rank runs its OWN StagingEngine: a multi-rank merge must sum
    per-group cumulative counters, not divide one rank's overlap by
    every rank's transfer (which would under-report overlap ~Nx)."""
    recs = []
    for rank in (0, 1, 2, 3):
        recs += [
            _rec("stage_out", 10.5 + rank, 0.4, tid=1, rank=rank,
                 bytes=100, overlap_s=0.38, wait_s=0.02),
            _rec("stage_wait", 11.0 + rank, 0.02, rank=rank,
                 overlap_s=0.38, wait_s=0.02),
        ]
    s = bubbles.staging_summary(recs)
    assert s["transfer_s"] == pytest.approx(1.6)
    assert s["overlap_s"] == pytest.approx(4 * 0.38)
    assert s["wait_s"] == pytest.approx(4 * 0.02)
    # a fully-hiding schedule reads ~95% on EVERY rank, so merged too
    assert s["overlap_frac"] == pytest.approx(0.95)
    assert s["staged_bytes"] == 400 and s["drains"] == 4


def test_stream_idle_tracker_matches_one_shot(tmp_path):
    """The scheduler's incremental tracker (reads only appended bytes)
    agrees with the one-shot whole-file computation, across polls and
    with a torn trailing line left un-consumed until completed."""
    path = str(tmp_path / "m.jsonl")
    first = [_rec("train", 101.0, 1.0), _rec("compile", 102.0, 0.8)]
    more = [_rec("train", 104.0, 1.0), _rec("stage_out", 104.5, 0.3, tid=1)]
    tracker = bubbles.StreamIdleTracker(path)
    assert tracker.poll() is None  # stream does not exist yet: no crash
    _write_stream(path, first)
    assert tracker.poll() == bubbles.stream_idle_frac(path)
    # append more + a torn half-line: the tracker must stop at the last
    # complete line and pick the rest up once finished
    with open(path, "a") as f:
        for r in more:
            f.write(json.dumps(r) + "\n")
        f.write('{"event": "span", "span": "tr')  # torn mid-append
    torn_val = tracker.poll()
    with open(path, "a") as f:
        f.write('ain", "ts": 106.0, "dur_s": 0.5, "self_s": 0.5, "tid": 0}\n')
    assert tracker.poll() == bubbles.stream_idle_frac(path)
    assert torn_val is not None  # the torn poll still judged complete lines


def test_staging_summary_legacy_stream_falls_back_to_durations():
    recs = [
        _rec("stage_out", 10.5, 0.4, tid=1),
        _rec("stage_wait", 11.0, 0.1),
    ]
    s = bubbles.staging_summary(recs)
    assert s["transfer_s"] == pytest.approx(0.4)
    assert s["wait_s"] == pytest.approx(0.1)
    assert s["overlap_s"] == pytest.approx(0.3)


def test_staging_engine_emits_cumulative_attrs(tmp_path):
    """The real engine: stage_out and stage_wait spans carry the
    cumulative accounting, and the final drain's attrs equal the
    engine's own counters exactly."""
    import jax.numpy as jnp

    from mpi_opt_tpu.train.staging import StagingEngine
    from mpi_opt_tpu.utils.metrics import MetricsLogger

    path = str(tmp_path / "m.jsonl")
    m = MetricsLogger(path=path)
    prior = trace.configure(m)
    try:
        with StagingEngine() as engine:
            engine.stage_out({"x": jnp.arange(64.0)}, lambda h: None)
            engine.drain()
            engine.stage_out({"x": jnp.arange(64.0)}, lambda h: None)
            engine.drain()
            final_wait, final_overlap = engine.wait_s, engine.overlap_s
    finally:
        trace.deconfigure(prior)
        m.close()
    from mpi_opt_tpu.obs.report import load_stream

    spans = [r for r in load_stream(path) if r.get("event") == "span"]
    outs = [r for r in spans if r["span"] == "stage_out"]
    waits = [r for r in spans if r["span"] == "stage_wait"]
    assert len(outs) == 2 and len(waits) == 2
    for r in outs + waits:
        assert isinstance(r["overlap_s"], (int, float)), r
        assert isinstance(r["wait_s"], (int, float)), r
    last = max(waits, key=lambda r: r["ts"])
    assert last["wait_s"] == pytest.approx(final_wait, abs=1e-4)
    assert last["overlap_s"] == pytest.approx(final_overlap, abs=1e-4)


# -- the roofline verdict -------------------------------------------------


def test_resolve_peak_cli_beats_calibration(monkeypatch):
    spans = [_rec("setup", 100.0, 0.1, device="TPU v5 lite")]
    assert bubbles.resolve_peak(spans, 200.0) == (200.0, "cli")
    # the shipped table is empty until a cap is measured on this
    # installation: an uncalibrated device resolves to nothing ...
    assert bubbles.resolve_peak(spans) == (None, None)
    # ... and a calibrated one to its line
    monkeypatch.setitem(bubbles.CALIBRATED_PEAK_TFLOPS, "TPU v5 lite", 157.0)
    peak, src = bubbles.resolve_peak(spans)
    assert peak == 157.0 and src == "calibration:TPU v5 lite"
    assert bubbles.resolve_peak([_rec("setup", 0.1, 0.1, device="martian")]) == (
        None,
        None,
    )


def test_roofline_per_launch_transfer_bound_on_stall():
    recs = [
        # launch 1: a third of its window is un-hidden stage_wait
        _rec("train", 103.0, 3.0, flops=10e12, launch=1, self_s=2.0),
        _rec("stage_wait", 102.5, 1.2),
        # launch 2: clean compute
        _rec("train", 105.0, 1.0, flops=10e12, launch=2),
    ]
    roof = bubbles.roofline(recs, bubbles.analyze(recs), bubbles.staging_summary(recs), 157.0, "cli")
    by_launch = {e["launch"]: e for e in roof["per_launch"]}
    assert by_launch[1]["bound"] == "transfer-bound"
    assert by_launch[1]["stall_frac"] > bubbles.TRANSFER_BOUND_FRAC
    assert by_launch[2]["bound"] == "compute-bound"
    assert by_launch[2]["mxu_frac"] == pytest.approx(10.0 / 157.0, abs=1e-3)


def test_roofline_run_verdict_precedence():
    # bubble-bound: half the wall is a bare gap
    idle = [_rec("train", 101.0, 1.0, flops=1e12), _rec("train", 104.0, 1.0, flops=1e12)]
    rep = attribute({"s": idle}, peak_tflops=157.0)
    assert rep["roofline"]["bound"] == "bubble-bound"
    # transfer-bound: low idle, but waits dominate the wall
    xfer = [
        _rec("train", 102.0, 2.0, flops=1e12),
        _rec("stage_wait", 103.5, 1.5, overlap_s=0.1, wait_s=1.5),
        _rec("stage_out", 103.4, 1.4, tid=1),
    ]
    rep = attribute({"s": xfer}, peak_tflops=157.0)
    assert rep["roofline"]["bound"] == "transfer-bound"
    # compute-bound: busy wall, no staging
    comp = [_rec("train", 101.0, 1.0, flops=1e12), _rec("train", 102.0, 1.0, flops=1e12)]
    rep = attribute({"s": comp}, peak_tflops=157.0)
    assert rep["roofline"]["bound"] == "compute-bound"
    assert rep["roofline"]["mxu_frac"] == pytest.approx(1.0 / 157.0, abs=1e-4)


def test_attribution_sections_none_without_spans():
    rep = attribute({"s": [{"event": "batch", "ts": 100.0}]})
    assert rep["bubbles"] is None
    assert rep["staging"] is None
    assert rep["roofline"] is None


# -- new attrs are registry-gated (satellite 1) ---------------------------


def test_new_span_attrs_registered():
    for attr in ("overlap_s", "wait_s", "idle_gap_s", "bound", "peak_tflops", "device"):
        assert events.is_span_attr(attr), attr


# -- the timeline export --------------------------------------------------


def _two_rank_streams():
    return {
        "rank0.out": [
            _rec("setup", 100.5, 0.5, rank=0, device="TPU v5 lite"),
            _rec("compile", 101.0, 0.5, rank=0, cache="cold"),
            _rec("train", 103.0, 2.0, rank=0, flops=4e12, launch=1),
            _rec("stage_out", 103.5, 0.4, rank=0, tid=1, bytes=1000),
            {"event": "preempt_drain", "ts": 103.6, "rank": 0},
        ],
        "rank1.out": [
            _rec("train", 104.0, 1.5, rank=1, flops=3e12, launch=1),
        ],
    }


def test_timeline_schema_and_structure(tmp_path):
    streams = _two_rank_streams()
    doc = timeline.build(streams, peak_tflops=157.0)
    assert timeline.validate_timeline(doc) == []
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X" and e["cat"] == "span"]
    spans = [r for recs in streams.values() for r in recs if r.get("event") == "span"]
    assert len(xs) == len(spans)
    # per-rank process rows with names, per-thread tracks
    names = {
        e["args"]["name"]
        for e in evs
        if e["ph"] == "M" and e["name"] == "process_name"
    }
    assert names == {"rank 0", "rank 1"}
    tnames = {
        e["args"]["name"]
        for e in evs
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert any("staging" in n for n in tnames)
    # span attrs ride as args; roofline verdict lands on train events
    train_ev = next(e for e in xs if e["name"] == "train" and e["args"].get("flops") == 4e12)
    assert train_ev["args"]["peak_tflops"] == 157.0
    assert train_ev["args"]["bound"] == "compute-bound"
    assert train_ev["args"]["mxu_frac"] == pytest.approx(2.0 / 157.0, abs=1e-3)
    # non-span events become instants; the bubble analysis its own track
    assert any(e["ph"] == "i" and e["name"] == "preempt_drain" for e in evs)
    idle = [e for e in evs if e.get("cat") == "bubble"]
    assert idle and all(e["tid"] == timeline.IDLE_TID for e in idle)
    assert all("idle_gap_s" in e["args"] for e in idle)
    # ts are normalized to the earliest begin (no negative timestamps)
    assert min(e["ts"] for e in evs) >= 0
    # write path: atomic, loadable
    out = str(tmp_path / "tl.json")
    n = timeline.write_timeline(streams, out)
    with open(out) as f:
        assert len(json.load(f)["traceEvents"]) == n


def test_timeline_empty_and_validator_catches_damage():
    doc = timeline.build({})
    assert doc["traceEvents"] == [] and timeline.validate_timeline(doc) == []
    assert timeline.validate_timeline("nope")
    assert timeline.validate_timeline({"traceEvents": [{"ph": "X"}]})
    bad_dur = {"traceEvents": [{"name": "t", "ph": "X", "pid": 1, "tid": 0, "ts": 0, "dur": -1}]}
    assert any("dur" in p for p in timeline.validate_timeline(bad_dur))


def test_trace_cli_timeline_flag(tmp_path, capsys):
    path = _write_stream(tmp_path / "m.jsonl", [_rec("train", 101.0, 1.0, launch=1)])
    out = str(tmp_path / "tl.json")
    assert trace_main([path, "--timeline", out, "--json"]) == 0
    captured = capsys.readouterr()
    json.loads(captured.out)  # --json stdout stays one parseable object
    assert "timeline:" in captured.err
    with open(out) as f:
        assert timeline.validate_timeline(json.load(f)) == []
    # --timeline cannot combine with --diff (one run's streams only)
    with pytest.raises(SystemExit) as e:
        trace_main(["--diff", path, path, "--timeline", out])
    assert e.value.code == 2
    with pytest.raises(SystemExit) as e:
        trace_main([path, "--peak-tflops", "-3"])
    assert e.value.code == 2


# -- the gate: idle_frac / min_overlap / min_mxu_frac ---------------------


def _busy_stream(stall_s=0.0):
    """A synthetic traced run: 4 train launches back-to-back, with an
    optional seeded staging stall (a bare device-idle hole covered only
    by stage_wait) in the middle."""
    recs = [_rec("setup", 100.2, 0.2, device="TPU v5 lite")]
    t = 100.2
    for launch in range(1, 5):
        if launch == 3 and stall_s:
            recs.append(_rec("stage_wait", t + stall_s, stall_s, overlap_s=0.0, wait_s=stall_s))
            t += stall_s
        recs.append(_rec("train", t + 1.0, 1.0, flops=40e12, launch=launch))
        t += 1.0
    return recs


def test_gate_idle_frac_seeded_staging_stall(tmp_path, capsys):
    """The acceptance contract: a --gate with an idle_frac budget exits
    1 on a seeded staging-stall run and 0 on self-diff."""
    base = _write_stream(tmp_path / "base.jsonl", _busy_stream())
    stalled = _write_stream(tmp_path / "new.jsonl", _busy_stream(stall_s=4.0))
    tol = str(tmp_path / "tol.json")
    with open(tol, "w") as f:
        json.dump({"default": 10.0, "idle_frac": 0.3}, f)
    assert trace_main(["--diff", base, base, "--gate", tol, "--json"]) == 0
    capsys.readouterr()
    assert trace_main(["--diff", base, stalled, "--gate", tol, "--json"]) == 1
    rep = json.loads(capsys.readouterr().out)
    assert rep["gate"]["ok"] is False
    assert any("idle fraction" in v for v in rep["gate"]["violations"])
    # the stall is attributed, not just counted: staging_wait names it
    assert rep["bubbles"]["new_idle_frac"] > 0.3


def test_gate_min_overlap_and_min_mxu(tmp_path, capsys):
    good = [
        _rec("train", 101.0, 1.0, flops=100e12, launch=1),
        _rec("stage_out", 101.5, 0.4, tid=1, overlap_s=0.38, wait_s=0.02),
        _rec("stage_wait", 101.6, 0.02, overlap_s=0.38, wait_s=0.02),
    ]
    bad = [
        _rec("train", 101.0, 1.0, flops=5e12, launch=1),
        _rec("stage_out", 101.5, 0.4, tid=1, overlap_s=0.05, wait_s=0.35),
        _rec("stage_wait", 102.0, 0.35, overlap_s=0.05, wait_s=0.35),
    ]
    g = _write_stream(tmp_path / "good.jsonl", good)
    b = _write_stream(tmp_path / "bad.jsonl", bad)
    tol = str(tmp_path / "tol.json")
    with open(tol, "w") as f:
        json.dump({"default": 10.0, "min_overlap": 0.5, "min_mxu_frac": 0.15}, f)
    args = ["--diff", g, g, "--gate", tol, "--json", "--peak-tflops", "157"]
    assert trace_main(args) == 0
    capsys.readouterr()
    assert trace_main(["--diff", g, b, "--gate", tol, "--json", "--peak-tflops", "157"]) == 1
    rep = json.loads(capsys.readouterr().out)
    vs = rep["gate"]["violations"]
    assert any("overlap" in v for v in vs), vs
    assert any("MXU" in v for v in vs), vs


def test_gate_legacy_embed_without_sections(tmp_path):
    """Satellite: legacy embeds (no bubbles/staging/roofline) diff
    without crashing; an EXPLICIT idle_frac budget on one is a lost-
    coverage violation, min_overlap skips (nothing was staged)."""
    from mpi_opt_tpu.obs.diff import apply_gate, diff_attributions

    legacy = {
        "wall_s": 5.0,
        "phases": {
            "train": {"count": 2, "total_s": 4.0, "self_s": 4.0, "p50_s": 2.0, "p95_s": 2.0}
        },
        "compile": {"cold": {"count": 0, "total_s": 0}, "persistent": {"count": 0, "total_s": 0}},
        "train": None,
        "time_to_first_trial_s": None,
        "memory": None,
    }
    rep = diff_attributions(legacy, legacy)
    assert rep["bubbles"] is None and rep["staging"] is None and rep["roofline"] is None
    gate = apply_gate(rep, {"min_overlap": 0.5})
    assert gate["ok"], gate["violations"]
    gate = apply_gate(rep, {"idle_frac": 0.3})
    assert not gate["ok"]
    assert any("no bubble analysis" in v for v in gate["violations"])


# -- end to end: traced wave sweep ---------------------------------------


def test_traced_wave_sweep_overlap_and_timeline(tmp_path, capsys):
    """The acceptance drill: a traced wave-scheduled fused PBT sweep.
    The bubble/staging attribution must reproduce the engine's measured
    staging-overlap number (probe_wave's metric, now in the summary
    JSON) within 5%, busy+idle must sum to the wall exactly, and the
    timeline export must validate."""
    from mpi_opt_tpu.cli import main

    mf = str(tmp_path / "m.jsonl")
    rc = main(
        [
            "--workload", "fashion_mlp", "--algorithm", "pbt", "--fused",
            "--no-mesh", "--population", "4", "--generations", "2",
            "--steps-per-generation", "1", "--wave-size", "2", "--seed", "0",
            "--metrics-file", mf, "--trace",
        ]
    )
    out = capsys.readouterr().out
    assert rc == 0
    summary = None
    for line in out.splitlines():
        try:
            doc = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(doc, dict) and "event" not in doc:
            summary = doc
    assert summary is not None and summary.get("stage_overlap_s") is not None
    from mpi_opt_tpu.obs.report import load_stream

    rep = attribute({"m": load_stream(mf)})
    stg = rep["staging"]
    assert stg is not None and stg["drains"] >= 2
    # the engine's own number, reproduced from the trace (5% + the
    # summary's 1e-3 rounding quantum for near-zero CPU transfers)
    assert stg["overlap_s"] == pytest.approx(
        summary["stage_overlap_s"], rel=0.05, abs=2e-3
    )
    assert stg["wait_s"] == pytest.approx(
        summary["stage_wait_s"], rel=0.05, abs=2e-3
    )
    bub = rep["bubbles"]
    assert bub is not None
    assert bub["busy_s"] + bub["idle_s"] == pytest.approx(bub["wall_s"], abs=0.01)
    assert rep["roofline"] is not None and rep["roofline"]["bound"] in (
        "compute-bound",
        "transfer-bound",
        "bubble-bound",
    )
    # the timeline over the same stream validates (Perfetto-loadable)
    tl = str(tmp_path / "tl.json")
    assert trace_main([mf, "--timeline", tl]) == 0
    capsys.readouterr()
    with open(tl) as f:
        doc = json.load(f)
    assert timeline.validate_timeline(doc) == []
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"train", "stage_out", "stage_wait"} <= names
