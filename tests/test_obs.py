"""Observability layer (obs/): span tracing, attribution, registry lint.

Covers the ISSUE-8 test satellites: disabled-mode overhead (a span
with no sink does zero JSON work), thread safety under StagingEngine's
background transfer thread, multi-rank merge ordering, TF/s arithmetic
against known FLOP counts, the trace-CLI JSON schema gate, and the
event-name registry lint that stops silent stream-schema drift.
"""

from __future__ import annotations

import json
import os
import threading
import time

import pytest

from mpi_opt_tpu.obs import events, trace
from mpi_opt_tpu.obs.report import attribute, discover_streams, load_stream, trace_main
from mpi_opt_tpu.utils.metrics import MetricsLogger

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_trace_state():
    """Every test starts untraced and restores whatever was configured
    before it (the same nesting contract cli.main honors)."""
    saved = trace.save()
    trace.deconfigure()
    yield
    trace.deconfigure(saved)


def _spans(path):
    return [r for r in load_stream(path) if r.get("event") == "span"]


# -- the tracer ----------------------------------------------------------


def test_disabled_span_does_zero_json_work(monkeypatch):
    """The null contract: with no sink, a span never touches json — it
    only maintains the thread-local stack the heartbeat phase needs."""

    def boom(*a, **k):  # any serialization attempt fails the test
        raise AssertionError("json.dumps called with tracing disabled")

    monkeypatch.setattr(json, "dumps", boom)
    assert not trace.enabled()
    with trace.span("train", launch=1):
        assert trace.current_phase() == "train"
    assert trace.current_phase() is None


def test_span_record_fields_and_self_time(tmp_path):
    path = str(tmp_path / "m.jsonl")
    m = MetricsLogger(path=path)
    prior = trace.configure(m, rank=2, tenant="alice")
    try:
        with trace.span("train", launch=3) as sp:
            with trace.span("journal", n=1):
                time.sleep(0.02)
            sp["flops"] = 1e9
    finally:
        trace.deconfigure(prior)
        m.close()
    spans = _spans(path)
    by_name = {r["span"]: r for r in spans}
    assert set(by_name) == {"train", "journal"}
    tr, jn = by_name["train"], by_name["journal"]
    for r in (tr, jn):
        assert r["rank"] == 2 and r["tenant"] == "alice"
        assert isinstance(r["ts"], float) and r["dur_s"] > 0
    assert tr["flops"] == 1e9 and tr["launch"] == 3
    # self time excludes the nested journal span's duration
    assert tr["self_s"] <= tr["dur_s"] - jn["dur_s"] + 1e-3
    # child emitted before parent (exit order), both ts-stamped at exit
    assert jn["ts"] <= tr["ts"]


def test_traced_decorator_and_exception_emission(tmp_path):
    path = str(tmp_path / "m.jsonl")
    m = MetricsLogger(path=path)
    prior = trace.configure(m)
    try:

        @trace.traced("save")
        def do_save():
            return 7

        assert do_save() == 7
        with pytest.raises(ValueError, match="boom"):
            with trace.span("restore"):
                raise ValueError("boom")
    finally:
        trace.deconfigure(prior)
        m.close()
    names = [r["span"] for r in _spans(path)]
    # the crashed phase is visible in the attribution, not vanished
    assert names == ["save", "restore"]
    assert trace.current_phase() is None  # stack unwound past the raise


def test_suppressed_spans_do_not_emit(tmp_path):
    path = str(tmp_path / "m.jsonl")
    m = MetricsLogger(path=path)
    prior = trace.configure(m)
    try:
        with trace.suppressed():
            with trace.span("compile"):
                pass
        with trace.span("train"):
            pass
    finally:
        trace.deconfigure(prior)
        m.close()
    assert [r["span"] for r in _spans(path)] == ["train"]


def test_thread_safety_concurrent_spans(tmp_path):
    """N threads spanning through one sink concurrently: every line
    parses whole (MetricsLogger serializes sink writes) and per-thread
    nesting stays separate (distinct tids)."""
    path = str(tmp_path / "m.jsonl")
    m = MetricsLogger(path=path)
    prior = trace.configure(m)
    n_threads, per_thread = 4, 50

    def work(i):
        for k in range(per_thread):
            with trace.span("train", launch=k, worker=i):
                pass

    try:
        threads = [threading.Thread(target=work, args=(i,)) for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        trace.deconfigure(prior)
        m.close()
    spans = _spans(path)  # load_stream skips any malformed line: count proves none
    assert len(spans) == n_threads * per_thread
    assert len({r["tid"] for r in spans}) == n_threads


def test_staging_engine_spans_and_heartbeat_phase(tmp_path):
    """The background transfer thread traces its fetches (stage_out with
    bytes), drain traces the un-hidden wait, and the worker's heartbeat
    carries phase=stage_out — the 'stalled during stage_out' signal."""
    import numpy as np

    from mpi_opt_tpu.health import heartbeat
    from mpi_opt_tpu.train.staging import StagingEngine

    path = str(tmp_path / "m.jsonl")
    hb_path = str(tmp_path / "hb.json")
    m = MetricsLogger(path=path)
    prior = trace.configure(m)
    heartbeat.configure(hb_path)
    got = []
    try:
        import jax.numpy as jnp

        with StagingEngine() as engine:
            engine.stage_out({"x": jnp.arange(64.0)}, lambda h: got.append(h))
            engine.drain()
    finally:
        heartbeat.deconfigure()
        trace.deconfigure(prior)
        m.close()
    assert len(got) == 1 and np.asarray(got[0]["x"]).shape == (64,)
    by_name = {}
    for r in _spans(path):
        by_name.setdefault(r["span"], []).append(r)
    assert by_name["stage_out"][0]["bytes"] > 0
    assert "stage_wait" in by_name
    # worker thread != main thread in the records
    assert by_name["stage_out"][0]["tid"] != by_name["stage_wait"][0]["tid"]
    beat = heartbeat.read_beat(hb_path)
    assert beat is not None and beat["phase"] == "stage_out"


def test_heartbeat_phase_from_active_span(tmp_path):
    from mpi_opt_tpu.health import heartbeat

    hb = str(tmp_path / "hb.json")
    heartbeat.configure(hb)
    try:
        with trace.span("stage_in"):
            heartbeat.beat(stage="wave 1")
        in_span = heartbeat.read_beat(hb)
        heartbeat.beat(stage="boundary")
        outside = heartbeat.read_beat(hb)
    finally:
        heartbeat.deconfigure()
    assert in_span["phase"] == "stage_in"
    assert in_span["progress"]["stage"] == "wave 1"
    assert outside["phase"] is None  # no active span anywhere


def test_heartbeat_phase_carries_boundary_op(tmp_path):
    """Boundary spans fold their ``op`` attribute into the heartbeat
    phase (ISSUE 18 satellite): a stall during SHA's rung cut reads
    "stalled during boundary:rung_cut" in the launch event, not just
    "boundary" — the engine's boundary_span helper beats on entry so
    the phase is fresh even if the boundary op itself wedges."""
    from mpi_opt_tpu.health import heartbeat
    from mpi_opt_tpu.train.engine import boundary_span

    hb = str(tmp_path / "hb.json")
    heartbeat.configure(hb)
    try:
        with boundary_span("rung_cut", rung=2):
            cut = heartbeat.read_beat(hb)  # beat happens on span entry
        with trace.span("boundary", op="exploit"):
            heartbeat.beat(stage="gen 3")
        exploit = heartbeat.read_beat(hb)
    finally:
        heartbeat.deconfigure()
    assert cut["phase"] == "boundary:rung_cut"
    assert cut["progress"]["stage"] == "boundary rung_cut"
    assert exploit["phase"] == "boundary:exploit"


def test_launch_stall_phases_from_beat_files(tmp_path):
    """launch.py's stall event includes each wedged rank's last-beat
    phase (active-span field, progress-stage fallback)."""
    from mpi_opt_tpu.health.heartbeat import Heartbeat
    from mpi_opt_tpu.launch import _hb_path, _stall_phases

    d = str(tmp_path)
    with trace.span("stage_in"):
        Heartbeat(_hb_path(d, 0)).beat(stage="wave 2")
    Heartbeat(_hb_path(d, 1)).beat(stage="driver")  # no span: stage fallback
    phases = _stall_phases(d, [0, 1, 2])  # rank 2 never beat
    assert phases == {"0": "stage_in", "1": "driver", "2": None}


# -- attribution ---------------------------------------------------------


def _rec(span, ts, dur, self_s=None, **attrs):
    return {
        "event": "span",
        "span": span,
        "ts": ts,
        "dur_s": dur,
        "self_s": dur if self_s is None else self_s,
        "tid": 0,
        **attrs,
    }


def test_multi_rank_merge_ordering_and_wall():
    """Two rank streams with interleaved timestamps merge by absolute
    ``ts``; the merged wall spans the earliest begin to the latest end."""
    a = [_rec("train", 103.0, 2.0, rank=0), _rec("save", 104.5, 0.5, rank=0)]
    b = [_rec("train", 102.0, 1.0, rank=1), _rec("train", 106.0, 1.5, rank=1)]
    rep = attribute({"rank0.out": a, "rank1.out": b})
    assert [s["label"] for s in rep["streams"]] == ["rank0.out", "rank1.out"]
    # earliest begin = 102-1 = 101; latest end = 106
    assert rep["wall_s"] == pytest.approx(5.0)
    assert rep["streams"][0]["rank"] == 0 and rep["streams"][1]["rank"] == 1
    assert rep["phases"]["train"]["count"] == 3
    # per-stream walls are local: rank0 spans 101.0->104.5? no: begin
    # 103-2=101, end 104.5 -> 3.5
    assert rep["streams"][0]["wall_s"] == pytest.approx(3.5)


def test_tflops_arithmetic_against_known_flops():
    recs = [
        _rec("train", 10.0, 1.0, flops=2e12, launch=1),
        _rec("train", 13.0, 2.0, flops=4e12, launch=2),
    ]
    rep = attribute({"s": recs})
    t = rep["train"]
    assert t["flops"] == pytest.approx(6e12)
    assert t["train_s"] == pytest.approx(3.0)
    assert t["tflops_per_sec"] == pytest.approx(2.0)
    per = {e["launch"]: e["tflops_per_sec"] for e in t["per_launch"]}
    assert per == {1: pytest.approx(2.0), 2: pytest.approx(2.0)}


def test_attribution_self_time_and_compile_breakdown():
    recs = [
        _rec("compile", 100.8, 0.8, cache="cold"),
        _rec("compile", 101.0, 0.1, cache="persistent"),
        # train span enclosing both compiles: self excludes them
        _rec("train", 103.0, 3.0, self_s=2.1, launch=1),
    ]
    rep = attribute({"s": recs})
    assert rep["compile"]["cold"] == {"count": 1, "total_s": 0.8}
    assert rep["compile"]["persistent"] == {"count": 1, "total_s": 0.1}
    ph = rep["phases"]
    assert ph["train"]["self_s"] == pytest.approx(2.1)
    assert ph["train"]["total_s"] == pytest.approx(3.0)
    # attributed = sum of self times, never double-counting nesting
    assert rep["attributed_s"] == pytest.approx(0.8 + 0.1 + 2.1)
    # wall = begin(compile cold)=100.0 .. end(train)=103.0
    assert rep["wall_s"] == pytest.approx(3.0)
    assert rep["coverage"] == pytest.approx(1.0)


def test_time_to_first_trial_from_batch_event_and_train_span():
    recs = [
        {"event": "resume", "ts": 100.0},
        _rec("setup", 103.0, 3.0),
        {"event": "batch", "ts": 104.0},
        _rec("train", 106.0, 1.0),
    ]
    rep = attribute({"s": recs})
    # first trial evidence: the batch event at 104, stream start 100
    assert rep["time_to_first_trial_s"] == pytest.approx(4.0)


def test_per_tenant_breakdown():
    recs_a = [_rec("train", 101.0, 1.0, tenant="alice")]
    recs_b = [_rec("train", 102.0, 0.5, tenant="bob"), _rec("save", 102.5, 0.2, tenant="bob")]
    rep = attribute({"a": recs_a, "b": recs_b})
    assert set(rep["tenants"]) == {"alice", "bob"}
    assert rep["tenants"]["bob"]["save"]["count"] == 1
    assert rep["tenants"]["alice"]["train"]["self_s"] == pytest.approx(1.0)


# -- the trace CLI -------------------------------------------------------


def test_trace_cli_json_schema(tmp_path, capsys):
    path = str(tmp_path / "m.jsonl")
    with open(path, "w") as f:
        for r in (
            {"event": "resume", "ts": 100.0},
            _rec("train", 105.0, 5.0, flops=1e12, launch=1, rank=0),
            _rec("save", 105.5, 0.5, rank=0),
        ):
            f.write(json.dumps(r) + "\n")
    assert trace_main([path, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    # the stable --json surface benches/CI consume
    for key in (
        "streams",
        "records",
        "span_records",
        "wall_s",
        "attributed_s",
        "coverage",
        "phases",
        "compile",
        "train",
        "time_to_first_trial_s",
        "bubbles",
        "staging",
        "roofline",
        "tenants",
    ):
        assert key in rep, key
    assert rep["phases"]["train"]["count"] == 1
    for stat in ("count", "total_s", "self_s", "wall_pct", "p50_s", "p95_s"):
        assert stat in rep["phases"]["train"], stat
    assert rep["train"]["tflops_per_sec"] == pytest.approx(0.2)


def test_trace_cli_dir_discovery_skips_ledgers(tmp_path, capsys):
    d = str(tmp_path)
    for name in ("rank0.out", "rank1.out"):
        with open(os.path.join(d, name), "w") as f:
            f.write(json.dumps(_rec("train", 100.0, 1.0)) + "\n")
    # a ledger sniffs as kind=header, not an event stream: excluded
    with open(os.path.join(d, "sweep.jsonl"), "w") as f:
        f.write(json.dumps({"kind": "header", "version": 1}) + "\n")
    assert sorted(os.path.basename(p) for p in discover_streams(d)) == [
        "rank0.out",
        "rank1.out",
    ]
    assert trace_main([d, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert len(rep["streams"]) == 2


def test_trace_cli_empty_dir_is_an_error(tmp_path, capsys):
    assert trace_main([str(tmp_path), "--json"]) == 1
    out = capsys.readouterr()
    assert "no metrics streams" in out.err
    json.loads(out.out)  # --json stdout stays machine-parseable


# -- registry lint (the schema-drift gate) -------------------------------


def test_event_and_span_registry_lint():
    """Every literal event/span name at every call site in the codebase
    must be registered in obs/events.py — adding an event means adding
    one reviewed line there (the `ts` field was once added ad hoc; the
    NAME space is now gated)."""
    problems = events.lint(REPO_ROOT)
    assert problems == [], "\n".join(problems)


def test_registry_scan_sees_known_sites():
    """The AST scanner actually finds the emitters the lint gates on
    (an empty scan would make the lint vacuously green)."""
    sites = list(events.scan_call_sites(REPO_ROOT))
    kinds = {(k, n) for _p, _l, k, n in sites}
    assert ("event", "summary") in kinds  # metrics.log in utils/metrics.py
    assert ("event", "stall") in kinds  # launch.py _event
    assert ("event", "snapshot_corrupt") in kinds  # integrity notify
    assert ("span", "train") in kinds  # fused drivers
    assert ("span", "stage_out") in kinds  # staging worker


# -- flops hint gating ---------------------------------------------------


def test_segment_flops_hint_gated_on_tracing(tmp_path):
    from mpi_opt_tpu.train.common import segment_flops_hint

    class Dummy:
        pass

    wl = Dummy()
    # tracing off: no probe, no cache, None
    assert segment_flops_hint(wl, 4, 10) is None
    assert not hasattr(wl, "_flops_hint_cache")
    # tracing on with a non-population workload: the probe fails soft
    # (population_sweep_flops returns None) and the failure is cached
    m = MetricsLogger(path=str(tmp_path / "m.jsonl"))
    prior = trace.configure(m)
    try:
        assert segment_flops_hint(wl, 4, 10) is None
        assert wl._flops_hint_cache == {(4, 10): None}
    finally:
        trace.deconfigure(prior)
        m.close()


# -- launch-window profiling ---------------------------------------------


def test_parse_launch_window():
    from mpi_opt_tpu.utils.profiling import parse_launch_window

    assert parse_launch_window("3") == (3, 3)
    assert parse_launch_window("2:5") == (2, 5)
    for bad in ("0", "3:2", "a", "1:2:3"):
        with pytest.raises(ValueError):
            parse_launch_window(bad)


def test_profile_window_launch_ticks(tmp_path, monkeypatch):
    import jax

    from mpi_opt_tpu.utils import profiling

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: calls.append(("stop",)))
    d = str(tmp_path / "prof")
    with profiling.profile_window(d, launches=(2, 2)):
        profiling.launch_tick()  # launch 1: before the window
        assert not profiling.active() and calls == []
        profiling.launch_tick()  # launch 2: window opens
        assert profiling.active() and calls == [("start", d)]
        profiling.launch_tick()  # launch 3: window closed
        assert not profiling.active()
    assert calls == [("start", d), ("stop",)]
    # a window never closed by ticks is closed by the context exit
    calls.clear()
    with profiling.profile_window(d, launches=(1, 99)):
        profiling.launch_tick()
    assert calls == [("start", d), ("stop",)] and not profiling.active()


def test_cli_validates_profile_launches(capsys):
    from mpi_opt_tpu.cli import main

    with pytest.raises(SystemExit) as e:
        main(["--workload", "quadratic", "--profile-launches", "2:3"])
    assert e.value.code == 2
    assert "requires --profile-dir" in capsys.readouterr().err


# -- spans back to process start (ISSUE 25) --------------------------------


def test_startup_span_is_synthesized_once_a_process(tmp_path, monkeypatch):
    """The first ``configure`` of a process emits ``setup op=startup``
    with its TRUE start (the OS's start time of the process); a later
    configure (a tenant slice, a second in-process run) emits none."""
    monkeypatch.setattr(trace, "_STARTUP_EMITTED", False)  # conftest latches it
    path = str(tmp_path / "m.jsonl")
    m = MetricsLogger(path=path)
    age = trace._process_age_s()
    prior = trace.configure(m)
    trace.deconfigure(prior)
    prior = trace.configure(m)  # again: no second startup
    trace.deconfigure(prior)
    m.close()
    (sp,) = [r for r in _spans(path) if r.get("op") == "startup"]
    assert sp["span"] == "setup" and sp["self_s"] == sp["dur_s"]
    # this pytest process has been up for seconds at least, and the
    # span's start is the process's start: ts - dur_s is the same
    # moment the OS reports, to the clock tick
    assert age > 1.0 and sp["dur_s"] == pytest.approx(age, abs=0.5)
    assert sp["ts"] - sp["dur_s"] == pytest.approx(time.time() - trace._process_age_s(), abs=0.5)


def test_traced_run_spans_init_population_and_the_profiler(tmp_path, capsys, monkeypatch):
    """A traced tiny fused sweep with ``--profile-dir``: the stream
    holds ``setup op=startup``, ``setup op=init_population`` and
    ``profile op=start/stop`` with ``dir`` (where the trace lies), the
    profile spans are not inside the trace they bracket, and the whole
    stream passes the registry."""
    from mpi_opt_tpu.cli import main
    from mpi_opt_tpu.utils import profiling

    monkeypatch.setattr(trace, "_STARTUP_EMITTED", False)
    mf, pdir = str(tmp_path / "m.jsonl"), str(tmp_path / "prof")
    rc = main(
        [
            "--workload", "fashion_mlp", "--algorithm", "pbt", "--fused",
            "--no-mesh", "--population", "2", "--generations", "3",
            "--steps-per-generation", "1", "--gen-chunk", "1", "--seed", "0",
            "--metrics-file", mf, "--trace",
            "--profile-dir", pdir, "--profile-launches", "2:2",
        ]
    )
    capsys.readouterr()
    assert rc == 0 and not profiling.active()
    spans = _spans(mf)
    assert spans[0]["span"] == "setup" and spans[0]["op"] == "startup"
    (init,) = [r for r in spans if r.get("op") == "init_population"]
    assert init["span"] == "setup" and init["members"] == 2
    prof = [r for r in spans if r["span"] == "profile"]
    assert [r["op"] for r in prof] == ["start", "stop"]
    assert all(r["dir"] == pdir for r in prof)
    assert os.path.isdir(os.path.join(pdir, "plugins", "profile"))
    # start precedes launch 2's train span, stop follows it
    order = [(r["span"], r.get("op") or r.get("launch")) for r in spans if r["span"] in ("profile", "train")]
    assert order == [("train", 1), ("profile", "start"), ("train", 2), ("profile", "stop"), ("train", 3)]
    for r in spans:
        assert events.is_span(r["span"])
        extra = set(r) - {"event", "t", "ts", "span", "dur_s", "self_s", "tid", "rank", "tenant"}
        assert all(events.is_span_attr(k) for k in extra), (r["span"], extra)


# -- service live phase --------------------------------------------------


def test_service_live_phase_surface(tmp_path):
    from mpi_opt_tpu.health.heartbeat import Heartbeat
    from mpi_opt_tpu.service.spool import live_phase

    d = str(tmp_path)
    with trace.span("train"):
        Heartbeat(os.path.join(d, "heartbeat.json")).beat(stage="gen 2")
    status = {"state": "running", "slice_started_ts": time.time() - 2.0}
    live = live_phase(d, status)
    assert live["phase"] == "train"
    assert 1.0 <= live["slice_elapsed_s"] <= 60.0
    assert live_phase(d, {"state": "parked"}) is None
    # beat-less running tenant: fields degrade to None, never an error
    empty = live_phase(str(tmp_path / "nope"), {"state": "running"})
    assert empty == {"phase": None, "slice_elapsed_s": None}


# -- end to end: the schema gate on a real traced sweep ------------------


def test_traced_fused_sweep_end_to_end(tmp_path, capsys):
    """Tier-1 twin of probes/tier1.sh's TRACE_DRILL: a tiny fused PBT
    sweep traced into a metrics file, rendered by the trace CLI —
    compile/train/save spans present, wall sums sane, achieved TF/s and
    time-to-first-trial reported."""
    from mpi_opt_tpu.cli import main

    mf = str(tmp_path / "m.jsonl")
    rc = main(
        [
            "--workload", "fashion_mlp", "--algorithm", "pbt", "--fused",
            "--no-mesh", "--population", "2", "--generations", "2",
            "--steps-per-generation", "1", "--seed", "0",
            "--checkpoint-dir", str(tmp_path / "ck"),
            "--metrics-file", mf, "--trace",
        ]
    )
    capsys.readouterr()  # drop the sweep's own stdout
    assert rc == 0
    assert not trace.enabled()  # cli.main restored the entry state
    assert trace_main([mf, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    ph = rep["phases"]
    for need in ("compile", "train", "save", "digest", "setup"):
        assert need in ph and ph[need]["count"] > 0, (need, sorted(ph))
    assert rep["compile"]["cold"]["count"] > 0
    # wall sums within tolerance: attributed self-seconds cannot exceed
    # the single-threaded stream's wall (plus rounding epsilon)
    assert 0 < rep["attributed_s"] <= rep["wall_s"] * 1.05 + 0.5
    assert rep["coverage"] > 0.3
    assert rep["time_to_first_trial_s"] is not None
    # XLA:CPU cost analysis is available in this container, so the
    # train spans carry FLOPs and achieved TF/s is a number
    assert rep["train"] is not None and rep["train"]["tflops_per_sec"] > 0
    # the intra-phase sections (ISSUE 11) ride in every attribution:
    # bubble totals obey busy + idle == wall, and the roofline verdict
    # is one of the three bound classes
    bub = rep["bubbles"]
    assert bub is not None and bub["idle_frac"] is not None
    assert bub["busy_s"] + bub["idle_s"] == pytest.approx(bub["wall_s"], abs=0.01)
    assert rep["roofline"]["bound"] in (
        "compute-bound", "transfer-bound", "bubble-bound",
    )
