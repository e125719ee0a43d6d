"""One run of one benchmark cell.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Drives the entry a user calls, `mpi_opt_tpu.cli.main`, with the cell's
traffic (a fused PBT sweep, one launch a generation, journaled), inside a
window that a slice hook times (window.py): set-up ends at the first
generation boundary, the window is the whole generations that fit into
`--seconds` after it, and the sweep drains at the closing boundary by
the path a preemption takes (exit 75). Everything that belongs to one
cell, configuration, traffic mix or metric is a file found by its name
in `BENCHMARK.json`; this file knows none of those names.

The last line on standard output is the result object; the program's own
lines come before it. Without a TPU (or with fewer chips than the cell
asks for) the run exits non-zero and prints no result. `--rehearse`
drives the same code at the configuration's tiny `rehearse` sizes on the
CPU, for the harness's own tests: its line says `"platform": "cpu"` and
`"rehearsal": true`, and can never read as a chip record.
"""

from __future__ import annotations

import time

T0 = time.time()  # process start, as near as Python lets us stamp it

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import check  # noqa: E402
import stream  # noqa: E402
import window as window_mod  # noqa: E402
import work  # noqa: E402
import xplane  # noqa: E402

EX_TEMPFAIL = 75  # the CLI's exit after a drained (preempted / time-sliced) sweep
TRACED_LAUNCH = 2  # the window's first generation


def fail(msg: str, code: int = 1):
    print(f"benchmarks/run.py: {msg}", file=sys.stderr)
    sys.exit(code)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def merge(base: dict, over: dict) -> dict:
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            merge(base[k], v)
        else:
            base[k] = v
    return base


def resolve_cell(name: str, rehearse: bool, limits_path=None):
    """(bench, cell, configuration, traffic, limits) for a cell name."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        fail(f"no cell {name!r} in BENCHMARK.json (have {sorted(cells)})", 2)
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    limits = load_json(limits_path or os.path.join(HERE, "limits", name + ".json"))["limits"]
    if rehearse:
        merge(cfg, cfg["rehearse"]["config"])
        merge(traffic, traffic["rehearse"])
    return bench, cell, cfg, traffic, limits


def rehearse_workload(cfg: dict):
    """The configuration's workload at its `rehearse` sizes, for the
    CLI's injection seam (a rehearsal only; a chip run resolves the
    workload by name like any user)."""
    from mpi_opt_tpu.workloads import get_workload

    wl = get_workload(cfg["workload"])
    for k, v in cfg["rehearse"]["workload_attrs"].items():
        setattr(wl, k, v)
    return wl


def metric_names(bench: dict, cell: str, group: str) -> list:
    """Names of the group's metrics that this cell reports."""
    return [
        m["name"]
        for m in bench[group]
        if "workloads" not in m or cell in m["workloads"]
    ]


def read_metrics(names: list, units: dict, run) -> dict:
    out = {}
    for name in names:
        mod = check.load_module(os.path.join(HERE, "metrics", name + ".py"), "metric_" + name)
        value = mod.read(run)
        if value is None or not math.isfinite(value):
            continue  # a reader that finds nothing to read returns nothing
        out[name] = {"value": float(value), "unit": units[name]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--limits", default=None, help="another limits file (the harness's own tests)")
    args = ap.parse_args(argv)

    bench, cell, cfg, traffic, limits = resolve_cell(args.workload, args.rehearse, args.limits)
    chips = int(cell["chips"])
    sys.path.insert(0, ROOT)
    try:
        import jax
        from mpi_opt_tpu import cli
        from mpi_opt_tpu.health import shutdown
    except ImportError as e:
        fail(f"the system under test is not in this checkout: {e}", 2)

    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    if not args.rehearse and (devices[0].platform != "tpu" or len(devices) < chips):
        fail(
            f"cell {cell['name']} needs {chips} TPU chip(s); jax found "
            f"{len(devices)} x {devices[0].platform}",
            3,
        )
    kind = devices[0].device_kind
    peaks = None if args.rehearse else work.peaks(kind)

    out = os.path.join(HERE, "out", f"{cell['name']}.t{args.trace}")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    ledger_path = os.path.join(out, "ledger.jsonl")
    stream_path = os.path.join(out, "stream.jsonl")
    profile_dir = os.path.join(out, "profile")

    cli_args = ["--workload", cfg["workload"], "--seed", str(args.seed)]
    cli_args += ["--generations", "100000", "--ledger", ledger_path] + list(traffic["cli"])
    if args.trace:
        cli_args += ["--trace", "--metrics-file", stream_path]
        cli_args += ["--profile-dir", profile_dir, "--profile-launches", f"{TRACED_LAUNCH}:{TRACED_LAUNCH}"]
    kwargs = {"_workload": rehearse_workload(cfg)} if args.rehearse else {}

    # the state the sweep carries out of its first generation, for the
    # comparison: copied to the host at the first boundary, inside set-up
    slots = check.sample_members(args.seed, traffic["population"], traffic["check"]["sample_members"])
    held = {}
    win = window_mod.Window(
        args.seconds, shutdown.request, on_open=lambda: held.update(state=check.capture_slots(slots))
    )
    shutdown.set_slice_hook(win.hook)
    try:
        rc = cli.main(cli_args, **kwargs)
    finally:
        shutdown.clear_slice_hook()
    sys.stdout.flush()
    if rc != EX_TEMPFAIL or not win.closed:
        fail(f"the sweep did not drain at the window's end (exit {rc}, window closed: {win.closed})")

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    kwargs.clear()
    gc.collect()

    # the comparison: once the window has closed, the peak was read and
    # the program's state is freed
    t_check = time.time()
    header, ledger = check.read_ledger(ledger_path)
    ok, compared, detail = check.check_ledger(
        cfg, traffic, limits, args.seed, header, ledger, slots=slots, captured=held.get("state")
    )
    held.clear()
    check_s = time.time() - t_check

    window_recs = [r for g in range(1, win.generations + 1) for r in ledger.get(g, {}).values()]
    failed = sum(
        1
        for r in window_recs
        if r.get("status") != "ok" or r.get("score") is None or not math.isfinite(float(r["score"]))
    )
    spans = stream.load_spans(stream_path) if args.trace else []
    trace = None
    if args.trace:
        pb = xplane.find_xplane(profile_dir)
        if pb is not None:
            trace = xplane.reduce_trace(pb, {s["span"] for s in spans}) or None
    run = types.SimpleNamespace(
        t0=T0, window=win, spans=spans, trace=trace, cfg=cfg, traffic=traffic,
        chips=chips, peaks=peaks, work=work, ledger=ledger,
        population=traffic["population"], steps=traffic["steps_per_generation"],
        traced_launch=TRACED_LAUNCH if args.trace else None,
    )
    group = "per_layer" if args.trace else "end_to_end"
    units = {m["name"]: m["unit"] for m in bench[group]}
    metrics = read_metrics(metric_names(bench, cell["name"], group), units, run)

    device = {
        "platform": devices[0].platform,
        "kind": kind,
        "count": chips if not args.rehearse else len(devices),
        "memory_peak_bytes": memory_peak,
    }
    result = {
        "correct": bool(ok),
        "attempted": len(window_recs),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}
    if args.rehearse:
        result["rehearsal"] = True
    result["window"] = {
        "generations": win.generations,
        "seconds": win.end - win.start,
        "periods_s": win.periods(),
        "check_s": check_s,
        "check": detail,
    }
    result["compared"] = compared  # last: each number beside its limit
    print(json.dumps(result))
    sys.stdout.flush()
    for name, (value, limit) in compared.items():
        print(f"compared {name}: {value!r} limit {limit!r}", file=sys.stderr)  # None: not read
    return 0


if __name__ == "__main__":
    sys.exit(main())
