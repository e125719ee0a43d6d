#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once on one TPU chip, through the entry point a
user calls (``python -m mpi_opt_tpu``), at the full width of the models
the repo trains, and checks what comes out by the repo's own means:

1. pbt256  — fused PBT, ``cifar10_cnn`` (SmallCNN, 32/64 channels), the
   whole 256-member population resident, 2 generations x 100 steps,
   journaled, snapshotted and traced: CLI -> train/engine.py ->
   fused_pbt.py -> PopulationTrainer -> ledger -> snapshot;
2. driver  — the driver tier: ASHA over ``--backend tpu``'s slot pool,
   64 trials of ``fashion_mlp`` (BASELINE config 2);
3. resnet  — fused PBT, ``cifar100_resnet18`` at full width, 32 members
   (one chip's share of BASELINE config 5), 10 steps;
4. wave    — phase 1's exact arguments plus ``--wave-size 128``: the
   host-staged wave loop, whose ledger must be phase 1's up to the
   rounding of two compilations (``check_equivalent``).

``--chips 4`` runs INSTEAD (never in addition) the path that exists only
across chips: fused PBT ``cifar100_resnet18`` pop=128 on the automatic
('pop': 4) mesh in one process, compared record by record with the same
sweep on one chip (``--no-mesh --wave-size 32``: the only one-chip form
that journals the same 128 members), then fsck and a ``--resume`` from
the sharded snapshot.

One process per chip: this parent never imports jax (nor anything of
the repo that does). Every phase is a child process, run one after the
other, each the only holder of the chip while it lives. The device is
established inside the FIRST holder (a probe child), before any sweep
starts, and every later assertion reads the device out of the sweep's
own summary and spans, never out of this parent. Any failed phase makes
the script exit non-zero; nothing is caught and carried past.

The last line of standard output is, on success and only then,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Where jax finds no TPU the script exits non-zero and prints no result.

``--rehearse`` is the CPU rehearsal of the control flow (tiny sizes,
whatever platform jax finds). It can never print ``"ok": true``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
#: small results (ledgers, metrics streams, child logs): the chip tool
#: brings this directory back
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")
#: snapshots (gigabytes): stay on the machine, removed at the end
BIG = os.path.join(HERE, "chip_smoke_out")

#: a score is an accuracy over the 2048-row validation set: two
#: compilations of the same member (resident / wave, partitioned /
#: single-device) may disagree on this many rows' worth of it
SCORE_TOL = 10 / 2048

_PROBE = """
import json, jax
from mpi_opt_tpu.obs import memory
d = jax.devices()[0]
s = memory.sample() or {}
print(json.dumps({"platform": d.platform, "kind": d.device_kind,
                  "count": len(jax.devices()), "mem_source": s.get("source"),
                  "budget": memory.measured_budget()}))
"""


class Failed(Exception):
    """One assertion of one phase did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise Failed(msg)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# -- children ---------------------------------------------------------------


def child_env() -> dict:
    """The parent's environment with this checkout first on the path."""
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    return env


def run_child(name: str, argv: list, timeout: float):
    """Run one child to its end; (rc, stdout, wall seconds). Its stderr
    goes to ``OUT/<name>.err``. A child that outlives ``timeout`` is
    killed with its whole process group (the driver tier starts no
    pool, but nothing started here may survive this script)."""
    t0 = time.monotonic()
    with open(os.path.join(OUT, f"{name}.err"), "w") as err:
        proc = subprocess.Popen(
            argv, cwd=HERE, env=child_env(), stdout=subprocess.PIPE, stderr=err,
            text=True, start_new_session=True,
        )
        try:
            out, _ = proc.communicate(timeout=timeout)
        except BaseException:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
            raise
    with open(os.path.join(OUT, f"{name}.out"), "w") as f:
        f.write(out)
    return proc.returncode, out, time.monotonic() - t0


def err_tail(name: str, n: int = 25) -> str:
    try:
        with open(os.path.join(OUT, f"{name}.err")) as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def cli(name: str, args: list, timeout: float, via=None):
    """One ``python -m mpi_opt_tpu`` child (or ``via``: this script's
    own mesh child wrapping the same ``cli.main``); returns (summary,
    wall). Asserts exit code 0 and a parseable summary line."""
    argv = [sys.executable, "-m", "mpi_opt_tpu", *args] if via is None else [*via, *args]
    try:
        rc, out, wall = run_child(name, argv, timeout)
    except subprocess.TimeoutExpired:
        raise Failed(f"{name}: no end after {timeout:.0f}s (killed)\n{err_tail(name)}")
    check(rc == 0, f"{name}: exit code {rc}\n{err_tail(name)}")
    lines = [l for l in out.splitlines() if l.strip()]
    check(lines, f"{name}: printed nothing")
    try:
        summary = json.loads(lines[-1])
    except ValueError:
        raise Failed(f"{name}: last line is not JSON: {lines[-1][:200]!r}")
    check(isinstance(summary, dict), f"{name}: summary is not an object")
    return summary, wall


def probe(rehearse: bool) -> dict:
    """The first holder of the chip says what jax found. Not a TPU:
    exit non-zero at once, with no result line — and with nothing
    written: an earlier run's results are wiped only once there is a
    chip to make new ones on."""
    try:
        r = subprocess.run(
            [sys.executable, "-c", _PROBE], cwd=HERE, env=child_env(), capture_output=True,
            text=True, timeout=300,
        )
    except subprocess.TimeoutExpired:
        sys.exit("[chip_smoke] the device probe did not finish in 300s")
    if r.returncode != 0:
        sys.exit(
            f"[chip_smoke] the device probe failed (exit {r.returncode}):\n"
            + r.stderr[-2000:]
        )
    dev = json.loads(r.stdout.strip().splitlines()[-1])
    say(f"device: {dev}")
    if dev["platform"] != "tpu" and not rehearse:
        sys.exit(
            f"[chip_smoke] jax found platform {dev['platform']!r}, not a TPU: "
            "nothing was run"
        )
    return dev


# -- reading what a phase wrote ----------------------------------------------


def read_jsonl(path: str) -> list:
    with open(path) as f:
        return [json.loads(l) for l in f if l.strip()]


def check_device(name: str, summary: dict, dev: dict) -> None:
    want = {k: dev[k] for k in ("platform", "kind", "count")}
    check(
        summary.get("device") == want,
        f"{name}: summary names device {summary.get('device')}, the probe found {want}",
    )


def check_ledger(name: str, path: str, n_records=None, n_trials=None) -> list:
    """Header + records, every one ``ok`` with a finite score: exactly
    ``n_records`` of them (fused: one a member a generation), or one or
    more for each of ``n_trials`` trials (driver tier: one a rung a
    trial reached); then the repo's own ``report --validate``."""
    lines = read_jsonl(path)
    check(lines and lines[0].get("kind") == "header", f"{name}: ledger has no header")
    records = lines[1:]
    if n_records is not None:
        check(
            len(records) == n_records,
            f"{name}: ledger holds {len(records)} records, expected {n_records}",
        )
    if n_trials is not None:
        ids = {r.get("trial_id") for r in records}
        check(ids == set(range(n_trials)), f"{name}: ledger covers trials {sorted(ids)}")
    bad = [r for r in records if r.get("status") != "ok"]
    check(not bad, f"{name}: {len(bad)} records are not ok, e.g. {bad[:1]}")
    nonfinite = [
        r for r in records
        if not isinstance(r.get("score"), (int, float)) or not math.isfinite(r["score"])
    ]
    check(not nonfinite, f"{name}: {len(nonfinite)} records have no finite score")
    rc, out, _ = run_child(
        f"{name}.validate",
        [sys.executable, "-m", "mpi_opt_tpu", "report", path, "--validate"],
        300,
    )
    check(rc == 0, f"{name}: report --validate exit {rc}: {out[-500:]}")
    return records


def check_fsck(name: str, ckpt: str, ledger: str) -> None:
    rc, out, _ = run_child(
        f"{name}.fsck",
        [sys.executable, "-m", "mpi_opt_tpu", "fsck", ckpt, "--ledger", ledger, "--json"],
        600,
    )
    check(rc == 0, f"{name}: fsck exit {rc}: {out[-800:]}")
    rep = json.loads(out.strip().splitlines()[-1])
    statuses = [s["status"] for s in rep["steps"]]
    check(
        rep["ok"] and statuses and all(s == "verified" for s in statuses),
        f"{name}: fsck did not verify every step: {statuses}",
    )


def check_trace(name: str, path: str, dev: dict, rehearse: bool, memory: bool = True) -> dict:
    """Spans name the chip and read the allocator's own counters
    (``memory=False``: the driver tier's spans carry no memory notes,
    so none may disagree); returns the figures printed for the phase."""
    from mpi_opt_tpu.obs import report  # jax-free (see module docstring)

    records = read_jsonl(path)
    spans = [r for r in records if r.get("event") == "span"]
    kinds = {r["device"] for r in spans if "device" in r}
    check(kinds == {dev["kind"]}, f"{name}: spans name devices {kinds}, not {dev['kind']!r}")
    sources = {r["mem_src"] for r in spans if "mem_src" in r}
    want = {"live_arrays"} if rehearse and dev["platform"] != "tpu" else {"memory_stats"}
    check(
        sources == want if memory else sources <= want,
        f"{name}: span memory sources {sources}, expected {want}",
    )
    rep = report.attribute({name: records})
    compile_rep = rep["compile"]
    return {
        "time_to_first_trial_s": rep["time_to_first_trial_s"],
        "compiled": compile_rep["cold"]["count"],
        "compile_s": compile_rep["cold"]["total_s"],
        "cache_hits": compile_rep["persistent"]["count"],
        "peak_bytes": max((r["mem_peak_bytes"] for r in spans if "mem_peak_bytes" in r), default=None),
    }


def check_equivalent(name: str, ours: list, theirs: list) -> str:
    """Two journals of ONE sweep, run through two different compiled
    programs. On the CPU they are equal record for record (tested). On
    the chip two compilations round differently, so what must hold is:
    every record's identity (trial_id, member, boundary, status, step)
    is equal; the first boundary's params are equal (they are inputs,
    no device arithmetic reaches them) and its scores agree within
    SCORE_TOL; at a later boundary a member's params may differ only
    where the exploit ranked near-ties the other way, so at most four
    members (the one that moved and its neighbour, at either cut of the
    truncation) for each member whose score differed one boundary
    earlier, beside those whose params already differed there."""
    check(len(ours) == len(theirs), f"{name}: {len(ours)} records against {len(theirs)}")
    identity = ("trial_id", "member", "boundary", "status", "step")
    bad = [(a, b) for a, b in zip(ours, theirs) if any(a.get(k) != b.get(k) for k in identity)]
    check(not bad, f"{name}: {len(bad)} records differ in {identity}, first: {bad[:1]}")
    by_boundary: dict = {}
    for a, b in zip(ours, theirs):
        by_boundary.setdefault(a["boundary"], []).append((a, b))
    notes = []
    prev_scores = prev_params = 0
    for boundary in sorted(by_boundary):
        pairs = by_boundary[boundary]
        d_params = sum(a["params"] != b["params"] for a, b in pairs)
        d_scores = sum(a["score"] != b["score"] for a, b in pairs)
        worst = max(abs(a["score"] - b["score"]) for a, b in pairs)
        if boundary == min(by_boundary):
            check(d_params == 0, f"{name}: {d_params} members START from different params")
            check(
                worst <= SCORE_TOL,
                f"{name}: boundary {boundary} scores differ by up to {worst} (> {SCORE_TOL})",
            )
        else:
            allowed = 4 * prev_scores + prev_params
            check(
                d_params <= allowed,
                f"{name}: boundary {boundary}: {d_params} members' params differ, but the "
                f"{prev_scores} score differences one boundary earlier explain at most {allowed}",
            )
        notes.append(
            f"boundary {boundary}: {d_params} params and {d_scores} scores of "
            f"{len(pairs)} differ, max |score diff| {worst:.6f}"
        )
        prev_scores, prev_params = d_scores, d_params
    return "; ".join(notes)


def report_phase(name: str, wall: float, summary: dict, figures: dict) -> None:
    say(
        f"{name}: wall {wall:.1f}s, first trial at {figures['time_to_first_trial_s']}s, "
        f"compiled {figures['compiled']} programs in {figures['compile_s']}s, "
        f"{figures['cache_hits']} cache hits, best score {summary.get('best_score')}, "
        f"peak {figures['peak_bytes']} bytes"
    )


# -- one chip ------------------------------------------------------------------


def one_chip(dev: dict, rehearse: bool) -> None:
    if not rehearse:
        check(dev["mem_source"] == "memory_stats", f"memory source is {dev['mem_source']!r}")
        check(dev["budget"], "measured_budget() is None: wave sizing would fall back to a guess")
    pop, chunk, steps, wave = (4, 2, 1, 2) if rehearse else (256, 32, 100, 128)
    out = lambda f: os.path.join(OUT, f)

    def pbt_args(tag: str) -> list:
        return [
            "--workload", "cifar10_cnn", "--algorithm", "pbt", "--fused",
            "--population", str(pop), "--generations", "2",
            "--steps-per-generation", str(steps),
            "--member-chunk", str(chunk), "--gen-chunk", "1", "--seed", "0",
            "--ledger", out(f"{tag}.jsonl"),
            "--checkpoint-dir", os.path.join(BIG, f"{tag}.ckpt"),
            "--trace", "--metrics-file", out(f"{tag}.metrics.jsonl"),
        ]

    # 1. the headline deployment, whole population resident
    summary, wall = cli("pbt256", pbt_args("pbt256"), 900)
    check_device("pbt256", summary, dev)
    resident = check_ledger("pbt256", out("pbt256.jsonl"), 2 * pop)
    check_fsck("pbt256", os.path.join(BIG, "pbt256.ckpt"), out("pbt256.jsonl"))
    figures = check_trace("pbt256", out("pbt256.metrics.jsonl"), dev, rehearse)
    check(summary["n_trials"] == 2 * pop, f"pbt256: n_trials {summary['n_trials']}")
    report_phase("pbt256", wall, summary, figures)
    shutil.rmtree(os.path.join(BIG, "pbt256.ckpt"))

    # 2. the driver tier: slot pool + host_ops
    trials = 8 if rehearse else 64
    summary, wall = cli(
        "driver",
        [
            "--workload", "fashion_mlp", "--algorithm", "asha", "--backend", "tpu",
            "--trials", str(trials), "--seed", "0", "--ledger", out("driver.jsonl"),
            "--trace", "--metrics-file", out("driver.metrics.jsonl"),
        ],
        600,
    )
    check_device("driver", summary, dev)
    check(summary["n_trials"] == trials, f"driver: n_trials {summary['n_trials']}")
    check(summary["trials_failed"] == 0, f"driver: {summary['trials_failed']} trials failed")
    check_ledger("driver", out("driver.jsonl"), n_trials=trials)
    figures = check_trace("driver", out("driver.metrics.jsonl"), dev, rehearse, memory=False)
    report_phase("driver", wall, summary, figures)

    # 3. the largest supported member at full width
    rpop, rchunk, rsteps = (2, 1, 1) if rehearse else (32, 8, 10)
    summary, wall = cli(
        "resnet",
        [
            "--workload", "cifar100_resnet18", "--algorithm", "pbt", "--fused",
            "--population", str(rpop), "--generations", "1",
            "--steps-per-generation", str(rsteps), "--member-chunk", str(rchunk),
            "--seed", "0", "--ledger", out("resnet.jsonl"),
            "--trace", "--metrics-file", out("resnet.metrics.jsonl"),
        ],
        900,
    )
    check_device("resnet", summary, dev)
    check_ledger("resnet", out("resnet.jsonl"), rpop)
    figures = check_trace("resnet", out("resnet.metrics.jsonl"), dev, rehearse)
    report_phase("resnet", wall, summary, figures)

    # 4. wave mode: the same sweep, host-staged in two waves
    summary, wall = cli("wave", pbt_args("wave") + ["--wave-size", str(wave)], 900)
    check_device("wave", summary, dev)
    waved = check_ledger("wave", out("wave.jsonl"), 2 * pop)
    check_fsck("wave", os.path.join(BIG, "wave.ckpt"), out("wave.jsonl"))
    figures = check_trace("wave", out("wave.metrics.jsonl"), dev, rehearse)
    check(
        summary.get("stage_overlap_s") is not None,
        f"wave: summary has no stage_overlap_s: {sorted(summary)}",
    )
    say(f"wave vs resident: {check_equivalent('wave', resident, waved)}")
    say(f"wave: staged {summary.get('staged_bytes')} bytes, overlap {summary['stage_overlap_s']}s")
    report_phase("wave", wall, summary, figures)
    shutil.rmtree(os.path.join(BIG, "wave.ckpt"))


# -- four chips ------------------------------------------------------------------


def four_chips(dev: dict, rehearse: bool) -> None:
    check(dev["count"] == 4, f"--chips 4 on a host with {dev['count']} devices")
    pop, chunk, steps, wave = (8, 1, 1, 2) if rehearse else (128, 8, 10, 32)
    out = lambda f: os.path.join(OUT, f)
    ckpt = os.path.join(BIG, "mesh.ckpt")
    common = [
        "--workload", "cifar100_resnet18", "--algorithm", "pbt", "--fused",
        "--population", str(pop), "--generations", "2",
        "--steps-per-generation", str(steps), "--member-chunk", str(chunk),
        "--gen-chunk", "1", "--seed", "0",
    ]
    mesh_args = common + [
        "--ledger", out("mesh.jsonl"), "--checkpoint-dir", ckpt,
        "--trace", "--metrics-file", out("mesh.metrics.jsonl"),
    ]
    placement = out("mesh.placement.json")
    via = [sys.executable, os.path.abspath(__file__), "--mesh-child", placement, "--"]

    # the mesh path, in one process over the four chips
    summary, wall = cli("mesh", mesh_args, 1500, via=via)
    check_device("mesh", summary, dev)
    check(summary["mesh"] == {"pop": 4, "data": 1}, f"mesh: auto mesh is {summary['mesh']}")
    check(summary["n_chips"] == 4, f"mesh: n_chips {summary['n_chips']}")
    meshed = check_ledger("mesh", out("mesh.jsonl"), 2 * pop)
    with open(placement) as f:
        placed = json.load(f)
    check(
        placed["device_count"] == 4 and placed["leaves"] > 0
        and placed["leaves_on_four_devices"] == placed["leaves"],
        f"mesh: PopState placement {placed}",
    )
    if not rehearse:
        in_use = placed["bytes_in_use"]
        check(
            len(in_use) == 4 and all(in_use) and max(in_use) <= 2 * min(in_use),
            f"mesh: bytes_in_use per device {in_use} (one holds more than twice another)",
        )
    figures = check_trace("mesh", out("mesh.metrics.jsonl"), dev, rehearse)
    say(f"mesh: placement {placed}")
    report_phase("mesh", wall, summary, figures)

    # what it is compared with: the same 128 members on ONE chip
    one, wall1 = cli(
        "onechip",
        common + [
            "--no-mesh", "--wave-size", str(wave), "--ledger", out("onechip.jsonl"),
            "--trace", "--metrics-file", out("onechip.metrics.jsonl"),
        ],
        1500,
    )
    check(one["n_chips"] == 1 and one["mesh"] is None, f"onechip: ran on {one['mesh']}")
    single = check_ledger("onechip", out("onechip.jsonl"), 2 * pop)
    figures = check_trace("onechip", out("onechip.metrics.jsonl"), dev, rehearse)
    report_phase("onechip", wall1, one, figures)
    say(f"mesh vs one chip: {check_equivalent('mesh', meshed, single)}")

    # snapshot -> fsck -> --resume on the sharded state: set the newest
    # step aside, so the resume restores generation 1's sharded
    # snapshot and trains generation 2 again, verifying every member
    # record it re-derives against the journal
    check_fsck("mesh", ckpt, out("mesh.jsonl"))
    steps_on_disk = sorted(int(d) for d in os.listdir(ckpt) if d.isdigit())
    check(steps_on_disk == [1, 2], f"mesh: snapshot steps {steps_on_disk}")
    shutil.move(os.path.join(ckpt, "2"), os.path.join(BIG, "mesh.step2.aside"))
    resumed, wall2 = cli("resume", mesh_args + ["--resume"], 1500)
    check_device("resume", resumed, dev)
    check(
        resumed["journal"] == {"written": 0, "verified": pop},
        f"resume: journal {resumed['journal']}, expected {pop} re-derived records verified",
    )
    check(
        resumed["best_score"] == summary["best_score"]
        and resumed["best_params"] == summary["best_params"],
        "resume: the resumed sweep's best differs from the uninterrupted one's",
    )
    check_ledger("resume", out("mesh.jsonl"), 2 * pop)
    say(f"resume: wall {wall2:.1f}s, {pop} re-trained records verified against the journal")
    shutil.rmtree(ckpt)


def mesh_child(placement_path: str, argv: list) -> int:
    """The mesh phase's holder of the chips: the same ``cli.main(argv)``
    a ``python -m mpi_opt_tpu`` child runs, plus a look at where the
    final PopState lives — which only the process that holds it can
    take. (This function is the one place this file imports jax.)"""
    import jax

    import mpi_opt_tpu.train.fused_pbt as fp
    from mpi_opt_tpu.cli import main as cli_main

    real = fp.fused_pbt
    kept = {}

    def keeping(*a, **k):
        kept["res"] = real(*a, **k)
        return kept["res"]

    fp.fused_pbt = keeping  # run_fused looks the name up at call time
    rc = cli_main(argv)
    if rc == 0:
        state = kept["res"]["state"]
        leaves = jax.tree.leaves(state)
        on_four = sum(
            1 for l in leaves
            if len({s.device for s in l.addressable_shards}) == 4
            and not l.sharding.is_fully_replicated
        )
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        placed = {
            "device_count": jax.device_count(),
            "leaves": len(leaves),
            "leaves_on_four_devices": on_four,
            "bytes_in_use": [s.get("bytes_in_use") for s in stats],
        }
        tmp = f"{placement_path}.tmp"
        with open(tmp, "w") as f:
            json.dump(placed, f)
        os.replace(tmp, placement_path)  # whole or absent, never half
    return rc


# -- entry ---------------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run ONLY the four-chip mesh phase and its one-chip comparison",
    )
    p.add_argument(
        "--rehearse", action="store_true",
        help="tiny sizes on whatever platform jax finds: a rehearsal of the "
        "control flow that can never report ok",
    )
    p.add_argument("--mesh-child", metavar="PLACEMENT.json", help=argparse.SUPPRESS)
    p.add_argument("rest", nargs=argparse.REMAINDER, help=argparse.SUPPRESS)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.mesh_child:
        rest = args.rest[1:] if args.rest[:1] == ["--"] else args.rest
        return mesh_child(args.mesh_child, rest)
    t0 = time.monotonic()
    dev = probe(args.rehearse)
    for d in (OUT, BIG):
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
    try:
        if not args.rehearse:
            check(dev["count"] == args.chips, f"--chips {args.chips} on {dev['count']} devices")
        (four_chips if args.chips == 4 else one_chip)(dev, args.rehearse)
    except Failed as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        shutil.rmtree(BIG, ignore_errors=True)
    say(f"all phases passed in {time.monotonic() - t0:.0f}s")
    device = {k: dev[k] for k in ("platform", "kind", "count")}
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
