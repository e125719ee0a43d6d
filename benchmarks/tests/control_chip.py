"""The readings a cell's limits are set from, taken on the chip.

    chiprun -- python benchmarks/tests/control_chip.py --cell <cell> --seeds 12

In ONE process (set-up is long): for each seed the program runs two
generations of the cell's own sweep through `cli.main` (the timed
programs, at the timed sizes) with the harness's capture at the first
boundary, and the plain reference follows the sampled slots' source
members. Read for every sampled slot, each against the float32
reference: the norm gaps (update, momentum; worst leaf) and the score
gap in rows of

  sound      the program
  witness    the reference in the configuration's own bfloat16 compute
  fp8        the control: reference with operands rounded to float8_e4m3fn
  store16    the control: reference keeping parameters and momentum in
             bfloat16 between steps
and, on the first `--fault-seeds` seeds, the planted fault
  halfbatch  half of each minibatch left out, the mean over the rest
(a state left unchanged reads 1 by the norm measure and needs no run),
and, with `--momentum-seeds`, the program's own lower-precision path
(`MPI_OPT_TPU_MOMENTUM_DTYPE=bfloat16`) in the program's place.
One JSON line a seed goes to `chiprun_out/control/<cell>.jsonl`; the
summary is printed last.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import check  # noqa: E402
import run as harness  # noqa: E402
import window as window_mod  # noqa: E402


def program_run(cfg, traffic, seed, path, slots, workload=None):
    """Two generations through cli.main; (ledger generations, capture)."""
    from mpi_opt_tpu import cli
    from mpi_opt_tpu.health import shutdown

    if os.path.exists(path):
        os.remove(path)
    argv = ["--workload", cfg["workload"], "--seed", str(seed), "--generations", "2"]
    argv += ["--ledger", path] + list(traffic["cli"])
    kw = {"_workload": workload} if workload is not None else {}
    held = {}
    win = window_mod.Window(
        1e9, lambda: None, on_open=lambda: held.update(state=check.capture_slots(slots))
    )
    shutdown.set_slice_hook(win.hook)
    try:
        rc = cli.main(argv, **kw)
    finally:
        shutdown.clear_slice_hook()
    if rc != 0:
        raise RuntimeError(f"sweep exit {rc}")
    return check.read_ledger(path)[1], held.get("state")


def slot_readings(ref, slots, srcs, followed_base, got):
    """Per slot: worst-leaf update and momentum gaps, the worst leaves'
    names, and the median leaf's update gap."""
    import numpy as np

    out = []
    for j, i in enumerate(slots):
        s = int(srcs[0][i])
        gaps = check.leaf_gaps(ref, s, got["params"](j, s), got["momentum"](j, s), followed_base[s][:2])
        ups = sorted(((u, "/".join(k)) for k, (u, _) in gaps.items()), reverse=True)
        mos = sorted(((m, "/".join(k)) for k, (_, m) in gaps.items()), reverse=True)
        out.append({
            "update": ups[0][0], "update_leaf": ups[0][1], "update_2nd": ups[1][0],
            "update_median": float(np.median([u for u, _ in ups])),
            "momentum": mos[0][0], "momentum_leaf": mos[0][1],
            "momentum_median": float(np.median([m for m, _ in mos])),
        })
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2200000001)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--momentum-seeds", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax

    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    elif jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    _, cell, cfg, traffic, _ = harness.resolve_cell(args.cell, args.rehearse)
    wl = harness.rehearse_workload(cfg) if args.rehearse else None
    out_dir = os.path.join(ROOT, "chiprun_out", "control")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, args.cell + ".jsonl")
    ledger_path = os.path.join(BENCH, "out", "control_ledger.jsonl")
    os.makedirs(os.path.dirname(ledger_path), exist_ok=True)
    population, steps = traffic["population"], traffic["steps_per_generation"]
    n_val = cfg["data"]["n_val"]
    k = min(12, traffic["check"]["sample_members"])  # all followed here, whatever their rate
    lines = []
    for n in range(args.seeds):
        seed = args.first_seed + 7919 * n
        slots = check.sample_members(seed, population, k)
        t0 = time.time()
        gens, captured = program_run(cfg, traffic, seed, ledger_path, slots, wl)
        t_prog = time.time() - t0
        ref = check.Reference(cfg, population, steps, seed)
        missing, hgap, units, srcs = check.replay_decisions(ref, gens, steps)
        t0 = time.time()
        base = check.follow_sources(ref, slots, units, srcs)
        t_ref = time.time() - t0
        hp = {kk: [float(v[int(srcs[0][i])]) for i in slots] for kk, v in ref.hparams(units[0]).items()}
        from_capture = lambda c: {
            "params": lambda j, s: {kk: v[j] for kk, v in c["params"].items()},
            "momentum": lambda j, s: {kk: v[j] for kk, v in c["momentum"].items()},
        }
        from_followed = lambda f: {
            "params": lambda j, s: f[s][0], "momentum": lambda j, s: f[s][1],
        }
        journaled = [float(gens[0][int(srcs[0][i])]["score"]) * n_val for i in slots]
        base_rows = [base[int(srcs[0][i])][2] for i in slots]
        rec = {
            "seed": seed, "t_prog_s": t_prog, "t_ref_s": t_ref, "records_missing": missing,
            "hparam_gap": hgap, "slots": slots, "sources": [int(srcs[0][i]) for i in slots],
            "lr": hp["lr"], "momentum_hp": hp["momentum"], "reference_rows": base_rows,
            "sound": slot_readings(ref, slots, srcs, base, from_capture(captured)),
            "sound_rows": [abs(a - b) for a, b in zip(journaled, base_rows)],
        }
        for label, mode, store in (
            ("witness", "bf16", "float32"), ("fp8", "fp8", "float32"), ("store16", "bf16", "bfloat16"),
        ):
            f = check.follow_sources(ref, slots, units, srcs, mode, store)
            rec[label] = slot_readings(ref, slots, srcs, base, from_followed(f))
            rec[label + "_rows"] = [abs(f[int(srcs[0][i])][2] - b) for i, b in zip(slots, base_rows)]
        if n < args.fault_seeds:
            half = check.Reference(cfg, population, steps, seed, keep_rows=cfg["batch_size"] // 2)
            f = check.follow_sources(half, slots, units, srcs)
            rec["halfbatch"] = slot_readings(ref, slots, srcs, base, from_followed(f))
            rec["halfbatch_rows"] = [abs(f[int(srcs[0][i])][2] - b) for i, b in zip(slots, base_rows)]
        if n < args.momentum_seeds:
            os.environ["MPI_OPT_TPU_MOMENTUM_DTYPE"] = "bfloat16"
            try:
                _, c16 = program_run(cfg, traffic, seed, ledger_path, slots, wl)
            finally:
                del os.environ["MPI_OPT_TPU_MOMENTUM_DTYPE"]
            rec["momentum16"] = slot_readings(ref, slots, srcs, base, from_capture(c16))
        lines.append(rec)
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps(rec), flush=True)
    summary = {"cell": args.cell, "seeds": len(lines)}
    for label in ("sound", "witness", "fp8", "store16", "halfbatch", "momentum16"):
        rows = [r[label] for r in lines if label in r]
        if rows:
            summary[label] = {
                "update_worst_by_seed": [round(max(s["update"] for s in r), 4) for r in rows],
                "update_median_leaf_by_seed": [round(max(s["update_median"] for s in r), 4) for r in rows],
                "momentum_worst_by_seed": [round(max(s["momentum"] for s in r), 4) for r in rows],
                "momentum_median_leaf_by_seed": [round(max(s["momentum_median"] for s in r), 4) for r in rows],
            }
    summary["hparam_gap_max"] = max(r["hparam_gap"] for r in lines)
    summary["records_missing_max"] = max(r["records_missing"] for r in lines)
    print("SUMMARY " + json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
