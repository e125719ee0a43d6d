"""Readings for a cell whose configuration lists its planted controls.

    python benchmarks/tests/control_planted.py --cell <cell> --seeds 1 --reference-only [--out-dir D]

`control_mechanisms.py`'s method for a configuration whose file names
its controls under `"controls"` (`{label: cfg["control"]}`, which its
reference module reads): for each seed the sampled slots' source
members are followed on the float32 reference, and then again, once a
control, with the reference run another way. `store16`, `witness` and
`fp8` are the reference's own precisions (`control_chip.py`); every
other label is a planted control. Without `--reference-only` the
program's two generations run first through `cli.main` and give the
`sound` reading. A control that the comparison catches reads above the
limit by one of the numbers `check.py` prints. One JSON line a seed goes
to `<out-dir>/<cell>.planted.jsonl` (default `benchmarks/out/control`),
and every reading to stderr as soon as it is made.
"""

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import check  # noqa: E402
import control_chip  # noqa: E402
import run as harness  # noqa: E402

PRECISIONS = {"store16": ("bf16", "bfloat16"), "witness": ("bf16", "float32"), "fp8": ("fp8", "float32")}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--first-seed", type=int, default=2200000001)
    ap.add_argument("--controls", default=None, help="labels in the order they are read (default: store16, fp8 and every planted one)")
    ap.add_argument("--reference-only", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out-dir", default=os.path.join(BENCH, "out", "control"))
    args = ap.parse_args()

    import jax

    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    elif jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    _, cell, cfg, traffic, _ = harness.resolve_cell(args.cell, args.rehearse)
    planted = cfg.get("controls", {})
    labels = args.controls.split(",") if args.controls else ["store16", "fp8", *planted]
    wl = harness.rehearse_workload(cfg) if args.rehearse else None
    os.makedirs(args.out_dir, exist_ok=True)
    out_path = os.path.join(args.out_dir, args.cell + ".planted.jsonl")
    ledger_path = os.path.join(BENCH, "out", "control_ledger.jsonl")
    os.makedirs(os.path.dirname(ledger_path), exist_ok=True)
    population, steps = traffic["population"], traffic["steps_per_generation"]
    for n in range(args.seeds):
        seed = args.first_seed + 7919 * n
        slots = check.sample_members(seed, population, traffic["check"]["sample_members"])
        ref = check.Reference(cfg, population, steps, seed)
        rec = {"seed": seed, "slots": slots}
        if args.reference_only:
            units, srcs = [ref.initial_unit()], [list(range(population))]
        else:
            t0 = time.time()
            gens, captured = control_chip.program_run(cfg, traffic, seed, ledger_path, slots, wl)
            rec["t_prog_s"] = time.time() - t0
            jax.clear_caches()
            gc.collect()
            rec["records_missing"], rec["hparam_gap"], units, srcs = check.replay_decisions(ref, gens, steps)
        t0 = time.time()
        # every followed member is kept on the host: a member of half a
        # billion parameters left on the device beside the next
        # reference's does not fit one chip
        on_host = lambda f: {s: jax.device_get(v) for s, v in f.items()}
        base = on_host(check.follow_sources(ref, slots, units, srcs))
        rec["t_ref_s"] = time.time() - t0
        sources = [int(srcs[0][i]) for i in slots]
        rec["sources"] = sources
        rec["lr"] = [float(ref.hparams(units[0])["lr"][s]) for s in sources]
        rec["reference_score"] = [base[s][2] for s in sources]
        if not args.reference_only:
            got = {
                "params": lambda j, s: {k: v[j] for k, v in captured["params"].items()},
                "momentum": lambda j, s: {k: v[j] for k, v in captured["momentum"].items()},
            }
            rec["sound"] = control_chip.slot_readings(ref, slots, srcs, base, got)
            rec["sound_score_gap"] = [
                abs(ref.journaled_score(float(gens[0][s]["score"])) - b) for s, b in zip(sources, rec["reference_score"])
            ]
            del captured, got
            print(json.dumps({"seed": seed, "so_far": "sound", "readings": rec["sound"]}), file=sys.stderr, flush=True)
        followed = lambda f: {"params": lambda j, s: f[s][0], "momentum": lambda j, s: f[s][1]}
        for label in labels:
            t0 = time.time()
            if label in PRECISIONS:
                r, (mode, store) = ref, PRECISIONS[label]
            else:
                r, mode, store = check.Reference(dict(cfg, control=planted[label]), population, steps, seed), "f32", "float32"
            f = on_host(check.follow_sources(r, slots, units, srcs, mode, store))
            rec[label] = control_chip.slot_readings(ref, slots, srcs, base, followed(f))
            rec[label + "_score_gap"] = [abs(f[s][2] - b) for s, b in zip(sources, rec["reference_score"])]
            rec[label + "_s"] = time.time() - t0
            del r, f
            jax.clear_caches()
            gc.collect()
            print(json.dumps({"seed": seed, "so_far": label, "readings": rec[label], "score_gap": rec[label + "_score_gap"], "s": rec[label + "_s"]}), file=sys.stderr, flush=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
