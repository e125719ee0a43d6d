"""Readings for a cell whose configuration plants its own controls.

    chiprun -- python benchmarks/tests/control_mechanisms.py --cell <cell> --seeds 2 --slots 2

`control_chip.py`'s method (the program's two generations through
`cli.main` with the observer at the first boundary; the plain reference
follows the sampled slots' source members; every reading is a gap
against the float32 reference) with, beside `sound`, `witness` and
`store16`, one reading for every other entry of `--controls`: the reference
run with `cfg["control"]` set to that entry, which the configuration's
reference module reads (`reference/keye_vl2_30b_a3b.py`: the selection
left out, another top-k, the indexer's loss left out). A control that
the comparison catches reads above the limit by one of the numbers
`check.py` prints. Only `--slots` of the sampled slots are followed
(a reference member costs minutes at the timed sizes). One JSON line a
seed goes to `chiprun_out/control/<cell>.mechanisms.jsonl`, and every
reading to stderr as soon as it is made. `--reference-only` leaves the
sweep and the `sound` reading out: a control's gap is between two
references, and at the timed sizes the sweep beside them met the
one-chip machine's 40 GiB of host memory (PERF.md section 7).
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

CONTROLS = {
    "selection_off": {"selection": "off"},
    "topk_half": None,  # filled from the configuration: half its top-k
    "index_loss_off": {"index_loss": "off"},
}


def _rss_gib() -> float:
    with open("/proc/self/status") as f:
        return next(int(l.split()[1]) for l in f if l.startswith("VmRSS")) / 2**20


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2200000001)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument(
        "--controls", default="selection_off,topk_half,index_loss_off,store16,witness",
        help="in the order they are read; store16 and witness are the reference's own precisions",
    )
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument(
        "--reference-only", action="store_true",
        help="no sweep and no `sound` reading: the controls against the float32 reference alone (a third of the time)",
    )
    args = ap.parse_args()

    import jax

    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    elif jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    _, cell, cfg, traffic, _ = harness.resolve_cell(args.cell, args.rehearse)
    wl = harness.rehearse_workload(cfg) if args.rehearse else None
    controls = dict(CONTROLS, topk_half={"topk": cfg["sa_config"]["topk"] // 2})
    out_dir = os.path.join(ROOT, "chiprun_out", "control")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, args.cell + ".mechanisms.jsonl")
    ledger_path = os.path.join(BENCH, "out", "control_ledger.jsonl")
    os.makedirs(os.path.dirname(ledger_path), exist_ok=True)
    population, steps = traffic["population"], traffic["steps_per_generation"]
    for n in range(args.seeds):
        seed = args.first_seed + 7919 * n
        sampled = check.sample_members(seed, population, traffic["check"]["sample_members"])
        ref = check.Reference(cfg, population, steps, seed)
        slots = sampled[: args.slots]
        if args.reference_only:
            # no sweep: the controls' gaps are between two references, and the
            # first generation's members are their own sources
            gens, t_prog, missing, hgap = None, None, None, None
            units, srcs = [ref.initial_unit()], [list(range(population))]
        else:
            t0 = time.time()
            gens, captured = control_chip.program_run(cfg, traffic, seed, ledger_path, sampled, wl)
            t_prog = time.time() - t0
            jax.clear_caches()  # the sweep's programs: the references need the host's memory
            gc.collect()
            missing, hgap, units, srcs = check.replay_decisions(ref, gens, steps)
        t0 = time.time()
        base = check.follow_sources(ref, slots, units, srcs)
        t_ref = time.time() - t0
        lr = ref.hparams(units[0])["lr"]
        followed = lambda f: {"params": lambda j, s: f[s][0], "momentum": lambda j, s: f[s][1]}
        sources = [int(srcs[0][i]) for i in slots]
        base_rows = [base[s][2] for s in sources]
        rec = {
            "seed": seed, "t_prog_s": t_prog, "t_ref_s": t_ref, "records_missing": missing,
            "hparam_gap": hgap, "slots": slots, "sources": sources,
            "lr": [float(lr[s]) for s in sources], "reference_score": base_rows,
        }
        if gens is not None:
            got = {
                "params": lambda j, s: {k: v[sampled.index(slots[j])] for k, v in captured["params"].items()},
                "momentum": lambda j, s: {k: v[sampled.index(slots[j])] for k, v in captured["momentum"].items()},
            }
            rec["sound"] = control_chip.slot_readings(ref, slots, srcs, base, got)
            rec["sound_score_gap"] = [
                abs(ref.journaled_score(float(gens[0][s]["score"])) - b) for s, b in zip(sources, base_rows)
            ]
            del captured, got
        print(json.dumps({"seed": seed, "so_far": "sound", "readings": rec.get("sound"), "t_ref_s": t_ref, "rss_gib": _rss_gib()}), file=sys.stderr, flush=True)
        # the controls first: a cut call keeps what the limits need most. A
        # control's reference is built when its turn comes and dropped with
        # its compiled programs after it: at the timed sizes three of them
        # alive at once met the machine's 40 GiB
        precisions = {"store16": (None, "bf16", "bfloat16"), "witness": (None, "bf16", "float32")}
        variants = [
            (name, *precisions.get(name, (dict(cfg, control=controls.get(name)), "f32", "float32")))
            for name in args.controls.split(",")
            if name
        ]
        for label, control_cfg, mode, store in variants:
            t0 = time.time()
            r = ref if control_cfg is None else check.Reference(control_cfg, population, steps, seed)
            f = check.follow_sources(r, slots, units, srcs, mode, store)
            rec[label] = control_chip.slot_readings(ref, slots, srcs, base, followed(f))
            rec[label + "_score_gap"] = [abs(f[s][2] - b) for s, b in zip(sources, base_rows)]
            rec[label + "_s"] = time.time() - t0
            del r, f
            if control_cfg is not None:
                jax.clear_caches()
            gc.collect()
            # a reference member costs minutes at the timed sizes: what is read so far survives a cut call
            print(
                json.dumps({"seed": seed, "so_far": label, "readings": rec[label], "score_gap": rec[label + "_score_gap"], "s": rec[label + "_s"], "rss_gib": _rss_gib()}),
                file=sys.stderr, flush=True,
            )
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
