"""Run a set of benchmark runs on the chip, one process each, and keep
their result lines.

    chiprun -- python benchmarks/tests/chip_set.py --cell <cell> --seeds 11,12 --trace 0 --label setA

This parent never touches jax (a chip belongs to one process). Each
run's last line, with its wall time and exit code, is appended to
`chiprun_out/sets/<cell>.<label>.jsonl`; stderr tails go beside it.
"""

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--trace", default="0", help="0, 1, or one flag a seed: 0,0,1")
    ap.add_argument("--seconds", default="51")
    ap.add_argument("--label", default="set")
    ap.add_argument("--keep-ledger", action="store_true")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    traces = args.trace.split(",")
    if len(traces) == 1:
        traces = traces * len(seeds)
    out_dir = os.path.join(ROOT, "chiprun_out", "sets")
    os.makedirs(out_dir, exist_ok=True)
    base = os.path.join(out_dir, f"{args.cell}.{args.label}")
    bad = 0
    for n, (seed, trace) in enumerate(zip(seeds, traces)):
        cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", args.cell,
               "--seed", str(seed), "--seconds", args.seconds, "--trace", trace]
        t0 = time.time()
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        wall = time.time() - t0
        lines = p.stdout.strip().splitlines()
        try:
            result = json.loads(lines[-1]) if lines else None
        except ValueError:
            result = None
        rec = {"n": n, "seed": seed, "trace": int(trace), "rc": p.returncode, "wall_s": wall, "result": result}
        with open(base + ".jsonl", "a") as f:
            f.write(json.dumps(rec) + "\n")
        with open(base + f".{n}.err", "w") as f:
            f.write(p.stderr[-8000:])
        if args.keep_ledger:
            src = os.path.join(ROOT, "benchmarks", "out", f"{args.cell}.t{trace}", "ledger.jsonl")
            if os.path.exists(src):
                with open(src) as a, open(base + f".{n}.ledger.jsonl", "w") as b:
                    b.write(a.read())
        ok = p.returncode == 0 and result is not None and result.get("correct")
        bad += 0 if ok else 1
        brief = {"seed": seed, "trace": int(trace), "rc": p.returncode, "wall_s": round(wall, 1)}
        if result:
            brief["correct"] = result["correct"]
            brief["metrics"] = {k: v["value"] for k, v in result["metrics"].items()}
            brief["device"] = result["device"]
            brief["window"] = {k: result["window"][k] for k in ("generations", "seconds", "check_s")}
            brief["compared"] = result["compared"]
        else:
            brief["stderr_tail"] = p.stderr[-1500:]
        print(json.dumps(brief), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
