"""Headline benchmark: the north-star PBT sweep (small CNN, CIFAR-10).

Prints exactly ONE JSON line on stdout. Required keys:
    {"metric": ..., "value": N, "unit": "trials/sec/chip", "vs_baseline": N}
plus honesty/utilization extras: mfu, flops accounting, BOTH baseline
normalizations, and wall-clock-to-target-accuracy (the second metric of
record in BASELINE.json).

Unit of work ("trial") = one PBT member-generation: steps_per_gen
training steps + a full validation eval for one population member.
Both sides do identical work on identical shapes.

- TPU side: the fused on-device PBT sweep (train/fused_pbt.py) —
  population x generations member-generations in one XLA program on
  the real chip. A structurally-identical warmup run (same static args)
  populates the compile cache first so the measurement is steady-state
  throughput, which is what a >1-generation sweep experiences.
  The default population is 256 — the north-star sweep size
  (BASELINE.json: "256-member PBT CIFAR-10 CNN sweep").

- Baseline: a torch-CPU member-generation — the reference's actual
  per-rank stack (torch/keras on CPU over MPI), same layer shapes,
  batch, and eval size, single-threaded like one MPI rank. Measured
  directly (~80 s/member-gen on this box, fast enough to measure
  live). This is deliberately the STRONGEST honest baseline available:
  our own CPU backend (XLA:CPU) executes conv training at ~0.7 GFLOP/s
  on this host vs torch's ~46 GFLOP/s — a pathology of XLA:CPU codegen
  here, not a property of the reference — so using it as the
  denominator would inflate the speedup ~65x. The jax-pool protocol
  remains available via --baseline-pool (cached in CPU_BASELINE.json;
  takes ~40 min first-ever). Full story: PERF_NOTES.md.

Baseline normalizations (both reported; the headline ``vs_baseline`` is
the 8-rank one):
- ``vs_baseline`` / ``vs_8rank_equiv``: TPU throughput vs an 8-rank
  pool at 8x the measured single-rank rate. This box has
  os.cpu_count()=1, so a real 8-rank pool would timeshare one core;
  linear scaling is the generous-to-the-baseline stand-in for the
  north star's "8-rank MPI" (zero MPI overhead charged).
- ``vs_one_rank``: TPU throughput vs the single measured rank.

MFU: sweep FLOPs (composed from single-trip XLA cost-analysis pieces —
see utils/flops.py for why whole-program counts can't be trusted)
divided by (wall x chip bf16 peak), and also divided by the *measured*
matmul cap of this device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def bench_tpu(args):
    from mpi_opt_tpu.utils.compile_cache import wire_compile_cache

    wire_compile_cache()
    import jax

    if jax.default_backend() != "tpu":
        # a measurement path that finds no chip fails; XLA:CPU numbers
        # must never land under this record's device metrics
        sys.exit(
            f"bench.py measures the chip and jax found platform "
            f"{jax.default_backend()!r}; run it where a TPU is attached"
        )
    from mpi_opt_tpu.train.fused_pbt import fused_pbt
    from mpi_opt_tpu.utils.flops import mfu, population_sweep_flops
    from mpi_opt_tpu.utils.profiling import profile_window
    from mpi_opt_tpu.workloads import get_workload

    wl = get_workload("cifar10_cnn")
    population, generations, steps = args.population, args.generations, args.steps
    log(f"[bench] tpu side: backend={jax.default_backend()} pop={population} "
        f"gens={generations} steps={steps} member_chunk={args.member_chunk} "
        f"gen_chunk={args.gen_chunk}")
    kw = dict(
        population=population,
        generations=generations,
        steps_per_gen=steps,
        seed=args.seed,
        member_chunk=args.member_chunk,
        gen_chunk=args.gen_chunk,
    )
    # span tracing across warmup + measurement (opt-out: --no-trace):
    # the attribution JSON rides in the bench record, so BENCH_r06+
    # carries compile-vs-train-vs-save seconds — including the warmup
    # compile wall the ROADMAP wants measured — beside trials/s
    trace_prior = trace_metrics = trace_path = None
    if not args.no_trace:
        import tempfile

        from mpi_opt_tpu.obs import trace as _trace
        from mpi_opt_tpu.utils.metrics import MetricsLogger

        trace_path = args.trace_file or os.path.join(
            tempfile.mkdtemp(prefix="bench_trace_"), "bench.jsonl"
        )
        trace_metrics = MetricsLogger(path=trace_path)
        trace_prior = _trace.configure(trace_metrics)
    # warmup is an IDENTICAL invocation: generations is a static jit arg
    # (scan length), so only the same-arg call guarantees the measured
    # run is a pure cache hit / steady-state execution
    t0 = time.perf_counter()
    fused_pbt(wl, **kw)
    log(f"[bench] warmup (compile+run) {time.perf_counter()-t0:.1f}s")
    with profile_window(args.profile_dir):
        t0 = time.perf_counter()
        result = fused_pbt(wl, **kw)
        wall = time.perf_counter() - t0
    trace_rep = None
    if trace_prior is not None:
        from mpi_opt_tpu.obs import trace as _trace

        _trace.deconfigure(trace_prior)
        trace_metrics.close()
    # device-memory watermark (obs/memory.py): sampled AFTER the
    # measured run while the sweep's state is still resident, and
    # BEFORE the cap probe below — peak_bytes_in_use is process-
    # lifetime and cannot be reset, so the probe's ~100 MiB matmul
    # buffers would otherwise wear into the sweep's recorded watermark
    # (the number the wave-size/bf16 planning consumes)
    from mpi_opt_tpu.obs import memory as _obs_memory

    device_memory = _obs_memory.watermark()
    if device_memory is not None:
        log(f"[bench] device memory: {device_memory}")
    # the cap is measured AFTER tracing deconfigures (its probe compiles
    # must not pollute the attribution) and BEFORE the attribution is
    # built, so the embedded roofline is judged against the MEASURED
    # roof of this very device, not a calibration-table stand-in
    cap_tf = measure_platform_cap()
    if trace_prior is not None:
        from mpi_opt_tpu.obs.report import bench_attribution

        trace_rep = bench_attribution(trace_path, peak_tflops=cap_tf)
        log(f"[bench] trace stream {trace_path}: coverage {trace_rep['coverage']}")
        # intra-phase verdicts (ISSUE 11): the embed carries the full
        # bubbles/staging/roofline sections; the log shows the headline
        bub, roof = trace_rep.get("bubbles"), trace_rep.get("roofline")
        if bub is not None and bub.get("idle_frac") is not None:
            log(f"[bench] idle fraction {bub['idle_frac']:.1%} "
                f"({bub['idle_s']}s over {bub['gaps']} gap(s); "
                f"by cause: {bub['by_cause']})")
        stg = trace_rep.get("staging")
        if stg is not None and stg.get("overlap_frac") is not None:
            log(f"[bench] staging overlap {stg['overlap_frac']:.1%} "
                f"(hidden {stg['overlap_s']}s of {stg['transfer_s']}s)")
        if roof is not None:
            if roof.get("mxu_frac") is not None:
                log(f"[bench] roofline: {roof['bound']} "
                    f"(MXU {roof['mxu_frac']:.1%}, cap {roof['peak_tflops']} "
                    f"TF/s [{roof['peak_source']}])")
            else:
                log(f"[bench] roofline: {roof['bound']} (no platform cap — "
                    "measured on TPU backends only; MXU fraction unavailable)")
    trials = population * generations
    tps = trials / wall
    # flops accounting AFTER the timed window (it lowers/compiles tiny
    # one-member programs — that must not count against the sweep)
    flops = population_sweep_flops(
        wl, population, generations, steps, n_evals=generations
    )

    # wall-clock to target val-acc (metric of record #2): launch-granular
    # — launch boundaries use their measured durations, only generations
    # inside one launch are prorated (utils.metrics)
    from mpi_opt_tpu.utils.metrics import sweep_wall_to_target as _wtt

    curve = [float(v) for v in result["best_curve"]]
    wall_to_target = _wtt(result, wall, args.target_acc)

    util = mfu(flops, wall, jax.devices()[0])
    log(f"[bench] tpu: {trials} member-gens in {wall:.2f}s -> {tps:.3f} trials/s/chip; "
        f"best={result['best_score']:.3f} curve={[round(v, 3) for v in curve]}")
    if flops:
        log(f"[bench] flops={flops:.3e} ({flops/wall/1e12:.1f} TFLOP/s, "
            f"mfu={'-' if util is None else round(util, 4)} of nominal peak, "
            f"platform cap {cap_tf and round(cap_tf, 1)} TF/s)")
    return {
        "platform_matmul_tflops": round(cap_tf, 1) if cap_tf else None,
        "mfu_vs_platform_cap": (
            round(flops / wall / 1e12 / cap_tf, 4) if flops and cap_tf else None
        ),
        "tps": tps,
        "wall": wall,
        "best": float(result["best_score"]),
        "curve": curve,
        "wall_to_target": wall_to_target,
        "flops": flops,
        "mfu": util,
        "device": jax.devices()[0].device_kind,
        "trace": trace_rep,
        "trace_stream": trace_path if args.trace_file else None,
        "device_memory": device_memory,
    }


def measure_platform_cap(iters=4, loops=200):
    """Measured matmul throughput cap of THIS device (TF/s).

    bf16 4096^3 matmuls looped inside ONE program with only a scalar
    serial dependency between iterations, fetched once — so neither
    dispatch nor the fetch touches the number. It should approach the
    datasheet peak (197 TF/s in bf16 on a v5e); where a device delivers
    less, this is the real ceiling. Reported alongside nominal-peak
    MFU, never instead of it.

    History: an 8-deep ``b = (a @ b) * 1e-3`` chain underreads ~2.4x —
    the full-matrix dependency plus the elementwise rescale pass
    serialize HBM traffic (probes/probe_mxu_pack.py found the gap). The
    cap must be the strongest attainable measurement or "vs cap" ratios
    flatter us.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    M = 4096
    a = jax.random.normal(jax.random.key(0), (M, M), jnp.bfloat16)
    b = jax.random.normal(jax.random.key(1), (M, M), jnp.bfloat16) * 0.01

    @jax.jit
    def step(a, b):
        def body(i, s):
            x = a + s  # scalar serial dependency: no hoisting, no chain
            y = x @ b
            return jnp.sum(y).astype(jnp.bfloat16) * jnp.bfloat16(1e-9)

        return jax.lax.fori_loop(0, loops, body, jnp.bfloat16(0))

    float(step(a, b))  # warm (compile)
    t0 = time.perf_counter()
    for _ in range(iters):
        s = step(a, b)
    float(s)
    dt = (time.perf_counter() - t0) / iters
    return loops * 2 * M**3 / dt / 1e12


def bench_cpu_baseline_torch(steps, seed, measure_steps=20):
    """Reference-fidelity baseline: one MPI rank's member-generation in
    torch on CPU (the reference stack), single-threaded.

    Same work as one TPU-side member-generation: ``steps`` SGD+momentum
    steps on a SmallCNN of identical layer shapes at batch 256, plus a
    full 2048-image validation eval. Per-step cost is steady-state
    constant on CPU, so we measure ``measure_steps`` and scale — stated
    in the provenance. Augmentation is omitted on this side (the TPU
    side pays for it), which favors the baseline, i.e. is conservative
    for the reported speedup.

    Returns (trials_per_sec, provenance_str).
    """
    import torch
    import torch.nn.functional as tF
    from torch import nn

    torch.manual_seed(seed)
    torch.set_num_threads(1)  # one rank = one core, like the MPI reference

    w, n_classes, batch, n_val = 32, 10, 256, 2048

    class TorchSmallCNN(nn.Module):
        # mirrors models/cnn.py SmallCNN: conv32-conv32-pool-conv64-
        # conv64-pool-fc128-fc10, GroupNorm(8)
        def __init__(self):
            super().__init__()
            chans = [3, w, w, 2 * w, 2 * w]
            self.blocks = nn.ModuleList(
                nn.ModuleList([
                    nn.Conv2d(chans[i], chans[i + 1], 3, padding=1),
                    nn.GroupNorm(8, chans[i + 1]),
                ])
                for i in range(4)
            )
            self.fc1 = nn.Linear(2 * w * 8 * 8, 4 * w)
            self.fc2 = nn.Linear(4 * w, n_classes)

        def forward(self, x):
            for i, (conv, gn) in enumerate(self.blocks):
                x = tF.relu(gn(conv(x)))
                if i % 2 == 1:
                    x = tF.max_pool2d(x, 2)
            x = x.flatten(1)
            return self.fc2(tF.relu(self.fc1(x)))

    model = TorchSmallCNN()
    opt = torch.optim.SGD(model.parameters(), lr=0.1, momentum=0.9, weight_decay=1e-4)
    g = torch.Generator().manual_seed(seed)
    x = torch.randn(batch, 3, 32, 32, generator=g)
    y = torch.randint(0, n_classes, (batch,), generator=g)

    def step():
        opt.zero_grad()
        tF.cross_entropy(model(x), y).backward()
        opt.step()

    step(); step()  # warm (allocator, oneDNN primitive caches)
    t0 = time.perf_counter()
    for _ in range(measure_steps):
        step()
    per_step = (time.perf_counter() - t0) / measure_steps

    model.eval()
    vx = torch.randn(n_val, 3, 32, 32, generator=g)
    with torch.no_grad():
        model(vx[:batch])  # warm
        t0 = time.perf_counter()
        for i in range(0, n_val, batch):
            model(vx[i : i + batch])
        eval_s = time.perf_counter() - t0

    member_gen_s = steps * per_step + eval_s
    tps = 1.0 / member_gen_s
    provenance = (
        f"torch-CPU single-thread (reference per-rank stack), same layer "
        f"shapes/batch/eval: {per_step:.2f}s/step x {steps} + {eval_s:.1f}s "
        f"eval = {member_gen_s:.1f}s/member-gen (per-step measured over "
        f"{measure_steps} steady-state steps)"
    )
    log(f"[bench] cpu baseline (torch): {provenance} -> {tps:.5f} trials/s/rank")
    return tps, provenance


def bench_cpu_baseline(steps, seed, n_workers, cache_path="CPU_BASELINE.json",
                       b_small=2, b_large=12):
    """Reference-architecture stand-in: process-per-trial evaluation,
    genuinely on CPU (the pool worker pins the platform).

    A real 100-step member-generation takes this box's single core tens
    of minutes (round 1's '5.79s' baseline was secretly running on the
    TPU through the then-unpinned inline path — fixed since, and the
    honest number is ~400x slower). Measuring cost(steps) directly is
    therefore infeasible inside a bench run; instead we measure
    cost(b_small) and cost(b_large) warm (the per-step cost on one core
    is strictly linear — no batching/caching effects across steps) and
    extrapolate: cost(S) = cost(b_small) + slope * (S - b_small), where
    the intercept carries the fixed per-trial work (final eval +
    dispatch). The result, with its full provenance, is cached in
    ``cache_path`` so repeat bench runs (e.g. the driver's) don't repay
    a multi-minute measurement; delete the file to re-measure.
    """
    import json as _json
    import os as _os

    import jax

    from mpi_opt_tpu.backends.cpu import CPUBackend
    from mpi_opt_tpu.trial import Trial
    from mpi_opt_tpu.workloads import get_workload

    # cache key covers everything that changes the measured number: the
    # workload/model, the measurement protocol (b_small/b_large +
    # extrapolation scheme, versioned), and the run shape — a stale
    # cache must re-measure, not silently feed the headline vs_baseline
    # (ADVICE round 2)
    workload_name = "cifar10_cnn"
    protocol = 2  # bump when the measurement scheme changes
    cache_key = {
        "steps": steps,
        "n_workers": n_workers,
        "workload": workload_name,
        "b_small": b_small,
        "b_large": b_large,
        "protocol": protocol,
    }
    if _os.path.exists(cache_path):
        with open(cache_path) as f:
            rec = _json.load(f)
        if all(rec.get(k) == v for k, v in cache_key.items()):
            log(f"[bench] cpu baseline from {cache_path}: "
                f"{rec['pool_trials_per_sec']:.6f} trials/s ({rec['provenance']})")
            return rec["pool_trials_per_sec"]

    wl = get_workload(workload_name)
    space = wl.default_space()
    be = CPUBackend(wl, n_workers=n_workers, seed=seed)

    def make_trials(base_id, budget):
        out = []
        for i in range(n_workers):
            key = jax.random.fold_in(jax.random.key(seed), base_id + i)
            unit = __import__("numpy").asarray(space.sample_unit(key, 1))[0]
            out.append(
                Trial(
                    trial_id=base_id + i,
                    params=space.materialize_row(unit),
                    unit=unit,
                    budget=budget,
                )
            )
        return out

    def timed_eval(base_id, budget):
        """Wall for one batch of n_workers PARALLEL trials (pool.map):
        with perfect scaling this equals one trial's cost, and the pool
        completes n_workers trials per such wall."""
        t0 = time.perf_counter()
        be.evaluate(make_trials(base_id, budget))
        return time.perf_counter() - t0

    log(f"[bench] cpu baseline: warming {n_workers}-process pool "
        f"(compiles budget={b_small}/{b_large} programs; slow first-ever)")
    t0 = time.perf_counter()
    timed_eval(0, b_small)  # compile+run small program
    timed_eval(100, b_large)  # compile+run large program
    log(f"[bench] pool warm in {time.perf_counter()-t0:.1f}s")
    c_small = timed_eval(200, b_small)
    c_large = timed_eval(300, b_large)
    be.close()
    slope = max((c_large - c_small) / (b_large - b_small), 0.0)
    c_steps = c_small + slope * (steps - b_small)
    # the pool finishes n_workers parallel trials per c_steps of wall
    pool_tps = n_workers / c_steps
    provenance = (
        f"linear extrapolation: batch-wall({b_small})={c_small:.1f}s, "
        f"batch-wall({b_large})={c_large:.1f}s -> {slope:.2f}s/step, "
        f"batch-wall({steps})={c_steps:.1f}s for {n_workers} parallel "
        f"trials, measured on a platform-pinned CPU pool"
    )
    log(f"[bench] cpu: {provenance} -> {pool_tps:.6f} trials/s ({n_workers} procs)")
    rec = {
        **cache_key,
        "cost_small_s": round(c_small, 2),
        "cost_large_s": round(c_large, 2),
        "slope_s_per_step": round(slope, 3),
        "cost_steps_s": round(c_steps, 2),
        "pool_trials_per_sec": pool_tps,
        "provenance": provenance,
    }
    # tmp+replace: a Ctrl-C mid-dump must not leave a torn cache
    # file that every later bench run trips over (sweeplint
    # atomic-write — the same idiom as service/spool status writes)
    tmp = f"{cache_path}.tmp{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            _json.dump(rec, f, indent=1)
        os.replace(tmp, cache_path)
    except OSError as e:
        log(f"[bench] could not cache baseline: {e}")
    finally:
        if os.path.exists(tmp):  # failed mid-write: no orphan debris
            os.unlink(tmp)
    return pool_tps


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--population", type=int, default=256)
    p.add_argument("--generations", type=int, default=4)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--member-chunk", type=int, default=32)
    p.add_argument(
        "--gen-chunk",
        type=int,
        default=1,
        help="generations per program launch",
    )
    p.add_argument("--target-acc", type=float, default=0.70)
    p.add_argument("--workers", type=int, default=min(8, os.cpu_count() or 8))
    p.add_argument("--skip-baseline", action="store_true")
    p.add_argument(
        "--baseline-pool",
        action="store_true",
        help="use the jax-CPU process pool as the baseline instead of the "
        "torch reference stack (slow + understates the reference on this "
        "host; see PERF_NOTES.md)",
    )
    p.add_argument("--profile-dir", default=None)
    p.add_argument(
        "--no-trace",
        action="store_true",
        help="measure without span tracing (drops the phase breakdown)",
    )
    p.add_argument(
        "--trace-file",
        default=None,
        help="keep the span-trace stream here (default: a temp file — "
        "only the attribution lands in the record)",
    )
    args = p.parse_args()

    from mpi_opt_tpu.obs.diff import BENCH_SCHEMA_VERSION

    tpu = bench_tpu(args)
    record = {
        # versioned record shape: the bench-record drift gate
        # (tests/test_bench_schema.py) and `trace --diff`'s trajectory
        # loading both key on it — bump obs/diff.py BENCH_SCHEMA_VERSION
        # when the shape changes, never drift silently
        "schema_version": BENCH_SCHEMA_VERSION,
        "metric": "pbt_cifar10_cnn_member_generations_per_sec_per_chip",
        "value": round(tpu["tps"], 4),
        "unit": "trials/sec/chip",
        "population": args.population,
        "generations": args.generations,
        "steps_per_gen": args.steps,
        "device": tpu["device"],
        "best_val_acc": round(tpu["best"], 4),
        "target_acc": args.target_acc,
        "wall_to_target_s": (
            round(tpu["wall_to_target"], 2) if tpu["wall_to_target"] is not None else None
        ),
        "flops_total": tpu["flops"],
        "tflops_per_sec": (
            round(tpu["flops"] / tpu["wall"] / 1e12, 2) if tpu["flops"] else None
        ),
        "mfu": round(tpu["mfu"], 4) if tpu["mfu"] is not None else None,
        "platform_matmul_tflops": tpu["platform_matmul_tflops"],
        "mfu_vs_platform_cap": tpu["mfu_vs_platform_cap"],
        # span-trace phase attribution (obs/): compile vs train vs save
        # seconds + achieved TF/s per launch + time-to-first-trial, plus
        # the round-8 intra-phase sections (bubbles/staging/roofline) —
        # None under --no-trace
        "trace": tpu["trace"],
        "trace_stream": tpu["trace_stream"],
        # device-memory watermark (obs/memory.py): peak/steady HBM with
        # its accounting source — None only in a jax-less environment
        "device_memory": tpu["device_memory"],
    }
    if args.skip_baseline:
        record["vs_baseline"] = 1.0
        record["baseline"] = "skipped"
    else:
        if args.baseline_pool:
            pool_tps = bench_cpu_baseline(args.steps, args.seed, args.workers)
            per_rank = pool_tps / args.workers
            prov = (
                f"jax-CPU {args.workers}-proc pool (XLA:CPU runs convs at "
                f"~0.7 GFLOP/s on this host — understates the reference ~65x; "
                f"PERF_NOTES.md)"
            )
        else:
            per_rank, prov = bench_cpu_baseline_torch(args.steps, args.seed)
        rank8 = 8.0 * per_rank
        record["cpu_rank_trials_per_sec"] = round(per_rank, 5)
        record["vs_one_rank"] = round(tpu["tps"] / per_rank, 2)
        record["vs_8rank_equiv"] = round(tpu["tps"] / rank8, 2)
        # the headline number is the HONEST normalization: one chip vs an
        # 8-rank pool at the measured single-rank rate (linear scaling
        # assumed for the baseline — generous to it: zero MPI overhead)
        record["vs_baseline"] = record["vs_8rank_equiv"]
        record["baseline"] = f"8-rank equivalent = 8 x single-rank rate; rank = {prov}"
    print(json.dumps(record))


if __name__ == "__main__":
    main()
