"""Probe: does storing momentum in bf16 buy back optimizer-update bandwidth?

Round-2 trace: the per-layer SGD+momentum update fusions are ~26% of
device time and run at the platform's measured effective HBM bandwidth
(PERF_NOTES.md "Trace-level breakdown") — not fusible further, so the
only lever is BYTES. Momentum stored bf16 cuts the update's traffic
from 20 B/elem (read g,m,p + write m,p at f32) to 16 B/elem — a ~20%
cut on a 26% slice, ~5% end-to-end ceiling. Worth one measured A/B:
throughput AND learning (bf16 momentum rounds small gradient
accumulations to zero; the probe must show the curve is intact, not
just that it's faster — the pool-swap probe died on exactly that).

A/B on the real chip, north-star shapes (SmallCNN, pop=256, batch 256):
  python probes/probe_bf16_momentum.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax


from mpi_opt_tpu.train.fused_pbt import fused_pbt  # noqa: E402
from mpi_opt_tpu.workloads import get_workload  # noqa: E402


def run(momentum_dtype, pop=256, gens=2, steps=100):
    wl = get_workload("cifar10_cnn")
    kw = dict(
        population=pop,
        generations=gens,
        steps_per_gen=steps,
        seed=0,
        # bench.py's north-star settings: unchunked pop>=128 fails at the
        # remote compiler (PERF_NOTES.md "remote-compiler limits")
        member_chunk=32,
        gen_chunk=1,
    )
    # the env knob is part of workload_arrays' trainer cache key, so
    # each arm gets its own trainer without manual cache surgery
    os.environ["MPI_OPT_TPU_MOMENTUM_DTYPE"] = momentum_dtype
    try:
        fused_pbt(wl, **kw)  # warm
        t0 = time.perf_counter()
        res = fused_pbt(wl, **kw)
        wall = time.perf_counter() - t0
    finally:
        os.environ.pop("MPI_OPT_TPU_MOMENTUM_DTYPE", None)
    curve = [round(float(v), 4) for v in res["best_curve"]]
    rate = pop * gens / wall
    print(f"momentum={momentum_dtype}: {wall:.1f}s = {rate:.2f} member-gens/s "
          f"best={res['best_score']:.4f} curve={curve}", flush=True)
    return wall, res


if __name__ == "__main__":
    run("float32")
    run("bfloat16")
    run("float32")  # repeat to bound run-to-run noise
