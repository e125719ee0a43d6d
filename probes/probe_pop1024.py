"""Probe: single-chip population ceiling for the north-star CNN sweep.

The headline measures pop=256 (BASELINE north_star). This charts the
throughput curve up to pop=1024 — 4x the north-star population on ONE
chip. Measured result (PERF_NOTES.md "single-chip population
envelope"): throughput is flat 857->874 member-steps/s through
pop=512, then pop=1024 RESOURCE_EXHAUSTs — 4.5 GB of params+momentum
plus the update's transient double-residency tips the 16 GB chip, so
bigger populations shard over the mesh's 'pop' axis (the design's
scaling path; BASELINE config 5 puts pop=1024 on a v4-32).

Run: python probes/probe_pop1024.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax


from mpi_opt_tpu.train.fused_pbt import fused_pbt  # noqa: E402
from mpi_opt_tpu.workloads import get_workload  # noqa: E402

wl = get_workload("cifar10_cnn")
for pop in (256, 512, 1024):
    kw = dict(
        population=pop,
        generations=1,
        steps_per_gen=100,
        seed=0,
        member_chunk=32,
        gen_chunk=1,
    )
    t0 = time.perf_counter()
    fused_pbt(wl, **kw)  # warm/compile
    warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = fused_pbt(wl, **kw)
    wall = time.perf_counter() - t0
    rate = pop * 100 / wall
    print(
        f"pop={pop}: warm {warm:.1f}s, timed {wall:.1f}s = "
        f"{rate:.0f} member-steps/s ({pop / wall:.2f} member-gens/s) "
        f"best={res['best_score']:.3f}",
        flush=True,
    )
