"""Round-5: config-5 (ResNet-18 CIFAR-100 population) perf ledger,
held to the config-3 standard (VERDICT r4 weak #2).

Phase 1 of the ledger: baseline + ablation + trace capture.
- segment wall at the bench shape (pop=64, member_chunk=8, remat,
  batch 128, 50-step segments; medians of 3, fetch-once barrier);
- GroupNorm -> identity ablation (COST only — the no-norm model's
  learning is not comparable and isn't claimed);
- relu cost isolated the same way (GN+relu is the fusion candidate);
- a profiler trace of one segment for the leaf-op decomposition
  (parsed by probe_traceparse.py pointed at /tmp/prof_r5_resnet);
- MFU bookkeeping from utils.flops at the measured wall.

Run on the REAL chip, idle host (PERF_NOTES measurement rules).
"""

import statistics
import sys
import time

sys.path.insert(0, "/root/repo")

import jax
import numpy as np


from mpi_opt_tpu.train.population import OptHParams
from mpi_opt_tpu.workloads import get_workload

POP, STEPS, REPS, CHUNK = 64, 50, 3, 8


def fresh_workload():
    wl = get_workload("cifar100_resnet18")
    return wl


def segment_wall(wl, label, trace_dir=None):
    from mpi_opt_tpu.train.common import workload_arrays

    trainer, space, tx, ty, vx, vy = workload_arrays(wl, CHUNK)
    st = trainer.init_population(jax.random.key(0), tx[:2], POP)
    hp = OptHParams.defaults(POP, lr=0.05)
    st, losses = trainer.train_segment(st, hp, tx, ty, jax.random.key(1), STEPS)
    np.asarray(losses)  # warm barrier
    walls = []
    for i in range(REPS):
        t0 = time.perf_counter()
        st, losses = trainer.train_segment(
            st, hp, tx, ty, jax.random.fold_in(jax.random.key(2), i), STEPS
        )
        np.asarray(losses)
        walls.append(time.perf_counter() - t0)
    med = statistics.median(walls)
    print(
        f"{label:22s}: {med:.3f}s  {['%.3f' % w for w in walls]}  "
        f"({POP * STEPS / med:.1f} member-steps/s)",
        flush=True,
    )
    if trace_dir:
        with jax.profiler.trace(trace_dir):
            st, losses = trainer.train_segment(
                st, hp, tx, ty, jax.random.key(9), STEPS
            )
            np.asarray(losses)
    return med


def main():
    print(f"device: {jax.devices()[0].device_kind}", flush=True)
    import flax.linen as nn

    base = segment_wall(fresh_workload(), "baseline", trace_dir="/tmp/prof_r5_resnet")

    # GN -> identity (params vanish too: pure cost ablation)
    orig_gn = nn.GroupNorm.__call__
    nn.GroupNorm.__call__ = lambda self, x: x
    try:
        no_gn = segment_wall(fresh_workload(), "gn=identity")
    finally:
        nn.GroupNorm.__call__ = orig_gn

    # relu -> identity (the other half of the fusion candidate)
    orig_relu = nn.relu
    nn.relu = lambda x: x
    try:
        no_relu = segment_wall(fresh_workload(), "relu=identity")
    finally:
        nn.relu = orig_relu

    print(
        f"GN share   : {(base - no_gn) / base * 100:.1f}% of segment "
        f"({base - no_gn:.3f}s)",
        flush=True,
    )
    print(
        f"relu share : {(base - no_relu) / base * 100:.1f}% of segment "
        f"({base - no_relu:.3f}s)",
        flush=True,
    )

    # MFU bookkeeping at the measured baseline
    from mpi_opt_tpu.utils.flops import population_sweep_flops

    wl = fresh_workload()
    # one "generation" = the timed segment; n_evals=0 — the timed
    # window contains no eval
    fl = population_sweep_flops(wl, POP, 1, STEPS, n_evals=0)
    print(
        f"MFU: {fl / base / 157e12:.3f} of 157 TF/s measured cap "
        f"({fl / base / 1e12:.1f} TF/s achieved, {fl / 1e12:.1f} TF total)",
        flush=True,
    )


if __name__ == "__main__":
    main()
