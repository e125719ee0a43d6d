"""A/B: nn.max_pool (select-and-scatter VJP) vs reshape+reduce-max
(elementwise tie-splitting VJP) in the SmallCNN population sweep.

Motivation: the round-2 trace showed select-and-scatter (max-pool
backward) at ~8% of device time, making the reshape variant look like
free throughput. Measured verdict on the real chip (2026-07-30,
pop=64 x 2 gens x 100 steps, seed 0, identical everything else):

    nn.max_pool     : 15.6 s, best_curve [0.311, 0.548]
    reshape+max     : 17.7 s, best_curve [0.166, 0.211]

i.e. the "optimization" was 14% SLOWER (the 6-D reshaped reduce under
vmap lowers worse than reduce-window) and collapsed learning (in bf16,
post-GroupNorm activations tie inside 2x2 windows often enough that
the split-among-ties subgradient materially dilutes the signal
select-and-scatter's send-to-first keeps concentrated). Both effects
refute the swap; SmallCNN keeps nn.max_pool.

Run from /root/repo: python probes/probe_pool_ab.py {old|new}
"""

import sys
import time

sys.path.insert(0, ".")


def main(mode):
    import jax

    import mpi_opt_tpu.models.cnn as cnn

    if mode == "new":  # the refuted variant
        import jax.numpy as jnp

        def reshape_pool(x):
            b, h, w, c = x.shape
            return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))

        import flax.linen as nn

        nn.max_pool_orig = nn.max_pool
        cnn.nn.max_pool = lambda x, *_a, **_k: reshape_pool(x)

    from mpi_opt_tpu.train.fused_pbt import fused_pbt
    from mpi_opt_tpu.workloads import get_workload

    wl = get_workload("cifar10_cnn")
    kw = dict(population=64, generations=2, steps_per_gen=100, seed=0,
              member_chunk=32, gen_chunk=1)
    fused_pbt(wl, **kw)  # warm
    t0 = time.time()
    r = fused_pbt(wl, **kw)
    wall = time.time() - t0
    print(f"{mode}: wall={wall:.2f}s "
          f"curve={[round(float(v), 3) for v in r['best_curve']]}")


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "old")
