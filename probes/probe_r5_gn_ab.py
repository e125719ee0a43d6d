"""Round-5: A/B the fused Pallas GN+ReLU kernel inside the ResNet
population segment (pop=64, member_chunk=8, remat, 50 steps) on the
real chip — wall AND a 2-gen learning sanity check, per the
pool-swap-probe protocol."""
import statistics
import sys
import time

sys.path.insert(0, "/root/repo")

import jax
import numpy as np


from mpi_opt_tpu.train.population import OptHParams
from mpi_opt_tpu.workloads.vision import Cifar100ResNet18

POP, STEPS, REPS, CHUNK = 64, 50, 3, 8


def segment_wall(wl, label):
    from mpi_opt_tpu.train.common import workload_arrays

    trainer, space, tx, ty, vx, vy = workload_arrays(wl, CHUNK)
    st = trainer.init_population(jax.random.key(0), tx[:2], POP)
    hp = OptHParams.defaults(POP, lr=0.05)
    t0 = time.perf_counter()
    st, losses = trainer.train_segment(st, hp, tx, ty, jax.random.key(1), STEPS)
    np.asarray(losses)
    warm = time.perf_counter() - t0
    walls = []
    for i in range(REPS):
        t0 = time.perf_counter()
        st, losses = trainer.train_segment(
            st, hp, tx, ty, jax.random.fold_in(jax.random.key(2), i), STEPS
        )
        np.asarray(losses)
        walls.append(time.perf_counter() - t0)
    med = statistics.median(walls)
    print(f"{label:18s}: {med:.3f}s (warm {warm:.0f}s) {['%.3f' % w for w in walls]} "
          f"({POP*STEPS/med:.1f} member-steps/s)", flush=True)
    return med


def learn2g(wl, label):
    from mpi_opt_tpu.train.fused_pbt import fused_pbt

    res = fused_pbt(wl, population=32, generations=2, steps_per_gen=100,
                    seed=0, gen_chunk=1, member_chunk=CHUNK, snapshot_last=False)
    print(f"{label:18s}: learn2g best={res['best_score']:.4f}", flush=True)


print(f"device: {jax.devices()[0].device_kind}", flush=True)
base = segment_wall(Cifar100ResNet18(pallas_gn=False), "xla-gn")
pal = segment_wall(Cifar100ResNet18(pallas_gn=True), "pallas-gn")
print(f"delta: {(base-pal)/base*100:+.1f}% ({base-pal:+.3f}s)", flush=True)
learn2g(Cifar100ResNet18(pallas_gn=False), "xla-gn")
learn2g(Cifar100ResNet18(pallas_gn=True), "pallas-gn")
