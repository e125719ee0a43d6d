import sys, statistics, time
sys.path.insert(0, "/root/repo")
import jax
import numpy as np
from mpi_opt_tpu.train.population import OptHParams
from mpi_opt_tpu.workloads.vision import Cifar100ResNet18
from mpi_opt_tpu.train.common import workload_arrays

POP, STEPS = 64, 50
for chunk in (8, 16, 32, 0):
    try:
        wl = Cifar100ResNet18()
        trainer, space, tx, ty, vx, vy = workload_arrays(wl, chunk)
        st = trainer.init_population(jax.random.key(0), tx[:2], POP)
        hp = OptHParams.defaults(POP, lr=0.05)
        t0 = time.perf_counter()
        st, losses = trainer.train_segment(st, hp, tx, ty, jax.random.key(1), STEPS)
        np.asarray(losses)
        warm = time.perf_counter() - t0
        walls = []
        for i in range(3):
            t0 = time.perf_counter()
            st, losses = trainer.train_segment(st, hp, tx, ty, jax.random.fold_in(jax.random.key(2), i), STEPS)
            np.asarray(losses)
            walls.append(time.perf_counter() - t0)
        med = statistics.median(walls)
        print(f"member_chunk={chunk:3d}: {med:.3f}s (warm {warm:.0f}s) "
              f"{['%.2f' % w for w in walls]} ({POP*STEPS/med:.1f} member-steps/s)", flush=True)
    except Exception as e:
        print(f"member_chunk={chunk:3d}: FAIL {type(e).__name__} {str(e)[:140]}", flush=True)
