import sys, statistics, time
sys.path.insert(0, "/root/repo")
import jax
import numpy as np
from mpi_opt_tpu.train.population import OptHParams
from mpi_opt_tpu.workloads.vision import Cifar100ResNet18
from mpi_opt_tpu.train.common import workload_arrays

POP, STEPS = 64, 50
for remat in (False,):
    try:
        wl = Cifar100ResNet18(remat=remat)
        trainer, space, tx, ty, vx, vy = workload_arrays(wl, 8)
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
        print(f"remat={remat}: {med:.3f}s (warm {warm:.0f}s) {['%.2f' % w for w in walls]}", flush=True)
    except Exception as e:
        print(f"remat={remat}: FAIL {type(e).__name__} {str(e)[:180]}", flush=True)
