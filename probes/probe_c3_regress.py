"""Fused config-3 5x slowdown: reproduce with minimal sweep, timed per launch."""
import sys, time
sys.path.insert(0, "/root/repo")
import jax
from mpi_opt_tpu.train.fused_pbt import fused_pbt
from mpi_opt_tpu.workloads import get_workload

wl = get_workload("cifar10_cnn")
kw = dict(population=32, generations=2, steps_per_gen=100, seed=0, gen_chunk=2)
for i in range(3):
    t0 = time.perf_counter()
    res = fused_pbt(wl, **kw)
    print(f"run {i}: {time.perf_counter()-t0:.1f}s launch_walls={[round(w,1) for w in res['launch_walls']]}")
