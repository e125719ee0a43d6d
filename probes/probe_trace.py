import time, jax, numpy as np
from mpi_opt_tpu.train.fused_pbt import fused_pbt
from mpi_opt_tpu.workloads import get_workload
from mpi_opt_tpu.utils.profiling import profile_window
wl = get_workload("cifar10_cnn")
r = fused_pbt(wl, population=32, generations=2, steps_per_gen=100, seed=0)  # warm
r = None
with profile_window("/tmp/prof_fused"):
    r = fused_pbt(wl, population=32, generations=2, steps_per_gen=100, seed=0)
print("done")
