import sys, time, os
sys.path.insert(0, "/root/repo")
import jax
jax.config.update("jax_platforms", "cpu")
from mpi_opt_tpu.workloads import get_workload

t0 = time.perf_counter()
wl = get_workload("cifar10_cnn")
d = wl.data()
print(f"data gen: {time.perf_counter()-t0:.1f}s train={d['train_x'].shape}", flush=True)
t0 = time.perf_counter()
score = wl.evaluate({"lr": 0.1, "momentum": 0.9, "weight_decay": 1e-4,
                     "flip_prob": 0.2, "shift": 2.0}, budget=5, seed=0)
print(f"evaluate(budget=5): {time.perf_counter()-t0:.1f}s score={score:.3f}", flush=True)
t0 = time.perf_counter()
score = wl.evaluate({"lr": 0.1, "momentum": 0.9, "weight_decay": 1e-4,
                     "flip_prob": 0.2, "shift": 2.0}, budget=100, seed=0)
print(f"evaluate(budget=100): {time.perf_counter()-t0:.1f}s score={score:.3f}", flush=True)
