import sys, time
sys.path.insert(0, "/root/repo")
import jax
import numpy as np
from mpi_opt_tpu.workloads.vision import Cifar100ResNet18
from mpi_opt_tpu.train.common import workload_arrays

wl = Cifar100ResNet18()
trainer, space, tx, ty, vx, vy = workload_arrays(wl, 8)
st = trainer.init_population(jax.random.key(0), tx[:2], 64)
leaves = jax.tree.leaves({"p": st.params, "m": st.momentum})
nbytes = sum(l.nbytes for l in leaves)
print(f"pool bytes: {nbytes/1e9:.2f} GB, {len(leaves)} leaves", flush=True)
t0 = time.perf_counter()
host = jax.device_get({"p": st.params, "m": st.momentum})
w = time.perf_counter() - t0
print(f"device_get: {w:.1f}s = {nbytes/w/1e6:.1f} MB/s", flush=True)
