import sys, time, jax, numpy as np
from mpi_opt_tpu.train.fused_pbt import fused_pbt
from mpi_opt_tpu.workloads import get_workload
wl = get_workload("cifar10_cnn")
G, S = 2, 100
for P, C in ((128, 64), (256, 64), (256, 32), (512, 64)):
    try:
        t0 = time.time()
        r = fused_pbt(wl, population=P, generations=G, steps_per_gen=S, seed=0, member_chunk=C)
        cold = time.time()-t0
        r = None
        t0 = time.time()
        r = fused_pbt(wl, population=P, generations=G, steps_per_gen=S, seed=0, member_chunk=C)
        dt = time.time()-t0
        print(f"P={P} C={C}: cold {cold:.1f}s warm {dt:.2f}s -> {P*G/dt:.2f} member-gens/s", flush=True)
        r = None
    except Exception as e:
        print(f"P={P} C={C} FAIL {type(e).__name__} {str(e)[:100]}", flush=True)
