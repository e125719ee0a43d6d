import time
import jax
from mpi_opt_tpu.train.fused_pbt import fused_pbt
from mpi_opt_tpu.workloads import get_workload
wl = get_workload("cifar10_cnn")
# warm small, then probe single-program execution length at pop=128
for gens, steps in [(2, 20), (4, 100), (8, 100)]:
    t0 = time.perf_counter()
    try:
        r = fused_pbt(wl, population=128, generations=gens, steps_per_gen=steps, seed=0, member_chunk=32)
        print(f"g={gens} s={steps}: OK wall={time.perf_counter()-t0:.1f}s best={r['best_score']:.3f}", flush=True)
    except Exception as e:
        print(f"g={gens} s={steps}: FAIL {type(e).__name__} {str(e)[:100]}", flush=True)
        break
