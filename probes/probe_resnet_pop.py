import sys, time
sys.path.insert(0, "/root/repo")
import jax
from mpi_opt_tpu.train.fused_pbt import fused_pbt
from mpi_opt_tpu.workloads import get_workload
wl = get_workload("cifar100_resnet18")
for pop in (96, 128):
    kw = dict(population=pop, generations=2, steps_per_gen=50, seed=0,
              member_chunk=8, gen_chunk=1)
    try:
        t0 = time.perf_counter(); fused_pbt(wl, **kw)
        warm = time.perf_counter() - t0
        t0 = time.perf_counter(); r = fused_pbt(wl, **kw)
        wall = time.perf_counter() - t0
        print(f"pop={pop}: OK {pop*2/wall:.3f} trials/s (wall {wall:.1f}s warm {warm:.0f}s)", flush=True)
    except Exception as e:
        print(f"pop={pop}: FAIL {type(e).__name__} {str(e)[:100]}", flush=True)
        break
