import sys, time
sys.path.insert(0, "/root/repo")
import jax
from mpi_opt_tpu.train.fused_pbt import fused_pbt
from mpi_opt_tpu.workloads import get_workload
wl = get_workload("cifar10_cnn")
for chunk in (32, 64, 128):
    kw = dict(population=256, generations=2, steps_per_gen=100, seed=0,
              member_chunk=chunk, gen_chunk=1)
    try:
        t0 = time.perf_counter(); fused_pbt(wl, **kw)
        warm = time.perf_counter() - t0
        t0 = time.perf_counter(); r = fused_pbt(wl, **kw)
        wall = time.perf_counter() - t0
        print(f"chunk={chunk}: {512/wall:.2f} trials/s (wall {wall:.1f}s, warm {warm:.0f}s, best {r['best_score']:.3f})", flush=True)
    except Exception as e:
        print(f"chunk={chunk}: FAIL {type(e).__name__} {str(e)[:90]}", flush=True)
