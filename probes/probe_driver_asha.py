"""Where does the config-2 driver path's wall go? Count evaluate()
calls, their batch sizes/rem spans, and per-call wall on the real chip."""
import sys, time
sys.path.insert(0, "/root/repo")

import jax

from mpi_opt_tpu.algorithms import get_algorithm
from mpi_opt_tpu.backends import get_backend
from mpi_opt_tpu.driver import run_search
from mpi_opt_tpu.workloads import get_workload

wl = get_workload("fashion_mlp")
asha = lambda s: get_algorithm("asha")(
    wl.default_space(), seed=s, max_trials=64, min_budget=10, max_budget=270, eta=3)

be = get_backend("tpu", wl, population=64, seed=0)
run_search(asha(0), be)  # warmup compiles
be.reset()

calls = []
orig = be.evaluate
def spy(trials):
    t0 = time.perf_counter()
    rems = sorted({max(0, t.budget - be._trained.get(t.trial_id, 0)) for t in trials})
    out = orig(trials)
    calls.append((len(trials), rems, time.perf_counter() - t0))
    return out
be.evaluate = spy
t0 = time.perf_counter()
res = run_search(asha(0), be)
wall = time.perf_counter() - t0
be.close()
print(f"total wall {wall:.2f}s n_evals {res.n_evals} evaluate_calls {len(calls)}")
for n, rems, w in calls:
    print(f"  n={n:3d} rems={rems} wall={w:.3f}s")
print(f"sum of evaluate walls: {sum(w for _,_,w in calls):.2f}s")
