"""Driver BOHB on the TPU slot pool, round-3 protocol (warm + reset +
timed), after the round-4 host_ops fix. Round-3 recorded 1.07
trials/s/chip (388.5 s for the 415-trial R=270 plan, 703 evaluations)."""
import sys, time
sys.path.insert(0, "/root/repo")
import jax
from mpi_opt_tpu.algorithms import get_algorithm
from mpi_opt_tpu.backends import get_backend
from mpi_opt_tpu.driver import run_search
from mpi_opt_tpu.workloads import get_workload

wl = get_workload("fashion_mlp")
bohb = lambda s: get_algorithm("bohb")(wl.default_space(), seed=s, max_budget=270, eta=3)
be = get_backend("tpu", wl, population=64, seed=0)
t0 = time.perf_counter()
run_search(bohb(0), be)
print(f"warmup {time.perf_counter()-t0:.1f}s", flush=True)
be.reset()
res = run_search(bohb(0), be)
be.close()
print(f"driver BOHB: {res.n_trials} trials, {res.n_evals} evals, "
      f"{res.wall_s:.2f}s = {res.n_trials/res.wall_s:.2f} trials/s/chip, "
      f"best={res.best.score:.4f}")
