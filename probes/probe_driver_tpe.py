"""Where does config-4's driver wall go?"""
import sys, time
sys.path.insert(0, "/root/repo")
import jax
from mpi_opt_tpu.algorithms import get_algorithm
from mpi_opt_tpu.backends import get_backend
from mpi_opt_tpu.driver import run_search
from mpi_opt_tpu.workloads import get_workload

wl = get_workload("tabular_mlp")
space = wl.default_space()
cls = get_algorithm("tpe")
be = get_backend("tpu", wl, population=64, seed=0)
run_search(cls(space, seed=1, max_trials=192, budget=30), be)
be.reset()

algo = cls(space, seed=0, max_trials=256, budget=30)
t_nb = t_rb = t_ev = 0.0
nb0, rb0, ev0 = algo.next_batch, algo.report_batch, be.evaluate
calls = []
def nb(n):
    global t_nb; t0=time.perf_counter(); out=nb0(n); t_nb += time.perf_counter()-t0; return out
def rb(r):
    global t_rb; t0=time.perf_counter(); out=rb0(r); t_rb += time.perf_counter()-t0; return out
def ev(ts):
    global t_ev; t0=time.perf_counter(); out=ev0(ts); d=time.perf_counter()-t0; t_ev += d; calls.append((len(ts), d)); return out
algo.next_batch, algo.report_batch, be.evaluate = nb, rb, ev
t0 = time.perf_counter()
res = run_search(algo, be)
wall = time.perf_counter()-t0
be.close()
print(f"wall {wall:.2f}s nb {t_nb:.2f}s rb {t_rb:.2f}s ev {t_ev:.2f}s calls {calls}")
