"""Probe: does the ResNet-18/CIFAR-100-synthetic config learn, and how fast?

Round-2 verdict item 3: config 5's bench showed best val-acc 0.0239
(chance = 0.01) after 2 gens x 50 steps — a throughput demo. Before
paying for the full pop=64 learning sweep, chart the trajectory at a
smaller population to calibrate generations needed (and the dataset's
difficulty, if the curve is flat).

Run on the real chip: python probes/probe_c5_learn.py [pop] [gens] [steps]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax


from mpi_opt_tpu.train.fused_pbt import fused_pbt  # noqa: E402
from mpi_opt_tpu.workloads import get_workload  # noqa: E402

pop = int(sys.argv[1]) if len(sys.argv) > 1 else 32
gens = int(sys.argv[2]) if len(sys.argv) > 2 else 10
steps = int(sys.argv[3]) if len(sys.argv) > 3 else 50

wl = get_workload("cifar100_resnet18")
t0 = time.perf_counter()
res = fused_pbt(
    wl,
    population=pop,
    generations=gens,
    steps_per_gen=steps,
    seed=0,
    member_chunk=8,
    gen_chunk=1,
)
wall = time.perf_counter() - t0
curve = [round(float(v), 4) for v in res["best_curve"]]
print(f"pop={pop} gens={gens} steps={steps} wall={wall:.1f}s")
print(f"best={res['best_score']:.4f}")
print(f"curve={curve}")
print(f"launch_walls={[round(w, 1) for w in res['launch_walls']]}")
print(f"best_params={ {k: round(v, 4) if isinstance(v, float) else v for k, v in res['best_params'].items()} }")
