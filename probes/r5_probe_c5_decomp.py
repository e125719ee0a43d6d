import sys, statistics, time
sys.path.insert(0, "/root/repo")
import jax
import numpy as np
from mpi_opt_tpu.train.population import OptHParams
from mpi_opt_tpu.workloads.vision import Cifar100ResNet18
from mpi_opt_tpu.train.common import workload_arrays

POP, STEPS = 64, 50
wl = Cifar100ResNet18()
trainer, space, tx, ty, vx, vy = workload_arrays(wl, 8)
print("val set:", vx.shape, flush=True)
st = trainer.init_population(jax.random.key(0), tx[:2], POP)
hp = OptHParams.defaults(POP, lr=0.05)

# warm all three programs
st, losses = trainer.train_segment(st, hp, tx, ty, jax.random.key(1), STEPS)
scores = trainer.eval_population(st, vx, vy); np.asarray(scores)
st2 = trainer.gather_members(st, jax.numpy.arange(POP)[::-1]); np.asarray(jax.tree.leaves(st2.params)[0][:1, :1])
st = st2

def med(fn, n=3):
    walls = []
    for i in range(n):
        t0 = time.perf_counter(); fn(i); walls.append(time.perf_counter() - t0)
    return statistics.median(walls), walls

def _train(i):
    global st
    st, losses = trainer.train_segment(st, hp, tx, ty, jax.random.fold_in(jax.random.key(2), i), STEPS)
    np.asarray(losses)
t, tw = med(_train)
print(f"train 50 steps : {t:.3f}s {['%.2f' % w for w in tw]}", flush=True)

def _eval(i):
    np.asarray(trainer.eval_population(st, vx, vy))
t, ew = med(_eval)
print(f"eval_population: {t:.3f}s {['%.2f' % w for w in ew]}", flush=True)

def _gather(i):
    global st
    st = trainer.gather_members(st, jax.numpy.arange(POP)[::-1])
    np.asarray(jax.tree.leaves(st.params)[0][:1, :1])
t, gw = med(_gather)
print(f"exploit gather : {t:.3f}s {['%.2f' % w for w in gw]}", flush=True)

# whole fused generation for reference (train+eval+exploit in ONE program)
from mpi_opt_tpu.train.fused_pbt import run_fused_pbt
from mpi_opt_tpu.train.common import HParamsFn
import jax.numpy as jnp
hf = HParamsFn(space, wl)
disc = tuple(bool(b) for b in space.discrete_mask())
unit = jnp.full((POP, space.dim), 0.5, jnp.float32)
key = jax.random.key(3)
out = run_fused_pbt(trainer, st, unit, hf, tx, ty, vx, vy, key, disc, 1, STEPS)
np.asarray(out[3])  # warm
st, unit, key = out[0], out[1], out[2]
def _gen(i):
    global st, unit, key
    st, unit, key, best, mean, fs = run_fused_pbt(trainer, st, unit, hf, tx, ty, vx, vy, key, disc, 1, STEPS)
    np.asarray(best)
t, fw = med(_gen)
print(f"fused 1-gen    : {t:.3f}s {['%.2f' % w for w in fw]}", flush=True)
