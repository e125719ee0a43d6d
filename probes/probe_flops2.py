import jax, jax.numpy as jnp
from mpi_opt_tpu.workloads import get_workload
from mpi_opt_tpu.train.population import OptHParams

wl = get_workload("cifar10_cnn")
tr = wl.make_trainer(donate=False)
d = wl.data()
tx, ty = jnp.asarray(d["train_x"]), jnp.asarray(d["train_y"])
print("batch_size:", tr.batch_size, "train_x:", tx.shape, tx.dtype)
P = 8
key = jax.random.key(0)
state = tr.init_population(key, tx[:2], P)
hp = OptHParams.defaults(P)
# cost of a 1-step segment
jf = tr.train_segment  # functools.partial(jit(...), self)
c = jf.lower(state, hp, tx, ty, key, steps=1).compile().cost_analysis()
if isinstance(c, (list, tuple)): c = c[0]
print("train_segment P=8 steps=1 flops:", c.get("flops"), "bytes accessed:", c.get("bytes accessed"))
print("per member-step GFLOP:", c.get("flops")/P/1e9)
