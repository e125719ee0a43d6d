import jax, jax.numpy as jnp, re
from mpi_opt_tpu.workloads import get_workload
from mpi_opt_tpu.train.population import OptHParams
wl = get_workload("cifar10_cnn")
tr = wl.make_trainer(donate=False)
d = wl.data()
tx, ty = jnp.asarray(d["train_x"]), jnp.asarray(d["train_y"])
P = 32
state = tr.init_population(jax.random.key(0), tx[:2], P)
hp = OptHParams.defaults(P)
jf = tr.train_segment
txt = jf.lower(state, hp, tx, ty, jax.random.key(1), steps=1).compile().as_text()
convs = [l.strip() for l in txt.splitlines() if "convolution(" in l or "%convolution" in l and "fusion" not in l]
for l in convs[:20]:
    print(l[:240])
print("n conv lines:", len(convs))
