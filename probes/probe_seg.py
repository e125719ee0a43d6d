import time
import numpy as np
import jax, jax.numpy as jnp
from mpi_opt_tpu.workloads import get_workload
from mpi_opt_tpu.train.population import OptHParams
wl = get_workload("cifar10_cnn")
d = wl.data()
tx, ty = jnp.asarray(d["train_x"]), jnp.asarray(d["train_y"])
for P, chunk in ((32, 0), (64, 0), (128, 32), (256, 32)):
    tr = wl.make_trainer(donate=False, member_chunk=chunk)
    state = tr.init_population(jax.random.key(0), tx[:2], P)
    hp = OptHParams.defaults(P)
    key = jax.random.key(1)
    st, loss = tr.train_segment(state, hp, tx, ty, key, steps=50)
    np.asarray(loss)  # warmup same static args
    t0 = time.perf_counter()
    st, loss = tr.train_segment(st, hp, tx, ty, key, steps=50)
    np.asarray(loss)
    dt = time.perf_counter() - t0
    ms = P * 50
    print(f"P={P} chunk={chunk}: {dt:.2f}s, {ms/dt:.0f} msteps/s "
          f"({ms/dt*36.6e9/1e12:.1f} TF/s)", flush=True)
