import jax
from mpi_opt_tpu.workloads import get_workload
from mpi_opt_tpu.utils.flops import population_sweep_flops
import mpi_opt_tpu.utils.flops as F

# unwrap the try/except to see the real error
import traceback
wl = get_workload("cifar100_resnet18")
try:
    trainer = wl.make_trainer(donate=False)
    from mpi_opt_tpu.train.population import OptHParams
    import jax.numpy as jnp
    d = wl.data()
    tx, ty = jnp.asarray(d["train_x"]), jnp.asarray(d["train_y"])
    vx, vy = jnp.asarray(d["val_x"])[:1024], jnp.asarray(d["val_y"])[:1024]
    key = jax.random.key(0)
    state = trainer.init_population(key, tx[:2], 1)
    hp = OptHParams.defaults(1)
    jf = trainer.train_segment
    f_step = F.compiled_flops(jf, state, hp, tx, ty, key, steps=1)
    print("f_step:", f_step)
    f_eval = F.compiled_flops(type(trainer).eval_population, trainer, state, vx, vy, eval_chunk=1024)
    print("f_eval:", f_eval)
except Exception:
    traceback.print_exc()
