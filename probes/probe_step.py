"""Ablate the member train step: where do the 36ms/step go?"""
import time, jax, jax.numpy as jnp, numpy as np, flax.linen as nn
from mpi_opt_tpu.models import SmallCNN
from mpi_opt_tpu.train import PopulationTrainer, OptHParams
from mpi_opt_tpu.data import load_dataset

P, B, STEPS = 32, 256, 50
d = load_dataset("cifar10", n_train=4096, n_val=512)
tx, ty = jnp.asarray(d["train_x"]), jnp.asarray(d["train_y"])

class NoNormCNN(nn.Module):
    n_classes: int = 10
    width: int = 32
    dtype: jnp.dtype = jnp.bfloat16
    @nn.compact
    def __call__(self, x):
        w = self.width
        x = x.astype(self.dtype)
        for i, ch in enumerate((w, w, 2*w, 2*w)):
            x = nn.Conv(ch, (3,3), padding="SAME", dtype=self.dtype, name=f"conv{i}")(x)
            x = nn.relu(x)
            if i % 2 == 1:
                x = nn.max_pool(x, (2,2), strides=(2,2))
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(4*w, dtype=self.dtype, name="fc1")(x))
        return nn.Dense(self.n_classes, dtype=self.dtype, name="fc2")(x).astype(jnp.float32)

def run(model, augment, label):
    tr = PopulationTrainer(
        apply_fn=lambda p, x: model.apply({"params": p}, x),
        init_fn=lambda r, x: model.init(r, x)["params"],
        batch_size=B, augment=augment, donate=False)
    st = tr.init_population(jax.random.key(0), tx[:2], P)
    hp = OptHParams.defaults(P)
    st2, l = tr.train_segment(st, hp, tx, ty, jax.random.key(1), STEPS)
    np.asarray(l)
    t0 = time.time()
    st2, l = tr.train_segment(st, hp, tx, ty, jax.random.key(2), STEPS)
    np.asarray(l)
    dt = (time.time()-t0)/STEPS
    print(f"{label}: {dt*1e3:.2f} ms/step ({P*1000/ (dt*1e3):.0f} member-steps/s)")

run(SmallCNN(), True,  "GN  + aug (current)")
run(SmallCNN(), False, "GN  no-aug")
run(NoNormCNN(), True, "noGN + aug")
run(NoNormCNN(), False,"noGN no-aug")
