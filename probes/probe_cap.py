import time
import numpy as np
import jax, jax.numpy as jnp

# platform cap probe: ideal MXU shapes, work >> dispatch overhead
M = K = N = 4096
a = jax.random.normal(jax.random.key(0), (M, K), jnp.bfloat16)
b = jax.random.normal(jax.random.key(1), (K, N), jnp.bfloat16) * 0.01

@jax.jit
def step(b):
    # 8 chained matmuls: 8 * 137 GFLOP = 1.1 TFLOP per dispatch
    for _ in range(8):
        b = (a @ b) * 1e-3
    return b.astype(jnp.bfloat16)

b1 = step(b); np.asarray(b1[0, 0])
t0 = time.perf_counter()
iters = 10
for _ in range(iters):
    b1 = step(b1)
np.asarray(b1[0, 0])
dt = (time.perf_counter() - t0) / iters
fl = 8 * 2 * M * K * N
print(f"square {M}: {dt*1e3:.2f} ms/dispatch ({fl/dt/1e12:.1f} TF/s of 394 peak)")
