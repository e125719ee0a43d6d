import time
import numpy as np
import jax, jax.numpy as jnp

conv1 = lambda xi, wi: jax.lax.conv_general_dilated(xi, wi, (1,1), "SAME", dimension_numbers=("NHWC","HWIO","NHWC"))

def bench(P, B, HW, C, O, n=10):
    x = jax.random.normal(jax.random.key(0), (P, B, HW, HW, C), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(1), (P, 3, 3, C, O), jnp.bfloat16) * 0.05
    fn = jax.vmap(conv1)
    loss = lambda x, w: jnp.sum(fn(x, w) ** 2).astype(jnp.float32)

    @jax.jit
    def step(x, w):
        gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
        # chain: output feeds next input so iterations can't collapse
        return x + 1e-6 * gx, w + 1e-6 * gw

    x1, w1 = step(x, w)
    np.asarray(jnp.sum(w1))  # force full completion
    t0 = time.perf_counter()
    for _ in range(n):
        x1, w1 = step(x1, w1)
    np.asarray(jnp.sum(w1))  # host fetch = real barrier
    dt = (time.perf_counter() - t0) / n
    fl = 3 * 2 * P*B*HW*HW*9*C*O
    print(f"P={P} B={B} {HW}x{HW} C={C} O={O}: {dt*1e3:.2f} ms ({fl/dt/1e12:.1f} TF/s)", flush=True)

bench(32, 256, 32, 32, 32)
bench(32, 256, 32, 64, 64)
bench(32, 128, 32, 128, 128)
bench(32, 256, 16, 64, 64)
bench(32, 256, 16, 128, 128)
