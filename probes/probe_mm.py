import time, sys
import numpy as np
import jax, jax.numpy as jnp

def timed(name, fl_per_iter, step, init, n=20):
    print(f"compiling {name} ...", flush=True)
    @jax.jit
    def run(c):
        return jax.lax.fori_loop(0, n, lambda i, c: step(c), c)
    t0 = time.perf_counter()
    c = run(init); jax.tree.map(lambda a: np.asarray(jnp.ravel(a)[0]), c)
    print(f"  compile+first {time.perf_counter()-t0:.1f}s", flush=True)
    t0 = time.perf_counter()
    c = run(c)
    jax.tree.map(lambda a: np.asarray(jnp.ravel(a)[0]), c)
    dt = (time.perf_counter() - t0) / n
    print(f"{name}: {dt*1e3:.3f} ms/iter ({fl_per_iter/dt/1e12:.1f} TF/s)", flush=True)

def mm(P, M, K, N):
    a = jax.random.normal(jax.random.key(0), (P, M, K), jnp.bfloat16)
    b = jax.random.normal(jax.random.key(1), (P, K, N), jnp.bfloat16) * 0.01
    def step(b):
        y = jnp.einsum("pmk,pkn->pmn", a, b)
        return (b + 1e-6 * y[:, :K, :]).astype(b.dtype)
    timed(f"mm P={P} M={M} K={K} N={N}", 2*P*M*K*N, step, b)

mm(32, 8192, 288, 32)
mm(32, 8192, 288, 128)
mm(1, 8192, 2048, 2048)
