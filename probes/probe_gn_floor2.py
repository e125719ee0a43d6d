"""Yardstick for probe_gn_floor: pure streaming op (y = 2x + 1) at the
same shape gives the platform's real bandwidth for this access pattern;
GN's pass count = GN time / per-pass time."""
import statistics, sys, time
sys.path.insert(0, "/root/repo")
import jax, jax.numpy as jnp

P, B, H, W, C = 32, 256, 32, 32, 32
x = jax.random.normal(jax.random.key(0), (P, B, H, W, C), jnp.bfloat16)
ITERS = 40
@jax.jit
def run(x):
    def body(i, acc):
        y = x * (2.0 + acc * 1e-20) + 1.0          # read x, write y
        return acc + y[0, 0, 0, 0, 0].astype(jnp.float32) * 1e-9
    return jax.lax.fori_loop(0, ITERS, body, 0.0)
float(run(x))
walls = []
for _ in range(3):
    t0 = time.perf_counter(); float(run(x)); walls.append(time.perf_counter() - t0)
per = statistics.median(walls) / ITERS
gb = x.size * 2 / 1e9
print(f"stream per-iter {per*1e3:.2f} ms for {2*gb:.2f} GB (r+w) -> {2*gb/per:.0f} GB/s; "
      f"one-pass time {per/2*1e3:.2f} ms/pass-GBset")
