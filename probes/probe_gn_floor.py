"""Is XLA's GroupNorm already at the bandwidth floor at config-3 shapes?

GN fwd+bwd at the workload's activation shape, fori-loop fetch-once
harness. Floor = minimum HBM passes (fwd: read x + write y; bwd: read
x, dy + write dx) at the platform's measured effective bandwidth
(~100-200 GB/s, PERF_NOTES). If measured ~ floor, a fused Pallas GN
has no headroom; if >> floor, XLA is making extra passes worth fusing.
"""
import statistics, sys, time
sys.path.insert(0, "/root/repo")
import flax.linen as nn
import jax, jax.numpy as jnp
import numpy as np

P, B, H, W, C = 32, 256, 32, 32, 32
gn = nn.GroupNorm(num_groups=8, dtype=jnp.bfloat16)
key = jax.random.key(0)
x = jax.random.normal(key, (P, B, H, W, C), jnp.bfloat16)
params = jax.vmap(lambda k: gn.init(k, jnp.zeros((B, H, W, C), jnp.bfloat16)))(
    jax.random.split(key, P))

def loss(p, x):
    y = jax.vmap(lambda pm, xm: gn.apply(pm, xm))(p, x)
    return jnp.sum(nn.relu(y).astype(jnp.float32)) * 1e-9

ITERS = 20
@jax.jit
def run(p, x):
    def body(i, acc):
        l, grads = jax.value_and_grad(loss, argnums=(0, 1))(p, x + acc * 1e-20)
        return acc + l + jnp.sum(grads[1][0, 0, 0, 0, 0].astype(jnp.float32))
    return jax.lax.fori_loop(0, ITERS, body, 0.0)

float(run(params, x))  # compile
walls = []
for _ in range(3):
    t0 = time.perf_counter(); float(run(params, x)); walls.append(time.perf_counter() - t0)
per_iter = statistics.median(walls) / ITERS
gb = P * B * H * W * C * 2 / 1e9  # one pass over the activation, bf16
# fwd: read x, write y (2 passes) + bwd: read x, read dy, write dx (3)
floor_gb = 5 * gb
print(f"per-iter {per_iter*1e3:.1f} ms; activation pass = {gb:.2f} GB; "
      f"5-pass floor at 150 GB/s = {floor_gb/150*1e3:.1f} ms; "
      f"implied bw if floor-bound = {floor_gb/per_iter:.0f} GB/s")
