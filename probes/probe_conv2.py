"""Compare population-conv strategies: P members, each its own 3x3 kernel."""
import time, functools
import jax, jax.numpy as jnp

P, B, H, W, C, O = 32, 256, 32, 32, 32, 32
kx = jax.random.key(0)
x = jax.random.normal(kx, (P, B, H, W, C), jnp.bfloat16)
w = jax.random.normal(jax.random.key(1), (P, 3, 3, C, O), jnp.bfloat16) * 0.05

conv1 = lambda xi, wi: jax.lax.conv_general_dilated(xi, wi, (1,1), "SAME", dimension_numbers=("NHWC","HWIO","NHWC"))

def strat_vmap(x, w):
    return jax.vmap(conv1)(x, w)

def strat_grouped(x, w):
    # members as feature groups: [B,H,W,P*C] conv [3,3,C,P*O] fgc=P
    xg = jnp.transpose(x, (1,2,3,0,4)).reshape(B,H,W,P*C)
    wg = jnp.transpose(w, (1,2,0,3,4)).reshape(3,3,C,P*O)
    # note w layout per group: HWIO with I=C per group
    wg = w.transpose(1,2,3,0,4).reshape(3,3,C,P*O)  # [3,3,C,P,O] -> groups on O
    yg = jax.lax.conv_general_dilated(xg, wg, (1,1), "SAME",
        dimension_numbers=("NHWC","HWIO","NHWC"), feature_group_count=P)
    return jnp.transpose(yg.reshape(B,H,W,P,O), (3,0,1,2,4))

def strat_im2col(x, w):
    pat = jax.vmap(lambda xi: jax.lax.conv_general_dilated_patches(
        xi, (3,3), (1,1), "SAME", dimension_numbers=("NHWC","HWIO","NHWC")))(x)  # [P,B,H,W,9C]
    wf = w.transpose(0,3,1,2,4).reshape(P, C*9, O)  # patches order: C,ky,kx? -> match below
    # conv_general_dilated_patches channel order is (C, kh, kw) flattened
    return jnp.einsum("pbhwk,pko->pbhwo", pat, wf)

def bench(name, fn):
    loss = lambda x, w: jnp.sum(fn(x, w) ** 2).astype(jnp.float32)
    g = jax.jit(jax.grad(loss, argnums=(0, 1)))
    try:
        r = g(x, w); jax.block_until_ready(r)
        t0 = time.perf_counter()
        for _ in range(10):
            r = g(x, w)
        jax.block_until_ready(r)
        dt = (time.perf_counter() - t0) / 10
        fl = 3 * 2 * P*B*H*W*9*C*O  # fwd+bwd approx 3x fwd
        print(f"{name}: {dt*1e3:.2f} ms/iter  ({fl/dt/1e12:.1f} TF/s eff)")
    except Exception as e:
        print(f"{name}: FAIL {type(e).__name__} {str(e)[:100]}")

# correctness check fwd
y0 = strat_vmap(x, w); y1 = strat_grouped(x, w); y2 = strat_im2col(x, w)
import numpy as np
print("grouped maxdiff:", float(jnp.abs(y0-y1).max()))
print("im2col  maxdiff:", float(jnp.abs(y0-y2).max()))
bench("vmap(conv)   ", strat_vmap)
bench("grouped fgc=P", strat_grouped)
bench("im2col matmul", strat_im2col)
