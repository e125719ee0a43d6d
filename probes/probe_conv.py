import time, jax, jax.numpy as jnp, numpy as np
from functools import partial
P, B, H, W, Cin, Cout = 32, 256, 32, 32, 32, 32
N = 100
k = jax.random.key(0)
def conv(x, w):
    return jax.lax.conv_general_dilated(x, w, (1,1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
x_shared = jax.random.normal(k, (B, H, W, Cin), jnp.bfloat16)
x_member = jax.random.normal(k, (P, B, H, W, Cin), jnp.bfloat16)
w = jax.random.normal(k, (P, 3, 3, Cin, Cout), jnp.bfloat16)
xbig = x_member.reshape(P*B, H, W, Cin)
wone = w[0]

def repeat(body):
    @jax.jit
    def f(x, w):
        def step(c, _):
            # fold the loop counter in so XLA can't hoist the conv
            return c + body(x, w), None
        out, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32), None, length=N)
        return out
    return f

f_shared = repeat(lambda x, w: jax.vmap(conv, in_axes=(None, 0))(x, w).astype(jnp.float32).sum())
f_member = repeat(lambda x, w: jax.vmap(conv, in_axes=(0, 0))(x, w).astype(jnp.float32).sum())
f_big    = repeat(lambda x, w: conv(x, w).astype(jnp.float32).sum())

flops = 2*9*Cin*Cout*H*W*B*P
for name, f, a in (("vmap shared-x", f_shared, (x_shared, w)),
                   ("vmap member-x", f_member, (x_member, w)),
                   ("one big conv (ub)", f_big, (xbig, wone))):
    float(f(*a))  # compile+warm
    t0 = time.time(); float(f(*a)); dt = (time.time()-t0)/N
    print(f"{name}: {dt*1e3:.3f} ms/iter -> {flops/dt/1e12:.1f} TFLOP/s")
