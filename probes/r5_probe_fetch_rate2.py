import sys, time
sys.path.insert(0, "/root/repo")
import jax
import jax.numpy as jnp
import numpy as np

# single contiguous buffers at several sizes: is the 15 MB/s per-byte or per-transfer?
for mb in (64, 512, 2048):
    x = jnp.ones((mb * 1024 * 1024 // 4,), jnp.float32)
    x.block_until_ready() if hasattr(x, "block_until_ready") else np.asarray(x[:1])
    t0 = time.perf_counter()
    h = jax.device_get(x)
    w = time.perf_counter() - t0
    print(f"{mb:5d} MB single buffer: {w:.1f}s = {mb/w:.1f} MB/s", flush=True)
