"""Does packing population members into MXU lanes help? (VERDICT r3 item 4)

The population conv is block-diagonal as a bilinear form: member m's
output needs member m's activations AND member m's weights, so any
dense-matmul packing of k members into the 128-lane dimension must
either (a) replicate the K (reduction) dimension k-fold with a
block-diagonal weight matrix — doing k x the MACs — or (b) give each
member its own matmul with N = Cout lanes. There is no formulation
where k members share one LHS: the lane fill gained is exactly paid
back in wasted MACs. This probe measures that equivalence on the real
chip rather than asserting it.

Measured 2026-07-30 (previous installation's v5e, not re-measured):

    single member   [8192,288]@[288,32]    : 14.3 TF/s useful
    4-pack blockdiag [8192,1152]@[1152,128]: 57.1 raw = 14.3 TF/s useful
    same-K full-lane [8192,288]@[288,128]  : 23.9 TF/s (unreachable bound)
    cap             4096^3                 : 157  TF/s

packed == single to three digits -> packing refuted; see PERF_NOTES.md
"Round 3 — MXU member-packing refuted by measurement". The same run
exposed that the round-2 platform-cap probe underread the machine 2.4x
(64.8 vs 157 TF/s) — bench.py's measure_platform_cap now uses this
harness's pattern.

Harness notes (both matter):
- a blocking fetch per iteration would time the fetch; loop the work
  inside one program behind a scalar serial dependency and fetch once;
- `x = a + s` (s the carried scalar) defeats loop-invariant hoisting
  without serializing through the full result matrix the way round 2's
  `b = (a @ b) * 1e-3` chain did.

Run from /root/repo: python probes/probe_mxu_pack.py
"""

import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np


def rate(M, K, N, loops, iters=4):
    """TF/s of [M,K]@[K,N] bf16 matmuls, `loops` per program, one fetch."""
    a = jax.random.normal(jax.random.key(0), (M, K), jnp.bfloat16)
    b = jax.random.normal(jax.random.key(1), (K, N), jnp.bfloat16) * 0.01

    @jax.jit
    def step(a, b):
        def body(i, s):
            x = a + s
            y = x @ b
            return jnp.sum(y).astype(jnp.bfloat16) * jnp.bfloat16(1e-9)

        return jax.lax.fori_loop(0, loops, body, jnp.bfloat16(0))

    float(step(a, b))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        s = step(a, b)
    float(s)
    t = (time.perf_counter() - t0) / iters
    return 2 * M * K * N * loops / t / 1e12


def main():
    print(f"device: {jax.devices()[0].device_kind}", flush=True)
    r_single = rate(8192, 288, 32, 8000)
    r_packed = rate(8192, 1152, 128, 2000)  # one packed matmul = 4 members
    r_ideal = rate(8192, 288, 128, 2000)
    r_cap = rate(4096, 4096, 4096, 200)
    print(f"single (N=32, 25% lanes):     {r_single:6.1f} TF/s useful")
    print(f"packed (N=128, 4x K): raw     {r_packed:6.1f} -> useful {r_packed/4:6.1f} TF/s")
    print(f"ideal  (N=128, 1x K, bound):  {r_ideal:6.1f} TF/s")
    print(f"cap    (4096^3):              {r_cap:6.1f} TF/s")
    win = r_packed / 4 / r_single
    print(f"\npacked/single useful rate: {win:.2f}x "
          f"({'packing WINS' if win > 1.05 else 'packing refuted'})")


if __name__ == "__main__":
    main()
