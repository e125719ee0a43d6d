"""Round-4 perf-ledger close-out: the two traced slices left unattacked.

(a) `convert_reduce` fusions (~16% of device time, round-2 trace): the
    f32 loss path around bf16 compute — logits upcast, f32 log_softmax,
    f32 mean. A/B: compute log_softmax in bf16 (mean still f32) and
    measure BOTH wall and learning, pool-swap-probe protocol.
(b) GroupNorm's share of the ~1.7x non-MXU factor: wall with GroupNorm
    replaced by identity (a COST measurement — the no-norm model's
    learning is not comparable, and isn't claimed to be).

Config-3 shapes (SmallCNN, pop=32, batch 256, 100-step segments), real
chip, fetch-once harness per PERF_NOTES measurement rules.
"""
import statistics
import sys
import time

sys.path.insert(0, "/root/repo")

import jax
import jax.numpy as jnp
import numpy as np


from mpi_opt_tpu.train.population import OptHParams, PopulationTrainer
from mpi_opt_tpu.workloads import get_workload

POP, STEPS, REPS = 32, 100, 3


def segment_wall(wl):
    from mpi_opt_tpu.train.common import workload_arrays

    trainer, space, tx, ty, vx, vy = workload_arrays(wl)
    st = trainer.init_population(jax.random.key(0), tx[:2], POP)
    hp = OptHParams.defaults(POP, lr=0.05)
    # warm (compile) + timed medians; fetch of the final loss is the barrier
    st, losses = trainer.train_segment(st, hp, tx, ty, jax.random.key(1), STEPS)
    np.asarray(losses)
    walls = []
    for i in range(REPS):
        t0 = time.perf_counter()
        st, losses = trainer.train_segment(st, hp, tx, ty, jax.random.fold_in(jax.random.key(2), i), STEPS)
        np.asarray(losses)
        walls.append(time.perf_counter() - t0)
    return statistics.median(walls), walls


def learn_score(wl):
    from mpi_opt_tpu.train.fused_pbt import fused_pbt

    res = fused_pbt(wl, population=POP, generations=2, steps_per_gen=STEPS, seed=0, gen_chunk=1)
    return res["best_score"]


def loss_bf16(self, params, hp, key, bx, by):
    """_member_loss with the softmax in bf16: kills the logits upcast +
    f32 log_softmax convert_reduce pair; only the final mean runs f32."""
    from mpi_opt_tpu.train.population import _augment

    if self.augment and bx.ndim == 4:
        bx = _augment(key, bx, hp.flip_prob, hp.shift)
    logits = self.apply_fn(params, bx)
    logp = jax.nn.log_softmax(logits.astype(jnp.bfloat16))
    picked = jnp.take_along_axis(logp, by[:, None], axis=1)
    return -jnp.mean(picked.astype(jnp.float32))


def main():
    print(f"device: {jax.devices()[0].device_kind}")

    wl_a = get_workload("cifar10_cnn")
    base_w, base_walls = segment_wall(wl_a)
    base_learn = learn_score(get_workload("cifar10_cnn"))
    print(f"A baseline      : {base_w:.3f}s {['%.3f' % w for w in base_walls]}  learn2g={base_learn:.4f}")

    orig = PopulationTrainer._member_loss
    PopulationTrainer._member_loss = loss_bf16
    try:
        wl_b = get_workload("cifar10_cnn")
        wl_b._fused_cache = None
        b_w, b_walls = segment_wall(wl_b)
        wl_b2 = get_workload("cifar10_cnn")
        wl_b2._fused_cache = None
        b_learn = learn_score(wl_b2)
    finally:
        PopulationTrainer._member_loss = orig
    print(f"B bf16 softmax  : {b_w:.3f}s {['%.3f' % w for w in b_walls]}  learn2g={b_learn:.4f}  "
          f"wall {100 * (1 - b_w / base_w):+.1f}%")

    import flax.linen as nn

    orig_gn = nn.GroupNorm
    nn.GroupNorm = lambda **kw: (lambda x: x)  # identity: pure cost measurement
    try:
        wl_c = get_workload("cifar10_cnn")
        wl_c._fused_cache = None
        c_w, c_walls = segment_wall(wl_c)
    finally:
        nn.GroupNorm = orig_gn
    print(f"C no-GroupNorm  : {c_w:.3f}s {['%.3f' % w for w in c_walls]}  "
          f"GN share of segment wall ~{100 * (1 - c_w / base_w):.1f}%")


if __name__ == "__main__":
    main()
