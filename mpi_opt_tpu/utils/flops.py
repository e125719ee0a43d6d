"""FLOPs accounting + MFU (model-flops-utilization) for benchmark runs.

BASELINE.json's metric of record is throughput (trials/sec/chip); MFU is
the companion number that says how much of the chip that throughput
actually uses — without it, "fast" can mean "faster than one CPU" while
leaving most of the MXU idle (the round-1 failure mode).

FLOPs come from XLA's own cost model (``Compiled.cost_analysis()``) on
the exact executable being measured, not from a hand-derived per-layer
formula — so rematerialization, eval passes, and the PBT/ASHA decision
kernels are all counted as compiled, and the number stays correct when
the model changes. Peak numbers are the published dense bf16 ratings
per TPU generation (MXU path; the models package computes in bf16).
"""

from __future__ import annotations

from typing import Optional

# (substring of jax Device.device_kind, dense bf16 peak FLOP/s per chip)
# Published per-chip numbers (Google Cloud TPU documentation, each
# generation's system-architecture page): v4 275 TF, v5e 197 TF (its
# 394 is the int8 figure), v5p 459 TF, v6e/Trillium 918 TF. Matching is
# substring-based because device_kind strings vary across libtpu
# versions ("TPU v5 lite", "TPU v5e", ...).
_PEAKS = (
    ("v6e", 918e12),
    ("trillium", 918e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def peak_flops_per_chip(device=None) -> Optional[float]:
    """Dense bf16 peak FLOP/s for ``device`` (default: first device).

    Returns None off-TPU (CPU has no meaningful single peak for MFU).
    A TPU kind that is not in the table is an error, never a guess: a
    utilization against the wrong peak reads as a measurement.
    """
    import jax

    if device is None:
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "").lower()
    if "tpu" not in kind and getattr(device, "platform", "") != "tpu":
        return None
    for tag, peak in _PEAKS:
        if tag in kind:
            return peak
    raise ValueError(
        f"no published bf16 peak for TPU kind {device.device_kind!r} in "
        "utils/flops.py _PEAKS; add it with its source"
    )


def compiled_flops(jitted_fn, *args, **kwargs) -> Optional[float]:
    """Total FLOPs of one execution of ``jitted_fn(*args, **kwargs)``,
    from XLA's cost analysis of the compiled executable.

    Uses the AOT path (``lower().compile()``); with the persistent
    compilation cache enabled (bench.py sets it) this re-hits the cache
    of the measured run rather than recompiling. Returns None when the
    backend's cost analysis is unavailable (some plugin backends).

    CAVEAT (measured on this container, 2026-07-30): XLA counts a
    While-loop body ONCE, not per trip — a whole-sweep program with
    ``lax.scan`` loops reports ~10x under truth. Only trust this on
    programs whose scans have trip count 1; for sweeps, compose with
    ``population_sweep_flops`` below.
    """
    try:
        cost = jitted_fn.lower(*args, **kwargs).compile().cost_analysis()
        return float(cost["flops"])
    except Exception:
        return None


def population_sweep_flops(
    workload, population: int, generations: int, steps_per_gen: int,
    n_evals: Optional[int] = None, eval_chunk: int = 1024,
) -> Optional[float]:
    """FLOPs of a fused population sweep, composed from XLA-counted
    single-trip pieces scaled by their true trip counts.

    Lowers a ONE-member, ONE-step train segment and a one-member,
    one-chunk eval (every scan inside has trip count 1, where XLA's
    count is exact — verified against hand math for the SmallCNN:
    36.6 GFLOP/member-step vs ~38 by hand) and scales linearly:
    flops are exactly linear in members/steps/chunks; the only
    approximation is the shared per-step batch gather being charged
    per member, and gathers contribute bytes, not flops.

    ``n_evals`` defaults to generations — fused PBT evaluates once per
    generation and its final scores are a gather of the last
    generation's eval, not a re-eval (train/fused_pbt.py).
    """
    import jax
    import jax.numpy as jnp

    try:
        trainer = workload.make_trainer(donate=False)  # no member_chunk:
        # lax.map would add an inner loop and re-trigger the While caveat
        from mpi_opt_tpu.train.population import OptHParams

        d = workload.data()
        tx = jnp.asarray(d["train_x"])
        ty = jnp.asarray(d["train_y"])
        vx = jnp.asarray(d["val_x"])[:eval_chunk]
        vy = jnp.asarray(d["val_y"])[:eval_chunk]
        key = jax.random.key(0)
        state = trainer.init_population(key, tx[:2], 1)
        hp = OptHParams.defaults(1)
        f_step = compiled_flops(trainer.train_segment, state, hp, tx, ty, key, steps=1)
        f_eval = compiled_flops(
            type(trainer).eval_population.program(trainer),
            state, vx, vy, eval_chunk=eval_chunk,
        )
        if f_step is None or f_eval is None:
            raise RuntimeError(
                f"cost analysis returned no flops (step={f_step}, eval={f_eval})"
            )
        n_val = int(jnp.shape(jnp.asarray(d["val_y"]))[0])
        n_chunks = -(-n_val // eval_chunk)
        if n_evals is None:
            n_evals = generations
        return population * (
            generations * steps_per_gen * f_step + n_evals * n_chunks * f_eval
        )
    except Exception as e:
        # None (not a crash) keeps benches running without flops, but a
        # silent None turns MFU into a mystery — say why on stderr
        import sys

        print(
            f"[flops] population_sweep_flops unavailable: {type(e).__name__}: {e}",
            file=sys.stderr,
        )
        return None


def mfu(total_flops: Optional[float], seconds: float, device=None) -> Optional[float]:
    """Achieved FLOP/s as a fraction of the chip's dense bf16 peak."""
    peak = peak_flops_per_chip(device)
    if not total_flops or not peak or seconds <= 0:
        return None
    return total_flops / seconds / peak
