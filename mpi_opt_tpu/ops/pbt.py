"""PBT exploit/explore as pure array ops over a population axis.

Reference behavior (SURVEY.md §2 row 5; reference unreadable): PBT ranks
the population after each generation; the bottom truncation-fraction
copies weights + hyperparameters from a random top performer (exploit)
and perturbs the copied hyperparameters (explore). In the reference this
is an ``MPI_Allgather`` of scores followed by per-rank decisions and
point-to-point weight transfers.

TPU-native design: the decision is computed here as a source-index map
``src_idx: int32[n]`` — member i should continue from member
``src_idx[i]``'s state (``src_idx[i] == i`` for survivors). The backend
then realises the exploit as ONE gather along the population axis:

    pop_state = jax.tree.map(lambda x: x[src_idx], pop_state)

which XLA lowers to an on-device gather (or an all-to-all over a sharded
mesh axis) — weights never touch the host.

Explore perturbs in unit-cube space: continuous dims get truncated
Gaussian noise (equivalently a multiplicative perturbation for
log-uniform domains, since they are log-affine in unit space); discrete
dims resample with probability ``resample_prob``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from mpi_opt_tpu.ops.common import rank_descending


@dataclasses.dataclass(frozen=True)
class PBTConfig:
    truncation_frac: float = 0.25  # bottom frac exploits, top frac is source pool
    perturb_scale: float = 0.15  # stddev of unit-space Gaussian perturbation
    resample_prob: float = 0.1  # per-discrete-dim chance to resample on explore

    def __post_init__(self):
        # past a half the bottom cut and the source pool overlap: a loser
        # would copy a member that is itself replaced
        if not 0.0 < self.truncation_frac <= 0.5:
            raise ValueError(f"truncation_frac must be in (0, 0.5], got {self.truncation_frac}")


def pbt_exploit_explore(
    key: jax.Array,
    unit: jax.Array,  # float32[n, d] population hparams, unit cube
    scores: jax.Array,  # float32[n], higher is better
    discrete_mask: jax.Array,  # bool[d]
    cfg: PBTConfig = PBTConfig(),
):
    """One PBT generation decision.

    Returns:
        new_unit: float32[n, d] — hparams after exploit+explore.
        src_idx: int32[n] — state-source map for the weight gather.
        exploited: bool[n] — which members were replaced.

    Fully jittable; ``n``, ``d`` and ``cfg`` are static.
    """
    with jax.named_scope("exploit"):
        return _exploit_explore(key, unit, scores, discrete_mask, cfg)


def pbt_exploit_explore_mo(
    key: jax.Array,
    unit: jax.Array,  # float32[n, d]
    norm_scores: jax.Array,  # float32[n, m] maximize-form objective matrix
    discrete_mask: jax.Array,  # bool[d]
    cfg: PBTConfig = PBTConfig(),
    norm_bounds=None,  # float32[m] maximize-form bounds, or None
):
    """Multi-objective PBT decision: truncation-exploit by Pareto rank.

    Identical mechanics to :func:`pbt_exploit_explore` — same key
    splits, same truncation/perturb/resample ops — except the
    population is ranked by :func:`~mpi_opt_tpu.objectives.pareto.
    pareto_score` (non-dominated front, then crowding, with
    constraint-aware degradation) instead of a scalar. Stays a single
    compiled boundary op. Returns the scalar triple plus the effective
    selection scores ``float32[n]`` for observability.
    """
    from mpi_opt_tpu.objectives.pareto import pareto_score

    with jax.named_scope("exploit"):
        eff = pareto_score(norm_scores, norm_bounds=norm_bounds)
        new_unit, src_idx, bottom = _exploit_explore(
            key, unit, eff, discrete_mask, cfg
        )
    return new_unit, src_idx, bottom, eff


def _exploit_explore(key, unit, scores, discrete_mask, cfg):
    """Shared exploit/explore body; ``scores`` is whatever effective
    scalar ranks the population (raw score, or a Pareto effective
    score). The op sequence here is the PR-16 scalar sequence verbatim
    — the scalar path's bit-identity (PERF_NOTES round 6) hangs on the
    key-split order and op order not changing."""
    n, d = unit.shape
    k_src, k_noise, k_resample, k_resample_val = jax.random.split(key, 4)

    # never past a half of the population (an odd ``n`` at 0.5 rounds
    # up): no source is ever replaced, ``src_idx[src_idx] == src_idx``,
    # which ``PopulationTrainer.exploit_members`` copies in place by
    n_cut = max(1, min(int(round(n * cfg.truncation_frac)), n // 2))
    rank, order = rank_descending(scores)

    bottom = rank >= (n - n_cut)  # losers: exploit
    # each member draws a uniformly-random member of the top cut
    src_choice = order[jax.random.randint(k_src, (n,), 0, n_cut)]
    src_idx = jnp.where(bottom, src_choice, jnp.arange(n))

    copied = unit[src_idx]

    # explore: truncated-Gaussian jitter on continuous dims
    noise = jax.random.normal(k_noise, (n, d)) * cfg.perturb_scale
    perturbed = jnp.clip(copied + noise, 0.0, 1.0)
    # discrete dims: occasional uniform resample instead of jitter
    resample = jax.random.uniform(k_resample, (n, d)) < cfg.resample_prob
    fresh = jax.random.uniform(k_resample_val, (n, d))
    disc = jnp.where(resample, fresh, copied)
    explored = jnp.where(discrete_mask[None, :], disc, perturbed)

    new_unit = jnp.where(bottom[:, None], explored, unit)
    return new_unit, src_idx, bottom
