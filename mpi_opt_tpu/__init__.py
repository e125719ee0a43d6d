"""mpi_opt_tpu — a TPU-native hyperparameter-optimization framework.

A from-scratch re-design of the capabilities of ``quantummind/mpi_opt``
(an MPI coordinator/worker HPO framework; see SURVEY.md — the reference
mount was empty at survey time, so the capability surface is taken from
BASELINE.json) built TPU-first:

- trial evaluation is a single vmapped population kernel
  ``jax.jit(jax.vmap(train_step))`` over a population axis, instead of
  per-rank MPI workers;
- PBT exploit/explore and ASHA rung reductions are ``lax.top_k`` /
  gathers executed on-device, instead of ``MPI_Allgather`` + per-rank
  decisions;
- scaling is a ``jax.sharding.Mesh(('pop', 'data'))`` with XLA
  collectives over ICI/DCN, instead of MPI process blocks.

Public surface:
    SearchSpace, Domain subclasses      — mpi_opt_tpu.space
    Trial records                       — mpi_opt_tpu.trial
    decision kernels (asha, pbt, tpe)   — mpi_opt_tpu.ops
    algorithms / backends / driver / CLI — see README; added incrementally
"""

__version__ = "0.1.0"

# The public names resolve on first use (PEP 562): ``space`` imports
# jax, and processes that must stay off jax import this package too —
# the launch supervisor and ``chip_smoke.py``'s parent start children
# that need the chip, which a parent that has touched jax would hold.
_LAZY = {
    "SearchSpace": "space",
    "Uniform": "space",
    "LogUniform": "space",
    "IntUniform": "space",
    "Choice": "space",
    "Trial": "trial",
    "TrialResult": "trial",
    "TrialStatus": "trial",
}

__all__ = [*_LAZY, "__version__"]


def __getattr__(name):
    module = _LAZY.get(name)
    if module is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(f"{__name__}.{module}"), name)
