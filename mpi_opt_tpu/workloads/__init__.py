"""Workload registry: trial evaluators / model zoo (SURVEY.md §2 row 10).

A workload bundles a default search space with the train-and-score
functions the backends call. Two evaluation protocols:

- stateless: ``evaluate(params, budget, seed) -> score`` — train from
  scratch to ``budget``; what the reference's MPIWorker does per trial.
- stateful: ``init_state``/``train`` — resumable training for ASHA
  promotions and PBT inheritance without retraining from scratch.

NN workloads additionally expose the pieces the TPU population backend
vmaps (see mpi_opt_tpu/backends/tpu.py).
"""

from mpi_opt_tpu.workloads.base import Workload

_REGISTRY: dict[str, type] = {}


def register(cls):
    _REGISTRY[cls.name] = cls
    return cls


def get_workload(name: str, **kwargs) -> Workload:
    try:
        cls = _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown workload {name!r}; available: {sorted(_REGISTRY)}") from None
    return cls(**kwargs)


def available() -> list[str]:
    return sorted(_REGISTRY)


# import for registration side effects (chaos last: it wraps the others)
from mpi_opt_tpu.workloads import digits, language, synthetic, tabular, vision  # noqa: E402,F401
from mpi_opt_tpu.workloads import chaos  # noqa: E402,F401

__all__ = ["Workload", "register", "get_workload", "available"]
