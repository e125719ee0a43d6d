"""Workload protocol."""

from __future__ import annotations

import abc
from typing import Any, Optional

from mpi_opt_tpu.space import SearchSpace


class Workload(abc.ABC):
    """A tunable training task.

    Subclasses must implement ``default_space`` and at least one of the
    two evaluation protocols. ``evaluate`` has a default implementation
    in terms of the stateful protocol.
    """

    name: str = "base"

    @abc.abstractmethod
    def default_space(self) -> SearchSpace:
        ...

    # -- stateful protocol (optional) ------------------------------------

    def init_state(self, params: dict, seed: int) -> Any:
        raise NotImplementedError(f"{self.name} has no stateful protocol")

    def train(self, state: Any, params: dict, steps: int, seed: int):
        """Advance training by ``steps``; returns (state, score)."""
        raise NotImplementedError(f"{self.name} has no stateful protocol")

    @property
    def stateful(self) -> bool:
        return type(self).train is not Workload.train

    # -- stateless protocol ----------------------------------------------

    def evaluate(self, params: dict, budget: int, seed: int) -> float:
        state = self.init_state(params, seed)
        _, score = self.train(state, params, budget, seed)
        return float(score)

    # -- multi-objective protocol (ISSUE 17) ------------------------------

    def objective_metrics(self) -> tuple[str, ...]:
        """Metric names the workload's multi-metric eval path can
        produce (empty = scalar-only). An ``--objectives`` spec must
        draw every name from this set; the CLI validates before
        anything compiles."""
        return ()

    def evaluate_multi(self, params: dict, budget: int, seed: int, names) -> dict:
        """Stateless multi-metric evaluation: ``{name: float}`` for the
        requested metric names (each from ``objective_metrics``)."""
        raise NotImplementedError(f"{self.name} has no multi-metric eval path")


def resolve_momentum_dtype():
    """The single resolution point for the momentum STORAGE dtype knob
    (probes/probe_bf16_momentum.py A/B): the env var, else None (= match
    params, f32). workload_arrays' trainer cache key and make_trainer
    must see the SAME value — resolving it twice independently is how a
    stale-dtype trainer gets silently served from the cache. The value
    is normalized through ``jnp.dtype`` so alias spellings ('f4',
    'float32') compare equal in checkpoint configs and cache keys."""
    import os

    raw = os.environ.get("MPI_OPT_TPU_MOMENTUM_DTYPE")
    if not raw:
        return None
    import jax.numpy as jnp

    return str(jnp.dtype(raw))


class PopulationWorkload(Workload):
    """Workloads evaluable as rows of a vmapped population (NN models).

    Subclasses set ``dataset``, ``batch_size``, ``augment`` and implement
    ``_model(n_classes)``; they get the population protocol consumed by
    the TPU backend (``data``/``make_trainer``/``make_hparams``) plus a
    stateless ``evaluate`` (n=1 population, runs on whatever platform the
    process defaults to — CPU in pool workers), which is the per-rank
    parity path mirroring the reference's MPIWorker unit of work.
    """

    dataset: str = ""
    batch_size: int = 256
    augment: bool = True
    # synthetic sets are subsettable; sklearn loaders have fixed sizes
    # (subclasses with fixed-size data set these to None)
    default_n_train: int | None = 16384
    default_n_val: int | None = 2048

    def __init__(self, n_train: int | None = None, n_val: int | None = None):
        self.n_train = n_train if n_train is not None else self.default_n_train
        self.n_val = n_val if n_val is not None else self.default_n_val
        self._data = None

    def _model(self, n_classes: int):
        raise NotImplementedError

    def data(self) -> dict:
        if self._data is None:
            from mpi_opt_tpu.data import load_dataset

            kwargs = {}
            if self.n_train is not None:
                kwargs = {"n_train": self.n_train, "n_val": self.n_val}
            self._data = load_dataset(self.dataset, **kwargs)
        return self._data

    def make_trainer(
        self, member_chunk: int = 0, donate: bool = True, mesh=None, momentum_dtype=None
    ):
        import jax.numpy as jnp

        from mpi_opt_tpu.train import PopulationTrainer

        model = self._model(self.data()["n_classes"])
        if momentum_dtype is None:
            momentum_dtype = resolve_momentum_dtype()
        return PopulationTrainer(
            apply_fn=lambda params, x: model.apply({"params": params}, x),
            init_fn=lambda rng, sample_x: model.init(rng, sample_x)["params"],
            batch_size=self.batch_size,
            augment=self.augment,
            member_chunk=member_chunk,
            donate=donate,
            mesh=mesh,
            momentum_dtype=jnp.dtype(momentum_dtype) if momentum_dtype else None,
        )

    def make_hparams(self, values: dict):
        import jax.numpy as jnp

        from mpi_opt_tpu.train import OptHParams

        zeros = jnp.zeros_like(values["lr"])
        return OptHParams(
            lr=values["lr"],
            momentum=values["momentum"],
            weight_decay=values["weight_decay"],
            flip_prob=values.get("flip_prob", zeros),
            shift=values.get("shift", zeros),
        )

    def _eval_state(self, params: dict, budget: int, seed: int):
        """Shared n=1 from-scratch training for the stateless eval paths."""
        import jax
        import jax.numpy as jnp

        if not hasattr(self, "_eval_cache"):
            d = self.data()
            self._eval_cache = (
                self.make_trainer(),
                self.default_space(),
                jnp.asarray(d["train_x"]),
                jnp.asarray(d["train_y"]),
                jnp.asarray(d["val_x"]),
                jnp.asarray(d["val_y"]),
            )
        trainer, unit_space, train_x, train_y, val_x, val_y = self._eval_cache
        row = unit_space.params_to_unit(params)
        values = unit_space.from_unit(jnp.asarray(row)[None, :])
        hp = self.make_hparams(values)
        key = jax.random.key(seed)
        k_init, k_train = jax.random.split(key)
        state = trainer.init_population(k_init, train_x[:2], 1)
        state, _ = trainer.train_segment(state, hp, train_x, train_y, k_train, int(budget))
        return trainer, state, val_x, val_y

    def evaluate(self, params: dict, budget: int, seed: int) -> float:
        """Single-trial from-scratch training; see class docstring.

        The trainer and device arrays are cached on the instance —
        a trainer owns its compiled programs, so a fresh trainer per
        call would recompile every trial.
        """
        trainer, state, val_x, val_y = self._eval_state(params, budget, seed)
        acc = trainer.eval_population(state, val_x, val_y)
        return float(acc[0])

    def objective_metrics(self) -> tuple[str, ...]:
        from mpi_opt_tpu.train.common import POPULATION_METRICS

        return POPULATION_METRICS

    def evaluate_multi(self, params: dict, budget: int, seed: int, names) -> dict:
        """Multi-metric twin of ``evaluate``: one n=1 training run, then
        the same per-member metric columns the fused path computes
        (``train.common.eval_population_objectives``), so driver-path
        and fused-path objective values agree by construction."""
        from mpi_opt_tpu.train.common import eval_population_objectives

        trainer, state, val_x, val_y = self._eval_state(params, budget, seed)
        mo = eval_population_objectives(trainer, state, val_x, val_y, tuple(names))
        return {name: float(mo[0, j]) for j, name in enumerate(names)}
