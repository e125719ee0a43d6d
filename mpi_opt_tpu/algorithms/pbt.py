"""Population Based Training, host-side generational bookkeeping.

Reference behavior (SURVEY.md §2 row 5; reference unreadable): a fixed
population trains in parallel; each generation, losers copy winners'
weights + hyperparameters (exploit) and perturb them (explore). The
reference synchronizes this with ``MPI_Allgather`` of scores and
point-to-point weight transfers between ranks.

Host-side role here: this class drives PBT *through the generic backend
interface* — it emits one generation of member-trials at a time, and on
a full generation's results calls the same ``ops.pbt_exploit_explore``
kernel the TPU backend fuses on-device. Weight copies are communicated
to the backend as ``inherit_from`` metadata (trial_id of the source
member); a stateful backend maps that to a state copy — the TPU backend
instead realises it as a pure gather along the population axis without
any host involvement (see backends/tpu.py), which is the fast path.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from mpi_opt_tpu.algorithms.base import Algorithm
from mpi_opt_tpu.utils.hostdev import host_ops
from mpi_opt_tpu.ops.pbt import PBTConfig, pbt_exploit_explore
from mpi_opt_tpu.space import SearchSpace
from mpi_opt_tpu.trial import TrialResult, TrialStatus


class PBT(Algorithm):
    name = "pbt"

    def __init__(
        self,
        space: SearchSpace,
        seed: int = 0,
        population: int = 32,
        generations: int = 10,
        steps_per_generation: int = 200,
        config: PBTConfig = PBTConfig(),
    ):
        super().__init__(space, seed)
        self.population = population
        self.generations = generations
        self.steps_per_generation = steps_per_generation
        self.config = config
        self.generation = 0
        # slot -> current trial occupying it; a "trial" here is one
        # member-generation (fresh id per generation, as each may carry
        # new hparams/weights lineage)
        self._slots: list[int] = []
        self._pending: set[int] = set()  # spawned but unreported
        self._dispatch: list[int] = []  # spawned but not yet handed to a backend
        self._gen_scores = np.zeros(population, dtype=np.float32)
        self._unit = None  # float32[population, d] current hparams

    def _spawn_generation(self, unit: np.ndarray, inherit: np.ndarray | None):
        """Create this generation's member trials and queue them."""
        prev_slots = list(self._slots)
        self._slots = []
        for slot in range(self.population):
            t = self._new_trial(unit[slot], budget=self.steps_per_generation * (self.generation + 1))
            t.history = []
            if inherit is not None:
                src_slot = int(inherit[slot])
                t.params["__inherit_from__"] = prev_slots[src_slot]
                t.params["__slot__"] = slot
            else:
                t.params["__inherit_from__"] = None
                t.params["__slot__"] = slot
            self._slots.append(t.trial_id)
            self._pending.add(t.trial_id)
            self._dispatch.append(t.trial_id)

    def _pop_dispatch(self, n):
        out = []
        while self._dispatch and len(out) < n:
            t = self.trials[self._dispatch.pop(0)]
            t.status = TrialStatus.RUNNING
            out.append(t)
        return out

    def next_batch(self, n):
        if self.finished():
            return []
        if self._dispatch:
            return self._pop_dispatch(n)
        if self._pending:
            # fully dispatched, awaiting reports for this generation
            return []
        if self._unit is None:  # first generation
            with host_ops():  # tiny draw: no accelerator dispatch
                key = jax.random.key(self.seed)
                self._unit = np.asarray(self.space.sample_unit(key, self.population))
            self._spawn_generation(self._unit, None)
            return self._pop_dispatch(n)
        # close the generation: exploit/explore via the shared kernel —
        # [P]-sized decision math, CPU-pinned for the same reason as
        # sampling (utils.hostdev rationale); the FUSED path runs the
        # same kernel on-device where it composes with the state gather
        with host_ops():
            key = jax.random.fold_in(jax.random.key(self.seed), 1000 + self.generation)
            new_unit, src_idx, _ = pbt_exploit_explore(
                key,
                jnp.asarray(self._unit),
                jnp.asarray(self._gen_scores),
                jnp.asarray(self.space.discrete_mask()),
                self.config,
            )
            self._unit = np.asarray(new_unit)
            src_idx = np.asarray(src_idx)
        self.generation += 1
        if self.finished():
            return []
        self._spawn_generation(self._unit, np.asarray(src_idx))
        return self._pop_dispatch(n)

    def report_batch(self, results: Sequence[TrialResult]):
        for r in results:
            if not r.ok:
                # a failed member scores -inf for the generation: it
                # ranks at the bottom of the exploit cut (rank_descending
                # sorts -inf last), so the next generation REPLACES it —
                # hparams and state copied from a surviving winner. NaN
                # would be wrong here: it also sorts last under argsort,
                # but any downstream arithmetic on the score vector
                # would propagate it
                t = self._mark_failed(r)
                self._pending.discard(r.trial_id)
                self._gen_scores[t.params["__slot__"]] = -np.inf
                continue
            t = self.trials[r.trial_id]
            t.record(r.score, r.step)
            t.status = TrialStatus.DONE
            self._pending.discard(r.trial_id)
            self._gen_scores[t.params["__slot__"]] = r.score

    def finished(self):
        return self.generation >= self.generations and not self._pending

    # -- checkpoint -------------------------------------------------------

    def state_dict(self):
        d = super().state_dict()
        d["pbt"] = {
            "generation": self.generation,
            "slots": list(self._slots),
            "gen_scores": self._gen_scores.tolist(),
            "unit": None if self._unit is None else self._unit.tolist(),
            # everything unreported, in slot order, for re-dispatch on resume
            "pending": [t for t in self._slots if t in self._pending],
            # per-member metadata, which base-class trial reconstruction
            # (unit -> params re-materialization) does not preserve
            "inherit": {
                str(tid): self.trials[tid].params.get("__inherit_from__")
                for tid in self._slots
                if tid in self.trials
            },
        }
        return d

    def load_state_dict(self, state):
        super().load_state_dict(state)
        p = state["pbt"]
        self.generation = p["generation"]
        self._slots = list(p["slots"])
        self._gen_scores = np.asarray(p["gen_scores"], dtype=np.float32)
        self._unit = None if p["unit"] is None else np.asarray(p["unit"], dtype=np.float32)
        # restore current-generation member metadata
        inherit = p.get("inherit", {})
        for slot, tid in enumerate(self._slots):
            if tid in self.trials:
                self.trials[tid].params["__slot__"] = slot
                self.trials[tid].params["__inherit_from__"] = inherit.get(str(tid))
        # in-flight results died with the old process: re-dispatch them
        pending = [int(t) for t in p.get("pending", [])]
        self._pending = set(pending)
        self._dispatch = list(pending)
