"""Asynchronous Successive Halving (ASHA), host-side bookkeeping.

Reference behavior (SURVEY.md §2 row 4; reference unreadable): trials
start at the lowest budget rung; when a trial finishes a rung, it is
promoted to the next rung if it ranks in the top 1/eta of all scores
recorded at that rung so far, otherwise it is stopped — asynchronously,
without waiting for the rung to fill (the reference coordinates this
with MPI messages between coordinator and ranks).

Here the promotion rule is evaluated on the host over numpy arrays
(scores at a rung are tiny); the *synchronous* population-wide variant —
``mpi_opt_tpu.train.fused_asha.fused_sha`` — runs the rung cuts
on-device through ``mpi_opt_tpu.ops.asha_cut``. Budgets are cumulative: a promoted
trial's ``budget`` is the next rung's total step count, and stateful
backends resume from the trial's saved state rather than retraining.
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np

from mpi_opt_tpu.algorithms.base import Algorithm
from mpi_opt_tpu.utils.hostdev import host_ops
from mpi_opt_tpu.ops.asha import asha_rungs
from mpi_opt_tpu.space import SearchSpace
from mpi_opt_tpu.trial import TrialResult, TrialStatus


class ASHA(Algorithm):
    name = "asha"

    def __init__(
        self,
        space: SearchSpace,
        seed: int = 0,
        max_trials: int = 64,
        min_budget: int = 1,
        max_budget: int = 27,
        eta: int = 3,
        id_base: int = 0,
    ):
        super().__init__(space, seed, id_base=id_base)
        self.max_trials = max_trials
        self.eta = eta
        self.rungs = asha_rungs(min_budget, max_budget, eta)
        # scores recorded per rung: rung index -> {trial_id: score}
        self.rung_scores: list[dict[int, float]] = [dict() for _ in self.rungs]
        self._suggested = 0
        self._promotable: list[int] = []  # trial ids awaiting their next rung
        self._outstanding: set[int] = set()

    # -- contract ---------------------------------------------------------

    def next_batch(self, n):
        out = []
        # trials whose results were lost to a checkpoint/restore cycle
        # get re-dispatched before anything else
        self._drain_requeue(out, n)
        # continuing trials next: they free memory sooner and drive the
        # search deeper (same priority the async rule gives promotions)
        while self._promotable and len(out) < n:
            tid = self._promotable.pop(0)
            t = self.trials[tid]
            t.status = TrialStatus.RUNNING
            out.append(t)
        # CPU-pinned sampling (utils.hostdev: one-row samples need no
        # accelerator dispatch); also covers BOHB's model-sampling
        # override of _sample_fresh
        with host_ops():
            while len(out) < n and self._suggested < self.max_trials:
                key = jax.random.fold_in(jax.random.key(self.seed), self._suggested)
                # warm-start points (ingest_observations) take the first
                # fresh slots; they enter the rung race as ordinary
                # lowest-rung trials and must earn their promotions
                seed_u = self._next_seed_unit()
                unit = seed_u if seed_u is not None else self._sample_fresh(key)
                t = self._new_trial(unit, budget=self.rungs[0])
                t.status = TrialStatus.RUNNING
                out.append(t)
                self._suggested += 1
        self._outstanding.update(t.trial_id for t in out)
        return out

    def report_batch(self, results: Sequence[TrialResult]):
        for r in results:
            if not r.ok:
                # the failed trial leaves the rung race entirely: it is
                # discarded from _outstanding (so finished() can close
                # without waiting on it forever), never enters
                # rung_scores (a NaN there would promote — NaN compares
                # false against everything, so it always looks top-k),
                # and is never promotable
                self._outstanding.discard(r.trial_id)
                self._mark_failed(r)
                continue
            t = self.trials[r.trial_id]
            self._outstanding.discard(r.trial_id)
            t.record(r.score, r.step)
            rung = t.rung
            self.rung_scores[rung][t.trial_id] = float(r.score)
            if rung == len(self.rungs) - 1:
                t.status = TrialStatus.DONE
                continue
            if self._promotes(rung, r.score):
                t.rung = rung + 1
                t.budget = self.rungs[t.rung]
                t.status = TrialStatus.PAUSED
                self._promotable.append(t.trial_id)
            else:
                t.status = TrialStatus.STOPPED

    def finished(self):
        no_new = self._suggested >= self.max_trials
        return (
            no_new and not self._promotable and not self._outstanding and not self._requeue
        )

    def ingest_observations(self, observations):
        # best() seeding: the prior's best point joins the first cohort
        # at the lowest rung (cheap to verify, promoted only on merit)
        return self._ingest_seed_points(observations)

    # -- fresh-trial sampling (overridable: BOHB swaps in a model) --------

    def _sample_fresh(self, key) -> np.ndarray:
        """Unit-cube row for a brand-new trial. ASHA itself samples
        uniformly; model-based variants (algorithms/bohb.py) override
        this single point to keep the halving logic one source of truth."""
        return np.asarray(self.space.sample_unit(key, 1))[0]

    # -- promotion rule ---------------------------------------------------

    def _promotes(self, rung: int, score: float) -> bool:
        """Async rule: in the top 1/eta of scores recorded at this rung."""
        scores = np.array(list(self.rung_scores[rung].values()))
        k = max(1, int(np.ceil(len(scores) / self.eta)))
        # count of strictly-better scores < k  =>  within top-k
        return int((scores > score).sum()) < k

    # -- checkpoint -------------------------------------------------------

    def state_dict(self):
        d = super().state_dict()
        d["asha"] = {
            "suggested": self._suggested,
            "promotable": list(self._promotable),
            "rung_scores": [dict(r) for r in self.rung_scores],
        }
        return d

    def load_state_dict(self, state):
        super().load_state_dict(state)
        a = state["asha"]
        self._suggested = a["suggested"]
        self._promotable = list(a["promotable"])
        self.rung_scores = [
            {int(k): v for k, v in r.items()} for r in a["rung_scores"]
        ]
        self._outstanding = set()
        # in-flight trials (still RUNNING in the restored ledger) lost
        # their results with the old process; re-dispatch them rather
        # than dropping them as RUNNING forever
        self._requeue_running()
