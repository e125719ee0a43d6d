"""TPE host wrapper around the vectorized acquisition kernel.

Reference behavior (SURVEY.md §2 row 6; reference unreadable): suggest
points maximizing l(x)/g(x) over Parzen estimators of good/bad trials.

The math lives in ``mpi_opt_tpu.ops.tpe.tpe_suggest`` (fixed-shape ring
buffer, batched candidate scoring). This class owns the buffer and the
trial ledger; the kernel is jitted once and reused for the whole search
regardless of how much history accumulates.
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np

from mpi_opt_tpu.algorithms.base import Algorithm
from mpi_opt_tpu.utils.hostdev import host_ops
from mpi_opt_tpu.ops.tpe import TPEConfig, tpe_suggest
from mpi_opt_tpu.space import SearchSpace
from mpi_opt_tpu.trial import TrialResult, TrialStatus


class TPE(Algorithm):
    name = "tpe"

    def __init__(
        self,
        space: SearchSpace,
        seed: int = 0,
        max_trials: int = 64,
        budget: int = 1,
        n_startup: int = 10,  # pure-random warmup before the surrogate kicks in
        buffer_size: int = 512,
        config: TPEConfig = TPEConfig(),
    ):
        super().__init__(space, seed)
        self.max_trials = max_trials
        self.budget = budget
        self.n_startup = n_startup
        self.config = config
        self.buffer_size = buffer_size
        self._obs_unit = np.zeros((buffer_size, space.dim), dtype=np.float32)
        self._obs_score = np.zeros(buffer_size, dtype=np.float32)
        self._valid = np.zeros(buffer_size, dtype=bool)
        self._n_obs = 0
        self._suggested = 0
        self._done = 0
        self._suggest_fn = jax.jit(tpe_suggest, static_argnames=("n_suggest", "cfg"))

    def ingest_observations(self, observations):
        """Prior-sweep observations become surrogate priors: they fill
        the observation ring exactly as live reports do, count toward
        ``n_startup`` (enough priors engage the surrogate from the very
        first suggestion), and never touch the trial ledger — they are
        observations, not trials, so ``best()``/``n_trials``/budget
        accounting are unaffected. Ascending score order: if the prior
        overflows the ring, the wrap evicts the WORST observations."""
        finite = [o for o in observations if np.isfinite(o.score)]
        finite.sort(key=lambda o: o.score)
        for o in finite:
            slot = self._n_obs % self.buffer_size
            self._obs_unit[slot] = np.asarray(o.unit, dtype=np.float32)
            self._obs_score[slot] = o.score
            self._valid[slot] = True
            self._n_obs += 1
        return len(finite)

    def next_batch(self, n):
        out = []
        self._drain_requeue(out, n)
        # the surrogate can only ever score n_candidates points, so a
        # backend capacity above that is clamped (not an IndexError)
        take = min(n - len(out), self.max_trials - self._suggested, self.config.n_candidates)
        if take <= 0:
            return out
        # CPU-pinned: the acquisition over a 512-row buffer is trivial
        # compute, and on the default device it costs a dispatch and a
        # blocking fetch per suggest batch (utils.hostdev rationale)
        with host_ops():
            key = jax.random.fold_in(jax.random.key(self.seed), self._suggested)
            if self._n_obs < self.n_startup:
                unit = np.asarray(self.space.sample_unit(key, take))
            else:
                # round n_suggest up to a power of two so varying batch
                # remainders hit at most log2(capacity) compiled variants
                block = 1 << (take - 1).bit_length()
                sugg, _ = self._suggest_fn(
                    key,
                    self._obs_unit,
                    self._obs_score,
                    self._valid,
                    n_suggest=min(block, self.config.n_candidates),
                    cfg=self.config,
                )
                unit = np.asarray(sugg[:take])
        for i in range(take):
            t = self._new_trial(unit[i], budget=self.budget)
            t.status = TrialStatus.RUNNING
            out.append(t)
        self._suggested += take
        return out

    def report_batch(self, results: Sequence[TrialResult]):
        for r in results:
            if not r.ok:
                # failed trials never enter the observation ring: a NaN
                # score would poison the Parzen moments, and counting it
                # toward n_startup would engage the surrogate on garbage
                self._mark_failed(r)
                self._done += 1
                continue
            t = self.trials[r.trial_id]
            t.record(r.score, r.step)
            t.status = TrialStatus.DONE
            slot = self._n_obs % self.buffer_size
            self._obs_unit[slot] = t.unit
            self._obs_score[slot] = r.score
            self._valid[slot] = True
            self._n_obs += 1
            self._done += 1

    def finished(self):
        return self._done >= self.max_trials

    # -- checkpoint -------------------------------------------------------

    def state_dict(self):
        d = super().state_dict()
        d["tpe"] = {
            "obs_unit": self._obs_unit.tolist(),
            "obs_score": self._obs_score.tolist(),
            "valid": self._valid.tolist(),
            "n_obs": self._n_obs,
            "suggested": self._suggested,
            "done": self._done,
        }
        return d

    def load_state_dict(self, state):
        super().load_state_dict(state)
        t = state["tpe"]
        self._obs_unit = np.asarray(t["obs_unit"], dtype=np.float32)
        self._obs_score = np.asarray(t["obs_score"], dtype=np.float32)
        self._valid = np.asarray(t["valid"], dtype=bool)
        self._n_obs = t["n_obs"]
        self._suggested = t["suggested"]
        self._done = t["done"]
        self._requeue_running()
