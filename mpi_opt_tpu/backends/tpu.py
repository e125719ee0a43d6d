"""TPU population backend: trials are rows of one vmapped population.

This replaces the reference's Coordinator/MPIWorker runtime (SURVEY.md
§2 rows 7-9; reference unreadable — contract from BASELINE.json
north_star: "the per-rank trial-evaluation loop becomes a single vmapped
population kernel running on-device ... registered under the existing
``backend=`` plugin hook ... opt-in via ``--backend=tpu``").

Architecture:

- A device-resident **slot pool**: ``PopState`` with ``pool_size``
  member slots (params + momentum), initialized once. Trials map to
  slots; the mapping lives on the host (tiny), the states never leave
  the device.
- ``evaluate(trials)`` runs the WHOLE batch — even one mixing ASHA
  rungs — as one program chain, padded to a power of two (bounded
  recompile surface): gather source states → overwrite fresh members
  with new inits → ``train_segment_masked`` (the jitted
  scan-of-vmapped-steps, each member frozen past its own remaining
  budget) → eval → scatter back into the pool. One blocking score
  fetch per batch, instead of the naive plan's one per rung group.
- PBT inheritance (``__inherit_from__``) and ASHA warm resume are both
  just gathers from the pool — the reference's MPI weight transfers and
  re-dispatches collapse into device-side index ops.
- Eviction: slots are LRU-recycled. Losing a slot is safe — budgets are
  cumulative, so an evicted trial retrains from scratch to its budget.

The per-search costs that remain on the host: one dataset upload, one
tiny score download per batch, and the trial ledger.
"""

from __future__ import annotations

import functools
import time
from collections import OrderedDict
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from mpi_opt_tpu.backends.base import Backend, register_backend
from mpi_opt_tpu.trial import Trial, TrialResult, failed_result
from mpi_opt_tpu.workloads.base import Workload


@register_backend
class TPUPopulationBackend(Backend):
    name = "tpu"

    def __init__(
        self,
        workload: Workload,
        population: int = 32,
        seed: int = 0,
        member_chunk: int = 0,
        slot_slack: int = 2,
        eval_chunk: int = 1024,
        mesh=None,
    ):
        if not hasattr(workload, "make_trainer"):
            raise ValueError(
                f"workload {workload.name!r} has no population protocol "
                "(make_trainer/make_hparams/data); use --backend cpu"
            )
        super().__init__(workload)
        self.population = population
        self.seed = seed
        self.member_chunk = member_chunk
        self.eval_chunk = eval_chunk
        # optional ('pop','data') mesh: the slot pool shards its member
        # axis over 'pop' and batches constrain over 'data', so the
        # driver path reaches the same mesh layer the fused sweeps use
        self.mesh = mesh
        # slack >= 2 guarantees every batch can pin its sources (<= pop)
        # AND allocate its outputs (<= pop) without evicting a pinned
        # slot; +1 scratch slot absorbs padding writes
        self.pool_size = population * max(2, slot_slack) + 1
        if mesh is not None:
            # the pool only shards if its slot axis divides the 'pop'
            # axis (shard_popstate falls back to replication otherwise,
            # which would silently defeat the mesh); round up — extra
            # slots just enlarge the free list
            n_pop = mesh.shape["pop"]
            self.pool_size = -(-self.pool_size // n_pop) * n_pop
        self._scratch = self.pool_size - 1
        self._setup_done = False
        self._step_counter = 0
        # host-side ledger
        self._slot_of: "OrderedDict[int, int]" = OrderedDict()  # trial_id -> slot (LRU order)
        self._trained: dict[int, int] = {}  # trial_id -> steps completed

    @property
    def capacity(self) -> int:
        return self.population

    # -- lazy device setup ------------------------------------------------

    def _setup(self):
        if self._setup_done:
            return
        # single placement point shared with the fused sweeps: trainer
        # built for (member_chunk, mesh), datasets device-resident and
        # mesh-replicated (train/common.py)
        from mpi_opt_tpu.train.common import workload_arrays

        (
            self._trainer,
            self._space,
            self._train_x,
            self._train_y,
            self._val_x,
            self._val_y,
        ) = workload_arrays(self.workload, self.member_chunk, self.mesh)
        key = jax.random.fold_in(jax.random.key(self.seed), 7001)
        self._pool = self._trainer.init_population(
            key, self._train_x[:2], self.pool_size
        )
        self._pool = self._place_pool(self._pool)
        self._free = [s for s in range(self.pool_size) if s != self._scratch]
        self._setup_done = True

    def _place_pool(self, pool):
        """Shard the slot pool's member axis over the mesh 'pop' axis
        (no-op without a mesh, and zero-copy when already placed)."""
        if self.mesh is None:
            return pool
        from mpi_opt_tpu.parallel.mesh import shard_popstate

        return shard_popstate(pool, self.mesh)

    # -- slot management --------------------------------------------------

    def _alloc_slot(self, trial_id: int, pinned: set[int]) -> int:
        if self._free:
            slot = self._free.pop()
        else:
            # evict the least-recently-used *unpinned* trial; retraining
            # from scratch is always correct because budgets are
            # cumulative. Slots referenced by the in-flight batch are
            # pinned — evicting one mid-plan would silently turn a warm
            # resume into an under-trained fresh init.
            for old_id, cand in self._slot_of.items():  # LRU order
                if cand not in pinned:
                    slot = cand
                    del self._slot_of[old_id]
                    self._trained.pop(old_id, None)
                    break
            else:
                raise RuntimeError(
                    "slot pool exhausted by a single batch; raise slot_slack"
                )
        self._slot_of[trial_id] = slot
        return slot

    def _touch(self, trial_id: int):
        self._slot_of.move_to_end(trial_id)

    # -- evaluation --------------------------------------------------------

    def evaluate(self, trials: Sequence[Trial]) -> list[TrialResult]:
        self._setup()
        # -- atomic plan over the whole batch -----------------------------
        # Phase A: resolve every trial's state source against the CURRENT
        # ledger and pin those slots, so phase-B allocations can never
        # evict a source this batch still needs.
        pinned: set[int] = set()
        resolved = []
        for t in trials:
            src = t.params.get("__inherit_from__")
            if t.trial_id in self._slot_of:  # warm resume
                src_slot = self._slot_of[t.trial_id]
                done = self._trained.get(t.trial_id, 0)
                fresh = False
                self._touch(t.trial_id)
            elif src is not None and src in self._slot_of:  # PBT exploit copy
                src_slot = self._slot_of[src]
                done = self._trained.get(src, 0)
                fresh = False
            else:  # fresh member (or evicted lineage: full retrain)
                src_slot = self._scratch
                done = 0
                fresh = True
            pinned.add(src_slot)
            resolved.append((t, src_slot, fresh, done))
        # Phase B: allocate output slots (own slot for resumes). The
        # whole batch — even one mixing ASHA rungs — runs as ONE device
        # program with per-member remaining-step masks
        # (train_segment_masked): one program per rung group meant one
        # launch and one blocking score fetch per group. Frozen members
        # burn discarded-update FLOPs instead (which side of that trade
        # wins on a locally attached chip: ROADMAP D2).
        entries = []
        for t, src_slot, fresh, done in resolved:
            if t.trial_id in self._slot_of:
                out_slot = self._slot_of[t.trial_id]
            else:
                out_slot = self._alloc_slot(t.trial_id, pinned)
            pinned.add(out_slot)
            rem = max(0, t.budget - done)
            entries.append((t, src_slot, fresh, out_slot, rem))
        results = self._run_batch(entries)
        return [results[t.trial_id] for t in trials]

    def _run_batch(self, entries: list) -> dict[int, TrialResult]:
        """entries: (trial, src_slot, fresh, out_slot, rem) plan rows —
        one device program chain and ONE blocking score fetch for the
        whole batch."""
        if not entries:
            # empty batches must stay free AND not tick _step_counter:
            # reset()'s bit-identical-replay guarantee depends on the
            # RNG stream position being a pure function of the evaluated
            # batches
            return {}
        t0 = time.perf_counter()
        n = len(entries)
        n_pad = 1 << (n - 1).bit_length()  # pow2-pad: bounded recompiles

        gather_idx = np.full(n_pad, self._scratch, dtype=np.int32)
        out_slots = np.full(n_pad, self._scratch, dtype=np.int32)
        fresh = np.zeros(n_pad, dtype=bool)
        unit = np.zeros((n_pad, self._space.dim), dtype=np.float32)
        rem = np.zeros(n_pad, dtype=np.int32)  # padding rows never train

        for i, (t, src_slot, is_fresh, out_slot, t_rem) in enumerate(entries):
            unit[i] = t.unit
            gather_idx[i] = src_slot
            fresh[i] = is_fresh
            out_slots[i] = out_slot
            rem[i] = t_rem

        key = jax.random.fold_in(
            jax.random.key(self.seed), 9000 + self._step_counter
        )
        self._step_counter += 1
        k_init, k_train = jax.random.split(key)

        # device program: gather -> fresh-overwrite -> masked-train ->
        # eval -> scatter (async dispatches; the score fetch below is
        # the only host sync)
        sub = self._trainer.gather_members(self._pool, jnp.asarray(gather_idx))
        if self.mesh is not None and n_pad % self.mesh.shape["pop"] == 0:
            # the gather's output layout follows XLA's guess; re-place so
            # the group trains sharded over 'pop' (skipped for groups
            # smaller than the axis — they run replicated, which is
            # correct, just not parallel)
            from mpi_opt_tpu.parallel.mesh import shard_popstate

            sub = shard_popstate(sub, self.mesh)
        if fresh[:n].any():  # steady-state resume/inherit batches skip init
            fresh_states = self._trainer.init_population(k_init, self._train_x[:2], n_pad)
            sub = self._trainer.select_members(jnp.asarray(fresh), fresh_states, sub)
        hp = self.workload.make_hparams(self._space.from_unit(jnp.asarray(unit)))
        max_steps = int(rem.max())
        if max_steps > 0:
            sub, _ = self._trainer.train_segment_masked(
                sub, hp, self._train_x, self._train_y, k_train, max_steps,
                jnp.asarray(rem),
            )
        scores = self._trainer.eval_population(
            sub, self._val_x, self._val_y, eval_chunk=self.eval_chunk
        )
        self._pool = self._place_pool(_scatter(self._pool, sub, jnp.asarray(out_slots)))

        # fetch_global: on a process-spanning mesh (config-5 multi-host)
        # eval_population's output is not fully addressable and a plain
        # np.asarray raises
        from mpi_opt_tpu.parallel.mesh import fetch_global

        scores = fetch_global(scores)
        wall = time.perf_counter() - t0
        out: dict[int, TrialResult] = {}
        for i, (t, _, _, _, _) in enumerate(entries):
            s = float(scores[i])
            if np.isfinite(s):
                self._trained[t.trial_id] = t.budget
                out[t.trial_id] = TrialResult(
                    trial_id=t.trial_id,
                    score=s,
                    step=t.budget,
                    wall_time=wall / n,
                )
            else:
                # same per-trial failure contract as the CPU backend: a
                # diverged member (NaN/inf eval) reports as failed, not
                # as an "ok" result whose poison score every consumer
                # must remember to isfinite-gate. The diverged state is
                # EVICTED from the ledger (slot back on the free list),
                # mirroring the CPU stateful path's store-nothing rule:
                # a driver retry then resolves the trial as fresh and
                # retrains from scratch instead of re-evaluating the
                # wreck for zero steps, and a PBT successor can never
                # inherit it
                slot = self._slot_of.pop(t.trial_id, None)
                if slot is not None:
                    self._free.append(slot)
                self._trained.pop(t.trial_id, None)
                out[t.trial_id] = failed_result(
                    t.trial_id,
                    t.budget,
                    f"non-finite score {s!r} (member diverged)",
                    score=s,
                    wall_time=wall / n,
                )
        return out

    def close(self):
        pass

    def reset(self):
        """Per-search state back to construction time, pool buffers kept.

        Every post-reset trial resolves as fresh (the ledger is empty),
        so stale pool contents are unreachable except through the
        scratch slot, which is never read as a real member; resetting
        ``_step_counter`` restores the RNG stream, so a reset backend
        produces BIT-IDENTICAL results to a newly constructed one
        (tested) while keeping the device pool and compiled programs.
        """
        if not self._setup_done:
            return
        self._slot_of.clear()
        self._trained.clear()
        self._free = [s for s in range(self.pool_size) if s != self._scratch]
        self._step_counter = 0

    # -- checkpoint/resume ------------------------------------------------
    #
    # The slot pool is the expensive thing to lose: every live trial's
    # params + momentum. host_state_dict carries the ledger that gives
    # the pool meaning (trial -> slot, steps trained, RNG counter);
    # device_state is the pool pytree itself.

    def host_state_dict(self) -> dict:
        if not self._setup_done:
            return {"setup": False}
        return {
            "setup": True,
            "slot_of": list(self._slot_of.items()),  # preserves LRU order
            "trained": list(self._trained.items()),
            "free": list(self._free),
            "step_counter": self._step_counter,
        }

    def load_host_state_dict(self, state: dict) -> None:
        if not state.get("setup", False):
            return
        self._setup()
        self._slot_of = OrderedDict((int(k), int(v)) for k, v in state["slot_of"])
        self._trained = {int(k): int(v) for k, v in state["trained"]}
        self._free = [int(s) for s in state["free"]]
        self._step_counter = int(state["step_counter"])

    def device_state(self):
        return self._pool if self._setup_done else None

    def load_device_state(self, pool) -> None:
        """Install a restored pool (numpy pytree from orbax) on-device."""
        from mpi_opt_tpu.train import PopState

        self._setup()
        if not isinstance(pool, PopState):
            # orbax round-trips the flax.struct dataclass as a plain dict
            pool = PopState(
                params=pool["params"], momentum=pool["momentum"], step=pool["step"]
            )
        got = jax.tree.structure(pool)
        want = jax.tree.structure(self._pool)
        if got != want:
            raise ValueError(
                f"restored pool structure {got} does not match this "
                f"backend's pool {want} (different workload/population?)"
            )
        # treedefs ignore leaf shapes: a pool checkpointed under a
        # different mesh/pool_size (pool_size rounds to the 'pop' axis)
        # has the same structure but different slot counts — installing
        # it would let the scratch slot collide with a live slot and
        # silently corrupt members on every padded scatter
        got_shapes = [tuple(x.shape) for x in jax.tree.leaves(pool)]
        want_shapes = [tuple(x.shape) for x in jax.tree.leaves(self._pool)]
        if got_shapes != want_shapes:
            raise ValueError(
                "restored pool leaf shapes do not match this backend's "
                f"pool (saved slot count {got_shapes[0][0]}, this backend "
                f"{want_shapes[0][0]} — resumed under a different mesh or "
                "population?)"
            )
        got_dtypes = [x.dtype for x in jax.tree.leaves(pool)]
        want_dtypes = [x.dtype for x in jax.tree.leaves(self._pool)]
        if got_dtypes != want_dtypes:
            raise ValueError(
                "restored pool leaf dtypes do not match this backend's pool "
                "(saved under a different momentum storage dtype? see "
                "MPI_OPT_TPU_MOMENTUM_DTYPE) — refusing rather than feeding "
                "mismatched state into the compiled programs"
            )
        # free the freshly-initialized pool BEFORE uploading the restored
        # one: a ResNet-scale pool cannot afford 2x residency
        self._pool = None
        self._pool = self._place_pool(jax.tree.map(jnp.asarray, pool))


@functools.partial(jax.jit, donate_argnames=("pool",))
def _scatter(pool, sub, slots):
    """Write member states back into their pool slots.

    Padding entries all target the scratch slot; duplicate-index writes
    there are benign (scratch content is never read as a real member).
    The old pool is donated: a scatter-update aliases in place, so the
    slot pool costs 1x its size in HBM instead of 2x at update time.
    """
    return jax.tree.map(lambda p, s: p.at[slots].set(s), pool, sub)
