"""Durable checkpoint/resume of search state (SURVEY.md §2 row 13, §5).

The reference's failure model is MPI's: one rank dies, the gang dies,
the sweep restarts from zero. The TPU-native recovery path is
checkpoint-restart: the host-side search state (tiny JSON — trial
ledger, algorithm bookkeeping, RNG counters) and the device-resident
population state (params + momentum, the expensive thing to lose) are
written together through orbax, and a restarted process resumes
mid-sweep. In-flight trials at save time are re-dispatched on load by
each algorithm's ``_requeue_running`` recovery (see algorithms/base.py).

Layout: one orbax ``CheckpointManager`` step per completed driver batch,
``max_to_keep`` most recent retained. Items:
- ``search``: JSON — ``algorithm.state_dict()`` + backend host ledger.
- ``pool``: pytree — the backend's device state (present only for
  backends that carry one, i.e. the TPU population backend's slot pool).

Saves are asynchronous (orbax's background thread) so the driver loop
is never blocked on serialization of a multi-GB pool; ``close()`` (or
the context manager) drains pending writes.

Integrity (utils/integrity.py): every save writes a ``manifest`` item
with per-item content digests, and once the async write has committed
the step is SEALED with a file-level manifest (at the next save or at
close — wherever the loop would wait for the writer anyway). Restore
re-hashes a sealed step's files before orbax decodes anything, then
verifies the item digests BEFORE any state is applied, quarantines a
failing step (rename to
``<step>.corrupt``) and walks back to the newest older retained step —
``keep`` is therefore the fallback budget (default 3: the latest may be
torn by a SIGKILL mid-async-save, leaving two verified fallbacks). Only
when no verified step remains does restore raise
``NoVerifiedSnapshotError`` (the CLI exits EX_DATAERR=65, which the
launch supervisor treats as non-retryable).
"""

from __future__ import annotations

import os
from typing import Any, Optional

import orbax.checkpoint as ocp

from mpi_opt_tpu.obs import memory, trace
from mpi_opt_tpu.utils import integrity, resources


def _prune_superseded(mgr, directory: str) -> Optional[int]:
    """Delete the OLDEST retained step (the retention-prune half of the
    ENOSPC recovery): a superseded verified step is exactly the bytes
    retention policy was already going to discard — reclaiming it to
    land the CURRENT save trades fallback depth for forward progress.
    The newest step is NEVER touched (it is the resume point a parked
    run recovers through); with fewer than two steps there is nothing
    prunable and the caller parks instead. Returns the pruned step."""
    import shutil

    steps = sorted(mgr.all_steps())
    if len(steps) < 2:
        return None
    victim = int(steps[0])
    shutil.rmtree(os.path.join(directory, str(victim)), ignore_errors=True)
    mgr.reload()  # forget the deleted step
    return victim


def _seal_committed(mgr, directory: str, unsealed: list) -> None:
    """Wait for the async writer, then seal every step in ``unsealed``
    that committed (``integrity.seal_step``); ``unsealed`` is emptied.
    A step whose write failed, or that retention already deleted, has
    no committed directory and is skipped. One process writes the seal:
    process 0, which is also the one that commits the step."""
    import jax

    mgr.wait_until_finished()
    while unsealed:
        step_dir = os.path.join(directory, str(unsealed.pop(0)))
        if jax.process_index() == 0 and os.path.exists(
            os.path.join(step_dir, integrity.COMMIT_MARKER)
        ):
            integrity.seal_step(step_dir)


def _check_seal(directory: str, step: int) -> None:
    """Raise SnapshotCorruptError unless every file of a sealed step
    still matches its seal (an unsealed step passes: see
    ``integrity.check_seal``)."""
    problems = integrity.check_seal(os.path.join(directory, str(step)))
    if problems:
        raise integrity.SnapshotCorruptError("; ".join(problems))


def _wait_classified(mgr, directory: str, unsealed: list) -> None:
    """Drain pending async saves (and seal what they committed) with the
    storage classification: orbax
    saves are asynchronous, so a REAL disk-full often surfaces not at
    the enqueue (_save_storage_guard's territory) but in the background
    writer — re-raised here at close()'s ``wait_until_finished``. An
    unclassified ENOSPC escaping close() would exit as a generic rc 1
    traceback and launch.py would burn its whole retry budget on it —
    the exact failure mode the classifier exists to end. The failed
    write never committed its step, so durable state is the last
    committed step and the free-disk + --resume recovery holds."""
    try:
        _seal_committed(mgr, directory, unsealed)
    except Exception as e:
        if not resources.is_storage_full(e):
            raise
        raise resources.StorageFull(
            "async snapshot write hit a full disk; durable state is the "
            "last committed step — free disk space and relaunch with "
            "--resume",
            path=directory,
        ) from e


def _save_storage_guard(mgr, directory: str, unsealed: list, enqueue) -> None:
    """Seal what the previous save committed (orbax waits for that
    write before it starts a new one anyway), then run ``enqueue()``
    (the orbax save) — both under the storage-exhaustion
    lifecycle (ISSUE 13): a classified ENOSPC/EDQUOT gets ONE
    retention-prune retry — delete the oldest superseded retained step,
    never the newest — then parks by raising typed ``StorageFull`` (the
    CLI maps it to ``EX_IOERR``=74, which launch.py treats as
    non-retryable-with-diagnostics and the service as parked). The
    chaos seam (``resources.disk_fault``) sits INSIDE each attempt so
    ``inject_enospc`` schedules are re-consulted on the retry, exactly
    like the spool injector. Non-storage failures propagate raw."""

    def attempt():
        resources.disk_fault("snapshot_save", directory)
        _seal_committed(mgr, directory, unsealed)
        enqueue()

    try:
        attempt()
        return
    except Exception as e:
        if not resources.is_storage_full(e):
            raise
        first = e
    victim = _prune_superseded(mgr, directory)
    if victim is None:
        # nothing prunable without touching the newest verified step:
        # park now, state intact (the failed save never landed)
        raise resources.StorageFull(
            "snapshot save hit a full disk and no superseded retained "
            "step remains to prune (the newest verified step is never "
            "touched); free disk space and relaunch with --resume",
            path=directory,
        ) from first
    resources.notify("snapshot_pruned", step=victim, directory=directory)
    try:
        attempt()
    except Exception as e:
        if not resources.is_storage_full(e):
            raise
        raise resources.StorageFull(
            "snapshot save still hit a full disk after pruning one "
            f"superseded step ({victim}); free disk space and relaunch "
            "with --resume",
            path=directory,
        ) from e


def _step_item_names(mgr, directory: str, step: int) -> set:
    """Item names present in a snapshot step, via the manager's
    metadata probe with a directory-listing fallback (see the warning
    rationale in SearchCheckpointer._item_names)."""
    try:
        meta = mgr.item_metadata(step)
        names = set(meta.keys()) if hasattr(meta, "keys") else set()
        if names:
            return names
    except Exception as e:
        import warnings

        warnings.warn(
            f"checkpoint metadata probe failed at step {step} "
            f"({type(e).__name__}: {e}); falling back to directory "
            "listing to detect snapshot items",
            RuntimeWarning,
            stacklevel=2,
        )
    step_dir = os.path.join(directory, str(step))
    return set(os.listdir(step_dir)) if os.path.isdir(step_dir) else set()


def _restore_walk(mgr, directory: str, attempt):
    """Last-good-fallback restore: try retained steps newest-first via
    ``attempt(step)``; a step that fails decode or digest verification
    is QUARANTINED (renamed, never deleted) and the walk continues on
    the next older step. Returns ``(step, attempt_result)``, or None
    when the directory holds no steps at all (caller starts fresh).
    Raises NoVerifiedSnapshotError when steps existed but every one was
    quarantined — restarting cannot help, the caller must abort loudly.

    OSError is NOT corruption evidence: an I/O blip (EIO, NFS timeout,
    permission) says the *filesystem* is sick, not the bytes — it gets
    one retry, and a persistent OSError re-raises RAW so an intact
    checkpoint tree is never renamed away for a transient outage.
    (A SIGKILL-torn step surfaces as a decode/digest failure, not an
    OSError: its files are short or mangled, not unreadable; an
    UNcommitted torn step is invisible to orbax here and handled by
    ``fsck``.)"""
    quarantined: list = []
    had_any = False
    while True:
        # a fresh manager reflects disk; after each quarantine rename
        # the reload() below refreshes the step list
        steps = sorted(mgr.all_steps(), reverse=True)
        if not steps:
            break
        had_any = True
        step = steps[0]
        retried_io = False
        while True:
            try:
                return step, attempt(step)
            except OSError as e:
                if retried_io:
                    raise  # persistent I/O failure: not corruption
                retried_io = True
                integrity.notify(
                    "snapshot_io_retry",
                    step=step,
                    directory=directory,
                    error=f"{type(e).__name__}: {e}"[:500],
                )
                continue
            except Exception as e:
                q = integrity.quarantine_step(directory, step)
                quarantined.append(q or os.path.join(directory, str(step)))
                integrity.notify(
                    "snapshot_corrupt",
                    step=step,
                    directory=directory,
                    error=f"{type(e).__name__}: {e}"[:500],
                    quarantined_to=None if q is None else os.path.basename(q),
                )
                mgr.reload()  # forget the renamed step
                break
    if had_any or quarantined:
        raise integrity.NoVerifiedSnapshotError(directory, quarantined)
    return None


class SearchCheckpointer:
    """Periodic durable snapshots of (algorithm, backend) state."""

    def __init__(self, directory: str, every: int = 1, keep: int = 3):
        if every < 1:
            raise ValueError(f"checkpoint every must be >= 1, got {every}")
        self.directory = os.path.abspath(directory)
        self.every = every
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(max_to_keep=keep, create=True),
        )
        self._unsealed: list = []  # steps saved here, not yet sealed

    # -- save --------------------------------------------------------------

    def maybe_save(self, step: int, algorithm, backend) -> bool:
        """Save if ``step`` is on the cadence; returns whether it saved."""
        if step % self.every:
            return False
        self.save(step, algorithm, backend)
        return True

    def save(self, step: int, algorithm, backend) -> None:
        # the save span bounds the HOST-side cost (state collection +
        # digest + async enqueue); orbax's background write time shows
        # up in close()'s save_wait span instead
        with trace.span("save", step=step) as sp:
            memory.note(sp)  # pre-fetch watermark: device pool still resident
            search = {
                "algorithm": algorithm.state_dict(),
                "backend": backend.host_state_dict(),
            }
            items = {"search": ocp.args.JsonSave(search)}
            tree_items = {}
            pool = backend.device_state()
            if pool is not None:
                items["pool"] = ocp.args.StandardSave(pool)
                tree_items["pool"] = pool
            # verified save: per-item content digests ride inside the step
            # (digesting a device pool costs one sync host fetch — the price
            # of restore being able to prove the bytes survived)
            manifest = integrity.build_manifest({"search": search}, tree_items)
            items[integrity.MANIFEST_ITEM] = ocp.args.JsonSave(manifest)
            _save_storage_guard(
                self._mgr,
                self.directory,
                self._unsealed,
                lambda: self._mgr.save(step, args=ocp.args.Composite(**items)),
            )
            self._unsealed.append(step)

    # -- restore -----------------------------------------------------------

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def restore_into(self, algorithm, backend) -> Optional[int]:
        """Load the newest VERIFIED snapshot into a fresh algorithm/
        backend pair, quarantining corrupt steps and walking back (see
        ``_restore_walk``). Restore and digest-verify complete before
        the first mutation, so a corrupt ``pool`` item can never leave
        a half-loaded algorithm behind.

        Returns the restored step, or None if the directory holds no
        checkpoint (caller starts fresh). Raises NoVerifiedSnapshotError
        when steps exist but none verifies.
        """

        def attempt(step):
            _check_seal(self.directory, step)
            items: dict[str, Any] = {"search": ocp.args.JsonRestore()}
            names = self._item_names(step)
            has_pool = "pool" in names
            if has_pool:
                items["pool"] = ocp.args.StandardRestore()
            has_manifest = integrity.MANIFEST_ITEM in names
            if has_manifest:
                items[integrity.MANIFEST_ITEM] = ocp.args.JsonRestore()
            with trace.span("restore", step=step):
                r = self._mgr.restore(step, args=ocp.args.Composite(**items))
            if has_manifest:
                problems = integrity.verify_restored(
                    getattr(r, integrity.MANIFEST_ITEM),
                    {"search": r.search},
                    {"pool": r.pool} if has_pool else {},
                )
                if problems:
                    raise integrity.SnapshotCorruptError("; ".join(problems))
            else:
                # pre-manifest step: resumable (same rule as config keys
                # added after a snapshot format existed) but announced
                integrity.notify(
                    "snapshot_unverified", step=step, directory=self.directory
                )
            return r, has_pool

        res = _restore_walk(self._mgr, self.directory, attempt)
        if res is None:
            return None
        step, (r, has_pool) = res
        # apply phase: everything above is decoded temporaries — a
        # failure from here is schema/config drift in live code, not
        # snapshot corruption, and must surface raw (quarantining a
        # good snapshot for a program bug would destroy the evidence)
        algorithm.load_state_dict(r.search["algorithm"])
        backend.load_host_state_dict(r.search["backend"])
        if has_pool:
            backend.load_device_state(r.pool)
        return step

    def _item_names(self, step: int) -> set:
        # the metadata probe is best-effort, but a silent blanket
        # swallow would hide an orbax API break indefinitely:
        # _step_item_names surfaces what failed (type + step) before
        # falling back to the weaker directory-listing heuristic
        return _step_item_names(self._mgr, self.directory, step)

    # -- lifecycle ---------------------------------------------------------

    def close(self) -> None:
        # save_wait: where the async saves' background write time
        # surfaces on the host (the drain before the manager closes) —
        # and where a background writer's ENOSPC re-raises, classified
        with trace.span("save_wait"):
            _wait_classified(self._mgr, self.directory, self._unsealed)
        self._mgr.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class SweepCheckpointer:
    """Durable snapshots of a fused on-device sweep, at the sweep's own
    granularity (PBT: launches; SHA: rungs; Hyperband: brackets via
    per-bracket directories).

    Items per orbax step:
    - ``sweep`` (StandardSave): host copies of the carried arrays
      (population state, unit hparams, RNG key data, scores...).
      Callers host-fetch BEFORE saving: the next launch may donate the
      device buffers out from under orbax's async writer.
    - ``meta`` (JsonSave): ``{"config": ..., **extra}`` — the sweep
      config is validated on restore, so a checkpoint from a different
      sweep shape raises instead of silently loading.
    """

    def __init__(self, directory: str, config: dict, keep: int = 3):
        self.config = config
        self.directory = os.path.abspath(directory)
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(max_to_keep=keep, create=True),
        )
        self._unsealed: list = []  # steps saved here, not yet sealed

    def save(self, step: int, sweep: dict, meta_extra: dict) -> None:
        with trace.span("save", step=step) as sp:
            memory.note(sp)  # snapshot-time watermark: sweep state resident
            meta = {"config": self.config, **meta_extra}
            # verified save: both items' content digests ride with the step
            # (sweep arrays are host-fetched by every caller, so digesting
            # costs hashing only, no extra device fetch)
            manifest = integrity.build_manifest({"meta": meta}, {"sweep": sweep})
            _save_storage_guard(
                self._mgr,
                self.directory,
                self._unsealed,
                lambda: self._mgr.save(
                    step,
                    args=ocp.args.Composite(
                        sweep=ocp.args.StandardSave(sweep),
                        meta=ocp.args.JsonSave(meta),
                        **{integrity.MANIFEST_ITEM: ocp.args.JsonSave(manifest)},
                    ),
                ),
            )
            self._unsealed.append(step)

    def restore(self):
        """(sweep_arrays, meta) from the newest VERIFIED snapshot, or
        None when the directory holds no steps. A step failing digest
        verification or decode is quarantined (``<step>.corrupt``) and
        restore walks back to the next older retained step; when no
        verified step remains, NoVerifiedSnapshotError. Raises
        ValueError on a config mismatch."""

        def attempt(step):
            _check_seal(self.directory, step)
            items = {
                "sweep": ocp.args.StandardRestore(),
                "meta": ocp.args.JsonRestore(),
            }
            names = _step_item_names(self._mgr, self.directory, step)
            has_manifest = integrity.MANIFEST_ITEM in names
            if has_manifest:
                items[integrity.MANIFEST_ITEM] = ocp.args.JsonRestore()
            with trace.span("restore", step=step):
                r = self._mgr.restore(step, args=ocp.args.Composite(**items))
            if has_manifest:
                problems = integrity.verify_restored(
                    getattr(r, integrity.MANIFEST_ITEM),
                    {"meta": r.meta},
                    {"sweep": r.sweep},
                )
                if problems:
                    raise integrity.SnapshotCorruptError("; ".join(problems))
            else:
                integrity.notify(
                    "snapshot_unverified", step=step, directory=self.directory
                )
            return r

        try:
            res = _restore_walk(self._mgr, self.directory, attempt)
        except integrity.NoVerifiedSnapshotError:
            # same contract as the config-mismatch raise below: callers
            # only reach their own close() via try/finally blocks
            # entered AFTER a successful restore
            self.close()
            raise
        if res is None:
            return None
        _step, r = res
        saved = dict(r.meta["config"])
        # config keys added AFTER a snapshot format existed compare
        # against their historical default, so genuine pre-upgrade
        # snapshots stay resumable instead of being refused for a key
        # their writer couldn't have known about. momentum_dtype and
        # init_unit_digest were added round 3; every earlier snapshot
        # was written under f32 momentum and a self-sampled cohort.
        saved.setdefault("momentum_dtype", "float32")
        if "init_unit_digest" in self.config:
            saved.setdefault("init_unit_digest", None)
        if "step_chunk" in self.config:
            saved.setdefault("step_chunk", 0)  # pre-upgrade sweeps were unchunked
        if "wave_size" in self.config:
            saved.setdefault("wave_size", 0)  # pre-upgrade sweeps were resident
        if "n_warm" in self.config:
            saved.setdefault("n_warm", 0)  # pre-upgrade TPE sweeps had no priors
        if saved != self.config:
            # name ONLY the mismatched keys: dumping two full config
            # dicts buries the one line that matters (wave_size vs
            # resident cross-resume is the common case and should read
            # as exactly that)
            diffs = [
                f"{k}: snapshot={saved.get(k, '<absent>')!r} vs "
                f"run={self.config.get(k, '<absent>')!r}"
                for k in sorted(set(saved) | set(self.config), key=str)
                if saved.get(k, "<absent>") != self.config.get(k, "<absent>")
            ]
            # close before raising: callers only reach their own close()
            # via try/finally blocks entered AFTER a successful restore
            self.close()
            raise ValueError(
                "checkpoint directory holds a different sweep "
                f"(mismatched {'; '.join(diffs)})"
            )
        return r.sweep, r.meta

    def close(self) -> None:
        with trace.span("save_wait"):
            _wait_classified(self._mgr, self.directory, self._unsealed)
        self._mgr.close()

    # -- population-sweep payload (shared by fused PBT / SHA) -------------

    def save_population_sweep(self, step, state, unit, key, scores, meta_extra):
        """Snapshot the standard fused-sweep payload. Host-fetches the
        population state BEFORE the async save (the caller's next launch
        donates those device buffers). Fetches via ``fetch_global`` so a
        sweep sharded over a process-spanning mesh can snapshot: every
        process fetches the same global value (a collective for sharded
        leaves) and orbax's own multihost coordination handles the write.
        """
        import jax
        import numpy as np

        from mpi_opt_tpu.parallel.mesh import fetch_global

        tree = {"params": state.params, "momentum": state.momentum, "step": state.step}
        if all(
            not isinstance(l, jax.Array) or l.is_fully_addressable
            for l in jax.tree.leaves(tree)
        ):
            # single-process: one batched fetch (a ResNet pool is dozens
            # of leaves; per-leaf synchronous fetches would lengthen the
            # pause before the async save)
            host = jax.device_get(tree)
        else:
            host = jax.tree.map(fetch_global, tree)
        self.save(
            step,
            sweep={
                "state": host,
                "unit": fetch_global(unit),
                "key_data": np.asarray(jax.random.key_data(key)),
                # fetch_global, not np.asarray: both current callers pass
                # host arrays (no-op), but the docstring invites device
                # arrays and a process-spanning scores shard would crash
                # at its first snapshot otherwise
                "scores": fetch_global(scores),
            },
            meta_extra=meta_extra,
        )

    # -- wave-scheduled sweep payload (host-staged populations) -----------

    def restore_wave_sweep(self):
        """(sweep_payload, meta) for a wave-scheduled fused sweep, or
        None; ValueError on config mismatch (restore() closes on that
        path). The payload's arrays are host numpy by construction — a
        beyond-residency population LIVES on host, so wave snapshots
        save the staging pools directly, no device fetch involved.
        Two shapes, discriminated by ``meta['waves_done']``:

        - generation boundary (``waves_done == 0``): ``front`` (the
          post-training pool), ``perm`` (the exploit source map the next
          generation's stage-in applies), ``unit``, ``key_data`` (the
          next carried key), ``scores`` (post-exploit).
        - between waves (``waves_done == k``): both pools (``front``
          read / ``back`` written-through-wave-k), ``perm``, ``unit``,
          ``key_data`` (the PRE-generation carried key — train/exploit
          keys re-derive from it on resume), ``scores`` (pre-exploit,
          NaN past the completed prefix).

        Key wrapping and pool writability (orbax may restore read-only
        arrays) are the caller's job — see train/fused_pbt.py and the
        shared wave engine's ``writable`` helper (train/engine.py).
        """
        return self.restore()

    def restore_population_sweep(self):
        """(PopState, unit, key, scores, meta) from the latest snapshot,
        or None. Raises ValueError on config mismatch (restore() closes
        the manager on that path)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from mpi_opt_tpu.train.population import PopState

        r = self.restore()
        if r is None:
            return None
        sweep, meta = r
        state = PopState(
            params=sweep["state"]["params"],
            momentum=sweep["state"]["momentum"],
            step=sweep["state"]["step"],
        )
        key = jax.random.wrap_key_data(jnp.asarray(sweep["key_data"]))
        return state, sweep["unit"], key, np.asarray(sweep["scores"]), meta
