"""Host-staged member waves: population > device residency.

The single-chip population envelope is RESIDENCY-bound, not speed-bound
(PERF_NOTES "single-chip population envelope": pop=1024 SmallCNN is
4.5 GB of params+momentum and dies RESOURCE_EXHAUSTED at warmup while
member throughput stays flat to pop=512). The reference's MPI worker
pool never hits this wall — members live in host processes and visit
the accelerator one trial at a time. This module is the fused-path
answer: keep a resident WAVE of W members on device, stream the cold
population through host memory, and hide the host<->device transfer
cost behind wave compute.

Three pieces:

- ``StagingEngine``: a single background worker thread that fetches
  trained wave state device->host (``jax.device_get`` blocks until the
  wave's compute completes, so the fetch doubles as that wave's
  completion barrier) and writes it into the host pool. The main thread
  meanwhile dispatches the NEXT wave's stage-in + compute, so the
  stage-out of wave k overlaps the compute of wave k+1 and a wave's
  transfer is paid only where it outlasts a wave's compute (how much
  of it the chip hides is unmeasured on this installation — PERF.md
  open questions). ``drain()`` is the generation boundary's
  completion barrier; its block time is the UN-hidden remainder of the
  transfer cost, which is why the engine accounts both.

- Host pool helpers: the cold population lives as one numpy pytree with
  a leading [P] member axis (``population_pool``, built from abstract
  member shapes); waves slice rows out (``stage_in``) and the engine
  writes trained rows back (``write_rows``). Two pools ping-pong per
  boundary (read the previous generation's/rung's states while writing
  this one's), which is what lets the NEXT boundary's stage-in apply
  the algorithm's survivor/winner index map lazily — PBT's exploit
  gather and SHA's rung-cut gather both become an indexed read, not an
  extra full-population copy. The per-algorithm wave loops live in
  train/engine.py (the shared fused engine); this module stays the
  transport + pool layer.

- ``estimate_wave_size``: the ``--wave-size auto`` residency estimate —
  per-member params+momentum bytes from ``jax.eval_shape`` (no compute,
  no allocation) against the device's reported memory budget, with
  double-buffer + activation headroom.

Memory contract: device holds at most TWO waves (the one computing and
the one being fetched); host holds two full population pools plus one
wave-sized staging slice.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from typing import Any, Callable, Optional

import jax
import numpy as np


def tree_bytes(tree: Any) -> int:
    """Total leaf bytes of an array pytree (host or device)."""
    return sum(
        int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
        for l in jax.tree.leaves(tree)
    )


def population_pool(trainer, sample_x, population: int) -> dict:
    """Zeroed host pool for a full population's carried state, from
    ABSTRACT member shapes (``jax.eval_shape`` over the trainer's init:
    no device allocation — the whole point is that the full population
    never exists on device). Layout matches ``PopState`` fields."""
    params_sd = jax.eval_shape(trainer.init_fn, jax.random.key(0), sample_x)
    mk = lambda sd, dt: np.zeros((population,) + tuple(sd.shape), np.dtype(dt))
    dt = trainer.momentum_dtype
    return {
        "params": jax.tree.map(lambda sd: mk(sd, sd.dtype), params_sd),
        "momentum": jax.tree.map(lambda sd: mk(sd, dt or sd.dtype), params_sd),
        "step": np.zeros((population,), np.int32),
    }


def stage_in(pool: Any, rows: np.ndarray, mesh=None) -> Any:
    """Device copy of ``pool``'s ``rows`` (host gather + device_put).

    ``rows`` is an index array, so the previous generation's exploit
    source map composes for free: passing ``perm[lo:hi]`` stages in the
    WINNERS' states — the MPI weight transfer of the reference, as a
    host-side indexed read. With a mesh the wave lands sharded over
    'pop' (replicated, with the standard warning, when the wave size
    does not divide the axis). device_put is async — dispatching the
    wave's compute right after overlaps the upload with whatever the
    device is still finishing.

    Under multi-process SPMD every process holds the FULL host pool
    (identical by construction: the stage-in permutation is derived
    from in-jit RNG decisions every rank computes identically — the
    PERF_NOTES round-6 moral) and this function stages ITS devices'
    shard of the wave: a process-spanning mesh routes through
    ``shard_popstate_global``, whose per-shard callback reads only the
    rows this process's devices own.
    """
    sliced = jax.tree.map(lambda l: l[rows], pool)
    if mesh is None:
        return jax.device_put(sliced)
    from mpi_opt_tpu.parallel.mesh import (
        shard_popstate,
        shard_popstate_global,
        spans_processes,
    )

    if spans_processes(mesh):
        return shard_popstate_global(sliced, mesh)
    return shard_popstate(sliced, mesh)


def _fetch_tree(tree: Any) -> Any:  # sweeplint: barrier(the staging worker's fetch IS the wave's completion barrier — it blocks on the transfer thread, never the main loop)
    """Host copy of a wave's trained state, on the staging worker.

    The common case (host-local mesh or no mesh) is one batched
    ``jax.device_get``. Under a process-spanning mesh the leaves are
    NOT fully addressable and the fetch routes through
    ``fetch_global_batched`` — a collective (``process_allgather``), so
    it relies on the engine's strict-FIFO worker and the SPMD ranks'
    identical stage_out order: every process's staging thread issues
    the same collectives in the same sequence, the same discipline the
    deferred ledger flush already depends on.
    """
    leaves, treedef = jax.tree.flatten(tree)
    if any(isinstance(l, jax.Array) and not l.is_fully_addressable for l in leaves):
        from mpi_opt_tpu.parallel.mesh import fetch_global_batched

        return jax.tree.unflatten(treedef, fetch_global_batched(leaves))
    return jax.device_get(tree)


def write_rows(pool: Any, lo: int, host_tree: Any) -> None:
    """Write a fetched wave (host arrays) into pool rows [lo, lo+W)."""

    def _assign(dst, src):
        dst[lo : lo + src.shape[0]] = src

    jax.tree.map(_assign, pool, host_tree)


class StagingEngine:
    """One background transfer thread + overlap accounting.

    ``stage_out(tree, on_host)`` enqueues: the worker fetches ``tree``
    to host (blocking THERE, not on the main thread) and calls
    ``on_host(host_tree)`` — jobs run strictly FIFO so pool writes are
    ordered. ``drain()`` blocks until every enqueued job has completed
    and re-raises the first worker error.

    Accounting (surfaced as ``staged_bytes`` / ``stage_overlap_s`` in
    sweep results and the metrics summary):
    - ``staged_bytes``: bytes moved, both directions (``note_bytes``
      adds the main thread's stage-in puts).
    - ``transfer_s``: worker busy seconds (fetch + pool write).
    - ``wait_s``: main-thread seconds blocked in ``drain()`` — the
      transfer cost that compute did NOT hide.
    - ``overlap_s`` = max(0, transfer_s - wait_s): the hidden part. A
      healthy wave schedule has overlap_s ~ transfer_s and wait_s ~ the
      final wave's fetch only.

    The cumulative ``overlap_s``/``wait_s`` values also ride on every
    ``stage_out``/``stage_wait`` span as attrs (ISSUE 11), so a traced
    run carries its overlap evidence in the stream itself — including
    a wave run killed mid-generation, whose summary counters never
    reach a result dict.
    """

    def __init__(self):
        self._q: "queue.Queue" = queue.Queue()
        self._errors: list[BaseException] = []
        self._lock = threading.Lock()
        self._idle = threading.Condition(self._lock)
        self._pending = 0
        self.staged_bytes = 0
        self.transfers = 0
        self.transfer_s = 0.0
        self.wait_s = 0.0
        self._thread = threading.Thread(
            target=self._loop, name="mpi-opt-staging", daemon=True
        )
        self._thread.start()
        self._closed = False

    # -- worker ----------------------------------------------------------

    def _loop(self):  # sweeplint: barrier(the transfer thread IS the barrier: its whole job is host<->device copies)
        from mpi_opt_tpu.health import heartbeat
        from mpi_opt_tpu.obs import memory, trace

        while True:
            job = self._q.get()
            if job is None:
                return
            tree, on_host = job
            t0 = time.perf_counter()
            try:
                # the stage_out span runs on THIS thread (obs/trace.py is
                # thread-safe): because device_get doubles as the wave's
                # completion barrier, its duration carries compute-wait +
                # transfer — overlap analysis reads it against the main
                # thread's train/stage_wait spans by timestamp
                with trace.span("stage_out") as sp:
                    # device_get blocks until the arrays' producing programs
                    # finish — this IS the wave's completion barrier, paid
                    # on this thread while the main thread dispatches ahead
                    host = _fetch_tree(tree)
                    on_host(host)
                    n_bytes = tree_bytes(host)
                    sp["bytes"] = n_bytes
                    # post-fetch watermark: both waves (computing +
                    # fetched) were resident just before this point — the
                    # reading the wave-size estimate needs validated
                    memory.note(sp)
                    with self._lock:
                        self.staged_bytes += n_bytes
                        self.transfers += 1
                        n = self.transfers
                        # the engine's CUMULATIVE overlap accounting on
                        # every transfer span (ISSUE 11): a wave run
                        # killed mid-generation still carries partial
                        # overlap evidence in its trace — the summary
                        # counters alone die with the process. This
                        # job's own elapsed rides in because transfer_s
                        # is only folded in by the finally below.
                        done_s = self.transfer_s + (time.perf_counter() - t0)
                        sp["wait_s"] = round(self.wait_s, 6)
                        sp["overlap_s"] = round(max(0.0, done_s - self.wait_s), 6)
                    # per-transfer liveness: the main thread parks in
                    # drain() at generation boundaries, so without beats
                    # from HERE a hung host<->device stage (a wedged
                    # runtime) freezes the wave silently until the
                    # whole-generation timeout — with them, launch.py's
                    # --stall-timeout can be sized to one wave's transfer.
                    # Beaten INSIDE the span so the beat's phase field
                    # reads "stage_out" — what a stall report shows.
                    # (heartbeat.beat is thread-safe; no-op when the CLI
                    # configured no heartbeat file)
                    heartbeat.beat(stage="staging transfer", transfers=n)
            # sweeplint: disable=drain-swallow -- transfer-thread containment: the error is stored and re-raised to the main thread by drain()
            except BaseException as e:  # surfaced by drain()
                with self._lock:
                    self._errors.append(e)
            finally:
                with self._lock:
                    self.transfer_s += time.perf_counter() - t0
                    self._pending -= 1
                    if self._pending == 0:
                        self._idle.notify_all()

    # -- main-thread API -------------------------------------------------

    def stage_out(self, tree: Any, on_host: Callable[[Any], None]) -> None:
        if self._closed:
            raise RuntimeError("StagingEngine is closed")
        with self._lock:
            if self._errors:  # fail fast instead of queueing onto a wreck
                raise self._errors[0]
            self._pending += 1
        self._q.put((tree, on_host))

    def note_bytes(self, n: int) -> None:
        """Account main-thread transfer bytes (stage-in device_puts)."""
        with self._lock:
            self.staged_bytes += int(n)

    def drain(self) -> None:
        """Completion barrier: block until all enqueued transfers are
        done; re-raise the first worker error. Block time is accounted
        as un-hidden transfer cost (``wait_s``) and traced as a
        ``stage_wait`` span — the staging cost compute did NOT hide,
        now a number the trace CLI reports instead of a summed counter."""
        from mpi_opt_tpu.obs import trace

        t0 = time.perf_counter()
        with trace.span("stage_wait") as sp:
            with self._idle:
                while self._pending:
                    self._idle.wait(timeout=0.5)
                self.wait_s += time.perf_counter() - t0
                # at a drain every enqueued transfer has completed, so
                # these are the engine's EXACT cumulative numbers — the
                # per-generation overlap evidence the trace layer
                # promotes into attribution (obs/bubbles.py)
                sp["wait_s"] = round(self.wait_s, 6)
                sp["overlap_s"] = round(self.overlap_s, 6)
                if self._errors:
                    raise self._errors[0]

    @property
    def overlap_s(self) -> float:
        return max(0.0, self.transfer_s - self.wait_s)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._q.put(None)
        self._thread.join(timeout=60)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def _per_member_bytes(trainer, sample_x) -> int:
    """The static per-member envelope: params at their own dtypes plus
    momentum at the trainer's storage dtype, from ``jax.eval_shape``
    over the trainer's init (abstract — no compute, no allocation).
    ONE home for the byte math ``estimate_wave_size`` sizes with and
    ``envelope_report`` validates against measurement."""
    params_sd = jax.eval_shape(trainer.init_fn, jax.random.key(0), sample_x)
    p_bytes = tree_bytes(params_sd)
    m_dt = trainer.momentum_dtype
    if m_dt is None:
        return 2 * p_bytes
    itemsize = np.dtype(m_dt).itemsize
    return p_bytes + sum(
        int(np.prod(l.shape)) * itemsize for l in jax.tree.leaves(params_sd)
    )


def measured_train_peak(metrics_path: str) -> Optional[int]:
    """The max ``mem_peak_bytes`` watermark over the device-occupying
    spans (train / stage_in / stage_out) of a prior traced run's JSONL
    metrics stream (ISSUE 10 instrumented them; ISSUE 13 closes the
    loop by reading them back). None when the stream has no usable
    watermark — untraced run, missing file, or pre-watermark records.
    Torn/foreign lines are skipped, not fatal: a metrics stream is
    append-only and may end mid-line after a kill."""
    import json

    peak = None
    try:
        with open(metrics_path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if not isinstance(rec, dict) or rec.get("event") != "span":
                    continue
                if rec.get("span") not in ("train", "stage_in", "stage_out"):
                    continue
                v = rec.get("mem_peak_bytes")
                if isinstance(v, (int, float)):
                    peak = max(peak or 0, int(v))
    except OSError:
        return None
    return peak


def envelope_report(trainer, sample_x, population: int, metrics_path: str) -> dict:
    """Validate the static per-member envelope math against a MEASURED
    watermark (the carried ROADMAP item: "validate the 4.5 GB pop=1024
    envelope math against measured mem_peak_bytes watermarks").

    ``metrics_path`` is a prior traced run of the SAME (workload,
    population) — its train-span ``mem_peak_bytes`` is what the
    population actually cost the device (allocator counters on TPU;
    live-array accounting on CPU, which also sees datasets — the
    ``measured_over_static`` ratio is therefore a CEILING of the true
    state overhead there, honest but conservative). Returns::

        {"per_member_bytes", "static_pop_bytes", "measured_peak_bytes",
         "measured_over_static"}

    with None measurement fields when the stream carries no watermark.
    The static math is validated (not replaced): a ratio far above the
    activation-headroom assumption baked into ``estimate_wave_size``'s
    35% offer means the envelope UNDERestimates and auto waves would
    OOM — feed the measurement back via that function's
    ``measured_peak`` argument."""
    per_member = _per_member_bytes(trainer, sample_x)
    static_pop = per_member * int(population)
    peak = measured_train_peak(metrics_path)
    return {
        "per_member_bytes": int(per_member),
        "static_pop_bytes": int(static_pop),
        "measured_peak_bytes": None if peak is None else int(peak),
        "measured_over_static": (
            None if peak is None or static_pop <= 0 else round(peak / static_pop, 4)
        ),
    }


def estimate_wave_size(
    trainer,
    sample_x,
    population: int,
    mesh=None,
    budget_bytes: Optional[int] = None,
    measured_peak: Optional[tuple] = None,
) -> int:
    """Residency estimate for ``--wave-size auto``: the largest wave the
    device budget fits with double-buffer + activation headroom.

    Per-member bytes come from ``jax.eval_shape`` over the trainer's
    init (abstract — no compute, no allocation): params at their own
    dtypes plus momentum at the trainer's storage dtype. Budget
    resolution order (ISSUE 10): ``budget_bytes`` argument, else the
    ``MPI_OPT_TPU_DEVICE_BYTES`` env var (the operator's EXPLICIT
    override — it must beat a measurement, or there is no way to size
    waves for a device other than the one present), else the device's
    MEASURED capacity (``obs.memory.measured_budget()``: the
    ``memory_stats`` ``bytes_limit`` — absent on CPU), else a
    conservative 8 GiB default.
    Only ~35% of it is offered to ONE wave's params+momentum: the wave
    loop keeps up to two waves resident (compute + in-flight fetch) and
    training needs activation/update headroom on top (the measured
    envelope: 4.5 GB of state tipped a 16 GB chip — PERF_NOTES).

    ``measured_peak`` (ISSUE 13, closing the ROADMAP envelope-math
    item): ``(peak_bytes, resident_members)`` from a prior traced run —
    typically ``measured_train_peak(stream)`` with the members that run
    held resident. The measured all-in per-member cost (state +
    activations + double buffer, everything the allocator actually saw)
    sizes a second wave estimate WITHOUT the 35% static headroom guess
    (the measurement already includes what the guess models, modulo a
    15% safety margin), and the SMALLER of the two estimates wins —
    measurement tightens the static math, never loosens it past what
    the envelope would allow.

    With a mesh the wave shards over the 'pop' axis, so the budget
    scales by that axis and the result is rounded DOWN to a multiple of
    it (replicated waves would defeat the mesh silently). Returns a
    value in [1, population]; ``population`` means everything fits —
    callers run resident mode.

    Under multi-process SPMD this is a PER-HOST estimate (the budget
    sources — env override, ``memory_stats`` — describe the local
    devices); ``resolve_wave_size`` min-agrees the settled cap across
    ranks through the coord plane, so heterogeneous hosts converge on
    the most constrained one's answer rather than each guessing.
    """
    per_member = _per_member_bytes(trainer, sample_x)
    if budget_bytes is None:
        env = os.environ.get("MPI_OPT_TPU_DEVICE_BYTES")
        if env:
            budget_bytes = int(env)
    if budget_bytes is None:
        from mpi_opt_tpu.obs import memory as obs_memory

        budget_bytes = obs_memory.measured_budget()
    if budget_bytes is None:
        budget_bytes = 8 << 30
    n_pop = int(mesh.shape["pop"]) if mesh is not None else 1
    w = int(budget_bytes * 0.35 * n_pop // max(1, per_member))
    if measured_peak:
        peak_bytes, members = measured_peak
        if peak_bytes and members:
            # all-in measured cost per member: no 0.35 headroom guess
            # (the watermark already holds activations + buffers), just
            # a 15% safety margin against run-to-run spread
            measured_member = max(1, int(peak_bytes) // max(1, int(members)))
            w_measured = int(budget_bytes * 0.85 * n_pop // measured_member)
            w = min(w, max(1, w_measured))
    if mesh is not None and w > n_pop:
        w -= w % n_pop
    return max(1, min(population, w))
