"""Default CPU backend: process-parallel trial evaluation.

Reference parity (SURVEY.md §1-§3; reference unreadable): the
reference's default path evaluates trials on MPI ranks — a Coordinator
sends hyperparameters to MPIWorker processes, which train and report a
score. This container has no MPI, so rank-parallelism is rebuilt on
``multiprocessing`` (same process-per-trial execution model, same
role as the 8-rank MPI baseline in BASELINE.json's north star — and the
measured baseline that bench.py compares the TPU backend against).

Two paths:
- stateless (random/TPE/ASHA from-scratch): trials fan out to a process
  pool; the workload is reconstructed in each worker by registry name so
  nothing unpicklable crosses the fork.
- stateful (PBT inheritance / ASHA warm resume): training states must
  persist between evaluations. By default they live in the parent and
  training runs in-process — correct but sequential, and structurally
  UNINTERRUPTIBLE (no ``trial_timeout`` can reap an in-parent hang).
  ``isolate_stateful=True`` moves the whole stateful path (state store
  included) into ONE dedicated spawned worker process: same sequential
  semantics, same inheritance behavior, but the process boundary makes
  the deadline enforceable — a hung trial is reaped as status=timeout
  and the worker killed + respawned (its state store resets, so
  successors inheriting from lost trials retrain from scratch — the
  same fallback as inheriting from an unknown id).
"""

from __future__ import annotations

import math
import multiprocessing as mp
import os
import time
from collections import OrderedDict
from typing import Any, Sequence

from mpi_opt_tpu.backends.base import Backend, register_backend
from mpi_opt_tpu.trial import Trial, TrialResult, failed_result
from mpi_opt_tpu.workloads.base import Workload

_WORKER_WORKLOAD: Workload | None = None


def _init_worker(workload_name: str, workload_kwargs: dict):
    global _WORKER_WORKLOAD
    from mpi_opt_tpu.workloads import get_workload

    _WORKER_WORKLOAD = get_workload(workload_name, **workload_kwargs)


def _init_pool_worker(workload_name: str, workload_kwargs: dict):
    """Pool-process initializer (never runs in the parent).

    CPU workers must never grab the TPU: the parent may hold it, and a
    chip belongs to one process at a time. The worker inherits the
    parent's JAX_PLATFORMS (tpu, or unset) and has imported jax by the
    time this runs (unpickling the initializer imports this module), so
    the pin goes through jax.config; the environment variable is set
    for whatever the worker itself starts.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        import jax
    except ImportError:
        jax = None  # workload may not need jax at all
    if jax is not None:
        # No blanket swallow: if the pin fails (backend already up in
        # this child), continuing would let N workers race the real TPU
        # and hang — fail loudly instead.
        jax.config.update("jax_platforms", "cpu")
        # XLA:CPU takes minutes to compile conv training programs, and
        # a fresh pool otherwise pays that on every process start
        from mpi_opt_tpu.utils.compile_cache import wire_compile_cache

        wire_compile_cache()
    _init_worker(workload_name, workload_kwargs)


def _eval_one(args):
    """Evaluate one job, NEVER letting a trial's exception escape the
    worker: a raising trial poisons pool.map's whole batch (every other
    job's result is discarded with it), so the failure is materialized
    as a failed TrialResult right where it happens. Non-finite scores
    are mapped onto the same contract — the host driver path's
    equivalent of the fused sweeps' isfinite masking."""
    trial_id, params, budget, seed = args
    t0 = time.perf_counter()
    try:
        score = float(_WORKER_WORKLOAD.evaluate(params, budget, seed))
    except Exception as e:
        return failed_result(
            trial_id,
            budget,
            f"{type(e).__name__}: {e}",
            wall_time=time.perf_counter() - t0,
        )
    if not math.isfinite(score):
        return failed_result(
            trial_id,
            budget,
            f"non-finite score {score!r}",
            score=score,
            wall_time=time.perf_counter() - t0,
        )
    return TrialResult(
        trial_id=trial_id,
        score=score,
        step=budget,
        wall_time=time.perf_counter() - t0,
    )


def _stateful_eval(
    workload: Workload,
    states: "OrderedDict[int, Any]",
    trained: dict,
    max_states: int,
    trial_id: int,
    raw_params: dict,
    budget: int,
    seed: int,
) -> TrialResult:
    """One stateful evaluation against a (states, trained) store — the
    SINGLE implementation behind both the in-parent path and the
    ``isolate_stateful`` worker process, so warm-resume/inheritance
    semantics cannot drift between them."""
    t0 = time.perf_counter()
    params = _clean(raw_params)
    src = raw_params.get("__inherit_from__")
    if src is not None and src in states:
        state = states[src]
        done = trained.get(src, 0)
    elif trial_id in states:
        state = states[trial_id]
        done = trained[trial_id]
    else:
        state = workload.init_state(params, seed)
        done = 0
    remaining = max(0, budget - done)
    try:
        state, score = workload.train(state, params, remaining, seed)
    except Exception as e:
        # the failed member's state is NOT stored: a PBT successor
        # inheriting from it would resume a half-trained wreck
        return failed_result(
            trial_id,
            budget,
            f"{type(e).__name__}: {e}",
            wall_time=time.perf_counter() - t0,
        )
    if not math.isfinite(float(score)):
        return failed_result(
            trial_id,
            budget,
            f"non-finite score {float(score)!r}",
            score=float(score),
            wall_time=time.perf_counter() - t0,
        )
    states[trial_id] = state
    states.move_to_end(trial_id)
    trained[trial_id] = budget
    while len(states) > max_states:
        old, _ = states.popitem(last=False)
        trained.pop(old, None)
    return TrialResult(
        trial_id=trial_id,
        score=float(score),
        step=budget,
        wall_time=time.perf_counter() - t0,
    )


def _stateful_worker_main(conn, workload_name, workload_kwargs, seed, max_states):
    """Entry point of the ``isolate_stateful`` worker (spawned child).

    Owns the (states, trained) store for its lifetime; jobs arrive as
    ``(trial_id, raw_params, budget)`` tuples and leave as TrialResults.
    ``"reset"`` clears the store (Backend.reset), ``None`` exits. The
    initial ``("ready", pid)`` handshake lets the parent exclude child
    cold-start (spawn + jax import + platform pin) from any trial's
    deadline."""
    try:
        _init_pool_worker(workload_name, workload_kwargs)
    # sweeplint: disable=drain-swallow -- spawned worker: no drain protocol here; init failure is reported to the parent over the pipe and the worker exits
    except BaseException as e:
        try:
            conn.send(("init_failed", f"{type(e).__name__}: {e}"))
        finally:
            return
    states: "OrderedDict[int, Any]" = OrderedDict()
    trained: dict = {}
    conn.send(("ready", os.getpid()))
    while True:
        try:
            msg = conn.recv()
        except EOFError:
            return
        if msg is None:
            return
        if msg == "reset":
            states.clear()
            trained.clear()
            conn.send("reset_ok")
            continue
        trial_id, raw_params, budget = msg
        conn.send(
            _stateful_eval(
                _WORKER_WORKLOAD, states, trained, max_states,
                trial_id, raw_params, budget, seed,
            )
        )


@register_backend
class CPUBackend(Backend):
    name = "cpu"

    def __init__(
        self,
        workload: Workload,
        n_workers: int = 0,  # 0 -> os.cpu_count()
        seed: int = 0,
        workload_kwargs: dict | None = None,
        max_states: int = 256,
        trial_timeout: float | None = None,  # seconds per trial, None = unbounded
        isolate_stateful: bool = False,  # stateful path in a spawned worker
    ):
        super().__init__(workload)
        self.n_workers = n_workers or (os.cpu_count() or 1)
        self.seed = seed
        if trial_timeout is not None and trial_timeout <= 0:
            raise ValueError(f"trial_timeout must be > 0, got {trial_timeout}")
        self.trial_timeout = trial_timeout
        self.isolate_stateful = bool(isolate_stateful)
        self._workload_kwargs = workload_kwargs or {}
        self._pool = None
        self._stateful_proc = None
        self._stateful_conn = None
        self._warned_stateful_platform = False
        self._warned_stateful_timeout = False
        # trial_id -> training state, FIFO-bounded: PBT mints fresh trial
        # ids every generation and would otherwise accumulate every
        # generation's model states until OOM (inheritance only ever
        # reaches one generation back; ASHA resumes are also recent)
        self.max_states = max_states
        self._states: "OrderedDict[int, Any]" = OrderedDict()
        self._trained: dict[int, int] = {}  # trial_id -> steps completed

    @property
    def capacity(self) -> int:
        return self.n_workers

    def _get_pool(self):
        if self._pool is None:
            # spawn, not fork: the parent has live JAX threads and forking
            # a multithreaded process risks deadlock in the children
            ctx = mp.get_context("spawn")
            self._pool = ctx.Pool(
                self.n_workers,
                initializer=_init_pool_worker,
                initargs=(self.workload.name, self._workload_kwargs),
            )
        return self._pool

    def evaluate(self, trials: Sequence[Trial]) -> list[TrialResult]:
        if self.workload.stateful:
            if self.isolate_stateful:
                # the state store lives in a dedicated spawned worker:
                # same sequential semantics as in-parent, but the
                # process boundary makes trial_timeout enforceable
                return [self._evaluate_stateful_isolated(t) for t in trials]
            # stateful path: warm resumes + PBT inheritance need the
            # state store, which lives in this process
            if self.trial_timeout is not None and not self._warned_stateful_timeout:
                # in-parent execution cannot be interrupted, so the
                # deadline the user asked for is unenforceable here —
                # say so instead of silently pretending it's active
                self._warned_stateful_timeout = True
                import warnings

                warnings.warn(
                    "cpu backend: trial_timeout cannot be enforced for "
                    "stateful workloads evaluating in-parent (an "
                    "in-process call can't be interrupted) — exceptions "
                    "and non-finite scores are still caught, hangs are "
                    "not reaped. Pass isolate_stateful=True "
                    "(--isolate-stateful) to run the stateful path in a "
                    "killable worker process",
                    stacklevel=3,
                )
            return [self._evaluate_stateful(t) for t in trials]
        jobs = [
            (t.trial_id, _clean(t.params), t.budget, self.seed) for t in trials
        ]
        # a timeout can only be enforced across a process boundary (a
        # hung in-parent call can't be interrupted), so it forces the
        # pool path even for single-trial batches
        if (
            self.trial_timeout is None
            and (self.n_workers == 1 or len(jobs) == 1)
            and self._inline_ok()
        ):
            self._ensure_inline_worker()
            return [_eval_one(j) for j in jobs]
        return self._evaluate_pool(jobs)

    def _evaluate_pool(self, jobs) -> list[TrialResult]:
        """Per-job async dispatch: one trial raising (caught in-worker)
        never takes the rest of the batch with it — pool.map would
        discard every result on the first exception. Hangs and hard
        worker crashes are additionally reaped, but ONLY under a
        configured ``trial_timeout``: a crashed worker's job simply
        never completes (mp.Pool repopulates workers without completing
        lost jobs), so without a deadline its ``get`` blocks forever —
        same exposure as before this layer, and the reason --trial-
        timeout is the recommended production setting."""
        pool = self._get_pool()
        t0 = time.monotonic()
        asyncs = [pool.apply_async(_eval_one, (j,)) for j in jobs]
        out: list[TrialResult] = []
        broken = False
        for i, (job, a) in enumerate(zip(jobs, asyncs)):
            if self.trial_timeout is None:
                wait = None
            else:
                # job i starts no later than wave i // n_workers, so its
                # deadline is (wave+1) whole timeouts from batch start
                # (plus dispatch grace): a job queued behind a hung
                # worker still gets its own full window, while the whole
                # batch is bounded by ~timeout * n_jobs / n_workers
                allowance = self.trial_timeout * (i // self.n_workers + 1) + 1.0
                wait = max(0.05, t0 + allowance - time.monotonic())
            try:
                out.append(a.get(wait))
            except mp.TimeoutError:
                broken = True
                out.append(
                    failed_result(
                        job[0],
                        job[2],
                        f"no result within {self.trial_timeout}s "
                        "(trial hung, or its worker crashed)",
                        status="timeout",
                        wall_time=time.monotonic() - t0,
                    )
                )
            except Exception as e:
                # pool-level failure (worker killed hard enough that the
                # result machinery raised instead of hanging)
                broken = True
                out.append(
                    failed_result(
                        job[0],
                        job[2],
                        f"worker failure: {type(e).__name__}: {e}",
                        wall_time=time.monotonic() - t0,
                    )
                )
        if broken:
            # a reaped job's worker is still wedged (or gone): recycle
            # the whole pool so the next batch starts with clean workers
            self._rebuild_pool()
        return out

    def _rebuild_pool(self):
        if self._pool is not None:
            self._pool.terminate()
            self._pool.join()
            self._pool = None

    def _inline_ok(self) -> bool:
        """Inline (in-parent) evaluation is only allowed when the parent
        is a CPU-platform process: a single-trial batch under
        ``--backend cpu`` must never train on the TPU just because the
        parent process defaults to it. Otherwise route through the
        pinned pool. Side-effect free: never initializes a JAX backend
        just to ask which one is default (that would acquire the very
        accelerator this guard exists to avoid touching)."""
        try:
            import jax
        except ImportError:
            return True
        # the first entry of jax_platforms (JAX_PLATFORMS, --platform)
        # is the platform this process initializes; without a pin it
        # takes whatever accelerator it finds, so only a cpu-first pin
        # is safe
        platforms = (jax.config.jax_platforms or "").split(",")
        return platforms[0] == "cpu"

    def _ensure_inline_worker(self):
        """Install the parent-side workload once and reuse it across
        evaluate() calls (a fresh instance per call would discard
        PopulationWorkload's _eval_cache: recompile + dataset
        regeneration every batch)."""
        global _WORKER_WORKLOAD
        _WORKER_WORKLOAD = self.workload

    def _evaluate_stateful(self, t: Trial) -> TrialResult:
        # stateful training is inherently in-parent (the state store
        # lives here); on a TPU-default parent that means the "cpu"
        # backend actually trains on the accelerator — surface it rather
        # than silently violating the placement the user asked for
        if not self._warned_stateful_platform and not self._inline_ok():
            self._warned_stateful_platform = True
            import warnings

            warnings.warn(
                "cpu backend: stateful workload trains in the parent process, "
                "whose JAX platform is not cpu — use --backend tpu for "
                "on-device population training, or pin the parent to cpu",
                stacklevel=3,
            )
        return _stateful_eval(
            self.workload, self._states, self._trained, self.max_states,
            t.trial_id, t.params, t.budget, self.seed,
        )

    # -- process-isolated stateful evaluation (--isolate-stateful) ---------

    def _ensure_stateful_worker(self) -> None:
        """Spawn (or respawn) the dedicated stateful worker and wait for
        its readiness handshake, so child cold-start (spawn + jax import
        + platform pin, seconds of wall) is never billed to a trial's
        deadline."""
        if self._stateful_proc is not None and self._stateful_proc.is_alive():
            return
        self._kill_stateful_worker()
        ctx = mp.get_context("spawn")
        parent, child = ctx.Pipe()
        proc = ctx.Process(
            target=_stateful_worker_main,
            args=(
                child,
                self.workload.name,
                self._workload_kwargs,
                self.seed,
                self.max_states,
            ),
            daemon=True,
        )
        proc.start()
        child.close()
        self._stateful_proc, self._stateful_conn = proc, parent
        # generous fixed window: this is process bring-up, not a trial
        if not parent.poll(120.0):
            self._kill_stateful_worker()
            raise RuntimeError("stateful worker did not come up within 120s")
        try:
            msg = parent.recv()
        except (EOFError, OSError) as e:
            self._kill_stateful_worker()
            raise RuntimeError(
                f"stateful worker died during startup ({type(e).__name__})"
            ) from None
        if not (isinstance(msg, tuple) and msg[0] == "ready"):
            self._kill_stateful_worker()
            raise RuntimeError(f"stateful worker failed to initialize: {msg!r}")

    def _kill_stateful_worker(self) -> None:
        if self._stateful_proc is None:
            return
        proc, conn = self._stateful_proc, self._stateful_conn
        self._stateful_proc = self._stateful_conn = None
        if proc.is_alive():
            proc.terminate()
            proc.join(timeout=2.0)
            if proc.is_alive():  # ignored the TERM: it is truly wedged
                proc.kill()
        proc.join()
        if conn is not None:
            conn.close()

    def _evaluate_stateful_isolated(self, t: Trial) -> TrialResult:
        self._ensure_stateful_worker()
        t0 = time.monotonic()
        try:
            self._stateful_conn.send((t.trial_id, t.params, t.budget))
        except (BrokenPipeError, OSError):
            # worker died between trials: one respawn, then evaluate
            self._kill_stateful_worker()
            self._ensure_stateful_worker()
            self._stateful_conn.send((t.trial_id, t.params, t.budget))
        if self._stateful_conn.poll(self.trial_timeout):
            try:
                return self._stateful_conn.recv()
            except (EOFError, OSError):
                # the worker died MID-trial (segfault/OOM-kill/os._exit):
                # no result will ever arrive, and the state store died
                # with it — successors inheriting lost states retrain
                # from scratch (the standard unknown-id fallback)
                self._kill_stateful_worker()
                return failed_result(
                    t.trial_id,
                    t.budget,
                    "stateful worker died mid-trial (state store reset; "
                    "inheritors retrain from scratch)",
                    wall_time=time.monotonic() - t0,
                )
        # deadline passed with the worker alive: the trial hung — the
        # reap the in-parent path structurally cannot do (ROADMAP open
        # item closed by process isolation)
        self._kill_stateful_worker()
        return failed_result(
            t.trial_id,
            t.budget,
            f"no result within {self.trial_timeout}s (stateful trial "
            "hung; worker killed, state store reset)",
            status="timeout",
            wall_time=time.monotonic() - t0,
        )

    def reset(self):
        """Drop the stateful-path state store (see Backend.reset): a new
        search's trial ids must not warm-resume the previous search's
        states. The worker pool (process spawns) is kept — and so is the
        isolated stateful worker, whose store is cleared via message
        (falling back to a kill if it doesn't answer)."""
        self._states.clear()
        self._trained.clear()
        if self._stateful_proc is not None and self._stateful_proc.is_alive():
            try:
                self._stateful_conn.send("reset")
                if self._stateful_conn.poll(10.0) and self._stateful_conn.recv() == "reset_ok":
                    return
            except (BrokenPipeError, EOFError, OSError):
                pass
            self._kill_stateful_worker()

    def close(self):
        if self._pool is not None:
            self._pool.terminate()
            self._pool.join()
            self._pool = None
        if self._stateful_proc is not None:
            try:
                self._stateful_conn.send(None)  # clean exit request
                self._stateful_proc.join(timeout=2.0)
            except (BrokenPipeError, OSError):
                pass
            self._kill_stateful_worker()


def _clean(params: dict) -> dict:
    """Strip framework-internal keys before handing params to workloads."""
    return {k: v for k, v in params.items() if not k.startswith("__")}
