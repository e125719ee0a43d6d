"""The suggestion service: one resident process answering suggest →
report → lookup traffic over a filesystem spool, backed by the batched
TPE acquisition kernel (``ops/tpe.py:tpe_suggest``) warm-started from
the ledger corpus.

Why this exists (ISSUE 14 / ROADMAP "cross-sweep knowledge"): the
acquisition kernel scores thousands of candidates per jitted call
(BENCH config 4: ~2176 suggestions/s), which is orders of magnitude
more suggestion throughput than any single sweep consumes — so one
chip can serve suggestion traffic for MANY external sweeps that bring
their own evaluation capacity. The transport is the same
no-network-needed shape as the sweep service's spool: clients
atomic-write request files, the server atomic-writes responses::

    SDIR/requests/<req>.json    # {"id", "op", ...} (client-owned)
    SDIR/responses/<id>.json    # the answer (server-owned)
    SDIR/control/stop           # flag: finish the queue and exit 0

Ops: ``suggest`` (n unit-cube points + typed params, acquisition-
ranked), ``report`` (a completed external evaluation: enters the
observation ring, the corpus cache, and — when the server journals —
the server's own ledger, so the knowledge COMPOUNDS: a suggestion
tenant's ledger is itself corpus material for the next index), and
``lookup`` (the CorpusCache view: exact hit, near-match ``fidelity:
"prior"`` evidence, or miss).

Tenant integration: ``run_suggest_tenant`` is the flat-CLI entry
(``--suggest-serve DIR``) and is submittable to the sweep service
unchanged — every served request beats the heartbeat and ticks the
cooperative slice hook, so the scheduler time-slices a suggestion
tenant exactly like a sweep (drain parks it with exit 75; its ledger +
``--resume`` rebuild the ring on the next slice); the stop flag or an
idle timeout completes it (exit 0).
"""

from __future__ import annotations

import os
import time
from typing import Optional

import numpy as np

from mpi_opt_tpu.service.spool import _read_json, _write_json_atomic

#: response written for a request the server cannot parse — the client
#: gets an answer (not a timeout) and the queue never wedges on garbage
_MALFORMED = {"error": "malformed request (need JSON with id/op)"}


def spool_paths(sdir: str) -> dict:
    return {
        "requests": os.path.join(sdir, "requests"),
        "responses": os.path.join(sdir, "responses"),
        "control": os.path.join(sdir, "control"),
    }


def ensure_spool(sdir: str) -> dict:
    paths = spool_paths(sdir)
    for p in paths.values():
        os.makedirs(p, exist_ok=True)
    return paths


def stop_path(sdir: str) -> str:
    return os.path.join(sdir, "control", "stop")


#: responses a client never consumed (it timed out, or died after
#: writing its request) are expired after this age; swept on idle ticks
_RESPONSE_TTL_S = 600.0
_RESPONSE_GC_EVERY_S = 60.0


def _sweep_responses(resp_dir: str, ttl_s: float = _RESPONSE_TTL_S) -> None:
    """Best-effort expiry of abandoned response files — clients unlink
    the answers they consume, so anything older than the TTL has no
    reader left and is only inode debris."""
    now = time.time()
    try:
        names = os.listdir(resp_dir)
    except OSError:
        return
    for name in names:
        path = os.path.join(resp_dir, name)
        try:
            if now - os.path.getmtime(path) > ttl_s:
                os.unlink(path)
        except OSError:
            pass  # consumed/replaced mid-sweep: exactly the goal


class SuggestServer:
    """The acquisition state: a fixed-shape observation ring (the TPE
    algorithm's layout — one jit for the server's lifetime) plus the
    corpus-backed near-match cache. Transport-free: ``handle`` answers
    one request dict; the serve loop owns the filesystem."""

    def __init__(
        self,
        space,
        seed: int = 0,
        buffer_size: int = 512,
        n_startup: int = 10,
        config=None,
    ):
        import jax

        from mpi_opt_tpu.ledger.cache import CorpusCache
        from mpi_opt_tpu.ops.tpe import TPEConfig, tpe_suggest

        self.space = space
        self.seed = seed
        self.n_startup = n_startup
        self.config = config or TPEConfig()
        self.buffer_size = buffer_size
        self._obs_unit = np.zeros((buffer_size, space.dim), dtype=np.float32)
        self._obs_score = np.zeros(buffer_size, dtype=np.float32)
        self._valid = np.zeros(buffer_size, dtype=bool)
        self._n_obs = 0
        self._suggested = 0  # fold-in counter: every batch draws fresh keys
        self._next_id = 0  # journaled report serial
        self.cache = CorpusCache(space)
        self._suggest_fn = jax.jit(tpe_suggest, static_argnames=("n_suggest", "cfg"))

    # -- state feeds -------------------------------------------------

    def _push(self, unit: np.ndarray, score: float) -> None:
        slot = self._n_obs % self.buffer_size
        self._obs_unit[slot] = np.asarray(unit, dtype=np.float32)
        self._obs_score[slot] = score
        self._valid[slot] = True
        self._n_obs += 1

    def ingest(self, observations) -> int:
        """Corpus warm start: ascending score order so a prior that
        overflows the ring evicts its own worst rows first (the TPE
        algorithm's rule)."""
        finite = [o for o in observations if np.isfinite(o.score)]
        finite.sort(key=lambda o: o.score)
        for o in finite:
            self._push(o.unit, float(o.score))
        return len(finite)

    def seed_from_ledger(self, records) -> int:
        """Resume: rebuild the ring and the exact cache from the
        server's OWN journaled reports (every report below journals one
        trial record), and continue the report serial past them."""
        from mpi_opt_tpu.ledger.warmstart import observations_from_records

        obs, _skips = observations_from_records(records, self.space)
        n = self.ingest(obs)
        self.cache.seed_from(records)
        self.cache.seed_prior(records)
        if records:
            self._next_id = 1 + max(int(r["trial_id"]) for r in records)
        return n

    # -- ops ---------------------------------------------------------

    def suggest(self, n: int) -> dict:
        import jax

        from mpi_opt_tpu.utils.hostdev import host_ops

        n = max(1, min(int(n), self.config.n_candidates))
        with host_ops():  # tiny acquisition: no accelerator dispatch
            key = jax.random.fold_in(jax.random.key(self.seed), self._suggested)
            if self._n_obs < self.n_startup:
                unit = np.asarray(self.space.sample_unit(key, n))
            else:
                # power-of-two block rounding: varying client batch
                # sizes hit at most log2(n_candidates) jit variants
                block = 1 << (n - 1).bit_length()
                sugg, _ = self._suggest_fn(
                    key,
                    self._obs_unit,
                    self._obs_score,
                    self._valid,
                    n_suggest=min(block, self.config.n_candidates),
                    cfg=self.config,
                )
                unit = np.asarray(sugg[:n])
        self._suggested += n
        return {
            "units": [[float(v) for v in row] for row in unit],
            "params": [
                self.space.canonical_params(self.space.materialize_row(row))
                for row in unit
            ],
            "n_obs": self._n_obs,
        }

    def report(self, req: dict, ledger=None, meta=None) -> dict:
        """One external evaluation enters the knowledge state (ring +
        cache + optional journal). ``params`` (canonical dict) or
        ``unit`` (row list) identifies the point; non-finite scores
        journal as failed and never touch the ring. ``meta`` rides the
        journal record verbatim (the HTTP front door stamps its
        idempotency key here so a restarted server can rebuild its
        dedup index from the journal)."""
        from mpi_opt_tpu.ledger.warmstart import _decode_params
        from mpi_opt_tpu.trial import TrialResult, failed_result

        score = float(req.get("score", float("nan")))
        budget = int(req.get("budget") or 0)
        if req.get("unit") is not None:
            unit = np.asarray(req["unit"], dtype=np.float32)
            params = self.space.materialize_row(unit)
        elif req.get("params") is not None:
            params = _decode_params(self.space, dict(req["params"]))
            unit = self.space.params_to_unit(params)
        else:
            return {"error": "report needs params or unit"}
        tid = self._next_id
        self._next_id += 1
        if np.isfinite(score):
            result = TrialResult(
                trial_id=tid, score=score, step=budget, wall_time=0.0
            )
            self._push(unit, score)
        else:
            result = failed_result(
                trial_id=tid, step=budget, error="non-finite reported score"
            )
        self.cache.put(params, result)
        if ledger is not None:
            # fsync-durable BEFORE the ack, the same ordering rule as
            # the driver's journal-before-report: a client that saw the
            # ack must find its evidence in the ledger after any crash
            ledger.record_trial(
                result, self.space.canonical_params(params), meta=meta
            )
        return {"ok": result.ok, "trial_id": tid, "n_obs": self._n_obs}

    def lookup(self, req: dict) -> dict:
        """The CorpusCache view: exact → prior → miss, never a result
        substitute (the prior answer says so via ``fidelity``)."""
        from mpi_opt_tpu.ledger.warmstart import _decode_params

        params = _decode_params(self.space, dict(req.get("params") or {}))
        budget = int(req.get("budget") or 0)
        exact = self.cache.get(params, budget, trial_id=-1)
        if exact is not None:
            return {
                "hit": "exact",
                "score": exact.score,
                "step": exact.step,
            }
        prior = self.cache.get_prior(params, trial_id=-1)
        if prior is not None:
            return {
                "hit": "prior",
                "score": prior.score,
                "step": prior.step,
                "fidelity": prior.extra["fidelity"],
                "prior_kind": prior.extra["prior_kind"],
            }
        return {"hit": None}

    def handle(self, req: dict, ledger=None, meta=None) -> dict:
        op = req.get("op")
        try:
            if op == "suggest":
                return self.suggest(int(req.get("n") or 1))
            if op == "report":
                return self.report(req, ledger=ledger, meta=meta)
            if op == "lookup":
                return self.lookup(req)
        except (KeyError, TypeError, ValueError) as e:
            # a bad point/params shape is the CLIENT's error: answer it
            # (the sweep service's tenant_reject moral — one malformed
            # request must not take down the server every other client
            # is riding on), never crash the resident process
            return {"error": f"{type(e).__name__}: {e}"}
        except Exception as e:
            from mpi_opt_tpu.ledger.store import LedgerError

            if isinstance(e, LedgerError):
                return {"error": str(e)}
            raise
        return {"error": f"unknown op {op!r}"}


def serve_loop(
    server: SuggestServer,
    sdir: str,
    metrics,
    ledger=None,
    # 10 ms: the idle poll IS the serving latency floor for a serial
    # client (it writes its next request only after reading the last
    # response, so the server is asleep when every request lands) — at
    # 0.05 the p50 round trip measured 53 ms of which 50 was this nap
    poll_seconds: float = 0.01,
    idle_timeout: Optional[float] = None,
    max_requests: Optional[int] = None,
) -> dict:
    """Answer requests until stop/idle/drain. Returns the summary dict;
    raises SweepInterrupted on a drain request (the caller maps it to
    the EX_TEMPFAIL park, exactly like a sweep)."""
    from mpi_opt_tpu.health import heartbeat, shutdown
    from mpi_opt_tpu.health.shutdown import SweepInterrupted

    paths = ensure_spool(sdir)
    served = suggestions = reports = 0
    last_activity = time.monotonic()
    next_gc = time.monotonic() + _RESPONSE_GC_EVERY_S
    stopped = stop_seen = False
    while True:
        if not stop_seen and os.path.exists(stop_path(sdir)):
            # latch AND consume: the flag means "finish what is queued,
            # then exit 0" — the queue drains below before we break, and
            # unlinking keeps a stale flag from instantly stopping the
            # NEXT server (a --resume'd tenant) on this spool
            stop_seen = True
            try:
                os.unlink(stop_path(sdir))
            except OSError:
                pass
        try:
            pending = sorted(
                f for f in os.listdir(paths["requests"]) if f.endswith(".json")
            )
        except OSError:
            pending = []  # transient listing failure: next poll retries
        if not pending:
            if stop_seen:
                stopped = True
                break
            if shutdown.requested():
                raise SweepInterrupted(shutdown.active_signal(), at=f"request {served}")
            if max_requests is not None and served >= max_requests:
                stopped = True
                break
            if (
                idle_timeout is not None
                and time.monotonic() - last_activity >= idle_timeout
            ):
                stopped = True
                break
            # idle housekeeping: expire abandoned responses (a client
            # that timed out or died never consumes its answer, and a
            # resident server must not grow responses/ without bound)
            if time.monotonic() >= next_gc:
                _sweep_responses(paths["responses"])
                next_gc = time.monotonic() + _RESPONSE_GC_EVERY_S
            time.sleep(poll_seconds)
            continue
        for fname in pending:
            rpath = os.path.join(paths["requests"], fname)
            req = _read_json(rpath)
            if req is None or not req.get("id"):
                # torn client write or garbage: answer under the file's
                # stem so the writer still gets a response, then clear
                rid = fname[: -len(".json")]
                ans = dict(_MALFORMED, id=rid)
            else:
                rid = str(req["id"])
                ans = dict(server.handle(req, ledger=ledger), id=rid)
            # respond-then-unlink: a crash between the two re-serves the
            # request on restart — the response rewrite is atomic and
            # the client takes whichever answer it reads first
            _write_json_atomic(os.path.join(paths["responses"], f"{rid}.json"), ans)
            try:
                os.unlink(rpath)
            except OSError:
                pass
            served += 1
            last_activity = time.monotonic()
            op = (req or {}).get("op")
            if op == "suggest":
                suggestions += len(ans.get("params") or [])
            elif op == "report":
                reports += 1
            metrics.log(
                "suggest_request",
                op=op,
                served=served,
                n_obs=server._n_obs,
                error=ans.get("error"),
            )
            # the tenant's liveness pulse + cooperative slice point:
            # every served request is a natural boundary, so the sweep
            # service can time-slice a suggestion tenant like a sweep
            heartbeat.beat(stage="suggest", served=served, reports=reports)
            shutdown.poll_slice(f"request {served}")
            if shutdown.requested():
                raise SweepInterrupted(
                    shutdown.active_signal(), at=f"request {served}"
                )
            if max_requests is not None and served >= max_requests:
                stopped = True
                break
        if stopped:
            break
    summary = {
        "served": served,
        "suggestions": suggestions,
        "reports": reports,
        "n_obs": server._n_obs,
        "stopped": stopped,
    }
    metrics.log("suggest_stop", **summary)
    return summary
