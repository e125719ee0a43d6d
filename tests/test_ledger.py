"""Durable sweep ledger: journal format, crash-resume equivalence,
warm-start, dedup cache, and the report CLI.

The headline is the acceptance drill: a sweep killed mid-run resumes
from its ledger and reports the IDENTICAL completed-trial set to the
algorithm — no lost evaluations, no double-reported ones, and no
re-evaluation of any trial already journaled ok.
"""

import json
import math
import os

import numpy as np
import pytest

from mpi_opt_tpu.algorithms import ASHA, RandomSearch, TPE
from mpi_opt_tpu.algorithms.base import Observation
from mpi_opt_tpu.backends.cpu import CPUBackend
from mpi_opt_tpu.driver import run_search
from mpi_opt_tpu.ledger import (
    EvalCache,
    LedgerError,
    SweepLedger,
    read_ledger,
    validate_ledger,
    warm_start,
)
from mpi_opt_tpu.ledger.store import result_from_record
from mpi_opt_tpu.trial import TrialResult, TrialStatus, failed_result
from mpi_opt_tpu.utils.metrics import MetricsLogger
from mpi_opt_tpu.workloads import get_workload


def _ledger(tmp_path, name="sweep.jsonl"):
    led = SweepLedger(str(tmp_path / name))
    led.ensure_header({"algorithm": "random", "seed": 0, "space_hash": "x"})
    return led


def _ok(tid, score, step=20):
    return TrialResult(trial_id=tid, score=score, step=step, wall_time=0.5)


class SpyBackend(CPUBackend):
    """CPU backend that counts evaluate() calls per trial_id and can be
    armed to die (simulated driver kill) after N evaluations."""

    def __init__(self, *a, die_after=None, **kw):
        super().__init__(*a, **kw)
        self.evaluated_ids = []
        self.die_after = die_after

    def evaluate(self, trials):
        if self.die_after is not None and len(self.evaluated_ids) >= self.die_after:
            raise KeyboardInterrupt("simulated driver kill")
        self.evaluated_ids.extend(t.trial_id for t in trials)
        return super().evaluate(trials)


# -- store: format, durability shape, torn-tail recovery -------------------


def test_header_and_records_round_trip(tmp_path):
    led = _ledger(tmp_path)
    led.record_trial(_ok(0, 1.5), {"lr": 0.1, "reg": 0.3})
    led.record_trial(
        failed_result(1, step=20, error="boom"), {"lr": 9.0, "reg": 0.1}, attempts=3
    )
    led.close()

    header, records, n_torn = read_ledger(led.path)
    assert n_torn == 0
    assert header["version"] == 1 and header["config"]["algorithm"] == "random"
    assert [r["trial_id"] for r in records] == [0, 1]
    assert records[0]["status"] == "ok" and records[0]["score"] == 1.5
    # non-finite scores journal as null (JSON has no NaN) and restore
    # through failed_result
    assert records[1]["status"] == "failed" and records[1]["score"] is None
    assert records[1]["attempts"] == 3
    restored = result_from_record(records[1])
    assert not restored.ok and math.isnan(restored.score)
    assert restored.error == "boom"


def test_reopen_validates_header_config(tmp_path):
    led = _ledger(tmp_path)
    led.record_trial(_ok(0, 1.0), {"lr": 0.1, "reg": 0.3})
    led.close()
    led2 = SweepLedger(led.path)
    with pytest.raises(LedgerError, match="different sweep"):
        led2.ensure_header({"algorithm": "tpe", "seed": 0, "space_hash": "x"})
    # matching config is accepted and keeps the original sweep_id
    led2.ensure_header({"algorithm": "random", "seed": 0, "space_hash": "x"})
    assert led2.sweep_id == led.sweep_id
    led2.close()


def test_torn_tail_line_is_truncated_not_fatal(tmp_path):
    led = _ledger(tmp_path)
    led.record_trial(_ok(0, 1.0), {"lr": 0.1, "reg": 0.3})
    led.record_trial(_ok(1, 2.0), {"lr": 0.2, "reg": 0.3})
    led.close()
    # simulate a crash mid-append: a torn final line, no trailing newline
    with open(led.path, "a") as f:
        f.write('{"kind": "trial", "trial_id": 2, "sco')

    led2 = SweepLedger(led.path)
    assert led2.n_torn == 1
    assert sorted(led2.completed()) == [0, 1]
    # the fragment was physically truncated: the next append starts on a
    # clean line boundary and the file parses strictly again
    led2.ensure_header({"algorithm": "random", "seed": 0, "space_hash": "x"})
    led2.record_trial(_ok(2, 3.0), {"lr": 0.3, "reg": 0.3})
    led2.close()
    assert validate_ledger(led.path) == []
    _, records, _ = read_ledger(led.path, strict=True)
    assert [r["trial_id"] for r in records] == [0, 1, 2]


def test_schema_invalid_complete_tail_refuses_not_truncates(tmp_path):
    """Torn means NOT-VALID-JSON: a tail line that parses but fails
    schema checks was written whole (edited / another tool) — loading
    must refuse, not silently destroy a completed trial's record."""
    led = _ledger(tmp_path)
    led.record_trial(_ok(0, 1.0), {"lr": 0.1, "reg": 0.3})
    led.close()
    with open(led.path, "a") as f:
        f.write(json.dumps({"kind": "trial", "trial_id": 1, "params": {},
                            "status": "weird", "step": 1}) + "\n")
    before = open(led.path).read()
    with pytest.raises(LedgerError, match="status"):
        SweepLedger(led.path)
    assert open(led.path).read() == before  # nothing was truncated


def test_warm_start_decodes_exotic_choice_options(tmp_path):
    """Choice options journal as their repr via _plain; warm-start must
    map them back to the live option objects, not feed repr strings to
    value_to_index."""
    from mpi_opt_tpu.ledger.warmstart import load_observations
    from mpi_opt_tpu.space import Choice, SearchSpace, Uniform

    space = SearchSpace({"k": Choice([(1, 2), (3, 4)]), "u": Uniform(0.0, 1.0)})
    led = SweepLedger(str(tmp_path / "prior.jsonl"))
    led.ensure_header({"space_hash": space.space_hash()})
    led.record_trial(_ok(0, 2.0), space.canonical_params({"k": (3, 4), "u": 0.5}))
    led.close()
    (obs,), skips = load_observations(led.path, space)
    assert skips == {}
    assert obs.score == 2.0
    # the decoded unit row round-trips to the original option
    assert space.materialize_row(obs.unit)["k"] == (3, 4)


def test_malformed_mid_file_refuses_to_load(tmp_path):
    led = _ledger(tmp_path)
    led.record_trial(_ok(0, 1.0), {"lr": 0.1, "reg": 0.3})
    led.close()
    lines = open(led.path).read().splitlines()
    lines.insert(1, "not json at all")
    with open(led.path, "w") as f:
        f.write("\n".join(lines) + "\n")
    # a torn line anywhere but the tail means the file was edited or
    # mixed with another stream — guessing would corrupt a resume
    with pytest.raises(LedgerError, match="line 2"):
        SweepLedger(led.path)
    assert validate_ledger(led.path) != []


def test_validate_flags_schema_problems(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text(
        json.dumps({"kind": "header", "version": 1, "sweep_id": "s", "config": {}})
        + "\n"
        + json.dumps({"kind": "trial", "trial_id": 0, "params": {}, "status": "weird", "step": 1})
        + "\n"
    )
    assert any("status" in prob for prob in validate_ledger(str(p)))


# -- cache: exact-match memo, ok-only --------------------------------------


def test_cache_hits_only_exact_params_and_budget():
    space = get_workload("quadratic").default_space()
    cache = EvalCache(space)
    params = {"lr": 0.1, "reg": 0.3}
    cache.put(params, _ok(0, 1.25, step=20))
    hit = cache.get({"lr": 0.1, "reg": 0.3}, budget=20, trial_id=7)
    assert hit is not None and hit.trial_id == 7 and hit.score == 1.25
    assert hit.extra["cache_hit"] is True
    assert cache.get({"lr": 0.1, "reg": 0.30000001}, 20, 8) is None
    assert cache.get(params, 40, 9) is None  # other budget: other computation
    # internal driver keys never change the identity
    assert cache.get({**params, "__inherit_from__": 3}, 20, 10) is not None


def test_cache_seed_from_duplicate_params_at_different_budgets():
    """The budget is part of the key: one point evaluated at two
    budgets (an ASHA trial at rungs 10 and 270) seeds TWO memo entries,
    and each budget's hit serves its own recorded score (ISSUE 14
    satellite: the both-keys-survive contract gets direct coverage)."""
    space = get_workload("quadratic").default_space()
    cache = EvalCache(space)
    params = space.canonical_params({"lr": 0.1, "reg": 0.3})
    assert (
        cache.seed_from(
            [
                {"status": "ok", "score": 0.4, "step": 10, "params": params},
                {"status": "ok", "score": 0.9, "step": 270, "params": params},
            ]
        )
        == 2
    )
    assert len(cache) == 2
    assert cache.get(params, 10, 1).score == pytest.approx(0.4)
    assert cache.get(params, 270, 2).score == pytest.approx(0.9)
    assert cache.get(params, 100, 3) is None  # un-seen budget: miss


def test_cache_never_caches_failures():
    space = get_workload("quadratic").default_space()
    cache = EvalCache(space)
    cache.put({"lr": 0.1, "reg": 0.3}, failed_result(0, step=20, error="x"))
    assert len(cache) == 0
    # and ledger-seeded caches skip non-ok records too
    assert (
        cache.seed_from(
            [{"status": "failed", "score": None, "step": 20, "params": {"lr": 0.1, "reg": 0.3}}]
        )
        == 0
    )


# -- replay-resume: the acceptance drill -----------------------------------

CHAOS = {"inner": "quadratic", "exc": 0.12, "nan": 0.08, "seed": 10}


def _search(workload, ledger=None, backend=None, algo=None, **kw):
    algo = algo or RandomSearch(workload.default_space(), seed=0, max_trials=20, budget=20)
    b = backend or SpyBackend(workload, n_workers=1, workload_kwargs=CHAOS)
    m = MetricsLogger()
    try:
        res = run_search(algo, b, metrics=m, ledger=ledger, **kw)
    finally:
        b.close()
    return algo, res, m, b


def test_chaos_killed_sweep_resumes_to_identical_trial_set(tmp_path):
    """Kill a chaos sweep mid-run; the ledger resume completes with the
    same completed-trial set as the uninterrupted run, replays rather
    than re-evaluates, and ends with a best no worse."""
    wl = get_workload("chaos", **CHAOS)

    whole_algo, whole_res, _, whole_b = _search(wl)
    whole_ids = {t.trial_id for t in whole_algo.trials.values()}

    led = SweepLedger(str(tmp_path / "sweep.jsonl"))
    led.ensure_header({"algorithm": "random", "seed": 0})
    crash_b = SpyBackend(wl, n_workers=1, workload_kwargs=CHAOS, die_after=8)
    with pytest.raises(KeyboardInterrupt):
        _search(wl, ledger=led, backend=crash_b)
    led.close()
    n_before = len(SweepLedger(led.path).records)
    assert 0 < n_before < 20  # died mid-sweep, after journaling some trials

    led2 = SweepLedger(led.path)
    led2.ensure_header({"algorithm": "random", "seed": 0})
    algo2, res2, m2, b2 = _search(wl, ledger=led2)
    led2.close()

    # identical completed set: nothing lost, nothing double-reported
    assert {t.trial_id for t in algo2.trials.values()} == whole_ids
    assert res2.n_replayed == n_before
    assert m2.replayed == n_before
    # journaled trials were never re-evaluated by the resumed backend
    assert not (set(b2.evaluated_ids) & set(crash_b.evaluated_ids))
    assert len(b2.evaluated_ids) == 20 - n_before
    # per-trial outcomes match the uninterrupted run exactly (chaos
    # faults are deterministic in params)
    for tid, t in whole_algo.trials.items():
        t2 = algo2.trials[tid]
        assert t2.status == t.status
        assert t2.score == t.score or (t.score is None and t2.score is None)
    assert res2.best.score == pytest.approx(whole_res.best.score, abs=1e-12)
    assert res2.best.trial_id == whole_res.best.trial_id


def test_replay_covers_final_failures_without_reevaluation(tmp_path):
    """FINAL failed records replay as failures: the algorithm sees the
    same FAILED reports, and the backend is not consulted for them."""
    wl = get_workload("chaos", **CHAOS)
    led = _ledger(tmp_path)
    algo1, res1, _, b1 = _search(wl, ledger=led)
    led.close()
    n_failed = sum(t.status == TrialStatus.FAILED for t in algo1.trials.values())
    assert n_failed > 0  # the chaos mix injected failures

    led2 = SweepLedger(led.path)
    algo2, res2, _, b2 = _search(wl, ledger=led2)
    led2.close()
    assert b2.evaluated_ids == []  # full replay, zero evaluations
    assert res2.n_replayed == 20 and res2.n_evals == 0
    assert (
        sum(t.status == TrialStatus.FAILED for t in algo2.trials.values()) == n_failed
    )


def test_replay_divergence_is_refused(tmp_path):
    """A ledger whose records no longer match the suggestion stream
    (here: a different algorithm seed) must refuse to replay, not
    silently report wrong params' scores."""
    wl = get_workload("quadratic")
    led = _ledger(tmp_path)
    _search(wl, ledger=led, backend=SpyBackend(wl, n_workers=1))
    led.close()
    led2 = SweepLedger(led.path)
    other = RandomSearch(wl.default_space(), seed=1, max_trials=20, budget=20)
    with pytest.raises(LedgerError, match="diverged at trial 0"):
        _search(wl, ledger=led2, algo=other, backend=SpyBackend(wl, n_workers=1))
    led2.close()


def test_cache_hit_skips_evaluate_and_is_journaled(tmp_path):
    """A re-suggested duplicate point is served from the cache: the
    backend never sees it, metrics count it, and the hit is journaled
    as a cached ok record."""
    wl = get_workload("quadratic")
    space = wl.default_space()
    led = _ledger(tmp_path)

    algo1, res1, _, _ = _search(wl, ledger=led, backend=SpyBackend(wl, n_workers=1))
    led.close()

    # same seed => the SAME params stream, but fresh trial ids (shifted
    # id space, as a second Hyperband-style bracket would allocate), so
    # replay-by-id misses and the exact-match cache is what must serve
    # every point
    led2 = SweepLedger(led.path)
    led2.ensure_header({"algorithm": "random", "seed": 0, "space_hash": "x"})
    algo2 = RandomSearch(space, seed=0, max_trials=20, budget=20)
    algo2._next_id = 1000
    b2 = SpyBackend(wl, n_workers=1)
    m2 = MetricsLogger()
    res2 = run_search(algo2, b2, metrics=m2, ledger=led2)
    b2.close()
    led2.close()
    assert b2.evaluated_ids == []
    assert res2.n_cache_hits == 20 and m2.cache_hits == 20
    assert res2.best.score == pytest.approx(res1.best.score, abs=1e-12)
    # the hits are journaled as this sweep's own (cached) records
    _, records, _ = read_ledger(led.path)
    cached = [r for r in records if r.get("cached")]
    assert len(cached) == 20 and all(r["attempts"] == 0 for r in cached)


# -- warm start ------------------------------------------------------------


def _prior_ledger(tmp_path, space, name="prior.jsonl"):
    """A finished prior sweep's ledger over ``space``."""
    wl = get_workload("quadratic")
    led = SweepLedger(str(tmp_path / name))
    led.ensure_header(
        {"algorithm": "random", "seed": 0, "space_hash": space.space_hash()}
    )
    algo = RandomSearch(space, seed=0, max_trials=12, budget=20)
    b = CPUBackend(wl, n_workers=1)
    res = run_search(algo, b, ledger=led)
    b.close()
    led.close()
    return led.path, res


def test_warm_start_seeds_random_with_prior_best(tmp_path):
    wl = get_workload("quadratic")
    space = wl.default_space()
    path, prior_res = _prior_ledger(tmp_path, space)

    algo = RandomSearch(space, seed=99, max_trials=4, budget=20)
    n = warm_start(algo, path)
    assert n == 1  # best() seeding: the prior's best point
    first = algo.next_batch(4)[0]
    assert first.params["lr"] == pytest.approx(prior_res.best.params["lr"], rel=1e-5)
    assert first.params["reg"] == pytest.approx(prior_res.best.params["reg"], rel=1e-5)


def test_warm_start_gives_tpe_priors_and_engages_surrogate(tmp_path):
    wl = get_workload("quadratic")
    space = wl.default_space()
    path, _ = _prior_ledger(tmp_path, space)

    cold = TPE(space, seed=3, max_trials=8, budget=20, n_startup=10)
    warm = TPE(space, seed=3, max_trials=8, budget=20, n_startup=10)
    n = warm_start(warm, path)
    assert n == 12  # every ok prior observation entered the ring
    assert warm._n_obs == 12 and warm._valid.sum() == 12
    # enough priors put the surrogate in charge from the FIRST batch:
    # the warm suggestions differ from the cold startup's uniform draws
    cold_batch = np.stack([t.unit for t in cold.next_batch(4)])
    warm_batch = np.stack([t.unit for t in warm.next_batch(4)])
    assert not np.allclose(cold_batch, warm_batch)
    # observations are facts, not trials: no ledger entries, no best()
    assert warm.n_trials == 4 and warm.best() is None


def test_warm_start_asha_seed_enters_lowest_rung(tmp_path):
    wl = get_workload("quadratic")
    space = wl.default_space()
    path, prior_res = _prior_ledger(tmp_path, space)
    algo = ASHA(space, seed=5, max_trials=6, min_budget=5, max_budget=45, eta=3)
    assert warm_start(algo, path) == 1
    first = algo.next_batch(3)[0]
    assert first.budget == algo.rungs[0]
    assert first.params["lr"] == pytest.approx(prior_res.best.params["lr"], rel=1e-5)


def test_warm_start_refuses_other_space(tmp_path):
    from mpi_opt_tpu.space import SearchSpace, Uniform

    wl = get_workload("quadratic")
    path, _ = _prior_ledger(tmp_path, wl.default_space())
    other = SearchSpace({"lr": Uniform(0.0, 1.0), "reg": Uniform(0.0, 1.0)})
    algo = RandomSearch(other, seed=0, max_trials=4)
    with pytest.raises(LedgerError, match="space hash"):
        warm_start(algo, path)


def test_warm_start_counts_undecodable_choice_as_skip(tmp_path):
    """A hash-matched ledger holding one record whose Choice value no
    live option canonicalizes to loses THAT record (counted in skips)
    instead of refusing the whole prior (ISSUE 14 satellite)."""
    from mpi_opt_tpu.ledger.warmstart import load_observations
    from mpi_opt_tpu.space import Choice, SearchSpace, Uniform

    space = SearchSpace({"k": Choice(["a", "b"]), "u": Uniform(0.0, 1.0)})
    led = SweepLedger(str(tmp_path / "prior.jsonl"))
    led.ensure_header({"space_hash": space.space_hash()})
    led.record_trial(_ok(0, 1.0), space.canonical_params({"k": "a", "u": 0.5}))
    led.record_trial(_ok(1, 2.0), {"k": "zzz", "u": 0.5})  # no such option
    led.record_trial(failed_result(2, step=20, error="boom"), {"k": "b", "u": 0.1})
    led.close()
    obs, skips = load_observations(led.path, space)
    assert len(obs) == 1 and obs[0].score == 1.0
    assert skips == {"not_ok": 1, "bad_choice": 1}


def test_best_observation_nonfinite_guard():
    """Non-finite priors never seed a sweep: NaN cannot win (x > nan is
    False), +inf must not win, and an all-diverged prior seeds nothing
    (ISSUE 14 satellite: the guard gets direct coverage)."""
    from mpi_opt_tpu.ledger.warmstart import best_observation

    unit = np.zeros(2, dtype=np.float32)
    mixed = [
        Observation(unit=unit, score=float("nan")),
        Observation(unit=unit, score=0.7),
        Observation(unit=unit, score=float("inf")),
        Observation(unit=unit, score=0.9),
        Observation(unit=unit, score=float("-inf")),
    ]
    assert best_observation(mixed).score == pytest.approx(0.9)
    diverged = [
        Observation(unit=unit, score=float("nan")),
        Observation(unit=unit, score=float("inf")),
    ]
    assert best_observation(diverged) is None
    assert best_observation([]) is None


# -- space identity --------------------------------------------------------


def test_space_hash_and_canonical_params():
    from mpi_opt_tpu.space import Choice, LogUniform, SearchSpace, Uniform

    s1 = SearchSpace({"lr": LogUniform(1e-3, 4.0), "reg": Uniform(0.0, 1.0)})
    s2 = SearchSpace({"lr": LogUniform(1e-3, 4.0), "reg": Uniform(0.0, 1.0)})
    s3 = SearchSpace({"lr": LogUniform(1e-3, 2.0), "reg": Uniform(0.0, 1.0)})
    assert s1.space_hash() == s2.space_hash()
    assert s1.space_hash() != s3.space_hash()

    # canonicalization drops internal keys, orders by dimension, and is
    # stable across a JSON round trip (the replay verification relies
    # on byte-equality of params_key)
    p = {"reg": 0.3, "lr": np.float32(0.25), "__inherit_from__": 2}
    canon = s1.canonical_params(p)
    assert list(canon) == ["lr", "reg"]
    assert s1.params_key(json.loads(json.dumps(canon))) == s1.params_key(p)
    with pytest.raises(KeyError, match="missing"):
        s1.canonical_params({"lr": 0.1})

    sc = SearchSpace({"c": Choice([True, False]), "u": Uniform(0, 1)})
    assert sc.params_key({"c": True, "u": 0.5}) == sc.params_key(
        json.loads(json.dumps(sc.canonical_params({"c": True, "u": 0.5})))
    )


# -- observation contract --------------------------------------------------


def test_ingest_never_seeds_nonfinite_points():
    space = get_workload("quadratic").default_space()
    algo = RandomSearch(space, seed=0, max_trials=4)
    obs = [
        Observation(unit=np.array([0.9, 0.9], np.float32), score=float("nan")),
        Observation(unit=np.array([0.1, 0.2], np.float32), score=1.0),
    ]
    assert algo.ingest_observations(obs) == 1
    np.testing.assert_allclose(algo._seed_units[0], [0.1, 0.2])


def test_base_algorithm_default_ingests_nothing():
    from mpi_opt_tpu.algorithms import PBT

    space = get_workload("quadratic").default_space()
    algo = PBT(space, seed=0, population=4, generations=2, steps_per_generation=1)
    assert algo.ingest_observations([Observation(np.zeros(2, np.float32), 1.0)]) == 0


# -- rank-0-only journaling (multi-process SPMD; read-only ledgers) --------


def test_read_only_ledger_never_touches_the_file(tmp_path):
    """Non-zero SPMD ranks open the SHARED journal read-only: full
    in-memory bookkeeping (header verification, completed() replay,
    record_trial views stay rank-identical) with zero file writes — N
    ranks fsync-appending one journal would interleave records and
    corrupt the stream."""
    path = str(tmp_path / "sweep.jsonl")
    led = SweepLedger(path)
    led.ensure_header({"algorithm": "random", "seed": 0, "space_hash": "x"})
    led.record_trial(TrialResult(trial_id=0, score=1.0, step=5), {"lr": 1.0})
    led.close()
    before = open(path).read()

    ro = SweepLedger(path, read_only=True)
    assert ro.read_only
    ro.ensure_header({"algorithm": "random", "seed": 0, "space_hash": "x"})
    assert 0 in ro.completed()  # replay view works
    rec = ro.record_trial(TrialResult(trial_id=1, score=2.0, step=5), {"lr": 2.0})
    assert rec["trial_id"] == 1 and 1 in ro.completed()  # in-memory only
    ro.close()
    assert open(path).read() == before  # not a byte written

    # config drift is refused on read-only ranks too (parity with rank 0)
    ro2 = SweepLedger(path, read_only=True)
    with pytest.raises(LedgerError, match="different sweep"):
        ro2.ensure_header({"algorithm": "tpe", "seed": 0, "space_hash": "x"})
    ro2.close()


def test_read_only_ledger_fresh_path_creates_nothing(tmp_path):
    """A non-zero rank starting a FRESH sweep must not create the file
    either — rank 0 owns the header; the rank keeps an in-memory header
    so its own bookkeeping (record_trial) still functions."""
    path = str(tmp_path / "fresh.jsonl")
    ro = SweepLedger(path, read_only=True)
    ro.ensure_header({"algorithm": "random", "seed": 0, "space_hash": "x"})
    ro.record_trial(TrialResult(trial_id=0, score=1.0, step=5), {"lr": 1.0})
    assert ro.completed() == {0: ro.records[0]}
    ro.close()
    assert not os.path.exists(path)


def test_replay_consistency_cross_check(tmp_path):
    """fsck's ledger cross-check: every trial a snapshot's search state
    records as final must hold a journal record (the driver fsyncs the
    record BEFORE reporting to the algorithm, so the journal can never
    lag a snapshot); a missing final means the pair is torn."""
    from mpi_opt_tpu.ledger.report import replay_consistency
    from mpi_opt_tpu.ledger.store import SweepLedger
    from mpi_opt_tpu.trial import TrialResult

    led = str(tmp_path / "sweep.jsonl")
    with SweepLedger(led) as lg:
        lg.ensure_header({"algorithm": "random", "seed": 0})
        for tid in (0, 1, 2):
            lg.record_trial(
                TrialResult(trial_id=tid, score=0.5, step=1),
                {"lr": 0.1},
            )
    search = {
        "algorithm": {
            "trials": [
                {"trial_id": 0, "status": "done"},
                {"trial_id": 1, "status": "failed"},
                {"trial_id": 3, "status": "running"},  # in-flight: exempt
            ]
        }
    }
    assert replay_consistency(led, search) == []
    # a snapshot final with no journal record is flagged
    search["algorithm"]["trials"].append({"trial_id": 7, "status": "done"})
    problems = replay_consistency(led, search)
    assert len(problems) == 1 and "7" in problems[0]
    # unreadable journal degrades to a problem string, not a crash
    assert replay_consistency(str(tmp_path / "nope.jsonl"), search)


def test_multi_fidelity_journal_validates_and_replays_every_rung(tmp_path):
    """ASHA evaluates a promoted trial once per rung: the journal holds
    one final record per (trial, step), `report --validate` accepts it,
    and a resume is served EACH rung's own record — never a trial's top
    rung as its first (found by chip_smoke's driver tier, PR 21)."""
    from mpi_opt_tpu.algorithms import ASHA
    from mpi_opt_tpu.ledger.store import validate_ledger

    wl = get_workload("quadratic")

    def asha():
        return ASHA(
            wl.default_space(), seed=0, max_trials=8, min_budget=10, max_budget=270, eta=3
        )

    led = _ledger(tmp_path)
    algo1, res1, _, b1 = _search(wl, ledger=led, algo=asha(), backend=SpyBackend(wl, n_workers=1))
    led.close()
    assert validate_ledger(led.path) == []
    records = SweepLedger(led.path).records
    evaluations = {(r["trial_id"], r["step"]) for r in records}
    assert len(evaluations) == len(records) > 8  # promotions journaled per rung
    assert len({r["trial_id"] for r in records}) == 8

    led2 = SweepLedger(led.path)
    algo2, res2, _, b2 = _search(wl, ledger=led2, algo=asha(), backend=SpyBackend(wl, n_workers=1))
    led2.close()
    assert b2.evaluated_ids == []  # every rung replayed, none re-evaluated
    assert res2.n_replayed == len(records) and res2.n_evals == 0
    assert res2.best.params == res1.best.params and res2.best.score == res1.best.score
    assert len(SweepLedger(led.path).records) == len(records)  # nothing re-journaled
    assert validate_ledger(led.path) == []
