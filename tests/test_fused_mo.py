"""Fused multi-objective sweeps end to end (ISSUE 17): journaled
objective vectors, scalar-ledger back-compat, crash→resume record
identity, resume verification of vectors, report ``--best-under``, and
the snapshot-config gate between scalar and MO resumes.

The headline invariants:
- an MO fused sweep journals one raw ``scores`` vector beside the
  scalarized ``score`` per member record, validating clean under the
  same schema v1;
- a SCALAR fused sweep's ledger carries NO ``scores``/``objective_spec``
  key anywhere — pre-17 consumers see byte-identical output;
- a sweep killed mid-run resumes to the record-identical journal of an
  unkilled run, vectors included;
- a resumed boundary whose recomputed vector diverges from the journal
  refuses (LedgerError), same as the scalar path;
- ``report --best-under`` answers typed (feasible / least_violation),
  and refuses unknown objectives, contradictory operators, and scalar
  ledgers.
"""

import json

import numpy as np
import pytest

import mpi_opt_tpu.train.fused_asha as fa
import mpi_opt_tpu.train.fused_pbt as fp
from mpi_opt_tpu.ledger import (
    FusedJournal,
    LedgerError,
    SweepLedger,
    validate_ledger,
)
from mpi_opt_tpu.ledger.report import summarize_ledger
from mpi_opt_tpu.objectives import ObjectiveSpec
SPEC = ObjectiveSpec.parse("accuracy:max,params:min")
KW = dict(population=6, generations=3, steps_per_gen=4, seed=3, gen_chunk=1)


@pytest.fixture(scope="module")
def wl(shared_workload):
    return shared_workload("digits_mlp")


def _mo_ledger(path, space, algorithm="pbt", spec=SPEC):
    led = SweepLedger(str(path))
    led.ensure_header(
        {
            "mode": "fused",
            "granularity": "generation",
            "algorithm": algorithm,
            "seed": KW["seed"],
            "space_hash": space.space_hash(),
            "objectives": "accuracy:max,params:min",
        },
        objective_spec=spec.spec(),
    )
    return led


def _records(path):
    return [json.loads(l) for l in open(path).read().splitlines()[1:]]


@pytest.fixture(scope="module")
def mo_sweep(wl, tmp_path_factory):
    """(result, ledger) of the undisturbed multi-objective sweep, run and
    journaled once a module; the tests read both and write into neither."""
    led = _mo_ledger(tmp_path_factory.mktemp("mo_sweep") / "mo.jsonl", wl.default_space())
    res = fp.fused_pbt(wl, ledger=led, objectives=SPEC, **KW)
    led.close()
    return res, led


def test_mo_pbt_journals_vectors_and_scalarized_score(mo_sweep):
    res, led = mo_sweep

    assert validate_ledger(led.path) == []
    recs = _records(led.path)
    assert len(recs) == KW["population"] * KW["generations"]
    for r in recs:
        if r["status"] != "ok":
            continue
        assert isinstance(r["scores"], list) and len(r["scores"]) == SPEC.m
        # score IS the scalarized primary (accuracy:max → identity)
        assert r["score"] == pytest.approx(r["scores"][0])
        assert all(np.isfinite(v) for v in r["scores"])

    # the spec rides the header top-level beside space_spec, durable
    header = json.loads(open(led.path).readline())
    assert ObjectiveSpec.from_spec(header["objective_spec"]) == SPEC
    assert "space_spec" not in header["config"]  # both are metadata keys

    # the result carries the typed Pareto block
    assert res["objectives"] == ["accuracy", "params"]
    p = res["pareto"]
    assert p["front_size"] == len(p["front_members"]) >= 1
    assert p["selection"] == "feasible"  # unconstrained spec: always
    assert p["hypervolume"] >= 0.0
    assert len(p["front_scores"]) == p["front_size"]

    # report recomputes the same front from the journaled vectors
    rep = summarize_ledger(led.path)
    mo = rep["multi_objective"]
    assert [o["name"] for o in mo["objectives"]] == ["accuracy", "params"]
    assert mo["evaluated"] == KW["population"]  # end-state: one row/member
    assert mo["front_size"] >= 1
    assert mo["hypervolume"] == pytest.approx(p["hypervolume"])


def test_scalar_fused_ledger_carries_no_mo_keys(tmp_path, wl):
    """Back-compat floor: a scalar sweep's ledger must be EXACTLY what
    pre-17 binaries wrote — no ``scores`` key in any record, no
    ``objective_spec`` in the header, no MO block in the report."""
    space = wl.default_space()
    led = SweepLedger(str(tmp_path / "scalar.jsonl"))
    led.ensure_header(
        {
            "mode": "fused",
            "granularity": "generation",
            "algorithm": "pbt",
            "seed": KW["seed"],
            "space_hash": space.space_hash(),
        }
    )
    res = fp.fused_pbt(wl, ledger=led, **KW)
    led.close()

    header = json.loads(open(led.path).readline())
    assert "objective_spec" not in header
    assert "objectives" not in header["config"]
    for r in _records(led.path):
        assert "scores" not in r
    assert res["objectives"] is None and res["pareto"] is None
    assert summarize_ledger(led.path)["multi_objective"] is None
    with pytest.raises(LedgerError, match="multi-objective"):
        summarize_ledger(led.path, best_under="params<=100")


def test_mo_crash_resume_record_identical(tmp_path, wl, mo_sweep):
    """Acceptance drill: kill an MO sweep mid-run, ``--resume`` it, and
    the journal — vectors included — is record-identical to an unkilled
    run's."""
    space = wl.default_space()
    _, clean = mo_sweep

    real = fp.run_fused_pbt
    calls = {"n": 0}

    def crashing(*a, **k):
        calls["n"] += 1
        if calls["n"] == 3:  # die after 2 completed launches
            raise RuntimeError("simulated TPU worker crash")
        return real(*a, **k)

    ckpt = str(tmp_path / "ck")
    led = _mo_ledger(tmp_path / "killed.jsonl", space)
    import unittest.mock as mock

    with mock.patch.object(fp, "run_fused_pbt", crashing):
        with pytest.raises(RuntimeError, match="simulated"):
            fp.fused_pbt(wl, checkpoint_dir=ckpt, ledger=led, objectives=SPEC, **KW)
    led.close()

    led = _mo_ledger(tmp_path / "killed.jsonl", space)
    resumed = fp.fused_pbt(
        wl, checkpoint_dir=ckpt, ledger=led, objectives=SPEC, **KW
    )
    led.close()

    assert validate_ledger(led.path) == []

    def durable(path):
        # project away per-run identity (sweep_id, wall-clock): every
        # FACT of the sweep — vectors included — must be identical
        keys = ("trial_id", "member", "boundary", "boundary_size", "params",
                "status", "score", "scores", "step")
        return [
            {k: r.get(k) for k in keys} for r in _records(path)
        ]

    assert durable(led.path) == durable(clean.path)
    # the resumed result's front matches the clean run's
    whole = summarize_ledger(clean.path)["multi_objective"]
    again = summarize_ledger(led.path)["multi_objective"]
    assert again == whole
    assert resumed["pareto"]["selection"] == "feasible"


def test_resume_verify_catches_diverged_vector(tmp_path, wl):
    """A re-computed boundary whose scalar scores match but whose
    objective VECTOR diverges is a different trajectory — refused."""
    space = wl.default_space()
    led = _mo_ledger(tmp_path / "v.jsonl", space)
    j = FusedJournal(led, space)
    rng = np.random.default_rng(0)
    u = rng.random((3, space.dim), dtype=np.float32)
    scores = np.array([0.5, 0.6, 0.7])
    mo = np.array([[0.5, 100.0], [0.6, 200.0], [0.7, 300.0]])
    j.record_boundary(0, [0, 1, 2], u, scores, step=5, scores_mo=mo)
    led.close()

    led2 = SweepLedger(led.path)
    j2 = FusedJournal(led2, space)
    # identical recomputation verifies (no rewrite)
    j2.record_boundary(0, [0, 1, 2], u, scores, step=5, scores_mo=mo)
    assert j2.written == 0 and j2.verified == 3
    bad = mo.copy()
    bad[1, 1] = 999.0
    with pytest.raises(LedgerError, match="diverges"):
        j2.record_boundary(0, [0, 1, 2], u, scores, step=5, scores_mo=bad)
    led2.close()


def test_report_best_under_typed_answers(mo_sweep):
    _, led = mo_sweep

    # a satisfiable bound answers feasible with a concrete winner
    mo = summarize_ledger(led.path)["multi_objective"]
    loosest = max(r["scores"][1] for r in mo["front"])
    rep = summarize_ledger(led.path, best_under=f"params<={loosest * 10}")
    bu = rep["multi_objective"]["best_under"]
    assert bu["kind"] == "feasible" and bu["trial_id"] is not None
    assert bu["scores"][1] <= loosest * 10

    # an unsatisfiable bound DEGRADES (typed), never crashes
    rep = summarize_ledger(led.path, best_under="params<=0.5")
    bu = rep["multi_objective"]["best_under"]
    assert bu["kind"] == "least_violation"
    assert bu["violation"] > 0 and bu["trial_id"] is not None

    # unknown objective and contradictory operator are typed refusals
    with pytest.raises(LedgerError, match="names 'bogus'"):
        summarize_ledger(led.path, best_under="bogus<=1")
    with pytest.raises(LedgerError, match="must use '>='"):
        summarize_ledger(led.path, best_under="accuracy<=0.5")


def test_mo_snapshot_refuses_scalar_resume(tmp_path, wl):
    """The checkpoint config carries the objectives spec ONLY on MO
    sweeps, so an MO snapshot refuses a scalar resume (and vice versa)
    instead of silently continuing under a different selection rule."""
    ckpt = str(tmp_path / "ck")
    fp.fused_pbt(wl, checkpoint_dir=ckpt, objectives=SPEC, **KW)
    with pytest.raises(ValueError, match="mismatch"):
        fp.fused_pbt(wl, checkpoint_dir=ckpt, **KW)


def test_mo_sha_journals_vectors(tmp_path, wl):
    space = wl.default_space()
    led = _mo_ledger(tmp_path / "sha.jsonl", space, algorithm="asha")
    res = fa.fused_sha(
        wl,
        n_trials=6,
        min_budget=2,
        max_budget=8,
        eta=2,
        seed=3,
        ledger=led,
        objectives=SPEC,
    )
    led.close()

    assert validate_ledger(led.path) == []
    recs = _records(led.path)
    assert len(recs) == 6 + 3 + 2  # rung sizes under eta=2
    for r in recs:
        if r["status"] == "ok":
            assert len(r["scores"]) == SPEC.m
            assert r["score"] == pytest.approx(r["scores"][0])
    assert res["objectives"] == ["accuracy", "params"]
    assert res["pareto"]["front_size"] >= 1
    assert summarize_ledger(led.path)["multi_objective"]["front_size"] >= 1


# -- scores drift gates (satellite 3) -------------------------------------


def _write_ledger(path, header, records):
    with open(path, "w") as f:
        f.write(json.dumps(header) + "\n")
        for r in records:
            f.write(json.dumps(r) + "\n")


def _rec(space, **over):
    base = {
        "v": 1,
        "kind": "trial",
        "trial_id": 0,
        "status": "ok",
        "params": {"lr": 0.01, "momentum": 0.5, "weight_decay": 1e-4},
        "score": 0.5,
        "step": 5,
        "seed": 0,
    }
    base.update(over)
    return base


def test_validate_flags_mistyped_scores_and_accepts_absent(tmp_path, wl):
    """The drift gate for the OPTIONAL ``scores`` field: absent is valid
    forever (that is the whole scalar history); present-but-mistyped is
    flagged, and an ok record may not carry a null objective entry."""
    space = wl.default_space()
    header = {
        "v": 1,
        "kind": "header",
        "config": {"space_hash": space.space_hash()},
    }
    path = str(tmp_path / "drift.jsonl")

    # absent scores: valid forever
    _write_ledger(path, header, [_rec(space)])
    assert validate_ledger(path) == []

    # well-typed vector (null allowed on a failed record): valid
    _write_ledger(
        path,
        header,
        [
            _rec(space, scores=[0.5, 120.0]),
            _rec(space, trial_id=1, status="failed", score=None, scores=None),
        ],
    )
    assert validate_ledger(path) == []

    # mistyped shapes are each flagged
    for bad, match in [
        (_rec(space, scores=[]), "non-empty"),
        (_rec(space, scores="0.5"), "non-empty"),
        (_rec(space, scores=[0.5, "fast"]), "non-numeric"),
        (_rec(space, scores=[0.5, True]), "non-numeric"),
        (_rec(space, scores=[0.5, None]), "null objective"),
    ]:
        _write_ledger(path, header, [bad])
        problems = validate_ledger(path)
        assert problems and match in problems[0], (bad["scores"], problems)
