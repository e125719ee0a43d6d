"""Fused-path ledger durability (ledger/fused.py): member-granular
boundary journaling, torn-boundary recovery, resume verification, and
cross-mode warm-start.

The headline invariants under test:
- one journaled record per member per boundary, same schema v1 the
  driver path writes, validating clean;
- the only append-kill damage shape (a torn FINAL boundary) is flagged
  by strict validation and self-healed on load; every OTHER boundary
  damage refuses to load;
- a re-computed boundary VERIFIES against its records (divergence =
  LedgerError) and a journal lagging its snapshot is refused;
- fused records warm-start driver algorithms and vice versa — the only
  gate is the space hash.
"""

import json
import os

import numpy as np
import pytest

from mpi_opt_tpu.ledger import (
    FusedJournal,
    LedgerError,
    SweepLedger,
    scan_boundaries,
    validate_ledger,
)
from mpi_opt_tpu.ledger.report import (
    fused_replay_consistency,
    summarize_ledger,
)
from mpi_opt_tpu.ledger.warmstart import best_observation, load_observations


@pytest.fixture(scope="module")
def space(shared_workload):
    return shared_workload("fashion_mlp", n_train=64, n_val=32).default_space()


def _fused_ledger(tmp_path, space, name="fused.jsonl"):
    led = SweepLedger(str(tmp_path / name))
    led.ensure_header(
        {
            "mode": "fused",
            "granularity": "generation",
            "algorithm": "pbt",
            "seed": 0,
            "space_hash": space.space_hash(),
        }
    )
    return led


def _units(n, space, seed=0):
    rng = np.random.default_rng(seed)
    return rng.random((n, space.dim), dtype=np.float32)


def test_record_boundary_journals_one_record_per_member(tmp_path, space):
    led = _fused_ledger(tmp_path, space)
    j = FusedJournal(led, space)
    u = _units(3, space)
    j.record_boundary(0, [0, 1, 2], u, [0.5, float("nan"), 0.7], step=5)
    j.record_boundary(1, [0, 1, 2], u, [0.6, 0.8, 0.9], step=10)
    led.close()
    assert j.written == 6
    assert validate_ledger(led.path) == []
    recs = [json.loads(l) for l in open(led.path).read().splitlines()[1:]]
    assert [r["trial_id"] for r in recs] == list(range(6))
    assert [r["boundary"] for r in recs] == [0, 0, 0, 1, 1, 1]
    assert all(r["boundary_size"] == 3 for r in recs)
    # non-finite member score -> failed with null score (strict JSON)
    nan_rec = recs[1]
    assert nan_rec["status"] == "failed" and nan_rec["score"] is None
    # canonical params decode back through the space (cross-mode edge)
    assert set(recs[0]["params"]) == set(space.names)


def test_resume_verifies_instead_of_rewriting(tmp_path, space):
    led = _fused_ledger(tmp_path, space)
    j = FusedJournal(led, space)
    u = _units(3, space)
    scores = np.array([0.5, 0.6, 0.7])
    j.record_boundary(0, [0, 1, 2], u, scores, step=5)
    led.close()

    led2 = SweepLedger(led.path)
    j2 = FusedJournal(led2, space)
    assert j2.complete_prefix() == 1
    j2.record_boundary(0, [0, 1, 2], u, scores, step=5)
    assert j2.written == 0 and j2.verified == 3
    # the file did not grow: verification never re-appends
    assert len(led2.records) == 3
    with pytest.raises(LedgerError, match="diverges"):
        j2.record_boundary(0, [0, 1, 2], u, scores + 0.5, step=5)
    led2.close()


def test_status_divergence_is_refused(tmp_path, space):
    led = _fused_ledger(tmp_path, space)
    j = FusedJournal(led, space)
    u = _units(2, space)
    j.record_boundary(0, [0, 1], u, [0.5, 0.6], step=5)
    with pytest.raises(LedgerError, match="status"):
        j.record_boundary(0, [0, 1], u, [0.5, float("nan")], step=5)
    led.close()


def test_torn_final_boundary_flagged_then_healed(tmp_path, space):
    led = _fused_ledger(tmp_path, space)
    j = FusedJournal(led, space)
    u = _units(3, space)
    j.record_boundary(0, [0, 1, 2], u, [0.1, 0.2, 0.3], step=5)
    j.record_boundary(1, [0, 1, 2], u, [0.4, 0.5, 0.6], step=10)
    led.close()
    # the mid-journal-kill shape: drop the final boundary's last record
    lines = open(led.path).read().splitlines()
    open(led.path, "w").write("\n".join(lines[:-1]) + "\n")

    problems = validate_ledger(led.path)
    assert any("torn" in p and "boundary 1" in p for p in problems)

    led2 = SweepLedger(led.path)  # load self-heals: partial boundary dropped
    assert led2.n_torn_boundary == 2
    j2 = FusedJournal(led2, space)
    assert j2.complete_prefix() == 1
    j2.record_boundary(1, [0, 1, 2], u, [0.4, 0.5, 0.6], step=10)
    led2.close()
    assert validate_ledger(led.path) == []
    # the healed + re-journaled file is record-identical to the original
    recs = [json.loads(l) for l in open(led.path).read().splitlines()[1:]]
    assert [r["trial_id"] for r in recs] == list(range(6))


def test_midfile_partial_boundary_refuses_to_load(tmp_path, space):
    led = _fused_ledger(tmp_path, space)
    j = FusedJournal(led, space)
    u = _units(2, space)
    j.record_boundary(0, [0, 1], u, [0.1, 0.2], step=5)
    j.record_boundary(1, [0, 1], u, [0.3, 0.4], step=10)
    led.close()
    # delete a MID-FILE record (boundary 0's second member): not an
    # append-crash shape — must refuse, never silently truncate
    lines = open(led.path).read().splitlines()
    del lines[2]
    open(led.path, "w").write("\n".join(lines) + "\n")
    with pytest.raises(LedgerError, match="damaged beyond"):
        SweepLedger(led.path)
    assert validate_ledger(led.path)  # strict mode flags it too


def test_journal_lagging_snapshot_is_refused(tmp_path, space):
    led = _fused_ledger(tmp_path, space)
    j = FusedJournal(led, space)
    j.record_boundary(0, [0, 1], _units(2, space), [0.1, 0.2], step=5)
    # a snapshot claiming 2 boundaries complete is AHEAD of the journal
    with pytest.raises(LedgerError, match="lags the snapshot"):
        j.require_prefix(2)
    j.require_prefix(1)  # the journaled prefix passes
    led.close()
    assert fused_replay_consistency(led.path, 1) == []
    assert fused_replay_consistency(led.path, 2)


def test_scan_boundaries_structural_problems():
    def rec(b, m, size=2, tid=0):
        return {
            "kind": "trial", "trial_id": tid, "member": m, "boundary": b,
            "boundary_size": size, "params": {}, "status": "ok",
            "score": 0.5, "step": 1,
        }

    # duplicate member
    _by, _sz, probs, _t = scan_boundaries([rec(0, 0), rec(0, 0, tid=1)])
    assert any("twice" in p for p in probs)
    # inconsistent declared size
    _by, _sz, probs, _t = scan_boundaries([rec(0, 0), rec(0, 1, size=3, tid=1)])
    assert any("inconsistent" in p for p in probs)
    # non-contiguous boundary blocks
    _by, _sz, probs, _t = scan_boundaries(
        [rec(0, 0, size=1), rec(1, 0, size=2, tid=1), rec(0, 1, size=1, tid=2)]
    )
    assert any("out of order" in p or "non-contiguous" in p for p in probs)
    # index gap
    _by, _sz, probs, _t = scan_boundaries([rec(0, 0, size=1), rec(2, 0, size=1, tid=1)])
    assert any("contiguous range" in p for p in probs)
    # driver record mixed into a fused journal
    _by, _sz, probs, _t = scan_boundaries(
        [rec(0, 0, size=1), {"kind": "trial", "trial_id": 9, "params": {},
                             "status": "ok", "score": 1.0, "step": 1}]
    )
    assert any("mixed" in p for p in probs)


def test_bracket_offsets_compose_one_contiguous_journal(tmp_path, space):
    """Hyperband-style composite: two bracket views over ONE ledger,
    placed by boundary/trial/member offsets, read back as a single
    contiguous boundary sequence."""
    led = _fused_ledger(tmp_path, space)
    u = _units(4, space)
    j0 = FusedJournal(led, space)  # bracket 0: 2 rungs, 4->2 trials
    j0.record_boundary(0, [0, 1, 2, 3], u, [0.1, 0.2, 0.3, 0.4], step=3)
    j0.record_boundary(1, [2, 3], u[:2], [0.5, 0.6], step=9)
    j1 = FusedJournal(led, space, boundary_offset=2, trial_offset=6, member_offset=4)
    j1.record_boundary(0, [0, 1], u[:2], [0.7, 0.8], step=9)  # bracket 1
    led.close()
    assert validate_ledger(led.path) == []
    recs = [json.loads(l) for l in open(led.path).read().splitlines()[1:]]
    assert [r["boundary"] for r in recs] == [0, 0, 0, 0, 1, 1, 2, 2]
    assert [r["trial_id"] for r in recs] == list(range(8))
    assert [r["member"] for r in recs] == [0, 1, 2, 3, 2, 3, 4, 5]
    # a fresh composite view sees the whole prefix
    led2 = SweepLedger(led.path, read_only=True)
    assert FusedJournal(led2, space).complete_prefix() == 3


def test_fused_report_renders_boundary_view(tmp_path, space):
    led = _fused_ledger(tmp_path, space)
    j = FusedJournal(led, space)
    u = _units(3, space)
    j.record_boundary(0, [0, 1, 2], u, [0.5, float("nan"), 0.7], step=5)
    led.close()
    rep = summarize_ledger(led.path)
    assert rep["fused"]["granularity"] == "generation"
    assert rep["fused"]["boundaries"] == 1
    assert rep["fused"]["member_records"] == 3
    assert rep["fused"]["member_failures"] == [1]
    assert rep["by_status"]["ok"] == 2 and rep["by_status"]["failed"] == 1


def test_cross_mode_warm_start_fused_to_driver(tmp_path, space):
    """A fused ledger's member records load as driver observations: the
    acceptance direction (fused ledger seeds a driver TPE sweep)."""
    from mpi_opt_tpu.algorithms.tpe import TPE

    led = _fused_ledger(tmp_path, space)
    j = FusedJournal(led, space)
    u = _units(3, space)
    j.record_boundary(0, [0, 1, 2], u, [0.5, float("nan"), 0.7], step=5)
    led.close()
    obs, skips = load_observations(led.path, space)
    assert len(obs) == 2  # failed member never becomes an observation
    assert skips == {"not_ok": 1}  # ...and the loss is COUNTED, not silent
    assert best_observation(obs).score == pytest.approx(0.7)
    # params round-trip: the best observation's unit decodes back to
    # (approximately) the journaled member's unit row
    np.testing.assert_allclose(obs[-1].unit, u[2], atol=1e-5)
    algo = TPE(space, seed=0, max_trials=4, budget=5)
    assert algo.ingest_observations(obs) == 2


def test_cross_mode_warm_start_refused_only_on_space_hash(tmp_path, space):
    """The reverse direction's ONLY gate is the space hash — a forged
    hash refuses, a matching fused/driver header never does."""
    led = _fused_ledger(tmp_path, space)
    FusedJournal(led, space).record_boundary(
        0, [0], _units(1, space), [0.5], step=5
    )
    led.close()
    assert len(load_observations(led.path, space)[0]) == 1  # mode never refuses
    # forge a different space hash into the header
    lines = open(led.path).read().splitlines()
    hdr = json.loads(lines[0])
    hdr["config"]["space_hash"] = "deadbeefdeadbeef"
    open(led.path, "w").write("\n".join([json.dumps(hdr)] + lines[1:]) + "\n")
    with pytest.raises(LedgerError, match="space hash"):
        load_observations(led.path, space)


def test_driver_records_before_fused_also_flagged_as_mixed():
    driver = {"kind": "trial", "trial_id": 0, "params": {}, "status": "ok",
              "score": 1.0, "step": 1}
    fused = {"kind": "trial", "trial_id": 1, "member": 0, "boundary": 0,
             "boundary_size": 1, "params": {}, "status": "ok", "score": 0.5,
             "step": 1}
    # both interleavings of a mixed file are refused, not just one
    for order in ([driver, fused], [fused, driver]):
        _by, _sz, probs, _t = scan_boundaries(order)
        assert any("mixed" in p for p in probs), order


def test_open_ledger_reentry_heals_partial_boundary(tmp_path, space):
    """The in-process --retries shape: an error escapes mid-boundary
    (k of N member records appended), then a fused driver re-enters
    with the SAME open ledger object. The fresh FusedJournal must heal
    the partial boundary (memory AND file) and re-journal it — not
    misdiagnose a sweep-shape divergence."""
    led = _fused_ledger(tmp_path, space)
    u = _units(3, space)
    j = FusedJournal(led, space)
    j.record_boundary(0, [0, 1, 2], u, [0.1, 0.2, 0.3], step=5)
    # simulate the escaped-mid-boundary state: 1 of 3 records appended
    led.record_member(trial_id=3, member=0, boundary=1, boundary_size=3,
                      canonical_params={}, score=0.4, step=10)

    j2 = FusedJournal(led, space)  # the retry's fresh view, same object
    assert led.n_torn_boundary == 1
    assert j2.complete_prefix() == 1
    j2.record_boundary(1, [0, 1, 2], u, [0.4, 0.5, 0.6], step=10)
    led.close()
    assert validate_ledger(led.path) == []
    recs = [json.loads(l) for l in open(led.path).read().splitlines()[1:]]
    assert [r["trial_id"] for r in recs] == list(range(6))


# -- one block a boundary: one fsync, the per-record path's lines ----------


def _decode_per_element(space, row):
    """The per-record decode the block replaced: one scalar
    ``from_unit`` a dimension (the values ``params_key`` has always
    keyed), kept here as the reference ``materialize_rows`` must match."""
    import jax.numpy as jnp

    from mpi_opt_tpu.utils.hostdev import host_ops

    with host_ops():
        return {
            name: dom.materialize(np.asarray(dom.from_unit(jnp.asarray(row[i]))))
            for i, (name, dom) in enumerate(space.domains.items())
        }


def _count_fsyncs(monkeypatch):
    fsyncs = []
    real_fsync = os.fsync
    monkeypatch.setattr(os, "fsync", lambda fd: (fsyncs.append(fd), real_fsync(fd)))
    return fsyncs


def _lines(path):
    return [json.loads(l) for l in open(path).read().splitlines()[1:]]


@pytest.mark.parametrize("n", [1, 32, 512])
def test_boundary_is_one_block_with_one_fsync(tmp_path, space, monkeypatch, n):
    from mpi_opt_tpu.obs import trace
    from mpi_opt_tpu.obs.report import load_stream
    from mpi_opt_tpu.train.common import journal_boundary
    from mpi_opt_tpu.utils.metrics import MetricsLogger

    led = _fused_ledger(tmp_path, space)
    u = _units(n, space, seed=n)
    u[0] = 1.0  # the unit cube's far corner decodes like any other row
    members = list(range(n))
    scores = np.linspace(0.1, 0.9, n)
    scores[n // 2] = np.nan
    fsyncs = _count_fsyncs(monkeypatch)
    stream = str(tmp_path / "m.jsonl")
    m = MetricsLogger(path=stream)
    prior = trace.configure(m)
    try:
        journal_boundary(FusedJournal(led, space), 0, members, u, scores, step=7)
    finally:
        trace.deconfigure(prior)
        m.close()
    assert len(fsyncs) == 1
    spans = [r for r in load_stream(stream) if r.get("span") == "journal"]
    assert [(s["n"], s["fsyncs"]) for s in spans] == [(n, 1)]
    led.close()

    # the per-record path, over a copy of the same header: n fsyncs, and
    # the same lines field for field but the wall-clock stamp
    ref_path = tmp_path / "ref.jsonl"
    ref_path.write_text(open(led.path).readline())
    ref = SweepLedger(str(ref_path))
    del fsyncs[:]
    for i in members:
        ref.record_member(
            trial_id=i, member=i, boundary=0, boundary_size=n,
            canonical_params=space.canonical_params(_decode_per_element(space, u[i])),
            score=scores[i], step=7,
        )
    ref.close()
    assert len(fsyncs) == n
    got, want = _lines(led.path), _lines(str(ref_path))
    for rec in got + want:
        del rec["ts"]
    assert got == want
    assert open(led.path).read().count("\n") == n + 1


def test_record_boundary_joins_an_open_batch(tmp_path, space, monkeypatch):
    led = _fused_ledger(tmp_path, space)
    j = FusedJournal(led, space)
    fsyncs = _count_fsyncs(monkeypatch)
    with led.batched():
        # joins the caller's block: no fsync of its own
        assert j.record_boundary(0, [0, 1, 2], _units(3, space), [0.1, 0.2, 0.3], step=5) == 0
        assert fsyncs == []
    assert len(fsyncs) == 1  # the outer block's exit makes both durable
    assert j.record_boundary(1, [0, 1, 2], _units(3, space), [0.4, 0.5, 0.6], step=9) == 1
    led.close()
    assert validate_ledger(led.path) == []


class _Killed(BaseException):
    """The kill: nothing after it runs but the blocks' own exits."""


@pytest.mark.parametrize("written", ["whole", "short"])
def test_kill_inside_the_block_before_its_fsync_recovers(tmp_path, space, monkeypatch, written):
    """A kill after the block's writes (all of them, or some) and before
    its fsync: the snapshot of that boundary was never saved, so a
    resume restores one boundary and re-computes the second. A whole
    block loads complete and verifies; a short one is a torn final
    boundary, truncated on load and re-journaled."""
    led = _fused_ledger(tmp_path, space)
    u = _units(4, space)
    j = FusedJournal(led, space)
    j.record_boundary(0, [0, 1, 2, 3], u, [0.1, 0.2, 0.3, 0.4], step=5)
    cut = 4 if written == "whole" else 2
    real_write = led._write_line
    calls = []

    def write_then_die(rec):
        real_write(rec)
        calls.append(rec)
        if len(calls) == cut:
            raise _Killed

    monkeypatch.setattr(led, "_write_line", write_then_die)
    monkeypatch.setattr(os, "fsync", lambda fd: None)  # the kill comes first
    with pytest.raises(_Killed):
        j.record_boundary(1, [0, 1, 2, 3], u, [0.5, 0.6, 0.7, 0.8], step=9)
    led._file.close()
    monkeypatch.undo()

    led2 = SweepLedger(led.path)
    assert led2.n_torn_boundary == (0 if written == "whole" else cut)
    j2 = FusedJournal(led2, space)
    j2.require_prefix(1)  # the snapshot the resume restores
    j2.record_boundary(1, [0, 1, 2, 3], u, [0.5, 0.6, 0.7, 0.8], step=9)
    assert (j2.verified, j2.written) == ((4, 0) if written == "whole" else (0, 4))
    j2.require_prefix(2)
    led2.close()
    assert validate_ledger(led.path) == []
    assert [r["trial_id"] for r in _lines(led.path)] == list(range(8))
