"""Snapshot integrity (utils/integrity.py): verified saves, last-good
fallback, quarantine, and the fsck audit — the bounding layer for the
one failure class the restart loop could not survive: a torn or
bit-rotted latest snapshot turning "free restart" into a crash loop.
"""

import json
import os

import numpy as np
import pytest

from mpi_opt_tpu.utils import integrity
from mpi_opt_tpu.utils.checkpoint import SweepCheckpointer
from mpi_opt_tpu.workloads.chaos import inject_corrupt_save, inject_torn_save


# -- digests ---------------------------------------------------------------


def test_tree_digest_stable_across_dataclass_and_dict_structure():
    """orbax round-trips a flax.struct PopState as a plain dict; the
    save-side digest (dataclass) must equal the restore-side digest
    (dict) or every verified restore would false-positive corrupt."""
    from mpi_opt_tpu.train.population import PopState

    params = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
    momentum = {"w": np.ones((2, 3), np.float32)}
    step = np.array([3, 4], np.int32)
    as_dataclass = PopState(params=params, momentum=momentum, step=step)
    as_dict = {"params": params, "momentum": momentum, "step": step}
    assert integrity.tree_digest(as_dataclass) == integrity.tree_digest(as_dict)


def test_tree_digest_sensitive_to_content_dtype_and_shape():
    base = {"a": np.arange(4, dtype=np.float32)}
    assert integrity.tree_digest(base) == integrity.tree_digest(
        {"a": np.arange(4, dtype=np.float32)}
    )
    # one flipped value
    mut = {"a": np.array([0, 1, 2, 4], np.float32)}
    assert integrity.tree_digest(base) != integrity.tree_digest(mut)
    # same bytes, different dtype view
    assert integrity.tree_digest(base) != integrity.tree_digest(
        {"a": np.arange(4, dtype=np.float32).view(np.int32)}
    )
    # same bytes, different shape
    assert integrity.tree_digest({"a": np.zeros((2, 3))}) != integrity.tree_digest(
        {"a": np.zeros((3, 2))}
    )


def test_json_digest_canonicalizes_tuples_and_int_keys():
    """The digest must survive one json round trip — exactly what orbax
    JsonSave/JsonRestore applies to the value."""
    before = {"curve": (1.0, 2.0), "by_rung": {0: "a", 10: "b"}}
    after = json.loads(json.dumps(before))  # lists, string keys
    assert integrity.json_digest(before) == integrity.json_digest(after)
    assert integrity.json_digest(before) != integrity.json_digest(
        {"curve": (1.0, 2.5), "by_rung": {0: "a", 10: "b"}}
    )


def test_manifest_verify_catches_mutation_missing_and_extra_items():
    meta = {"config": {"seed": 0}, "gen": 2}
    sweep = {"state": {"p": np.arange(3, dtype=np.float32)}}
    man = integrity.build_manifest({"meta": meta}, {"sweep": sweep})
    assert integrity.verify_restored(man, {"meta": meta}, {"sweep": sweep}) == []
    # mutated array leaf
    bad = {"state": {"p": np.array([0, 9, 2], np.float32)}}
    assert any(
        "sweep" in p
        for p in integrity.verify_restored(man, {"meta": meta}, {"sweep": bad})
    )
    # item recorded but not restored (the torn-save shape)
    assert any(
        "not restored" in p
        for p in integrity.verify_restored(man, {"meta": meta}, {})
    )
    # item present but never recorded
    assert any(
        "not in manifest" in p
        for p in integrity.verify_restored(
            man, {"meta": meta}, {"sweep": sweep, "ghost": sweep}
        )
    )


# -- quarantine ------------------------------------------------------------


def test_quarantine_step_renames_never_deletes(tmp_path):
    d = str(tmp_path)
    os.makedirs(tmp_path / "7")
    (tmp_path / "7" / "payload").write_text("evidence")
    q = integrity.quarantine_step(d, 7)
    assert q.endswith("7.corrupt") and os.path.isdir(q)
    assert (tmp_path / "7.corrupt" / "payload").read_text() == "evidence"
    assert not (tmp_path / "7").exists()
    # collision: a second quarantine of a re-written step 7 gets a suffix
    os.makedirs(tmp_path / "7")
    q2 = integrity.quarantine_step(d, 7)
    assert q2.endswith("7.corrupt.1")
    assert sorted(os.path.basename(p) for p in integrity.list_quarantined(d)) == [
        "7.corrupt",
        "7.corrupt.1",
    ]
    # a missing step dir is a no-op, not a crash
    assert integrity.quarantine_step(d, 99) is None


def test_observer_receives_notify_and_clears(tmp_path):
    got = []
    integrity.set_observer(lambda event, **f: got.append((event, f)))
    try:
        integrity.notify("snapshot_corrupt", step=3)
    finally:
        integrity.clear_observer()
    assert got == [("snapshot_corrupt", {"step": 3})]
    # unobserved notify degrades to a warning, never a crash
    with pytest.warns(RuntimeWarning, match="snapshot_corrupt"):
        integrity.notify("snapshot_corrupt", step=4)


# -- last-good fallback through SweepCheckpointer --------------------------


CFG = {"workload": "toy", "population": 4, "seed": 0, "momentum_dtype": "float32"}


def _save_steps(d, steps):
    ck = SweepCheckpointer(d, CFG)
    for s in steps:
        ck.save(
            s,
            sweep={"state": {"p": np.full((4,), float(s), np.float32)}},
            meta_extra={"gen": s},
        )
    ck.close()


def test_restore_walks_back_to_last_good_and_quarantines(tmp_path):
    d = str(tmp_path / "ck")
    _save_steps(d, [1, 2, 3])
    inject_corrupt_save(d)  # latest = 3
    events = []
    integrity.set_observer(lambda event, **f: events.append((event, f)))
    try:
        ck = SweepCheckpointer(d, CFG)
        sweep, meta = ck.restore()
        ck.close()
    finally:
        integrity.clear_observer()
    assert meta["gen"] == 2
    np.testing.assert_array_equal(
        sweep["state"]["p"], np.full((4,), 2.0, np.float32)
    )
    assert [e for e, _ in events] == ["snapshot_corrupt"]
    assert events[0][1]["step"] == 3
    assert os.path.isdir(os.path.join(d, "3.corrupt"))  # quarantined, not deleted
    assert not os.path.isdir(os.path.join(d, "3"))


def test_restore_torn_save_falls_back(tmp_path):
    """The SIGKILL-mid-async-save shape: a truncated file inside the
    committed latest step must quarantine + fall back, not crash."""
    d = str(tmp_path / "ck")
    _save_steps(d, [1, 2])
    inject_torn_save(d)
    events = []
    integrity.set_observer(lambda event, **f: events.append(event))
    try:
        ck = SweepCheckpointer(d, CFG)
        _sweep, meta = ck.restore()
        ck.close()
    finally:
        integrity.clear_observer()
    assert meta["gen"] == 1
    assert "snapshot_corrupt" in events


def test_no_verified_snapshot_raises_distinct_error(tmp_path):
    d = str(tmp_path / "ck")
    _save_steps(d, [1, 2])
    for s in (1, 2):
        inject_corrupt_save(d, step=s)
    integrity.set_observer(lambda *a, **k: None)
    try:
        ck = SweepCheckpointer(d, CFG)
        with pytest.raises(integrity.NoVerifiedSnapshotError, match="no verified snapshot"):
            ck.restore()
    finally:
        integrity.clear_observer()
    # both steps quarantined; the evidence survives
    assert sorted(os.path.basename(q) for q in integrity.list_quarantined(d)) == [
        "1.corrupt",
        "2.corrupt",
    ]


def test_empty_directory_still_returns_none(tmp_path):
    ck = SweepCheckpointer(str(tmp_path / "fresh"), CFG)
    assert ck.restore() is None
    ck.close()


def test_legacy_step_without_manifest_is_resumable_with_notice(tmp_path):
    """Pre-upgrade snapshots carry no manifest item: they must stay
    resumable (same rule as config keys added after a format existed),
    announced via snapshot_unverified rather than refused."""
    import orbax.checkpoint as ocp

    d = str(tmp_path / "ck")
    mgr = ocp.CheckpointManager(
        d, options=ocp.CheckpointManagerOptions(create=True)
    )
    mgr.save(
        1,
        args=ocp.args.Composite(
            sweep=ocp.args.StandardSave({"state": {"p": np.zeros(3, np.float32)}}),
            meta=ocp.args.JsonSave({"config": CFG, "gen": 1}),
        ),
    )
    mgr.wait_until_finished()
    mgr.close()
    events = []
    integrity.set_observer(lambda event, **f: events.append(event))
    try:
        ck = SweepCheckpointer(d, CFG)
        _sweep, meta = ck.restore()
        ck.close()
    finally:
        integrity.clear_observer()
    assert meta["gen"] == 1
    assert events == ["snapshot_unverified"]


def test_keep_default_leaves_fallback_depth(tmp_path):
    """keep defaults to 3: the latest step may be torn by the very crash
    that triggered the resume, leaving TWO verified fallbacks."""
    d = str(tmp_path / "ck")
    _save_steps(d, [1, 2, 3, 4, 5])
    kept = sorted(int(x) for x in os.listdir(d) if x.isdigit())
    assert kept == [3, 4, 5]


def test_config_mismatch_names_only_mismatched_keys(tmp_path):
    d = str(tmp_path / "ck")
    _save_steps(d, [1])
    run_cfg = dict(CFG, population=8)
    ck = SweepCheckpointer(d, run_cfg)
    with pytest.raises(ValueError, match="different sweep") as exc:
        ck.restore()
    msg = str(exc.value)
    assert "population: snapshot=4 vs run=8" in msg
    # matched keys stay OUT of the message (the whole point of the diff)
    assert "workload" not in msg and "seed" not in msg


# -- fsck ------------------------------------------------------------------


def test_fsck_flags_corruption_repairs_and_reports_quarantine(tmp_path, capsys):
    d = str(tmp_path / "ck")
    _save_steps(d, [1, 2, 3])
    assert integrity.fsck_main([d, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["ok"] is True
    assert [s["status"] for s in rep["steps"]] == ["verified"] * 3
    assert rep["newest_verified"]["step"] == 3

    inject_corrupt_save(d)
    assert integrity.fsck_main([d, "--json"]) == 1
    rep = json.loads(capsys.readouterr().out)
    assert rep["ok"] is False
    by_step = {s["step"]: s["status"] for s in rep["steps"]}
    assert by_step == {1: "verified", 2: "verified", 3: "corrupt"}

    # --repair quarantines; the run still reports the corruption it found
    assert integrity.fsck_main([d, "--json", "--repair"]) == 1
    rep = json.loads(capsys.readouterr().out)
    assert rep["repaired"] == ["3.corrupt"]

    # post-repair: clean, with the quarantine visible
    assert integrity.fsck_main([d, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["ok"] is True and rep["quarantined"] == ["3.corrupt"]
    assert rep["newest_verified"]["step"] == 2


def test_fsck_surfaces_uncommitted_torn_step(tmp_path, capsys):
    """A step dir without the orbax commit marker (killed mid-save,
    before commit) is invisible to orbax but fsck must surface it —
    debris that --repair quarantines."""
    d = str(tmp_path / "ck")
    _save_steps(d, [1, 2])
    os.makedirs(os.path.join(d, "3", "sweep"))
    with open(os.path.join(d, "3", "sweep", "partial"), "w") as f:
        f.write("torn")
    assert integrity.fsck_main([d, "--json"]) == 1
    rep = json.loads(capsys.readouterr().out)
    torn = [s for s in rep["steps"] if s["status"] == "torn"]
    assert len(torn) == 1 and torn[0]["step"] == 3
    assert integrity.fsck_main([d, "--repair", "--json"]) == 1
    capsys.readouterr()
    assert os.path.isdir(os.path.join(d, "3.corrupt"))
    assert integrity.fsck_main([d, "--json"]) == 0
    capsys.readouterr()


def test_fsck_usage_errors(tmp_path, capsys):
    with pytest.raises(SystemExit) as exc:
        integrity.fsck_main([str(tmp_path / "missing")])
    assert exc.value.code == 2
    capsys.readouterr()


def test_fsck_repairs_torn_ledger_tail_and_gates_on_explicit_only(tmp_path, capsys):
    """A torn FINAL ledger line (kill mid-append) is the recoverable
    damage shape: an explicit --ledger flags it (exit 1), --repair
    truncates it (the same self-heal a resume applies), and the next
    audit is green. An AUTO-detected sibling's problems are reported
    but never fail the audit — fsck cannot prove the sibling belongs to
    this sweep."""
    from mpi_opt_tpu.ledger.store import SweepLedger, validate_ledger
    from mpi_opt_tpu.trial import TrialResult

    d = str(tmp_path / "ck")
    _save_steps(d, [1, 2])
    led = str(tmp_path / "sweep.jsonl")
    with SweepLedger(led) as lg:
        lg.ensure_header({"algorithm": "random", "seed": 0})
        lg.record_trial(TrialResult(trial_id=0, score=0.5, step=1), {"lr": 0.1})
    with open(led, "a") as f:
        f.write('{"kind": "trial", "trial_id": 1, "trunc')  # torn tail

    # auto-detect (the single sniffing sibling): reported, NOT fatal
    assert integrity.fsck_main([d, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["ledger"]["path"] == led
    assert rep["ledger"]["cross_checked"] is False
    assert rep["ledger"]["problems"]  # the tear is visible

    # explicit: fatal, and --repair truncates the tear in place
    assert integrity.fsck_main([d, "--json", "--ledger", led]) == 1
    capsys.readouterr()
    assert integrity.fsck_main([d, "--json", "--ledger", led, "--repair"]) == 1
    rep = json.loads(capsys.readouterr().out)
    assert rep["ledger"]["torn_tail"] is True
    assert any("torn tail truncated" in r for r in rep["repaired"])
    assert validate_ledger(led) == []

    assert integrity.fsck_main([d, "--json", "--ledger", led]) == 0
    capsys.readouterr()


# -- per-shard parallel save digests (ISSUE 6 satellite) -------------------


def test_parallel_digest_equals_serial(monkeypatch):
    """The thread-pool leaf-hashing path must produce the EXACT digest
    the serial path does (per-leaf digests combine in sorted path
    order) — snapshots written on multi-core hosts verify on 1-core
    ones and vice versa."""
    rng = np.random.default_rng(0)
    tree = {f"shard_{i}": rng.standard_normal(4096).astype(np.float32) for i in range(6)}
    serial = integrity.tree_digest(tree)  # far below the threshold
    monkeypatch.setattr(integrity, "_PARALLEL_DIGEST_BYTES", 1)
    assert integrity.tree_digest(tree) == serial


def test_parallel_digest_unverifiable_leaf_still_returns_none(monkeypatch):
    class Opaque:
        shape = ()
        dtype = "float32"

    monkeypatch.setattr(integrity, "_PARALLEL_DIGEST_BYTES", 1)
    monkeypatch.setattr(integrity, "_leaf_digest", lambda l: None)
    assert integrity.tree_digest({"a": np.ones(4), "b": np.ones(4)}) is None


# -- fsck --deep: ocdbt-internal checksums (ISSUE 6 satellite) -------------


def _rot_nested_process_store(step_dir):
    """Flip one bit in a nested ocdbt.process_* data file — the rot
    shape a plain restore (and therefore the manifest layer) reads
    straight past, because restores resolve through the top-level
    database."""
    import glob

    files = sorted(
        glob.glob(os.path.join(step_dir, "*", "ocdbt.process_*", "d", "*")),
        key=os.path.getsize,
    )
    assert files, "expected nested ocdbt process-store data files"
    tgt = files[-1]
    raw = bytearray(open(tgt, "rb").read())
    raw[len(raw) // 2] ^= 0x40
    open(tgt, "wb").write(bytes(raw))
    return tgt


def test_fsck_deep_catches_ocdbt_internal_rot(tmp_path, capsys):
    from mpi_opt_tpu.utils.integrity import fsck_main

    ck = str(tmp_path / "ck")
    snap = SweepCheckpointer(ck, {"a": 1})
    snap.save(1, sweep={"x": np.arange(64.0), "y": np.ones((16, 16), np.float32)},
              meta_extra={"m": 2})
    snap.close()
    assert fsck_main([ck, "--deep"]) == 0  # clean tree audits clean, deeply
    capsys.readouterr()
    _rot_nested_process_store(os.path.join(ck, "1"))
    # a SEALED step: the file-level digests flag it without --deep
    assert fsck_main([ck]) == 1
    capsys.readouterr()
    # a step killed between commit and seal has only the item digests,
    # which verify what a restore RETURNS — that layer passes
    os.remove(os.path.join(ck, "1", integrity.SEAL_FILE))
    assert fsck_main([ck]) == 0
    capsys.readouterr()
    # --deep reads every ocdbt key back: tensorstore's CRC-32C flags it
    assert fsck_main([ck, "--deep", "--json"]) == 1
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    (entry,) = report["steps"]
    assert entry["status"] == "corrupt"
    assert any("CRC" in p or "ocdbt" in p for p in entry["problems"])
    # --deep --repair quarantines it like any other corrupt step
    assert fsck_main([ck, "--deep", "--repair"]) == 1
    capsys.readouterr()
    assert integrity.list_quarantined(ck)


# -- sealed steps: every file of a step is covered -------------------------


def test_any_one_truncated_file_quarantines_and_walks_back(tmp_path):
    """The guarantee in full: whichever ONE file of the newest step is
    truncated — orbax's own metadata (which the installed orbax reads
    past), array data, a nested process store, the seal itself — the
    step is quarantined and restore lands on the step before it."""

    def files_of(d):  # data-file names differ from save to save: go by position
        return integrity._step_files(os.path.join(d, "2")) + [integrity.SEAL_FILE]

    d0 = str(tmp_path / "ck0")
    _save_steps(d0, [1, 2])
    n = len(files_of(d0))
    assert n >= 8  # metadata, three items, nested stores, seal
    integrity.set_observer(lambda *a, **k: None)
    try:
        for i in range(n):
            d = str(tmp_path / f"ck{i + 1}")
            _save_steps(d, [1, 2])
            rels = files_of(d)
            assert len(rels) == n
            rel = rels[i]
            path = os.path.join(d, "2", *rel.split("/"))
            with open(path, "r+b") as f:
                f.truncate(os.path.getsize(path) // 2)
            ck = SweepCheckpointer(d, CFG)
            _sweep, meta = ck.restore()
            ck.close()
            assert meta["gen"] == 1, rel
            assert os.path.isdir(os.path.join(d, "2.corrupt")), rel
    finally:
        integrity.clear_observer()


def test_seal_lands_at_the_next_save_and_at_close(tmp_path):
    """A step is sealed where the loop waits for the async writer
    anyway: at the next save, and at close."""
    d = str(tmp_path / "ck")
    ck = SweepCheckpointer(d, CFG)
    sweep = {"state": {"p": np.zeros((4,), np.float32)}}
    ck.save(1, sweep=sweep, meta_extra={"gen": 1})
    ck.save(2, sweep=sweep, meta_extra={"gen": 2})
    assert integrity.check_seal(os.path.join(d, "1")) == []
    ck.close()
    assert integrity.check_seal(os.path.join(d, "2")) == []


def test_unsealed_step_falls_back_to_item_digests(tmp_path):
    """Killed between commit and seal: the step has no _SEAL, restores
    on its item digests alone, and a flipped payload bit still
    quarantines it."""
    d = str(tmp_path / "ck")
    _save_steps(d, [1, 2])
    for s in (1, 2):
        os.remove(os.path.join(d, str(s), integrity.SEAL_FILE))
    assert integrity.check_seal(os.path.join(d, "2")) is None
    ck = SweepCheckpointer(d, CFG)
    _sweep, meta = ck.restore()
    ck.close()
    assert meta["gen"] == 2
    data = sorted(
        os.path.join(r, f)
        for r, _d, fs in os.walk(os.path.join(d, "2", "sweep", "d"))
        for f in fs
    )[0]
    raw = bytearray(open(data, "rb").read())
    raw[len(raw) // 2] ^= 0x01
    open(data, "wb").write(bytes(raw))
    integrity.set_observer(lambda *a, **k: None)
    try:
        ck = SweepCheckpointer(d, CFG)
        _sweep, meta = ck.restore()
        ck.close()
    finally:
        integrity.clear_observer()
    assert meta["gen"] == 1
