"""Durable checkpoint/resume: kill a sweep mid-flight, resume, match the
uninterrupted run (SURVEY.md §2 row 13, §5)."""

import numpy as np
import pytest

from mpi_opt_tpu.algorithms import PBT, RandomSearch
from mpi_opt_tpu.backends.cpu import CPUBackend
from mpi_opt_tpu.backends.tpu import TPUPopulationBackend
from mpi_opt_tpu.driver import run_search
from mpi_opt_tpu.utils.checkpoint import SearchCheckpointer
from mpi_opt_tpu.workloads import get_workload


@pytest.fixture(scope="module")
def quad():
    return get_workload("quadratic")


def _best_units(algo):
    return sorted(tuple(np.round(t.unit, 6)) for t in algo.trials.values())


def test_kill_and_resume_matches_uninterrupted(tmp_path, quad):
    """Random search through the CPU backend: interrupt after 2 batches,
    resume from disk in a FRESH process-equivalent (new algorithm/backend
    objects), finish; the trial set and best score must equal the
    uninterrupted run's exactly."""
    space = quad.default_space()

    # uninterrupted reference
    ref = RandomSearch(space, seed=11, max_trials=12, budget=5)
    b = CPUBackend(quad, n_workers=1)
    run_search(ref, b)
    b.close()

    # interrupted run: checkpoint every batch, stop after 2
    ckpt_dir = str(tmp_path / "ck")
    algo = RandomSearch(space, seed=11, max_trials=12, budget=5)
    b1 = CPUBackend(quad, n_workers=1)
    with SearchCheckpointer(ckpt_dir, every=1) as ck:
        run_search(algo, b1, max_batches=2, checkpointer=ck)
    b1.close()
    assert 0 < sum(t.score is not None for t in algo.trials.values()) < 12

    # fresh objects, resume from disk, run to completion
    algo2 = RandomSearch(space, seed=0, max_trials=12, budget=5)
    b2 = CPUBackend(quad, n_workers=1)
    with SearchCheckpointer(ckpt_dir, every=1) as ck2:
        step = ck2.restore_into(algo2, b2)
        assert step == 2
        run_search(algo2, b2, checkpointer=ck2)
    b2.close()

    assert algo2.finished()
    assert _best_units(algo2) == _best_units(ref)
    assert algo2.best().score == pytest.approx(ref.best().score, abs=1e-6)


def test_tpu_backend_pool_roundtrip(shared_workload, tmp_path):
    """PBT through the population backend: kill mid-sweep, resume with a
    fresh backend whose slot pool is restored from orbax; the finished
    search must match the uninterrupted run exactly (weights inherited
    across the kill boundary, not retrained)."""
    wl = shared_workload("fashion_mlp", n_train=256, n_val=128, attrs={"batch_size": 16})
    space = wl.default_space()

    def make_algo():
        return PBT(space, seed=21, population=4, generations=3, steps_per_generation=4)

    def make_backend():
        return TPUPopulationBackend(wl, population=4, seed=21)

    ref = make_algo()
    run_search(ref, make_backend())

    ckpt_dir = str(tmp_path / "ck")
    algo = make_algo()
    with SearchCheckpointer(ckpt_dir, every=1) as ck:
        run_search(algo, make_backend(), max_batches=2, checkpointer=ck)

    algo2 = make_algo()
    b2 = make_backend()
    with SearchCheckpointer(ckpt_dir, every=1) as ck2:
        assert ck2.restore_into(algo2, b2) == 2
        run_search(algo2, b2, checkpointer=ck2)

    assert algo2.finished()
    ref_scores = {t.trial_id: t.score for t in ref.trials.values()}
    got_scores = {t.trial_id: t.score for t in algo2.trials.values()}
    assert set(got_scores) == set(ref_scores)
    for tid, s in ref_scores.items():
        assert got_scores[tid] == pytest.approx(s, abs=1e-6), tid


def test_restore_into_empty_dir_is_none(tmp_path, quad):
    algo = RandomSearch(quad.default_space(), seed=1, max_trials=4, budget=2)
    b = CPUBackend(quad, n_workers=1)
    with SearchCheckpointer(str(tmp_path / "empty")) as ck:
        assert ck.restore_into(algo, b) is None
    b.close()


def test_cli_checkpoint_resume_flow(tmp_path):
    """End-to-end through the CLI flags: run, interrupt (via tiny trial
    budget split across invocations is not expressible — instead verify
    the flags wire up: a full run writes checkpoints, and --resume on a
    finished search exits cleanly without re-running trials)."""
    import json

    from mpi_opt_tpu.cli import main

    ckpt = str(tmp_path / "cli_ck")
    rc = main(
        [
            "--workload", "quadratic", "--algorithm", "random", "--trials", "6",
            "--budget", "3", "--backend", "cpu", "--workers", "1",
            "--checkpoint-dir", ckpt,
        ]
    )
    assert rc == 0
    ck = SearchCheckpointer(ckpt)
    assert ck.latest_step() is not None
    ck.close()


def test_restore_falls_back_to_last_good_snapshot(tmp_path, quad):
    """Corrupt the LATEST step (silent bit-rot): restore_into must
    quarantine it (rename, never delete), fall back to the next older
    verified step, and the resumed search must still finish with the
    uninterrupted run's exact trial set — the last-good-fallback
    guarantee that keeps a poisoned snapshot from crash-looping the
    restart budget."""
    from mpi_opt_tpu.utils import integrity
    from mpi_opt_tpu.workloads.chaos import inject_corrupt_save

    space = quad.default_space()
    ref = RandomSearch(space, seed=11, max_trials=12, budget=5)
    b = CPUBackend(quad, n_workers=1)
    run_search(ref, b)
    b.close()

    ckpt_dir = str(tmp_path / "ck")
    algo = RandomSearch(space, seed=11, max_trials=12, budget=5)
    b1 = CPUBackend(quad, n_workers=1)
    with SearchCheckpointer(ckpt_dir, every=1) as ck:
        run_search(algo, b1, max_batches=3, checkpointer=ck)
    b1.close()

    inject_corrupt_save(ckpt_dir)  # latest = step 3
    events = []
    integrity.set_observer(lambda event, **f: events.append((event, f)))
    try:
        algo2 = RandomSearch(space, seed=0, max_trials=12, budget=5)
        b2 = CPUBackend(quad, n_workers=1)
        with SearchCheckpointer(ckpt_dir, every=1) as ck2:
            step = ck2.restore_into(algo2, b2)
            assert step == 2  # walked back past the poisoned step 3
            run_search(algo2, b2, checkpointer=ck2)
        b2.close()
    finally:
        integrity.clear_observer()
    assert [e for e, _ in events] == ["snapshot_corrupt"]
    assert events[0][1]["step"] == 3
    import os

    assert os.path.isdir(os.path.join(ckpt_dir, "3.corrupt"))  # evidence kept
    assert algo2.finished()
    assert _best_units(algo2) == _best_units(ref)
    assert algo2.best().score == pytest.approx(ref.best().score, abs=1e-6)


def test_search_checkpointer_keep_depth_is_fallback_budget(tmp_path, quad):
    """keep defaults to 3: the latest step may be the torn one, leaving
    two verified fallbacks (README documents keep as the fallback
    budget)."""
    import os

    space = quad.default_space()
    algo = RandomSearch(space, seed=5, max_trials=6, budget=2)
    b = CPUBackend(quad, n_workers=1)
    ckpt_dir = str(tmp_path / "ck")
    with SearchCheckpointer(ckpt_dir, every=1) as ck:
        run_search(algo, b, checkpointer=ck)
    b.close()
    kept = sorted(int(d) for d in os.listdir(ckpt_dir) if d.isdigit())
    assert kept == [4, 5, 6]


@pytest.mark.slow  # 24 s of subprocess sweeps here (2026-09-26); passes
def test_sigkill_during_async_save_resumes_on_prior_verified_step(tmp_path):
    """The ISSUE-5 acceptance drill for the driver path, end to end
    through real processes: SIGKILL a journaled+checkpointed sweep while
    orbax's async writer may still be in flight; `fsck --repair`
    quarantines whatever the kill tore; `--resume` lands on the prior
    verified step with the journaled ledger still consistent, and the
    finished sweep matches a clean run's best."""
    import json
    import os
    import signal
    import subprocess
    import sys
    import time

    from mpi_opt_tpu.cli import main
    from mpi_opt_tpu.utils import integrity

    ck = str(tmp_path / "ck")
    led = str(tmp_path / "sweep.jsonl")
    # chaos slow=1.0: every trial sleeps 0.3 s (scores untouched), so
    # the sweep is mid-flight long enough for the kill to land between
    # a step's commit and the next async save
    args = [
        "--workload", "quadratic", "--algorithm", "random",
        "--trials", "24", "--budget", "200", "--workers", "1",
        "--seed", "3", "--platform", "cpu", "--no-mesh",
        "--chaos", "slow=1.0,slow_s=0.3,seed=0",
        "--checkpoint-dir", ck, "--ledger", led,
    ]
    p = subprocess.Popen(
        [sys.executable, "-m", "mpi_opt_tpu", *args],
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
        cwd="/root/repo",
    )
    try:
        # kill as soon as a second step's commit marker lands — the
        # next async save (and the process) die mid-flight
        deadline = time.time() + 300
        while time.time() < deadline:
            steps = [
                d for d in (os.listdir(ck) if os.path.isdir(ck) else [])
                if d.isdigit()
                and os.path.exists(os.path.join(ck, d, "_CHECKPOINT_METADATA"))
            ]
            if len(steps) >= 2 or p.poll() is not None:
                break
            time.sleep(0.02)
        assert p.poll() is None, "sweep finished before the kill landed"
        os.kill(p.pid, signal.SIGKILL)
    finally:
        p.wait()

    # repair: quarantine anything the kill tore (rc 1 when it found
    # debris, 0 when the kill happened to land between writes)
    assert integrity.fsck_main([ck, "--repair", "--json"]) in (0, 1)
    # the journal survived append-fsync-consistent
    from mpi_opt_tpu.ledger.store import validate_ledger

    assert validate_ledger(led) == []
    # resume completes from the prior verified step
    rc = main(args + ["--resume"])
    assert rc == 0
    # post-resume audit: everything verified, journal consistent with
    # the newest snapshot
    assert integrity.fsck_main([ck, "--json", "--ledger", led]) == 0
    # and the recovered sweep found the clean run's best
    clean = str(tmp_path / "clean.jsonl")
    assert main([
        "--workload", "quadratic", "--algorithm", "random",
        "--trials", "24", "--budget", "200", "--workers", "1",
        "--seed", "3", "--ledger", clean,
        "--chaos", "slow=1.0,slow_s=0.3,seed=0",
    ]) == 0
    from mpi_opt_tpu.ledger.report import summarize_ledger

    got = summarize_ledger(led)["best"]
    want = summarize_ledger(clean)["best"]
    assert got["score"] == pytest.approx(want["score"], abs=1e-9)
    assert got["trial_id"] == want["trial_id"]


def test_metadata_probe_failure_warns_before_fallback(tmp_path, quad):
    """The item-metadata probe is best-effort, but its blanket except
    must not be SILENT: a probe that always fails (an orbax API break)
    should be visible as a warning naming the exception and step, while
    the directory-listing fallback still resolves the snapshot items."""
    space = quad.default_space()
    algo = RandomSearch(space, seed=13, max_trials=4, budget=2)
    b = CPUBackend(quad, n_workers=1)
    with SearchCheckpointer(str(tmp_path / "ck"), every=1) as ck:
        run_search(algo, b, max_batches=1, checkpointer=ck)
        # drain the async save: the directory-listing fallback can only
        # see a step whose write has committed
        ck._mgr.wait_until_finished()
        step = ck.latest_step()
        assert step is not None

        def broken_probe(_step):
            raise RuntimeError("orbax item_metadata API drifted")

        ck._mgr.item_metadata = broken_probe
        with pytest.warns(RuntimeWarning, match=r"metadata probe failed at step 1.*RuntimeError"):
            names = ck._item_names(step)
        assert "search" in names  # the fallback still found the items
    b.close()
