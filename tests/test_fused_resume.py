"""Crash-recovery of fused PBT sweeps (SURVEY.md §5 failure model).

The platform this framework targets demonstrably kills TPU workers
mid-sweep (PERF_NOTES.md); these tests prove a killed sweep resumes
from its launch-granular orbax snapshots to the BIT-IDENTICAL result
of an uninterrupted run — the RNG key is part of the snapshot, so the
continued trajectory is exactly the one the crash interrupted.
"""

import os

import numpy as np
import pytest

import mpi_opt_tpu.train.fused_pbt as fp

MLP = dict(n_train=256, n_val=128)


@pytest.fixture(scope="module")
def wl(shared_workload):
    return shared_workload("fashion_mlp", **MLP)


KW = dict(population=8, generations=4, steps_per_gen=5, seed=2, gen_chunk=1)
TPE_KW = dict(n_trials=9, batch=3, budget=4, seed=3)
BOHB_KW = dict(max_budget=4, eta=2, seed=1, random_fraction=0.5)

# the uninterrupted sweeps the drills below end equal to, run once a
# module: results are host arrays and plain values, and no test writes
# into one


@pytest.fixture(scope="module")
def whole(wl):
    return fp.fused_pbt(wl, **KW)


@pytest.fixture(scope="module")
def tpe_whole(wl):
    import mpi_opt_tpu.train.fused_tpe as ft

    return ft.fused_tpe(wl, **TPE_KW)


@pytest.fixture(scope="module")
def bohb_whole(wl):
    from mpi_opt_tpu.train.fused_bohb import fused_bohb

    return fused_bohb(wl, **BOHB_KW)


def test_crash_resume_bit_identical(wl, whole, tmp_path, monkeypatch):
    real = fp.run_fused_pbt
    calls = {"n": 0}

    def crashing(*a, **k):
        calls["n"] += 1
        if calls["n"] == 3:  # die mid-sweep, after 2 completed launches
            raise RuntimeError("simulated TPU worker crash")
        return real(*a, **k)

    ckpt = str(tmp_path / "ck")
    monkeypatch.setattr(fp, "run_fused_pbt", crashing)
    with pytest.raises(RuntimeError, match="simulated"):
        fp.fused_pbt(wl, checkpoint_dir=ckpt, **KW)
    monkeypatch.setattr(fp, "run_fused_pbt", real)

    resumed = fp.fused_pbt(wl, checkpoint_dir=ckpt, **KW)
    np.testing.assert_array_equal(resumed["best_curve"], whole["best_curve"])
    np.testing.assert_array_equal(resumed["mean_curve"], whole["mean_curve"])
    np.testing.assert_array_equal(resumed["unit"], whole["unit"])
    assert resumed["best_score"] == whole["best_score"]
    # launch durations survive the crash: pre-crash launches' measured
    # walls come from the snapshot, the rest are measured live, and the
    # set aligns with the launch split (launchwise wall-to-target input)
    assert resumed["launch_gens"] == whole["launch_gens"]
    assert len(resumed["launch_walls"]) == len(resumed["launch_gens"])
    assert all(w > 0 for w in resumed["launch_walls"])


def test_pre_upgrade_snapshot_resume_reports_no_launch_walls(wl, whole, tmp_path, monkeypatch):
    """A snapshot from before round 3 lacks BOTH the 'momentum_dtype'
    config key and the 'launch_walls' meta — emulated by editing the
    on-disk orbax JSON, exactly what an old snapshot looks like. The
    resume must (a) not be refused by the config check (an absent key
    compares as its historical f32 default), (b) produce the
    bit-identical sweep result, and (c) mark the duration set unknown
    (None) so the metric helper falls back to whole-sweep prorating
    instead of crashing on a misaligned list."""
    import glob
    import json

    from mpi_opt_tpu.utils.metrics import sweep_wall_to_target

    ckpt = str(tmp_path / "ck")
    real = fp.run_fused_pbt
    calls = {"n": 0}

    def crashing(*a, **k):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("simulated TPU worker crash")
        return real(*a, **k)

    monkeypatch.setattr(fp, "run_fused_pbt", crashing)
    with pytest.raises(RuntimeError, match="simulated"):
        fp.fused_pbt(wl, checkpoint_dir=ckpt, **KW)
    monkeypatch.setattr(fp, "run_fused_pbt", real)

    hit = 0
    # orbax's JsonSave lands at <step>/meta/metadata (no extension)
    for path in glob.glob(f"{ckpt}/*/meta/metadata"):
        with open(path) as f:
            d = json.load(f)
        if isinstance(d, dict) and "config" in d:
            d["config"].pop("momentum_dtype", None)
            d.pop("launch_walls", None)
            with open(path, "w") as f:
                json.dump(d, f)
            hit += 1
    assert hit, "no snapshot meta JSON found to rewrite"
    # a genuine pre-upgrade snapshot predates the integrity manifest
    # too: drop the item, or the (correct!) digest check would flag the
    # meta edit above as tampering and quarantine the step
    import shutil

    for mdir in glob.glob(f"{ckpt}/*/manifest"):
        shutil.rmtree(mdir)
    # ... and the file-level seal, which would flag both edits
    for seal in glob.glob(f"{ckpt}/*/_SEAL"):
        os.remove(seal)

    resumed = fp.fused_pbt(wl, checkpoint_dir=ckpt, **KW)
    np.testing.assert_array_equal(resumed["best_curve"], whole["best_curve"])
    assert resumed["launch_walls"] is None
    assert sweep_wall_to_target(resumed, 10.0, -1.0) == pytest.approx(2.5)


def test_resume_after_completion_skips_all_launches(wl, tmp_path, monkeypatch):
    ckpt = str(tmp_path / "ck")
    first = fp.fused_pbt(wl, checkpoint_dir=ckpt, **KW)

    def boom(*a, **k):  # a re-run must not execute anything
        raise AssertionError("completed sweep re-ran a launch")

    monkeypatch.setattr(fp, "run_fused_pbt", boom)
    again = fp.fused_pbt(wl, checkpoint_dir=ckpt, **KW)
    np.testing.assert_array_equal(again["best_curve"], first["best_curve"])
    assert again["best_score"] == first["best_score"]


def test_step_chunk_deterministic_learns_and_matches_shapes(wl):
    """step_chunk (sub-generation launch splitting) is deterministic,
    returns the same result shapes as the fused scan, and still learns.
    It is NOT bit-identical to the unchunked sweep (documented: folded
    sub-segment keys), so equality is asserted between two step-chunked
    runs, not against the scan."""
    kw = dict(population=8, generations=3, steps_per_gen=6, seed=5, step_chunk=2)
    a = fp.fused_pbt(wl, **kw)
    b = fp.fused_pbt(wl, **kw)
    np.testing.assert_array_equal(a["best_curve"], b["best_curve"])
    assert a["best_score"] == b["best_score"]
    assert len(a["best_curve"]) == 3
    assert a["launch_gens"] == [1, 1, 1]
    assert len(a["launch_walls"]) == 3
    # shapes/semantics match the scan path's result contract
    scan = fp.fused_pbt(wl, population=8, generations=3, steps_per_gen=6, seed=5)
    assert set(a.keys()) == set(scan.keys())


def test_step_chunk_crash_resume_identical(wl, tmp_path, monkeypatch):
    """Generation-granular snapshots make a killed step-chunked sweep
    resume to the identical result of an uninterrupted one."""
    kw = dict(population=8, generations=4, steps_per_gen=6, seed=6, step_chunk=3)
    whole = fp.fused_pbt(wl, **kw)

    real = fp._run_stepped_generation
    calls = {"n": 0}

    def crashing(*a, **k):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("simulated TPU worker crash")
        return real(*a, **k)

    ckpt = str(tmp_path / "ck")
    monkeypatch.setattr(fp, "_run_stepped_generation", crashing)
    with pytest.raises(RuntimeError, match="simulated"):
        fp.fused_pbt(wl, checkpoint_dir=ckpt, **kw)
    monkeypatch.setattr(fp, "_run_stepped_generation", real)
    resumed = fp.fused_pbt(wl, checkpoint_dir=ckpt, **kw)
    np.testing.assert_array_equal(resumed["best_curve"], whole["best_curve"])
    assert resumed["best_score"] == whole["best_score"]


def test_step_chunk_changes_trajectory_and_guards_resume(wl, tmp_path):
    """step_chunk is part of the checkpoint config: it changes the RNG
    derivation (a different search trajectory), so resuming an
    unchunked snapshot with step_chunk set must be refused."""
    ckpt = str(tmp_path / "ck")
    fp.fused_pbt(wl, checkpoint_dir=ckpt, **KW)
    with pytest.raises(ValueError, match="different sweep"):
        fp.fused_pbt(wl, checkpoint_dir=ckpt, step_chunk=2, **KW)


def test_step_chunk_on_mesh_keeps_pop_sharding(shared_workload):
    """step_chunk adds host-side launch boundaries inside a generation;
    the population must stay sharded over 'pop' across them (XLA output
    shardings propagate through train sub-launches AND the boundary
    program's exploit gather) — a silent fallback to replication would
    defeat the mesh without failing any correctness check."""
    import jax

    from mpi_opt_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(n_pop=8, n_data=1)
    wl = shared_workload("fashion_mlp", label="pop8 data1 mesh", **MLP)
    res = fp.fused_pbt(
        wl, population=8, generations=2, steps_per_gen=4, seed=0,
        step_chunk=2, mesh=mesh,
    )
    for leaf in jax.tree.leaves(res["state"].params):
        assert not leaf.sharding.is_fully_replicated, leaf.sharding
    assert 0.0 <= res["best_score"] <= 1.0


def test_step_chunk_accepts_zero_steps_like_unchunked(wl):
    """Degenerate steps_per_gen=0 (eval/exploit only) must behave the
    same chunked and unchunked — regression: the split once divided by
    zero for total=0."""
    res = fp.fused_pbt(wl, population=4, generations=2, steps_per_gen=0, step_chunk=2)
    assert len(res["best_curve"]) == 2


def test_step_chunk_rejects_gen_chunk_combination(wl):
    with pytest.raises(ValueError, match="ambiguous"):
        fp.fused_pbt(
            wl, population=4, generations=4, steps_per_gen=4, gen_chunk=2, step_chunk=2
        )


def test_snapshot_last_false_skips_final_save(wl, tmp_path):
    """A bench-style caller consumes the result immediately; the final
    launch's snapshot (a multi-GB, minutes-long host fetch at ResNet
    scale on this platform) must be skippable without losing mid-sweep
    crash protection."""
    import os

    ckpt = str(tmp_path / "ck")
    fp.fused_pbt(wl, checkpoint_dir=ckpt, snapshot_every=2, snapshot_last=False, **KW)
    steps = sorted(int(d) for d in os.listdir(ckpt) if d.isdigit())
    assert steps == [2]  # 4 launches: mid-sweep save kept, final skipped


def test_momentum_dtype_mismatch_refuses_resume(shared_workload, tmp_path, monkeypatch):
    """Momentum storage dtype is carried-state structure: resuming an
    f32-momentum snapshot under MPI_OPT_TPU_MOMENTUM_DTYPE=bfloat16 must
    refuse cleanly (config mismatch), not crash in the scan carry."""
    # an instance of its own: the refused resume builds the bfloat16 trainer first
    wl = shared_workload("fashion_mlp", label="momentum dtype flips", **MLP)
    ckpt = str(tmp_path / "ck")
    real = fp.run_fused_pbt
    calls = {"n": 0}

    def crashing(*a, **k):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("simulated TPU worker crash")
        return real(*a, **k)

    monkeypatch.setattr(fp, "run_fused_pbt", crashing)
    with pytest.raises(RuntimeError, match="simulated"):
        fp.fused_pbt(wl, checkpoint_dir=ckpt, **KW)
    monkeypatch.setattr(fp, "run_fused_pbt", real)
    monkeypatch.setenv("MPI_OPT_TPU_MOMENTUM_DTYPE", "bfloat16")
    with pytest.raises(ValueError, match="different sweep"):
        fp.fused_pbt(wl, checkpoint_dir=ckpt, **KW)


def test_checkpoint_config_mismatch_raises(wl, tmp_path):
    ckpt = str(tmp_path / "ck")
    fp.fused_pbt(wl, checkpoint_dir=ckpt, **KW)
    other = dict(KW, seed=KW["seed"] + 1)
    with pytest.raises(ValueError, match="different sweep"):
        fp.fused_pbt(wl, checkpoint_dir=ckpt, **other)


def test_sha_crash_resume_bit_identical(wl, tmp_path, monkeypatch):
    """Rung-granular SHA recovery: kill after rung 2, resume, and the
    final result must equal the uninterrupted sweep exactly."""
    import mpi_opt_tpu.train.fused_asha as fa

    kw = dict(n_trials=9, min_budget=2, max_budget=18, eta=3, seed=4)
    whole = fa.fused_sha(wl, **kw)

    real = fa._cut_and_gather
    calls = {"n": 0}

    def crashing(*a, **k):
        calls["n"] += 1
        if calls["n"] == 2:  # die at the second rung's cut
            raise RuntimeError("simulated TPU worker crash")
        return real(*a, **k)

    ckpt = str(tmp_path / "sha")
    monkeypatch.setattr(fa, "_cut_and_gather", crashing)
    with pytest.raises(RuntimeError, match="simulated"):
        fa.fused_sha(wl, checkpoint_dir=ckpt, **kw)
    monkeypatch.setattr(fa, "_cut_and_gather", real)

    resumed = fa.fused_sha(wl, checkpoint_dir=ckpt, **kw)
    assert resumed["best_score"] == whole["best_score"]
    assert resumed["best_trial"] == whole["best_trial"]
    np.testing.assert_array_equal(resumed["stop_rung"], whole["stop_rung"])
    np.testing.assert_array_equal(resumed["last_score"], whole["last_score"])
    assert resumed["best_params"] == whole["best_params"]


def test_sha_resume_after_completion(wl, tmp_path, monkeypatch):
    import mpi_opt_tpu.train.fused_asha as fa

    kw = dict(n_trials=6, min_budget=2, max_budget=6, eta=3, seed=5)
    ckpt = str(tmp_path / "sha")
    first = fa.fused_sha(wl, checkpoint_dir=ckpt, **kw)

    def boom(*a, **k):
        raise AssertionError("completed sweep re-trained a rung")

    # a completed sweep must replay from its final snapshot without
    # touching the trainer
    monkeypatch.setattr(type(fa.workload_arrays(wl, 0, None)[0]), "train_segment",
                        property(lambda self: boom), raising=False)
    again = fa.fused_sha(wl, checkpoint_dir=ckpt, **kw)
    assert again["best_score"] == first["best_score"]
    assert again["best_trial"] == first["best_trial"]


def test_sha_checkpoint_config_mismatch_raises(wl, tmp_path):
    import mpi_opt_tpu.train.fused_asha as fa

    ckpt = str(tmp_path / "sha")
    fa.fused_sha(wl, n_trials=6, min_budget=2, max_budget=6, eta=3, seed=5,
                 checkpoint_dir=ckpt)
    with pytest.raises(ValueError, match="different sweep"):
        fa.fused_sha(wl, n_trials=9, min_budget=2, max_budget=6, eta=3, seed=5,
                     checkpoint_dir=ckpt)


# -- chaos preempt/crash -> resume on fused TPE and BOHB (ISSUE 6) ---------
#
# The resume drill matrix above covers PBT launches and SHA rungs; these
# close the gap for TPE batch boundaries and BOHB's bracket/rung chain —
# both the SIGKILL-shaped crash (mid-sweep exception) and the SIGTERM-
# shaped graceful preemption (shutdown flag honored at the next
# launch_boundary, off-cadence snapshot flushed, SweepInterrupted).


def _arm_preempt(monkeypatch, after_boundaries: int):
    """Deterministic preemption: shutdown.requested() flips true after
    N launch_boundary polls (stubbed flag, not a real signal, so the
    drill is exact about WHERE the drain lands)."""
    from mpi_opt_tpu.health import shutdown as sm

    calls = {"n": 0}

    def requested():
        calls["n"] += 1
        return calls["n"] > after_boundaries

    monkeypatch.setattr(sm, "requested", requested)
    monkeypatch.setattr(sm, "active_signal", lambda: "SIGTERM")


def test_tpe_preempt_drain_resume_bit_identical(wl, tpe_whole, tmp_path, monkeypatch):
    import mpi_opt_tpu.train.fused_tpe as ft
    from mpi_opt_tpu.health import SweepInterrupted

    kw, whole = TPE_KW, tpe_whole

    ckpt = str(tmp_path / "tpe")
    _arm_preempt(monkeypatch, after_boundaries=1)
    with pytest.raises(SweepInterrupted) as exc:
        ft.fused_tpe(wl, checkpoint_dir=ckpt, **kw)
    assert "tpe generation 2/3" in exc.value.at  # drained mid-sweep
    monkeypatch.undo()

    resumed = ft.fused_tpe(wl, checkpoint_dir=ckpt, **kw)
    np.testing.assert_array_equal(resumed["best_curve"], whole["best_curve"])
    np.testing.assert_array_equal(resumed["obs_scores"], whole["obs_scores"])
    np.testing.assert_array_equal(resumed["obs_unit"], whole["obs_unit"])
    assert resumed["best_score"] == whole["best_score"]


def test_tpe_crash_resume_reuses_snapshot_boundaries(wl, tpe_whole, tmp_path, monkeypatch):
    """SIGKILL-shaped death one generation after the last snapshot:
    the resume re-trains ONLY the incomplete generations (the crashing
    stub proves gen 1's program never re-runs)."""
    import mpi_opt_tpu.train.fused_tpe as ft

    kw, whole = TPE_KW, tpe_whole

    real = ft.tpe_generation
    calls = {"n": 0}

    def crashing(*a, **k):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("simulated TPU worker crash")
        return real(*a, **k)

    ckpt = str(tmp_path / "tpe")
    monkeypatch.setattr(ft, "tpe_generation", crashing)
    with pytest.raises(RuntimeError, match="simulated"):
        ft.fused_tpe(wl, checkpoint_dir=ckpt, **kw)
    calls["n"] = 10  # any further crash-stub hit would raise; reset gate
    seen = {"gens": 0}

    def counting(*a, **k):
        seen["gens"] += 1
        return real(*a, **k)

    monkeypatch.setattr(ft, "tpe_generation", counting)
    resumed = ft.fused_tpe(wl, checkpoint_dir=ckpt, **kw)
    assert seen["gens"] == 2  # gen 0 replayed from snapshot, 1-2 re-trained
    np.testing.assert_array_equal(resumed["best_curve"], whole["best_curve"])
    assert resumed["best_score"] == whole["best_score"]


def test_bohb_crash_resume_bit_identical(wl, bohb_whole, tmp_path, monkeypatch):
    """Bracket-granular BOHB recovery: die inside the SECOND bracket;
    the resume replays bracket 0 from its final snapshot (its persisted
    cohort reused) and finishes identically to an unkilled run."""
    import mpi_opt_tpu.train.fused_asha as fa
    from mpi_opt_tpu.train.fused_bohb import fused_bohb

    kw, whole = BOHB_KW, bohb_whole

    real = fa.fused_sha
    calls = {"n": 0}

    def crashing(*a, **k):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("simulated TPU worker crash")
        return real(*a, **k)

    ckpt = str(tmp_path / "bohb")
    monkeypatch.setattr(fa, "fused_sha", crashing)
    with pytest.raises(RuntimeError, match="simulated"):
        fused_bohb(wl, checkpoint_dir=ckpt, **kw)
    monkeypatch.setattr(fa, "fused_sha", real)

    resumed = fused_bohb(wl, checkpoint_dir=ckpt, **kw)
    assert resumed["best_score"] == whole["best_score"]
    assert resumed["best_params"] == whole["best_params"]
    assert [b["best_score"] for b in resumed["brackets"]] == [
        b["best_score"] for b in whole["brackets"]
    ]


def test_bohb_preempt_drain_resume_bit_identical(wl, bohb_whole, tmp_path, monkeypatch):
    from mpi_opt_tpu.health import SweepInterrupted
    from mpi_opt_tpu.train.fused_bohb import fused_bohb

    kw, whole = BOHB_KW, bohb_whole

    ckpt = str(tmp_path / "bohb")
    _arm_preempt(monkeypatch, after_boundaries=2)
    with pytest.raises(SweepInterrupted):
        fused_bohb(wl, checkpoint_dir=ckpt, **kw)
    monkeypatch.undo()

    resumed = fused_bohb(wl, checkpoint_dir=ckpt, **kw)
    assert resumed["best_score"] == whole["best_score"]
    assert resumed["best_params"] == whole["best_params"]


def test_pbt_crash_resume_journal_identical_to_unkilled(wl, tmp_path, monkeypatch):
    """The fused-ledger acceptance core at library level: a crashed +
    resumed sweep's journal holds the IDENTICAL record set an unkilled
    run writes (ids, members, boundaries, params, scores), with the
    already-journaled boundary VERIFIED (not re-written) on resume."""
    import json

    from mpi_opt_tpu.ledger import SweepLedger, validate_ledger

    space = wl.default_space()

    def open_ledger(path):
        led = SweepLedger(path)
        led.ensure_header(
            {"mode": "fused", "granularity": "generation", "algorithm": "pbt",
             "seed": KW["seed"], "space_hash": space.space_hash()}
        )
        return led

    clean_led = str(tmp_path / "clean.jsonl")
    led = open_ledger(clean_led)
    fp.fused_pbt(wl, ledger=led, **KW)
    led.close()

    real = fp.run_fused_pbt
    calls = {"n": 0}

    def crashing(*a, **k):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("simulated TPU worker crash")
        return real(*a, **k)

    kill_led = str(tmp_path / "killed.jsonl")
    ckpt = str(tmp_path / "ck")
    led = open_ledger(kill_led)
    monkeypatch.setattr(fp, "run_fused_pbt", crashing)
    with pytest.raises(RuntimeError, match="simulated"):
        fp.fused_pbt(wl, checkpoint_dir=ckpt, ledger=led, **KW)
    led.close()
    monkeypatch.setattr(fp, "run_fused_pbt", real)

    led = open_ledger(kill_led)
    resumed = fp.fused_pbt(wl, checkpoint_dir=ckpt, ledger=led, **KW)
    led.close()
    # snapshot cadence is every launch here, so the resume re-journals
    # exactly the post-crash generations and verifies none
    assert resumed["journal"]["written"] == 2 * KW["population"]

    def records(path):
        return [
            {k: r[k] for k in ("trial_id", "member", "boundary",
                               "boundary_size", "params", "status", "score",
                               "step")}
            for r in (json.loads(l) for l in open(path).read().splitlines()[1:])
        ]

    assert records(kill_led) == records(clean_led)
    assert validate_ledger(kill_led) == []
