"""Wave-scheduled fused PBT: populations beyond device residency.

The tentpole contract (ISSUE 4): with ``wave_size=W < population``, each
generation trains resident waves of W members in sequence, staging cold
members' params+momentum on host between waves, while exploit/explore at
the generation boundary operates over the FULL population. On the CPU
backend wave mode is BIT-IDENTICAL to resident mode (stronger than the
step_chunk documented-equivalent standard): batch RNG is shared
population-wide, member RNG windows the full split, and the
unit->hparams mapping is applied in-program (eager/compiled transform
ulps would otherwise flip discrete augmentation draws — see
``_wave_train_program``).
"""

import os
import signal

import numpy as np
import pytest

import jax

import mpi_opt_tpu.train.fused_pbt as fp
from mpi_opt_tpu.health import shutdown
from mpi_opt_tpu.ops.pbt import PBTConfig


@pytest.fixture(scope="module")
def wl(shared_workload):
    return shared_workload("fashion_mlp", n_train=256, n_val=128)


KW = dict(population=8, generations=3, steps_per_gen=4, seed=2)


@pytest.fixture(scope="module")
def waves_of_3(wl):
    """The undisturbed wave sweep the drills below end equal to, run
    once a module (read, never written into)."""
    return fp.fused_pbt(wl, wave_size=3, **KW)


def _tree_equal(a, b):
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def test_wave_mode_bit_identical_to_resident(wl):
    """pop <= residency parity: a forced wave cap (including a
    NON-dividing one — balanced waves [3,3,2]) reproduces the resident
    scan bit-for-bit: curves, hparams, winner, params AND momentum."""
    res = fp.fused_pbt(wl, **KW)
    wav = fp.fused_pbt(wl, wave_size=3, **KW)
    np.testing.assert_array_equal(res["best_curve"], wav["best_curve"])
    np.testing.assert_array_equal(res["mean_curve"], wav["mean_curve"])
    np.testing.assert_array_equal(res["unit"], wav["unit"])
    assert res["best_score"] == wav["best_score"]
    assert res["best_params"] == wav["best_params"]
    assert res["member_failures"] == wav["member_failures"]
    assert _tree_equal(res["state"].params, wav["state"].params)
    assert _tree_equal(res["state"].momentum, wav["state"].momentum)
    # staging observability: cold members really moved through host
    assert wav["n_waves"] == 3 and wav["wave_lens"] == [3, 3, 2]
    assert wav["staged_bytes"] > 0
    assert wav["stage_transfer_s"] >= 0 and wav["stage_overlap_s"] >= 0


def test_wave_mode_bit_identical_on_mesh(shared_workload):
    """Same parity on the virtual 8-device CPU mesh: waves shard over
    'pop' (W=8 divides the axis) and the result still matches the
    resident sharded sweep exactly."""
    from mpi_opt_tpu.parallel.mesh import make_mesh

    wl = shared_workload("fashion_mlp", label="pop8 data1 mesh", n_train=256, n_val=128)
    mesh = make_mesh(n_pop=8, n_data=1)
    kw = dict(population=16, generations=2, steps_per_gen=3, seed=3)
    res = fp.fused_pbt(wl, mesh=mesh, **kw)
    wav = fp.fused_pbt(wl, mesh=mesh, wave_size=8, **kw)
    np.testing.assert_array_equal(res["best_curve"], wav["best_curve"])
    np.testing.assert_array_equal(res["unit"], wav["unit"])
    assert res["best_score"] == wav["best_score"]
    assert _tree_equal(res["state"].params, wav["state"].params)


def test_wave_cap_at_or_above_population_runs_resident(wl):
    """wave_size >= population means everything fits: the resident path
    runs (no staging machinery, no wave keys in the result)."""
    res = fp.fused_pbt(wl, wave_size=KW["population"], **KW)
    assert "wave_size" not in res
    assert "staged_bytes" not in res


def test_full_population_exploit_crosses_wave_boundaries(wl):
    """pop > residency semantics: truncation selection must rank ALL
    members, not each wave separately. With truncation 1/8 (n_cut=1)
    every loser exploits THE global-best member — the test asserts that
    a loser in one wave selected a source member from a DIFFERENT wave
    (the cold member with the global-best score), i.e. winner weights
    crossed a wave boundary through the host pool."""
    spy = []
    real = fp._wave_exploit

    def recording(key, unit, scores, **kw):
        out = real(key, unit, scores, **kw)
        spy.append((np.asarray(scores), np.asarray(out[1])))
        return out

    fp._wave_exploit = recording
    try:
        wav = fp.fused_pbt(
            wl, wave_size=2, cfg=PBTConfig(truncation_frac=1 / 8), **KW
        )
    finally:
        fp._wave_exploit = real
    assert len(spy) == KW["generations"]
    wave_of = lambda i: i // 2  # wave_size=2: members [2k, 2k+1] share a wave
    crossed = 0
    for scores, src in spy:
        exploited = np.nonzero(src != np.arange(len(src)))[0]
        assert len(exploited) == 1  # n_cut=1: exactly one loser per gen
        for i in exploited:
            # full-population semantics: the source is the GLOBAL best
            assert src[i] == int(np.argmax(scores))
            if wave_of(src[i]) != wave_of(i):
                crossed += 1
    assert crossed > 0, "pinned seed should exploit across a wave boundary"
    assert 0.0 <= wav["best_score"] <= 1.0


def test_wave_crash_resume_bit_identical(wl, waves_of_3, tmp_path):
    """Hard crash mid-sweep: resume from the generation-boundary
    snapshot finishes with the uninterrupted sweep's exact result."""
    whole = waves_of_3
    real = fp._run_wave
    calls = {"n": 0}

    def crashing(*a, **k):
        calls["n"] += 1
        if calls["n"] == 5:  # gen 0 = 3 waves; die inside gen 1
            raise RuntimeError("simulated TPU worker crash")
        return real(*a, **k)

    ckpt = str(tmp_path / "ck")
    fp._run_wave = crashing
    try:
        with pytest.raises(RuntimeError, match="simulated"):
            fp.fused_pbt(wl, wave_size=3, checkpoint_dir=ckpt, **KW)
    finally:
        fp._run_wave = real
    resumed = fp.fused_pbt(wl, wave_size=3, checkpoint_dir=ckpt, **KW)
    np.testing.assert_array_equal(resumed["best_curve"], whole["best_curve"])
    np.testing.assert_array_equal(resumed["unit"], whole["unit"])
    assert resumed["best_score"] == whole["best_score"]
    assert len(resumed["launch_walls"]) == KW["generations"]


def test_wave_preempt_between_waves_resumes_without_retraining(wl, waves_of_3, tmp_path):
    """Graceful shutdown BETWEEN waves flushes a mid-generation
    snapshot; the resume re-trains only the remaining waves (completed
    waves' states come from the host pools) and still reproduces the
    clean run bit-for-bit."""
    whole = waves_of_3
    ckpt = str(tmp_path / "ck")
    real = fp._run_wave
    calls = {"n": 0}

    def preempting(*a, **k):
        calls["n"] += 1
        out = real(*a, **k)
        if calls["n"] == 4:  # after gen 1 wave 1 -> drain at wave boundary
            os.kill(os.getpid(), signal.SIGTERM)
        return out

    with shutdown.ShutdownGuard():
        fp._run_wave = preempting
        try:
            with pytest.raises(shutdown.SweepInterrupted):
                fp.fused_pbt(wl, wave_size=3, checkpoint_dir=ckpt, **KW)
        finally:
            fp._run_wave = real
    counting = {"n": 0}

    def counted(*a, **k):
        counting["n"] += 1
        return real(*a, **k)

    fp._run_wave = counted
    try:
        resumed = fp.fused_pbt(wl, wave_size=3, checkpoint_dir=ckpt, **KW)
    finally:
        fp._run_wave = real
    # 2 waves left in gen 1 + 3 in gen 2; the snapshot's completed wave
    # is NOT re-trained
    assert counting["n"] == 5
    np.testing.assert_array_equal(resumed["best_curve"], whole["best_curve"])
    assert resumed["best_score"] == whole["best_score"]
    assert _tree_equal(resumed["state"].params, whole["state"].params)


def test_wave_corrupt_snapshot_falls_back_bit_identical(wl, waves_of_3, tmp_path):
    """The ISSUE-5 acceptance drill for wave sweeps: kill mid-sweep,
    bit-rot the LATEST snapshot, resume — restore quarantines the bad
    step (kept as evidence, not deleted), falls back to the previous
    verified generation boundary, and the finished sweep is still
    bit-identical to the uninterrupted run; fsck reports the
    quarantine."""
    import json

    from mpi_opt_tpu.utils import integrity
    from mpi_opt_tpu.workloads.chaos import inject_corrupt_save

    whole = waves_of_3
    real = fp._run_wave
    calls = {"n": 0}

    def crashing(*a, **k):
        calls["n"] += 1
        if calls["n"] == 8:  # gens 0,1 = 6 waves; die inside gen 2 —
            # boundary snapshots for steps 3 (gen 0) AND 6 (gen 1) exist
            raise RuntimeError("simulated TPU worker crash")
        return real(*a, **k)

    ckpt = str(tmp_path / "ck")
    fp._run_wave = crashing
    try:
        with pytest.raises(RuntimeError, match="simulated"):
            fp.fused_pbt(wl, wave_size=3, checkpoint_dir=ckpt, **KW)
    finally:
        fp._run_wave = real

    inject_corrupt_save(ckpt)  # bit-rot the latest step (6)
    events = []
    integrity.set_observer(lambda event, **f: events.append((event, f)))
    try:
        resumed = fp.fused_pbt(wl, wave_size=3, checkpoint_dir=ckpt, **KW)
    finally:
        integrity.clear_observer()
    assert [e for e, _ in events] == [("snapshot_corrupt")]
    assert events[0][1]["step"] == 6
    assert os.path.isdir(os.path.join(ckpt, "6.corrupt"))  # quarantined, kept
    # last-good fallback (gen-0 boundary) + carried-key chain => the
    # exact result the unkilled sweep produced
    np.testing.assert_array_equal(resumed["best_curve"], whole["best_curve"])
    np.testing.assert_array_equal(resumed["unit"], whole["unit"])
    assert resumed["best_score"] == whole["best_score"]
    assert resumed["best_params"] == whole["best_params"]
    assert _tree_equal(resumed["state"].params, whole["state"].params)
    assert _tree_equal(resumed["state"].momentum, whole["state"].momentum)
    # fsck: the audit sees the quarantine and a clean remaining tree
    import contextlib
    import io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = integrity.fsck_main([ckpt, "--json"])
    assert rc == 0
    rep = json.loads(buf.getvalue())
    assert "6.corrupt" in rep["quarantined"]
    assert all(s["status"] == "verified" for s in rep["steps"])


def test_wave_resume_after_completion_runs_nothing(wl, tmp_path):
    ckpt = str(tmp_path / "ck")
    first = fp.fused_pbt(wl, wave_size=3, checkpoint_dir=ckpt, **KW)
    real = fp._run_wave

    def boom(*a, **k):
        raise AssertionError("completed sweep re-ran a wave")

    fp._run_wave = boom
    try:
        again = fp.fused_pbt(wl, wave_size=3, checkpoint_dir=ckpt, **KW)
    finally:
        fp._run_wave = real
    np.testing.assert_array_equal(again["best_curve"], first["best_curve"])
    assert again["best_score"] == first["best_score"]


def test_wave_snapshot_refused_by_resident_resume(wl, tmp_path):
    """wave_size is part of the checkpoint config identity: the wave
    payload (host pools + perm) must not load into a resident run."""
    ckpt = str(tmp_path / "ck")
    fp.fused_pbt(wl, wave_size=3, checkpoint_dir=ckpt, **KW)
    with pytest.raises(ValueError, match="different sweep"):
        fp.fused_pbt(wl, checkpoint_dir=ckpt, **KW)


def test_wave_rejects_launch_chunking(wl):
    with pytest.raises(ValueError, match="ambiguous"):
        fp.fused_pbt(wl, wave_size=3, step_chunk=2, **KW)
    with pytest.raises(ValueError, match="ambiguous"):
        fp.fused_pbt(wl, wave_size=3, gen_chunk=2, **KW)


# -- staging engine unit tests -------------------------------------------


def test_staging_engine_roundtrip_and_accounting():
    import jax.numpy as jnp

    from mpi_opt_tpu.train import staging

    eng = staging.StagingEngine()
    pool = {"a": np.zeros((8, 4), np.float32)}
    dev = jnp.ones((2, 4), jnp.float32) * 7

    eng.stage_out({"state": {"a": dev}, "scores": jnp.zeros((2,))},
                  lambda host: staging.write_rows(pool, 2, host["state"]))
    eng.drain()
    assert np.array_equal(pool["a"][2:4], np.full((2, 4), 7.0))
    assert np.array_equal(pool["a"][:2], np.zeros((2, 4)))
    assert eng.staged_bytes == 2 * 4 * 4 + 2 * 4  # state + f32 scores
    assert eng.transfer_s >= 0 and eng.wait_s >= 0
    eng.close()


def test_staging_engine_propagates_worker_errors():
    from mpi_opt_tpu.train import staging

    eng = staging.StagingEngine()

    def bad(host):
        raise RuntimeError("writer exploded")

    eng.stage_out({"x": np.zeros(3)}, bad)
    with pytest.raises(RuntimeError, match="writer exploded"):
        eng.drain()
    eng.close()


def test_stage_in_applies_permutation():
    from mpi_opt_tpu.train import staging

    pool = {"a": np.arange(8, dtype=np.float32).reshape(8, 1)}
    dev = staging.stage_in(pool, np.array([5, 1, 6]))
    assert np.asarray(dev["a"]).ravel().tolist() == [5.0, 1.0, 6.0]


def test_estimate_wave_size_respects_budget_and_population(wl):
    from mpi_opt_tpu.train.common import workload_arrays
    from mpi_opt_tpu.train.staging import estimate_wave_size, tree_bytes

    trainer, _, tx, *_ = workload_arrays(wl, 0, None)
    # a generous budget fits everything -> resident signal
    assert estimate_wave_size(trainer, tx[:2], 8, budget_bytes=1 << 40) == 8
    # a tiny budget still returns a runnable wave of at least 1
    assert estimate_wave_size(trainer, tx[:2], 8, budget_bytes=1) == 1
    # a budget sized for ~2 members (past the 0.35 safety factor) caps
    # the wave below the population
    params_sd = jax.eval_shape(trainer.init_fn, jax.random.key(0), tx[:2])
    member = 2 * tree_bytes(params_sd)  # params + f32 momentum
    w = estimate_wave_size(trainer, tx[:2], 8, budget_bytes=int(member * 2 / 0.35))
    assert 1 <= w <= 2


def test_estimate_wave_size_budget_resolution_order(wl, monkeypatch):
    """ISSUE 10 satellite: auto mode resolves its budget as explicit
    argument > MPI_OPT_TPU_DEVICE_BYTES env (operator override) >
    MEASURED memory_stats bytes_limit (obs/memory.py) > 8 GiB default —
    one assertion per rung of the order."""
    from mpi_opt_tpu.obs import memory as obs_memory
    from mpi_opt_tpu.train.common import workload_arrays
    from mpi_opt_tpu.train.staging import estimate_wave_size, tree_bytes

    trainer, _, tx, *_ = workload_arrays(wl, 0, None)
    params_sd = jax.eval_shape(trainer.init_fn, jax.random.key(0), tx[:2])
    member = 2 * tree_bytes(params_sd)  # params + f32 momentum

    def budget_for(members):  # a budget the 0.35 factor maps to ~members
        return int(member * members / 0.35) + 1024

    # 1) the measured device capacity is used when nothing overrides it
    # (the CPU backend reports no memory_stats, so the measurement is
    # injected — on a real TPU this is the allocator's bytes_limit)
    monkeypatch.delenv("MPI_OPT_TPU_DEVICE_BYTES", raising=False)
    monkeypatch.setattr(obs_memory, "measured_budget", lambda device=None: budget_for(4))
    assert estimate_wave_size(trainer, tx[:2], 8) == 4
    # 2) the env var is the operator's EXPLICIT override: it beats the
    # measurement (sizing waves for a device other than the one present)
    monkeypatch.setenv("MPI_OPT_TPU_DEVICE_BYTES", str(budget_for(2)))
    assert estimate_wave_size(trainer, tx[:2], 8) == 2
    # 3) an explicit budget_bytes argument beats both
    assert estimate_wave_size(trainer, tx[:2], 8, budget_bytes=1) == 1
    # 4) nothing available -> the conservative 8 GiB default (which this
    # tiny MLP trivially fits: resident signal)
    monkeypatch.delenv("MPI_OPT_TPU_DEVICE_BYTES")
    monkeypatch.setattr(obs_memory, "measured_budget", lambda device=None: None)
    assert estimate_wave_size(trainer, tx[:2], 8) == 8


def test_staging_engine_beats_heartbeat_per_transfer(tmp_path):
    """ISSUE 6 satellite: the background transfer thread beats the rank
    heartbeat per completed transfer, so a hung host<->device stage is
    caught by --stall-timeout instead of freezing a wave silently while
    the main thread parks in drain()."""
    import jax.numpy as jnp

    from mpi_opt_tpu.health import heartbeat
    from mpi_opt_tpu.train import staging

    hb_path = str(tmp_path / "rank.hb")
    heartbeat.configure(hb_path)
    try:
        eng = staging.StagingEngine()
        try:
            for _ in range(3):
                eng.stage_out({"x": jnp.ones((8,))}, lambda host: None)
            eng.drain()
        finally:
            eng.close()
        rec = heartbeat.read_beat(hb_path)
        assert rec is not None and rec["beats"] >= 3
        assert rec["progress"]["stage"] == "staging transfer"
        assert rec["progress"]["transfers"] == 3
        assert eng.transfers == 3
    finally:
        heartbeat.deconfigure()


def test_wave_journal_identical_to_resident(wl, tmp_path):
    """Wave scheduling is bit-identical to resident mode, so one ledger
    records the same trajectory either way: the journaled record sets
    (ids, members, boundaries, params, scores) must be EQUAL — which is
    also why wave_size is deliberately not ledger identity."""
    import json

    from mpi_opt_tpu.ledger import SweepLedger, validate_ledger

    space = wl.default_space()
    kw = dict(population=6, generations=2, steps_per_gen=3, seed=2)

    def run(path, wave_size):
        led = SweepLedger(path)
        led.ensure_header(
            {"mode": "fused", "granularity": "generation", "algorithm": "pbt",
             "seed": kw["seed"], "space_hash": space.space_hash()}
        )
        res = fp.fused_pbt(wl, wave_size=wave_size, ledger=led, **kw)
        led.close()
        return res

    resident = str(tmp_path / "resident.jsonl")
    waved = str(tmp_path / "waved.jsonl")
    r_res = run(resident, wave_size=0)
    r_wav = run(waved, wave_size=4)  # 2 waves, non-dividing split
    assert r_res["journal"]["written"] == r_wav["journal"]["written"] == 12
    assert validate_ledger(resident) == [] and validate_ledger(waved) == []

    def records(path):
        keep = ("trial_id", "member", "boundary", "boundary_size", "params",
                "status", "score", "step")
        return [
            {k: r[k] for k in keep}
            for r in map(json.loads, open(path).read().splitlines()[1:])
        ]

    assert records(resident) == records(waved)
