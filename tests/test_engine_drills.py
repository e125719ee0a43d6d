"""Engine parity + chaos drills for the newly wave-capable algorithms.

ISSUE 18: the fused launch/stage/drain/OOM skeleton now lives ONCE in
train/engine.py, so wave scheduling, OOM wave-halving, and the
drain/durability contracts extend from fused PBT to fused SHA, TPE, and
BOHB. These tests pin the two acceptance bars for each algorithm:

- PARITY: wave mode reproduces the resident sweep bit-for-bit on the
  CPU backend, for dividing AND non-dividing wave sizes;
- DRILLS: a run hit by an injected device OOM (``chaos.inject_oom``,
  wave kind), a hard crash, or a SIGTERM preemption ends with results
  — and a ledger — record-identical to an undisturbed run.

PBT's equivalents live in test_fused_waves.py / test_resources.py; the
drills here go through each adapter's own ``_run_wave`` seam, which the
shared engine resolves at call time precisely so tests can intercept it.
"""

import json
import os
import signal

import numpy as np
import pytest

import jax

import mpi_opt_tpu.train.fused_asha as fa
import mpi_opt_tpu.train.fused_tpe as ft
from mpi_opt_tpu.health import shutdown
from mpi_opt_tpu.ledger import SweepLedger, validate_ledger
from mpi_opt_tpu.utils import resources
from mpi_opt_tpu.workloads.chaos import inject_oom


@pytest.fixture(scope="module")
def wl(shared_workload):
    return shared_workload("fashion_mlp", n_train=256, n_val=128)


SHA_KW = dict(n_trials=8, min_budget=2, max_budget=8, eta=2, seed=3)
TPE_KW = dict(n_trials=10, batch=4, budget=4, seed=5)
BOHB_KW = dict(max_budget=4, eta=2, seed=7)

# the undisturbed sweeps the cases below are held equal to, each run once
# a module (results are read, never written into)


@pytest.fixture(scope="module")
def sha_resident(wl):
    return fa.fused_sha(wl, **SHA_KW)


@pytest.fixture(scope="module")
def sha_waves_of_4(wl):
    return fa.fused_sha(wl, wave_size=4, **SHA_KW)


@pytest.fixture(scope="module")
def tpe_resident(wl):
    return ft.fused_tpe(wl, **TPE_KW)


@pytest.fixture(scope="module")
def tpe_waves_of_2(wl):
    return ft.fused_tpe(wl, wave_size=2, **TPE_KW)


@pytest.fixture(scope="module")
def bohb_waves_of_2(wl):
    from mpi_opt_tpu.train.fused_bohb import fused_bohb

    return fused_bohb(wl, wave_size=2, **BOHB_KW)


def _tree_equal(a, b):
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def _ledger(path, space, algorithm, seed):
    led = SweepLedger(str(path))
    led.ensure_header(
        {
            "mode": "fused",
            "granularity": "generation",
            "algorithm": algorithm,
            "seed": seed,
            "space_hash": space.space_hash(),
        }
    )
    return led


def _records(path):
    keep = ("trial_id", "member", "boundary", "boundary_size", "params",
            "status", "score", "step")
    with open(path) as f:
        return [
            {k: r.get(k) for k in keep}
            for r in map(json.loads, f.read().splitlines()[1:])
        ]


# -- parity: wave == resident, dividing and non-dividing splits -------------


@pytest.mark.parametrize("wave_size", [3, 4])  # [3,3,2] and [4,4]
def test_sha_wave_bit_identical_to_resident(wl, sha_resident, wave_size):
    res = sha_resident
    wav = fa.fused_sha(wl, wave_size=wave_size, **SHA_KW)
    np.testing.assert_array_equal(res["last_score"], wav["last_score"])
    np.testing.assert_array_equal(res["stop_rung"], wav["stop_rung"])
    assert res["best_score"] == wav["best_score"]
    assert res["best_trial"] == wav["best_trial"]
    assert res["best_params"] == wav["best_params"]
    assert res["rung_history"] == wav["rung_history"]
    assert res["member_failures"] == wav["member_failures"]
    # staging observability: rung cohorts really moved through host
    assert wav["wave_size"] == wave_size
    assert wav["staged_bytes"] > 0
    assert "wave_size" not in res  # resident result shape unchanged


@pytest.mark.parametrize("wave_size", [2, 3])  # [2,2] and [2,1] per gen of 4
def test_tpe_wave_bit_identical_to_resident(wl, tpe_resident, wave_size):
    res = tpe_resident
    wav = ft.fused_tpe(wl, wave_size=wave_size, **TPE_KW)
    np.testing.assert_array_equal(res["obs_unit"], wav["obs_unit"])
    np.testing.assert_array_equal(res["obs_scores"], wav["obs_scores"])
    np.testing.assert_array_equal(res["best_curve"], wav["best_curve"])
    assert res["best_score"] == wav["best_score"]
    assert res["best_params"] == wav["best_params"]
    assert res["member_failures"] == wav["member_failures"]
    assert wav["wave_size"] == wave_size
    assert wav["staged_bytes"] > 0
    assert "wave_size" not in res


def test_bohb_wave_matches_resident(wl, bohb_waves_of_2):
    from mpi_opt_tpu.train.fused_bohb import fused_bohb

    res = fused_bohb(wl, **BOHB_KW)
    wav = bohb_waves_of_2
    assert res["best_score"] == wav["best_score"]
    assert res["best_params"] == wav["best_params"]
    assert res["member_failures"] == wav["member_failures"]
    for b_res, b_wav in zip(res["brackets"], wav["brackets"]):
        assert b_res["rung_sizes"] == b_wav["rung_sizes"]
        assert b_res["best_score"] == b_wav["best_score"]
        assert b_res["n_model_sampled"] == b_wav["n_model_sampled"]
    # at least one bracket's cohort exceeded the cap and staged
    assert wav["staged_bytes"] > 0 and wav["n_waves"] > 0


# -- drill: injected device OOM -> wave-halving, record-identical -----------


def test_sha_oom_backoff_record_identical(wl, tmp_path):
    """An OOM injected into rung 2's wave (W=4: rung 1 runs two waves,
    ordinals 1-2; rung 2's single wave is ordinal 3) halves the cap,
    re-runs THAT rung from its already-derived keys, and the sweep ends
    bit-identical to the clean run with a record-identical ledger."""
    space = wl.default_space()
    led_a = _ledger(tmp_path / "clean.jsonl", space, "asha", SHA_KW["seed"])
    try:
        clean = fa.fused_sha(wl, wave_size=4, ledger=led_a, **SHA_KW)
    finally:
        led_a.close()

    events = []
    resources.set_observer(lambda e, **f: events.append((e, f)))
    inj, uninstall = inject_oom(at_launch=3, kind="wave")
    led_b = _ledger(tmp_path / "oom.jsonl", space, "asha", SHA_KW["seed"])
    try:
        faulted = fa.fused_sha(
            wl, wave_size=4, oom_backoff=2, ledger=led_b, **SHA_KW
        )
    finally:
        led_b.close()
        uninstall()
        resources.clear_observer()

    assert inj.faults_fired == 1
    assert faulted["oom_backoffs"] == 1
    assert faulted["wave_size"] == 2  # settled cap after one halving
    assert [e for e, _ in events].count("oom_backoff") == 1
    assert clean["best_score"] == faulted["best_score"]
    assert clean["best_params"] == faulted["best_params"]
    assert clean["rung_history"] == faulted["rung_history"]
    np.testing.assert_array_equal(clean["last_score"], faulted["last_score"])
    assert validate_ledger(led_b.path) == []
    assert _records(tmp_path / "clean.jsonl") == _records(tmp_path / "oom.jsonl")


def test_pbt_oom_backoff_record_identical(wl, tmp_path):
    """Fused PBT rides the SAME shared engine (ISSUE 20 closes the
    chaos matrix): an OOM injected into generation 2's first wave
    (W=4 over pop 8: two waves per gen, ordinal 3) halves the cap,
    re-runs that generation's waves from the already-derived keys, and
    the sweep ends bit-identical to the clean run with a
    record-identical ledger."""
    import mpi_opt_tpu.train.fused_pbt as fp

    kw = dict(population=8, generations=3, steps_per_gen=2, seed=2)
    space = wl.default_space()
    led_a = _ledger(tmp_path / "clean.jsonl", space, "pbt", kw["seed"])
    try:
        clean = fp.fused_pbt(wl, wave_size=4, ledger=led_a, **kw)
    finally:
        led_a.close()

    inj, uninstall = inject_oom(at_launch=3, kind="wave")
    led_b = _ledger(tmp_path / "oom.jsonl", space, "pbt", kw["seed"])
    try:
        faulted = fp.fused_pbt(
            wl, wave_size=4, oom_backoff=2, ledger=led_b, **kw
        )
    finally:
        led_b.close()
        uninstall()

    assert inj.faults_fired == 1
    assert faulted["oom_backoffs"] == 1
    assert faulted["wave_size"] == 2  # settled cap after one halving
    np.testing.assert_array_equal(clean["best_curve"], faulted["best_curve"])
    np.testing.assert_array_equal(clean["unit"], faulted["unit"])
    assert clean["best_score"] == faulted["best_score"]
    assert clean["best_params"] == faulted["best_params"]
    assert validate_ledger(led_b.path) == []
    assert _records(tmp_path / "clean.jsonl") == _records(tmp_path / "oom.jsonl")


def test_tpe_oom_backoff_record_identical(wl, tmp_path):
    """Same drill through the TPE adapter: the batch re-runs from its
    already-drawn suggestions (the suggest program is NOT re-entered, so
    the RNG chain is untouched) under the halved cap."""
    space = wl.default_space()
    led_a = _ledger(tmp_path / "clean.jsonl", space, "tpe", TPE_KW["seed"])
    try:
        clean = ft.fused_tpe(wl, wave_size=2, ledger=led_a, **TPE_KW)
    finally:
        led_a.close()

    inj, uninstall = inject_oom(at_launch=3, kind="wave")  # gen 2, wave 1
    led_b = _ledger(tmp_path / "oom.jsonl", space, "tpe", TPE_KW["seed"])
    try:
        faulted = ft.fused_tpe(
            wl, wave_size=2, oom_backoff=2, ledger=led_b, **TPE_KW
        )
    finally:
        led_b.close()
        uninstall()

    assert inj.faults_fired == 1
    assert faulted["oom_backoffs"] == 1
    assert faulted["wave_size"] == 1
    np.testing.assert_array_equal(clean["obs_unit"], faulted["obs_unit"])
    np.testing.assert_array_equal(clean["obs_scores"], faulted["obs_scores"])
    np.testing.assert_array_equal(clean["best_curve"], faulted["best_curve"])
    assert clean["best_params"] == faulted["best_params"]
    assert validate_ledger(led_b.path) == []
    assert _records(tmp_path / "clean.jsonl") == _records(tmp_path / "oom.jsonl")


def test_bohb_oom_backoff_matches_clean(wl, bohb_waves_of_2):
    """BOHB inherits the drill through its brackets' fused_sha: an OOM
    in the FIRST bracket's first wave backs off inside that bracket;
    later brackets see identical observations, so the model's cohorts
    — and the final pick — match the clean run exactly."""
    from mpi_opt_tpu.train.fused_bohb import fused_bohb

    kw, clean = BOHB_KW, bohb_waves_of_2
    inj, uninstall = inject_oom(at_launch=1, kind="wave")
    try:
        faulted = fused_bohb(wl, wave_size=2, oom_backoff=2, **kw)
    finally:
        uninstall()
    assert inj.faults_fired == 1
    assert faulted["oom_backoffs"] == 1
    assert clean["best_score"] == faulted["best_score"]
    assert clean["best_params"] == faulted["best_params"]
    for b_c, b_f in zip(clean["brackets"], faulted["brackets"]):
        assert b_c["best_score"] == b_f["best_score"]
        assert b_c["n_model_sampled"] == b_f["n_model_sampled"]


def test_sha_oom_without_budget_raises_typed(wl):
    """oom_backoff=0: the classified DeviceOOM propagates for the CLI's
    exit-74 mapping — no silent retry, same contract as PBT."""
    _inj, uninstall = inject_oom(at_launch=1, kind="wave")
    try:
        with pytest.raises(resources.DeviceOOM):
            fa.fused_sha(wl, wave_size=4, oom_backoff=0, **SHA_KW)
    finally:
        uninstall()


# -- drill: crash / preemption -> resume, record-identical ------------------


def test_sha_wave_crash_resume_bit_identical(wl, sha_waves_of_4, tmp_path):
    """Hard crash inside rung 1's second wave: resume restores the
    rung-boundary snapshot, re-trains only the interrupted rung, and
    finishes with the undisturbed sweep's exact result."""
    whole = sha_waves_of_4
    real = fa._run_wave
    calls = {"n": 0}

    def crashing(*a, **k):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("simulated TPU worker crash")
        return real(*a, **k)

    ckpt = str(tmp_path / "ck")
    fa._run_wave = crashing
    try:
        with pytest.raises(RuntimeError, match="simulated"):
            fa.fused_sha(wl, wave_size=4, checkpoint_dir=ckpt, **SHA_KW)
    finally:
        fa._run_wave = real
    resumed = fa.fused_sha(wl, wave_size=4, checkpoint_dir=ckpt, **SHA_KW)
    np.testing.assert_array_equal(resumed["last_score"], whole["last_score"])
    assert resumed["best_score"] == whole["best_score"]
    assert resumed["best_params"] == whole["best_params"]
    assert resumed["rung_history"] == whole["rung_history"]


def test_tpe_wave_preempt_resumes_record_identical(wl, tmp_path):
    """SIGTERM between waves: the sweep drains at the next boundary
    (graceful, exit-75 semantics), and the resumed run re-trains only
    from the last generation snapshot — it appends only the un-run
    tail's records (the journaled prefix is honored, not rewritten),
    and the final records equal an undisturbed run's."""
    space = wl.default_space()
    led_a = _ledger(tmp_path / "clean.jsonl", space, "tpe", TPE_KW["seed"])
    try:
        whole = ft.fused_tpe(wl, wave_size=2, ledger=led_a, **TPE_KW)
    finally:
        led_a.close()

    ckpt = str(tmp_path / "ck")
    real = ft._run_wave
    calls = {"n": 0}

    def preempting(*a, **k):
        calls["n"] += 1
        out = real(*a, **k)
        if calls["n"] == 3:  # gen 0 = 2 waves; die inside gen 1
            os.kill(os.getpid(), signal.SIGTERM)
        return out

    led_b = _ledger(tmp_path / "kill.jsonl", space, "tpe", TPE_KW["seed"])
    with shutdown.ShutdownGuard():
        ft._run_wave = preempting
        try:
            with pytest.raises(shutdown.SweepInterrupted):
                ft.fused_tpe(
                    wl, wave_size=2, checkpoint_dir=ckpt, ledger=led_b, **TPE_KW
                )
        finally:
            ft._run_wave = real
            led_b.close()

    led_c = SweepLedger(str(tmp_path / "kill.jsonl"))
    try:
        resumed = ft.fused_tpe(
            wl, wave_size=2, checkpoint_dir=ckpt, ledger=led_c, **TPE_KW
        )
    finally:
        led_c.close()
    # the kill drained mid-generation 1, so snapshot AND journal both
    # end at generation 0: the resume re-runs only gens 1-2 and appends
    # exactly their records — nothing before the snapshot is re-written
    # (re-journaling an already-written boundary would double records
    # and fail the file-level comparisons below)
    assert resumed["journal"]["written"] == TPE_KW["batch"] + 2
    np.testing.assert_array_equal(resumed["obs_scores"], whole["obs_scores"])
    np.testing.assert_array_equal(resumed["best_curve"], whole["best_curve"])
    assert resumed["best_params"] == whole["best_params"]
    assert validate_ledger(str(tmp_path / "kill.jsonl")) == []
    assert _records(tmp_path / "clean.jsonl") == _records(tmp_path / "kill.jsonl")


def test_sha_wave_snapshot_refused_by_resident_resume(wl, tmp_path):
    """wave_size is config identity for SHA too: a wave sweep's
    snapshot must not load into a resident resume (and resident
    snapshots keep their pre-engine config bytes, so old checkpoints
    stay resumable — the setdefault back-compat in checkpoint.py)."""
    ckpt = str(tmp_path / "ck")
    fa.fused_sha(wl, wave_size=4, checkpoint_dir=ckpt, **SHA_KW)
    with pytest.raises(ValueError, match="different sweep"):
        fa.fused_sha(wl, checkpoint_dir=ckpt, **SHA_KW)


def test_tpe_wave_resume_adopts_settled_cap(wl, tpe_waves_of_2, tmp_path):
    """The OOM-settled execution cap travels in snapshot meta
    (wave_size_run): a resume adopts it instead of re-paying the
    halvings, while the REQUESTED cap stays the config identity."""
    ckpt = str(tmp_path / "ck")
    inj, uninstall = inject_oom(at_launch=1, kind="wave")
    real = ft._run_wave
    calls = {"n": 0}

    def crashing(*a, **k):
        calls["n"] += 1
        # gen 0 re-runs as 4 unit waves after the halving (2 -> 1);
        # crash in gen 1 so a snapshot with the settled cap exists
        if calls["n"] == 6:
            raise RuntimeError("simulated crash after backoff")
        return real(*a, **k)

    ft._run_wave = crashing
    try:
        with pytest.raises(RuntimeError, match="simulated"):
            ft.fused_tpe(
                wl, wave_size=2, oom_backoff=2, checkpoint_dir=ckpt, **TPE_KW
            )
    finally:
        ft._run_wave = real
        uninstall()
    assert inj.faults_fired == 1

    whole = tpe_waves_of_2
    resumed = ft.fused_tpe(
        wl, wave_size=2, oom_backoff=2, checkpoint_dir=ckpt, **TPE_KW
    )
    assert resumed["wave_size"] == 1  # adopted, not re-learned
    assert resumed["oom_backoffs"] == 0  # no new OOM was paid
    np.testing.assert_array_equal(resumed["obs_scores"], whole["obs_scores"])
    assert resumed["best_params"] == whole["best_params"]
