"""Resource-exhaustion robustness (ISSUE 13): the utils/resources.py
classifier, the wave scheduler's device-OOM adaptive backoff, the
snapshot layer's ENOSPC prune-then-park, and the exit-74 mapping across
the CLI / launch supervisor / service state machine.

The two acceptance drills' cores live here (tier1.sh runs the
subprocess twins): drill A — a wave-mode fused PBT sweep with an
injected OOM at wave k completes via automatic wave-size backoff,
bit-identical params/curves and a record-identical ledger; drill B —
an injected ENOSPC during a snapshot save gets at most one
retention-prune retry (never touching the newest verified step), exits
74 with no torn step, and after the injector clears ``--resume``
completes with ``fsck`` clean.
"""

import errno
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

import mpi_opt_tpu.train.fused_pbt as fp
from mpi_opt_tpu import launch
from mpi_opt_tpu.cli import main as cli_main
from mpi_opt_tpu.service import tenants as tstates
from mpi_opt_tpu.utils import resources
from mpi_opt_tpu.utils.exitcodes import EX_IOERR, classify
from mpi_opt_tpu.workloads.chaos import (
    DiskFullInjector,
    OOMInjector,
    inject_enospc,
    inject_oom,
)


@pytest.fixture(scope="module")
def wl(shared_workload):
    return shared_workload("fashion_mlp", n_train=256, n_val=128)


KW = dict(population=8, generations=3, steps_per_gen=4, seed=2)


# -- the classifier ---------------------------------------------------------


def test_storage_full_classifier():
    assert resources.is_storage_full(OSError(errno.ENOSPC, "no space"))
    assert resources.is_storage_full(OSError(errno.EDQUOT, "quota"))
    assert not resources.is_storage_full(OSError(errno.EIO, "io"))
    assert not resources.is_storage_full(ValueError("ENOSPC"))
    e = resources.storage_full_error("/some/path", op="fsync")
    assert isinstance(e, resources.StorageFull) and isinstance(e, OSError)
    assert resources.is_storage_full(e) and e.errno == errno.ENOSPC


def test_device_oom_classifier_type_gate():
    assert resources.is_device_oom(resources.synthetic_resource_exhausted("t"))
    # message alone is NOT enough: a user exception quoting the token
    # must not classify (the type-first rule)
    assert not resources.is_device_oom(ValueError("RESOURCE_EXHAUSTED: fake"))
    assert not resources.is_device_oom(
        jax.errors.JaxRuntimeError("INTERNAL: something else died")
    )
    oom = resources.as_device_oom(
        resources.synthetic_resource_exhausted("x"), wave_size=4
    )
    assert isinstance(oom, resources.DeviceOOM) and oom.wave_size == 4
    assert resources.as_device_oom(ValueError("nope")) is None
    # an already-typed DeviceOOM passes through unchanged
    assert resources.as_device_oom(oom) is oom


def test_oom_funnel_classifies_and_passes_raw():
    with pytest.raises(resources.DeviceOOM) as exc:
        with resources.oom_funnel(wave_size=8):
            raise resources.synthetic_resource_exhausted("funnel")
    assert exc.value.wave_size == 8
    with pytest.raises(ValueError):  # everything else propagates raw
        with resources.oom_funnel():
            raise ValueError("not an OOM")


# -- exit-code + state-machine mapping --------------------------------------


def test_exit74_mapping():
    assert classify(EX_IOERR) == "io_error"
    # the service parks (state intact; freeing the resource + --resume
    # recovers) instead of terminal-failing
    assert tstates.after_slice(EX_IOERR, cancel_requested=False) == tstates.PARKED
    assert tstates.after_slice(EX_IOERR, cancel_requested=True) == tstates.CANCELLED


def test_supervisor_aborts_on_resource_error_without_retrying(
    tmp_path, monkeypatch, capsys
):
    """Exit 74 is a resource answer: a restart changes nothing until an
    operator frees the resource — the supervisor must abort with
    diagnostics, budget untouched (the exit-65 rule's sibling)."""

    def fake_spawn(n, rest, log_dir, heartbeat=False, coord=None):
        procs = []
        for i in range(n):
            out = open(os.path.join(log_dir, f"rank{i}.out"), "w")
            err = open(os.path.join(log_dir, f"rank{i}.err"), "w")
            p = subprocess.Popen(
                [sys.executable, "-c", f"raise SystemExit({EX_IOERR})"],
                stdout=out,
                stderr=err,
            )
            procs.append((p, out, err))
        return procs

    monkeypatch.setattr(launch, "_spawn_ranks", fake_spawn)
    rc = launch.main([
        "--n-proc", "1",
        "--retries", "5",
        "--poll-interval", "0.01",
        "--term-grace", "0.1",
        "--log-dir", str(tmp_path),
        "--", "--workload", "quadratic",
    ])
    assert rc == 1
    events = [
        json.loads(l) for l in capsys.readouterr().out.splitlines() if '"event"' in l
    ]
    names = [e["event"] for e in events]
    assert "restart" not in names and "preempt_restart" not in names
    last = events[-1]
    assert last["event"] == "failed" and last.get("resource_exhausted") is True
    assert last["returncode"] == EX_IOERR


# -- retry_io: storage exhaustion is an answer ------------------------------


def test_retry_io_never_retries_enospc():
    from mpi_opt_tpu.service.spool import retry_io

    calls = {"n": 0}
    sleeps = []

    def full():
        calls["n"] += 1
        raise OSError(errno.ENOSPC, "disk full")

    with pytest.raises(OSError):
        retry_io(full, sleep=sleeps.append)
    # ONE attempt, zero backoff sleeps: spinning on a full disk only
    # delays the diagnosis
    assert calls["n"] == 1 and sleeps == []

    # contrast: transient EIO still rides the backoff schedule
    calls["n"] = 0

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError(errno.EIO, "blip")
        return "ok"

    assert retry_io(flaky, sleep=sleeps.append) == "ok"
    assert calls["n"] == 3 and len(sleeps) == 2


# -- chaos injectors: seeded, deterministic, uninstallable ------------------


def test_inject_enospc_schedule_and_seam():
    inj, uninstall = inject_enospc(fail=2, op="snapshot_save")
    try:
        with pytest.raises(resources.StorageFull):
            resources.disk_fault("snapshot_save", "/d")
        with pytest.raises(resources.StorageFull):
            resources.disk_fault("snapshot_save", "/d")
        resources.disk_fault("snapshot_save", "/d")  # op 2: past schedule
        resources.disk_fault("ledger_fsync", "/l")  # other kinds untouched
        assert inj.faults_fired == 2
    finally:
        uninstall()
    resources.disk_fault("snapshot_save", "/d")  # seam cleared


def test_inject_enospc_fail_from_is_persistent():
    inj = DiskFullInjector(fail_from=1)
    inj("snapshot_save", "/d")  # op 0 lands
    for _ in range(3):  # ops 1..3: the disk stays full
        with pytest.raises(resources.StorageFull):
            inj("snapshot_save", "/d")
    assert inj.faults_fired == 3


def test_inject_oom_fires_at_chosen_ordinal():
    inj, uninstall = inject_oom(at_launch=2, kind="wave")
    try:
        resources.launch_fault("launch")  # other kind: not counted
        resources.launch_fault("wave")  # ordinal 1
        with pytest.raises(jax.errors.JaxRuntimeError) as exc:
            resources.launch_fault("wave")  # ordinal 2: fires
        assert resources.is_device_oom(exc.value)
        resources.launch_fault("wave")  # ordinal 3: past
        assert inj.faults_fired == 1
    finally:
        uninstall()
    with pytest.raises(ValueError):
        OOMInjector(at_launch=0)


# -- drill A core: OOM at wave k -> backoff, bit-identical ------------------


def _fused_ledger(path, space, seed):
    from mpi_opt_tpu.ledger import SweepLedger

    led = SweepLedger(str(path), read_only=False)
    led.ensure_header(
        {
            "mode": "fused",
            "granularity": "generation",
            "algorithm": "pbt",
            "workload": "fashion_mlp",
            "backend": "fused",
            "seed": seed,
            "space_hash": space.space_hash(),
            "population": KW["population"],
            "generations": KW["generations"],
            "steps_per_generation": KW["steps_per_gen"],
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


def test_wave_oom_backoff_bit_identical_with_ledger(wl, tmp_path):
    """Drill A: an injected OOM at wave 2 of generation 2 (W=4 -> two
    waves per generation) halves the wave to 2, re-runs that generation,
    and the sweep completes with params/curves BIT-IDENTICAL to the
    unfaulted run and a record-identical ledger."""
    from mpi_opt_tpu.train.common import workload_arrays

    _trainer, space, *_ = workload_arrays(wl, 0, None)
    led_a = _fused_ledger(tmp_path / "clean.jsonl", space, KW["seed"])
    try:
        clean = fp.fused_pbt(wl, wave_size=4, ledger=led_a, **KW)
    finally:
        led_a.close()

    events = []
    resources.set_observer(lambda e, **f: events.append((e, f)))
    inj, uninstall = inject_oom(at_launch=4, kind="wave")  # gen 2, wave 2
    led_b = _fused_ledger(tmp_path / "oom.jsonl", space, KW["seed"])
    try:
        faulted = fp.fused_pbt(wl, wave_size=4, oom_backoff=2, ledger=led_b, **KW)
    finally:
        led_b.close()
        uninstall()
        resources.clear_observer()

    assert inj.faults_fired == 1
    assert faulted["oom_backoffs"] == 1
    assert faulted["wave_size"] == 2 and faulted["n_waves"] == 4
    assert [e for e, _ in events].count("oom_backoff") == 1
    np.testing.assert_array_equal(clean["best_curve"], faulted["best_curve"])
    np.testing.assert_array_equal(clean["mean_curve"], faulted["mean_curve"])
    np.testing.assert_array_equal(clean["unit"], faulted["unit"])
    assert clean["best_params"] == faulted["best_params"]
    for a, b in zip(
        jax.tree.leaves(clean["state"].params), jax.tree.leaves(faulted["state"].params)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(
        jax.tree.leaves(clean["state"].momentum),
        jax.tree.leaves(faulted["state"].momentum),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # record-identical ledger: the backed-off run journals the SAME
    # member history (the re-run generation journals once, post-retry)
    assert _records(tmp_path / "clean.jsonl") == _records(tmp_path / "oom.jsonl")


def test_wave_oom_without_budget_raises_typed(wl):
    """--oom-backoff 0 (or an exhausted budget): the classified
    DeviceOOM propagates — the CLI maps it to exit 74."""
    _inj, uninstall = inject_oom(at_launch=1, kind="wave")
    try:
        with pytest.raises(resources.DeviceOOM):
            fp.fused_pbt(wl, wave_size=4, oom_backoff=0, **KW)
    finally:
        uninstall()


def test_resident_oom_classifies_typed(wl):
    """Resident mode has no wave to halve: the launch funnel still
    types the error so launch.py never burns retries on it."""
    _inj, uninstall = inject_oom(at_launch=1, kind="launch")
    try:
        with pytest.raises(resources.DeviceOOM):
            fp.fused_pbt(wl, **KW)
    finally:
        uninstall()


# -- drill B core: ENOSPC -> prune once -> park -> resume clean -------------


def test_snapshot_save_prunes_then_parks(tmp_path):
    """The retention-prune rule: one superseded retained step is
    reclaimed (never the newest) and the save retried ONCE; a disk
    that stays full parks with typed StorageFull."""
    from mpi_opt_tpu.utils.checkpoint import SweepCheckpointer

    d = str(tmp_path / "ck")
    snap = SweepCheckpointer(d, {"k": 1, "momentum_dtype": "float32"})
    payload = lambda v: {"x": np.full((4,), v, np.float32)}
    events = []
    resources.set_observer(lambda e, **f: events.append((e, f)))
    try:
        snap.save(1, sweep=payload(1.0), meta_extra={"m": 1})
        snap.save(2, sweep=payload(2.0), meta_extra={"m": 2})
        snap._mgr.wait_until_finished()
        _inj, uninstall = inject_enospc(fail_from=0, op="snapshot_save")
        try:
            with pytest.raises(resources.StorageFull):
                snap.save(3, sweep=payload(3.0), meta_extra={"m": 3})
        finally:
            uninstall()
        # exactly one prune: the oldest (1) reclaimed, the newest (2)
        # untouched — and restorable (no torn step, nothing quarantined)
        assert not os.path.isdir(os.path.join(d, "1"))
        assert os.path.isdir(os.path.join(d, "2"))
        assert [e for e, _ in events if e == "snapshot_pruned"] == ["snapshot_pruned"]
        # after the disk frees, the same checkpointer keeps working and
        # the newest verified step restores
        snap.save(3, sweep=payload(3.0), meta_extra={"m": 3})
        snap._mgr.wait_until_finished()  # settle the async write
        sweep, meta = snap.restore()
        assert meta["m"] == 3
    finally:
        resources.clear_observer()
        snap.close()


def test_snapshot_save_parks_without_prunable_step(tmp_path):
    """With only the newest step retained there is nothing prunable:
    park immediately, step intact."""
    from mpi_opt_tpu.utils.checkpoint import SweepCheckpointer

    d = str(tmp_path / "ck")
    snap = SweepCheckpointer(d, {"k": 1})
    try:
        snap.save(1, sweep={"x": np.zeros((2,), np.float32)}, meta_extra={"m": 1})
        snap._mgr.wait_until_finished()
        _inj, uninstall = inject_enospc(fail_from=0, op="snapshot_save")
        try:
            with pytest.raises(resources.StorageFull):
                snap.save(2, sweep={"x": np.ones((2,), np.float32)}, meta_extra={"m": 2})
        finally:
            uninstall()
        assert os.path.isdir(os.path.join(d, "1"))  # newest never touched
    finally:
        snap.close()


def test_cli_enospc_exit74_then_resume_fsck_clean(tmp_path, capsys):
    """Drill B end to end (driver path): ENOSPC mid-sweep -> at most one
    retention-prune retry -> exit 74 with intact durable state; after
    the injector clears, --resume completes and fsck + report
    --validate exit 0."""
    ck, led = str(tmp_path / "ck"), str(tmp_path / "sweep.jsonl")
    argv = [
        "--workload", "quadratic", "--algorithm", "random",
        "--trials", "8", "--budget", "3", "--workers", "1", "--seed", "0",
        "--checkpoint-dir", ck, "--ledger", led,
    ]
    _inj, uninstall = inject_enospc(fail_from=2, op="snapshot_save")
    try:
        rc = cli_main(argv)
    finally:
        uninstall()
    out = capsys.readouterr().out
    assert rc == EX_IOERR
    parked = json.loads(out.strip().splitlines()[-1])
    assert parked["kind"] == "storage_full" and "resource_exhausted" in parked

    # the injector cleared (= operator freed disk): ordinary resume
    rc = cli_main(argv + ["--resume"])
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and summary["n_trials"] == 8
    assert cli_main(["fsck", ck]) == 0
    assert cli_main(["report", led, "--validate"]) == 0
    capsys.readouterr()


def test_async_save_drain_enospc_classifies(tmp_path):
    """Review-round fix: orbax saves are ASYNC — a real disk-full often
    surfaces in the background writer and re-raises at close()'s
    wait_until_finished, not at the guarded enqueue. That path must
    classify too (incl. through an explicit `raise X from enospc`
    wrapper, the orbax/tensorstore shape), or the run exits rc 1 and
    launch.py burns retries on it."""
    from mpi_opt_tpu.utils.checkpoint import SweepCheckpointer

    snap = SweepCheckpointer(str(tmp_path / "ck"), {"k": 1})
    real_wait = snap._mgr.wait_until_finished
    try:

        def boom():
            try:
                raise OSError(errno.ENOSPC, "no space")
            except OSError as root:
                raise RuntimeError("async write failed") from root

        snap._mgr.wait_until_finished = boom
        with pytest.raises(resources.StorageFull):
            snap.close()
    finally:
        # the manager's own close() re-enters wait_until_finished —
        # un-shim it so teardown drains for real
        snap._mgr.wait_until_finished = real_wait
        snap._mgr.close()


def test_ledger_fsync_enospc_classifies(tmp_path):
    from mpi_opt_tpu.ledger import SweepLedger

    led = SweepLedger(str(tmp_path / "l.jsonl"), read_only=False)
    try:
        _inj, uninstall = inject_enospc(fail_from=0, op="ledger_fsync")
        try:
            with pytest.raises(resources.StorageFull):
                led.ensure_header({"algorithm": "random", "space_hash": "x"})
        finally:
            uninstall()
    finally:
        led.close()


# -- service: exit-74 parks with a cooldown, not a spin ---------------------


def test_scheduler_skips_io_parked_tenant_until_cooldown(tmp_path):
    from mpi_opt_tpu.service import leases
    from mpi_opt_tpu.service.scheduler import SweepService
    from mpi_opt_tpu.service.spool import TenantDir, _write_json_atomic

    svc = SweepService(str(tmp_path), poll_seconds=0.01)
    t = TenantDir(svc.spool.tenants_dir, "job-io")
    os.makedirs(t.dir)
    _write_json_atomic(t.job_path, {"id": "job-io", "argv": ["--workload", "quadratic"]})
    status = {
        "id": "job-io", "tenant": "a", "state": tstates.PARKED, "slices": 1,
        "park_reason": "io_error", "retry_after_ts": time.time() + 3600,
    }
    t.write_status(status)
    assert svc._pick_next() is None  # held out of rotation

    svc._status_memo.clear()
    t.write_status(dict(status, retry_after_ts=time.time() - 1))
    pick = svc._pick_next()  # cooldown passed: re-probed
    assert pick is not None and pick[0].job_id == "job-io"
    leases.release(pick[0].lease, pick[1])


# -- envelope validation (carried ROADMAP item, on CPU) ---------------------


def test_envelope_report_against_traced_run(wl, tmp_path):
    """Validate the static per-member envelope math against a REAL
    traced run's measured watermark (live-array accounting on this CPU
    container): the measured peak must cover the static population
    state — the direction the 4.5 GB pop=1024 projection needs — and
    the report carries the ratio for the TPU re-measure."""
    from mpi_opt_tpu.obs import trace
    from mpi_opt_tpu.train.common import workload_arrays
    from mpi_opt_tpu.train.staging import envelope_report, measured_train_peak
    from mpi_opt_tpu.utils.metrics import MetricsLogger

    stream = str(tmp_path / "m.jsonl")
    m = MetricsLogger(path=stream)
    prior = trace.configure(m)
    try:
        fp.fused_pbt(wl, population=8, generations=1, steps_per_gen=2, seed=0)
    finally:
        trace.deconfigure(prior)
        m.close()
    trainer, _space, train_x, *_ = workload_arrays(wl, 0, None)
    peak = measured_train_peak(stream)
    assert peak is not None and peak > 0
    rep = envelope_report(trainer, train_x[:2], 8, stream)
    assert rep["measured_peak_bytes"] == peak
    assert rep["per_member_bytes"] > 0
    assert rep["static_pop_bytes"] == rep["per_member_bytes"] * 8
    # the measured watermark covers the resident population state (it
    # also sees datasets/activations, so it is an upper bound: ratio>=1)
    assert rep["measured_over_static"] >= 1.0


def test_estimate_wave_size_measured_peak_tightens(wl):
    from mpi_opt_tpu.train.common import workload_arrays
    from mpi_opt_tpu.train.staging import _per_member_bytes, estimate_wave_size

    trainer, _space, train_x, *_ = workload_arrays(wl, 0, None)
    per_member = _per_member_bytes(trainer, train_x[:2])
    budget = per_member * 64  # static math offers 0.35 * 64 = 22 members
    w_static = estimate_wave_size(trainer, train_x[:2], 1024, budget_bytes=budget)
    assert w_static == 22
    # a traced run measured each member costing 4x its static state:
    # the measured estimate (0.85 * 64 / 4 = 13) must win
    w_meas = estimate_wave_size(
        trainer, train_x[:2], 1024, budget_bytes=budget,
        measured_peak=(per_member * 4 * 8, 8),
    )
    assert w_meas == 13
    # a measurement LOOSER than the static envelope never loosens it
    w_loose = estimate_wave_size(
        trainer, train_x[:2], 1024, budget_bytes=budget,
        measured_peak=(per_member * 8, 8),
    )
    assert w_loose == w_static


# -- the resource-funnel checker --------------------------------------------


def test_resource_funnel_checker_fixtures():
    from mpi_opt_tpu.analysis import check_source
    from mpi_opt_tpu.analysis.checkers_resources import ResourceFunnelChecker

    def run(src, path="mpi_opt_tpu/train/somewhere.py"):
        return check_source(src, path=path, checkers=[ResourceFunnelChecker()])

    # true positives: each ad-hoc handling shape is a finding
    assert run("try:\n    f()\nexcept XlaRuntimeError:\n    pass\n")
    assert run(
        "import jax.errors\n"
        "def g(e):\n"
        "    return isinstance(e, jax.errors.JaxRuntimeError)\n"
    )
    assert run('def g(e):\n    return "RESOURCE_EXHAUSTED" in str(e)\n')
    assert run("import errno\ndef g(e):\n    return e.errno == errno.ENOSPC\n")
    assert run("from errno import ENOSPC\n")

    # the classifier's own home is exempt
    assert not run(
        "def g(e):\n    return 'RESOURCE_EXHAUSTED' in str(e)\n",
        path="mpi_opt_tpu/utils/resources.py",
    )
    # the funnel's products are the sanctioned surface
    assert not run(
        "from mpi_opt_tpu.utils.resources import DeviceOOM, is_storage_full\n"
        "def g(e):\n"
        "    if is_storage_full(e):\n"
        "        return 'full'\n"
        "    try:\n"
        "        pass\n"
        "    except DeviceOOM:\n"
        "        pass\n"
    )
    # docstrings/messages merely mentioning the token are not handling
    assert not run('"""dies RESOURCE_EXHAUSTED at warmup"""\nx = 1\n')
