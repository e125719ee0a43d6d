"""Metrics utilities: the wall-clock-to-target metric of record."""

import numpy as np

import pytest

from mpi_opt_tpu.utils.metrics import (
    MetricsLogger,
    wall_to_target,
    wall_to_target_launchwise,
)


def test_wall_to_target_prorates_by_generation():
    # target reached at generation index 1 of 4 -> 2/4 of the wall
    assert wall_to_target([0.5, 0.8, 0.9, 0.95], 100.0, 0.75) == 50.0
    # reached immediately -> one generation's share
    assert wall_to_target([0.9, 0.95], 60.0, 0.75) == 30.0
    # never reached -> None
    assert wall_to_target([0.1, 0.2], 60.0, 0.75) is None
    # exact-equality counts as reached (>=, not >)
    assert wall_to_target([0.75], 10.0, 0.75) == 10.0
    # accepts numpy inputs (the benches pass device-derived arrays)
    assert wall_to_target(np.asarray([0.2, 0.8]), 10.0, 0.5) == 10.0


def test_wall_to_target_launchwise_uses_measured_boundaries():
    # two launches of 2 gens: 10s then 30s (the second launch is slower —
    # exactly what whole-sweep prorating gets wrong). Target reached at
    # gen index 2 = first gen of launch 2 -> 10 + 30 * 1/2 = 25.
    curve = [0.2, 0.4, 0.8, 0.9]
    assert wall_to_target_launchwise(curve, [2, 2], [10.0, 30.0], 0.75) == 25.0
    # whole-sweep prorating would have said 40 * 3/4 = 30
    assert wall_to_target(curve, 40.0, 0.75) == 30.0
    # reached in the first launch's first gen
    assert wall_to_target_launchwise([0.9, 0.9], [2], [10.0], 0.5) == 5.0
    # never reached
    assert wall_to_target_launchwise([0.1, 0.2], [1, 1], [5.0, 5.0], 0.75) is None
    # identical launch costs == the uniform assumption: both agree
    assert wall_to_target_launchwise(curve, [2, 2], [20.0, 20.0], 0.75) == 30.0
    # misaligned inputs are errors, not silent misattribution
    with pytest.raises(ValueError, match="align"):
        wall_to_target_launchwise(curve, [2, 2], [10.0], 0.75)
    with pytest.raises(ValueError, match="curve"):
        wall_to_target_launchwise(curve, [2, 3], [10.0, 30.0], 0.75)


def test_fused_pbt_reports_launch_walls(shared_workload):
    """The fused sweep returns measured per-launch durations aligned with
    its launch split, and a resumed sweep restores pre-crash durations."""
    from mpi_opt_tpu.train.fused_pbt import fused_pbt

    wl = shared_workload("fashion_mlp", n_train=512, n_val=256)
    res = fused_pbt(wl, population=4, generations=3, steps_per_gen=2, seed=0, gen_chunk=2)
    assert res["launch_gens"] == [2, 1]
    assert len(res["launch_walls"]) == 2
    assert all(w > 0 for w in res["launch_walls"])


def test_metrics_logger_per_chip_normalization(tmp_path):
    import json

    path = tmp_path / "m.jsonl"
    m = MetricsLogger(path=str(path), n_chips=4)
    m.count_trials(8)
    m.log("batch", size=8)
    # trials/sec/chip divides by the chip count; pin the clock far from
    # zero so the two live wall reads agree to high precision
    import math
    import time

    m.t_start = time.perf_counter() - 100.0
    per_chip = m.trials_per_sec_per_chip()
    total = m.trials_done / max(m.wall, 1e-9)
    assert math.isclose(per_chip * 4, total, rel_tol=1e-4)
    m.close()  # release the file handle (ResourceWarning-clean)
    rec = json.loads(path.read_text().splitlines()[0])
    assert rec["event"] == "batch" and rec["size"] == 8


def test_summary_includes_failure_counters():
    m = MetricsLogger()
    m.count_trials(10)
    m.count_failure("failed")
    m.count_failure("failed")
    m.count_failure("timeout")
    m.count_retries(3)
    s = m.summary()
    assert s["trials"] == 10
    assert s["trials_failed"] == 2
    assert s["trials_timeout"] == 1
    assert s["trials_retried"] == 3
    # fresh loggers report explicit zeros (operators diff summaries)
    z = MetricsLogger().summary()
    assert (z["trials_failed"], z["trials_retried"], z["trials_timeout"]) == (0, 0, 0)


def test_summary_includes_health_counters():
    """preempted / stalls_detected (health layer) reach the summary
    record operators alarm on — explicit zeros when nothing happened."""
    m = MetricsLogger()
    m.count_preempted()
    m.count_stalls(2)
    s = m.summary()
    assert s["preempted"] == 1
    assert s["stalls_detected"] == 2
    z = MetricsLogger().summary()
    assert (z["preempted"], z["stalls_detected"]) == (0, 0)


def test_null_logger_log_path_is_sink_free(monkeypatch):
    """null_logger() must stay zero-cost on the hot path: with no file
    and no stream, log() must not serialize (the driver logs per-batch
    and per-failure events unconditionally)."""
    from mpi_opt_tpu.utils import metrics as metrics_mod
    from mpi_opt_tpu.utils.metrics import null_logger

    def boom(*a, **k):
        raise AssertionError("json.dumps called on the null-logger path")

    monkeypatch.setattr(metrics_mod.json, "dumps", boom)
    m = null_logger()
    rec = m.log("batch", size=4)
    assert rec["event"] == "batch" and rec["size"] == 4
    m.count_failure("timeout")
    s = m.summary()
    assert s["trials_timeout"] == 1


def test_summary_includes_staging_counters():
    """staged_bytes / stage_overlap_s (wave-scheduled fused sweeps)
    reach the metrics summary; zero-valued when no staging ran."""
    from mpi_opt_tpu.utils.metrics import MetricsLogger

    m = MetricsLogger()
    m.count_staging(1024, 0.5)
    m.count_staging(1024, 0.25)
    s = m.summary()
    assert s["staged_bytes"] == 2048
    assert s["stage_overlap_s"] == 0.75
    z = MetricsLogger().summary()
    assert z["staged_bytes"] == 0 and z["stage_overlap_s"] == 0.0


def test_summary_includes_snapshot_quarantine_counter():
    """snapshots_quarantined (integrity layer) reaches the summary
    record operators alarm on — explicit zero when nothing happened."""
    from mpi_opt_tpu.utils.metrics import MetricsLogger

    m = MetricsLogger()
    m.count_quarantined()
    m.count_quarantined(2)
    assert m.summary()["snapshots_quarantined"] == 3
    assert MetricsLogger().summary()["snapshots_quarantined"] == 0


def test_summary_includes_members_journaled():
    """members_journaled (fused-ledger member records appended) reaches
    the metrics summary; zero-valued when no fused journaling ran."""
    from mpi_opt_tpu.utils.metrics import MetricsLogger

    m = MetricsLogger()
    m.count_journaled(8)
    m.count_journaled(4)
    assert m.summary()["members_journaled"] == 12
    assert MetricsLogger().summary()["members_journaled"] == 0
