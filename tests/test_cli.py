"""CLI end-to-end: the config-1 minimum slice, in-process."""

import contextlib
import io
import json
import os
import shutil

import jax.errors

import pytest

from mpi_opt_tpu.cli import build_parser, main


def _run(argv):
    """stdout of one ``main(argv)`` that ends with 0, for the module's
    shared runs (``capsys`` is a test's own)."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert main(argv) == 0
    return out.getvalue()


FUSED_PBT = [
    "--workload", "fashion_mlp",
    "--algorithm", "pbt",
    "--fused",
    "--population", "8",
    "--generations", "2",
    "--steps-per-generation", "5",
    "--seed", "0",
]


@pytest.fixture(scope="module")
def fused_pbt_checkpointed(tmp_path_factory):
    """``FUSED_PBT --checkpoint-dir ck`` run once a module: (stdout, ck).
    A test that goes on in the directory copies it first."""
    ck = str(tmp_path_factory.mktemp("fused_pbt") / "ck")
    return _run(FUSED_PBT + ["--checkpoint-dir", ck]), ck


@pytest.fixture(scope="module")
def fused_pbt_no_mesh():
    """stdout of ``FUSED_PBT --no-mesh``, run once a module."""
    return _run(FUSED_PBT + ["--no-mesh"])


def test_parser_defaults():
    args = build_parser().parse_args(["--workload", "digits"])
    assert args.backend == "cpu"  # CPU path stays default; tpu is opt-in
    assert args.algorithm == "random"


def test_parser_rejects_unknown_backend():
    with pytest.raises(SystemExit):
        build_parser().parse_args(["--workload", "digits", "--backend", "cuda"])


def test_config1_minimum_slice(capsys):
    rc = main(
        [
            "--workload", "digits",
            "--algorithm", "random",
            "--trials", "4",
            "--budget", "40",
            "--workers", "1",
            "--seed", "0",
        ]
    )
    assert rc == 0
    lines = [l for l in capsys.readouterr().out.strip().splitlines() if l.startswith("{")]
    summary = json.loads(lines[-1])
    assert summary["n_trials"] == 4
    assert summary["best_score"] > 0.8
    assert summary["trials_per_sec_per_chip"] > 0
    assert "C" in summary["best_params"]


def test_cli_quadratic_pbt(capsys):
    rc = main(
        [
            "--workload", "quadratic",
            "--algorithm", "pbt",
            "--population", "8",
            "--generations", "3",
            "--steps-per-generation", "5",
            "--workers", "1",
        ]
    )
    assert rc == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["n_trials"] == 24


def test_fused_pbt_cli(fused_pbt_checkpointed):
    lines = [l for l in fused_pbt_checkpointed[0].strip().splitlines() if l.startswith("{")]
    summary = json.loads(lines[-1])
    assert summary["backend"] == "fused"
    assert summary["n_trials"] == 16
    assert len(summary["best_curve"]) == 2
    assert 0.0 <= summary["best_score"] <= 1.0
    assert "lr" in summary["best_params"]


def test_fused_asha_cli(capsys):
    rc = main(
        [
            "--workload", "fashion_mlp",
            "--algorithm", "asha",
            "--fused",
            "--trials", "9",
            "--min-budget", "5",
            "--max-budget", "45",
            "--eta", "3",
            "--seed", "0",
        ]
    )
    assert rc == 0
    lines = [l for l in capsys.readouterr().out.strip().splitlines() if l.startswith("{")]
    summary = json.loads(lines[-1])
    assert summary["backend"] == "fused"
    assert summary["n_trials"] == 9
    assert summary["rung_sizes"][0] == 9
    assert 0.0 <= summary["best_score"] <= 1.0


def test_fused_rejects_non_population_workload():
    with pytest.raises(SystemExit):
        main(["--workload", "digits", "--algorithm", "pbt", "--fused"])


def test_fused_pbt_step_chunk_cli(capsys, monkeypatch):
    """--step-chunk actually reaches fused_pbt (a dropped kwarg would
    run unchunked and every summary assertion would still pass, so the
    plumbing is asserted directly) and the sweep completes."""
    import mpi_opt_tpu.train.fused_pbt as fpbt

    seen = {}
    real = fpbt.fused_pbt

    def spying(workload, **kw):
        seen.update(kw)
        return real(workload, **kw)

    monkeypatch.setattr(fpbt, "fused_pbt", spying)
    rc = main(
        [
            "--workload", "fashion_mlp",
            "--algorithm", "pbt",
            "--fused",
            "--population", "4",
            "--generations", "2",
            "--steps-per-generation", "4",
            "--step-chunk", "2",
            "--seed", "0",
        ]
    )
    assert rc == 0
    assert seen["step_chunk"] == 2
    summary = _summary(capsys)
    assert summary["backend"] == "fused"
    assert summary["n_trials"] == 8
    assert len(summary["best_curve"]) == 2
    assert 0.0 <= summary["best_score"] <= 1.0


def test_fused_random_cli(capsys):
    """Fused random search = the single-rung case of fused SHA: one
    cohort trains to --budget in lockstep, no cuts."""
    rc = main(
        [
            "--workload", "fashion_mlp",
            "--algorithm", "random",
            "--fused",
            "--trials", "6",
            "--budget", "5",
            "--seed", "0",
        ]
    )
    assert rc == 0
    lines = [l for l in capsys.readouterr().out.strip().splitlines() if l.startswith("{")]
    summary = json.loads(lines[-1])
    assert summary["backend"] == "fused"
    assert summary["n_trials"] == 6
    assert summary["rung_budgets"] == [5]  # exactly one rung, no cuts
    assert summary["rung_sizes"] == [6]
    assert 0.0 <= summary["best_score"] <= 1.0


def test_fused_bohb_cli(capsys):
    rc = main(
        [
            "--workload", "fashion_mlp",
            "--algorithm", "bohb",
            "--fused",
            "--max-budget", "9",
            "--eta", "3",
            "--seed", "0",
        ]
    )
    assert rc == 0
    lines = [l for l in capsys.readouterr().out.strip().splitlines() if l.startswith("{")]
    summary = json.loads(lines[-1])
    assert summary["backend"] == "fused"
    assert summary["n_trials"] == 9 + 5 + 3
    assert len(summary["brackets"]) == 3
    assert "n_model_sampled" in summary["brackets"][0]
    assert 0.0 <= summary["best_score"] <= 1.0


def test_unknown_algorithm_rejected_at_parse():
    # argparse choices guard: unknown names never reach run_fused (its
    # own else-branch is a registry-drift guard for algorithms added
    # without fused support)
    with pytest.raises(SystemExit):
        main(["--workload", "fashion_mlp", "--algorithm", "nope", "--fused"])


def test_fused_tpe_cli(capsys):
    rc = main(
        [
            "--workload", "fashion_mlp",
            "--algorithm", "tpe",
            "--fused",
            "--trials", "8",
            "--population", "4",
            "--budget", "5",
            "--seed", "0",
        ]
    )
    assert rc == 0
    lines = [l for l in capsys.readouterr().out.strip().splitlines() if l.startswith("{")]
    summary = json.loads(lines[-1])
    assert summary["backend"] == "fused"
    assert summary["n_trials"] == 8
    assert len(summary["best_curve"]) == 2
    assert 0.0 <= summary["best_score"] <= 1.0


def _summary(capsys):
    return _summary_from(capsys.readouterr().out)


def test_fused_cli_auto_mesh(capsys):
    """On a multi-device host the fused CLI path must run sharded by
    default (VERDICT r2 item 1): the conftest's 8 virtual devices should
    yield an 8-way 'pop' mesh with per-chip accounting to match."""
    rc = main(FUSED_PBT)
    assert rc == 0
    summary = _summary(capsys)
    assert summary["mesh"] == {"pop": 8, "data": 1}
    assert summary["n_chips"] == 8


def test_fused_cli_mesh_flags(capsys):
    rc = main(
        [
            "--workload", "fashion_mlp",
            "--algorithm", "pbt",
            "--fused",
            "--population", "8",
            "--generations", "2",
            "--steps-per-generation", "5",
            "--n-data", "2",
            "--seed", "0",
        ]
    )
    assert rc == 0
    summary = _summary(capsys)
    assert summary["mesh"] == {"pop": 4, "data": 2}
    assert summary["n_chips"] == 8


def test_fused_cli_no_mesh_runs_single_device(fused_pbt_no_mesh):
    summary = _summary_from(fused_pbt_no_mesh)
    assert summary["mesh"] is None
    # ADVICE r2: per-chip divisor = devices the sweep actually ran on (1)
    assert summary["n_chips"] == 1


def test_no_mesh_contradicts_mesh_flags():
    with pytest.raises(SystemExit):
        main(
            [
                "--workload", "fashion_mlp",
                "--algorithm", "pbt",
                "--fused",
                "--no-mesh",
                "--n-data", "2",
            ]
        )


def test_fused_checkpoint_requires_explicit_resume(capsys, tmp_path, fused_pbt_checkpointed):
    """A checkpoint dir holding a previous sweep must not silently
    replay it: resuming is --resume opt-in, like the driver path
    (ADVICE r2)."""
    out, left = fused_pbt_checkpointed
    ck = str(tmp_path / "ck")
    shutil.copytree(left, ck)  # the directory the module's run left, this test's own copy
    argv = FUSED_PBT + ["--checkpoint-dir", ck]
    first = _summary_from(out)
    with pytest.raises(SystemExit):  # stale dir, no --resume: refuse
        main(argv)
    capsys.readouterr()
    assert main(argv + ["--resume"]) == 0  # explicit resume: replays fine
    resumed = _summary(capsys)
    assert resumed["best_score"] == pytest.approx(first["best_score"], abs=1e-6)


def test_has_snapshot_matches_orbax_layout_only(tmp_path):
    """Only committed orbax step dirs (digit name + _CHECKPOINT_METADATA
    marker) count as snapshots: unrelated numeric directories sharing
    the tree — e.g. profiler output dated dirs — must not block a fresh
    sweep with a 'pass --resume' error (VERDICT r3 weak #6)."""
    from mpi_opt_tpu.cli import _has_snapshot

    ck = tmp_path / "ck"
    (ck / "plugins" / "profile" / "20260730").mkdir(parents=True)
    (ck / "cohort_0.npz").parent.mkdir(exist_ok=True)
    assert not _has_snapshot(str(ck))
    # a real committed orbax step flips it
    step = ck / "bracket_0" / "2"
    step.mkdir(parents=True)
    (step / "_CHECKPOINT_METADATA").write_text("{}")
    assert _has_snapshot(str(ck))


def test_fused_population_must_divide_mesh(capsys):
    """--fused --population 100 on an 8-device mesh would replicate the
    standing cohort on every device (an effectively single-device
    sweep); the CLI refuses with the fix spelled out (VERDICT r3 #7)."""
    with pytest.raises(SystemExit):
        main(
            [
                "--workload", "fashion_mlp",
                "--algorithm", "pbt",
                "--fused",
                "--population", "100",
                "--generations", "2",
            ]
        )
    err = capsys.readouterr().err
    assert "does not divide the mesh 'pop' axis" in err
    assert "--population 96 or 104" in err


def test_fused_retries_transient_failure(capsys, monkeypatch):
    """--retries N: a transient runtime death (worker crash/restart)
    mid-sweep is retried — with --checkpoint-dir that retry is a resume,
    the automatic form of the kill-and-rerun recovery the snapshot tests
    prove by hand (SURVEY.md §5 failure recovery)."""
    import mpi_opt_tpu.train.fused_pbt as fpbt

    real = fpbt.fused_pbt
    calls = {"n": 0}

    def flaky(workload, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            # the class an accelerator runtime's crash errors arrive as —
            # _is_transient type-gates on it before the marker scan
            raise jax.errors.JaxRuntimeError(
                "TPU worker process crashed or restarted"
            )
        return real(workload, **kw)

    monkeypatch.setattr(fpbt, "fused_pbt", flaky)
    argv = [
        "--workload", "fashion_mlp",
        "--algorithm", "pbt",
        "--fused",
        "--population", "8",
        "--generations", "2",
        "--steps-per-generation", "4",
        "--no-mesh",
    ]
    # without --retries the failure propagates
    with pytest.raises(RuntimeError, match="crashed"):
        main(argv)
    capsys.readouterr()
    calls["n"] = 0
    assert main(argv + ["--retries", "1"]) == 0
    assert calls["n"] == 2
    out = capsys.readouterr().out
    assert '"event": "retry"' in out  # the retry is visible in metrics
    summary = _summary_from(out)
    assert 0.0 <= summary["best_score"] <= 1.0


def test_fused_retries_never_mask_program_errors(monkeypatch, capsys):
    """A non-transient error (the program being wrong) is NEVER retried:
    N retries of a shape error are N identical failures."""
    import mpi_opt_tpu.train.fused_pbt as fpbt

    calls = {"n": 0}

    def broken(workload, **kw):
        calls["n"] += 1
        raise ValueError("bad shapes")

    monkeypatch.setattr(fpbt, "fused_pbt", broken)
    with pytest.raises(ValueError, match="bad shapes"):
        main([
            "--workload", "fashion_mlp", "--algorithm", "pbt", "--fused",
            "--population", "4", "--generations", "1", "--no-mesh",
            "--retries", "3",
        ])
    assert calls["n"] == 1
    capsys.readouterr()


def test_multihost_flags_must_be_complete(capsys):
    """Partial bring-up flags are a launch-script bug: refuse with the
    full recipe rather than auto-detecting half a cluster."""
    with pytest.raises(SystemExit):
        main([
            "--workload", "fashion_mlp", "--algorithm", "pbt", "--fused",
            "--population", "4", "--generations", "1", "--no-mesh",
            "--coordinator", "127.0.0.1:1234",
        ])
    err = capsys.readouterr().err
    assert "--coordinator, --num-processes and --process-id" in err


def test_fused_retries_type_gate_beats_marker_text(monkeypatch, capsys):
    """A program error whose MESSAGE happens to quote a transient marker
    (a dataset path containing 'unavailable') must not be retried: the
    type gate runs before the substring scan (ADVICE r4 / VERDICT r4
    weak #4)."""
    import mpi_opt_tpu.train.fused_pbt as fpbt

    calls = {"n": 0}

    def broken(workload, **kw):
        calls["n"] += 1
        raise ValueError("dataset file '/data/unavailable/train.npz' deadline")

    monkeypatch.setattr(fpbt, "fused_pbt", broken)
    with pytest.raises(ValueError, match="unavailable"):
        main([
            "--workload", "fashion_mlp", "--algorithm", "pbt", "--fused",
            "--population", "4", "--generations", "1", "--no-mesh",
            "--retries", "3",
        ])
    assert calls["n"] == 1
    capsys.readouterr()


def _summary_from(out):
    lines = [l for l in out.strip().splitlines() if l.startswith("{")]
    for l in reversed(lines):
        d = json.loads(l)
        if "best_score" in d:
            return d
    raise AssertionError(out)


@pytest.mark.chaos
def test_cli_chaos_drill_counts_failures_and_matches_clean_best(capsys):
    """--chaos end-to-end: the sweep completes, the summary carries the
    injected-failure counters, and the best trial matches the clean
    run's (constants shared with tests/test_chaos.py)."""
    base = [
        "--workload", "quadratic",
        "--algorithm", "random",
        "--trials", "30",
        "--budget", "20",
        "--workers", "2",
        "--seed", "0",
    ]
    assert main(base) == 0
    clean = _summary(capsys)
    assert clean["trials_failed"] == 0

    assert main(base + ["--chaos", "exc=0.12,nan=0.08,seed=19"]) == 0
    out = capsys.readouterr().out
    drill = _summary_from(out)
    assert drill["trials_failed"] == 9  # 5 exc + 4 nan, deterministic
    assert drill["trials_retried"] == 0 and drill["trials_timeout"] == 0
    assert drill["best_score"] == pytest.approx(clean["best_score"], abs=1e-9)
    assert drill["best_params"] == clean["best_params"]
    # per-trial failures are visible as metrics events, not just tallies
    assert '"event": "trial_failed"' in out
    # the summary EVENT carries the counters too (operators tail metrics)
    summary_events = [
        json.loads(l) for l in out.splitlines()
        if l.startswith("{") and '"event": "summary"' in l
    ]
    assert summary_events and summary_events[-1]["trials_failed"] == 9


@pytest.mark.chaos
def test_cli_trial_retries_reach_the_driver(capsys):
    """--trial-retries N: retry attempts show up in the summary counters
    (chaos faults are deterministic, so every retry re-fails — the knob
    exists for nondeterministic production failures)."""
    rc = main([
        "--workload", "quadratic", "--algorithm", "random",
        "--trials", "30", "--budget", "20", "--workers", "2", "--seed", "0",
        "--chaos", "exc=0.12,nan=0.08,seed=19",
        "--trial-retries", "1",
    ])
    assert rc == 0
    s = _summary(capsys)
    assert s["trials_failed"] == 9
    assert s["trials_retried"] == 9


@pytest.mark.chaos
def test_cli_max_failure_rate_aborts_systemic_failure(capsys):
    """A sweep whose failure fraction crosses --max-failure-rate exits
    nonzero with an 'aborted' line instead of grinding to the end."""
    rc = main([
        "--workload", "quadratic", "--algorithm", "random",
        "--trials", "60", "--budget", "20", "--workers", "1", "--seed", "0",
        "--chaos", "exc=0.9,seed=0",
        "--max-failure-rate", "0.5",
    ])
    assert rc == 1
    captured = capsys.readouterr()
    lines = [l for l in captured.out.strip().splitlines() if l.startswith("{")]
    aborted = json.loads(lines[-1])
    assert "aborted" in aborted and "max_failure_rate" in aborted["aborted"]
    assert "systemic" in captured.err


def test_cli_chaos_rejects_fused():
    with pytest.raises(SystemExit):
        main([
            "--workload", "fashion_mlp", "--algorithm", "pbt", "--fused",
            "--population", "4", "--generations", "1",
            "--chaos", "exc=0.5",
        ])


def test_cli_chaos_rejects_bad_spec(capsys):
    with pytest.raises(SystemExit):
        main([
            "--workload", "quadratic", "--trials", "2",
            "--chaos", "explode=0.5",
        ])
    assert "unknown chaos key" in capsys.readouterr().err


def test_cli_chaos_rejects_tpu_backend(capsys):
    with pytest.raises(SystemExit):
        main([
            "--workload", "fashion_mlp", "--backend", "tpu",
            "--trials", "2", "--chaos", "exc=0.5",
        ])
    assert "cpu backend" in capsys.readouterr().err


def test_fused_summary_reports_member_failures(fused_pbt_no_mesh):
    """Every fused sweep's summary carries the per-generation diverged-
    member tallies (ROADMAP open item) — zero for a healthy sweep, but
    PRESENT, so operators can alarm on it."""
    out = fused_pbt_no_mesh
    summary = _summary_from(out)
    assert summary["member_failures"] == [0, 0]
    # ...and the metrics summary event carries the total
    events = [json.loads(l) for l in out.splitlines() if '"event": "summary"' in l]
    assert events[-1]["member_failures"] == 0


# -- durable sweep ledger (--ledger / --warm-start / report) ---------------


LEDGER_ARGS = [
    "--workload", "quadratic",
    "--algorithm", "random",
    "--trials", "10",
    "--budget", "20",
    "--workers", "1",
    "--seed", "0",
]


def test_cli_ledger_journals_and_resumes(capsys, tmp_path):
    """--ledger end-to-end: journal a sweep, refuse a stale ledger
    without --resume, replay it fully with --resume (zero evaluations),
    and report the same best."""
    led = str(tmp_path / "sweep.jsonl")
    assert main(LEDGER_ARGS + ["--ledger", led]) == 0
    first = _summary(capsys)
    lines = open(led).read().splitlines()
    assert len(lines) == 11  # header + one record per trial
    assert json.loads(lines[0])["kind"] == "header"

    with pytest.raises(SystemExit):  # stale ledger, no --resume: refuse
        main(LEDGER_ARGS + ["--ledger", led])
    assert "pass --resume" in capsys.readouterr().err

    assert main(LEDGER_ARGS + ["--ledger", led, "--resume"]) == 0
    resumed = _summary(capsys)
    assert resumed["replayed"] == 10
    assert resumed["best_score"] == pytest.approx(first["best_score"], abs=1e-12)
    # a full replay journals nothing new
    assert len(open(led).read().splitlines()) == 11


def test_cli_ledger_refuses_config_drift(capsys, tmp_path):
    led = str(tmp_path / "sweep.jsonl")
    assert main(LEDGER_ARGS + ["--ledger", led]) == 0
    capsys.readouterr()
    with pytest.raises(SystemExit):
        main(LEDGER_ARGS[:-1] + ["7", "--ledger", led, "--resume"])  # other seed
    assert "different sweep" in capsys.readouterr().err


def test_cli_warm_start_and_space_check(capsys, tmp_path):
    led = str(tmp_path / "prior.jsonl")
    assert main(LEDGER_ARGS + ["--ledger", led]) == 0
    prior = _summary(capsys)
    # a warm-started sweep over the same space runs fine and its first
    # suggestion is the prior best (seed 1 would otherwise sample fresh)
    rc = main(
        [
            "--workload", "quadratic", "--algorithm", "random",
            "--trials", "4", "--budget", "20", "--workers", "1",
            "--seed", "1", "--warm-start", led,
        ]
    )
    assert rc == 0
    out = capsys.readouterr().out
    warm = _summary_from(out)
    assert '"event": "warm_start"' in out
    assert warm["best_score"] >= prior["best_score"] - 1e-9
    # a different workload = different space: refused via the space hash
    with pytest.raises(SystemExit):
        main(
            [
                "--workload", "digits", "--algorithm", "random",
                "--trials", "2", "--workers", "1", "--warm-start", led,
            ]
        )
    assert "space hash" in capsys.readouterr().err


def test_cli_ledger_flag_validation(capsys, tmp_path):
    led = str(tmp_path / "l.jsonl")
    for argv, msg in (
        (
            ["--workload", "quadratic", "--trials", "2",
             "--ledger", led, "--warm-start", led],
            "PRIOR sweep",
        ),
        (
            # a path ALIAS of the same file is still self-feeding
            ["--workload", "quadratic", "--trials", "2", "--ledger", led,
             "--warm-start", str(tmp_path / "." / "l.jsonl")],
            "PRIOR sweep",
        ),
        (
            # the self-feed guard is mode-independent (fused included)
            ["--workload", "fashion_mlp", "--algorithm", "pbt", "--fused",
             "--population", "4", "--generations", "1", "--ledger", led,
             "--warm-start", led],
            "PRIOR sweep",
        ),
    ):
        with pytest.raises(SystemExit) as exc:
            main(argv)
        assert exc.value.code == 2
        assert msg in capsys.readouterr().err


def test_cli_bad_warm_start_does_not_wedge_fresh_ledger(capsys, tmp_path):
    """--warm-start is validated BEFORE the new ledger's header commits:
    a typo'd prior path must not journal itself into the fresh ledger's
    identity (which would refuse the corrected re-run)."""
    led = str(tmp_path / "new.jsonl")
    with pytest.raises(SystemExit) as exc:
        main(LEDGER_ARGS + ["--ledger", led, "--warm-start", str(tmp_path / "typo.jsonl")])
    assert exc.value.code == 2
    assert "--warm-start" in capsys.readouterr().err
    assert not os.path.exists(led)  # nothing was committed
    # the corrected re-run works with the same --ledger path
    prior = str(tmp_path / "prior.jsonl")
    assert main(LEDGER_ARGS + ["--ledger", prior]) == 0
    capsys.readouterr()
    assert main(LEDGER_ARGS + ["--ledger", led, "--warm-start", prior]) == 0
    capsys.readouterr()


def test_cli_warm_start_not_reingested_on_checkpoint_resume(capsys, tmp_path):
    """Priors ingested before a checkpoint live inside the restored
    state (TPE's obs ring is checkpointed): a --resume re-run must skip
    re-ingestion instead of double-weighting them."""
    prior = str(tmp_path / "prior.jsonl")
    assert main(LEDGER_ARGS + ["--ledger", prior]) == 0
    capsys.readouterr()
    ck = str(tmp_path / "ck")
    base = [
        "--workload", "quadratic", "--algorithm", "tpe",
        "--trials", "6", "--budget", "20", "--workers", "1", "--seed", "3",
        "--warm-start", prior, "--checkpoint-dir", ck,
    ]
    assert main(base) == 0
    assert '"event": "warm_start"' in capsys.readouterr().out
    out2 = None
    assert main(base + ["--resume"]) == 0
    out2 = capsys.readouterr().out
    assert '"event": "warm_start_skipped"' in out2
    assert '"event": "warm_start"' not in out2.replace("warm_start_skipped", "X")


def test_report_subcommand_text_json_and_validate(capsys, tmp_path):
    """`mpi_opt_tpu report`: renders a ledger, --json machine mode, and
    --validate as the CI schema gate (exit 1 on malformed records) —
    this test IS the tier-1 wiring that catches ledger-format drift."""
    led = str(tmp_path / "sweep.jsonl")
    # chaos seed 6 injects 4 exc faults over this 10-trial capacity-1
    # stream (faults are a pure function of (seed, params), so the
    # count is stable across machines)
    assert main(LEDGER_ARGS + ["--ledger", led, "--chaos", "exc=0.2,seed=6"]) == 0
    sweep = _summary(capsys)

    assert main(["report", led]) == 0
    out = capsys.readouterr().out
    assert "best:" in out and "failed=" in out

    assert main(["report", led, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    one = rep["ledgers"][0]
    assert one["trials"] == 10
    assert one["by_status"]["failed"] > 0  # the chaos drill's injections
    assert one["by_status"]["ok"] + one["by_status"]["failed"] == 10
    # the sweep summary rounds to 6 decimals; the report keeps full precision
    assert rep["best"]["score"] == pytest.approx(sweep["best_score"], abs=1e-6)

    assert main(["report", led, "--validate"]) == 0

    # any malformed record (torn tail included) fails validation loudly
    with open(led, "a") as f:
        f.write('{"kind": "trial", "trial_id": 99, "trunc')
    assert main(["report", led, "--validate"]) == 1
    capsys.readouterr()


def test_cli_fsck_json_schema_repair_resume_cycle(capsys, tmp_path):
    """`mpi_opt_tpu fsck`: the CI contract mirroring report --validate —
    exit 0 + ok:true on a clean tree, exit 1 with the corrupt step named
    after bit-rot, --repair quarantines, --resume recovers via last-good
    fallback, and the final audit shows the quarantine. This test IS the
    tier-1 wiring that catches fsck schema drift (probes/tier1.sh runs
    the same cycle as a shell drill)."""
    from mpi_opt_tpu.workloads.chaos import inject_corrupt_save

    ck = str(tmp_path / "ck")
    base = [
        "--workload", "quadratic", "--algorithm", "random",
        "--trials", "6", "--budget", "3", "--workers", "1",
        "--seed", "0", "--checkpoint-dir", ck,
    ]
    assert main(base) == 0
    capsys.readouterr()

    assert main(["fsck", ck, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    # the stable schema fsck's CI consumers key on
    assert set(rep) >= {
        "dir", "ok", "steps", "newest_verified", "repaired", "quarantined", "ledger",
    }
    assert rep["ok"] is True
    assert [s["status"] for s in rep["steps"]] == ["verified"] * 3  # keep=3
    assert rep["newest_verified"]["step"] == 6

    inject_corrupt_save(ck)
    assert main(["fsck", ck, "--json"]) == 1
    rep = json.loads(capsys.readouterr().out)
    assert rep["ok"] is False
    assert {s["step"]: s["status"] for s in rep["steps"]}[6] == "corrupt"

    assert main(["fsck", ck, "--json", "--repair"]) == 1  # found + repaired
    rep = json.loads(capsys.readouterr().out)
    assert rep["repaired"] == ["6.corrupt"]

    # --resume recovers from the prior verified step and completes
    assert main(base + ["--resume"]) == 0
    out = capsys.readouterr().out
    assert '"event": "resume"' in out and '"step": 5' in out
    s = _summary_from(out)
    assert s["n_trials"] == 6 and s["best_score"] is not None

    assert main(["fsck", ck, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["ok"] is True and rep["quarantined"] == ["6.corrupt"]


def test_cli_resume_with_no_verified_snapshot_exits_data_error(capsys, tmp_path):
    """Every retained step poisoned: --resume must exit the distinct
    EX_DATAERR (65) — the code launch.py refuses to retry — after
    quarantining the evidence, and say so on the single-JSON-line
    contract."""
    from mpi_opt_tpu.workloads.chaos import (
        _committed_step_dirs,
        inject_corrupt_save,
    )

    ck = str(tmp_path / "ck")
    base = [
        "--workload", "quadratic", "--algorithm", "random",
        "--trials", "4", "--budget", "3", "--workers", "1",
        "--seed", "0", "--checkpoint-dir", ck,
    ]
    assert main(base) == 0
    capsys.readouterr()
    poisoned = [step for step, _path in _committed_step_dirs(ck)]
    for step in poisoned:
        inject_corrupt_save(ck, step=step)
    assert len(poisoned) == 3  # keep=3 retained steps, all now bad
    rc = main(base + ["--resume"])
    out = capsys.readouterr().out
    assert rc == 65
    lines = [json.loads(l) for l in out.splitlines() if l.startswith("{")]
    data_err = [l for l in lines if "data_error" in l]
    assert data_err and "no verified snapshot" in data_err[-1]["data_error"]
    # the corruption events reached the metrics stream with the counter
    summaries = [l for l in lines if l.get("event") == "summary"]
    assert summaries[-1]["snapshots_quarantined"] == 3
    assert sum(1 for l in lines if l.get("event") == "snapshot_corrupt") == 3
    # quarantines, not deletions
    assert sorted(d for d in os.listdir(ck) if d.endswith(".corrupt")) == [
        f"{s}.corrupt" for s in poisoned
    ]


def test_cli_validates_failure_policy_flags(capsys):
    """Bad policy values are usage errors (exit 2 + message), not raw
    ValueError tracebacks from deep inside the run."""
    for argv, msg in (
        (["--trial-retries", "-1"], "--trial-retries must be >= 0"),
        (["--max-failure-rate", "0"], "--max-failure-rate must be in (0, 1]"),
        (["--max-failure-rate", "1.5"], "--max-failure-rate must be in (0, 1]"),
        (["--trial-timeout", "0"], "--trial-timeout must be > 0"),
    ):
        with pytest.raises(SystemExit) as exc:
            main(["--workload", "quadratic", "--trials", "2", *argv])
        assert exc.value.code == 2
        assert msg in capsys.readouterr().err


# -- graceful shutdown (health/): exit 75, flushed state, free resume ------


def test_cli_isolate_stateful_rejected_off_the_cpu_path(capsys):
    with pytest.raises(SystemExit) as exc:
        main([
            "--workload", "fashion_mlp", "--algorithm", "pbt", "--fused",
            "--population", "4", "--generations", "1",
            "--isolate-stateful",
        ])
    assert exc.value.code == 2
    assert "--isolate-stateful" in capsys.readouterr().err


@pytest.mark.chaos
def test_cli_preempt_drill_exits_75_with_flushed_ledger_then_resumes(capsys, tmp_path):
    """The acceptance drill, in-process: a chaos ``preempt`` SIGTERM
    mid-sweep yields a flushed ledger and exit code 75; the re-run with
    --resume replays the journaled trials and finishes with the clean
    run's best. Chaos seed 13 puts the single preempt draw at trial
    index 6 of this 12-trial seed-0 stream (so the drain journals 7
    trials)."""
    clean_args = [
        "--workload", "quadratic", "--algorithm", "random",
        "--trials", "12", "--budget", "10", "--workers", "1", "--seed", "0",
    ]
    assert main(clean_args) == 0
    clean = _summary(capsys)

    led = str(tmp_path / "sweep.jsonl")
    drill = clean_args + ["--ledger", led, "--chaos", "preempt=0.15,seed=13"]
    rc = main(drill)
    out = capsys.readouterr().out
    assert rc == 75
    pre = [
        json.loads(l) for l in out.splitlines()
        if l.startswith("{") and '"preempted": true' in l and '"event"' not in l
    ][-1]
    assert pre["signal"] == "SIGTERM" and pre["trials_done"] == 7
    # the metrics summary event carries the preempted counter
    sev = [json.loads(l) for l in out.splitlines() if '"event": "summary"' in l][-1]
    assert sev["preempted"] == 1
    # the journal was fsync-flushed BEFORE exit: header + 7 trials
    lines = open(led).read().splitlines()
    assert len(lines) == 8
    assert json.loads(lines[0])["kind"] == "header"

    # resume: replay the 7, run the remaining 5, match the clean best
    assert main(drill + ["--resume"]) == 0
    resumed = _summary(capsys)
    assert resumed["replayed"] == 7
    assert resumed["n_trials"] == 12
    assert resumed["best_score"] == pytest.approx(clean["best_score"], abs=1e-12)


def test_fused_preempt_drains_snapshot_and_exits_75(capsys, tmp_path, monkeypatch):
    """Fused sweeps drain at launch boundaries too: with a shutdown
    pending, the first launch completes, its snapshot is flushed, and
    the CLI exits 75; the --resume re-run finishes the sweep from that
    snapshot. The drain flag is stubbed (not a real signal) so the test
    is deterministic about WHERE the preemption lands."""
    from mpi_opt_tpu.health import shutdown as shutdown_mod

    ck = str(tmp_path / "ck")
    argv = [
        "--workload", "fashion_mlp", "--algorithm", "pbt", "--fused",
        "--population", "4", "--generations", "2",
        "--steps-per-generation", "2", "--gen-chunk", "1", "--no-mesh",
        "--seed", "0", "--checkpoint-dir", ck,
    ]
    monkeypatch.setattr(shutdown_mod, "requested", lambda: True)
    monkeypatch.setattr(shutdown_mod, "active_signal", lambda: "SIGTERM")
    rc = main(argv)
    out = capsys.readouterr().out
    assert rc == 75
    pre = [
        json.loads(l) for l in out.splitlines()
        if l.startswith("{") and '"preempted": true' in l
    ][-1]
    assert pre["backend"] == "fused" and "launch 1/2" in pre["at"]
    monkeypatch.undo()  # signals back to normal: the resume must finish
    assert main(argv + ["--resume"]) == 0
    resumed = _summary(capsys)
    assert len(resumed["best_curve"]) == 2  # both generations present
    assert 0.0 <= resumed["best_score"] <= 1.0


def test_cli_heartbeat_file_beats_per_batch(tmp_path, capsys):
    """--heartbeat-file: the driver writes one monotonic beat per
    completed batch — the liveness signal launch.py's stall watchdog
    consumes — and the configuration never leaks past main()."""
    from mpi_opt_tpu.health import heartbeat, read_beat

    hb = str(tmp_path / "rank0.hb")
    rc = main([
        "--workload", "quadratic", "--algorithm", "random",
        "--trials", "4", "--budget", "10", "--workers", "1", "--seed", "0",
        "--heartbeat-file", hb,
    ])
    capsys.readouterr()
    assert rc == 0
    rec = read_beat(hb)
    assert rec is not None and rec["beats"] == 4  # one per batch
    assert rec["progress"]["stage"] == "driver"
    assert heartbeat.active() is None  # deconfigured on the way out


def test_cli_wave_size_validation(capsys):
    """--wave-size bad values / wrong context are usage errors (rc=2),
    not tracebacks from fused_pbt deep in the run."""
    base = ["--workload", "fashion_mlp", "--algorithm", "pbt"]
    for argv in (
        base + ["--wave-size", "4"],  # requires --fused
        base + ["--fused", "--wave-size", "nope"],
        base + ["--fused", "--wave-size", "-1"],
        base + ["--fused", "--wave-size", "4", "--step-chunk", "2"],
        base + ["--fused", "--wave-size", "4", "--gen-chunk", "2"],
        # any algorithm is wave-capable now, but only under --fused
        ["--workload", "fashion_mlp", "--algorithm", "tpe",
         "--wave-size", "4"],
        ["--workload", "fashion_mlp", "--algorithm", "asha",
         "--wave-size", "4"],
    ):
        with pytest.raises(SystemExit) as ei:
            main(argv)
        assert ei.value.code == 2
        capsys.readouterr()


def test_cli_fused_sha_wave_summary_surfaces_staging(capsys):
    """--wave-size is no longer PBT-only: a fused SHA sweep accepts it
    and its summary carries the same staging observability block."""
    rc = main([
        "--workload", "fashion_mlp", "--algorithm", "asha", "--fused",
        "--trials", "8", "--min-budget", "2", "--max-budget", "4",
        "--eta", "2", "--wave-size", "4", "--no-mesh", "--seed", "0",
    ])
    assert rc == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    summary = json.loads(lines[-1])
    assert summary["wave_size"] == 4
    assert summary["staged_bytes"] > 0
    assert summary["rung_sizes"][0] == 8


def test_cli_fused_wave_summary_surfaces_staging(capsys):
    """--fused --wave-size: the summary JSON and the metrics summary
    both carry the staging observability (staged_bytes + overlap)."""
    rc = main([
        "--workload", "fashion_mlp", "--algorithm", "pbt", "--fused",
        "--population", "8", "--generations", "2",
        "--steps-per-generation", "3", "--wave-size", "4", "--no-mesh",
        "--seed", "0",
    ])
    assert rc == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    summary = json.loads(lines[-1])
    assert summary["wave_size"] == 4 and summary["n_waves"] == 2
    assert summary["staged_bytes"] > 0
    assert summary["stage_overlap_s"] >= 0
    msum = [json.loads(l) for l in lines if '"event": "summary"' in l][-1]
    assert msum["staged_bytes"] == summary["staged_bytes"]
    assert msum["stage_overlap_s"] >= 0


def test_cli_fused_diverged_summary_is_strict_json(capsys, monkeypatch):
    """ADVICE r5: an all-diverged fused sweep's NaNs (best_score AND
    curve entries) must serialize as null — json.dumps' bare NaN token
    breaks the single-JSON-line contract for strict parsers."""
    import mpi_opt_tpu.train.fused_pbt as fp

    nan = float("nan")
    diverged = {
        "best_score": nan,
        "best_params": None,
        "diverged": True,
        "best_curve": [0.5, nan],
        "mean_curve": [0.4, nan],
        "member_failures": [0, 8],
        "state": None,
        "unit": None,
        "launch_gens": [1, 1],
        "launch_walls": [0.1, 0.1],
    }
    monkeypatch.setattr(fp, "fused_pbt", lambda *a, **k: diverged)
    rc = main([
        "--workload", "fashion_mlp", "--algorithm", "pbt", "--fused",
        "--population", "8", "--generations", "2",
        "--steps-per-generation", "3", "--no-mesh",
    ])
    assert rc == 0
    line = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")][-1]

    def no_constants(s):  # NaN/Infinity tokens -> hard failure
        raise AssertionError(f"non-JSON constant emitted: {s}")

    summary = json.loads(line, parse_constant=no_constants)
    assert summary["best_score"] is None
    assert summary["best_params"] is None
    assert summary["best_curve"] == [0.5, None]


# -- fused-path ledger durability (ISSUE 6) --------------------------------


def test_cli_fused_ledger_preempt_resume_journal_identical(capsys, tmp_path, monkeypatch):
    """The fused acceptance drill end-to-end: a preempted --fused
    --ledger sweep exits 75 with the completed generation journaled;
    --resume re-trains only the incomplete generation; the final
    journal is record-identical to an unkilled run's and passes both
    `report --validate` and summary accounting."""
    from mpi_opt_tpu.health import shutdown as shutdown_mod
    from mpi_opt_tpu.ledger.report import report_main

    clean_led = str(tmp_path / "clean.jsonl")
    base = [
        "--workload", "fashion_mlp", "--algorithm", "pbt", "--fused",
        "--population", "4", "--generations", "2",
        "--steps-per-generation", "2", "--gen-chunk", "1", "--no-mesh",
        "--seed", "0",
    ]
    assert main(base + ["--ledger", clean_led]) == 0
    clean = _summary(capsys)
    assert clean["journal"] == {"written": 8, "verified": 0}

    led = str(tmp_path / "sweep.jsonl")
    ck = str(tmp_path / "ck")
    drill = base + ["--ledger", led, "--checkpoint-dir", ck]
    # drain at the FIRST boundary (the final boundary suppresses the
    # poll, so a 2-generation sweep has exactly one drain point) — the
    # generation's members are journaled BEFORE the drain honors the flag
    monkeypatch.setattr(shutdown_mod, "requested", lambda: True)
    monkeypatch.setattr(shutdown_mod, "active_signal", lambda: "SIGTERM")
    assert main(drill) == 75
    out = capsys.readouterr().out
    assert '"preempted": true' in out
    # generation 0's members were journaled before the drain
    assert len(open(led).read().splitlines()) == 1 + 4
    monkeypatch.undo()

    assert main(drill + ["--resume"]) == 0
    resumed = _summary(capsys)
    # only the incomplete generation re-journals; nothing re-verifies
    # (the completed one was never re-computed — its snapshot replayed)
    assert resumed["journal"] == {"written": 4, "verified": 0}
    assert resumed["best_score"] == clean["best_score"]

    def records(path):
        keep = ("trial_id", "member", "boundary", "boundary_size", "params",
                "status", "score", "step")
        return [
            {k: r[k] for k in keep}
            for r in map(json.loads, open(path).read().splitlines()[1:])
        ]

    assert records(led) == records(clean_led)
    assert report_main(["--validate", led, clean_led]) == 0
    capsys.readouterr()


def test_cli_fused_ledger_kill_fsck_repair_resume_cycle(capsys, tmp_path):
    """The tier-1 drill's state machine, in-process: a mid-journal kill
    leaves a torn final boundary + a snapshot at the previous one; fsck
    --ledger flags it (exit 1), --resume self-heals and re-journals,
    and the post-recovery audit is clean (validate + fsck exit 0)."""
    import shutil

    from mpi_opt_tpu.ledger.report import report_main
    from mpi_opt_tpu.utils.integrity import fsck_main

    led = str(tmp_path / "sweep.jsonl")
    ck = str(tmp_path / "ck")
    argv = [
        "--workload", "fashion_mlp", "--algorithm", "pbt", "--fused",
        "--population", "4", "--generations", "2",
        "--steps-per-generation", "2", "--gen-chunk", "1", "--no-mesh",
        "--seed", "0", "--ledger", led, "--checkpoint-dir", ck,
    ]
    assert main(argv) == 0
    clean_lines = open(led).read().splitlines()
    capsys.readouterr()

    # reconstruct the kill-mid-journal state: boundary 1 half-written
    # (2 of 4 records), and the snapshot that would have covered it
    # never committed — exactly what dying between record 6 and 7 leaves
    open(led, "w").write("\n".join(clean_lines[:7]) + "\n")
    shutil.rmtree(os.path.join(ck, "2"))

    assert fsck_main([ck, "--ledger", led]) == 1  # torn boundary FLAGGED
    out = capsys.readouterr().out
    assert "torn" in out
    assert main(argv + ["--resume"]) == 0  # heals + verifies + re-journals
    capsys.readouterr()

    # the healed + re-journaled ledger carries the clean run's exact
    # record content (only timestamps may differ)
    def strip_ts(lines):
        return [
            {k: v for k, v in json.loads(l).items() if k != "ts"}
            for l in lines
        ]

    assert strip_ts(open(led).read().splitlines()) == strip_ts(clean_lines)
    assert report_main(["--validate", led]) == 0
    assert fsck_main([ck, "--ledger", led]) == 0  # post-recovery audit clean
    capsys.readouterr()


def test_cli_fused_ledger_divergence_exits_data_error(capsys, tmp_path):
    """A journal whose scores belong to a DIFFERENT trajectory is a
    data dead-end: the resume's boundary verification raises and the
    CLI exits 65 (non-retryable), never silently re-writing history."""
    led = str(tmp_path / "sweep.jsonl")
    argv = [
        "--workload", "fashion_mlp", "--algorithm", "pbt", "--fused",
        "--population", "4", "--generations", "1",
        "--steps-per-generation", "2", "--no-mesh", "--seed", "0",
        "--ledger", led,
    ]
    assert main(argv) == 0
    capsys.readouterr()
    lines = open(led).read().splitlines()
    rec = json.loads(lines[1])
    rec["score"] = 0.123456  # a score this seed never produced
    lines[1] = json.dumps(rec)
    open(led, "w").write("\n".join(lines) + "\n")
    assert main(argv + ["--resume"]) == 65
    out = capsys.readouterr().out
    assert '"data_error"' in out and "diverges" in out


def test_cli_fused_warm_start_cross_mode(capsys, tmp_path):
    """--warm-start with --fused: a prior ledger (either mode) seeds
    the fused sweep; refusal happens ONLY on space-hash mismatch."""
    prior = str(tmp_path / "prior.jsonl")
    assert main([
        "--workload", "fashion_mlp", "--algorithm", "pbt", "--fused",
        "--population", "4", "--generations", "1",
        "--steps-per-generation", "2", "--no-mesh", "--seed", "0",
        "--ledger", prior,
    ]) == 0
    capsys.readouterr()
    fused_tpe = [
        "--workload", "fashion_mlp", "--algorithm", "tpe", "--fused",
        "--trials", "4", "--population", "2", "--budget", "2", "--no-mesh",
        "--seed", "1", "--warm-start", prior,
    ]
    assert main(fused_tpe) == 0
    out = capsys.readouterr().out
    assert '"event": "warm_start"' in out and '"observations": 4' in out

    # forge a foreign space hash: the SAME file now refuses — proving
    # the gate is the space, not the mode
    lines = open(prior).read().splitlines()
    hdr = json.loads(lines[0])
    hdr["config"]["space_hash"] = "feedfacefeedface"
    open(prior, "w").write("\n".join([json.dumps(hdr)] + lines[1:]) + "\n")
    with pytest.raises(SystemExit) as exc:
        main(fused_tpe)
    assert exc.value.code == 2
    assert "space hash" in capsys.readouterr().err
