"""The window hook, alone and on a `--rehearse` sweep."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import window  # noqa: E402

LIMITS = os.path.join(HERE, "data", "rehearse_limits.json")
CELL = "cifar10_cnn.pbt_pop512"


class Clock:
    def __init__(self, times):
        self.times = list(times)

    def __call__(self):
        return self.times.pop(0)


def test_window_is_whole_generations_and_stops_before_seconds():
    asked = []
    w = window.Window(10.0, lambda: asked.append(1), clock=Clock([100.0, 103.0, 106.0, 109.0, 112.0]))
    for _ in range(4):
        w.hook("pbt launch")
    # boundaries at +3, +6, +9: a fourth generation (3 s) would end at 12 > 10
    assert w.closed and asked == [1]
    assert w.generations == 3 and w.end - w.start == pytest.approx(9.0)
    w.hook("late")  # the draining boundary may call again: ignored
    assert w.generations == 3


def test_one_generation_longer_than_the_window_still_counts():
    asked = []
    w = window.Window(5.0, lambda: asked.append(1), clock=Clock([0.0, 29.0]))
    w.hook("a")
    assert not w.closed and w.generations == 0
    w.hook("b")
    assert w.closed and w.generations == 1 and w.periods() == [29.0]


def _run(args, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env.update({"JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"}, **(env_extra or {}))
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py")] + args,
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )


@pytest.fixture(scope="module")
def rehearsal():
    p = _run(["--workload", CELL, "--seed", "11", "--seconds", "12", "--trace", "1",
              "--rehearse", "--limits", LIMITS])
    assert p.returncode == 0, p.stderr[-2000:]
    return p, json.loads(p.stdout.strip().splitlines()[-1])


def test_rehearsal_line_has_the_contract_keys_and_reads_as_cpu(rehearsal):
    _, res = rehearsal
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "compared"
    assert res["device"]["platform"] == "cpu" and res["rehearsal"] is True
    assert res["correct"] is True and res["failed"] == 0


def test_rehearsal_window_closed_by_the_hook(rehearsal):
    p, res = rehearsal
    w = res["window"]
    assert w["generations"] >= 1 and res["attempted"] == 4 * w["generations"]
    assert w["generations"] == 1 or w["seconds"] <= 12
    assert "exit 75" in p.stderr  # the drain a preemption takes, taken as success
    for name in ("compiles_in_window", "engine_host_s_per_gen", "train_s_per_gen", "compile_s"):
        assert name in res["metrics"]
    assert res["metrics"]["compiles_in_window"]["value"] == 0
    # no device trace on the CPU: those readers return nothing, never 0
    assert "device_idle_share" not in res["metrics"]
    assert "kernels_roofline" not in res["metrics"] and "train_mfu" not in res["metrics"]


def test_compared_numbers_are_the_last_lines_of_stderr(rehearsal):
    p, res = rehearsal
    tail = p.stderr.strip().splitlines()[-len(res["compared"]):]
    assert all(line.startswith("compared ") and " limit " in line for line in tail)


def test_no_tpu_and_no_rehearse_exits_nonzero_with_no_result():
    p = _run(["--workload", CELL, "--seed", "1", "--seconds", "5", "--trace", "0"])
    assert p.returncode != 0
    assert not any(line.startswith("{") and '"correct"' in line for line in p.stdout.splitlines())


def test_outside_a_checkout_exits_nonzero(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks", ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed", "1", "--seconds", "5",
         "--trace", "0", "--rehearse"],
        cwd=tmp_path, env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""},
        capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0 and '"correct"' not in p.stdout
