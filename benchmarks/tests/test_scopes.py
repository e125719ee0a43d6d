"""Device seconds by the program's scopes: the wire walk, the naming
rules, and the reduction of a recorded fused generation.

`data/small.xplane.pb`: PR 24's three rounds of a small conv program
(no scope in it). `data/scoped.xplane.pb`: one fused PBT generation of
the `cifar10_cnn` configuration at its rehearse sizes on a TPU v5 lite,
device planes only (`record_scoped_trace.py`, PR 25).
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import scopes  # noqa: E402

SMALL = os.path.join(HERE, "data", "small.xplane.pb")
SCOPED = os.path.join(HERE, "data", "scoped.xplane.pb")

STEP = "jit(run_fused_pbt)/while/body/closed_call/jit(_train_segment)/train_segment/while/body/closed_call/"
MEMBER = STEP + "map_members/while/body/closed_call/"
EVAL = "jit(run_fused_pbt)/while/body/closed_call/jit(eval_population)/eval_population/while/body/closed_call/"


def test_wire_walk_reads_the_paths_of_the_small_trace():
    paths = {scopes.xplane._short(k): v for k, v in scopes.event_paths(SMALL).items()}
    assert paths["fusion.2"] == ("jit(step)/conv_general_dilated", "convolution fusion")
    assert paths["copy.1"] == ("jit(step)/tanh", "data formatting")
    assert paths["copy-start"] == ("", "copy-start")  # no tf_op: no path


@pytest.mark.parametrize(
    "path, phase",
    [
        (MEMBER + "vmap(jvp(member_loss))/SmallCNN/conv1/conv_general_dilated", "forward"),
        (MEMBER + "vmap(transpose(jvp(member_loss)))/SmallCNN/conv1/conv_general_dilated", "backward"),
        (MEMBER + "vmap(jvp(member_loss))/augment/jit(_roll_dynamic)/concatenate", "input"),
        (MEMBER + "vmap(optimizer_update)/mul", "optimizer"),
        (MEMBER + "vmap()/add", "train_rest"),
        (STEP + "map_members/while/body/dynamic_update_slice", "train_rest"),
        (STEP + "train_input/jit(_take)/gather", "input"),
        (STEP + "add", "train_rest"),
        (EVAL + "map_members/while/body/closed_call/vmap(SmallCNN)/gn0/reduce_sum", "eval"),
        (EVAL + "map_members/while/body/dynamic_slice", "eval"),
        ("jit(run_fused_pbt)/while/body/closed_call/exploit/jit(argsort)/sort", "exploit"),
        ("jit(run_fused_pbt)/while/body/closed_call/jit(gather_members)/gather_members/gather", "exploit"),
        ("jit(run_fused_pbt)/while/body/closed_call/is_finite", "unscoped"),
        # a name that only CONTAINS a scope's is not that scope
        ("jit(_threefry_split)/PopulationTrainer._train_input/while/body/add", "unscoped"),
        ("jit(step)/conv_general_dilated", "unscoped"),
        # nor is a jitted function of a scope's name (the parent commit's paths)
        ("jit(run_fused_pbt)/while/body/closed_call/jit(eval_population)/while/body/closed_call/vmap(SmallCNN)/conv0/conv_general_dilated", "unscoped"),
        ("jit(run_fused_pbt)/while/body/closed_call/jit(gather_members)/gather", "unscoped"),
        ("", "unscoped"),
    ],
)
def test_phase_of(path, phase):
    assert scopes.phase_of(path) == phase


@pytest.mark.parametrize(
    "path, category, cls",
    [
        (MEMBER + "vmap(jvp(member_loss))/SmallCNN/conv1/conv_general_dilated", "", "conv"),
        ("jit(step)/conv_general_dilated", "convolution fusion", "conv"),
        # a fusion the compiler calls a convolution is one whatever operation names it
        (MEMBER + "vmap(optimizer_update)/sub", "convolution fusion", "conv"),
        (MEMBER + "vmap(jvp(member_loss))/SmallCNN/gn0/reduce_sum", "non-fusion elementwise op", "groupnorm"),
        (MEMBER + "vmap(transpose(jvp(member_loss)))/ResNet/stage0_block0/gn1/mul", "loop fusion", "groupnorm"),
        (MEMBER + "vmap(jvp(member_loss))/ResNet/gn_stem/PallasGN/pallas_call", "custom-call", "groupnorm"),
        (MEMBER + "vmap(jvp(member_loss))/ResNet/stage1_block0/GroupNorm_0/rsqrt", "", "groupnorm"),
        (MEMBER + "vmap(jvp(member_loss))/SmallCNN/fc1/dot_general", "", "matmul"),
        (MEMBER + "vmap(transpose(jvp(member_loss)))/SmallCNN/select_and_scatter_add", "", "pool"),
        (MEMBER + "vmap(jvp(member_loss))/SmallCNN/reduce_window_max", "", "pool"),
        ("jit(step)/tanh", "data formatting", "copy"),
        (STEP + "map_members/while/body/dynamic_update_slice", "", "other"),
        ("", "", "other"),
    ],
)
def test_class_of(path, category, cls):
    assert scopes.class_of(path, category) == cls


def test_small_trace_has_no_scope_and_still_adds_up():
    red = scopes.reduce(SMALL)
    assert red["scoped_s"] == 0.0 and red["phase"]["unscoped"] == pytest.approx(red["busy_s"])
    assert red["busy_s"] == pytest.approx(12 * 114.05e-6, rel=0.02)  # as test_xplane reads it
    assert red["class"]["conv"] == pytest.approx(12 * 66.6e-6, rel=0.05)
    assert red["unscoped"][0][:2] == ["fusion.2", "jit(step)/conv_general_dilated"]


@pytest.fixture(scope="module")
def scoped():
    return scopes.reduce(SCOPED)


def test_recorded_generation_is_partitioned_by_phase(scoped):
    assert os.path.getsize(SCOPED) < 300_000
    busy = scoped["busy_s"]
    assert busy > 0
    assert sum(scoped["phase"].values()) == pytest.approx(busy, rel=0.01)
    assert sum(scoped["class"].values()) == pytest.approx(busy, rel=0.01)
    for phase in ("forward", "backward", "input", "train_rest", "eval", "exploit"):
        assert scoped["phase"][phase] > 0, phase
    # the TPU compiler left the update no kernel of its own: it rides in
    # the weight-gradient fusions, whose one path is the backward's
    assert scoped["phase"]["optimizer"] == 0.0
    assert scoped["class"]["conv"] > 0 and scoped["class"]["groupnorm"] > 0
    assert 100.0 * scoped["scoped_s"] / busy > 90.0
    assert len(scoped["unscoped"]) <= 10
    assert sum(r[2] for r in scoped["unscoped"]) <= scoped["phase"]["unscoped"] * (1 + 1e-9)


def test_table_prints_every_phase_and_class(scoped):
    text = scopes.format_table(scoped)
    for word in scopes.PHASES + scopes.CLASSES:
        assert word in text


def test_metric_readers_read_the_reduction(scoped, tmp_path):
    """Every device reader returns its part of one parsed reduction,
    found through the program's `profile` span; without that span (a
    program from before it) every one returns None."""
    import shutil
    import types

    import check

    run_dir = tmp_path / "plugins" / "profile" / "2026_01_01"
    run_dir.mkdir(parents=True)
    shutil.copy(SCOPED, run_dir / "host.xplane.pb")
    spans = [{"span": "profile", "op": "stop", "dir": str(tmp_path), "start": 1.0, "end": 2.0}]
    run = types.SimpleNamespace(spans=spans)
    old = types.SimpleNamespace(spans=[{"span": "train", "start": 1.0, "end": 2.0}])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"] if m["name"].startswith("device_") and "workloads" in m]
    assert len(names) == 10
    values = {}
    for name in names:
        mod = check.load_module(os.path.join(BENCH, "metrics", name + ".py"), "metric_" + name)
        values[name] = mod.read(run)
        assert mod.read(old) is None
    assert run._scopes == scoped  # parsed once, kept on the run
    phases = [values[f"device_{p}_s"] for p in ("forward", "backward", "optimizer", "input", "train_rest", "eval", "exploit")]
    assert sum(phases) + scoped["phase"]["unscoped"] == pytest.approx(scoped["busy_s"], rel=1e-9)
    assert values["device_conv_s"] == scoped["class"]["conv"]
    assert values["device_groupnorm_s"] == scoped["class"]["groupnorm"]
    assert 90.0 < values["device_scoped_share"] <= 100.0


def test_setup_unspanned_is_set_up_minus_the_union_of_spans():
    import types

    import check

    mod = check.load_module(os.path.join(BENCH, "metrics", "setup_unspanned_s.py"), "metric_setup_unspanned_s")
    window = types.SimpleNamespace(start=110.0)
    spans = [
        {"span": "setup", "start": 95.0, "end": 103.0},  # from before t0: clipped
        {"span": "compile", "start": 101.0, "end": 102.0},  # nested: counted once
        {"span": "train", "start": 104.0, "end": 109.0},
        {"span": "train", "start": 111.0, "end": 120.0},  # the window's: not set-up
    ]
    run = types.SimpleNamespace(t0=100.0, window=window, spans=spans)
    assert mod.read(run) == pytest.approx(2.0)  # 103-104 and 109-110
    assert mod.read(types.SimpleNamespace(t0=100.0, window=window, spans=[])) is None


def test_rehearsal_reports_the_front_door_and_no_device_metric():
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "cifar10_cnn.pbt_pop512",
         "--seed", "3000000011", "--seconds", "8", "--trace", "1", "--rehearse",
         "--limits", os.path.join(HERE, "data", "rehearse_limits.json")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    metrics = json.loads(p.stdout.strip().splitlines()[-1])["metrics"]
    assert 0.0 <= metrics["setup_unspanned_s"]["value"] < metrics["cli_to_first_launch_s"]["value"]
    assert not [name for name in metrics if name.startswith("device_")]
