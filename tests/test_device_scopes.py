"""Device scopes (obs/events.py DEVICE_SCOPES): the names the program
gives the phases of a fused generation, read back from what a profiler
would see — the compiled program's ``op_name`` paths — and held equal
to the copy the benchmark's reduction keeps (benchmarks/scopes.py).
Strings only: no timing happens here.
"""

from __future__ import annotations

import ast
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_opt_tpu.analysis.core import iter_python_files
from mpi_opt_tpu.obs.events import DEVICE_SCOPES

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "benchmarks"))

import scopes  # noqa: E402  (benchmarks/scopes.py)


@pytest.fixture(scope="module")
def tiny_cnn(shared_workload):
    return shared_workload("cifar10_cnn", n_train=128, n_val=64, attrs={"batch_size": 16})


def _tiny_decoder():
    """The token member at its configuration's rehearse sizes."""
    import json

    from mpi_opt_tpu.workloads import get_workload

    with open(os.path.join(REPO_ROOT, "benchmarks", "configs", "keye_vl2_30b_a3b.json")) as f:
        attrs = json.load(f)["rehearse"]["workload_attrs"]
    wl = get_workload("keye_vl2_30b_a3b")
    for name, value in attrs.items():
        setattr(wl, name, value)
    return wl


def _fused_pbt_op_names(wl, member_chunk):
    """Every ``op_name`` of the compiled fused PBT program at a tiny
    size (4 members, one generation of 2 steps)."""
    from mpi_opt_tpu.ops.pbt import PBTConfig
    from mpi_opt_tpu.train.common import HParamsFn, workload_arrays
    from mpi_opt_tpu.train.fused_pbt import run_fused_pbt

    trainer, space, tx, ty, vx, vy = workload_arrays(wl, member_chunk=member_chunk)
    state = trainer.init_population(jax.random.key(1), tx[:2], 4)
    compiled = run_fused_pbt.program(trainer).lower(
        state, space.sample_unit(jax.random.key(0), 4), HParamsFn(space, wl),
        train_x=tx, train_y=ty, val_x=vx, val_y=vy, key=jax.random.key(2),
        discrete_mask=tuple(bool(b) for b in space.discrete_mask()),
        generations=1, steps_per_gen=2, cfg=PBTConfig(),
    ).compile()
    return sorted(set(re.findall(r'op_name="([^"]+)"', compiled.as_text())))


@pytest.fixture(scope="module")
def op_names(tiny_cnn):
    """SmallCNN, 4 members in chunks of 2."""
    return _fused_pbt_op_names(tiny_cnn, 2)


@pytest.fixture(scope="module")
def decoder_op_names():
    """The token member, one member at a time."""
    return _fused_pbt_op_names(_tiny_decoder(), 1)


#: the scopes a member of its own opens inside ``member_loss`` and
#: ``eval_population`` (models/sparse_moe_decoder.py,
#: models/latent_moe_decoder.py): they book no phase
LATENT_SCOPES = ("latent_attention", "shared_experts", "dense_mlp")
MEMBER_SCOPES = ("attention", "indexer", "router", "experts", "loss_head") + LATENT_SCOPES


def test_benchmark_keeps_the_same_scope_names():
    """The contract PERF.md section 7 states: every scope the
    benchmark's reduction books a phase by is a scope of the program;
    the program's other scopes are the members' own."""
    assert set(scopes.SCOPES) <= set(DEVICE_SCOPES)
    assert set(scopes.PHASE_OF_SCOPE) | {"map_members"} == set(scopes.SCOPES)
    assert set(DEVICE_SCOPES) - set(scopes.SCOPES) == set(MEMBER_SCOPES)
    # the benchmark's own half (benchmarks/tests/test_scopes.py), kept in tier-1 too
    assert set(scopes.PHASE_OF_SCOPE.values()) | {"backward", "unscoped"} == set(scopes.PHASES)


def test_a_members_own_scopes_are_opened_inside_a_phase_scope_only(decoder_op_names):
    """Every operation under one of the member's scopes is also under
    ``member_loss`` or ``eval_population``, further out on its path: the
    innermost LISTED scope books the phase, so the phases stay an exact
    partition of the busy time."""
    inside = 0
    for name in decoder_op_names:
        if not name.startswith("jit("):
            continue  # a reducer's own computation (its add, its max): no device operation
        cores = [scopes._core(c) for c in name.split("/")]
        for i, core in enumerate(cores):
            if core in MEMBER_SCOPES:
                assert {"member_loss", "eval_population"} & set(cores[:i]), name
                assert scopes.phase_of(name) in ("forward", "backward", "eval"), name
                inside += 1
    assert inside > 100


@pytest.mark.parametrize(
    "program,scope",
    [("cnn", s) for s in scopes.SCOPES]
    + [("decoder", s) for s in DEVICE_SCOPES if s != "augment" and s not in LATENT_SCOPES],
)
def test_compiled_program_carries_every_scope(op_names, decoder_op_names, program, scope):
    names = op_names if program == "cnn" else decoder_op_names
    cores = {scopes._core(c) for name in names for c in name.split("/")}
    assert scope in cores, f"no op_name of the {program}'s fused PBT program carries {scope!r}"


def test_the_cnn_program_carries_no_scope_of_another_member(op_names):
    cores = {scopes._core(c) for name in op_names for c in name.split("/")}
    assert not cores & set(MEMBER_SCOPES)


def test_backward_convolution_is_named_by_jax(op_names):
    convs = [n for n in op_names if n.endswith("conv_general_dilated")]
    fwd = [n for n in convs if "/vmap(jvp(member_loss))/" in n]
    bwd = [n for n in convs if "transpose(jvp(member_loss))" in n]
    assert fwd and bwd
    assert {scopes.phase_of(n) for n in fwd} == {"forward"}
    assert {scopes.phase_of(n) for n in bwd} == {"backward"}
    evals = [n for n in convs if "eval_population" in n]
    assert evals and {scopes.phase_of(n) for n in evals} == {"eval"}
    assert {scopes.class_of(n, "") for n in convs} == {"conv"}


def test_every_phase_of_the_program_is_reached(op_names):
    phases = {scopes.phase_of(n) for n in op_names}
    assert phases >= {"forward", "backward", "optimizer", "input", "train_rest", "eval", "exploit"}


def test_named_scope_sites_use_registered_names_only():
    """Every ``jax.named_scope("...")`` under mpi_opt_tpu/ names a
    registered scope, and every registered scope has a site."""
    seen = set()
    for path in iter_python_files(os.path.join(REPO_ROOT, "mpi_opt_tpu")):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "named_scope"
            ):
                arg = node.args[0]
                assert isinstance(arg, ast.Constant), f"{path}:{node.lineno}: scope name is not a literal"
                assert arg.value in DEVICE_SCOPES, f"{path}:{node.lineno}: {arg.value!r} is not in DEVICE_SCOPES"
                seen.add(arg.value)
    assert seen == set(DEVICE_SCOPES)


# -- every model's convolutions and GroupNorms fall in their class --------


def _models():
    from mpi_opt_tpu.models import MLP, ResNet18, SmallCNN

    image = jnp.zeros((2, 8, 8, 3), jnp.float32)
    return {
        "MLP": (MLP(hidden=8, n_classes=4), jnp.zeros((2, 12), jnp.float32)),
        "SmallCNN": (SmallCNN(n_classes=4, width=8), image),
        "ResNet18": (ResNet18(n_classes=4, width=8), image),
        "ResNet18-pallas_gn": (ResNet18(n_classes=4, width=8, pallas_gn=True), image),
    }


def _equation_paths(jaxpr, out):
    """(path, primitive name) of every equation, sub-programs included,
    as a device trace would name it: name stack + primitive."""
    for eqn in jaxpr.eqns:
        out.append((f"{eqn.source_info.name_stack}/{eqn.primitive.name}", eqn.primitive.name))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _equation_paths(sub, out)
    return out


@pytest.mark.parametrize("name", ["MLP", "SmallCNN", "ResNet18", "ResNet18-pallas_gn"])
def test_model_primitives_fall_in_their_class(name):
    model, x = _models()[name]
    params = jax.eval_shape(lambda: model.init(jax.random.key(0), x))["params"]

    def loss(p):
        with jax.named_scope("member_loss"):
            return jnp.sum(model.apply({"params": p}, x))

    paths = _equation_paths(jax.make_jaxpr(jax.grad(loss))(params).jaxpr, [])
    # GroupNorm modules are the ones with a `scale`: their names are the
    # independent account of what the class has to catch
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    gn_modules = {kp[-2].key for kp, _ in flat if kp[-1].key == "scale"}
    convs = [p for p, prim in paths if prim == "conv_general_dilated"]
    gns = [p for p, _ in paths if any(scopes._core(c) in gn_modules for c in p.split("/"))]
    if name == "MLP":
        assert not convs and not gn_modules
        assert "matmul" in {scopes.class_of(p, "") for p, _ in paths}
        return
    assert convs and gns and gn_modules
    assert {scopes.class_of(p, "") for p in convs} == {"conv"}
    assert {scopes.class_of(p, "") for p in gns} == {"groupnorm"}
    # and both directions of both are there
    for group in (convs, gns):
        assert {scopes.phase_of(p) for p in group} == {"forward", "backward"}


# -- scopes change no arithmetic: a golden taken on the parent commit ------

# fused_pbt(cifar10_cnn n_train=128 n_val=16 batch 8, population=4,
# generations=3, steps_per_gen=2, seed=5, gen_chunk=1, member_chunk=2)
# at commit f27c065 (PR 24), before any scope existed. Taken again at
# PR 30 at a third of the rows (the golden of PR 25 evaluated 64 rows at
# batch 16 and ran 25 s, 18 of them XLA:CPU's convolutions; every number
# below is exact at any size), from a checkout of that commit:
#   git archive f27c065 | tar -x -C /root/scratch/golden && cd /root/scratch/golden && JAX_PLATFORMS=cpu python - <<'EOF'
#   import jax, numpy as np; jax.config.update("jax_num_cpu_devices", 8)
#   import mpi_opt_tpu.train.fused_pbt as fp; from mpi_opt_tpu.workloads import get_workload
#   wl = get_workload("cifar10_cnn", n_train=128, n_val=16); wl.batch_size = 8
#   res = fp.fused_pbt(wl, population=4, generations=3, steps_per_gen=2, seed=5, gen_chunk=1, member_chunk=2)
#   print(res["best_curve"], res["mean_curve"], res["best_score"], np.asarray(res["unit"]).tolist(),
#         sum(float(np.sum(np.square(np.asarray(l, np.float64)))) for l in jax.tree.leaves(res["state"].params)))
#   EOF
# and this tree gives the same bytes for the same script.
GOLDEN = {
    "best_curve": [0.0625, 0.25, 0.3125],
    "mean_curve": [0.0625, 0.140625, 0.140625],
    "best_score": 0.3125,
    "unit": [
        [0.08381330966949463, 0.22921323776245117, 0.11234712600708008, 0.2685335874557495, 0.12162470817565918],
        [0.4554823637008667, 0.5360288619995117, 0.47860443592071533, 0.1630462408065796, 0.07066178321838379],
        [0.015344500541687012, 0.5327630043029785, 0.1616910696029663, 0.7874373197555542, 0.07561564445495605],
        [0.1320643573999405, 0.6594825387001038, 0.3564930856227875, 0.8730793595314026, 0.19269245862960815],
    ],
    "param_sq_norm": 2108.3435368090763,
}


def test_resident_sweep_matches_the_parents_golden(shared_workload):
    import mpi_opt_tpu.train.fused_pbt as fp

    wl = shared_workload("cifar10_cnn", n_train=128, n_val=16, attrs={"batch_size": 8})
    res = fp.fused_pbt(
        wl, population=4, generations=3, steps_per_gen=2, seed=5,
        gen_chunk=1, member_chunk=2,
    )
    np.testing.assert_array_equal(res["best_curve"], np.float32(GOLDEN["best_curve"]))
    np.testing.assert_array_equal(res["mean_curve"], np.float32(GOLDEN["mean_curve"]))
    assert res["best_score"] == GOLDEN["best_score"]
    np.testing.assert_allclose(res["unit"], GOLDEN["unit"], rtol=0, atol=1e-7)
    sq = sum(
        float(np.sum(np.square(np.asarray(leaf, np.float64))))
        for leaf in jax.tree.leaves(res["state"].params)
    )
    assert sq == pytest.approx(GOLDEN["param_sq_norm"], rel=1e-6)


# -- the observer at the boundary -------------------------------------------


def test_boundary_observer_is_handed_the_state(shared_workload):
    """``launch_boundary(..., state=)`` hands the population state to an
    installed observer before the slice hook; resident and wave sweeps
    both pass it; nothing is called with none installed."""
    import mpi_opt_tpu.train.fused_pbt as fp
    from mpi_opt_tpu.health import shutdown
    from mpi_opt_tpu.train.common import launch_boundary

    order = []
    shutdown.set_boundary_observer(lambda stage, state: order.append(("observer", stage, state)))
    shutdown.set_slice_hook(lambda stage: order.append(("hook", stage)))
    try:
        launch_boundary("a", final=False, state="S")
        launch_boundary("b", final=True)  # the final boundary: observed, not sliced
    finally:
        shutdown.clear_slice_hook()
    assert order == [("observer", "a", "S"), ("hook", "a"), ("observer", "b", None)]

    seen = []

    def keep(stage, state):
        if state is None:  # a between-waves boundary has no whole state to show
            seen.append((stage, None, None))
            return
        leaf = jax.tree.leaves(state.params)[0]
        seen.append((stage, int(leaf.shape[0]), np.asarray(state.step).tolist()))

    shutdown.set_boundary_observer(keep)
    try:
        wl = shared_workload("fashion_mlp", n_train=128, n_val=64)
        kw = dict(population=4, generations=2, steps_per_gen=3, seed=1, gen_chunk=1)
        fp.fused_pbt(wl, **kw)
        fp.fused_pbt(wl, wave_size=2, **kw)
    finally:
        shutdown.set_boundary_observer(None)
    resident, waves = seen[:2], seen[2:]
    assert [s[0] for s in resident] == ["pbt launch 1/2", "pbt launch 2/2"]
    assert [s[1:] for s in resident] == [(4, [3] * 4), (4, [6] * 4)]
    # the wave path's generation boundaries show the whole host-staged population
    assert [s[1:] for s in waves if s[1] == 4] == [(4, [3] * 4), (4, [6] * 4)]
    shutdown.set_boundary_observer(None)
    launch_boundary("c", final=True, state="S")  # no observer: nothing happens
    assert len(seen) == len(resident) + len(waves)


# -- a profiled run keys the persistent cache by the names ------------------


def test_profiled_runs_key_the_cache_by_names():
    """jax strips debug info from the persistent cache's key, so a
    program that differs from a cached one only in its names comes back
    with the OLD names (measured on the chip, PR 25). Under
    ``keyed_by_names`` the key includes the locations, and a location
    is the name stack alone: it moves with a scope's name and not with
    the line the code stands on."""
    from mpi_opt_tpu.utils.compile_cache import keyed_by_names

    def program(scope):
        def f(x):
            with jax.named_scope(scope):
                return jnp.tanh(x) * 2
        return f

    def elsewhere(scope):  # the same program from other lines of this file
        def f(x):
            with jax.named_scope(scope):
                return jnp.tanh(x) * 2
        return f

    def keyed_text(fn):
        return jax.jit(fn).lower(1.0).as_text(debug_info=True)

    before = (
        jax.config.jax_compilation_cache_include_metadata_in_key,
        jax.config.jax_traceback_in_locations_limit,
    )
    with keyed_by_names(False):
        assert jax.config.jax_compilation_cache_include_metadata_in_key == before[0]
    assert "test_device_scopes.py" in keyed_text(program("a"))  # the default: file and line
    with keyed_by_names():
        assert jax.config.jax_compilation_cache_include_metadata_in_key is True
        a, a2, b = keyed_text(program("a")), keyed_text(elsewhere("a")), keyed_text(program("b"))
        assert "test_device_scopes.py" not in a and "/a/tanh" in a
        assert a == a2 and a != b
    after = (
        jax.config.jax_compilation_cache_include_metadata_in_key,
        jax.config.jax_traceback_in_locations_limit,
    )
    assert after == before
