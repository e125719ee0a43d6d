"""The FLOP and byte functions against hand numbers and against XLA's
own count of a one-member, one-step program."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import check  # noqa: E402
import work  # noqa: E402


def cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_smallcnn_member_step_is_38_4_gflop():
    c = cfg("cifar10_cnn")
    # by hand: 25.0 M MACs a row forward, x2 FLOPs, x3 passes, x256 rows
    assert work.forward_macs_per_row(c["layers"]) == 25_003_264
    assert work.member_step_flops(c) == pytest.approx(38.4e9, rel=0.005)
    assert work.n_params(c["layers"]) == 591_658  # the program's own leaf count


def test_resnet18_member_step_is_427_gflop():
    c = cfg("cifar100_resnet18")
    assert work.member_step_flops(c) == pytest.approx(427e9, rel=0.005)
    assert work.n_params(c["layers"]) == pytest.approx(11.2e6, rel=0.01)


@pytest.mark.parametrize("name", ["cifar10_cnn", "cifar100_resnet18"])
def test_layer_table_agrees_with_the_reference(name):
    """The table the FLOP functions read has the parameters the plain
    reference creates (shapes from the same configuration file)."""
    import numpy as np

    c = cfg(name)
    model = check.load_module(os.path.join(BENCH, c["reference"]), "ref_" + name)
    check.load_module(os.path.join(BENCH, "reference", "common.py"), "common")
    n = sum(int(np.prod(shape)) for _, _, _, shape in model.param_table(c))
    assert n == work.n_params(c["layers"])


def test_smallcnn_flops_against_cost_analysis():
    """XLA's count of one member's forward+backward step (float32 on the
    CPU backend) lies within 10% of the layer table's: XLA also counts
    normalisation, pooling, the loss and the update, and does not count
    the stem's unused input gradient."""
    import jax
    import jax.numpy as jnp

    c = cfg("cifar10_cnn")
    common = check.load_module(os.path.join(BENCH, "reference", "common.py"), "common")
    model = check.load_module(os.path.join(BENCH, c["reference"]), "ref_cifar10_cnn")
    params = {
        path: jax.ShapeDtypeStruct(shape, jnp.float32) for path, _, _, shape in model.param_table(c)
    }
    b = c["batch_size"]
    x = jax.ShapeDtypeStruct((b, 32, 32, 3), jnp.float32)
    y = jax.ShapeDtypeStruct((b,), jnp.int32)

    def loss(p, x, y):
        logp = jax.nn.log_softmax(model.apply(p, x, "f32", c))
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    compiled = jax.jit(jax.grad(loss)).lower(params, x, y).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    assert cost["flops"] == pytest.approx(work.member_step_flops(c), rel=0.10)
    assert common is not None


def test_generation_work_and_peaks():
    c = cfg("cifar10_cnn")
    flops, nbytes = work.generation_work(c, 512, 50)
    assert flops == pytest.approx(512 * (50 * 38.4e9 + 2048 * 50.0e6), rel=0.01)
    assert nbytes > 512 * 50 * 16 * 591_658
    p = work.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
