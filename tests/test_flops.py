"""FLOPs/MFU accounting (utils/flops.py)."""

import jax
import jax.numpy as jnp
import pytest

from mpi_opt_tpu.utils.flops import (
    compiled_flops,
    mfu,
    peak_flops_per_chip,
    population_sweep_flops,
)


def test_compiled_flops_matmul_exact():
    a = jnp.zeros((256, 256), jnp.float32)
    f = compiled_flops(jax.jit(lambda a, b: a @ b), a, a)
    if f is None:
        pytest.skip("cost analysis unavailable on this backend")
    assert f == pytest.approx(2 * 256**3, rel=0.01)


def test_population_sweep_flops_linear_scaling(shared_workload):

    wl = shared_workload("fashion_mlp", n_train=256, n_val=128)
    f1 = population_sweep_flops(wl, population=4, generations=2, steps_per_gen=3, n_evals=3)
    if f1 is None:
        pytest.skip("cost analysis unavailable on this backend")
    f2 = population_sweep_flops(wl, population=8, generations=2, steps_per_gen=3, n_evals=3)
    assert f1 > 0
    # flops are exactly linear in population (same evals per member)
    assert f2 == pytest.approx(2 * f1, rel=1e-6)
    # more steps -> strictly more flops, sublinear total (evals fixed)
    f3 = population_sweep_flops(wl, population=4, generations=2, steps_per_gen=6, n_evals=3)
    assert f1 < f3 < 2 * f1


def test_peak_and_mfu_off_tpu_is_none():
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        assert peak_flops_per_chip(dev) is not None
        assert 0 < mfu(1e12, 1.0, dev) < 1
    else:
        assert peak_flops_per_chip(dev) is None
        assert mfu(1e12, 1.0, dev) is None


class _FakeTpu:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


@pytest.mark.parametrize(
    "kind,peak",
    [("TPU v5 lite", 197e12), ("TPU v5e", 197e12), ("TPU v5p", 459e12), ("TPU v4", 275e12)],
)
def test_peak_table_is_the_published_bf16_figure(kind, peak):
    # v5e: 197 TF/s in bf16 — 394 is its int8 rating
    assert peak_flops_per_chip(_FakeTpu(kind)) == peak


@pytest.mark.parametrize("kind", ["TPU v5", "TPU v9 hyper"])
def test_unknown_tpu_kind_is_an_error_not_a_guess(kind):
    with pytest.raises(ValueError, match="no published bf16 peak"):
        peak_flops_per_chip(_FakeTpu(kind))
