import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_opt_tpu import Choice, IntUniform, LogUniform, SearchSpace, Uniform


@pytest.fixture
def space():
    return SearchSpace(
        {
            "lr": LogUniform(1e-4, 1e-1),
            "momentum": Uniform(0.5, 0.99),
            "layers": IntUniform(1, 4),
            "act": Choice(["relu", "tanh", "gelu"]),
        }
    )


def test_sample_shapes_and_ranges(space):
    key = jax.random.key(0)
    u = space.sample_unit(key, 100)
    assert u.shape == (100, 4)
    vals = space.from_unit(u)
    assert vals["lr"].shape == (100,)
    assert jnp.all(vals["lr"] >= 1e-4) and jnp.all(vals["lr"] <= 1e-1)
    assert jnp.all(vals["momentum"] >= 0.5) and jnp.all(vals["momentum"] <= 0.99)
    assert jnp.all(vals["layers"] >= 1) and jnp.all(vals["layers"] <= 4)
    assert jnp.all(vals["act"] >= 0) and jnp.all(vals["act"] <= 2)


def test_unit_roundtrip_continuous(space):
    key = jax.random.key(1)
    u = space.sample_unit(key, 50)
    vals = space.from_unit(u)
    u2 = space.to_unit(vals)
    # continuous dims roundtrip exactly (within float tolerance)
    np.testing.assert_allclose(u[:, 0], u2[:, 0], atol=1e-5)
    np.testing.assert_allclose(u[:, 1], u2[:, 1], atol=1e-5)
    # discrete dims roundtrip to the same bucket
    vals2 = space.from_unit(u2)
    np.testing.assert_array_equal(np.asarray(vals["layers"]), np.asarray(vals2["layers"]))
    np.testing.assert_array_equal(np.asarray(vals["act"]), np.asarray(vals2["act"]))


def test_loguniform_is_log_spaced(space):
    key = jax.random.key(2)
    vals = space.sample(key, 4000)
    lr = np.asarray(vals["lr"])
    # median of a log-uniform over [1e-4, 1e-1] is 10^-2.5
    assert 10**-2.8 < np.median(lr) < 10**-2.2


def test_materialize_row(space):
    row = np.array([0.5, 0.5, 0.5, 0.9])
    h = space.materialize_row(row)
    assert isinstance(h["lr"], float)
    assert isinstance(h["layers"], int)
    assert h["act"] == "gelu"


def _decode_per_element(space, row):
    """The one-scalar-a-dimension decode ``materialize_row`` did before
    rows were decoded a column at a time (what ``params_key`` keyed)."""
    from mpi_opt_tpu.utils.hostdev import host_ops

    with host_ops():
        return {
            name: dom.materialize(np.asarray(dom.from_unit(jnp.asarray(row[i]))))
            for i, (name, dom) in enumerate(space.domains.items())
        }


@pytest.mark.parametrize("at", ["zero", "one", "random"])
def test_materialize_rows_equals_row_by_row(at):
    space = SearchSpace(
        {
            "lr": LogUniform(1e-5, 1e-1),
            "momentum": Uniform(0.5, 0.99),
            "layers": IntUniform(1, 7),
            "act": Choice(["relu", "tanh", "gelu"]),
            "bias": Choice([True, False, None]),
            "wd": LogUniform(1e-6, 1e-2),
        }
    )
    n = 257
    if at == "random":
        units = np.array(space.sample_unit(jax.random.key(11), n))
    else:
        units = np.full((n, space.dim), 0.0 if at == "zero" else 1.0, np.float32)
    rows = space.materialize_rows(units)
    assert len(rows) == n
    for u, got in zip(units, rows):
        for want in (_decode_per_element(space, u), space.materialize_row(u)):
            assert list(got) == list(want)
            # value AND Python type: a ledger's params bytes may not drift
            assert [(type(v), v) for v in got.values()] == [
                (type(v), v) for v in want.values()
            ]


def test_discrete_mask(space):
    np.testing.assert_array_equal(space.discrete_mask(), [False, False, True, True])


def test_from_unit_is_jittable(space):
    f = jax.jit(space.from_unit)
    out = f(space.sample_unit(jax.random.key(3), 8))
    assert out["lr"].shape == (8,)
