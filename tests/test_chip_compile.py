"""Compiles for the chip, without the chip (ISSUE 21).

The TPU's compiler is installed here and compiles for a v5e that is
described, not attached. These tests hand it the main path's programs
at real widths — what interpret mode and XLA:CPU cannot show is whether
the chip's compiler takes them (tiling alignment, VMEM, device memory).
Nothing runs: a compile that passes is not a chip run.

This is the ONLY file that describes a topology, and it does so inside
a fixture: describing it loads libtpu, which one process at a time may
hold, so it must not happen while any module is imported (xdist workers
import every test file). Keep such tests in this one file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def on_chip(topo):
    """``on_chip(shape, dtype)``: an abstract array placed on one
    described chip (there is no device to hold a real one)."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    return make


@pytest.fixture(scope="module")
def key_on_chip(on_chip):
    k = jax.eval_shape(lambda: jax.random.key(0))
    return on_chip(k.shape, k.dtype)


D = 5  # the vision workloads' search-space width


def test_pbt_exploit_compiles_at_pop_256(on_chip, key_on_chip):
    from mpi_opt_tpu.ops.pbt import PBTConfig
    from mpi_opt_tpu.train.fused_pbt import _wave_exploit

    compiled = _wave_exploit.lower(
        key_on_chip, on_chip((256, D)), on_chip((256,)),
        discrete_mask=(False,) * D, cfg=PBTConfig(),
    ).compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0


def test_sha_rung_cut_compiles_at_64_trials(on_chip):
    from mpi_opt_tpu.train.fused_asha import _wave_cut

    compiled = _wave_cut.lower(on_chip((64, D)), on_chip((64,)), eta=3, k=22).compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0


def test_tpe_suggest_compiles_at_a_512_row_ring(on_chip, key_on_chip):
    from mpi_opt_tpu.ops.tpe import TPEConfig
    from mpi_opt_tpu.train.fused_tpe import _tpe_suggest_program

    compiled = _tpe_suggest_program.lower(
        on_chip((512, D)), on_chip((512,)), on_chip((512,), jnp.bool_), key_on_chip,
        n_suggest=64, cfg=TPEConfig(),
    ).compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0


# ResNet-18's first stage at batch 128 (32x32, 64 channels, 32 groups),
# 8 members under the population vmap: the largest block the kernel
# tiles (the other three stages compile too — CHANGES.md PR 21)
_GN_X = (8, 128, 32, 32, 64)


def _gn_population(x, scale, bias):
    from mpi_opt_tpu.ops.pallas_gn import group_norm_relu

    return jax.vmap(lambda a, s, b: group_norm_relu(a, s, b, 32, 1e-6, True))(
        x, scale, bias
    )


def test_pallas_gn_forward_compiles_to_a_kernel(on_chip):
    compiled = jax.jit(_gn_population).lower(
        on_chip(_GN_X, jnp.bfloat16), on_chip((8, 64)), on_chip((8, 64))
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel, not a fallback


def test_pallas_gn_backward_compiles_to_kernels(on_chip):
    def loss(x, scale, bias):
        return jnp.sum(_gn_population(x, scale, bias).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        on_chip(_GN_X, jnp.bfloat16), on_chip((8, 64)), on_chip((8, 64))
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2  # fwd + bwd kernels


# `temp_size_in_bytes` of the program below at the parent of PR 26
# (commit 9c6ab68: the chunk loop inside the step loop, and with it a
# stacked second copy of the population's state)
_PARENT_TEMP_BYTES = 5013452288


def test_smallcnn_member_chunk_train_step_compiles_and_fits(on_chip, key_on_chip):
    """A few steps of the headline trainer at SmallCNN's full 32/64
    channels: 128 members in four chunks of 32, batch 256. The TPU
    compiler's program cuts and stitches the population's state in the
    chunk loop alone — the step loop's body holds no slice, update or
    copy of a whole-population leaf — and needs less than the parent's
    temporaries."""
    import hlo_loops

    from mpi_opt_tpu.train.population import OptHParams
    from mpi_opt_tpu.workloads import get_workload

    wl = get_workload("cifar10_cnn")
    wl._data = {"n_classes": 10}  # the model needs only this; no data is made
    trainer = wl.make_trainer(member_chunk=32)
    pop, steps = 128, 3
    sample = jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    place = lambda tree: jax.tree.map(lambda s: on_chip(s.shape, s.dtype), tree)
    state = place(
        jax.eval_shape(lambda k, x: trainer.init_population(k, x, pop), key, sample)
    )
    hp = place(jax.eval_shape(lambda: OptHParams.defaults(pop)))
    compiled = trainer.train_segment.lower(
        state, hp, on_chip((wl.n_train, 32, 32, 3)), on_chip((wl.n_train,), jnp.int32),
        key_on_chip, steps=steps,
    ).compile()
    loops = hlo_loops.loops(compiled.as_text())  # a loop's body includes the loops nested in it
    assert steps in [l.trips for l in loops]
    assert [l.trips for l in loops if hlo_loops.state_cuts(l, state, copies_of=pop)] == [4]
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < _PARENT_TEMP_BYTES
    live = (
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        + ma.temp_size_in_bytes - ma.alias_size_in_bytes
    )
    assert live < 16 * 2**30  # one v5e chip's HBM


def test_selected_attention_compiles_to_kernels_at_the_decoders_widths(on_chip):
    """The last query tile of an 8192-token row at the token member's
    published widths (32 query heads over 4 key/value heads of 128, 512
    queries against all 8192 keys, one run-time mask for all heads):
    the forward, dq and dkv kernels of ops/selected_attention.py."""
    from mpi_opt_tpu.ops.selected_attention import masked_attention

    heads, kv_heads, rows, keys, d = 32, 4, 512, 8192, 128

    def loss(q, k, v, mask):
        out, lse = masked_attention(q, k, v, mask, 512)
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(jax.lax.stop_gradient(lse))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        on_chip((heads, rows, d), jnp.bfloat16), on_chip((kv_heads, keys, d), jnp.bfloat16),
        on_chip((kv_heads, keys, d), jnp.bfloat16), on_chip((rows, keys), jnp.bool_),
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 3
