"""Sharded-program proof for the conv/ResNet path (VERDICT r2 item 2).

The config-5 ResNet cannot be *executed* on a virtual CPU mesh at real
shapes (measured: >550 s XLA:CPU compile for a fused generation at
32x32), but the partitioned program can be *compiled* cheaply at 8x8
spatial with a width-8 model — and the compiled HLO is the ground truth
for both properties the multi-chip design rests on:

- the gradient all-reduce over the 'data' axis exists (the reference's
  data-parallel MPI allreduce, inserted by the SPMD partitioner from
  the batch sharding constraint alone), and
- parameter/optimizer tensors are partitioned over the 'pop' axis (the
  population actually shards, rather than silently replicating).

Abstract lowering (ShapeDtypeStructs carrying shardings) avoids paying
the width-8 init_population execution (~70 s on this box); only the
train_segment compile (~30 s, persistent-cached) is spent.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from mpi_opt_tpu.models import ResNet18
from mpi_opt_tpu.parallel.mesh import make_mesh, pop_sharding, replicate
from mpi_opt_tpu.train.population import OptHParams, PopulationTrainer

# In tier-1 since PR 21: these lower and partition a depth-cut ResNet
# without running it, 3-9 s each on the installed XLA:CPU.

POP = 8


def _resnet_trainer(mesh):
    model = ResNet18(n_classes=10, width=8, remat=True)
    return PopulationTrainer(
        apply_fn=lambda p, x: model.apply({"params": p}, x),
        init_fn=lambda r, x: model.init(r, x)["params"],
        batch_size=16,
        augment=True,
        mesh=mesh,
    )


def _lower_train_segment(mesh, steps=2):
    trainer = _resnet_trainer(mesh)
    tx = jax.ShapeDtypeStruct((64, 8, 8, 3), jnp.float32, sharding=replicate(mesh))
    ty = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=replicate(mesh))
    sample = jax.ShapeDtypeStruct((2, 8, 8, 3), jnp.float32)
    state_abs = jax.eval_shape(
        lambda k, x: trainer.init_population(k, x, POP), jax.random.key(0), sample
    )
    psh = pop_sharding(mesh)
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=psh), state_abs
    )
    hp = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=replicate(mesh)),
        jax.eval_shape(lambda: OptHParams.defaults(POP)),
    )
    key = jax.eval_shape(lambda: jax.random.key(0))
    traced = trainer.train_segment.trace(state, hp, tx, ty, key, steps)
    if isinstance(mesh, jax.sharding.AbstractMesh):
        # no concrete devices exist for an abstract mesh; lower for the
        # TARGET platform explicitly (which is also the honest one for
        # the v4-32 scaling claim)
        return traced.lower(lowering_platforms=("tpu",))
    return traced.lower()


def _tensor_allreduces(txt):
    return [
        l
        for l in txt.splitlines()
        if "all-reduce(" in l and re.search(r"(f32|bf16)\[\d", l)
    ]


def test_resnet_sharded_program_has_data_psum_and_pop_partitioning():
    """Compile (not just lower) the width-8 ResNet train segment over a
    (pop=2, data=4) mesh and assert both structural properties in the
    optimized HLO. Fails if the batch constraint (data psum) or the
    population sharding propagation disappears."""
    mesh = make_mesh(n_pop=2, n_data=4)
    txt = _lower_train_segment(mesh).compile().as_text()
    # 1. data-parallel gradient all-reduce over non-scalar tensors
    assert len(_tensor_allreduces(txt)) >= 1
    # 2. population tensors partitioned over 'pop': some instruction is
    # sharded 2-way on its leading (member) dim with the 4 data devices
    # in the replicated trailing tile
    assert re.search(
        r"sharding=\{devices=\[2[,0-9]*,4\]<=\[8\] last_tile_dim_replicate\}", txt
    ), "no pop-axis (2-way leading dim) partitioning found in compiled HLO"


def test_resnet_pop_only_mesh_has_no_tensor_allreduce():
    """Negative control on the SAME model (mirrors the MLP test at
    tests/test_parallel.py): a pop-only layout needs no tensor
    collective at all — members are independent. Lowering suffices for
    this check (the constraint that would create the psum is absent
    from the stablehlo itself)."""
    mesh = make_mesh(n_pop=8, n_data=1)
    txt = _lower_train_segment(mesh).as_text()
    assert "all_reduce" not in txt or not _tensor_allreduces(txt)


def test_resnet_lowers_at_v4_32_topology():
    """BASELINE config 5's target hardware is a v4-32 (32 chips). More
    devices than this container can even virtualize (conftest pins 8) is
    exactly what AbstractMesh exists for: lower the ResNet train segment
    over an abstract (pop=8, data=4) 32-device mesh and assert the
    program still carries the pop partitioning and stays on the conv
    path. Lowering-only — compilation needs concrete devices — but the
    sharding annotations in the StableHLO are what the SPMD partitioner
    consumes, so their presence at this topology is the scaling claim."""
    mesh = jax.sharding.AbstractMesh((8, 4), ("pop", "data"))
    txt = _lower_train_segment(mesh).as_text()
    assert "stablehlo.convolution" in txt
    # the mesh itself is declared at the 32-device topology
    assert re.search(r'sdy\.mesh @mesh = <\["pop"=8, "data"=4\]>', txt), (
        "no 8x4 mesh declaration in the lowered program"
    )
    # population tensors enter annotated over 'pop' (shardy dialect)
    assert re.search(r'sdy\.sharding<@mesh, \[\{"pop"\}', txt), (
        "no pop-axis sharding annotation at the 32-device topology"
    )
    # and the in-program batch constraint over 'data' survives at scale
    assert re.search(r'sdy\.sharding_constraint .*\[\{"data"\}', txt), (
        "no data-axis batch constraint at the 32-device topology"
    )


def test_resnet_sharded_hlo_keeps_conv_ops():
    """The partitioned program still lowers convs as convs (MXU path on
    real hardware) — a silent fallback to e.g. gather/matmul expansion
    would tank the config-5 perf model."""
    mesh = make_mesh(n_pop=2, n_data=4)
    txt = _lower_train_segment(mesh).as_text()
    assert "stablehlo.convolution" in txt
