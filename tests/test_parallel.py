"""Mesh layer + fused on-device PBT over a virtual 8-device mesh."""

import jax
import numpy as np
import pytest

from mpi_opt_tpu.ops.pbt import PBTConfig
from mpi_opt_tpu.parallel import make_mesh, pop_sharding, shard_popstate
from mpi_opt_tpu.train.fused_pbt import fused_pbt


def test_make_mesh_shapes():
    m = make_mesh(n_pop=4, n_data=2)
    assert m.shape == {"pop": 4, "data": 2}
    m2 = make_mesh(n_data=2)  # n_pop inferred: 8 devices / 2
    assert m2.shape == {"pop": 4, "data": 2}
    with pytest.raises(ValueError, match="not divisible"):
        make_mesh(n_data=3)
    with pytest.raises(ValueError, match="needs"):
        make_mesh(n_pop=16, n_data=1)


MLP = dict(n_train=512, n_val=256, attrs={"batch_size": 32})


@pytest.fixture(scope="module")
def workload(shared_workload):
    return shared_workload("fashion_mlp", **MLP)


@pytest.fixture(scope="module")
def meshed(shared_workload):
    """``meshed(n_pop, n_data)``: the mesh, and the instance that keeps
    the trainer built for it (one trainer an instance: the unmeshed
    sweeps' stays on ``workload``)."""

    def meshed(n_pop, n_data):
        wl = shared_workload("fashion_mlp", label=f"pop{n_pop} data{n_data} mesh", **MLP)
        return make_mesh(n_pop=n_pop, n_data=n_data), wl

    return meshed


def test_fused_pbt_learns(workload):
    r = fused_pbt(workload, population=8, generations=4, steps_per_gen=30, seed=0)
    assert r["best_curve"].shape == (4,)
    # best-of-population must improve over generations and beat chance
    assert r["best_score"] > 0.25
    assert r["best_curve"][-1] >= r["best_curve"][0] - 0.02
    assert set(r["best_params"]) == {"lr", "momentum", "weight_decay", "flip_prob", "shift"}


def test_fused_pbt_sharded_matches_unsharded(workload, meshed):
    """The same fused sweep over a ('pop','data') mesh must produce the
    same result — sharding is a layout, not a semantics change.

    Tolerance: measured single- vs 4x2-mesh divergence is <0.01 (bf16
    reduction-order noise over 20 training steps); 0.02 leaves margin
    without hiding a real semantics change."""
    r1 = fused_pbt(workload, population=8, generations=2, steps_per_gen=10, seed=3)
    mesh, on_mesh = meshed(4, 2)
    r2 = fused_pbt(on_mesh, population=8, generations=2, steps_per_gen=10, seed=3, mesh=mesh)
    assert r2["best_score"] == pytest.approx(r1["best_score"], abs=0.02)
    np.testing.assert_allclose(r2["mean_curve"], r1["mean_curve"], atol=0.02)


def _count_tensor_allreduces(workload, n_pop, n_data):
    """Compile one train segment over an (n_pop, n_data) mesh and count
    all-reduce ops over non-scalar tensors in the optimized HLO."""
    import re

    import jax.numpy as jnp

    from mpi_opt_tpu.parallel.mesh import replicate

    d = workload.data()
    tx, ty = jnp.asarray(d["train_x"]), jnp.asarray(d["train_y"])
    mesh = make_mesh(n_pop=n_pop, n_data=n_data)
    trainer = workload.make_trainer(mesh=mesh)
    st = shard_popstate(
        trainer.init_population(jax.random.key(0), tx[:2], 8), mesh
    )
    space = workload.default_space()
    hp = workload.make_hparams(space.from_unit(space.sample_unit(jax.random.key(1), 8)))
    txp, typ = jax.device_put(tx, replicate(mesh)), jax.device_put(ty, replicate(mesh))
    lowered = trainer.train_segment.lower(st, hp, txp, typ, jax.random.key(2), 3)
    txt = lowered.compile().as_text()
    return sum(
        1
        for line in txt.splitlines()
        if "all-reduce(" in line and re.search(r"(f32|bf16|i32|u32)\[\d", line)
    )


def test_data_axis_inserts_gradient_allreduce(workload):
    """The 'data' axis must be real: sharding the batch over it makes
    the SPMD partitioner emit a gradient all-reduce (the reference's
    data-parallel MPI allreduce). Pop-only meshes have only the scalar
    loss-mean all-reduce; if the batch constraint is dropped, the
    tensor all-reduce disappears and this test fails."""
    assert _count_tensor_allreduces(workload, n_pop=8, n_data=1) == 0
    assert _count_tensor_allreduces(workload, n_pop=2, n_data=4) > 0


def test_member_chunk_on_a_pop_mesh_cuts_each_devices_own_members(workload):
    """``member_chunk`` under a 'pop' mesh must chunk PER DEVICE.
    Chunking the global member axis scans over the sharded dimension,
    and the partitioner then all-gathers the whole population's state
    onto every device (found compiling config 5's four-chip share for a
    described v5e: 17 GiB a chip, PR 21). The chunked programs must
    hold no all-gather and must not change a single bit of the result."""
    import re

    import jax.numpy as jnp

    from mpi_opt_tpu.parallel.mesh import replicate

    d = workload.data()
    mesh = make_mesh(n_pop=4, n_data=1, devices=jax.devices()[:4])
    rep = replicate(mesh)
    tx, ty, vx, vy = (
        jax.device_put(jnp.asarray(d[k]), rep)
        for k in ("train_x", "train_y", "val_x", "val_y")
    )
    space = workload.default_space()
    hp = workload.make_hparams(space.from_unit(space.sample_unit(jax.random.key(1), 8)))
    out = {}
    for chunk in (0, 1):  # 8 members / 4 devices = 2 a device: two chunks of one
        trainer = workload.make_trainer(member_chunk=chunk, mesh=mesh, donate=False)
        st = shard_popstate(trainer.init_population(jax.random.key(0), tx[:2], 8), mesh)
        key = jax.random.key(2)
        if chunk:
            train = trainer.train_segment.lower(st, hp, tx, ty, key, 3).compile()
            evalp = type(trainer).eval_population.program(trainer).lower(st, vx, vy).compile()
            for txt in (train.as_text(), evalp.as_text()):
                assert not re.search(r"all-gather(-start)?\(", txt)
        st, _ = trainer.train_segment(st, hp, tx, ty, key, 3)
        assert jax.tree.leaves(st.params)[0].sharding == pop_sharding(mesh)
        out[chunk] = (jax.device_get(st.params), np.asarray(trainer.eval_population(st, vx, vy)))
    for a, b in zip(jax.tree.leaves(out[0][0]), jax.tree.leaves(out[1][0])):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(out[0][1], out[1][1])


def test_chunked_segment_on_a_pop_mesh_equals_one_device(workload):
    """The chunk loop outside the step loop, on four devices: chunk j
    of every device at once, cut on an axis no device shares. The
    result is the one-device result to the bit, the state stays sharded
    over 'pop', and the program holds no all-gather."""
    import re

    import jax.numpy as jnp

    from mpi_opt_tpu.parallel.mesh import replicate

    d = workload.data()
    mesh = make_mesh(n_pop=4, n_data=1, devices=jax.devices()[:4])
    tx, ty = jnp.asarray(d["train_x"]), jnp.asarray(d["train_y"])
    space = workload.default_space()
    hp = workload.make_hparams(space.from_unit(space.sample_unit(jax.random.key(1), 16)))
    key = jax.random.key(2)
    rem = jnp.arange(16, dtype=jnp.int32) % 4

    one = workload.make_trainer(member_chunk=2, donate=False)
    st = one.init_population(jax.random.key(0), tx[:2], 16)
    want, _ = one.train_segment(st, hp, tx, ty, key, 3)
    want_masked, _ = one.train_segment_masked(st, hp, tx, ty, key, 3, rem)

    four = workload.make_trainer(member_chunk=2, mesh=mesh, donate=False)  # 4 a device: 2 chunks
    rep = replicate(mesh)
    txm, tym = jax.device_put(tx, rep), jax.device_put(ty, rep)
    stm = shard_popstate(st, mesh)
    text = four.train_segment.lower(stm, hp, txm, tym, key, 3).compile().as_text()
    assert not re.search(r"all-gather(-start)?\(", text)
    got, _ = four.train_segment(stm, hp, txm, tym, key, 3)
    got_masked, _ = four.train_segment_masked(stm, hp, txm, tym, key, 3, rem)
    assert jax.tree.leaves(got.params)[0].sharding == pop_sharding(mesh)
    for a, b in zip(jax.tree.leaves((got, got_masked)), jax.tree.leaves((want, want_masked))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_shard_popstate_places_on_mesh(workload):
    mesh = make_mesh(n_pop=8, n_data=1)
    trainer = workload.make_trainer()
    d = workload.data()
    import jax.numpy as jnp

    st = trainer.init_population(jax.random.key(0), jnp.asarray(d["train_x"][:2]), 8)
    sharded = shard_popstate(st, mesh)
    leaf = jax.tree.leaves(sharded.params)[0]
    assert leaf.sharding == pop_sharding(mesh)
    assert len(leaf.devices()) == 8


class TestInitializeMultihost:
    """initialize_multihost is the config-5 bring-up shim; its contract:
    single-process requests degrade gracefully, explicit multi-host
    requests must never silently shrink to one process. In this test
    process the XLA backend is already up, so every inner
    jax.distributed.initialize raises — which is exactly the failure
    path being pinned down."""

    def test_single_process_swallows_late_init(self):
        from mpi_opt_tpu.parallel.mesh import initialize_multihost

        # no explicit world: failure to bring up distributed is fine,
        # and the current process index comes back
        assert initialize_multihost() == 0
        assert initialize_multihost(num_processes=1) == 0

    def test_explicit_coordinator_failure_raises(self):
        from mpi_opt_tpu.parallel.mesh import initialize_multihost

        with pytest.raises(RuntimeError):
            initialize_multihost(
                coordinator_address="127.0.0.1:1", num_processes=2, process_id=0
            )

    def test_explicit_world_size_failure_raises(self):
        from mpi_opt_tpu.parallel.mesh import initialize_multihost

        # num_processes>1 without a coordinator address is still an
        # explicit multi-process request: must raise, not shrink
        with pytest.raises(RuntimeError):
            initialize_multihost(num_processes=2)


def test_fused_pbt_final_state_sharded(meshed):
    """The fused sweep's carried population must END sharded over 'pop'
    — if any launch-boundary op (exploit gather, snapshot round-trip)
    dropped the placement, multi-chip sweeps would silently degrade to
    replicated execution."""
    mesh, workload = meshed(8, 1)
    r = fused_pbt(workload, population=8, generations=2, steps_per_gen=5, seed=1, mesh=mesh)
    leaves = jax.tree.leaves(r["state"].params)
    assert leaves, "fused_pbt result carries no state"
    for leaf in leaves:
        assert len(leaf.devices()) == 8, leaf.sharding
        assert not leaf.sharding.is_fully_replicated


def test_fused_tpe_sharded_matches_unsharded(workload, meshed):
    """Fused TPE over a mesh (incl. a tail generation that does not
    divide the 'pop' axis) must match the single-device trajectory."""
    from mpi_opt_tpu.train.fused_tpe import fused_tpe

    kw = dict(n_trials=12, batch=8, budget=5, seed=4)
    r1 = fused_tpe(workload, **kw)
    mesh, on_mesh = meshed(8, 1)
    r2 = fused_tpe(on_mesh, mesh=mesh, **kw)
    assert r2["best_score"] == pytest.approx(r1["best_score"], abs=0.02)
    np.testing.assert_allclose(r2["best_curve"], r1["best_curve"], atol=0.02)


def test_fused_sha_sharded_rounds_survivors_to_pop_axis(meshed):
    """On a mesh, rung survivor counts round UP to the 'pop' axis so
    cohorts stay shardable; a 16-trial eta-4 sweep on an 8-way mesh
    keeps 8 (not 4) survivors."""
    from mpi_opt_tpu.train.fused_asha import fused_sha

    mesh, workload = meshed(8, 1)
    r = fused_sha(
        workload, n_trials=16, min_budget=5, max_budget=20, eta=4, seed=2, mesh=mesh
    )
    assert r["rung_sizes"] == [16, 8]
    assert 0.0 <= r["best_score"] <= 1.0


def test_replication_fallback_warns(workload):
    """A leading axis that doesn't divide the 'pop' axis replicates —
    correct but effectively single-device, so it must WARN instead of
    silently serializing the sweep (VERDICT r3 #7)."""
    import warnings as _w

    import jax.numpy as jnp

    from mpi_opt_tpu.parallel.mesh import place_pop

    mesh = make_mesh(n_pop=8, n_data=1)
    state = {"w": jnp.zeros((10, 3)), "b": jnp.zeros((10,))}
    with pytest.warns(RuntimeWarning, match="does not divide the mesh 'pop' axis"):
        shard_popstate(state, mesh)
    with pytest.warns(RuntimeWarning, match="multiple of 8"):
        place_pop(jnp.zeros((9, 2)), mesh)
    # dividing axes stay silent
    with _w.catch_warnings():
        _w.simplefilter("error")
        shard_popstate({"w": jnp.zeros((16, 3))}, mesh)
        place_pop(jnp.zeros((8, 2)), mesh)
