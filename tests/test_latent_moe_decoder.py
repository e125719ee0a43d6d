"""The decoder with latent attention and shared experts
(models/latent_moe_decoder.py, the `kimi_vl_a3b` workload) against the
benchmark's plain reference (benchmarks/reference/kimi_vl_a3b.py), at
the configuration's `rehearse` sizes on the CPU: initial weights, loss
and every leaf's gradient, the score, the expert shares, the attention
kernels in the Pallas interpreter, and fused PBT through ``cli.main``.
The model computes in float32 here (the ``float32`` fixture sets
``COMPUTE_DTYPE``), so that the comparisons are tight; the sweeps run
it as the cell does, in bfloat16.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO_ROOT, "benchmarks")
sys.path.insert(0, BENCH)

import check  # noqa: E402  (benchmarks/check.py)
import run as harness  # noqa: E402  (benchmarks/run.py)
import scopes  # noqa: E402  (benchmarks/scopes.py)

from mpi_opt_tpu.models import latent_moe_decoder as lmd  # noqa: E402
from mpi_opt_tpu.models import sparse_moe_decoder as smd  # noqa: E402

CELL = "kimi_vl_a3b.pbt_pop2_seq8k"
LIMITS = os.path.join(BENCH, "tests", "data", "rehearse_limits.json")
SEED = 11


@pytest.fixture(scope="module")
def cfg():
    return harness.resolve_cell(CELL, True, LIMITS)[2]


@pytest.fixture(scope="module")
def ref(cfg):
    return check.Reference(cfg, 2, 2, SEED)


@pytest.fixture(scope="module")
def workload(cfg, shared_workload):
    return shared_workload("kimi_vl_a3b", attrs=cfg["rehearse"]["workload_attrs"])


@pytest.fixture(scope="module")
def members(workload, ref):
    """The population's initial state, as the sweep makes it."""
    trainer = workload.make_trainer(member_chunk=1, donate=False)
    x = jnp.asarray(workload.data()["train_x"][:2])
    return trainer.init_population(ref.k_init, x, 2).params


@pytest.fixture(scope="module")
def ref_member(ref):
    """Member 1's initial parameters on the reference, in one program
    (leaf by leaf, each initialiser compiles on its own)."""
    return jax.jit(lambda: ref.init_member(1)[0])()


@pytest.fixture
def float32(monkeypatch):
    """Products in float32 at `highest`, as the reference computes."""
    monkeypatch.setattr(smd, "COMPUTE_DTYPE", jnp.float32)


def _dims(workload, **over):
    return lmd.LatentDims(**dict(workload.dims, **over))


def test_initial_weights_and_data_equal_the_references(workload, members, ref, ref_member):
    """Every leaf of the dense layer and of the expert layers, by name
    and creation order, and the token rows over the held vocabulary."""
    want = ref_member
    got = check._flatten(jax.tree.map(lambda a: np.asarray(a[1]), members))
    assert set(got) == set(want)
    for leaf in want:
        np.testing.assert_array_equal(got[leaf], np.asarray(want[leaf]), err_msg=str(leaf))
    assert ("layer_0", "w_gate") in want and ("layer_0", "router") not in want
    assert want[("layer_1", "w_gate")].shape == (2, 32, 16) and want[("layer_1", "shared_up")].shape == (32, 32)
    data = workload.data()
    for name in ("train_x", "train_y", "val_x", "val_y"):
        np.testing.assert_array_equal(data[name], np.asarray(ref.data[name]), err_msg=name)


def _program_loss(model, params, bx, by):
    """(mean cross-entropy + mean balance loss, the rows' counts)."""
    rows = [model.apply({"params": params}, x, y) for x, y in zip(bx, by)]
    ce = sum(r[0] for r in rows) / (len(rows) * bx.shape[1])
    return ce + sum(r[1] for r in rows) / len(rows), [r[2] for r in rows]


def test_loss_and_every_gradient_match_the_reference(workload, ref, cfg, members, ref_member, float32):
    """One member's loss (cross-entropy and balance loss) and the
    gradient of every leaf, with the expert layer on its gathered path
    (the path an overflowing layer takes is the Keye member's, and its
    tests'). The tolerances are float32's own (the
    products' order differs between program and reference): a relative
    1e-5 on the loss and 2e-5 of a leaf's largest gradient. The
    reference computed in bfloat16 misses both by far (at these sizes its
    loss by 6.1e-4 relative, every one of the 41 leaves' gradients by
    1.4 to 15% of the leaf's largest): the test reads its loss, and
    asserts that it does."""
    data = workload.data()
    bx, by = jnp.asarray(data["train_x"][:2]), jnp.asarray(data["train_y"][:2])
    params = jax.tree.map(lambda a: a[1], members)
    want_params = ref_member
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.model.loss(p, None, None, bx, by, "f32", cfg)
    ))(want_params)
    model = lmd.LatentMoEDecoder(_dims(workload, expert_capacity=40))
    (got, counts), grads = jax.jit(jax.value_and_grad(lambda p: _program_loss(model, p, bx, by), has_aux=True))(params)
    assert max(int(c[:, 1].max()) for c in counts) <= 40  # no layer overflows: the gathered path
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    grads = check._flatten(grads)
    for leaf, g in want_grads.items():
        g = np.asarray(g)
        assert np.abs(g).max() > 0, leaf
        np.testing.assert_allclose(np.asarray(grads[leaf]), g, rtol=0, atol=2e-5 * np.abs(g).max(), err_msg=str(leaf))
    witness = jax.jit(lambda p: ref.model.loss(p, None, None, bx, by, "bf16", cfg))(want_params)
    assert float(witness) != pytest.approx(float(want), rel=1e-5)


def test_score_is_the_references(workload, ref, cfg, members, ref_member, float32):
    """``eval_population`` (one row a chunk, one member at a time)
    against the reference's score of the same weights (float32's
    relative 1e-5, as above)."""
    trainer = workload.make_trainer(member_chunk=1, donate=False)
    data = workload.data()
    from mpi_opt_tpu.train import PopState

    state = PopState(params=members, momentum=members, step=jnp.zeros((2,), jnp.int32))
    got = np.asarray(trainer.eval_population(state, jnp.asarray(data["val_x"]), jnp.asarray(data["val_y"])))
    want = ref.model.score(ref_member, ref.data["val_x"], ref.data["val_y"], "f32", cfg)
    assert got[1] == pytest.approx(float(want), rel=1e-5)


def test_the_shares_add_up(cfg, float32):
    """A layer of 8 published experts divided over 4 chips, two experts
    each: every share routes over all 8 (sigmoid scores, top 2, scaled)
    and computes its own experts' part; the 4 parts, with the shared
    experts that every chip computes alike counted once, add up to what
    the uncut reference gives for the whole layer. Tolerance: float32's
    round-off of the sums, 1e-5 of the largest output."""
    ref_model = check.load_module(os.path.join(BENCH, "reference", "kimi_vl_a3b.py"), "ref_kimi_shares")
    published, chips, d, width, t = 8, 4, 32, 16, 64
    held = published // chips
    keys = jax.random.split(jax.random.key(5), 8)
    h2 = jax.random.normal(keys[0], (t, d), jnp.float32)
    router = jax.random.normal(keys[1], (d, published), jnp.float32) / np.sqrt(d)
    wg, wu = (jax.random.normal(k, (published, d, width), jnp.float32) / np.sqrt(d) for k in keys[2:4])
    wd = jax.random.normal(keys[4], (published, width, d), jnp.float32) / np.sqrt(width)
    sg, su = (jax.random.normal(k, (d, 2 * width), jnp.float32) / np.sqrt(d) for k in keys[5:7])
    sd = jax.random.normal(keys[7], (2 * width, d), jnp.float32) / np.sqrt(2 * width)
    uncut = dict(cfg, n_routed_experts=published, num_experts_per_tok=2)
    p = dict(router=router, w_gate=wg, w_up=wu, w_down=wd, shared_gate=sg, shared_up=su, shared_down=sd)
    whole, _ = ref_model._experts(p, h2, "f32", uncut, {})
    for capacity in (0, 40):  # every token through every held expert, and the gathered path
        dims = lmd.LatentDims(
            hidden=d, experts_published=published, experts_held=held, experts_per_token=2,
            expert_width=width, expert_capacity=capacity,
        )

        @jax.jit
        def share(chip):
            # the chip's own experts come first in ITS numbering of the published experts
            gates = smd.route(h2, jnp.roll(router, -chip * held, axis=1), dims)
            own = lambda w: jax.lax.dynamic_slice_in_dim(w, chip * held, held, axis=0)
            return smd.held_experts(h2, gates, own(wg), own(wu), own(wd), dims), jnp.sum(gates > 0)

        parts = [share(chip) for chip in range(chips)]
        assert sum(int(n) for _, n in parts) == 2 * t  # every token's two experts, each on exactly one chip
        shared = lmd.swiglu(h2, sg, su, sd)
        total = sum(np.asarray(y, np.float64) for y, _ in parts) + np.asarray(shared, np.float64)
        np.testing.assert_allclose(total, np.asarray(whole), rtol=0, atol=1e-5 * np.abs(np.asarray(whole)).max())


def _kernel_census(jaxpr, counts=None):
    """{splash kernel phase: equations} of a jaxpr and every jaxpr inside it."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            phase = next(s for s in ("fwd", "dq", "dkv") if f"_{s}" in str(eqn.params["name"]))
            counts[phase] = counts.get(phase, 0) + 1
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _kernel_census(inner, counts)
    return counts


def test_the_causal_kernels_are_the_tiles_products(monkeypatch, float32):
    """The attention of a layer as the splash kernels under the static
    causal mask (Pallas interpreter), query/key width 192 against value
    width 128, against XLA's own products over query tiles (float32's
    round-off, 1e-5 of the largest; the kernels' gradients are the
    chip's to show: its runs follow the float32 reference). Under the
    layer's remat a train step runs the forward kernel once a layer:
    its context and log-sum-exp are saved by name."""
    from mpi_opt_tpu.ops import selected_attention as sa

    monkeypatch.setattr(sa, "_INTERPRET", True)
    t, heads = 256, 1
    dims = lmd.LatentDims(heads=heads, q_chunk=128)
    ks = jax.random.split(jax.random.key(2), 3)
    q, k = (jax.random.normal(kk, (t, heads, dims.qk_dim)) for kk in ks[:2])
    v = jax.random.normal(ks[2], (t, heads, dims.v_dim))
    paths = {}
    for kernels in (False, True):
        monkeypatch.setattr(lmd, "use_kernels", lambda dims, t, kernels=kernels: kernels)
        paths[kernels] = lmd.causal_attention(q, k, v, dims)
    (xla_ctx, xla_held), (ctx, held) = paths[False], paths[True]
    assert (xla_held, held) == (0, heads * t * (dims.v_dim * 4 + 4))
    np.testing.assert_allclose(np.asarray(ctx), np.asarray(xla_ctx), rtol=0, atol=1e-5 * np.abs(np.asarray(xla_ctx)).max())

    layer = lmd.LatentLayer(lmd.LatentDims(hidden=64, heads=2, q_chunk=128, expert_capacity=0), dense=False)
    rematted = jax.checkpoint(lambda p, x: layer.apply(p, x)[0], policy=smd.saved_for_backward())
    x = jax.random.normal(ks[0], (t, 64))
    params = jax.eval_shape(layer.init, jax.random.key(0), x)  # traced, never run
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: jnp.sum(rematted(p, x))))(params).jaxpr
    assert _kernel_census(jaxpr) == {"fwd": 1, "dq": 1, "dkv": 1}


def test_a_traced_sweep_counts_its_flops_from_the_equations(workload, monkeypatch):
    """A traced sweep's per-generation FLOPs come from the layer
    equations (``segment_flops``): no probe lowers the programs a
    second time."""
    from mpi_opt_tpu.obs import trace
    from mpi_opt_tpu.train import common
    from mpi_opt_tpu.utils import flops

    monkeypatch.setattr(trace, "enabled", lambda: True)
    monkeypatch.setattr(flops, "population_sweep_flops", lambda *a, **k: pytest.fail("probed"))
    dims = lmd.LatentDims(**workload.dims)
    assert common.segment_flops_hint(workload, 2, 2) == 2 * 2 * lmd.forward_macs(dims, 64) * (3 * 2 + 4)


@pytest.fixture(scope="module")
def cli_result(workload, ref):
    """`run.py --rehearse`: fused PBT through ``cli.main`` at the
    traffic's rehearse sizes, in bfloat16 as the cell runs it, journaled
    and compared by ``check.check_ledger`` against the float32
    reference. The sweep runs on the module's workload and the check on
    its reference (the same configuration, population, steps and seed),
    so that neither compiles its programs a second time."""
    buf = io.StringIO()
    made = lambda cfg, population, steps, seed, **kw: ref if (population, steps, seed) == (2, 2, SEED) and not kw else check.Reference(cfg, population, steps, seed, **kw)
    with pytest.MonkeyPatch.context() as mp, contextlib.redirect_stdout(buf):
        mp.setattr(harness, "rehearse_workload", lambda cfg: workload)
        mp.setattr(check, "Reference", made)
        rc = harness.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "0.2", "--trace", "0",
                           "--rehearse", "--limits", LIMITS])
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_two_generations_through_the_cli_end_inside_the_limits(cli_result):
    res = cli_result
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 2
    assert res["window"]["check"]["generations"] >= 2
    assert all(v is not None and v <= lim for v, lim in res["compared"].values())
    assert len(res["window"]["check"]["slots"]) == 2


@pytest.fixture(scope="module")
def latent_op_names(workload, cli_result):
    """Every ``op_name`` of the fused PBT program the CLI run compiled
    (two members, one at a time, one generation of 2 steps): the same
    arguments, so the compile is the run's own."""
    from mpi_opt_tpu.ops.pbt import PBTConfig
    from mpi_opt_tpu.train.common import HParamsFn, workload_arrays
    from mpi_opt_tpu.train.fused_pbt import run_fused_pbt

    trainer, space, tx, ty, vx, vy = workload_arrays(workload, member_chunk=1)
    state = trainer.init_population(jax.random.key(1), tx[:2], 2)
    compiled = run_fused_pbt.program(trainer).lower(
        state, space.sample_unit(jax.random.key(0), 2), HParamsFn(space, workload),
        train_x=tx, train_y=ty, val_x=vx, val_y=vy, key=jax.random.key(2),
        discrete_mask=tuple(bool(b) for b in space.discrete_mask()),
        generations=1, steps_per_gen=2, cfg=PBTConfig(truncation_frac=0.25),
    ).compile()
    return sorted(set(re.findall(r'op_name="([^"]+)"', compiled.as_text())))


#: the scopes this member opens inside ``member_loss`` and
#: ``eval_population``, besides the Keye member's that it shares
LATENT_SCOPES = ("latent_attention", "shared_experts", "dense_mlp")


def test_the_latent_decoders_scopes_are_opened_inside_a_phase_scope_only(latent_op_names):
    """Its own scopes book no phase: each is opened inside
    ``member_loss`` or ``eval_population`` (as the Keye member's are,
    tests/test_device_scopes.py)."""
    member_scopes = LATENT_SCOPES + ("router", "experts", "loss_head")
    inside = 0
    for name in latent_op_names:
        if not name.startswith("jit("):
            continue
        cores = [scopes._core(c) for c in name.split("/")]
        for i, core in enumerate(cores):
            if core in member_scopes:
                assert {"member_loss", "eval_population"} & set(cores[:i]), name
                assert scopes.phase_of(name) in ("forward", "backward", "eval"), name
                inside += 1
    assert inside > 100


@pytest.mark.parametrize("scope", LATENT_SCOPES + ("router", "experts", "loss_head", "member_loss", "eval_population"))
def test_the_latent_program_carries_every_scope(latent_op_names, scope):
    cores = {scopes._core(c) for name in latent_op_names for c in name.split("/")}
    assert scope in cores, f"no op_name of the latent decoder's fused PBT program carries {scope!r}"
