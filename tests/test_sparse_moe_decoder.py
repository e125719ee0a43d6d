"""The first member that reads tokens (models/sparse_moe_decoder.py,
workloads/language.py) against the benchmark's plain reference
(benchmarks/reference/keye_vl2_30b_a3b.py), at the configuration's
`rehearse` sizes on the CPU: data, initial weights, loss, every leaf's
gradient, the selection, the score, the expert shares, and two
generations of fused PBT through ``cli.main`` ending inside the
comparison's limits. The program computes in float32 here (the
``float32`` fixture sets the model's ``COMPUTE_DTYPE``) so that the
comparisons are tight; the sweeps at the end run it as the cell does,
in bfloat16.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
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

from mpi_opt_tpu.models import sparse_moe_decoder as smd  # noqa: E402

CELL = "keye_vl2_30b_a3b.pbt_pop4_seq8k"
LIMITS = os.path.join(BENCH, "tests", "data", "rehearse_limits.json")
SEED = 7
INDEXER = ("wq_index", "wk_index", "ww_index")
# what a member-step saves by name from its forward to its backward at the
# rehearse sizes (XLA's own path: the selection masks alone): 2 layers of two
# tiles of 32 queries against 32 and 64 keys, a byte a pair
SAVED_MIB = 2 * 32 * (32 + 64) / 2**20


@pytest.fixture(scope="module")
def cfg():
    return harness.resolve_cell(CELL, True, LIMITS)[2]


@pytest.fixture(scope="module")
def ref(cfg):
    return check.Reference(cfg, 4, 2, SEED)


@pytest.fixture(scope="module")
def workload(cfg):
    return harness.rehearse_workload(cfg)


@pytest.fixture
def float32(monkeypatch):
    """Products in float32 at `highest`, as the reference computes."""
    monkeypatch.setattr(smd, "COMPUTE_DTYPE", jnp.float32)


def _dims(workload, **over):
    return smd.DecoderDims(**dict(workload.dims, **over))


def _program_loss(model, params, bx, by):
    """(mean cross-entropy, mean indexer loss) over the rows."""
    rows = [model.apply({"params": params}, x, y) for x, y in zip(bx, by)]
    ce = sum(r[0] for r in rows) / (len(rows) * bx.shape[1])
    return ce, sum(r[1] for r in rows) / len(rows)


def test_token_recipe_equals_the_references(workload, ref):
    data = workload.data()
    for name in ("train_x", "train_y", "val_x", "val_y"):
        assert data[name].dtype == np.int32
        np.testing.assert_array_equal(data[name], np.asarray(ref.data[name]), err_msg=name)
    assert 0 <= data["train_x"].min() and data["train_x"].max() < workload.dims["vocab"]
    np.testing.assert_array_equal(data["train_x"][:, 1:], data["train_y"][:, :-1])


@pytest.fixture(scope="module")
def members(workload, ref):
    """The population's initial state, as the sweep makes it."""
    trainer = workload.make_trainer(member_chunk=1, donate=False)
    x = jnp.asarray(workload.data()["train_x"][:2])
    return trainer.init_population(ref.k_init, x, 4).params


def test_initial_weights_equal_the_references(members, ref):
    for member in (0, 3):
        want, _ = ref.init_member(member)
        got = check._flatten(jax.tree.map(lambda a: np.asarray(a[member]), members))
        assert set(got) == set(want)
        for leaf in want:
            np.testing.assert_array_equal(got[leaf], np.asarray(want[leaf]), err_msg=str(leaf))


def test_loss_and_every_gradient_match_the_reference(workload, ref, cfg, members, float32):
    """One member's loss and the gradient of every leaf, with the
    expert layer on its gathered path (no expert has more than 63 of
    the 64 tokens) and on the path an overflowing layer takes."""
    data = workload.data()
    bx, by = jnp.asarray(data["train_x"][:2]), jnp.asarray(data["train_y"][:2])
    params = jax.tree.map(lambda a: a[1], members)
    want_params, _ = ref.init_member(1)
    # one program, as the harness runs the reference (common.make_member_programs):
    # op by op its hundred small compiles were most of this test's time
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.model.loss(p, None, None, bx, by, "f32", cfg)
    ))(want_params)
    for capacity in (63, 4):
        model = smd.SparseMoEDecoder(_dims(workload, expert_capacity=capacity))
        (got, counts), grads = jax.jit(jax.value_and_grad(
            lambda p: (lambda ce, kl, rows: (ce + kl, rows))(
                *_program_loss(model, p, bx, by), [model.apply({"params": p}, x, y)[2] for x, y in zip(bx, by)]
            ), has_aux=True,
        ))(params)
        fullest = max(int(c[:, 2].max()) for c in counts)
        assert (fullest <= capacity) == (capacity == 63)
        assert float(got) == pytest.approx(float(want), rel=1e-5)
        grads = check._flatten(grads)
        for leaf, g in want_grads.items():
            g = np.asarray(g)
            assert np.abs(g).max() > 0, leaf
            np.testing.assert_allclose(
                np.asarray(grads[leaf]), g, rtol=0, atol=2e-5 * np.abs(g).max(), err_msg=f"{capacity} {leaf}"
            )


def test_the_two_losses_train_disjoint_leaves(workload, members, float32):
    """With the two stop-gradients the indexer's three leaves get no
    gradient from the cross-entropy, and no other leaf gets one from
    the indexer's loss."""
    model = smd.SparseMoEDecoder(_dims(workload))
    data = workload.data()
    bx, by = jnp.asarray(data["train_x"][:1]), jnp.asarray(data["train_y"][:1])
    params = jax.tree.map(lambda a: a[0], members)
    both = jax.jit(jax.jacrev(lambda p: jnp.stack(_program_loss(model, p, bx, by))))(params)
    for leaf, g in check._flatten(both).items():
        from_ce, from_index_loss = (bool(np.any(np.asarray(g[i]) != 0)) for i in (0, 1))
        assert (from_ce, from_index_loss) == ((False, True) if leaf[-1] in INDEXER else (True, False)), leaf


def test_selection_is_the_references_set():
    """Index scores without ties: the threshold mask holds exactly the
    `min(top_k, t + 1)` causal keys of largest score, the reference's
    sorted rows give the same set, and both are numpy's."""
    ref_model = check.load_module(os.path.join(BENCH, "reference", "keye_vl2_30b_a3b.py"), "ref_sel")
    rng = np.random.default_rng(0)
    scores = rng.permutation(48 * 80).reshape(48, 80).astype(np.float32) - 1000.0  # distinct, both signs
    scores[:, 5] = -np.inf  # a key no query may prefer to any other
    first, top_k = 24, 16
    got = np.asarray(smd.select_keys(jnp.asarray(scores), first, top_k))
    want = np.asarray(ref_model._selection(jnp.asarray(scores), first, top_k))
    np.testing.assert_array_equal(got, want)
    for r in range(48):
        t = first + r
        causal = np.arange(80) <= t
        best = np.argsort(-np.where(causal, scores[r], -np.inf), kind="stable")[: min(top_k, t + 1)]
        assert set(np.flatnonzero(got[r])) == set(best)
    # the k-th largest itself, over every bit pattern class
    keys = smd.order_key(jnp.asarray([[-np.inf, -2.5, -0.0, 0.0, 1e-30, 3.0, np.inf]], jnp.float32))
    assert np.all(np.diff(np.asarray(keys)[0].astype(np.int64)) >= 0)
    kth = smd.kth_largest(keys, jnp.asarray([3]))
    assert int(kth[0]) == int(keys[0, 4])


def test_score_is_the_references(workload, ref, cfg, members, float32):
    """``eval_population`` (one row a chunk, one member at a time)
    against the reference's score of the same weights."""
    wl = harness.rehearse_workload(cfg)
    trainer = wl.make_trainer(member_chunk=1, donate=False)
    data = wl.data()
    from mpi_opt_tpu.train import PopState

    state = PopState(params=members, momentum=members, step=jnp.zeros((4,), jnp.int32))
    got = np.asarray(trainer.eval_population(state, jnp.asarray(data["val_x"]), jnp.asarray(data["val_y"])))
    for member in (0, 2):
        params, _ = ref.init_member(member)
        want = ref.model.score(params, ref.data["val_x"], ref.data["val_y"], "f32", cfg)
        assert got[member] == pytest.approx(float(want), rel=1e-5)
        assert ref.journaled_score(float(got[member])) == float(got[member])


def test_a_member_trained_in_place_reaches_the_cut_members_state(cfg, ref, float32):
    """One member at a time without a batch axis: its steps run on its
    row of the population's state itself (no second copy of a member of
    gigabytes). The same segment with the member cut out once and
    vmapped, as small members are, ends in the same state."""
    states = []
    for unbatched in (True, False):
        wl = harness.rehearse_workload(cfg)
        trainer = wl.make_trainer(member_chunk=1, donate=False)
        trainer.member.single_unbatched = unbatched
        data = {k: jnp.asarray(v) for k, v in wl.data().items() if k != "n_classes"}
        state = trainer.init_population(ref.k_init, data["train_x"][:2], 2)
        hp = wl.make_hparams(wl.default_space().from_unit(jnp.full((2, 3), 0.5)))
        state, losses = trainer.train_segment(state, hp, data["train_x"], data["train_y"], ref.k_run, steps=2)
        states.append((state, losses))
    (a, (la, ca)), (b, (lb, cb)) = states
    assert np.asarray(a.step).tolist() == [2, 2]
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=1e-5)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-4, atol=1e-6)
    # the steps' own counters come out beside the losses, [steps, counters],
    # each the mean over the two members
    np.testing.assert_allclose(np.asarray(ca), np.asarray(cb), rtol=1e-6)
    assert trainer.member.counters == ("selected_keys", "routed_tokens", "fullest_expert_tokens", "saved_residual_mib")
    selected, routed, fullest, saved = np.asarray(ca).T
    assert np.all(saved == SAVED_MIB)
    # min(16, t + 1) keys a query of 64, and the keys tied with the last of
    # them (relu leaves many index scores 0 at these sizes); dense reads 32.5
    assert np.all((selected >= (136 + 48 * 16) / 64) & (selected < 20))
    # 64 tokens x 2 of 8 experts each, 2 held: 32 a layer expected
    assert np.all((routed > 8) & (routed < 64) & (fullest >= routed / 2) & (fullest <= 64))


def test_the_train_span_carries_the_members_counters(cfg, tmp_path, capsys):
    """A traced sweep through ``cli.main``: every launch's ``train``
    span carries what its members' train steps counted of their own
    work and what they saved by name for their backward pass, out of
    the program that trained them (no program beside it), and the
    stream passes the registry."""
    from mpi_opt_tpu.cli import main
    from mpi_opt_tpu.obs import events

    mf = str(tmp_path / "m.jsonl")
    rc = main(
        ["--workload", cfg["workload"], "--seed", "3", "--metrics-file", mf, "--trace", "--generations", "2"]
        + harness.resolve_cell(CELL, True, LIMITS)[3]["cli"],
        _workload=harness.rehearse_workload(cfg),
    )
    capsys.readouterr()
    assert rc == 0
    with open(mf) as f:
        spans = [r for r in map(json.loads, f) if r.get("event") == "span"]
    trains = [r for r in spans if r["span"] == "train"]
    assert [r["launch"] for r in trains] == [1, 2]
    for r in trains:
        assert r["saved_residual_mib"] == SAVED_MIB  # from the shapes: the same on every launch
        assert 14.125 <= r["selected_keys"] < 20  # min(16, t + 1) of 64, and ties
        assert 8 < r["routed_tokens"] < 64 and r["routed_tokens"] / 2 <= r["fullest_expert_tokens"] <= 64
    assert not [r for r in spans if r.get("op") == "member_counts"]
    for r in spans:
        extra = set(r) - {"event", "t", "ts", "span", "dur_s", "self_s", "tid", "rank", "tenant"}
        assert events.is_span(r["span"]) and all(events.is_span_attr(k) for k in extra), (r["span"], extra)


def test_the_shares_add_up(cfg, float32):
    """A layer of 16 published experts divided over 16 chips, one
    expert each: every share routes over all 16 and computes its own
    expert's part; the 16 parts add up to what the uncut reference
    gives for the whole layer (nothing in this layer is computed by
    every chip alike, so nothing is counted twice)."""
    ref_model = check.load_module(os.path.join(BENCH, "reference", "keye_vl2_30b_a3b.py"), "ref_shares")
    published, d, width, t = 16, 32, 16, 64
    keys = jax.random.split(jax.random.key(3), 5)
    h2 = jax.random.normal(keys[0], (t, d), jnp.float32)
    router = jax.random.normal(keys[1], (d, published), jnp.float32)
    wg = jax.random.normal(keys[2], (published, d, width), jnp.float32) / np.sqrt(d)
    wu = jax.random.normal(keys[3], (published, d, width), jnp.float32) / np.sqrt(d)
    wd = jax.random.normal(keys[4], (published, width, d), jnp.float32) / np.sqrt(width)
    uncut = dict(cfg, num_experts=published, num_experts_per_tok=4)
    whole = ref_model._experts(
        {("l", "router"): router, ("l", "w_gate"): wg, ("l", "w_up"): wu, ("l", "w_down"): wd},
        "l", h2, "f32", uncut,
    )
    for capacity in (0, 40):  # every token through the expert, and the gathered path
        dims = smd.DecoderDims(
            hidden=d, experts_published=published, experts_held=1, experts_per_token=4,
            expert_width=width, expert_capacity=capacity,
        )

        @jax.jit
        def share(chip):
            # the chip's own expert comes first in ITS numbering of the published experts
            gates = smd.route(h2, jnp.roll(router, -chip, axis=1), dims)
            own = lambda w: jax.lax.dynamic_slice_in_dim(w, chip, 1, axis=0)
            return smd.held_experts(h2, gates, own(wg), own(wu), own(wd), dims), jnp.sum(gates > 0)

        parts = [share(chip) for chip in range(published)]
        assert sum(int(n) for _, n in parts) == 4 * t  # every token's four experts, each on exactly one chip
        total = sum(np.asarray(y, np.float64) for y, _ in parts)
        np.testing.assert_allclose(total, np.asarray(whole), rtol=0, atol=1e-4 * np.abs(np.asarray(whole)).max())


def test_two_generations_through_the_cli_end_inside_the_limits(monkeypatch):
    """`run.py --rehearse`: fused PBT through ``cli.main`` at the
    configuration's rehearse sizes, in bfloat16 as the cell runs it,
    journaled, compared by ``check.check_ledger``. And the tier-1 copy
    of benchmarks/tests/test_control.py's observer case: the program's
    boundary observer hands the harness's capture ONE state, the one
    after the first generation, and no observer is installed once the
    window is open."""
    import window
    from mpi_opt_tpu.health import shutdown

    observers, steps = [], []
    real_hook, real_capture = window.Window.hook, check.capture_slots

    def hook(self, stage):
        observers.append(shutdown.get_boundary_observer())
        return real_hook(self, stage)

    def capture(state, slots):
        steps.append(np.asarray(state.step).tolist())
        return real_capture(state, slots)

    monkeypatch.setattr(window.Window, "hook", hook)
    monkeypatch.setattr(check, "capture_slots", capture)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = harness.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "0.2", "--trace", "0",
                           "--rehearse", "--limits", LIMITS])
    assert rc == 0
    res = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 4
    assert res["window"]["check"]["generations"] >= 2
    assert set(res["compared"]) == {"records_missing", "hparam_gap", "update_gap_median_leaf"}
    assert all(v is not None and v <= lim for v, lim in res["compared"].values())
    assert len(res["window"]["check"]["slots"]) == 2  # two members followed on the reference
    assert steps == [[2] * 4]  # one copy: the state after the first generation's 2 steps
    assert len(observers) >= 2 and all(o is None for o in observers)
    assert shutdown.get_boundary_observer() is None and shutdown.get_slice_hook() is None
