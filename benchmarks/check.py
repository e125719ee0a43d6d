"""The comparison that decides `correct`.

What the timed path produced is the ledger it wrote: for every
generation, each member's hyperparameters and its pre-exploit score.
This module replays the sweep's decisions on the plain reference
(`reference/`) and compares, number by number, each against a limit
from `limits/<cell>.json`:

  records_missing   member records the ledger lacks, or with a wrong
                    member / step / status, over every generation
                    (exact: limit 0)
  hparam_gap        worst gap, in unit-cube coordinates, between a
                    journaled hyperparameter and the reference's, over
                    every member of every generation (relative gaps blow
                    up where a uniform domain's value is near its zero
                    bound). Generation 0's rows are
                    the seed's draw; generation g+1's are the reference's
                    exploit/explore of generation g's JOURNALED scores
                    (one flipped rank changes a parent, so rows are only
                    comparable given the scores as journaled)
  update_gap_median_leaf
                    the state the sweep carries out of its first
                    generation (captured at the first boundary, for a
                    sample of population slots drawn from the seed): per
                    parameter leaf, the gap between the norm of the
                    program's change from the seed's weights and the
                    reference's, after the reference trained the slot's
                    source member through the same steps, against the
                    reference's norm of that leaf or of the median leaf,
                    whichever is larger; the MEDIAN leaf of the worst slot.
                    Slots whose source member trained at a learning rate
                    above the traffic's `check.max_lr` are not followed:
                    such a rate amplifies rounding or diverges, in the
                    reference's own bfloat16 witness as in the program
                    (readings in PERF.md section 6)

Read and printed beside them, but given no limit in the cells of PR 24
(no reading of the control or of a fault lies above the sound runs':
PERF.md section 6): `update_gap_worst_leaf` (the worst leaf: early
layers' small gradients in bfloat16, and members with a large learning
rate, swing it), `momentum_gap_median_leaf` / `momentum_gap_worst_leaf`
(the same gaps of the momentum buffers' norms), `score_gap_rows` /
`score_gap_max_rows` (mean and worst |journaled score - reference
score| in validation rows over the same members; after 50 steps a score
is a count of near-tied rows).

Leaves whose accumulated gradient is nought in the reference (momentum
norm under a thousandth of the median leaf's) are left out of both norm
gaps: they move by round-off alone.

The reference is float32 at `highest`; it takes the seed, the
configuration's file and the ledger, and nothing the program made.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from collections.abc import Mapping

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_module(path: str, name: str):
    """Import one file of the benchmark by path (the directories are
    not packages: a later PR adds a file, never edits an `__init__`)."""
    d = os.path.dirname(path)
    if d not in sys.path:
        sys.path.insert(0, d)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_ledger(path: str):
    """(header config, {generation: {member: record}})."""
    header, gens = None, {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            if rec.get("kind") == "header":
                header = rec
            elif rec.get("kind") == "trial":
                gens.setdefault(int(rec["boundary"]), {})[int(rec["member"])] = rec
    if header is None:
        raise ValueError(f"{path}: no ledger header")
    return header, gens


class Reference:
    """The plain reference of one cell, ready to train members."""

    def __init__(self, cfg: dict, population: int, steps: int, seed: int, keep_rows: int = 0):
        import jax.numpy as jnp

        self.common = load_module(os.path.join(HERE, "reference", "common.py"), "common")
        self.model = load_module(os.path.join(HERE, cfg["reference"]), "ref_" + cfg["name"])
        self.cfg, self.population, self.steps, self.seed = cfg, population, steps, seed
        self.k_init, self.k_unit, self.k_run = self.common.sweep_keys(seed)
        data = self.common.make_data(cfg["data"])
        self.data = {k: jnp.asarray(v) for k, v in data.items()}
        self.table = self.model.param_table(cfg)
        self.train_generation, self.score = self.common.make_member_programs(
            self.model.apply, cfg, population, steps, cfg["batch_size"], keep_rows
        )

    def initial_unit(self):
        import jax

        return jax.random.uniform(
            self.k_unit, (self.population, len(self.cfg["space"])), dtype="float32"
        )

    def hparams(self, unit):
        return self.common.from_unit(self.cfg["space"], unit)

    def exploit(self, g: int, unit, scores):
        _, k_pbt = self.common.generation_keys(self.k_run, g)
        p = self.cfg["pbt"]
        return self.common.exploit_explore(
            k_pbt, unit, scores, p["truncation_frac"], p["perturb_scale"]
        )

    def init_member(self, member: int):
        import jax
        import jax.numpy as jnp

        params = self.common.init_member(self.table, self.k_init, self.population, member)
        return params, jax.tree.map(jnp.zeros_like, params)

    def train(self, state, g: int, member: int, unit_row, mode="f32", store="float32"):
        """One generation of one member: (new state, correct rows)."""
        k_train, _ = self.common.generation_keys(self.k_run, g)
        hp = self.hparams(unit_row)
        p, m, _losses = self.train_generation(
            state[0], state[1], hp, k_train, member,
            self.data["train_x"], self.data["train_y"], mode=mode, store=store,
        )
        rows = int(self.score(p, self.data["val_x"], self.data["val_y"], mode=mode))
        return (p, m), rows


def _flatten(tree, prefix=()):
    """Nested dict of arrays -> {path tuple: array}."""
    out = {}
    for k, v in tree.items():
        if isinstance(v, Mapping):
            out.update(_flatten(v, prefix + (k,)))
        else:
            out[prefix + (k,)] = v
    return out


def find_population_state():
    """The population state the sweep holds at a boundary: the first
    local of a calling frame that has `params`, `momentum` and `step`.
    The program offers no accessor (PERF.md Open questions); the slice
    hook runs inside the sweep's host loop, so its state is in reach."""
    f = sys._getframe(1)
    while f is not None:
        for v in list(f.f_locals.values()):
            if hasattr(v, "params") and hasattr(v, "momentum") and hasattr(v, "step"):
                return v
        f = f.f_back
    return None


def capture_slots(slots: list):
    """Host copies of the sampled slots' parameters and momentum, as the
    sweep holds them right now: {"params": {path: [k, ...]}, "momentum":
    ...}, or None where no population state is in reach."""
    import jax

    state = find_population_state()
    if state is None:
        return None
    idx = np.asarray(slots, np.int32)
    take = jax.jit(lambda tree: jax.tree.map(lambda x: x[idx], tree))
    params, momentum = jax.device_get(take((state.params, state.momentum)))
    return {"params": _flatten(params), "momentum": _flatten(momentum)}


def sample_members(seed: int, population: int, k: int) -> list:
    rng = np.random.default_rng(seed)
    return sorted(int(i) for i in rng.choice(population, size=min(k, population), replace=False))


def replay_decisions(ref: Reference, gens: dict, steps: int):
    """Walk the journaled generations: structural faults, the worst
    hyperparameter gap, and the reference's own (unit, src_idx) chain.
    Returns (records_missing, hparam_gap, units, srcs)."""
    population, space = ref.population, ref.cfg["space"]
    n_gens = 1 + max(gens) if gens else 0
    missing, gap = 0, 0.0
    units, srcs = [], []
    unit = ref.initial_unit()
    for g in range(n_gens):
        recs = gens.get(g, {})
        want = {k: np.asarray(v, np.float64) for k, v in ref.hparams(unit).items()}
        to_unit = ref.common.to_unit
        scores = np.full((population,), -np.inf, np.float32)
        for i in range(population):
            rec = recs.get(i)
            if (
                rec is None
                or rec["status"] != "ok"
                or int(rec["step"]) != (g + 1) * steps
                or rec.get("score") is None
            ):
                missing += 1
                continue
            scores[i] = rec["score"]
            for dom in space:
                name = dom["name"]
                got = to_unit(space, name, float(rec["params"][name]))
                gap = max(gap, abs(got - to_unit(space, name, float(want[name][i]))))
        units.append(unit)
        unit, src = ref.exploit(g, unit, scores)
        srcs.append(np.asarray(src))
    return missing, gap, units, srcs


def follow_sources(ref: Reference, slots, units, srcs, mode="f32", store="float32"):
    """Train, on the reference, the generation-0 source member of every
    sampled slot: {member: (params, momentum, correct rows)}. Slot i
    leaves the first boundary holding member srcs[0][i]'s weights."""
    out = {}
    for i in slots:
        s = int(srcs[0][i])
        if s not in out:
            (p, m), rows = ref.train(ref.init_member(s), 0, s, units[0][s], mode, store)
            out[s] = (p, m, rows)
    return out


def _norm(a):
    return float(np.sqrt(np.sum(np.square(np.asarray(a, np.float64)))))


def leaf_gaps(ref: Reference, member: int, got_params: dict, got_momentum: dict, want):
    """{leaf: (update gap, momentum gap)} of one slot against the
    reference's `want` = (params, momentum) of its source member; leaves
    whose accumulated gradient is nought in the reference are left out,
    a leaf the program lacks reads infinity."""
    p0, _ = ref.init_member(member)
    d_ref = {k: _norm(np.asarray(want[0][k], np.float64) - np.asarray(p0[k], np.float64)) for k in p0}
    m_ref = {k: _norm(want[1][k]) for k in p0}
    med_d = float(np.median(list(d_ref.values())))
    med_m = float(np.median(list(m_ref.values())))
    out = {}
    for k in p0:
        if m_ref[k] < 1e-3 * med_m:
            continue
        if k not in got_params or k not in got_momentum:
            out[k] = (float("inf"), float("inf"))
            continue
        d = _norm(np.asarray(got_params[k], np.float64) - np.asarray(p0[k], np.float64))
        out[k] = (
            abs(d - d_ref[k]) / max(d_ref[k], med_d, 1e-30),
            abs(_norm(got_momentum[k]) - m_ref[k]) / max(m_ref[k], med_m, 1e-30),
        )
    return out


def norm_gaps(ref: Reference, member: int, got_params: dict, got_momentum: dict, want):
    """One slot's gaps: {"update": (median leaf, worst leaf),
    "momentum": (median leaf, worst leaf), "left_out": leaves}."""
    gaps = leaf_gaps(ref, member, got_params, got_momentum, want)
    inf = float("inf")
    if not gaps:
        return {"update": (inf, inf), "momentum": (inf, inf), "left_out": len(ref.table)}
    ups = [u for u, _ in gaps.values()]
    mos = [m for _, m in gaps.values()]
    return {
        "update": (float(np.median(ups)), max(ups)),
        "momentum": (float(np.median(mos)), max(mos)),
        "left_out": len(ref.table) - len(gaps),
    }


def score_gaps(gens: dict, rows: dict, n_val: int):
    """(mean, max) of |journaled - reference| in validation rows, for
    {(generation, member): reference rows}."""
    gaps = []
    for (g, i), r in rows.items():
        rec = gens.get(g, {}).get(i)
        if rec is None or rec.get("score") is None:
            gaps.append(float(n_val))
        else:
            gaps.append(abs(float(rec["score"]) * n_val - r))
    if not gaps:
        return float("nan"), float("nan")
    return float(np.mean(gaps)), float(np.max(gaps))


def check_ledger(cfg, traffic, limits, seed, header, gens, slots=None, captured=None):
    """Every number compared, beside its limit: {name: [value, limit]},
    and whether all of them hold. A number that is not finite, or that
    could not be read, fails. `header`, `gens`: `read_ledger`'s."""
    population, steps = traffic["population"], traffic["steps_per_generation"]
    ref = Reference(cfg, population, steps, seed)
    missing, gap, units, srcs = replay_decisions(ref, gens, steps)
    if int(header["config"].get("seed", seed)) != seed:
        missing += 1
    rule = traffic["check"]
    if slots is None:
        slots = sample_members(seed, population, rule["sample_members"])
    # the slots that are followed: source member's learning rate within the rule
    lr = np.asarray(ref.hparams(units[0])["lr"])
    kept = [j for j, i in enumerate(slots) if lr[int(srcs[0][i])] <= rule["max_lr"]]
    kept = kept[: rule["follow_at_most"]]
    followed = follow_sources(ref, [slots[j] for j in kept], units, srcs)
    rows = {(0, s): r for s, (_, _, r) in followed.items()}
    mean_gap, max_gap = score_gaps(gens, rows, cfg["data"]["n_val"])
    inf = float("inf")
    worst = {"update": [inf, inf], "momentum": [inf, inf]}  # [median leaf, worst leaf]
    skipped = 0
    if captured is not None and kept:
        worst = {"update": [0.0, 0.0], "momentum": [0.0, 0.0]}
        for j in kept:
            s = int(srcs[0][slots[j]])
            got_p = {k: v[j] for k, v in captured["params"].items()}
            got_m = {k: v[j] for k, v in captured["momentum"].items()}
            g = norm_gaps(ref, s, got_p, got_m, followed[s][:2])
            skipped = g["left_out"]
            for kind in worst:
                worst[kind] = [max(a, b) for a, b in zip(worst[kind], g[kind])]
    values = {
        "records_missing": float(missing),
        "hparam_gap": gap,
        "update_gap_median_leaf": worst["update"][0],
        "update_gap_worst_leaf": worst["update"][1],
        "momentum_gap_median_leaf": worst["momentum"][0],
        "momentum_gap_worst_leaf": worst["momentum"][1],
        "score_gap_rows": mean_gap,
        "score_gap_max_rows": max_gap,
    }
    ok = any(k in limits for k in values) and all(
        np.isfinite(values[k]) and values[k] <= float(limits[k]) for k in values if k in limits
    )
    # a number that could not be read is shown as null (and has failed)
    compared = {
        k: [values[k] if np.isfinite(values[k]) else None, float(limits[k])]
        for k in values
        if k in limits
    }
    detail = {
        "generations": len(units),
        "slots": [slots[j] for j in kept],
        "sources": [int(srcs[0][slots[j]]) for j in kept],
        "reference_rows": {str(s): r for s, (_, _, r) in sorted(followed.items())},
        "leaves_left_out": skipped,
        "not_compared": {k: values[k] for k in values if k not in limits},
    }
    return ok, compared, detail
