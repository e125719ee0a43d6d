"""Search-space definition, array-first.

Every domain maps to/from the unit cube so that whole populations of
hyperparameters are plain ``float32[n, d]`` arrays on device:

- algorithms (TPE acquisition, PBT explore perturbations) operate on the
  unit-cube representation with pure ``jax.numpy`` ops and therefore
  ``vmap``/``jit`` cleanly;
- the typed value view (log-scaled floats, ints, categorical choices) is
  materialised only at the edge, either host-side (``materialize``) or
  on-device (``from_unit`` is itself jittable).

Reference parity: mpi_opt's search-space (uniform / log-uniform /
choice parameters fed to its optimizer; reference unreadable, surface per
SURVEY.md §2 row 3) — re-designed so sampling is a single vectorized op
instead of per-trial Python objects.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def _plain(v):
    """One value -> a plain JSON scalar (bool/int/float/str/None), repr
    for anything exotic. Canonicalization rule shared by ``spec`` and
    ``canonical_params``: a live value and its JSON round trip must
    produce identical bytes (json floats round-trip exactly), so ledger
    replay can verify params by key equality. bool first: it IS an int."""
    if isinstance(v, (bool, str)) or v is None:
        return v
    if isinstance(v, (int, np.integer)):
        return int(v)
    if isinstance(v, (float, np.floating)):
        return float(v)
    return repr(v)


class Domain:
    """Base class for one hyperparameter's domain.

    Subclasses define a bijection (up to quantization) between the unit
    interval [0, 1] and the typed value space.
    """

    def from_unit(self, u: jax.Array) -> jax.Array:
        """Map unit-interval array -> value array (jittable)."""
        raise NotImplementedError

    def to_unit(self, v: jax.Array) -> jax.Array:
        """Map value array -> unit interval (jittable)."""
        raise NotImplementedError

    def materialize(self, v: Any):
        """Convert a scalar array element to the Python-typed value."""
        return float(v)

    @property
    def discrete(self) -> bool:
        return False


@dataclasses.dataclass(frozen=True)
class Uniform(Domain):
    low: float
    high: float

    def __post_init__(self):
        if not self.low < self.high:
            raise ValueError(f"Uniform requires low < high, got [{self.low}, {self.high}]")

    def from_unit(self, u):
        return self.low + (self.high - self.low) * u

    def to_unit(self, v):
        return (v - self.low) / (self.high - self.low)


@dataclasses.dataclass(frozen=True)
class LogUniform(Domain):
    low: float
    high: float

    def __post_init__(self):
        if self.low <= 0 or self.high <= 0:
            raise ValueError("LogUniform bounds must be positive")
        if not self.low < self.high:
            raise ValueError(f"LogUniform requires low < high, got [{self.low}, {self.high}]")

    def from_unit(self, u):
        lo, hi = np.log(self.low), np.log(self.high)
        return jnp.exp(lo + (hi - lo) * u)

    def to_unit(self, v):
        lo, hi = np.log(self.low), np.log(self.high)
        return (jnp.log(v) - lo) / (hi - lo)


@dataclasses.dataclass(frozen=True)
class IntUniform(Domain):
    low: int
    high: int  # inclusive

    def __post_init__(self):
        if not self.low <= self.high:
            raise ValueError(f"IntUniform requires low <= high, got [{self.low}, {self.high}]")

    def from_unit(self, u):
        n = self.high - self.low + 1
        idx = jnp.clip(jnp.floor(u * n), 0, n - 1)
        return self.low + idx

    def to_unit(self, v):
        n = self.high - self.low + 1
        # centre of the bucket, so from_unit(to_unit(v)) == v
        return ((v - self.low) + 0.5) / n

    def materialize(self, v):
        return int(v)

    @property
    def discrete(self):
        return True


@dataclasses.dataclass(frozen=True)
class Choice(Domain):
    options: tuple

    def __init__(self, options: Sequence[Any]):
        object.__setattr__(self, "options", tuple(options))

    def from_unit(self, u):
        n = len(self.options)
        return jnp.clip(jnp.floor(u * n), 0, n - 1)

    def to_unit(self, v):
        # v is the DEVICE representation: the option index, not the
        # option value (use SearchSpace.params_to_unit for typed values —
        # e.g. for Choice([True, False]) the value True is index 0, but
        # numerically True == 1 and would silently encode index 1 here)
        return (v + 0.5) / len(self.options)

    def value_to_index(self, value) -> int:
        for i, opt in enumerate(self.options):
            if opt is value or (type(opt) is type(value) and opt == value):
                return i
        raise ValueError(f"{value!r} is not one of {self.options}")

    def materialize(self, v):
        return self.options[int(v)]

    @property
    def discrete(self):
        return True


class SearchSpace:
    """An ordered mapping name -> Domain with vectorized sampling.

    The canonical array layout is ``float32[..., d]`` in unit-cube
    coordinates, with dimension order = insertion order of ``domains``.
    """

    def __init__(self, domains: Mapping[str, Domain]):
        self.domains = dict(domains)
        self.names = list(self.domains.keys())

    @property
    def dim(self) -> int:
        return len(self.names)

    # -- sampling ---------------------------------------------------------

    def sample_unit(self, key: jax.Array, n: int) -> jax.Array:
        """Uniform sample in the unit cube: ``float32[n, d]``."""
        return jax.random.uniform(key, (n, self.dim), dtype=jnp.float32)

    def from_unit(self, u: jax.Array) -> dict[str, jax.Array]:
        """Unit-cube array ``[..., d]`` -> dict of typed value arrays.

        Jittable; used on-device to turn a population matrix into the
        per-member hyperparameter arrays fed to the train step.

        The input is coerced to a jax array FIRST: domain maps mix
        float64 numpy scalars into their arithmetic (e.g. LogUniform's
        ``np.log`` bounds), and on a plain numpy ``u`` (a
        snapshot-restored cohort) NumPy would run the intermediate math
        in float64 and round to float32 only at the final jnp op —
        double rounding that flips the last ulp of values like the
        learning rate versus the all-float32 on-device path. A resumed
        sweep must map bit-identical hparams to the run it resumes.
        """
        u = jnp.asarray(u)
        return {
            name: dom.from_unit(u[..., i])
            for i, (name, dom) in enumerate(self.domains.items())
        }

    def to_unit(self, values: Mapping[str, jax.Array]) -> jax.Array:
        """Dict of *device-representation* arrays -> unit cube ``[..., d]``.

        Jittable inverse of ``from_unit``. For Choice domains the device
        representation is the option index; to encode typed Python
        values (option objects, bools) use ``params_to_unit``.
        """
        cols = [
            self.domains[name].to_unit(jnp.asarray(values[name], jnp.float32))
            for name in self.names
        ]
        return jnp.stack(cols, axis=-1)

    def params_to_unit(self, params: Mapping[str, Any]) -> np.ndarray:
        """Typed-value params dict (one point) -> unit-cube row (host side)."""
        from mpi_opt_tpu.utils.hostdev import host_ops

        row = np.zeros(self.dim, dtype=np.float32)
        with host_ops():  # scalar ops: never pay an accelerator round trip
            for i, (name, dom) in enumerate(self.domains.items()):
                v = params[name]
                if isinstance(dom, Choice):
                    v = dom.value_to_index(v)
                row[i] = float(np.asarray(dom.to_unit(jnp.asarray(float(v)))))
        return row

    def sample(self, key: jax.Array, n: int) -> dict[str, jax.Array]:
        """Sample n points, returned as typed value arrays."""
        return self.from_unit(self.sample_unit(key, n))

    # -- host-side edges --------------------------------------------------

    def materialize_row(self, u_row: np.ndarray) -> dict[str, Any]:
        """One unit-cube row -> a plain-Python hparam dict (host side)."""
        return self.materialize_rows(np.asarray(u_row)[None])[0]

    def materialize_rows(self, units: np.ndarray) -> list[dict[str, Any]]:
        """Unit-cube rows ``[n, d]`` -> one plain-Python hparam dict a
        row (host side).

        One ``from_unit`` a dimension over the whole column, in float32
        as the rows arrive: elementwise, so each value is bit-identical
        to decoding its row alone (a fused boundary's 512 records cost
        ``d`` ops, not ``512 * d``). CPU-pinned: on the default device
        each op is a dispatch and a blocking fetch (utils.hostdev).
        """
        from mpi_opt_tpu.utils.hostdev import host_ops

        units = np.asarray(units)
        cols = {}
        with host_ops():
            for i, (name, dom) in enumerate(self.domains.items()):
                cols[name] = np.asarray(dom.from_unit(jnp.asarray(units[:, i])))
        return [
            {name: dom.materialize(cols[name][j]) for name, dom in self.domains.items()}
            for j in range(units.shape[0])
        ]

    def discrete_mask(self) -> np.ndarray:
        """bool[d]: which dims are discrete (used by TPE/PBT perturbation)."""
        return np.array([d.discrete for d in self.domains.values()])

    # -- durable identity (ledger/warm-start; SURVEY.md §5) ---------------

    def spec(self) -> list[dict]:
        """JSON-able description of the space, in dimension order.

        This is the space's DURABLE identity: the ledger header records
        its hash so a resume or warm-start against a ledger written for
        a different space is refused instead of silently misdecoding
        unit rows. Dataclass fields capture each domain's full bounds;
        Choice options go through ``_plain`` so non-JSON option objects
        degrade to their repr deterministically.

        Multi-objective sweeps (ISSUE 17) journal a sibling
        ``objective_spec`` (objectives.ObjectiveSpec.spec — names,
        directions, constraint bounds) in the same header, top-level
        beside ``space_spec``: the space says WHERE the sweep searched,
        the objective spec says WHAT it optimized. Both ride outside
        the hashed config identity; objective identity enters identity
        through the config's ``objectives`` string instead.
        """
        out = []
        for name, dom in self.domains.items():
            d: dict[str, Any] = {"name": name, "kind": type(dom).__name__}
            for f in dataclasses.fields(dom):
                v = getattr(dom, f.name)
                d[f.name] = [_plain(o) for o in v] if isinstance(v, tuple) else _plain(v)
            out.append(d)
        return out

    def space_hash(self) -> str:
        """Stable short digest of ``spec()`` (order- and value-exact)."""
        payload = json.dumps(self.spec(), sort_keys=True)
        return hashlib.sha256(payload.encode()).hexdigest()[:16]

    def canonical_params(self, params: Mapping[str, Any]) -> dict[str, Any]:
        """One hparam dict -> its canonical JSON-able form.

        Internal keys (``__``-prefixed driver plumbing like
        ``__inherit_from__``) are dropped, keys are restricted to this
        space's dimensions in insertion order, and values normalize to
        plain JSON scalars — so the SAME point always serializes to the
        SAME bytes whether it arrives live from ``materialize_row`` or
        back from a ledger JSON round trip.
        """
        missing = [n for n in self.names if n not in params]
        if missing:
            raise KeyError(f"params missing dimensions {missing} of {self.names}")
        return {name: _plain(params[name]) for name in self.names}

    def params_key(self, params: Mapping[str, Any]) -> str:
        """Canonical exact-match key for one point (ledger dedup cache)."""
        return json.dumps(self.canonical_params(params), sort_keys=True)

    def __repr__(self):
        inner = ", ".join(f"{k}={v}" for k, v in self.domains.items())
        return f"SearchSpace({inner})"
