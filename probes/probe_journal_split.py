"""Split one fused boundary's journal into its decode and its appends.

ISSUE 34: a 512-member boundary of `cifar10_cnn` cost 0.894 s of idle
device (ledger, PR 30). This times, on this host's CPU and disk, for
the tree at ROOT (default: this checkout; point it at an unpacked
parent to read the parent): decoding 512 unit rows one at a time
(`materialize_row`) and in one pass (`materialize_rows`, where the
tree has it), appending the 512 records with an fsync each and inside
one `batched()` block, and `FusedJournal.record_boundary` whole.
Medians of REPS boundaries; prints one JSON line.

Run: python probes/probe_journal_split.py [ROOT] [LABEL]
"""

import json
import os
import shutil
import statistics
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(sys.argv[1]) if len(sys.argv) > 1 else os.path.dirname(HERE)
LABEL = sys.argv[2] if len(sys.argv) > 2 else "tree"
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from mpi_opt_tpu import LogUniform, SearchSpace, Uniform  # noqa: E402
from mpi_opt_tpu.ledger import FusedJournal, SweepLedger  # noqa: E402

N, REPS = 512, 7
# cifar10_cnn's space (workloads/vision.py), built here without its data
SPACE = SearchSpace(
    {
        "lr": LogUniform(1e-3, 1.0),
        "momentum": Uniform(0.5, 0.99),
        "weight_decay": LogUniform(1e-6, 1e-2),
        "flip_prob": Uniform(0.0, 0.5),
        "shift": Uniform(0.0, 4.0),
    }
)


def timed(fn):
    t = []
    for rep in range(REPS):
        t0 = time.perf_counter()
        fn(rep)
        t.append(time.perf_counter() - t0)
    return statistics.median(t)


def main():
    # the ledger lives on the checkout's disk, as the benchmark's does
    work = tempfile.mkdtemp(prefix="journal_split_", dir=ROOT)
    rng = np.random.default_rng(0)
    units = rng.random((N, SPACE.dim), dtype=np.float32)
    scores = rng.random(N)
    SPACE.materialize_row(units[0])  # the eager ops' first compiles
    out = {"label": LABEL, "n": N, "reps": REPS}
    out["decode_per_row_s"] = timed(lambda _: [SPACE.materialize_row(u) for u in units])
    if hasattr(SPACE, "materialize_rows"):
        out["decode_rows_s"] = timed(lambda _: SPACE.materialize_rows(units))
    params = [SPACE.canonical_params(SPACE.materialize_row(u)) for u in units]

    def ledger(name):
        led = SweepLedger(os.path.join(work, name), read_only=False)
        led.ensure_header({"mode": "fused", "granularity": "generation"})
        return led

    def append(led, b):
        for i in range(N):
            led.record_member(
                trial_id=b * N + i, member=i, boundary=b, boundary_size=N,
                canonical_params=params[i], score=scores[i], step=50,
            )

    def append_batched(led, b):
        with led.batched():
            append(led, b)

    try:
        for mode, fn in (("fsync_each", append), ("batched", append_batched)):
            led = ledger(mode + ".jsonl")
            out[f"append_{mode}_s"] = timed(lambda b: fn(led, b))
            led.close()
        led = ledger("record_boundary.jsonl")
        j = FusedJournal(led, SPACE)
        out["record_boundary_s"] = timed(
            lambda b: j.record_boundary(b, list(range(N)), units, scores, step=50)
        )
        led.close()
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
