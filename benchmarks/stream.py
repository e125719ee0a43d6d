"""Loading the program's span stream (`--trace --metrics-file`).

A copy of what the harness needs of `mpi_opt_tpu/obs/report.py`'s
stream loading: one JSON object a line, `event == "span"` records with
`span`, `dur_s`, `ts` (the END of the span, epoch seconds) and the
span's own attributes. A torn last line is skipped.
"""

from __future__ import annotations

import json


def load_spans(path: str) -> list:
    spans = []
    try:
        f = open(path)
    except OSError:
        return spans
    with f:
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("event") == "span" and "ts" in rec and "dur_s" in rec:
                rec["end"] = float(rec["ts"])
                rec["start"] = rec["end"] - float(rec["dur_s"])
                spans.append(rec)
    return spans


def named(spans: list, name: str) -> list:
    return [s for s in spans if s.get("span") == name]
