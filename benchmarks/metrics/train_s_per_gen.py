"""Mean `train` span (dispatch to the curve fetch) in the window."""

from _window_spans import generations


def read(run):
    rows = [r["train_s"] for r in generations(run) if r["train_s"] is not None]
    return sum(rows) / len(rows) if rows else None
