"""Boundary-to-boundary period minus that generation's `train` span,
mean over the window: journal, fetches, boundary service, host loop."""

from _window_spans import generations


def read(run):
    rows = [r for r in generations(run) if r["train_s"] is not None]
    if not rows:
        return None
    return sum(r["period_s"] - r["train_s"] for r in rows) / len(rows)
