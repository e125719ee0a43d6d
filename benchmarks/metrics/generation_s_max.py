"""The longest boundary-to-boundary period in the window."""

from _window_spans import generations


def read(run):
    rows = generations(run)
    return max(r["period_s"] for r in rows) if rows else None
