"""Seconds in `compile` spans (cold compiles and persistent-cache
loads alike) that ended before the window opened."""


def read(run):
    if not run.spans or run.window.start is None:
        return None
    return sum(
        s["dur_s"] for s in run.spans if s.get("span") == "compile" and s["end"] <= run.window.start
    )
