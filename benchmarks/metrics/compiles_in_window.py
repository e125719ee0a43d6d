"""`compile` spans (a cold compile or a cache load) that ended inside
the window. Has to read 0."""


def read(run):
    w = run.window
    if not run.spans or not w.closed:
        return None
    return float(
        sum(1 for s in run.spans if s.get("span") == "compile" and w.start < s["end"] <= w.end)
    )
