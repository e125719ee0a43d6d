"""Set-up seconds under no span of the program: process start to the
window's opening, minus the union of the program's spans clipped to it.
(The harness's own state capture at the first boundary stays in it: it
is not the program's.)"""

import xplane


def read(run):
    lo, hi = run.t0, run.window.start
    if not run.spans or hi is None:
        return None
    clipped = [(max(s["start"], lo), min(s["end"], hi)) for s in run.spans]
    covered = sum(e - s for s, e in xplane._union([iv for iv in clipped if iv[1] > iv[0]]))
    return (hi - lo) - covered
