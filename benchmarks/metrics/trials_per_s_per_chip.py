"""Member-generations journaled between the window's two stamps, over
the seconds between them and the chips (BASELINE.json's metric of
record). All the work and all the time of the window."""


def read(run):
    w = run.window
    if not w.closed or w.end <= w.start:
        return None
    first, last = 1, w.generations  # generation 0 is set-up's
    journaled = sum(len(run.ledger.get(g, {})) for g in range(first, last + 1))
    return journaled / (w.end - w.start) / run.chips
