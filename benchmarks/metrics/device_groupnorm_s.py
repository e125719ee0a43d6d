"""Device self time of GroupNorm in the traced generation, any phase: a
path component `gn*` / `GroupNorm*` / `PallasGN*` (and no convolution)."""

import scopes


def read(run):
    red = scopes.for_run(run)
    return red["class"]["groupnorm"] if red else None
