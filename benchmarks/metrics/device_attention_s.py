"""Device self time under scope `attention` in the traced generation
(the selected attention with its q/k/v/o projections, per-head norms and RoPE): forward, backward and evaluation
together. Nothing where the program opens no such scope."""

import scopes


def read(run):
    red = scopes.for_run(run)
    return red["scope"].get("attention") if red else None
