"""Device self time under `optimizer_update` (SGD, momentum, weight
decay) in the traced generation."""

import scopes


def read(run):
    red = scopes.for_run(run)
    return red["phase"]["optimizer"] if red else None
