"""Device self time under `eval_population` in the traced generation."""

import scopes


def read(run):
    red = scopes.for_run(run)
    return red["phase"]["eval"] if red else None
