"""Device self time of the traced generation's backward pass: operations
under `transpose(...member_loss...)`, JAX's own name for it."""

import scopes


def read(run):
    red = scopes.for_run(run)
    return red["phase"]["backward"] if red else None
