"""Device self time of the traced generation's forward pass: operations
under `member_loss` that JAX did not transpose, `augment` apart."""

import scopes


def read(run):
    red = scopes.for_run(run)
    return red["phase"]["forward"] if red else None
