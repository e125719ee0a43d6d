"""Device self time under scope `dense_mlp` in the traced generation
(the leading dense layer's SwiGLU, on every token): forward, backward
and evaluation together. Nothing where the program opens no such
scope."""

import scopes


def read(run):
    red = scopes.for_run(run)
    return red["scope"].get("dense_mlp") if red else None
