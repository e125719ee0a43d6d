"""Device self time under scope `experts` in the traced generation
(the held experts' products): forward, backward and evaluation
together. Nothing where the program opens no such scope."""

import scopes


def read(run):
    red = scopes.for_run(run)
    return red["scope"].get("experts") if red else None
