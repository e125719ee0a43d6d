"""Device self time under scope `router` in the traced generation
(the router: its product, the top experts a token, dispatch and combine): forward, backward and evaluation
together. Nothing where the program opens no such scope."""

import scopes


def read(run):
    red = scopes.for_run(run)
    return red["scope"].get("router") if red else None
