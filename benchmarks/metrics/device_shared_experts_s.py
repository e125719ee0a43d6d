"""Device self time under scope `shared_experts` in the traced
generation (the shared experts' three products, on every token):
forward, backward and evaluation together. Nothing where the program
opens no such scope."""

import scopes


def read(run):
    red = scopes.for_run(run)
    return red["scope"].get("shared_experts") if red else None
