"""Device self time under scope `loss_head` in the traced generation
(the final norm, the head's logits and the cross-entropy): forward, backward and evaluation
together. Nothing where the program opens no such scope."""

import scopes


def read(run):
    red = scopes.for_run(run)
    return red["scope"].get("loss_head") if red else None
