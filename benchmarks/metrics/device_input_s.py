"""Device self time of the minibatch gather and the augmentation
(`train_input` + `augment`) in the traced generation."""

import scopes


def read(run):
    red = scopes.for_run(run)
    return red["phase"]["input"] if red else None
