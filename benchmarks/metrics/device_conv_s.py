"""Device self time of convolutions in the traced generation, any phase:
`hlo_category` convolution, or a path ending in `conv_general_dilated`."""

import scopes


def read(run):
    red = scopes.for_run(run)
    return red["class"]["conv"] if red else None
