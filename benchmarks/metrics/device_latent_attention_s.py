"""Device self time under scope `latent_attention` in the traced
generation (the latent attention: Wq, Wkv_a, the latent's norm, Wkv_b,
RoPE, the causal attention, Wo): forward, backward and evaluation
together. Nothing where the program opens no such scope."""

import scopes


def read(run):
    red = scopes.for_run(run)
    return red["scope"].get("latent_attention") if red else None
