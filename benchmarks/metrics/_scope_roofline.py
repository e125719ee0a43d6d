"""A device scope's share of its roofline: the least time the chip
could take for the work of the layer-table entries that name the scope
(`"scope"` on an entry of the configuration's `layers`) over the
generation that was traced — every member's train steps, forward and
backward, and its evaluation's forward pass, from `work.py`'s functions
over those entries alone — against the scope's device seconds. The
least time is the larger of FLOPs over peak FLOP/s and least bytes over
peak bytes/s. The table counts the least work (the keys a query reads
under the selection, the expected tokens an expert), so a program that
computes more reads a lower share."""

import scopes


def read(run, scope: str):
    red = scopes.for_run(run)
    seconds = red["scope"].get(scope) if red else None
    entries = [l for l in run.cfg["layers"] if l.get("scope") == scope]
    if not seconds or not entries or run.peaks is None:
        return None
    flops, nbytes = run.work.generation_work(dict(run.cfg, layers=entries), run.population, run.steps)
    least = max(flops / run.peaks["flops_per_s"], nbytes / run.peaks["bytes_per_s"])
    return 100.0 * least / (seconds * run.chips)
