"""The whole step's share of the chip's peak: forward+backward FLOPs a
member-step from the configuration's layer table, times the
member-steps of the window's generations, over their seconds x chips x
peak FLOP/s. Evaluation and recomputation are not counted as work. (In
a traced run the generation in whose period the profiler writes its
trace is left out, as in the other per-generation readers.)"""

from _window_spans import generations


def read(run):
    rows = generations(run)
    if not rows or run.peaks is None:
        return None
    members = sum(len(run.ledger.get(r["generation"], {})) for r in rows)
    seconds = sum(r["period_s"] for r in rows)
    flops = run.work.member_step_flops(run.cfg) * members * run.steps
    return 100.0 * flops / (seconds * run.chips * run.peaks["flops_per_s"])
