"""The traced generation's XLA kernels against the roofline: the
least time the chip could take for the generation's work (the larger
of FLOPs over peak FLOP/s and least bytes over peak bytes/s, both from
the layer table) over the seconds the device was busy in the trace."""


def read(run):
    t = run.trace
    if not t or run.peaks is None or t["busy_s"] <= 0:
        return None
    flops, nbytes = run.work.generation_work(run.cfg, run.population, run.steps)
    least = max(flops / run.peaks["flops_per_s"], nbytes / run.peaks["bytes_per_s"])
    return 100.0 * least / (t["busy_s"] * run.chips)
