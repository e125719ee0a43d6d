"""Probe: wave-scheduled PBT beyond the single-chip residency envelope.

The round-3 envelope (PERF_NOTES "single-chip population envelope"):
pop=1024 SmallCNN is 4.5 GB of params+momentum and RESOURCE_EXHAUSTs at
warmup, while throughput is flat to pop=512. This probe (a) re-runs the
pop=1024 config WITH --wave-size so the population that could not run
at all completes on one chip, and (b) measures the staging overlap
efficiency: how much of the host<->device transfer time the
double-buffered background engine hid behind wave compute
(stage_overlap_s / stage_transfer_s; the un-hidden remainder is
stage_wait_s, paid at generation barriers).

An A/B at a resident-capable population (512, wave 256) also reports
the wave-mode overhead vs the resident scan — the cost of buying the
envelope.

Run: python probes/probe_wave.py [pop] [wave]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax


from mpi_opt_tpu.train.fused_pbt import fused_pbt  # noqa: E402
from mpi_opt_tpu.workloads import get_workload  # noqa: E402

pop = int(sys.argv[1]) if len(sys.argv) > 1 else 1024
wave = int(sys.argv[2]) if len(sys.argv) > 2 else 256

wl = get_workload("cifar10_cnn")
kw = dict(generations=2, steps_per_gen=100, seed=0, member_chunk=32)

# A: resident baseline at half the target (the biggest size that fits)
t0 = time.perf_counter()
res = fused_pbt(wl, population=min(pop, 512), **kw)
res_wall = time.perf_counter() - t0
print(
    f"resident pop={min(pop, 512)}: wall={res_wall:.1f}s "
    f"best={res['best_score']:.4f}",
    flush=True,
)

# B: wave-scheduled at the target population (beyond residency when
# pop=1024 on one chip)
t0 = time.perf_counter()
wav = fused_pbt(wl, population=pop, wave_size=wave, **kw)
wav_wall = time.perf_counter() - t0
xfer = wav["stage_transfer_s"]
hidden = wav["stage_overlap_s"]
eff = hidden / xfer if xfer > 0 else float("nan")
print(
    f"wave pop={pop} wave={wave} ({wav['n_waves']} waves): "
    f"wall={wav_wall:.1f}s best={wav['best_score']:.4f} "
    f"staged={wav['staged_bytes'] / 1e9:.2f} GB "
    f"transfer={xfer:.1f}s hidden={hidden:.1f}s wait={wav['stage_wait_s']:.1f}s "
    f"overlap_efficiency={eff:.2%}",
    flush=True,
)
ms = pop * kw["generations"] * kw["steps_per_gen"] / wav_wall
print(f"member-steps/s (wave): {ms:.0f}", flush=True)
