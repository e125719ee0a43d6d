"""Record the small device trace that test_xplane.py reduces.

Run on the chip (`chiprun -- python benchmarks/tests/record_trace.py`):
three calls of a small jitted conv program under `jax.profiler`, each
inside a `TraceAnnotation`, with a host sleep between them so the trace
has idle gaps of known cause. Writes the `.xplane.pb` and a JSON dump of
its structure under `chiprun_out/record_trace/`.
"""

import glob
import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main() -> int:
    out = os.path.join("chiprun_out", "record_trace")
    os.makedirs(out, exist_ok=True)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1

    @jax.jit
    def step(x, w):
        y = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
        )
        return jnp.tanh(y).astype(jnp.bfloat16)

    x = jnp.ones((64, 32, 32, 64), jnp.bfloat16)
    w = jnp.ones((3, 3, 64, 64), jnp.bfloat16) * 0.01
    step(x, w).block_until_ready()
    tdir = os.path.join(out, "trace")
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(tdir)
    for i in range(3):
        with jax.profiler.TraceAnnotation("train"):
            y = x
            for _ in range(4):
                y = step(y, w)
            y.block_until_ready()
        with jax.profiler.TraceAnnotation("journal"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(tdir, "plugins", "profile", "*", "*.xplane.pb"))[0]
    shutil.copy(pb, os.path.join(out, "small.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(pb)
    dump = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append(
                {
                    "name": line.name,
                    "n": len(evs),
                    "first": [
                        {
                            "name": e.name,
                            "start_ns": e.start_ns,
                            "duration_ns": e.duration_ns,
                            "stats": {k: str(v)[:80] for k, v in list(e.stats)[:12]},
                        }
                        for e in evs[:6]
                    ],
                }
            )
        dump.append({"plane": plane.name, "lines": lines})
    with open(os.path.join(out, "structure.json"), "w") as f:
        json.dump(dump, f, indent=1)
    print(json.dumps({"size": os.path.getsize(pb), "kind": dev.device_kind}))
    print(json.dumps(dev.memory_stats()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
