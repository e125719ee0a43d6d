"""Record the device trace of one fused generation that test_scopes.py
reduces by the program's scopes.

Run on the chip (`chiprun -- python benchmarks/tests/record_scoped_trace.py`):
the `cifar10_cnn` configuration at its `rehearse` sizes (4 members in
chunks of 2, 2 steps a generation, 64 validation rows) through
`cli.main`, the second generation under the profiler as in a `--trace 1`
run. The trace is copied to `chiprun_out/record_scoped_trace/` with only
what `scopes.reduce` reads: the device planes (`/host:metadata` holds
the whole program and is most of the file), their lines whole, and of
each operation's metadata the head of its name, `tf_op` and
`hlo_category` (the full HLO text, shapes and source stacks are four
fifths of a device plane).
Prints the phase x class table of what it recorded.
"""

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

import scopes  # noqa: E402
import xplane  # noqa: E402


KEPT_STATS = ("tf_op", "hlo_category")  # what scopes.event_paths reads
NAME_CHARS = 64  # `%convert_reduce_fusion.72 = ...`: the head names the operation


def _varint(value: int) -> bytes:
    out = bytearray()
    while True:
        byte = value & 0x7F
        value >>= 7
        out.append(byte | (0x80 if value else 0))
        if not value:
            return bytes(out)


def _field(num: int, wire: int, value) -> bytes:
    if wire == 0:
        return _varint(num << 3) + _varint(value)
    if wire == 2:
        return _varint(num << 3 | 2) + _varint(len(value)) + bytes(value)
    return _varint(num << 3 | wire) + int(value).to_bytes(8 if wire == 1 else 4, "little")


def _slim_plane(plane) -> bytes:
    """One device XPlane with its event metadata cut to what the
    reduction reads: id, the head of the name, and `KEPT_STATS`. The
    lines (the events and their times) are kept whole."""
    stat_ids = set()
    for num, wire, value in scopes._fields(plane):
        if num == 5 and wire == 2:  # stat_metadata entry: key=1, value=2 (id=1, name=2)
            for n, w, v in scopes._fields(value):
                if n == 2 and w == 2:
                    f = {a: c for a, b, c in scopes._fields(v) if a in (1, 2)}
                    if scopes._text(f.get(2, b"")) in KEPT_STATS:
                        stat_ids.add(f.get(1, 0))
    out = bytearray()
    for num, wire, value in scopes._fields(plane):
        if num != 4 or wire != 2:
            out += _field(num, wire, value)
            continue
        entry = bytearray()
        for n, w, v in scopes._fields(value):
            if n != 2 or w != 2:
                entry += _field(n, w, v)
                continue
            meta = bytearray()
            for a, b, c in scopes._fields(v):  # XEventMetadata
                if a == 1:
                    meta += _field(a, b, c)
                elif a == 2 and b == 2:
                    meta += _field(a, b, scopes._text(c)[:NAME_CHARS].encode())
                elif a == 5 and b == 2:
                    ids = [y for x, _, y in scopes._fields(c) if x == 1]
                    if ids and ids[0] in stat_ids:
                        meta += _field(a, b, c)
            entry += _field(2, 2, meta)
        out += _field(4, 2, entry)
    return bytes(out)


def strip(src: str, dst: str) -> None:
    """Copy an `.xplane.pb` keeping the device planes, slimmed."""
    with open(src, "rb") as f:
        space = memoryview(f.read())
    kept = bytearray()
    for num, wire, value in scopes._fields(space):
        # XSpace: planes=1; errors, warnings, hostnames go
        if num == 1 and wire == 2 and scopes.plane_name(value).startswith(xplane.DEVICE_PREFIX):
            kept += _field(1, 2, _slim_plane(value))
    with open(dst, "wb") as f:
        f.write(kept)


def main() -> int:
    import jax

    from mpi_opt_tpu import cli
    from mpi_opt_tpu.workloads import get_workload

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    with open(os.path.join(BENCH, "configs", "cifar10_cnn.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", "pbt_pop512.json")) as f:
        traffic = json.load(f)["rehearse"]
    wl = get_workload(cfg["workload"])
    for k, v in cfg["rehearse"]["workload_attrs"].items():
        setattr(wl, k, v)
    out = os.path.join(ROOT, "chiprun_out", "record_scoped_trace")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    tdir = os.path.join(out, "trace")
    rc = cli.main(
        ["--workload", cfg["workload"], "--seed", "7", "--generations", "3"]
        + list(traffic["cli"])
        + ["--trace", "--metrics-file", os.path.join(out, "stream.jsonl")]
        + ["--profile-dir", tdir, "--profile-launches", "2:2"],
        _workload=wl,
    )
    if rc != 0:
        return rc
    pb = xplane.find_xplane(tdir)
    dst = os.path.join(out, "scoped.xplane.pb")
    strip(pb, dst)
    print(json.dumps({"recorded_bytes": os.path.getsize(pb), "kept_bytes": os.path.getsize(dst)}))
    shutil.rmtree(tdir)
    print(scopes.format_table(scopes.reduce(dst)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
