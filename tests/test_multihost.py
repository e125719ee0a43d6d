"""Multi-process bring-up SUCCESS path (SURVEY.md §5 "multi-host").

``TestInitializeMultihost`` (test_parallel.py) pins the failure paths —
this file proves the success path this container CAN run: two real OS
processes (the stand-in for two TPU hosts), a localhost coordinator,
``initialize_multihost`` in each, a global ('pop','data') mesh spanning
both processes' devices, and a cross-process reduction whose result
agrees in both processes (gloo CPU collectives; on TPU hardware the
identical code rides ICI/DCN).

Subprocesses are unavoidable here: jax.distributed must initialize
before the XLA backend exists, and the pytest process's backend is
already up (and pinned to 8 virtual devices).
"""

import pytest

import socket
import subprocess
import sys

# In tier-1 since PR 21: the installed jax has cross-process collectives
# on the CPU backend (gloo), and the three tests take 3 + 20 + 16 s here.

_WORKER = r"""
import sys

import jax

# per-process platform pinning must happen BEFORE initialize_multihost
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)

from mpi_opt_tpu.parallel.mesh import make_mesh, initialize_multihost

pid, port = int(sys.argv[1]), sys.argv[2]
idx = initialize_multihost(
    coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid
)
assert idx == pid, (idx, pid)
assert jax.process_count() == 2, jax.process_count()

import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

# the global mesh spans BOTH processes' devices (4 = 2 procs x 2 local)
mesh = make_mesh(n_pop=2, n_data=2)
assert mesh.devices.size == 4
assert len(set(d.process_index for d in mesh.devices.flat)) == 2

x = jax.device_put(jnp.arange(8.0), NamedSharding(mesh, P(("pop", "data"))))
total = jax.jit(lambda a: jnp.sum(a), out_shardings=NamedSharding(mesh, P()))(x)
val = float(total.addressable_shards[0].data)
assert val == 28.0, val
print(f"RESULT {pid} {val}", flush=True)
"""


def _run_two_procs(worker_src: str, extra_args=(), timeout: int = 420) -> list[str]:
    """Spawn 2 SPMD worker ranks (argv: pid, coordinator port, *extra)
    and return their stdouts; kills stragglers on any failure so a hung
    rank can't outlive the test. Shared with test_multihost_families."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", worker_src, str(pid), str(port), *extra_args],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            cwd="/root/repo",
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=timeout)
            assert p.returncode == 0, f"worker failed:\n{out}\n{err}"
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    return outs


def test_two_process_bringup_and_global_psum():
    outs = _run_two_procs(_WORKER, timeout=240)
    for pid, out in enumerate(outs):
        assert f"RESULT {pid} 28.0" in out, out


# -- a REAL fused sweep across the process boundary ----------------------
#
# Bring-up + one psum is not a sweep (round-3 verdict item 1): config
# 5's v4-32 target is multi-HOST, where every process traces identical
# programs, the population shardings span processes, and the host-side
# ledger runs once per process. This worker runs a fused PBT sweep AND
# a fused SHA sweep (non-dividing first cohort -> replication fallback
# + rounded rungs) to completion on a global ('pop','data') mesh over
# 2 OS processes x 2 CPU devices, and prints the results; the test
# asserts both processes report the IDENTICAL best (the SPMD contract).

_SWEEP_WORKER = r"""
import sys

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
from mpi_opt_tpu.utils.compile_cache import wire_compile_cache
wire_compile_cache()

from mpi_opt_tpu.parallel.mesh import make_mesh, initialize_multihost

pid, port = int(sys.argv[1]), sys.argv[2]
initialize_multihost(
    coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid
)

import warnings

from mpi_opt_tpu.train.fused_pbt import fused_pbt
from mpi_opt_tpu.train.fused_asha import fused_sha
from mpi_opt_tpu.workloads import get_workload

mesh = make_mesh(n_pop=2, n_data=2)
assert len(set(d.process_index for d in mesh.devices.flat)) == 2

wl = get_workload("fashion_mlp", n_train=256, n_val=128)
wl.batch_size = 32

res = fused_pbt(
    wl, population=4, generations=2, steps_per_gen=2, seed=0, mesh=mesh
)
curve = ",".join(f"{v:.6f}" for v in res["best_curve"])
print(f"PBT {pid} {res['best_score']:.6f} [{curve}]", flush=True)

with warnings.catch_warnings():
    warnings.simplefilter("ignore")  # 5-cohort on 2-way axis replicates (by design here)
    sres = fused_sha(
        wl, n_trials=5, min_budget=1, max_budget=4, eta=2, seed=0, mesh=mesh
    )
print(f"SHA {pid} {sres['best_score']:.6f} {sres['best_trial']} "
      f"{sres['rung_sizes']}", flush=True)
"""


def test_two_process_fused_sweeps_agree():
    outs = _run_two_procs(_SWEEP_WORKER)
    pbt = [next(l for l in out.splitlines() if l.startswith("PBT")) for out in outs]
    sha = [next(l for l in out.splitlines() if l.startswith("SHA")) for out in outs]
    # identical best score, curve, winner, and rung plan in BOTH processes
    assert pbt[0].split(" ", 2)[2] == pbt[1].split(" ", 2)[2], pbt
    assert sha[0].split(" ", 2)[2] == sha[1].split(" ", 2)[2], sha


# -- checkpoint/resume across the process boundary -----------------------
#
# The failure-recovery story must survive multi-host too: a sweep
# sharded over a process-spanning mesh snapshots via fetch_global'd
# host copies + orbax's own multihost coordination, and a re-run with
# the same arguments replays from the final snapshot bit-identically in
# EVERY process.

_CKPT_WORKER = r"""
import sys

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
from mpi_opt_tpu.utils.compile_cache import wire_compile_cache
wire_compile_cache()

from mpi_opt_tpu.parallel.mesh import make_mesh, initialize_multihost

pid, port, ck = int(sys.argv[1]), sys.argv[2], sys.argv[3]
initialize_multihost(
    coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid
)

from mpi_opt_tpu.train.fused_pbt import fused_pbt
from mpi_opt_tpu.workloads import get_workload

mesh = make_mesh(n_pop=2, n_data=2)
wl = get_workload("fashion_mlp", n_train=256, n_val=128)
wl.batch_size = 32

kw = dict(population=4, generations=2, steps_per_gen=2, seed=0, mesh=mesh,
          gen_chunk=1, checkpoint_dir=ck)
res = fused_pbt(wl, **kw)
curve = ",".join(f"{v:.6f}" for v in res["best_curve"])
print(f"RUN1 {pid} {res['best_score']:.6f} [{curve}]", flush=True)
res2 = fused_pbt(wl, **kw)  # resumes from the final snapshot: pure replay
curve2 = ",".join(f"{v:.6f}" for v in res2["best_curve"])
print(f"RUN2 {pid} {res2['best_score']:.6f} [{curve2}]", flush=True)
"""


def test_two_process_checkpointed_sweep_replays(tmp_path):
    ck = str(tmp_path / "ck")
    outs = _run_two_procs(_CKPT_WORKER, extra_args=(ck,))
    lines = {}
    for out in outs:
        for l in out.splitlines():
            if l.startswith("RUN"):
                tag, pid, rest = l.split(" ", 2)
                lines[(tag, pid)] = rest
    # the checkpointed sweep and its replay agree, in BOTH processes
    assert lines[("RUN1", "0")] == lines[("RUN1", "1")], lines
    assert lines[("RUN2", "0")] == lines[("RUN2", "1")], lines
    assert lines[("RUN1", "0")] == lines[("RUN2", "0")], lines
