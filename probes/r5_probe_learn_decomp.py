import sys, time, shutil
sys.path.insert(0, "/root/repo")
import jax
from mpi_opt_tpu.workloads.vision import Cifar100ResNet18
from mpi_opt_tpu.train.fused_pbt import fused_pbt

wl = Cifar100ResNet18()
# warm the launch program (uncheckpointed 1-gen)
t0 = time.perf_counter()
fused_pbt(wl, population=64, generations=1, steps_per_gen=50, seed=0,
          member_chunk=8, gen_chunk=1, snapshot_last=False)
print(f"warm 1-gen {time.perf_counter()-t0:.1f}s", flush=True)

ckpt = "/tmp/probe_learn_ck"
shutil.rmtree(ckpt, ignore_errors=True)
t0 = time.perf_counter()
res = fused_pbt(wl, population=64, generations=4, steps_per_gen=50, seed=0,
                member_chunk=8, gen_chunk=1, checkpoint_dir=ckpt,
                snapshot_every=2, snapshot_last=False)
wall = time.perf_counter() - t0
print(f"4-gen checkpointed sweep: {wall:.1f}s  launch_walls={['%.1f' % w for w in res['launch_walls']]}", flush=True)
shutil.rmtree(ckpt, ignore_errors=True)
