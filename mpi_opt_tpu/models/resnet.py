"""ResNet-18 — the BASELINE config-5 model (PBT pop=1024, CIFAR-100).

CIFAR-style ResNet (3x3 stem, no max-pool, 4 stages of basic blocks),
following the models-package conventions: GroupNorm (stateless members;
exploit/explore stays a pure gather), bf16 compute with f32 params and
f32 logits, channel-last.

Population memory math (why config 5 is a multi-chip/chunked config):
full ResNet-18 is ~11.2M params. Per member, params + SGD momentum in
f32 = ~90 MB; pop=1024 of those is ~92 GB — an order of magnitude over
one v5e chip's 16 GB HBM, which is why BASELINE.json puts config 5 on a
v4-32 (32 chips). On a mesh the population axis shards it: 1024/32
members per chip = ~2.9 GB resident, comfortable. Single-chip runs cap
the population (~128 members = 11.5 GB resident) and bound *activation*
memory with ``member_chunk`` (the trainer lax.map's members in chunks).
``remat`` rematerializes block activations in the backward pass
(activations drop from every conv output to block boundaries, ~8x, for
~33% more FLOPs). Round-5 measurement: at the measured single-chip
envelope (pop=64, member_chunk=8, batch 128) the stored-backward
activations FIT, and remat=False is 18% faster per segment — so remat
is a knob for heavier per-chip loads, not the default (PERF_NOTES
round 5).

What one v5e chip holds (compiled for a described v5e, PR 21 — a
compile, not a run): the pop=32 train segment (batch 128) needs
8.6 GiB of temporaries at member_chunk=8 and 10.2 GiB at
member_chunk=32 or unchunked, on top of 2.9 GiB of donated state, so
all three fit 16 GB. Without donation the old and the new population
state are resident at once (2 x 5.75 GB at pop=64, before
activations). The previous installation ran pop=64 at member_chunk=8
and could not compile pop>=96 (not re-measured).
"""

from __future__ import annotations

from typing import Sequence

import flax.linen as nn
import jax.numpy as jnp


class PallasGN(nn.Module):
    """GroupNorm(+optional fused ReLU) through the Pallas kernel
    (ops/pallas_gn.py). Param names/shapes match ``nn.GroupNorm``
    (``scale``/``bias``), so the two variants' population states are
    interchangeable; stats run in f32 either way."""

    num_groups: int
    relu: bool = False
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        from mpi_opt_tpu.ops.pallas_gn import group_norm_relu

        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        return group_norm_relu(
            x.astype(self.dtype), scale, bias, self.num_groups, 1e-6, self.relu
        )


class BasicBlock(nn.Module):
    """Two 3x3 convs + identity/projection shortcut."""

    channels: int
    stride: int = 1
    dtype: jnp.dtype = jnp.bfloat16
    pallas_gn: bool = False

    @nn.compact
    def __call__(self, x):
        # 32 groups at full width; small test widths shrink the count
        groups = min(32, self.channels)
        if self.pallas_gn:
            gn = lambda name, relu=False: PallasGN(
                num_groups=groups, relu=relu, dtype=self.dtype, name=name
            )
            gn_relu = lambda name: gn(name, relu=True)
        else:
            gn = lambda name: nn.GroupNorm(
                num_groups=groups, dtype=self.dtype, name=name
            )
            gn_relu = lambda name: (lambda v: nn.relu(gn(name)(v)))
        y = nn.Conv(
            self.channels, (3, 3), strides=(self.stride, self.stride),
            padding="SAME", use_bias=False, dtype=self.dtype, name="conv1",
        )(x)
        y = gn_relu("gn1")(y)
        y = nn.Conv(
            self.channels, (3, 3), padding="SAME", use_bias=False,
            dtype=self.dtype, name="conv2",
        )(y)
        y = gn("gn2")(y)
        if x.shape[-1] != self.channels or self.stride != 1:
            x = nn.Conv(
                self.channels, (1, 1), strides=(self.stride, self.stride),
                use_bias=False, dtype=self.dtype, name="proj",
            )(x)
            x = gn("gn_proj")(x)
        return nn.relu(x + y)


class ResNet(nn.Module):
    """CIFAR-style ResNet; ResNet-18 = stage_sizes (2, 2, 2, 2).

    ``width`` scales all stage channels (64*width at the stem); tests use
    small widths/stages for CPU speed without changing program structure.
    """

    n_classes: int = 100
    stage_sizes: Sequence[int] = (2, 2, 2, 2)
    width: int = 64
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool = False
    pallas_gn: bool = False

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype)
        x = nn.Conv(
            self.width, (3, 3), padding="SAME", use_bias=False,
            dtype=self.dtype, name="stem",
        )(x)
        if self.pallas_gn:
            x = PallasGN(
                num_groups=min(32, self.width), relu=True, dtype=self.dtype,
                name="gn_stem",
            )(x)
        else:
            x = nn.relu(
                nn.GroupNorm(num_groups=min(32, self.width), dtype=self.dtype, name="gn_stem")(x)
            )
        block_cls = nn.remat(BasicBlock) if self.remat else BasicBlock
        for stage, n_blocks in enumerate(self.stage_sizes):
            channels = self.width * (2**stage)
            for b in range(n_blocks):
                stride = 2 if stage > 0 and b == 0 else 1
                x = block_cls(
                    channels=channels, stride=stride, dtype=self.dtype,
                    pallas_gn=self.pallas_gn, name=f"stage{stage}_block{b}",
                )(x)
        x = jnp.mean(x, axis=(1, 2))  # global average pool
        x = nn.Dense(self.n_classes, dtype=self.dtype, name="head")(x)
        return x.astype(jnp.float32)


def ResNet18(
    n_classes: int = 100, width: int = 64, remat: bool = False,
    pallas_gn: bool = False,
) -> ResNet:
    return ResNet(
        n_classes=n_classes, stage_sizes=(2, 2, 2, 2), width=width, remat=remat,
        pallas_gn=pallas_gn,
    )
