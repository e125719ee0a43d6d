"""Attention over a selection that is only known at run time, as TPU
kernels: JAX's splash attention (``jax.experimental.pallas.ops.tpu.
splash_attention``) driven by a dynamic mask.

The learned sparse attention of models/sparse_moe_decoder.py keeps, for
every query, the keys its index scorer ranks highest: a boolean
``[T, T]`` mask computed by the program itself, one for all heads. XLA
alone has to write every head's ``[T, T]`` float32 scores to HBM and
read them back several times (forward, the recompute, backward); the
kernels keep a tile of scores on the core, skip the blocks the mask
leaves empty (the causal upper half is never touched) and store only
each row's log-sum-exp. That log-sum-exp is also what the indexer's
loss needs to rebuild the attention probabilities, so it is returned.

The forward kernel runs once a train step: its context and log-sum-exp
are all the backward kernels need of it, and both carry the name
``RESIDUALS`` (``jax.ad_checkpoint.checkpoint_name``), so that a caller
that rematerialises around this call saves them by a
``save_only_these_names`` policy and its recompute holds no forward
kernel.

The kernels are the library's own (forward, dq, dkv, grouped-query
heads, softmax in float32); this module only builds the mask
information inside the traced program, shares one mask by all heads (a
mask with a head axis of one is the library's own convention for that),
and ties forward and backward together so that the log-sum-exp is an
output. ``causal_attention`` is the same kernels under a static causal
mask, for a decoder that attends to every earlier key
(models/latent_moe_decoder.py): the library's own kernel object, whose
value width may differ from the query/key width. ``_INTERPRET`` runs
the kernels in the Pallas interpreter (the CPU tests flip it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as sk
from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as ml
from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask_info as mi

_INTERPRET = False  # tests flip this for CPU interpret-mode runs

LANES = 128
# the forward kernel's context and log-sum-exp, for a remat policy to save
RESIDUALS = "selected_attention_residuals"


def supported(positions: int, head_dim: int) -> bool:
    """Whether the kernels take these shapes (whole 128-lane tiles)."""
    return positions % LANES == 0 and head_dim % LANES == 0


def _block_sizes(positions: int, block: int) -> sk.BlockSizes:
    b = min(block, positions)
    return sk.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
        block_q_dq=b, block_kv_dq=b,
    )


def _mask_infos(mask, bs: sk.BlockSizes):
    """The forward, dq and dkv kernels' views of one ``[T, T]`` mask
    (block occupancy in scalar memory, the partial blocks' bits)."""
    one_head = mask[None]
    fwd, _ = mi.process_dynamic_mask(one_head, (bs.block_q, bs.block_kv))
    dq, _ = mi.process_dynamic_mask(one_head, (bs.block_q_dq, bs.block_kv_dq))
    dkv, _ = mi.process_dynamic_mask_dkv(one_head, (bs.block_q_dkv, bs.block_kv_dkv))
    return fwd, dq, dkv


def _kernel_args(bs: sk.BlockSizes) -> dict:
    return dict(
        mask_value=sk.DEFAULT_MASK_VALUE, is_mqa=False, block_sizes=bs,
        residual_checkpoint_name=RESIDUALS, mask_function=None,
        attn_logits_soft_cap=None, interpret=_INTERPRET,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def masked_attention(q, k, v, mask, block: int = 512):
    """Softmax attention of ``q [H, T, D]`` (already scaled) over the
    keys ``mask [T, T]`` allows, grouped-query (``k``, ``v`` ``[Hkv, T,
    D]``, head ``h`` reads ``h // (H / Hkv)``): (context ``[H, T, D]``,
    log-sum-exp of the allowed scores ``[H, T]`` float32). Every row of
    the mask must allow a key. Differentiable in ``q``, ``k``, ``v``
    through the context; the log-sum-exp carries no gradient."""
    return _forward(q, k, v, mask, block)[0]


def _forward(q, k, v, mask, block):
    bs = _block_sizes(q.shape[1], block)
    fwd, dq, dkv = _mask_infos(mask, bs)
    fwd = _collapse(fwd)
    out, (lse,) = sk._splash_attention_forward(
        fwd, q, k, v, None, None, save_residuals=True, **_kernel_args(bs)
    )
    return (out, lse), (q, k, v, out, lse, _collapse(dq), _collapse(dkv), mask)


def _collapse(info):
    """``partial_mask_blocks`` as the kernels index it: one leading axis."""
    blocks = info.partial_mask_blocks
    return info._replace(partial_mask_blocks=blocks.reshape(-1, *blocks.shape[-2:]))


def _backward(block, res, cts):
    q, k, v, out, lse, dq_info, dkv_info, mask = res
    d_out, _ = cts  # the log-sum-exp's cotangent is not used (see masked_attention)
    bs = _block_sizes(q.shape[1], block)
    args = _kernel_args(bs)
    grads = sk._splash_attention_bwd(
        False, args["mask_value"], False, bs, None, None, None, _INTERPRET,
        (q, k, v, None, None, out, lse, dq_info, dkv_info), d_out,
    )
    return grads[3], grads[4], grads[5], np.zeros(mask.shape, jax.dtypes.float0)


masked_attention.defvjp(_forward, _backward)


def causal_attention(q, k, v, block: int = 512):
    """Causal softmax attention of ``q [H, T, Dqk]`` (already scaled)
    over ``k [H, T, Dqk]``, ``v [H, T, Dv]``, one key head a query head:
    context ``[H, T, Dv]``. The mask is static (each query reads the
    keys at and before its own position), so the library's own kernel
    object is built from it at trace time: blocks above the diagonal
    are skipped, those on it masked inside the kernel. The value width
    may differ from the query/key width. Differentiable in ``q``,
    ``k``, ``v``; the forward kernel's context and log-sum-exp carry
    the name ``RESIDUALS``, as ``masked_attention``'s do."""
    heads, t, _ = q.shape
    mask = ml.MultiHeadMask([ml.CausalMask((t, t))] * heads)
    kernel = sk.make_splash_mha(
        mask, block_sizes=_block_sizes(t, block), head_shards=1, q_seq_shards=1,
        residual_checkpoint_name=RESIDUALS, interpret=_INTERPRET,
    )
    return kernel(q, k, v)
