"""Model zoo (SURVEY.md §2 row 10): flax modules for the NN workloads.

All models follow TPU conventions: bfloat16 activations with float32
params and float32 logits/loss, channel-last layouts, GroupNorm instead
of BatchNorm (no mutable batch statistics — population members must be
pure pytrees so exploit/explore is a gather, and XLA fuses GN into the
surrounding ops). One member reads token rows: a decoder with learned
sparse attention and a held share of a mixture of experts
(sparse_moe_decoder.py; RMS norm, RoPE, grouped-query heads).
"""

from mpi_opt_tpu.models.mlp import MLP
from mpi_opt_tpu.models.cnn import SmallCNN
from mpi_opt_tpu.models.resnet import BasicBlock, ResNet, ResNet18
from mpi_opt_tpu.models.sparse_moe_decoder import DecoderDims, SparseMoEDecoder

__all__ = ["MLP", "SmallCNN", "BasicBlock", "ResNet", "ResNet18", "DecoderDims", "SparseMoEDecoder"]
