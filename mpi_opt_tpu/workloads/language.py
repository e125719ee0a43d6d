"""Next-token workloads: a population member that reads token rows.

The member is a decoder with one chip's share of a mixture of experts:
learned sparse attention (models/sparse_moe_decoder.py, Keye-VL-2.0's
block) or latent attention with shared experts and a leading dense
layer (models/latent_moe_decoder.py, Kimi-VL-A3B's), trained on seeded
token rows (data/tokens.py) and scored on a held-out loss. The
population protocol is ``PopulationWorkload``'s; what differs from the
image classifiers is the member handed to the trainer
(``DecoderMember``: its loss, its score, one validation row a chunk) and
the search space, which holds the optimizer's three hyperparameters
only (there is no augmentation to tune).
"""

from __future__ import annotations

import dataclasses

from mpi_opt_tpu.space import LogUniform, SearchSpace, Uniform
from mpi_opt_tpu.workloads import register
from mpi_opt_tpu.workloads.base import PopulationWorkload, resolve_momentum_dtype


class DecoderMember:
    """One decoder member for the trainer (the names of
    ``train.population.ClassifierMember``). A minibatch is ``[rows, T]``
    tokens with their next tokens; the loss is the mean next-token
    cross-entropy plus every layer's indexer loss, with the step's
    ``counters`` beside it; the score is minus
    the mean held-out cross-entropy. Rows are walked one at a time:
    a row's activations are all a chip has room for."""

    eval_chunk = 1  # validation rows a chunk: one row of T positions
    single_unbatched = True  # the expert layer branches on its own load
    # what a step's forward pass counted, beside its loss (span ``train``):
    # keys a query attends to under the selection (mean over layers),
    # tokens routed to the held experts a layer (mean over layers), the
    # most any one held expert was sent in any layer, and the MiB of
    # values the step saves by name from its forward to its backward
    # (the model's ``saved_for_backward``; from the shapes, at trace time)
    counters = ("selected_keys", "routed_tokens", "fullest_expert_tokens", "saved_residual_mib")

    def __init__(self, dims, positions: int):
        import jax.numpy as jnp

        from mpi_opt_tpu.models.sparse_moe_decoder import SparseMoEDecoder

        self.score_dtype = jnp.float32
        self.positions = positions
        self._train = SparseMoEDecoder(dims, index_loss=True)
        self._eval = SparseMoEDecoder(dims, index_loss=False)

    def init(self, rng, sample_x):
        # parameter shapes do not depend on the row's length: a short
        # row keeps the traced forward pass of the init program small
        row = sample_x[0, :16]
        return self._train.init(rng, row, row)["params"]

    def _rows(self, model, params, x, y):
        """(cross-entropy summed over positions, indexer loss, counts),
        each with the rows on the leading axis."""
        import jax

        one = lambda row: model.apply({"params": params}, row[0], row[1])
        if x.shape[0] == 1:
            return jax.tree.map(lambda a: a[None], one((x[0], y[0])))
        return jax.lax.map(one, (x, y))

    def loss(self, params, hp, key, bx, by):
        import jax.numpy as jnp

        # c float32 [rows, layers, (selected keys, routed tokens, fullest expert's, bytes saved)]
        ce, index_loss, c = self._rows(self._train, params, bx, by)
        counters = jnp.stack(
            [jnp.mean(c[..., 0]) / bx.shape[1], jnp.mean(c[..., 1]), jnp.max(c[..., 2]), jnp.sum(c[..., 3]) / 2**20]
        )
        return jnp.mean(ce) / bx.shape[1] + jnp.mean(index_loss), counters

    def score_sum(self, params, cx, cy):
        import jax.numpy as jnp

        # a padded row has targets < 0; validation rows divide into
        # chunks of one, so none is ever formed
        ce = self._rows(self._eval, params, cx, jnp.maximum(cy, 0))[0]
        return jnp.sum(jnp.where(cy[:, 0] >= 0, ce, 0.0))

    def score(self, total, n_val: int):
        return -total / (n_val * self.positions)


class LatentDecoderMember(DecoderMember):
    """The decoder with latent attention and shared experts
    (``LatentMoEDecoder``) for the trainer: the loss is the mean
    next-token cross-entropy plus every expert layer's weighted balance
    loss; its counters are the tokens routed to the held experts a layer
    (mean over the expert layers), the most any one held expert was sent
    in any layer, and the MiB the step saves by name from its forward to
    its backward."""

    counters = ("routed_tokens", "fullest_expert_tokens", "saved_residual_mib")

    def __init__(self, dims, positions: int):
        import jax.numpy as jnp

        from mpi_opt_tpu.models.latent_moe_decoder import LatentMoEDecoder

        self.score_dtype = jnp.float32
        self.positions = positions
        self._train = self._eval = LatentMoEDecoder(dims)

    def loss(self, params, hp, key, bx, by):
        import jax.numpy as jnp

        # c float32 [rows, expert layers, (routed tokens, fullest expert's)]
        ce, balance, c, saved = self._rows(self._train, params, bx, by)
        counters = jnp.stack([jnp.mean(c[..., 0]), jnp.max(c[..., 1]), jnp.sum(saved) / 2**20])
        return jnp.mean(ce) / bx.shape[1] + jnp.mean(balance), counters


@register
class KeyeVL2Decoder(PopulationWorkload):
    """Keye-VL-2.0-30B-A3B's decoder block at its published widths, one
    chip's share: 4 of its 48 layers, 8 of each layer's 128 routed
    experts (the router stays 128 wide, top 8), 1/8 of the vocabulary,
    rows of 8192 tokens. ``dims`` and the data sizes are plain
    attributes, so a test or a rehearsal runs the same code tiny."""

    name = "keye_vl2_30b_a3b"
    member_cls = DecoderMember
    dataset = "successor_tokens"
    batch_size = 1
    augment = False
    default_n_train = 512
    default_n_val = 8

    def __init__(self, n_train=None, n_val=None, positions: int = 8192, dims=None):
        from mpi_opt_tpu.models.sparse_moe_decoder import DecoderDims

        super().__init__(n_train=n_train, n_val=n_val)
        self.positions = positions
        self.dims = dims if dims is not None else dataclasses.asdict(DecoderDims())

    def default_space(self) -> SearchSpace:
        return SearchSpace(
            {
                "lr": LogUniform(1e-3, 1e-1),
                "momentum": Uniform(0.5, 0.99),
                "weight_decay": LogUniform(1e-6, 1e-2),
            }
        )

    def _dims(self):
        from mpi_opt_tpu.models.sparse_moe_decoder import DecoderDims

        return DecoderDims(**self.dims)

    def data(self) -> dict:
        if self._data is None:
            from mpi_opt_tpu.data import load_dataset

            self._data = load_dataset(
                self.dataset, n_train=self.n_train, n_val=self.n_val,
                positions=self.positions, vocab=self._dims().vocab,
            )
        return self._data

    def make_trainer(self, member_chunk: int = 0, donate: bool = True, mesh=None, momentum_dtype=None):
        import jax.numpy as jnp

        from mpi_opt_tpu.train import PopulationTrainer

        member = self.member_cls(self._dims(), self.positions)
        if momentum_dtype is None:
            momentum_dtype = resolve_momentum_dtype()
        return PopulationTrainer(
            apply_fn=None,
            init_fn=member.init,
            batch_size=self.batch_size,
            augment=False,
            member_chunk=member_chunk,
            donate=donate,
            mesh=mesh,
            momentum_dtype=jnp.dtype(momentum_dtype) if momentum_dtype else None,
            member=member,
        )


@register
class KimiVLDecoder(KeyeVL2Decoder):
    """Kimi-VL-A3B's language model at its published widths, one chip's
    share: the leading dense layer and 4 of its 26 expert layers, 8 of
    each expert layer's 64 routed experts (the router stays 64 wide,
    top 6) beside the two shared experts, 1/8 of the vocabulary, rows of
    8192 tokens. ``dims`` and the data sizes are plain attributes, as
    the Keye member's."""

    name = "kimi_vl_a3b"
    member_cls = LatentDecoderMember

    def __init__(self, n_train=None, n_val=None, positions: int = 8192, dims=None):
        from mpi_opt_tpu.models.latent_moe_decoder import LatentDims

        dims = dims if dims is not None else dataclasses.asdict(LatentDims())
        super().__init__(n_train=n_train, n_val=n_val, positions=positions, dims=dims)

    def _dims(self):
        from mpi_opt_tpu.models.latent_moe_decoder import LatentDims

        return LatentDims(**self.dims)

    def segment_flops(self, population: int, steps: int) -> float:
        """FLOPs of one generation (``steps`` train steps and one
        evaluation of every member) from the layer equations
        (``latent_moe_decoder.forward_macs``): a train step three times
        a forward pass, recomputation not counted. It stands in for the
        traced sweep's probe, which would lower the member's programs a
        second time to count them."""
        from mpi_opt_tpu.models.latent_moe_decoder import forward_macs

        row = 2.0 * forward_macs(self._dims(), self.positions)
        return population * row * (3 * steps * self.batch_size + self.n_val)
