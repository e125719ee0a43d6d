"""Dataset registry. See package docstring for the no-network policy."""

from __future__ import annotations

import numpy as np

from mpi_opt_tpu.data.synthetic import make_image_classification
from mpi_opt_tpu.data.tokens import make_token_rows

_CACHE: dict = {}


def _sklearn_tabular(loader_name: str, seed: int = 0, val_frac: float = 0.25):
    from sklearn import datasets as skd
    from sklearn.model_selection import train_test_split

    d = getattr(skd, loader_name)()
    x = np.asarray(d.data, dtype=np.float32)
    x = (x - x.mean(0)) / (x.std(0) + 1e-8)
    y = np.asarray(d.target)
    classification = y.dtype.kind in "iu"
    y = y.astype(np.int32) if classification else y.astype(np.float32)
    xtr, xva, ytr, yva = train_test_split(
        x, y, test_size=val_frac, random_state=seed,
        stratify=y if classification else None,
    )
    return {
        "train_x": xtr,
        "train_y": ytr,
        "val_x": xva,
        "val_y": yva,
        "n_classes": int(y.max()) + 1 if classification else 0,
    }


def _digits_images(seed: int = 0):
    """sklearn digits reshaped to [n, 8, 8, 1] images."""
    d = _sklearn_tabular("load_digits", seed)
    for k in ("train_x", "val_x"):
        d[k] = d[k].reshape(-1, 8, 8, 1)
    return d


DATASETS = {
    # real offline data
    "digits": lambda seed=0: _sklearn_tabular("load_digits", seed),
    "digits_image": _digits_images,
    "wine": lambda seed=0: _sklearn_tabular("load_wine", seed),
    "breast_cancer": lambda seed=0: _sklearn_tabular("load_breast_cancer", seed),
    "diabetes": lambda seed=0: _sklearn_tabular("load_diabetes", seed),  # regression
    # synthetic stand-ins, original shapes (no network in this container)
    "fashion_mnist": lambda seed=0, n_train=16384, n_val=2048, **kw: make_image_classification(
        n_train, n_val, 28, 28, 1, 10, seed=seed, **kw
    ),
    # cifar10 difficulty calibrated AT BENCH SCALE on the real chip
    # (2026-07-29: pop=32, batch 256, 8x100 steps, random hparams):
    # best-of-pop climbs 0.17 -> 0.69 across generations and keeps
    # rising — so config 3's metric of record (wall-clock to target
    # val-acc) discriminates instead of saturating at 1.0 in one
    # generation, which is what the old defaults (delta=0.2, noise=1.5,
    # protos=4, coarse=4) did.
    "cifar10": lambda seed=0, n_train=16384, n_val=2048, **kw: make_image_classification(
        n_train, n_val, 32, 32, 3, 10, seed=seed,
        **{"delta": 0.1, "noise": 2.0, "protos": 16, "coarse": 8, **kw}
    ),
    # label_noise=0.35: irreducible-error ceiling 1 - p + p/K = 0.6535,
    # so config-5's val-acc curve plateaus ~0.65 instead of memorizing
    # to 0.999 (round-3 verdict weak #3) and a 0.5 target sits mid-curve
    "cifar100": lambda seed=0, n_train=16384, n_val=2048, **kw: make_image_classification(
        n_train, n_val, 32, 32, 3, 100, seed=seed,
        **{"coarse": 6, "noise": 1.2, "delta": 0.3, "label_noise": 0.35, **kw}
    ),
    # token rows for next-token workloads (x = a row, y = its next tokens)
    "successor_tokens": lambda seed=0, n_train=512, n_val=8, positions=8192, vocab=18992: make_token_rows(
        n_train, n_val, positions, vocab, seed=seed
    ),
}


def load_dataset(name: str, **kwargs):
    key = (name, tuple(sorted(kwargs.items())))
    if key not in _CACHE:
        try:
            fn = DATASETS[name]
        except KeyError:
            raise ValueError(f"unknown dataset {name!r}; available: {sorted(DATASETS)}") from None
        _CACHE[key] = fn(**kwargs)
    return _CACHE[key]
