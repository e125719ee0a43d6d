"""Data loading (SURVEY.md §2 row 11).

The reference's workloads are Fashion-MNIST, CIFAR-10, CIFAR-100, UCI
tabular and sklearn digits (BASELINE.json configs). This container has
**no network**, so the torchvision/keras downloads those imply are
impossible; datasets resolve as:

- ``digits``, ``wine``, ``breast_cancer``, ``diabetes``: real data, from
  sklearn's offline bundles (UCI-derived tabular + image data).
- ``fashion_mnist``, ``cifar10``, ``cifar100``: deterministic synthetic
  stand-ins with the exact shapes/dtypes/class counts of the originals
  (see synthetic.py for the generative recipe). Benchmarks measure
  throughput, which depends on shapes, not pixels; accuracy-style tests
  assert learnability of the synthetic task instead of absolute numbers.
- ``successor_tokens``: seeded token rows for next-token workloads
  (tokens.py: a walk over a seeded table of each token's successors).

All loaders return host numpy; device placement is the backend's job
(one transfer per search, not per trial — that is the point of the
TPU-native design).
"""

from mpi_opt_tpu.data.loaders import DATASETS, load_dataset

__all__ = ["load_dataset", "DATASETS"]
