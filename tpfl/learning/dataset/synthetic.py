"""Synthetic datasets for network-free tests and benchmarks.

The reference pulls MNIST from the HF hub (``p2pfl/MNIST``,
examples/mnist.py:173) — unavailable in an egress-free environment, and a
poor benchmark dependency anyway. These generators produce seeded,
learnable classification data with the same shapes (28×28 "MNIST",
32×32×3 "CIFAR"), so every e2e test is hermetic.

Learnability: each class has a fixed random prototype vector; samples are
prototype + Gaussian noise. A linear model separates them quickly, which
reproduces the reference's test contract (accuracy > 0.5 after 2 rounds,
node_test.py:128-132) without the download.
"""

from __future__ import annotations

import numpy as np

from tpfl.learning.dataset.tpfl_dataset import TpflDataset


def synthetic_classification(
    shape: tuple[int, ...],
    n_classes: int = 10,
    n_train: int = 1000,
    n_test: int = 200,
    noise: float = 0.8,
    seed: int = 0,
    x_name: str = "image",
    y_name: str = "label",
) -> TpflDataset:
    """Gaussian-prototype classification data in [0, 1]."""
    rng = np.random.default_rng(seed)
    protos = rng.uniform(0.0, 1.0, size=(n_classes, *shape)).astype(np.float32)

    def make(n: int) -> tuple[np.ndarray, np.ndarray]:
        y = rng.integers(0, n_classes, size=n).astype(np.int32)
        x = protos[y] + rng.normal(0.0, noise, size=(n, *shape)).astype(np.float32)
        return np.clip(x, 0.0, 1.0), y

    x_tr, y_tr = make(n_train)
    x_te, y_te = make(n_test)
    return TpflDataset.from_arrays(
        x_tr, y_tr, x_te, y_te, x_name=x_name, y_name=y_name
    )


def synthetic_lm(
    seq_len: int = 64,
    vocab: int = 32,
    n_train: int = 256,
    n_test: int = 64,
    seed: int = 0,
) -> TpflDataset:
    """Learnable next-token data for TransformerLM tests: sequences
    follow a fixed random permutation walk (token_{t+1} =
    perm[token_t]) with occasional uniform noise, so a small causal LM
    beats the uniform-loss floor quickly. Columns: ``tokens`` (int
    features) / ``targets`` (one-step-shifted ids); export with
    ``x_tag="tokens", y_tag="targets", x_dtype=np.int32``."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(vocab)

    def make(n: int) -> tuple[np.ndarray, np.ndarray]:
        seqs = np.empty((n, seq_len + 1), np.int32)
        seqs[:, 0] = rng.integers(0, vocab, size=n)
        for t in range(seq_len):
            step = perm[seqs[:, t]]
            noise = rng.random(n) < 0.1
            seqs[:, t + 1] = np.where(
                noise, rng.integers(0, vocab, size=n), step
            )
        return seqs[:, :-1], seqs[:, 1:].astype(np.int32)

    x_tr, y_tr = make(n_train)
    x_te, y_te = make(n_test)
    return TpflDataset.from_arrays(
        x_tr, y_tr, x_te, y_te, x_name="tokens", y_name="targets"
    )


def synthetic_mnist(
    n_train: int = 1000, n_test: int = 200, seed: int = 0, noise: float = 0.8
) -> TpflDataset:
    """28×28 grayscale, 10 classes — MNIST-shaped."""
    return synthetic_classification(
        (28, 28), n_classes=10, n_train=n_train, n_test=n_test, seed=seed,
        noise=noise,
    )


def synthetic_cifar10(
    n_train: int = 1000, n_test: int = 200, seed: int = 0, noise: float = 0.8
) -> TpflDataset:
    """32×32×3, 10 classes — CIFAR-10-shaped."""
    return synthetic_classification(
        (32, 32, 3), n_classes=10, n_train=n_train, n_test=n_test, seed=seed,
        noise=noise,
    )
