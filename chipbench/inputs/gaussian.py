"""Gaussian class clusters: the benchmark's own copy of the program's
synthetic data generator.

The plain reference rebuilds every input from the seed and takes nothing
the system under test made.  This inputs file makes float32 rows of
``dim`` features: Gaussian class clusters for the private set, shifted
clusters for the public set, and a held-out test set from the private
distribution.  It is the same arithmetic as the program's generator,
kept here so that a change there cannot move the yardstick.

An inputs file gives ``make(config, seed)`` (``x_private``,
``y_private``, ``x_public``, ``x_test``, ``y_test``: ``x`` arrays in
their own dtype, ``y`` int32) and ``n_test(config)`` (the test set's
length).
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np


def classification_data(n_samples: int, n_classes: int, dim: int, seed: int,
                        cluster_scale: float = 3.0, noise: float = 1.0,
                        centers: np.ndarray | None = None, block: int = 65536):
    """Gaussian-mixture data: ``(x float32, y int32, centers)``.  Rows are
    drawn in blocks from one stream, which gives the same values as one
    draw of all rows, without float64 temporaries of the whole set."""
    rng = np.random.default_rng(seed)
    if centers is None:
        centers = rng.normal(size=(n_classes, dim)) * cluster_scale
    y = rng.integers(0, n_classes, size=n_samples)
    x = np.empty((n_samples, dim), np.float32)
    for lo in range(0, n_samples, block):
        hi = min(lo + block, n_samples)
        x[lo:hi] = centers[y[lo:hi]] + rng.normal(size=(hi - lo, dim)) * noise
    return x, y.astype(np.int32), centers


def public_private(n_private: int, n_public: int, n_classes: int, dim: int,
                   seed: int, cluster_scale: float = 3.0,
                   noise: float = 1.0) -> Dict[str, np.ndarray]:
    """Private labelled, public unlabelled (shifted centers) and test sets."""
    rng = np.random.default_rng(seed)
    xp, yp, centers = classification_data(n_private, n_classes, dim, seed,
                                          cluster_scale, noise)
    pub_centers = centers + rng.normal(size=centers.shape) * 1.0
    xu, _, _ = classification_data(n_public, n_classes, dim, seed + 1,
                                   centers=pub_centers, noise=noise)
    xt, yt, _ = classification_data(max(n_private // 5, 200), n_classes, dim,
                                    seed + 2, centers=centers, noise=noise)
    return {"x_private": xp, "y_private": yp, "x_public": xu,
            "x_test": xt, "y_test": yt}


def n_test(config: Dict[str, Any]) -> int:
    return max(config["private_size"] // 5, 200)


def make(config: Dict[str, Any], seed: int) -> Dict[str, np.ndarray]:
    return public_private(config["private_size"], config["public_size"],
                          config["n_classes"], config["dim"], seed,
                          config["cluster_scale"], config["noise"])
