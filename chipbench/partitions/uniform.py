"""The program's round-robin ("uniform") client partition: sample ``i``
goes to client ``i % K`` at slot ``i // K``, so every shard holds
``n // K`` or one more valid rows.

A partition file gives ``shards(x, y, n_clients)`` (``(xs (K, n_max,
...), ys (K, n_max), valid (K, n_max))``, keeping any trailing sample
dims) and ``rows(n_samples, n_clients)`` (valid rows per client, for
the FLOP count).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np


def shards(x: np.ndarray, y: np.ndarray, n_clients: int
           ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    n = len(y)
    n_max = -(-n // n_clients)
    total = n_clients * n_max
    xs = np.zeros((total,) + x.shape[1:], x.dtype)
    ys = np.zeros((total,), y.dtype)
    valid = np.zeros((total,), bool)
    xs[:n], ys[:n], valid[:n] = x, y, True
    perm = (1, 0) + tuple(range(2, xs.ndim + 1))
    xs = xs.reshape((n_max, n_clients) + x.shape[1:]).transpose(perm)
    return (np.ascontiguousarray(xs), np.ascontiguousarray(ys.reshape(n_max, n_clients).T),
            np.ascontiguousarray(valid.reshape(n_max, n_clients).T))


def rows(n_samples: int, n_clients: int) -> np.ndarray:
    base, extra = divmod(n_samples, n_clients)
    return base + (np.arange(n_clients) < extra).astype(np.int64)
