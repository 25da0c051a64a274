"""Operations and bytes that the algorithm needs, worked out from shapes.

Counts follow the algorithm, not today's code: only participants'
client work, only valid rows (no padding), and evaluation only on its
scheduled rounds.  A matmul of ``(n, a) @ (a, c)`` is ``2 n a c``
operations; bias adds, activations and the softmax are not counted.  A
training step on one row is the forward pass, the weight gradients and
the input gradients of every layer but the first (the data needs no
gradient).
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np


def mlp_dims(config: Dict[str, Any]) -> List[int]:
    return [config["dim"]] + [config["hidden"]] * config["mlp_depth"] + [config["n_classes"]]


def param_count(dims: List[int]) -> int:
    return sum(a * c + c for a, c in zip(dims[:-1], dims[1:]))


def forward_flops(dims: List[int]) -> int:
    """Per row."""
    return sum(2 * a * c for a, c in zip(dims[:-1], dims[1:]))


def train_step_flops(dims: List[int]) -> int:
    """Per row: forward, weight gradients, input gradients of layers 2..L."""
    layers = list(zip(dims[:-1], dims[1:]))
    return (forward_flops(dims) + sum(2 * a * c for a, c in layers)
            + sum(2 * a * c for a, c in layers[1:]))


def uniform_rows(n_samples: int, n_clients: int) -> np.ndarray:
    """Valid rows per client under the round-robin partition."""
    base, extra = divmod(n_samples, n_clients)
    return base + (np.arange(n_clients) < extra).astype(np.int64)


def train_rows(n_samples: int, n_clients: int) -> np.ndarray:
    """Rows each client trains on: the first 90% of its valid rows."""
    n = uniform_rows(n_samples, n_clients).astype(np.float32)
    return np.maximum((n * np.float32(0.9)).astype(np.int64), 1)


def participants(config: Dict[str, Any], traffic: Dict[str, Any]) -> int:
    m, k = traffic["participants"], config["n_clients"]
    return k if m == "all" else min(int(m), k)


def round_flops(config: Dict[str, Any], traffic: Dict[str, Any], *,
                eval_round: bool, distill: bool = True) -> float:
    """Operations one round needs; ``distill`` is false only on round 1."""
    dims = mlp_dims(config)
    fwd, step = forward_flops(dims), train_step_flops(dims)
    k, m = config["n_clients"], participants(config, traffic)
    pub_t = config["public_per_round"]
    n_priv = config["private_size"]
    rows = train_rows(n_priv, k)
    mean_train = float(np.mean(rows))
    total = m * config["local_steps"] * mean_train * step        # local training
    if distill:
        total += m * config["distill_steps"] * pub_t * step       # client distillation
    total += m * pub_t * fwd                                      # uplink predictions
    total += config["distill_steps"] * pub_t * step               # server distillation
    if eval_round:
        n_test = max(n_priv // 5, 200)
        n_val_pub = max(config["public_size"] // 10, 10)
        val_rows = n_priv - int(rows.sum())
        total += (n_test                  # server accuracy
                  + n_test                # every client's accuracy on its test rows
                  + val_rows              # every client's validation loss
                  + k * n_val_pub         # the clients' mean on the public validation split
                  + n_val_pub) * fwd      # the server's validation loss
    return float(total)


def call_flops(config: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    """Operations of one ``run(R)`` call after the first: every round
    distills, and rounds on the evaluation schedule (or the call's last)
    evaluate.  Calls start at multiples of ``R``, so the schedule is the
    same in every call."""
    r, every = traffic["rounds_per_call"], traffic["eval_every"]
    n_eval = sum(1 for t in range(1, r + 1) if t % every == 0 or t == r)
    return (n_eval * round_flops(config, traffic, eval_round=True)
            + (r - n_eval) * round_flops(config, traffic, eval_round=False))


# per element of the client stack: residual, min/max, quantize and
# dequantize, last-class reconstruction, clip and renormalize, weighted add
FUSED_FLOPS_PER_INPUT = 14
# per element of the teacher: mean, log, scale, exp, normalize
FUSED_FLOPS_PER_OUTPUT = 6


def fused_round_cost(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    """One round's fused codec + aggregation + sharpening: the
    participants' ``(m, |P^t|, N)`` stack and their weights are read once,
    the ``(|P^t|, N)`` base is read and the teacher written."""
    m = participants(config, traffic)
    pub_t, n = config["public_per_round"], config["n_classes"]
    stack = m * pub_t * n
    out = pub_t * n
    return {"flops": float(stack * FUSED_FLOPS_PER_INPUT + out * FUSED_FLOPS_PER_OUTPUT),
            "bytes": float(4 * (stack + m + 2 * out))}
