"""Operations and bytes that the algorithm needs, worked out from shapes.

Counts follow the algorithm, not today's code: only participants'
client work, only valid rows (no padding), and evaluation only on its
scheduled rounds.  A matmul of ``(n, a) @ (a, c)`` is ``2 n a c``
operations; bias adds, activations and the softmax are not counted.  A
training step on one row is the forward pass, the weight gradients and
the input gradients of every layer but the first (the data needs no
gradient).  The per-row counts are the configuration's family file's
(``forward_flops``, ``train_step_flops``), the valid rows per client its
partition file's (``rows``), the test set's length its inputs file's
(``n_test``).
"""
from __future__ import annotations

from pathlib import Path
from typing import Any, Dict

import numpy as np

from chipbench import spec


def train_rows(rows: np.ndarray) -> np.ndarray:
    """Rows each client trains on: the first 90% of its valid ``rows``
    (the engine's split, whatever the partition)."""
    n = rows.astype(np.float32)
    return np.maximum((n * np.float32(0.9)).astype(np.int64), 1)


def participants(config: Dict[str, Any], traffic: Dict[str, Any]) -> int:
    m, k = traffic["participants"], config["n_clients"]
    return k if m == "all" else min(int(m), k)


def round_flops(config: Dict[str, Any], traffic: Dict[str, Any], *,
                eval_round: bool, distill: bool = True, root: Path = spec.REPO) -> float:
    """Operations one round needs; ``distill`` is false only on round 1."""
    parts = spec.parts(config, root)
    fwd = parts["family"].forward_flops(config)
    step = parts["family"].train_step_flops(config)
    k, m = config["n_clients"], participants(config, traffic)
    pub_t = config["public_per_round"]
    valid = parts["partition"].rows(config["private_size"], k)
    rows = train_rows(valid)
    mean_train = float(np.mean(rows))
    total = m * config["local_steps"] * mean_train * step        # local training
    if distill:
        total += m * config["distill_steps"] * pub_t * step       # client distillation
    total += m * pub_t * fwd                                      # uplink predictions
    total += config["distill_steps"] * pub_t * step               # server distillation
    if eval_round:
        n_test = parts["inputs"].n_test(config)
        n_val_pub = max(config["public_size"] // 10, 10)
        val_rows = int(valid.sum()) - int(rows.sum())
        total += (n_test                  # server accuracy
                  + n_test                # every client's accuracy on its test rows
                  + val_rows              # every client's validation loss
                  + k * n_val_pub         # the clients' mean on the public validation split
                  + n_val_pub) * fwd      # the server's validation loss
    return float(total)


def call_flops(config: Dict[str, Any], traffic: Dict[str, Any], *,
               root: Path = spec.REPO) -> float:
    """Operations of one ``run(R)`` call after the first: every round
    distills, and rounds on the evaluation schedule (or the call's last)
    evaluate.  Calls start at multiples of ``R``, so the schedule is the
    same in every call."""
    r, every = traffic["rounds_per_call"], traffic["eval_every"]
    n_eval = sum(1 for t in range(1, r + 1) if t % every == 0 or t == r)
    return (n_eval * round_flops(config, traffic, eval_round=True, root=root)
            + (r - n_eval) * round_flops(config, traffic, eval_round=False, root=root))


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
