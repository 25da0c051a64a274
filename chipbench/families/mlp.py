"""FedAvg's 2NN and its kin: a ReLU MLP, ``dim`` -> ``hidden`` x
``mlp_depth`` -> ``n_classes``, as the program builds its clients'
homogeneous cohort, in plain float arithmetic.

A family file gives ``init(key, config, dtype)`` (the program's initial
weights from the same key), ``logits(params, x)``, and
``forward_flops(config)`` and ``train_step_flops(config)`` (operations
per sample, by ``chipbench.cost``'s rules).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp


def dims(config: Dict[str, Any]) -> List[int]:
    return [config["dim"]] + [config["hidden"]] * config["mlp_depth"] + [config["n_classes"]]


def param_count(config: Dict[str, Any]) -> int:
    d = dims(config)
    return sum(a * c + c for a, c in zip(d[:-1], d[1:]))


def init(key, config: Dict[str, Any], dtype):
    d = dims(config)
    params = {}
    for i, (a, c) in enumerate(zip(d[:-1], d[1:])):
        key, k1 = jax.random.split(key)
        params[f"w{i}"] = (jax.random.normal(k1, (a, c)) * math.sqrt(2.0 / a)).astype(dtype)
        params[f"b{i}"] = jnp.zeros((c,), dtype)
    return params


def logits(p, x):
    n = len(p) // 2
    for i in range(n):
        x = x @ p[f"w{i}"] + p[f"b{i}"]
        if i < n - 1:
            x = jax.nn.relu(x)
    return x


def forward_flops(config: Dict[str, Any]) -> int:
    d = dims(config)
    return sum(2 * a * c for a, c in zip(d[:-1], d[1:]))


def train_step_flops(config: Dict[str, Any]) -> int:
    """Forward, weight gradients, input gradients of layers 2..L."""
    d = dims(config)
    layers = list(zip(d[:-1], d[1:]))
    return (forward_flops(config) + sum(2 * a * c for a, c in layers)
            + sum(2 * a * c for a, c in layers[1:]))
