"""The system under test, as one cell drives it.

This is the only module of the benchmark that imports the program
(``repro``).  It builds the engine that ``repro.fl.api.run_method``
builds for the cell's configuration and traffic mix, and reads the
state the correctness comparison needs.  The window drives the engine
through its public ``run(R)``, which continues the same federation from
the last round it finished.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import numpy as np


def engine_seed(seed: int) -> int:
    """The run's seed as the program takes it: a non-negative int32."""
    return int(seed) % (2 ** 31 - 1)


def participation_rate(config: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    from chipbench import cost
    return cost.participants(config, traffic) / config["n_clients"]


def engine_config(config: Dict[str, Any], traffic: Dict[str, Any], seed: int):
    """The program's ``FLConfig`` for one cell: every key of the
    configuration that names one of its fields, and the mix's."""
    from repro.fl import FLConfig

    fields = {f.name for f in dataclasses.fields(FLConfig)}
    return FLConfig(seed=engine_seed(seed), eval_every=traffic["eval_every"],
                    rounds=traffic["rounds_per_call"],
                    participation=participation_rate(config, traffic),
                    fused_round=bool(traffic["fused_round"]),
                    **{k: v for k, v in config.items() if k in fields})


def build_engine(config: Dict[str, Any], traffic: Dict[str, Any], seed: int, devices):
    """The engine for one cell, constructed as ``run_method`` does, on the
    cell's ``devices``.  The program has no public constructor that
    ``run_method`` shares, so this repeats its construction from the same
    engine table.  The shard engine partitions its clients over a one-axis
    mesh of exactly ``devices``; the others run on the first of them, the
    default device."""
    from jax.sharding import Mesh
    from repro.fl import Scenario, fixed_fraction, full_participation
    from repro.fl.api import _ENGINES
    from repro.fl.shard_engine import CLIENT_AXIS
    from repro.fl.strategies import STRATEGIES

    cfg = engine_config(config, traffic, seed)
    rate = cfg.participation
    part = full_participation() if rate >= 1.0 else fixed_fraction(rate)
    strategy = STRATEGIES[config["method"]](beta=config["beta"])
    cls = _ENGINES[traffic["engine"]]
    kwargs = ({"mesh": Mesh(np.asarray(devices), (CLIENT_AXIS,))}
              if traffic["engine"] == "shard" else {})
    return cls(cfg, strategy, cache_duration=config["cache_duration"],
               scenario=Scenario(participation=part), **kwargs)


def client_leaves(engine, copy: bool = False) -> Dict[str, np.ndarray]:
    """The client parameter stack on the host, by leaf name.  ``copy``
    where the engine may overwrite it later."""
    import jax
    (stack,) = engine.client_params  # one homogeneous cohort
    return {k: np.array(v, copy=copy) for k, v in jax.device_get(stack).items()}


def server_leaves(engine) -> Dict[str, np.ndarray]:
    import jax
    return {k: np.asarray(v) for k, v in jax.device_get(engine.server_params).items()}


def cache_arrays(engine):
    import jax
    c = jax.device_get(engine.cache_g)
    return np.asarray(c.values), np.asarray(c.ts), np.asarray(c.present)


def history_record(hist) -> Dict[str, Any]:
    """Ledger and eval rows of one ``run(R)`` call."""
    evals = {}
    for i, t in enumerate(hist.rounds):
        evals[int(t)] = {"server_acc": hist.server_acc[i],
                         "client_acc": hist.client_acc[i],
                         "client_val_loss": hist.client_val_loss[i]}
        if i < len(hist.server_val_loss):
            evals[int(t)]["server_val_loss"] = hist.server_val_loss[i]
    return {"uplink": [r.uplink for r in hist.ledger.rounds],
            "downlink": [r.downlink for r in hist.ledger.rounds],
            "evals": evals}


def reference_setting(config: Dict[str, Any], traffic: Dict[str, Any]):
    from chipbench.reference import Setting
    codec = config["uplink_codec"]
    if not codec.startswith("cache_delta+quant") or config["downlink_codec"] != "identity":
        raise ValueError(f"the reference models cache_delta+quantB uplink and an "
                         f"identity downlink, not {codec!r}/{config['downlink_codec']!r}")
    if config["method"] != "scarlet":
        raise ValueError(f"the reference models SCARLET, not {config['method']!r}")
    from chipbench import cost
    return Setting(
        config=config, n_clients=config["n_clients"], n_classes=config["n_classes"],
        public_size=config["public_size"], public_per_round=config["public_per_round"],
        private_size=config["private_size"], local_steps=config["local_steps"],
        distill_steps=config["distill_steps"], lr=config["lr"], lr_dist=config["lr_dist"],
        beta=config["beta"], cache_duration=config["cache_duration"],
        quant_bits=int(codec[len("cache_delta+quant"):]),
        index_bytes=config["index_bytes"],
        participants=cost.participants(config, traffic),
        eval_every=traffic["eval_every"])
