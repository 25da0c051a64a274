"""Front door: run one FL method end-to-end."""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

from repro.fl.active_engine import ActiveSetFederatedDistillation
from repro.fl.async_engine import AsyncFederatedDistillation
from repro.fl.baselines import FedAvg, Individual
from repro.fl.cohorts import CohortSpec
from repro.fl.config import FLConfig
from repro.fl.rounds import FederatedDistillation, History
from repro.fl.scan_engine import ScannedFederatedDistillation
from repro.fl.scenarios import Scenario
from repro.fl.shard_engine import ShardedFederatedDistillation
from repro.fl.strategies import STRATEGIES
from repro.fl.traffic import TrafficModel

_ENGINES = {
    "host": FederatedDistillation,
    "scan": ScannedFederatedDistillation,
    "shard": ShardedFederatedDistillation,
    "active": ActiveSetFederatedDistillation,
    "async": AsyncFederatedDistillation,
}

__all__ = ["run_method"]


def run_method(
    method: str,
    cfg: FLConfig,
    *,
    cache_duration: int = 0,
    use_cache: Optional[bool] = None,
    rounds: Optional[int] = None,
    probabilistic_expiry: bool = False,
    scenario: Optional[Scenario] = None,
    track_local_caches: bool = False,
    engine: str = "host",
    rng_backend: Optional[str] = None,
    codec: Optional[str] = None,
    downlink_codec: Optional[str] = None,
    cohorts: Optional[Sequence[CohortSpec]] = None,
    fused_round: Optional[bool] = None,
    telemetry: Optional[bool] = None,
    traffic: Optional[TrafficModel] = None,
    **strategy_kw,
) -> History:
    """Run one FL method end-to-end and return its History.

    method in {scarlet, dsfl, cfd, comet, selective_fd, mean, fedavg,
    individual}.  ``cache_duration``>0 with ``use_cache=True`` plugs the
    soft-label cache into any distillation method (paper Fig. 11).
    ``scenario`` composes participation sampling, client outages, and
    heterogeneous schedules onto any distillation strategy (scenarios
    are ignored by the fedavg/individual baselines).

    ``engine="scan"`` runs the device-resident fused multi-round engine
    (one ``lax.scan`` program, zero per-round host round-trips; see
    :mod:`repro.fl.scan_engine`); ``engine="shard"`` additionally
    partitions the client axis over the ``cfg.mesh_spec`` device mesh
    (:mod:`repro.fl.shard_engine` — client counts beyond one chip's
    memory); ``engine="active"`` keeps client state in a host-side
    (optionally memory-mapped) store and runs only each round's active
    participants on device (:mod:`repro.fl.active_engine` — million-
    client populations at O(m) device cost, same byte-exact ledger);
    ``engine="async"`` runs buffered aggregation under a traffic model
    (:mod:`repro.fl.async_engine` — clients dispatch, train against
    possibly-stale caches, and report late; the server aggregates
    whatever arrived each window with optional staleness decay via the
    ``staleness_decay`` strategy option); ``engine="host"`` is the
    reference Python round loop.  ``rng_backend="jax"`` makes the host
    loop draw subsets/participation from the scanned engines' key
    stream so all engines are directly comparable.

    ``traffic`` (a :class:`repro.fl.traffic.TrafficModel`) supplies the
    async engine's arrival/latency/churn processes; it applies to
    ``engine="async"`` only.  Omitted, the async engine runs the
    synchronous default model (byte-identical to ``engine="scan"``).

    ``codec`` (uplink) / ``downlink_codec`` select soft-label wire
    codecs (:mod:`repro.compress` specs, e.g. ``"quant8"``,
    ``"cache_delta+quant8"``) — shorthand for setting the corresponding
    ``FLConfig`` fields; the ledger switches to the codec's analytic
    payload accounting on that direction.

    ``cohorts`` (a sequence of :class:`repro.fl.CohortSpec`, shorthand
    for ``FLConfig.cohorts``) gives clients heterogeneous model
    architectures — the distillation methods exchange only soft-labels,
    so any strategy/codec/engine combination runs unchanged over a
    cohort mix.  Parameter-sharing baselines (fedavg) and the
    no-collaboration baseline reject cohorts: they assume the single
    homogeneous ``(hidden, mlp_depth)`` model.

    ``fused_round`` (shorthand for ``FLConfig.fused_round``) opts the
    scan/shard engines into the fused round hot path
    (:mod:`repro.kernels.round_kernel`): uplink codec round trip +
    masked aggregation + sharpening in one Pallas kernel.  The host
    engine ignores it — it is the per-op reference the fused path is
    validated against.

    ``telemetry`` (shorthand for ``FLConfig.telemetry``) opts the run
    into device-plane telemetry (:mod:`repro.obs`): the returned
    ``History.telemetry`` holds one
    :class:`~repro.obs.device.RoundTelemetry` row per round (cache
    hit/miss census, staleness, payload bytes, entropy/beta gauges),
    computed inside the compiled round body on every engine.  The
    baselines reject it — there is no distillation round to instrument.
    """
    if engine not in _ENGINES:
        raise ValueError(f"unknown engine: {engine!r} "
                         f"(want one of {sorted(_ENGINES)})")
    if traffic is not None and engine != "async":
        raise ValueError("traffic models apply to engine='async' only "
                         "(the synchronous engines have no dispatch/"
                         "arrival split)")
    if codec is not None:
        cfg = dataclasses.replace(cfg, uplink_codec=codec)
    if downlink_codec is not None:
        cfg = dataclasses.replace(cfg, downlink_codec=downlink_codec)
    if cohorts is not None:
        cfg = dataclasses.replace(cfg, cohorts=tuple(cohorts))
    if fused_round is not None:
        cfg = dataclasses.replace(cfg, fused_round=fused_round)
    if telemetry is not None:
        cfg = dataclasses.replace(cfg, telemetry=telemetry)
    if method in ("fedavg", "individual"):
        if cfg.cohorts:
            raise ValueError(
                f"{method} assumes the homogeneous (hidden, mlp_depth) "
                "model; client-model cohorts only apply to "
                "distillation-based methods")
        if engine != "host":
            raise ValueError(f"{method} is a baseline with no scanned/sharded "
                             "path; use engine='host'")
        if rng_backend is not None:
            raise ValueError(f"{method} has no rng_backend knob (baselines "
                             "draw nothing from the round key stream)")
        if cfg.uplink_codec != "identity" or cfg.downlink_codec != "identity":
            raise ValueError(f"{method} exchanges parameters, not "
                             "soft-labels; codecs do not apply")
        if cfg.telemetry:
            raise ValueError(f"{method} has no distillation round to "
                             "instrument; telemetry applies to "
                             "distillation-based methods only")
        cls = FedAvg if method == "fedavg" else Individual
        return cls(cfg).run(rounds)
    strat = STRATEGIES[method](**strategy_kw)
    cls = _ENGINES[engine]
    kw = dict(cache_duration=cache_duration,
              use_cache=use_cache,
              probabilistic_expiry=probabilistic_expiry,
              scenario=scenario,
              track_local_caches=track_local_caches)
    if rng_backend is not None:
        kw["rng_backend"] = rng_backend
    if traffic is not None:
        kw["traffic"] = traffic
    return cls(cfg, strat, **kw).run(rounds)
