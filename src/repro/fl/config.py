"""Run configuration for the federated-distillation engine."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

from repro.fl.cohorts import CohortSpec


@dataclass(frozen=True)
class FLConfig:
    n_clients: int = 20
    n_classes: int = 10
    dim: int = 32
    rounds: int = 100
    local_steps: int = 5          # E
    distill_steps: int = 5        # E_dist
    lr: float = 0.1               # eta
    lr_dist: float = 0.1          # eta_dist
    public_size: int = 1000       # |P|
    public_per_round: int = 100   # |P^t|
    private_size: int = 2000
    alpha: float = 0.05           # Dirichlet
    participation: float = 1.0    # p
    hidden: int = 64
    mlp_depth: int = 2
    cluster_scale: float = 3.0   # class-center spread (task difficulty)
    noise: float = 1.0           # within-class noise (task difficulty)
    seed: int = 0
    eval_every: int = 10
    # wire codecs (repro.compress specs, e.g. "quant8", "cache_delta+quant8");
    # "identity" keeps the legacy dense-fp32 payloads and ledger values
    uplink_codec: str = "identity"
    downlink_codec: str = "identity"
    # request-list/index entry width in bytes (comm.index_bytes_for picks
    # 2 for public datasets <= 65k samples; 4 is the legacy conservative
    # default that all pinned ledger values assume)
    index_bytes: float = 4.0
    # heterogeneous client-model cohorts (repro.fl.cohorts): a tuple of
    # CohortSpec whose sizes sum to n_clients, assigned to contiguous
    # cohort-major client blocks.  None = one homogeneous cohort built
    # from (hidden, mlp_depth) — bit-identical to the pre-cohort path.
    # Soft-label shapes are architecture-independent, so strategies,
    # codecs, and the comm ledger are unaffected by the mix.
    cohorts: Optional[Tuple[CohortSpec, ...]] = None
    # client-sharded engine (engine="shard"): mesh to partition the
    # client axis over — "auto" (the widest local device count that
    # divides every cohort block; n_clients when homogeneous),
    # "DATA"/"DATAxMODEL" (e.g. "8", "2x4"), or
    # "production[_multipod]"; see repro.fl.shard_engine.resolve_mesh.
    # Explicit specs require n_clients divisible by the data-axis size.
    mesh_spec: str = "auto"
    # opt-in fast path: run the round hot path (uplink codec round trip
    # + participation-weighted reduction + ERA sharpening) as one fused
    # Pallas kernel (repro.kernels.round_kernel) instead of the per-op
    # chain.  Scan/shard engines only; requires a fused-capable strategy
    # and a kernel-expressible uplink codec (identity / quantN /
    # cache_delta[+quantN]).  The host engine ignores the flag — it is
    # the per-op reference the fused path is validated against.
    fused_round: bool = False
    # private/test shard assignment: "dirichlet" (the paper's non-IID
    # label partition — the default every pinned ledger/metric assumes)
    # or "uniform" (vectorized round-robin split, O(n) with no Python
    # loop over clients — the only partition that is tractable at the
    # active-set engine's K = 10^6 benchmark scale).
    partition: str = "dirichlet"
    # opt-in device-plane telemetry (repro.obs): compute a
    # RoundTelemetry row (cache hit/miss census, participation and
    # staleness counters, payload bytes, teacher-entropy/beta gauges)
    # inside the round body of every engine.  Leaves the lax.scan with
    # its other per-round outputs on the device engines, so the run
    # stays one XLA program with no host callbacks.  Off (the default) leaves every engine's program
    # and golden ledger byte-identical to a build without the feature.
    telemetry: bool = False
