"""Device-plane telemetry: the ``RoundTelemetry`` pytree and its math.

The scan/shard engines compile the entire run into ONE XLA program —
nothing crosses back to the host until the stacked per-round outputs
come out of the final ``lax.scan``.  Telemetry therefore cannot be a
Python-side logger: every counter and gauge here is a fixed-shape jnp
value computed *inside* the round body and stacked by the scan like
any other ``ys`` leaf.
No callbacks, no dynamic shapes, no host round-trips — the static
analyzer (``repro.analysis``) proves the instrumented round body is
free of host-callback primitives.

Parity contract: every integer counter is computed from REPLICATED
full-width inputs (the global participation draw, the pre-update cache
presence/miss masks, ``last_sync``) with the identical expression in
all three engines, so host x scan x shard counter stacks are
byte-equal.  Float gauges that average over participants reduce with a
``psum`` over the client mesh axis in the sharded engine (the same
two-phase contract strategy aggregation uses) and are asserted
allclose, not byte-equal.

Everything in this module is also safe to call from host-loop numpy
code: the helpers take anything ``jnp.asarray`` accepts.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import era as era_lib

__all__ = [
    "STALENESS_BUCKETS",
    "RoundTelemetry",
    "TelemetryLog",
    "zeros",
    "gate",
    "participants_per_cohort",
    "cache_signal_counts",
    "returning_client_count",
    "staleness_histogram",
    "participant_mean",
    "mean_entropy",
    "codec_error_mean",
]

# staleness histogram width: bucket b counts participants whose last
# participation was b rounds before the previous round (b = t-1 -
# last_sync, clipped into the top bucket).  Fixed so the pytree shape
# is static under scan.
STALENESS_BUCKETS = 8


class RoundTelemetry(NamedTuple):
    """One round's device-resident metrics (a scan-stackable pytree).

    Integer counters (byte-equal across engines):

    - ``participants``: (n_cohorts,) participating clients per cohort;
    - ``cache_hits`` / ``cache_miss_new`` / ``cache_expired``: the
      Alg. 3 signal census over the round's public subset P^t —
      CACHED / NEWLY_CACHED / EXPIRED counts (hits + new + expired
      == |P^t| on active rounds; cache-off runs count every request
      as new);
    - ``catch_up_clients``: returning stragglers (participating with
      ``last_sync < t-1``) served a catch-up package this round;
    - ``staleness_hist``: (STALENESS_BUCKETS,) histogram of
      ``t - 1 - last_sync`` over participants (bucket 0 = was present
      last round; top bucket clips).

    Byte counters (f32, still byte-equal — every input is an exact
    small integer so f32 and f64 arithmetic agree):

    - ``uplink_bytes`` / ``downlink_bytes``: the ledger's per-round
      payloads; ``catch_up_bytes``: the catch-up share of downlink.

    Float gauges (allclose across engines — reduction order differs):

    - ``teacher_entropy_pre``: mean Shannon entropy (nats) of the
      participant-mean soft labels as the server sees them (post
      uplink codec), BEFORE strategy sharpening/aggregation;
    - ``teacher_entropy_post``: mean entropy of the aggregated teacher
      after sharpening and the downlink codec — the pre/post gap is
      the ERA/Enhanced-ERA sharpening effect the paper studies;
    - ``beta``: the resolved sharpening knob
      (:meth:`repro.fl.strategies.base.Strategy.sharpen_gauge` —
      Enhanced ERA's static or adaptive beta, ERA's temperature, 0
      where the strategy has none);
    - ``codec_quant_error``: mean |decode(encode(z)) - z| over
      participating clients' uplink entries (0 for identity codecs).
    """

    participants: jnp.ndarray
    cache_hits: jnp.ndarray
    cache_miss_new: jnp.ndarray
    cache_expired: jnp.ndarray
    catch_up_clients: jnp.ndarray
    staleness_hist: jnp.ndarray
    uplink_bytes: jnp.ndarray
    downlink_bytes: jnp.ndarray
    catch_up_bytes: jnp.ndarray
    teacher_entropy_pre: jnp.ndarray
    teacher_entropy_post: jnp.ndarray
    beta: jnp.ndarray
    codec_quant_error: jnp.ndarray


# field partition used by the conformance suite: EXACT fields must be
# byte-equal across host/scan/shard; GAUGE fields are allclose only.
EXACT_FIELDS = ("participants", "cache_hits", "cache_miss_new",
                "cache_expired", "catch_up_clients", "staleness_hist",
                "uplink_bytes", "downlink_bytes", "catch_up_bytes")
GAUGE_FIELDS = ("teacher_entropy_pre", "teacher_entropy_post", "beta",
                "codec_quant_error")


def zeros(n_cohorts: int) -> RoundTelemetry:
    """The all-zero telemetry row (outage rounds)."""
    i0 = jnp.zeros((), jnp.int32)
    f0 = jnp.zeros((), jnp.float32)
    return RoundTelemetry(
        participants=jnp.zeros((n_cohorts,), jnp.int32),
        cache_hits=i0, cache_miss_new=i0, cache_expired=i0,
        catch_up_clients=i0,
        staleness_hist=jnp.zeros((STALENESS_BUCKETS,), jnp.int32),
        uplink_bytes=f0, downlink_bytes=f0, catch_up_bytes=f0,
        teacher_entropy_pre=f0, teacher_entropy_post=f0, beta=f0,
        codec_quant_error=f0)


def gate(tel: RoundTelemetry, keep) -> RoundTelemetry:
    """Zero the whole row unless ``keep`` (total-outage rounds must
    match the host loop's early return, which records nothing)."""
    z = zeros(tel.participants.shape[0])
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(keep, a, b), tel, z)


# ---------------------------------------------------------------------------
# counter math (replicated inputs -> byte-equal everywhere)
# ---------------------------------------------------------------------------

def participants_per_cohort(part, offsets: Sequence[int],
                            sizes: Sequence[int]) -> jnp.ndarray:
    """(n_cohorts,) participant counts from the FULL-width mask.

    ``offsets``/``sizes`` are the static cohort blocks
    (:class:`repro.fl.cohorts.ClientModels`), so plain slicing keeps
    the expression scan- and shard-safe (the sharded engine passes the
    replicated global draw, never the shard-local slice).
    """
    p = jnp.asarray(part).astype(jnp.int32)
    return jnp.stack([jnp.sum(p[off:off + n])
                      for off, n in zip(offsets, sizes)])


def cache_signal_counts(present, miss) -> Tuple[jnp.ndarray, jnp.ndarray,
                                                jnp.ndarray]:
    """(hits, newly_cached, expired) over the round's request list.

    Mirrors :func:`repro.core.cache.signals_for_round`: a non-miss is a
    CACHED hit; a miss splits into EXPIRED (was present) vs
    NEWLY_CACHED (never cached).  ``present``/``miss`` are the
    *pre-update* masks every engine already computes (``cached_at`` /
    ``miss_mask``), so the census is byte-equal by construction.
    Cache-off runs (all-miss, none present) count every request as
    newly cached.
    """
    p = jnp.asarray(present).astype(jnp.int32)
    m = jnp.asarray(miss).astype(jnp.int32)
    hits = jnp.sum(1 - m)
    expired = jnp.sum(m * p)
    new = jnp.sum(m * (1 - p))
    return hits.astype(jnp.int32), new.astype(jnp.int32), \
        expired.astype(jnp.int32)


def returning_client_count(part, last_sync, t) -> jnp.ndarray:
    """Participants whose last participation predates round ``t - 1``
    — exactly the clients :func:`repro.core.cache.catch_up_bytes_device`
    bills a catch-up package for.  Must see the PRE-update
    ``last_sync``."""
    ls = jnp.asarray(last_sync, jnp.int32)
    tt = jnp.asarray(t, jnp.int32)
    back = jnp.logical_and(jnp.asarray(part, bool), ls < tt - 1)
    return jnp.sum(back.astype(jnp.int32))


def staleness_histogram(part, last_sync, t,
                        n_buckets: int = STALENESS_BUCKETS) -> jnp.ndarray:
    """(n_buckets,) histogram of ``t - 1 - last_sync`` over this
    round's participants (pre-update ``last_sync``; top bucket clips).
    Bucket 0 therefore counts clients that were present last round."""
    ls = jnp.asarray(last_sync, jnp.int32)
    tt = jnp.asarray(t, jnp.int32)
    stale = jnp.clip(tt - 1 - ls, 0, n_buckets - 1)
    one_hot = jax.nn.one_hot(stale, n_buckets, dtype=jnp.int32)
    p = jnp.asarray(part, bool)
    return jnp.sum(jnp.where(p[:, None], one_hot, 0), axis=0)


# ---------------------------------------------------------------------------
# gauge math (participant reductions; psum on the sharded engine)
# ---------------------------------------------------------------------------

def participant_mean(z, part_f, n_part,
                     axis_name: Optional[str] = None) -> jnp.ndarray:
    """Mean of ``z`` (clients, ...) over participating clients.

    ``part_f``/``z`` may be shard-local; pass ``axis_name`` to psum the
    weighted sum over the client mesh axis (``n_part`` is already the
    replicated global count in both device engines).
    """
    zs = jnp.tensordot(jnp.asarray(part_f, jnp.float32),
                       jnp.asarray(z, jnp.float32), axes=1)
    if axis_name is not None:
        zs = jax.lax.psum(zs, axis_name)
    return zs / jnp.maximum(jnp.asarray(n_part, jnp.float32), 1.0)


def mean_entropy(p) -> jnp.ndarray:
    """Mean Shannon entropy (nats) over a (..., n_classes) batch of
    soft labels — the ERA pre/post sharpening gauge."""
    return jnp.mean(era_lib.entropy(jnp.asarray(p, jnp.float32)))


def codec_error_mean(z_post, z_pre, part_f, n_part,
                     axis_name: Optional[str] = None) -> jnp.ndarray:
    """Mean absolute uplink quantization error |decoded - transmitted|
    over participating clients' entries (0 for identity codecs)."""
    z_post = jnp.asarray(z_post, jnp.float32)
    z_pre = jnp.asarray(z_pre, jnp.float32)
    w = jnp.asarray(part_f, jnp.float32)
    err = jnp.sum(jnp.abs(z_post - z_pre)
                  * w.reshape((-1,) + (1,) * (z_post.ndim - 1)))
    if axis_name is not None:
        err = jax.lax.psum(err, axis_name)
    m = float(np.prod(z_post.shape[1:]))
    denom = jnp.maximum(jnp.asarray(n_part, jnp.float32) * m, 1.0)
    return err / denom


# ---------------------------------------------------------------------------
# host-side container
# ---------------------------------------------------------------------------

class TelemetryLog:
    """Host-side per-round telemetry record (numpy, never traced).

    The host loop ``append``s one :class:`RoundTelemetry` per round;
    the device engines build one from the scan's stacked ``ys`` via
    :meth:`from_stacked`.  Either way the log exposes the same
    ``stacks()`` / ``summary()`` / ``as_dict()`` views, so the
    conformance suite and the exporters are engine-agnostic.
    """

    def __init__(self, rounds: Optional[Iterable[RoundTelemetry]] = None):
        self._rounds: List[RoundTelemetry] = []
        for r in (rounds or []):
            self.append(r)

    def append(self, tel: RoundTelemetry) -> None:
        self._rounds.append(RoundTelemetry(
            *[np.asarray(leaf) for leaf in tel]))

    @classmethod
    def from_stacked(cls, stacked: RoundTelemetry) -> "TelemetryLog":
        """Rebuild from scan-stacked leaves (leading round axis)."""
        leaves = [np.asarray(leaf) for leaf in stacked]
        n = leaves[0].shape[0]
        return cls(RoundTelemetry(*[leaf[i] for leaf in leaves])
                   for i in range(n))

    def __len__(self) -> int:
        return len(self._rounds)

    def stacks(self) -> Dict[str, np.ndarray]:
        """field -> (T, ...) numpy stack, one row per round."""
        return {f: np.stack([np.asarray(getattr(r, f))
                             for r in self._rounds])
                for f in RoundTelemetry._fields}

    def totals(self) -> RoundTelemetry:
        acc = [np.zeros_like(np.asarray(leaf)) for leaf in self._rounds[0]]
        for r in self._rounds:
            acc = [a + np.asarray(leaf) for a, leaf in zip(acc, r)]
        return RoundTelemetry(*acc)

    def summary(self) -> Dict[str, Any]:
        """Scalar digest for reports / ``BENCH_*.json`` embedding."""
        if not self._rounds:
            return {"rounds": 0}
        s = self.stacks()
        active = s["participants"].sum(axis=1) > 0
        n_active = int(active.sum())
        requests = int(s["cache_hits"].sum() + s["cache_miss_new"].sum()
                       + s["cache_expired"].sum())

        def _mean_active(field):
            return float(s[field][active].mean()) if n_active else 0.0

        return {
            "rounds": len(self._rounds),
            "active_rounds": n_active,
            "participants_total": int(s["participants"].sum()),
            "cache_hits": int(s["cache_hits"].sum()),
            "cache_miss_new": int(s["cache_miss_new"].sum()),
            "cache_expired": int(s["cache_expired"].sum()),
            "cache_hit_rate": (float(s["cache_hits"].sum()) / requests
                               if requests else 0.0),
            "catch_up_clients": int(s["catch_up_clients"].sum()),
            "catch_up_bytes": float(s["catch_up_bytes"].sum()),
            "uplink_bytes": float(s["uplink_bytes"].sum()),
            "downlink_bytes": float(s["downlink_bytes"].sum()),
            "staleness_hist": [int(x) for x in
                               s["staleness_hist"].sum(axis=0)],
            "teacher_entropy_pre_mean": _mean_active("teacher_entropy_pre"),
            "teacher_entropy_post_mean": _mean_active("teacher_entropy_post"),
            "beta_mean": _mean_active("beta"),
            "beta_last": (float(s["beta"][active][-1]) if n_active else 0.0),
            "codec_quant_error_mean": _mean_active("codec_quant_error"),
        }

    def as_dict(self) -> Dict[str, Any]:
        """JSON-ready record (run records, ``fl_train`` dumps)."""
        return {
            "schema": 1,
            "rounds": len(self._rounds),
            "summary": self.summary(),
            "per_round": {f: np.asarray(v).tolist()
                          for f, v in self.stacks().items()},
        }
