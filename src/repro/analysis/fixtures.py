"""Deliberately broken components for analyzer self-tests.

Never registered anywhere — these exist so ``python -m repro.analysis
--selftest`` (and ``tests/test_analysis.py``) can prove each pass
actually fires: a silent analyzer that flags nothing is
indistinguishable from a working one on a healthy repo.

One fixture per bug class the analyzer exists to catch:

- :class:`CallbackSmugglerStrategy` — claims ``scan_safe`` with a host
  callback in the aggregation graph;
- :class:`HostRNGStrategy` — claims ``scan_safe`` while constructing a
  host numpy Generator mid-trace (invisible in the jaxpr: only the
  constructor spy catches it);
- :class:`StaleFlagStrategy` — pure jnp but declares
  ``scan_safe=False`` (the stale-conservative-flag warning);
- :class:`FalseFusedStrategy` — advertises ``supports_fused_round``
  without implementing the fused hooks;
- :func:`broken_kernel_cases` — Pallas entry points with a misaligned
  row block, a scalar parameter in VMEM, and a VMEM-overflowing block;
- :func:`broken_carry_fn` / :func:`fixed_carry_fn` — a shard_map whose
  replicated-carry claim is violated by ``axis_index`` taint (the PR 5
  ``last_sync`` bug, distilled) and its repaired twin;
- :func:`telemetry_callback_engine` — a telemetry-enabled scan engine
  whose ``telemetry_hook`` smuggles a ``jax.debug.callback`` into the
  round body (the "just log it from the hook" mistake that would turn
  the single-compilation engine into a per-round host round-trip);
- :func:`leaky_active_engine` — an active-set engine whose gathered
  O(m) client step folds the device-resident ``(K,)`` ``last_sync``
  mirror into a cost term (weighted by 0.0, so every K = 100 numeric
  test still passes) — the exact leak the K-separation pass exists to
  catch before it voids the O(m) device-memory claim at K = 10^6;
- :func:`async_staleness_callback_engine` — an async engine whose
  strategy overrides ``staleness_weight`` with a ``jax.pure_callback``
  (the "compute the decay curve in numpy" mistake): numerically
  correct, but it plants a host round-trip inside the scanned round
  body.  Built with ``staleness_decay != 1`` so the engine cannot
  statically skip the hook.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from repro.fl.strategies.base import Strategy


class CallbackSmugglerStrategy(Strategy):
    name = "fixture_callback_smuggler"
    scan_safe = True  # LIE: aggregate_masked escapes to the host

    def aggregate(self, z, um, t):
        return jnp.mean(z, axis=0), None

    def aggregate_masked(self, z, part, um, t):
        out = jax.ShapeDtypeStruct(z.shape[1:], z.dtype)
        return jax.pure_callback(
            lambda zz: np.mean(zz, axis=0).astype(zz.dtype), out, z)


class HostRNGStrategy(Strategy):
    name = "fixture_host_rng"
    scan_safe = True  # LIE: transmit draws from host numpy RNG

    def transmit(self, z, key=None):
        # numpy array + tracer broadcasts fine, so the TRACE SUCCEEDS
        # and the jaxpr looks pure — the draw is baked in as a constant
        # and every scan iteration reuses it (wrong), which only the
        # constructor spy can see statically.
        noise = np.random.default_rng(0).normal(0.0, 1e-3, (1,))
        return z + jnp.float32(noise[0])

    def aggregate(self, z, um, t):
        return jnp.mean(z, axis=0), None


class StaleFlagStrategy(Strategy):
    name = "fixture_stale_flag"
    scan_safe = False  # stale: everything below is pure traceable jnp

    def aggregate(self, z, um, t):
        return jnp.mean(z, axis=0), None


class FalseFusedStrategy(Strategy):
    name = "fixture_false_fused"
    scan_safe = True
    supports_fused_round = True  # LIE: fused hooks are not implemented

    def aggregate(self, z, um, t):
        return jnp.mean(z, axis=0), None


BROKEN_STRATEGIES = {
    "fixture_callback_smuggler": CallbackSmugglerStrategy,
    "fixture_host_rng": HostRNGStrategy,
    "fixture_stale_flag": StaleFlagStrategy,
    "fixture_false_fused": FalseFusedStrategy,
}

# level the jaxpr pass must emit for each broken strategy
EXPECTED_STRATEGY_LEVEL = {
    "fixture_callback_smuggler": "error",
    "fixture_host_rng": "error",
    "fixture_stale_flag": "warn",
    "fixture_false_fused": "error",
}


# ---------------------------------------------------------------------------
# Pallas fixtures
# ---------------------------------------------------------------------------

def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _scale_kernel(x_ref, s_ref, o_ref):
    o_ref[...] = x_ref[...] * s_ref[0]


def _misaligned(x):
    # 10-row blocks over f32: interprets fine, mis-tiles natively
    return pl.pallas_call(
        _copy_kernel, grid=(10,),
        in_specs=[pl.BlockSpec((10, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((10, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((100, 128), jnp.float32),
        interpret=False)(x)


def _vmem_scalar(x, s):
    # the scalar rides in VMEM instead of SMEM
    return pl.pallas_call(
        _scale_kernel, grid=(2,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0)),
                  pl.BlockSpec((1,), lambda i: (0,))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
        interpret=False)(x, s)


def _vmem_hog(x):
    # 16 MiB in + 16 MiB out per block: cannot fit a core's VMEM
    return pl.pallas_call(
        _copy_kernel, grid=(1,),
        in_specs=[pl.BlockSpec((4096, 1024), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((4096, 1024), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((4096, 1024), jnp.float32),
        interpret=False)(x)


def broken_kernel_cases():
    """(label, fn, abstract args, expected level) for the Pallas lint."""
    S, f32 = jax.ShapeDtypeStruct, jnp.float32
    return [
        ("fixture/misaligned-rows", _misaligned,
         (S((100, 128), f32),), "error"),
        ("fixture/scalar-in-vmem", _vmem_scalar,
         (S((16, 128), f32), S((1,), f32)), "error"),
        ("fixture/vmem-hog", _vmem_hog,
         (S((4096, 1024), f32),), "error"),
    ]


def analysis_cases():
    """Same triples without the expectation, matching the kernel-module
    protocol so the fixture file can be linted like a real module."""
    return [(label, fn, args) for label, fn, args, _ in broken_kernel_cases()]


# ---------------------------------------------------------------------------
# Telemetry fixtures
# ---------------------------------------------------------------------------

def telemetry_callback_engine():
    """A telemetry-enabled scan engine whose hook escapes to the host.

    The hook looks innocent — it returns the row unchanged — but the
    ``jax.debug.callback`` it calls plants a callback primitive inside
    the compiled round body.  ``repro.analysis.obs_checks.
    check_round_body`` must flag it as an error.
    """
    from repro.fl.config import FLConfig
    from repro.fl.scan_engine import ScannedFederatedDistillation
    from repro.fl.strategies import STRATEGIES

    cfg = FLConfig(n_clients=4, rounds=2, public_size=32, public_per_round=8,
                   n_classes=4, dim=8, hidden=8, private_size=32,
                   local_steps=1, distill_steps=1, seed=0, telemetry=True)
    eng = ScannedFederatedDistillation(cfg, STRATEGIES["mean"]())

    def leaky_hook(tel, t):
        jax.debug.callback(lambda h: None, tel.cache_hits)
        return tel

    eng.telemetry_hook = leaky_hook
    return eng


# ---------------------------------------------------------------------------
# Active-set fixtures
# ---------------------------------------------------------------------------

def leaky_active_engine():
    """An active-set engine whose O(m) client step touches O(K) state.

    The leak is numerically invisible — ``0.0 * sum(last_sync)`` — so
    every conformance cell still passes bit-exactly, but the compiled
    client step now closes over a ``(K,)`` array and device cost scales
    with the population again.  ``repro.analysis.active_checks.
    check_engine`` must flag it as an error.
    """
    from repro.analysis.active_checks import analysis_config
    from repro.fl.active_engine import ActiveSetFederatedDistillation
    from repro.fl.scenarios import Scenario, bernoulli_participation
    from repro.fl.strategies import STRATEGIES

    class LeakyActiveEngine(ActiveSetFederatedDistillation):
        def _client_step(self, args):
            out = super()._client_step(args)
            out["uplink"] = out["uplink"] + 0.0 * jnp.sum(
                self._get_last_sync_dev().astype(jnp.float32))
            return out

    return LeakyActiveEngine(
        analysis_config(), STRATEGIES["scarlet"](), cache_duration=2,
        scenario=Scenario(participation=bernoulli_participation(0.3)))


# ---------------------------------------------------------------------------
# Async-engine fixtures
# ---------------------------------------------------------------------------

def async_staleness_callback_engine():
    """An async engine whose staleness hook escapes to the host.

    The override is numerically identical to the stock exponential
    decay — it just computes it via ``jax.pure_callback`` — so every
    metric test passes, but the compiled round body now carries a
    callback primitive.  ``staleness_decay=0.5`` keeps the hook
    on-path (unit decay is statically skipped).
    ``repro.analysis.async_checks.check_engine`` must flag it as an
    error.
    """
    from repro.analysis.async_checks import analysis_config
    from repro.fl.async_engine import AsyncFederatedDistillation
    from repro.fl.strategies import EnhancedERAStrategy
    from repro.fl.traffic import ArrivalProcess, LatencyModel, TrafficModel

    class CallbackStalenessStrategy(EnhancedERAStrategy):
        name = "fixture_callback_staleness"

        def staleness_weight(self, staleness):
            decay = float(self.opts.get("staleness_decay", 1.0))
            out = jax.ShapeDtypeStruct(jnp.shape(staleness), jnp.float32)
            return jax.pure_callback(
                lambda s: (decay ** np.asarray(s, np.float32)).astype(
                    np.float32), out, staleness)

    traffic = TrafficModel(arrivals=ArrivalProcess("poisson", rate=1.5),
                           latency=LatencyModel("uniform", lo=0, hi=2))
    return AsyncFederatedDistillation(
        analysis_config(), CallbackStalenessStrategy(staleness_decay=0.5),
        traffic=traffic, cache_duration=2)


# ---------------------------------------------------------------------------
# Replication fixtures
# ---------------------------------------------------------------------------

def _fixture_mesh():
    from repro.launch.mesh import make_test_mesh
    return make_test_mesh(2, 4)


def broken_carry_fn():
    """The PR 5 ``last_sync`` bug, distilled: a carry leaf declared
    replicated (out_specs P()) whose update is keyed on the shard-local
    participation slice — shards disagree after one round."""
    mesh = _fixture_mesh()

    def body(last_sync, t):
        six = jax.lax.axis_index("data")
        kloc = last_sync.shape[0]
        part_local = (jnp.arange(kloc) + t + six) % 2 > 0  # shard-varying
        return jnp.where(part_local, t, last_sync)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                       out_specs=P(), check_vma=False)
    abstract = (jax.ShapeDtypeStruct((8,), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))
    return fn, abstract


def fixed_carry_fn():
    """The repaired twin: the shard-varying signal is psum'd over the
    mesh before touching the replicated carry."""
    mesh = _fixture_mesh()

    def body(last_sync, t):
        six = jax.lax.axis_index("data")
        kloc = last_sync.shape[0]
        part_local = (jnp.arange(kloc) + t + six) % 2 > 0
        # reduce to a replicated global view before the carry update
        part_global = jax.lax.psum(
            part_local.astype(jnp.int32), ("data", "model")) > 0
        return jnp.where(part_global, t, last_sync)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                       out_specs=P(), check_vma=False)
    abstract = (jax.ShapeDtypeStruct((8,), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))
    return fn, abstract
