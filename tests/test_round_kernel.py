"""Fused round kernel (``repro.kernels.round_kernel``): oracle parity,
bit-level parity with the per-op codec + aggregation chain, and the
engine-level validation of ``FLConfig.fused_round``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compress.codecs import get_codec
from repro.fl.config import FLConfig
from repro.fl.scan_engine import ScannedFederatedDistillation
from repro.fl.strategies import STRATEGIES
from repro.fl.strategies.scarlet import EnhancedERAStrategy
from repro.kernels import ops, ref, round_kernel

KEY = jax.random.PRNGKey(7)


def _probs(key, shape):
    return jax.random.dirichlet(key, jnp.ones(shape[-1]), shape[:-1])


def _mask(key, k):
    return (jax.random.uniform(key, (k,)) < 0.6).astype(jnp.float32)


MODES = [("identity", None), ("quant", 8), ("quant", 4),
         ("delta", None), ("delta", 8)]


# ---------------------------------------------------------------------------
# Kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("K,M,N", [(4, 8, 10), (7, 10, 10), (16, 33, 21),
                                   (3, 100, 100)])
@pytest.mark.parametrize("mode,bits", MODES)
@pytest.mark.parametrize("sharpen", [True, False])
def test_fused_round_matches_oracle(K, M, N, mode, bits, sharpen):
    z = _probs(KEY, (K, M, N))
    w = _mask(jax.random.fold_in(KEY, 1), K) * 1.7
    base = (_probs(jax.random.fold_in(KEY, 2), (M, N))
            if mode == "delta" else None)
    beta = 1.5 if sharpen else None
    out = round_kernel.fused_round(z, w, beta, base, mode=mode, bits=bits,
                                   sharpen=sharpen)
    exp = ref.fused_round(z, w, beta, base, mode=mode, bits=bits,
                          sharpen=sharpen)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-6)


def test_fused_round_block_sizing_auto_shrinks():
    """Large K must be cut into 8-aligned client chunks against the VMEM
    budget (rows stay 8-aligned) — and the chunked reduction must still
    match the oracle."""
    K, M, N = 1000, 24, 10
    z = _probs(KEY, (K, M, N))
    w = jnp.ones(K)
    for mode in round_kernel.MODES:
        bm, kc = round_kernel._auto_blocks(M, K, 128, mode, False)
        assert bm % 8 == 0 and bm >= 8
        assert kc % 8 == 0 and 8 <= kc <= K
        if mode != "identity":  # the codec temporaries force chunks
            assert kc < K
    # the interpreter's budget chunks quant at this K too
    assert round_kernel._auto_blocks(M, K, 128, "quant", True)[1] < K
    for mode, bits in (("identity", None), ("quant", 8)):
        out = round_kernel.fused_round(z, w, 1.5, mode=mode, bits=bits)
        exp = ref.fused_round(z, w, 1.5, mode=mode, bits=bits)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=1e-5, atol=1e-6)


def test_fused_round_validation_errors():
    z = _probs(KEY, (4, 8, 10))
    w = jnp.ones(4)
    with pytest.raises(ValueError, match="unknown mode"):
        round_kernel.fused_round(z, w, 1.5, mode="nope")
    with pytest.raises(ValueError, match="requires bits"):
        round_kernel.fused_round(z, w, 1.5, mode="quant")
    with pytest.raises(ValueError, match="requires beta"):
        round_kernel.fused_round(z, w, None, mode="identity", sharpen=True)
    with pytest.raises(ValueError, match="resolved base"):
        round_kernel.fused_round(z, w, 1.5, mode="delta")


# ---------------------------------------------------------------------------
# Bit-level parity with the per-op chain (what the engines replace)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", ["identity", "quant8", "cache_delta",
                                  "cache_delta+quant8"])
def test_strategy_fused_matches_perop_chain(spec):
    """``aggregate_masked_fused`` == codec.roundtrip + ``aggregate_masked``
    exactly in interpret mode (same f32 expression sequence); the
    acceptance tolerance of one quantization step (~scale/levels) is the
    native-TPU bound, so assert the much tighter interpret-mode band."""
    K, M, N = 6, 10, 10
    s = EnhancedERAStrategy(beta=1.5)
    codec = get_codec(spec)
    kspec = round_kernel.codec_kernel_spec(codec)
    assert kspec is not None
    z = _probs(KEY, (K, M, N))
    part = _mask(jax.random.fold_in(KEY, 3), K)
    base = _probs(jax.random.fold_in(KEY, 4), (M, N))
    present = jax.random.uniform(jax.random.fold_in(KEY, 5), (M,)) < 0.5

    if codec.is_identity:
        z_rt = z
    else:
        z_rt = codec.roundtrip(z, base=base, present=present)
    perop = s.aggregate_masked(z_rt, part, None, 1)
    fbase = (round_kernel.resolve_delta_base(base, present, M, N)
             if kspec["mode"] == "delta" else None)
    fused = s.aggregate_masked_fused(z, part, kspec, fbase, 1)
    # one-quant-step acceptance bound; interpret mode is in fact exact
    step = (1.0 / (2 ** (kspec["bits"] or 32) - 1)
            if kspec["bits"] else 1e-6)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(perop),
                               atol=step, rtol=1e-5)


def test_strategy_fused_interpret_mode_is_exact():
    """On this (CPU) backend the kernel runs the interpreter, which
    executes the identical f32 expression sequence — byte-equal output."""
    K, M, N = 6, 10, 10
    s = EnhancedERAStrategy(beta=1.5)
    codec = get_codec("cache_delta+quant8")
    z = _probs(KEY, (K, M, N))
    part = _mask(jax.random.fold_in(KEY, 3), K)
    base = _probs(jax.random.fold_in(KEY, 4), (M, N))
    present = jax.random.uniform(jax.random.fold_in(KEY, 5), (M,)) < 0.5
    perop = s.aggregate_masked(codec.roundtrip(z, base=base, present=present),
                               part, None, 1)
    fused = s.aggregate_masked_fused(
        z, part, {"mode": "delta", "bits": 8},
        round_kernel.resolve_delta_base(base, present, M, N), 1)
    assert np.asarray(perop).tobytes() == np.asarray(fused).tobytes()


def test_fused_total_outage_uniform_teacher():
    """All clients out: the fused path must reproduce
    ``aggregate_masked``'s ``jnp.where`` uniform-teacher guard."""
    K, M, N = 5, 8, 10
    s = EnhancedERAStrategy(beta=1.5)
    z = _probs(KEY, (K, M, N))
    part = jnp.zeros(K)
    fused = s.aggregate_masked_fused(z, part, {"mode": "identity",
                                               "bits": None}, None, 1)
    perop = s.aggregate_masked(z, part, None, 1)
    np.testing.assert_allclose(np.asarray(fused), np.full((M, N), 1.0 / N),
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(perop), atol=1e-7)


def test_partial_aggregate_fused_matches_two_phase():
    """The linear fused phase composes with finalize_aggregate to the
    same teacher as the per-op two-phase path (the shard contract)."""
    K, M, N = 8, 12, 10
    s = EnhancedERAStrategy(beta=1.5)
    codec = get_codec("quant8")
    z = _probs(KEY, (K, M, N))
    part = _mask(jax.random.fold_in(KEY, 6), K)
    z_rt = codec.roundtrip(z)
    perop = s.finalize_aggregate(s.partial_aggregate(z_rt, part, None, 1), 1)
    partials = s.partial_aggregate_fused(z, part, {"mode": "quant", "bits": 8},
                                         None, 1)
    fused = s.finalize_aggregate(partials, 1)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(perop),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# codec_kernel_spec / resolve_delta_base
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec,want", [
    ("identity", {"mode": "identity", "bits": None}),
    ("quant8", {"mode": "quant", "bits": 8}),
    ("quant4", {"mode": "quant", "bits": 4}),
    ("cache_delta", {"mode": "delta", "bits": None}),
    ("cache_delta+quant8", {"mode": "delta", "bits": 8}),
    ("topk2", None),  # no fused equivalent -> per-op path
])
def test_codec_kernel_spec(spec, want):
    assert round_kernel.codec_kernel_spec(get_codec(spec)) == want


def test_resolve_delta_base_matches_codec_base():
    codec = get_codec("cache_delta")
    M, N = 6, 10
    base = _probs(KEY, (M, N))
    present = jnp.asarray([True, False, True, True, False, False])
    a = np.asarray(codec._base(jnp.zeros((4, M, N)), base, present))
    b = np.asarray(round_kernel.resolve_delta_base(base, present, M, N))
    np.testing.assert_allclose(np.broadcast_to(b, a.shape), a, atol=0)
    # no cache at all -> uniform prior
    u = np.asarray(round_kernel.resolve_delta_base(None, None, M, N))
    np.testing.assert_allclose(u, 1.0 / N, atol=0)


# ---------------------------------------------------------------------------
# Engine construction validation
# ---------------------------------------------------------------------------

CFG = FLConfig(n_clients=4, n_classes=4, dim=6, rounds=2, local_steps=1,
               distill_steps=1, public_size=32, public_per_round=8,
               private_size=40, hidden=8, eval_every=10**6, fused_round=True)


def test_engine_rejects_unfusable_codec():
    with pytest.raises(ValueError, match="not kernel-expressible"):
        ScannedFederatedDistillation(
            dataclasses.replace(CFG, uplink_codec="topk2"),
            STRATEGIES["scarlet"](beta=1.5), cache_duration=4)


def test_engine_rejects_unfused_strategy():
    with pytest.raises(ValueError, match="no fused round path"):
        ScannedFederatedDistillation(
            CFG, STRATEGIES["dsfl"](T=0.1))


def test_engine_rejects_adaptive_beta():
    with pytest.raises(ValueError, match="no fused round path"):
        ScannedFederatedDistillation(
            CFG, STRATEGIES["scarlet"](beta="adaptive"), cache_duration=4)


def test_ops_entry_point():
    """The jit'd public wrapper dispatches with backend-detected
    interpret mode."""
    z = _probs(KEY, (4, 8, 10))
    out = ops.fused_round(z, jnp.ones(4), 1.5, mode="quant", bits=8)
    exp = ref.fused_round(z, jnp.ones(4), 1.5, mode="quant", bits=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-6)
