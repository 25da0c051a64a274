"""Pure-jnp oracles for every Pallas kernel (the correctness reference
against which interpret-mode kernel sweeps assert allclose)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_EPS = 1e-12


def enhanced_era(z_mean: jnp.ndarray, beta: float) -> jnp.ndarray:
    """SCARLET Eq. 4 over the last axis: z^beta / sum z^beta."""
    z = jnp.clip(z_mean.astype(jnp.float32), _EPS, None)
    logits = beta * jnp.log(z)
    return jax.nn.softmax(logits, axis=-1).astype(z_mean.dtype)


def enhanced_era_fused(z_clients: jnp.ndarray, beta: float) -> jnp.ndarray:
    """Fused mean-over-clients + sharpen: (K, B, N) -> (B, N)."""
    return enhanced_era(jnp.mean(z_clients.astype(jnp.float32), axis=0), beta)


def fused_round(z_clients: jnp.ndarray, weights: jnp.ndarray, beta=None,
                base: jnp.ndarray | None = None, *, mode: str = "identity",
                bits: int | None = None, sharpen: bool = True) -> jnp.ndarray:
    """Oracle for the fused round hot path: per-client uplink codec
    round trip, weighted reduction, optional Enhanced-ERA sharpening —
    composed from the per-op oracles / codec math (see
    ``repro.kernels.round_kernel`` for the contract)."""
    z = z_clients.astype(jnp.float32)
    K, M, N = z.shape
    if mode == "quant":
        z = quantize_dequantize(z, bits)
        z = jnp.maximum(z, 0.0)
        z = z / jnp.maximum(z.sum(axis=-1, keepdims=True), 1e-9)
    elif mode == "delta":
        b = base.astype(jnp.float32)[None]          # (1, M, N)
        r = z - b
        r = r[..., :-1]                             # last class sum-implied
        if bits is not None:
            r = quantize_dequantize(r, bits)
        r = jnp.concatenate([r, -r.sum(axis=-1, keepdims=True)], axis=-1)
        z = b + r
        z = jnp.maximum(z, 0.0)
        z = z / jnp.maximum(z.sum(axis=-1, keepdims=True), 1e-9)
    zsum = jnp.tensordot(weights.astype(jnp.float32), z, axes=(0, 0))
    if sharpen:
        return enhanced_era(zsum / K, beta).astype(z_clients.dtype)
    return zsum.astype(z_clients.dtype)


def quantize_dequantize(z: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Per-row min-max uniform quantization round trip over the last axis."""
    levels = float(2 ** bits - 1)
    z32 = z.astype(jnp.float32)
    zmin = z32.min(axis=-1, keepdims=True)
    zmax = z32.max(axis=-1, keepdims=True)
    scale = jnp.maximum(zmax - zmin, 1e-9)
    q = jnp.round((z32 - zmin) / scale * levels) / levels
    return (q * scale + zmin).astype(z.dtype)


def distill_loss(logits: jnp.ndarray, teacher: jnp.ndarray) -> jnp.ndarray:
    """Per-row soft-target CE: -sum_j t_j log_softmax(l)_j -> (B,)."""
    l32 = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(l32, axis=-1)
    return -jnp.sum(teacher.astype(jnp.float32) * logp, axis=-1)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, window: int = 0) -> jnp.ndarray:
    """Naive attention oracle. q: (B,Sq,H,dh); k/v: (B,Sk,Hkv,dh)."""
    B, Sq, H, dh = q.shape
    Hkv = k.shape[2]
    rep = H // Hkv
    kr = jnp.repeat(k, rep, axis=2)
    vr = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kr.astype(jnp.float32))
    s = s / jnp.sqrt(jnp.float32(dh))
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, vr.astype(jnp.float32))
    return o.astype(q.dtype)


def mlp_distill(params, x: jnp.ndarray, teacher: jnp.ndarray,
                keep: jnp.ndarray, lr: float, steps: int):
    """Oracle for ``mlp_distill_kernel.mlp_distill``: ``steps`` SGD steps
    of every kept client on ``rounds._kl``, rows x features, with each
    matmul's operands rounded to bfloat16 and accumulated in float32
    (gradients written out by hand: autodiff through the casts would
    round the weight gradients to bfloat16 too)."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    n = sum(1 for k in params if k.startswith("w"))

    def mm(a, b):
        return jnp.dot(a.astype(bf16), b.astype(bf16),
                       preferred_element_type=f32)

    def one(p, t):
        t = jnp.clip(t, _EPS, 1.0)
        for _ in range(steps):
            acts, pre = [x], []
            for i in range(n):
                h = mm(acts[i], p[f"w{i}"]) + p[f"b{i}"]
                if i < n - 1:
                    pre.append(h)
                    acts.append(jnp.maximum(h, 0.0))
            sm = jax.nn.softmax(h, axis=-1)
            g = (sm * t.sum(axis=-1, keepdims=True) - t) / x.shape[0]
            new = dict(p)
            for i in reversed(range(n)):
                new[f"w{i}"] = p[f"w{i}"] - lr * mm(acts[i].T, g)
                new[f"b{i}"] = p[f"b{i}"] - lr * g.sum(axis=0)
                if i > 0:
                    g = jnp.where(pre[i - 1] > 0, mm(g, p[f"w{i}"].T), 0.0)
            p = new
        return p

    out = jax.vmap(one, in_axes=(0, 0 if teacher.ndim == 3 else None))(
        params, teacher.astype(f32))
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(keep.reshape((-1,) + (1,) * (a.ndim - 1)), a, b),
        out, params)
