"""Pallas TPU kernel for the fused SCARLET round hot path.

Every round the server pulls a ``(K, m, N)`` soft-label stack through
the same op chain: uplink codec round trip (per-row min-max
quantize-dequantize, optionally residual-coded against the synchronized
cache), participation-weighted client reduction, and Enhanced-ERA power
sharpening (Eq. 4).  Run as separate ops (``quant_kernel`` +
``_simplex`` + weighted mean + ``era_kernel``) the stack crosses HBM
three-plus times per round; this kernel streams each row block through
VMEM exactly once — codec, reduction, and sharpening applied back to
back while the block is resident.

The grid is (row blocks, client chunks).  Each step holds a
``(kc, bm, Np)`` slab; the client chunks are the inner reduction axis,
summed into a ``(bm, Np)`` VMEM accumulator, and the sharpening
nonlinearity runs behind the last chunk.  ``_auto_blocks`` sizes ``kc``
and ``bm`` (both 8-aligned, ``runtime.align_block_rows``) so the
step's whole live set — input buffers plus the codec's temporaries —
fits the core's scoped VMEM; the quant and delta branches keep several
slab-sized temporaries live, so at K >= 100 an unchunked client axis
does not fit.  The class dim is padded to 128 lanes and masked in-kernel
with ``broadcasted_iota`` lane predicates, exactly as in the per-op
kernels it replaces.

Codec modes (must mirror ``repro.compress.codecs`` bit for bit — the
engines' comm ledger is analytic, so values may drift only within one
quantization step, and in interpret mode they do not drift at all):

- ``"identity"``: no wire loss;
- ``"quant"``: per-row min-max round trip to ``bits`` bits over the N
  valid lanes + simplex re-projection (``QuantCodec(renormalize=True)``);
- ``"delta"``: residual vs the resolved cache base, last class dropped
  (sum-zero constraint), inner min-max round trip over the first
  ``N - 1`` lanes when ``bits`` is set, reconstruction + simplex
  re-projection (``CacheDeltaCodec[+quantB]``).

Weighting: the kernel computes ``sum_k w_k * z_k`` and, when
``sharpen=True``, divides by K before sharpening — so the scan engine
passes ``w = part * (K / n_part)`` to reproduce
``scarlet.aggregate_masked`` exactly, while the shard engine passes the
raw participation mask with ``sharpen=False`` to get the two-phase
contract's linear moment ``zsum`` (psum'd across shards before
``finalize_aggregate`` sharpens once).

The total-outage uniform-teacher guard stays *outside* the kernel (a
``jnp.where`` on the tiny ``(m, N)`` output) so it matches
``scarlet.aggregate_masked`` bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import (
    SUBLANES_F32,
    VMEM_BUDGET_INTERPRET,
    VMEM_LIMIT_NATIVE,
    align_block_rows,
    fit_block_rows,
    resolve_interpret,
)

# numeric constants mirrored from the per-op path: one-quantization-step
# parity depends on using the *same* epsilons
_EPS_ERA = 1e-12       # era_kernel._EPS / core.era._EPS
_EPS_SCALE = 1e-9      # quant_kernel._EPS_SCALE
_EPS_SIMPLEX = 1e-9    # compress.codecs._EPS

MODES = ("identity", "quant", "delta")

# beta rides in SMEM as a (1,) array (scalar memory; see era_kernel)
_BETA_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)

# VMEM budget for one grid step's whole live set (see _auto_blocks):
# three quarters of a core's scoped VMEM natively; the interpreter has
# no VMEM, so its budget only sets the number of grid steps.
_VMEM_LIVE_BUDGET_NATIVE = VMEM_LIMIT_NATIVE * 3 // 4
_VMEM_LIVE_BUDGET_INTERPRET = VMEM_BUDGET_INTERPRET

# Slab-sized f32 arrays live at once per grid step, by codec mode: the
# double-buffered input plus the branch's temporaries.  Measured by
# compiling for a v5e chip, where Mosaic reports the scoped VMEM a
# failing kernel needs: about 2.1 slabs for identity and 7.3 for quant
# and delta (N=10, 2.5 MiB slab); rounded up.
_LIVE_SLABS = {"identity": 3, "quant": 8, "delta": 8}


def _qdq(r, valid, levels):
    """In-block min-max round trip over the ``valid`` lanes of each row
    — the exact ``quant_kernel._qdq_kernel`` math (incl. the [0, 1]
    level clamp), applied to an already-resident (K, bm, Np) block."""
    rmin = jnp.min(jnp.where(valid, r, jnp.inf), axis=-1, keepdims=True)
    rmax = jnp.max(jnp.where(valid, r, -jnp.inf), axis=-1, keepdims=True)
    scale = jnp.maximum(rmax - rmin, _EPS_SCALE)
    q = jnp.clip(jnp.round((r - rmin) / scale * levels) / levels, 0.0, 1.0)
    return q * scale + rmin


def _simplex(z, valid):
    """codecs._simplex with the padded lanes zeroed (so they neither
    count in the row sum nor leak into the reduction)."""
    z = jnp.where(valid, jnp.maximum(z, 0.0), 0.0)
    return z / jnp.maximum(jnp.sum(z, axis=-1, keepdims=True), _EPS_SIMPLEX)


def _fused_round_kernel(*refs, k_clients: int, n_valid: int,
                        levels: float | None, mode: str, sharpen: bool):
    it = iter(refs)
    z_ref, w_ref = next(it), next(it)
    base_ref = next(it) if mode == "delta" else None
    beta_ref = next(it) if sharpen else None
    o_ref, acc_ref = next(it), next(it)
    k = pl.program_id(1)

    z = z_ref[...].astype(jnp.float32)                   # (kc, bm, Np)
    # lane predicates at (1, 1, Np): they broadcast against the block,
    # so no block-sized int32 temporary stays live in VMEM
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, z.shape[2]), 2)
    valid = lane < n_valid

    if mode == "delta":
        b = base_ref[...].astype(jnp.float32)            # (bm, Np)
        r = z - b[None]
        res_valid = lane < (n_valid - 1)  # last class implied by sum-zero
        if levels is not None:
            r = _qdq(r, res_valid, levels)
        r = jnp.where(res_valid, r, 0.0)
        last = -jnp.sum(r, axis=-1, keepdims=True)
        r = jnp.where(lane == n_valid - 1, last, r)
        z = _simplex(b[None] + r, valid)
    elif mode == "quant":
        z = _simplex(_qdq(z, valid, levels), valid)
    else:
        z = jnp.where(valid, z, 0.0)

    w = w_ref[...].astype(jnp.float32)                   # (kc, 1)
    part = jnp.sum(z * w[:, :, None], axis=0)            # (bm, Np)

    # client chunks are the grid's inner (reduction) axis: the first
    # chunk stores its partial sum, later chunks add theirs
    @pl.when(k == 0)
    def _():
        acc_ref[...] = part

    @pl.when(k > 0)
    def _():
        acc_ref[...] += part

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        zsum = acc_ref[...]
        if sharpen:
            # identical to era_kernel._era_fused_kernel on the weighted stack
            zbar = zsum / k_clients
            beta = beta_ref[0]
            logz = jnp.log(jnp.maximum(zbar, _EPS_ERA)) * beta
            m = jnp.max(logz, axis=-1, keepdims=True)
            e = jnp.exp(logz - m)
            out = e / jnp.sum(e, axis=-1, keepdims=True)
        else:
            out = zsum
        o_ref[...] = out.astype(o_ref.dtype)


def _auto_blocks(m: int, k: int, n_padded: int, mode: str,
                 interpret: bool) -> tuple[int, int]:
    """(row block ``bm``, client chunk ``kc``) for an (m, k) problem.

    One grid step holds a ``(kc, bm, Np)`` slab of the client stack and
    keeps ``_LIVE_SLABS[mode]`` slab-sized f32 arrays live in VMEM.  The
    client axis is cut into as few equal 8-aligned chunks as fit the
    budget at an 8-row block; rows then grow (up to 128) while the
    chunk still fits.
    """
    budget = _VMEM_LIVE_BUDGET_INTERPRET if interpret else _VMEM_LIVE_BUDGET_NATIVE
    per_client_row = n_padded * 4 * _LIVE_SLABS[mode]
    kc_max = max(SUBLANES_F32, budget // (SUBLANES_F32 * per_client_row)
                 // SUBLANES_F32 * SUBLANES_F32)
    n_chunks = -(-k // kc_max)
    kc = align_block_rows(-(-k // n_chunks), k)
    bm = fit_block_rows(128, m, kc * per_client_row, budget)
    return bm, kc


@functools.partial(jax.jit, static_argnames=("mode", "bits", "sharpen",
                                             "block_m", "interpret"))
def fused_round(z_clients: jnp.ndarray, weights: jnp.ndarray, beta=None,
                base: jnp.ndarray | None = None, *, mode: str = "identity",
                bits: int | None = None, sharpen: bool = True,
                block_m: int | None = None,
                interpret: bool | None = None) -> jnp.ndarray:
    """Fused round hot path: (K, m, N) -> (m, N).

    ``weights`` is the (K,) per-client reduction weight (see module
    docs); ``base`` is the *resolved* delta base (``(m, N)``, required
    for ``mode="delta"`` — use :func:`resolve_delta_base`).  ``beta`` is
    required when ``sharpen=True``.  ``block_m`` overrides the auto-sized
    row block.  ``interpret=None`` auto-detects the backend.
    """
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r} (want one of {MODES})")
    if mode == "quant" and bits is None:
        raise ValueError("mode='quant' requires bits")
    if sharpen and beta is None:
        raise ValueError("sharpen=True requires beta")
    if mode == "delta" and base is None:
        raise ValueError("mode='delta' requires a resolved base "
                         "(resolve_delta_base)")
    interpret = resolve_interpret(interpret)
    K, M, N = z_clients.shape
    n_pad = (-N) % 128
    Np = N + n_pad
    # The client axis is padded to the sublane tile: the (kc, 1) weights
    # operand makes K a *sublane* dim, so an unaligned client count
    # (e.g. K=50) mis-tiles natively — caught by the static BlockSpec
    # lint (repro.analysis.pallas_checks).  It is further padded to a
    # whole number of client chunks.  Padded clients carry zero weight,
    # so the reduction (and the /K mean) is unchanged.
    K8 = align_block_rows(K, K)
    bm, kc = _auto_blocks(M, K8, Np, mode, interpret)
    if block_m is not None:
        bm = align_block_rows(block_m, M)
    k_pad = (-K) % kc
    Kp = K + k_pad
    m_pad = (-M) % bm
    z = jnp.pad(z_clients, ((0, k_pad), (0, m_pad), (0, n_pad)))
    Mp = M + m_pad
    w = jnp.pad(jnp.reshape(weights.astype(jnp.float32), (K, 1)),
                ((0, k_pad), (0, 0)))
    levels = float(2 ** bits - 1) if bits is not None else None

    operands = [z, w]
    in_specs = [
        pl.BlockSpec((kc, bm, Np), lambda i, k: (k, i, 0)),
        pl.BlockSpec((kc, 1), lambda i, k: (k, 0)),
    ]
    if mode == "delta":
        operands.append(jnp.pad(base.astype(jnp.float32),
                                ((0, m_pad), (0, n_pad))))
        in_specs.append(pl.BlockSpec((bm, Np), lambda i, k: (i, 0)))
    if sharpen:
        operands.append(jnp.asarray([beta], jnp.float32))
        in_specs.append(_BETA_SPEC)

    out = pl.pallas_call(
        functools.partial(_fused_round_kernel, k_clients=K, n_valid=N,
                          levels=levels, mode=mode, sharpen=sharpen),
        grid=(Mp // bm, Kp // kc),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, Np), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), z_clients.dtype),
        scratch_shapes=[pltpu.VMEM((bm, Np), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return out[:M, :N]


def analysis_cases():
    """(label, fn, abstract args) triples for the static BlockSpec lint
    (:mod:`repro.analysis.pallas_checks`); traced with
    ``interpret=False``, never executed."""
    S, f32 = jax.ShapeDtypeStruct, jnp.float32
    return [
        ("round/identity-sharpen-K200",
         lambda z, w: fused_round(z, w, 1.5, mode="identity",
                                  sharpen=True, interpret=False),
         (S((200, 100, 10), f32), S((200,), f32))),
        ("round/quant8-sharpen-K1000",
         lambda z, w: fused_round(z, w, 1.5, mode="quant", bits=8,
                                  sharpen=True, interpret=False),
         (S((1000, 64, 10), f32), S((1000,), f32))),
        ("round/delta8-linear-K50",
         lambda z, w, b: fused_round(z, w, None, b, mode="delta", bits=8,
                                     sharpen=False, interpret=False),
         (S((50, 24, 10), f32), S((50,), f32), S((24, 10), f32))),
    ]


# ---------------------------------------------------------------------------
# Engine-facing plumbing
# ---------------------------------------------------------------------------

def resolve_delta_base(base, present, m: int, n: int) -> jnp.ndarray:
    """The delta base as ``CacheDeltaCodec._base`` resolves it: the
    cached entry where one exists, the uniform prior elsewhere."""
    if base is None:
        return jnp.full((m, n), 1.0 / n, jnp.float32)
    if present is not None:
        base = jnp.where(present[..., None], base, 1.0 / n)
    return base


def codec_kernel_spec(codec) -> dict | None:
    """Kernel parameters for an uplink codec, or ``None`` when the codec
    has no fused equivalent (top-k, exotic compositions) and the per-op
    path must run."""
    from repro.compress.codecs import CacheDeltaCodec, IdentityCodec, QuantCodec

    if isinstance(codec, IdentityCodec):
        return {"mode": "identity", "bits": None}
    if isinstance(codec, QuantCodec) and codec.renormalize:
        return {"mode": "quant", "bits": codec.bits}
    if isinstance(codec, CacheDeltaCodec):
        if isinstance(codec.inner, IdentityCodec):
            return {"mode": "delta", "bits": None}
        if isinstance(codec.inner, QuantCodec) and not codec.inner.renormalize:
            return {"mode": "delta", "bits": codec.inner.bits}
    return None
