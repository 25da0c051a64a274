"""Pass 3: static BlockSpec lint over every kernel entry point.

Each kernel module exports ``analysis_cases()`` — (label, fn, abstract
args) triples covering its entry points at representative and
known-awkward shapes (small/odd rows, huge K, bf16).  The lint traces
each case with ``interpret=False`` forced (the BlockSpecs a native TPU
compile would see) and checks, without executing anything:

- **sublane alignment** (error): every VMEM block's second-minor dim
  must be a multiple of the dtype's sublane tile (8 for f32, 16 for
  bf16, 32 for int8).  Misaligned blocks interpret fine on CPU but
  mis-tile on real hardware — the ``era_kernel``/``attn_kernel``
  ``min(block, n)`` bug class.
- **lane alignment** (info): a last dim off the 128-lane tile is legal
  (Mosaic pads) but wastes lanes; surfaced for visibility only since
  small FL class counts make it routine.
- **SMEM scalars** (error): a tiny (<= 8 element) *input* operand in
  VMEM is almost certainly a scalar parameter missing its SMEM spec —
  a (1,) VMEM vector is not a valid compiled layout.
- **VMEM footprint**: single-buffered block bytes (all VMEM operands +
  scratch) over ~16 MB is an error (cannot fit a core's VMEM), over
  8 MB a warning (no headroom for double buffering).
"""
from __future__ import annotations

import importlib
import math
from typing import Iterable, List, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.report import Finding
from repro.analysis.traceutil import find_eqns
from repro.kernels.runtime import (
    LANES,
    VMEM_LIMIT_NATIVE,
    sublanes_for_dtype,
)

KERNEL_MODULES = (
    "repro.kernels.era_kernel",
    "repro.kernels.quant_kernel",
    "repro.kernels.round_kernel",
    "repro.kernels.distill_kernel",
    "repro.kernels.attn_kernel",
)

# single-buffer warn threshold: half of VMEM, leaving the compiler room
# to double-buffer the grid pipeline
_VMEM_WARN = VMEM_LIMIT_NATIVE // 2
_SCALAR_ELEMS = 8  # inputs at or below this are "scalar parameters"


def iter_cases(modules: Iterable[str] = KERNEL_MODULES):
    for modname in modules:
        mod = importlib.import_module(modname)
        for label, fn, args in mod.analysis_cases():
            yield label, fn, args


def _is_smem(bm) -> bool:
    aval = getattr(bm, "block_aval", None)
    return aval is not None and "smem" in str(
        getattr(aval, "memory_space", "")).lower()


def _block_dims(bm) -> Tuple[int, ...]:
    """Block extent per dim: ``Blocked(block_size=n)`` -> n, squeezed
    dims -> 1."""
    dims = (getattr(d, "block_size", d) for d in bm.block_shape)
    return tuple(int(d) if isinstance(d, int) else 1 for d in dims)


def check_case(label: str, fn, args) -> List[Finding]:
    findings: List[Finding] = []
    try:
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as e:  # noqa: BLE001
        return [Finding("error", "pallas", label,
                        f"case failed to trace: {type(e).__name__}: {e}")]
    eqns = find_eqns(closed.jaxpr, "pallas_call")
    if not eqns:
        return [Finding("warn", "pallas", label,
                        "no pallas_call in traced graph — nothing to lint")]
    clean = True
    for k, e in enumerate(eqns):
        tag = label if len(eqns) == 1 else f"{label}#call{k}"
        if e.params.get("interpret", False):
            findings.append(Finding(
                "info", "pallas", tag,
                "traced with interpret=True — BlockSpecs below are the "
                "interpreter's, not a native compile's"))
        gm = e.params["grid_mapping"]
        total_vmem = 0
        for i, bm in enumerate(gm.block_mappings):
            is_input = i < gm.num_inputs
            kind = "in" if is_input else "out"
            arr = bm.array_aval
            dims = _block_dims(bm)
            if _is_smem(bm):
                continue  # scalar memory: no tiling/VMEM constraints
            nbytes = math.prod(dims) * jnp.dtype(arr.dtype).itemsize
            total_vmem += nbytes
            if is_input and math.prod(dims) <= _SCALAR_ELEMS:
                clean = False
                findings.append(Finding(
                    "error", "pallas", tag,
                    f"{kind}[{i}] {dims} {arr.dtype}: scalar-sized operand "
                    "in VMEM — needs a pltpu.SMEM BlockSpec (a tiny VMEM "
                    "vector is not a valid compiled layout)"))
                continue
            if len(dims) >= 2:
                sub = sublanes_for_dtype(arr.dtype)
                if dims[-2] % sub:
                    clean = False
                    findings.append(Finding(
                        "error", "pallas", tag,
                        f"{kind}[{i}] block {dims} {arr.dtype}: sublane dim "
                        f"{dims[-2]} not a multiple of {sub} — misaligned "
                        "row block (interprets on CPU, mis-tiles on TPU)"))
                if dims[-1] % LANES and dims[-1] != arr.shape[-1]:
                    # a chosen tile off the lane grid; spanning the full
                    # array dim is exempt (nothing the kernel can do)
                    findings.append(Finding(
                        "info", "pallas", tag,
                        f"{kind}[{i}] block {dims}: lane dim {dims[-1]} off "
                        f"the {LANES}-lane tile (legal, padded by Mosaic)"))
        # scratch operands: trailing invars of the kernel jaxpr
        kjaxpr = e.params["jaxpr"]
        n_blocked = gm.num_inputs + gm.num_outputs
        for sv in kjaxpr.invars[len(kjaxpr.invars) - gm.num_scratch_operands:]:
            aval = sv.aval
            if "smem" in str(getattr(aval, "memory_space", "")).lower():
                continue
            total_vmem += (math.prod(aval.shape)
                           * jnp.dtype(aval.dtype).itemsize)
        del n_blocked
        if total_vmem > VMEM_LIMIT_NATIVE:
            clean = False
            findings.append(Finding(
                "error", "pallas", tag,
                f"per-block VMEM footprint {total_vmem / 2**20:.1f} MiB "
                f"exceeds the {VMEM_LIMIT_NATIVE / 2**20:.0f} MiB core "
                "limit — the kernel cannot compile natively"))
        elif total_vmem > _VMEM_WARN:
            findings.append(Finding(
                "warn", "pallas", tag,
                f"per-block VMEM footprint {total_vmem / 2**20:.1f} MiB "
                "leaves no room for double buffering "
                f"(warn threshold {_VMEM_WARN / 2**20:.0f} MiB)"))
    if clean:
        findings.append(Finding(
            "ok", "pallas", label,
            f"{len(eqns)} pallas_call(s): blocks aligned, scalars in SMEM, "
            "VMEM within budget"))
    return findings


def run(modules: Iterable[str] = KERNEL_MODULES) -> List[Finding]:
    findings: List[Finding] = []
    for label, fn, args in iter_cases(modules):
        findings.extend(check_case(label, fn, args))
    return findings
