"""Static contract analyzer: prove declared invariants at trace time.

Three passes, one CLI (``python -m repro.analysis``):

``jaxpr_checks``
    traces every registered strategy hook and codec ``encode``/
    ``decode`` on abstract shapes (:func:`jax.make_jaxpr` over
    ``ShapeDtypeStruct`` — nothing executes) and diffs the traced
    reality against the declared contract flags (``scan_safe``,
    ``supports_fused_round``, ``codec_kernel_spec``).

``replication_checks``
    walks the shard engine's one-round ``shard_map`` jaxpr tracking
    ``axis_index`` / sharded-input taint to prove every carry leaf the
    out_specs declare replicated really is replicated over non-client
    mesh axes (the engine runs ``check_vma=False``, so nothing else
    checks this — the PR 5 ``last_sync`` bug class).

``pallas_checks``
    lints every kernel entry point's native BlockSpecs (via each kernel
    module's ``analysis_cases()``): sublane-aligned row blocks, SMEM
    scalar operands, per-block VMEM footprint within budget.

This ``__init__`` stays import-light (no jax): ``__main__`` must set
``XLA_FLAGS`` before anything pulls jax in.
"""
from __future__ import annotations

from repro.analysis.report import Finding, Report

__all__ = ["Finding", "Report"]
