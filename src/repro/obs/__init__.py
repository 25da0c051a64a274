"""Run telemetry: device-resident round metrics + host-plane tracing.

Two planes, deliberately separate:

- **Device plane** (:mod:`repro.obs.device`): the ``RoundTelemetry``
  pytree the FL engines compute *inside* the compiled round body —
  cache hit/miss census, participation and staleness counters, payload
  bytes, teacher-entropy and sharpening gauges.  Opt-in via
  ``FLConfig.telemetry`` / ``run_method(telemetry=...)``; one row per
  round leaves the ``lax.scan`` with its other outputs, so the whole
  run stays one XLA program with no host callbacks.
- **Host plane** (:mod:`repro.obs.trace` / ``export`` / ``report``):
  monotonic span tracing around compile/run/eval blocks, Chrome-trace
  (Perfetto) + JSONL exporters, run records, and the
  ``python -m repro.obs`` renderer/validator.

On the profiler's clock, :func:`stage` names each stage of a round
body (``fl.<stage>`` scopes on the device's operations) and
:func:`span` marks host work (the engines' ``engine.*`` spans).

Importing ``repro.obs`` (or ``repro.obs.trace``) never imports jax:
launch scripts route their clocks through :func:`now` before they set
``XLA_FLAGS``.  Device-plane names are re-exported lazily.
"""
from __future__ import annotations

from typing import Any

from repro.obs.trace import STAGES, Span, SpanTracer, now, span, stage

__all__ = [
    "now", "Span", "SpanTracer", "STAGES", "stage", "span",
    # lazy (jax-importing) device-plane names
    "RoundTelemetry", "TelemetryLog",
]

_DEVICE_NAMES = ("RoundTelemetry", "TelemetryLog")


def __getattr__(name: str) -> Any:
    if name in _DEVICE_NAMES:
        from repro.obs import device

        return getattr(device, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
