"""Tracing: monotonic clocks, a span tracer, and names on the profiler's clock.

Importing this module never imports jax, so the launch scripts can
route their timing through :func:`now` before they set ``XLA_FLAGS``
and initialize the backend.  The functions that reach the profiler
import jax lazily:

- :func:`stage` names one stage of a round body (``fl.<stage>``, a
  ``jax.named_scope``).  It is trace-time metadata: every device
  operation the stage lowers to carries the name in its op path, and
  it costs nothing at run time.
- :func:`span` marks a block of host work with a
  ``jax.profiler.TraceAnnotation``.  Under a profiler trace the span
  lands on the host plane, on the same clock as the device's events.

Spans are recorded as a well-nested B/E event sequence *by
construction*: ``SpanTracer.span()`` pushes the begin event on entry
and the end event on exit, so the exported Chrome trace (Perfetto's
legacy JSON format) is always valid regardless of clock granularity — the
``python -m repro.obs validate`` check replays exactly this stack
discipline.
"""
from __future__ import annotations

import contextlib
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional

__all__ = ["now", "Span", "SpanTracer", "STAGES", "stage", "span"]

# the stages of a federated round, in the round bodies' order of work;
# the uplink codec belongs to ``aggregate`` (the fused path does codec,
# aggregation and sharpening in one kernel)
STAGES = ("client_distill", "local_train", "predict", "aggregate", "cache",
          "server_distill", "eval")


def now() -> float:
    """Monotonic seconds for duration measurement.

    ``time.perf_counter()`` — unlike ``time.time()`` it never jumps on
    NTP adjustment or DST, so durations cannot go negative.  The epoch
    is arbitrary: only differences are meaningful.
    """
    return time.perf_counter()


def stage(name: str):
    """``jax.named_scope("fl.<name>")`` for one of :data:`STAGES`."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r} (stages: {STAGES})")
    import jax

    return jax.named_scope(f"fl.{name}")


def span(name: str, **ids: Any):
    """A host span on the profiler's clock
    (``jax.profiler.TraceAnnotation``); ``ids`` become its arguments."""
    import jax

    return jax.profiler.TraceAnnotation(name, **ids)


@dataclass
class Span:
    """One completed (or still-open) span, relative to the tracer t0."""
    name: str
    start_s: float
    dur_s: float
    depth: int
    meta: Dict[str, Any] = field(default_factory=dict)

    def as_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "start_s": self.start_s,
                "dur_s": self.dur_s, "depth": self.depth,
                "meta": self.meta}


class SpanTracer:
    """Nestable wall-clock spans with Chrome-trace / JSONL export.

    >>> tr = SpanTracer("demo")
    >>> with tr.span("compile", engine="scan"):
    ...     with tr.span("lower"):
    ...         pass
    >>> trace = tr.chrome_trace()   # load in ui.perfetto.dev

    All clocks are :func:`now` (monotonic); timestamps in the exported
    trace are microseconds relative to tracer construction.  Once jax
    is loaded, each span also opens :func:`span` of its name, so it
    shows up in a profiler trace as well.
    """

    def __init__(self, name: str = "run",
                 meta: Optional[Dict[str, Any]] = None):
        self.name = name
        self.meta = dict(meta or {})
        self.t0 = now()
        self.spans: List[Span] = []
        self._events: List[Dict[str, Any]] = [
            {"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
             "args": {"name": f"repro.obs:{name}"}},
        ]
        self._depth = 0

    # -- recording ------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, **meta: Any) -> Iterator[Span]:
        """Context manager: times the enclosed block as one span."""
        start = now()
        rel = start - self.t0
        self._events.append(self._event(name, "B", rel, meta))
        self._depth += 1
        sp = Span(name, rel, 0.0, self._depth - 1, dict(meta))
        on_profiler = (span(name) if "jax" in sys.modules
                       else contextlib.nullcontext())
        try:
            with on_profiler:
                yield sp
        finally:
            self._depth -= 1
            sp.dur_s = now() - start
            self._events.append(self._event(name, "E", rel + sp.dur_s, {}))
            self.spans.append(sp)

    def _event(self, name: str, ph: str, rel_s: float,
               meta: Dict[str, Any]) -> Dict[str, Any]:
        ev = {"name": name, "ph": ph, "ts": rel_s * 1e6, "pid": 0, "tid": 0}
        if meta:
            ev["args"] = {k: _jsonable(v) for k, v in meta.items()}
        return ev

    # -- export views ---------------------------------------------------
    def chrome_trace(self) -> Dict[str, Any]:
        """Trace-event JSON (Chrome ``about:tracing`` / Perfetto)."""
        return {"traceEvents": list(self._events),
                "displayTimeUnit": "ms",
                "otherData": {"tracer": self.name,
                              **{k: _jsonable(v)
                                 for k, v in self.meta.items()}}}

    def jsonl_lines(self) -> List[Dict[str, Any]]:
        """One dict per completed span (newline-delimited export)."""
        return [s.as_dict() for s in self.spans]

    def total_s(self) -> float:
        return now() - self.t0


def _jsonable(v: Any) -> Any:
    return v if isinstance(v, (int, float, bool, str, type(None))) else str(v)

