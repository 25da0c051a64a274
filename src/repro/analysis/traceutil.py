"""Shared tracing machinery for the static analyzer.

Everything here operates on :func:`jax.make_jaxpr` output — functions
are traced on ``ShapeDtypeStruct`` arguments and never executed, so the
passes are cheap enough for CI and cannot be fooled by lucky concrete
inputs.
"""
from __future__ import annotations

import contextlib
from typing import Iterator, List, Optional, Tuple

import jax
import numpy as np
from jax.extend import core as jcore

# Primitives that escape the traced graph back to the host: fatal inside
# lax.scan (the scan-safe contract) and invisible to AOT cost models.
CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "host_callback_call", "outside_call",
})


def subjaxprs(eqn) -> Iterator[jcore.Jaxpr]:
    """Immediate sub-jaxprs of one equation (scan/cond/while/pjit/...)."""
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else [v]):
            if isinstance(x, jcore.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jcore.Jaxpr):
                yield x


def iter_eqns(jaxpr: jcore.Jaxpr):
    """Every equation in ``jaxpr``, recursing through sub-jaxprs."""
    for e in jaxpr.eqns:
        yield e
        for sub in subjaxprs(e):
            yield from iter_eqns(sub)


def primitive_names(jaxpr: jcore.Jaxpr) -> set:
    return {e.primitive.name for e in iter_eqns(jaxpr)}


def find_eqns(jaxpr: jcore.Jaxpr, name: str) -> List:
    return [e for e in iter_eqns(jaxpr) if e.primitive.name == name]


@contextlib.contextmanager
def record_host_rng(record: List[str]):
    """Monkeypatch the ``np.random`` constructors for the duration of a
    trace: host RNG draws are invisible in the jaxpr (numpy runs at
    trace time and bakes constants in), so the only reliable static
    detector is catching the constructor call itself."""
    orig_rng, orig_rs = np.random.default_rng, np.random.RandomState

    def spy_rng(*a, **k):
        record.append("np.random.default_rng")
        return orig_rng(*a, **k)

    def spy_rs(*a, **k):
        record.append("np.random.RandomState")
        return orig_rs(*a, **k)

    np.random.default_rng, np.random.RandomState = spy_rng, spy_rs
    try:
        yield record
    finally:
        np.random.default_rng, np.random.RandomState = orig_rng, orig_rs


class TraceResult:
    """Outcome of one abstract trace: the jaxpr (or the exception) plus
    what the host-side spies observed."""

    def __init__(self, jaxpr, error: Optional[BaseException],
                 host_rng: List[str]):
        self.jaxpr = jaxpr
        self.error = error
        self.host_rng = host_rng

    @property
    def ok(self) -> bool:
        return self.error is None

    @property
    def callbacks(self) -> set:
        if self.jaxpr is None:
            return set()
        return primitive_names(self.jaxpr.jaxpr) & CALLBACK_PRIMITIVES

    def scan_safety_violations(self) -> List[str]:
        """Why this trace is NOT scan-safe (empty list = safe)."""
        out = []
        if self.error is not None:
            out.append(f"trace failed: {type(self.error).__name__}: "
                       f"{_first_line(self.error)}")
        if self.callbacks:
            out.append(f"host callback primitives in graph: "
                       f"{sorted(self.callbacks)}")
        if self.host_rng:
            out.append(f"host numpy RNG constructed during trace: "
                       f"{sorted(set(self.host_rng))}")
        return out


def _first_line(exc: BaseException) -> str:
    return str(exc).strip().splitlines()[0][:200] if str(exc) else ""


def trace(fn, *args) -> TraceResult:
    """Trace ``fn`` on abstract args, capturing failure + host RNG use."""
    rec: List[str] = []
    with record_host_rng(rec):
        try:
            jaxpr = jax.make_jaxpr(fn)(*args)
        except Exception as e:  # noqa: BLE001 — any trace failure is data
            return TraceResult(None, e, rec)
    return TraceResult(jaxpr, None, rec)
