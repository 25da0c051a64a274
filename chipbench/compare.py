"""The comparison that decides ``correct``.

The window's step is one call of the engine, ``run(R)``: ``R`` federated
rounds in one go.  Set-up drives the engine from the seed through its
first call, the same call the window then repeats, and keeps what that
call produced.  Once the window has closed, the plain reference
(``chipbench.reference``) plays the same first ``R`` rounds from the same
seed, and these numbers compare the two:

``ledger``
    the widest relative gap between the per-round uplink and downlink
    bytes (counts of participants, requests, cache signals and catch-up
    entries, priced by the wire format);
``cache_state``
    public entries whose presence or timestamp differ (exact);
``teacher``
    the widest gap between cached soft labels: the teacher after the
    uplink codec, the aggregation and the sharpening;
``server_step`` and ``client_step``
    per parameter leaf, the gap between the program's and the
    reference's norm of the change over the call, ``||theta_R - theta_0||``,
    over the larger of the reference's norm for that leaf and for the
    median leaf; the worst leaf counts.  Client leaves are the whole
    stacked population, so a client that should not have moved counts.
    A leaf whose reference change is under a thousandth of the median
    leaf's moves by rounding alone and is left out;
``val_loss``
    on every evaluation round of the call, the relative gap of the
    server's distillation loss and the clients' mean validation loss.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import numpy as np

NAMES = ("ledger", "cache_state", "teacher", "server_step", "client_step", "val_loss")


def leaf_change(new, old, block: int = 256) -> Dict[str, float]:
    """``||new - old||`` per named leaf: float32 differences, summed in
    float64 over row blocks on the host, or reduced on the device for
    device arrays."""
    out = {}
    for name in new:
        a, b = new[name], old[name]
        if isinstance(a, np.ndarray):
            total = 0.0
            for lo in range(0, max(a.shape[0], 1), block):
                d = a[lo:lo + block].astype(np.float32) - b[lo:lo + block].astype(np.float32)
                total += float(np.sum(np.square(d), dtype=np.float64))
        else:
            import jax.numpy as jnp
            d = a.astype(jnp.float32) - b.astype(jnp.float32)
            total = float(jnp.sum(jnp.square(d)))
        out[name] = math.sqrt(total)
    return out


def step_gap(prog: Dict[str, float], ref: Dict[str, float]) -> float:
    """Worst leaf's gap of change norms (see the module docstring)."""
    if not all(map(math.isfinite, list(prog.values()) + list(ref.values()))):
        return math.inf
    med = float(np.median(list(ref.values())))
    worst = 0.0
    for name, r in ref.items():
        if r < 1e-3 * med:
            continue
        worst = max(worst, abs(prog[name] - r) / max(r, med))
    return worst


def readings(prog: Dict[str, Any], ref) -> Dict[str, float]:
    """The compared numbers; ``prog`` as ``run.first_call`` records it,
    ``ref`` a ``reference.Result`` (or one shaped like it)."""
    ledger = 0.0
    for key in ("uplink", "downlink"):
        p, r = np.asarray(prog[key], np.float64), np.asarray(getattr(ref, key), np.float64)
        if p.shape != r.shape:
            return dict.fromkeys(NAMES, math.inf)
        gap = float(np.max(np.abs(p - r) / np.maximum(np.abs(r), 1.0), initial=0.0))
        ledger = max(ledger, gap if math.isfinite(gap) else math.inf)
    cache_state = float(np.sum(prog["cache_present"] != ref.cache_present)
                        + np.sum((prog["cache_ts"] != ref.cache_ts) & ref.cache_present))
    both = prog["cache_present"] & ref.cache_present
    teacher = float(np.max(np.abs(prog["cache_values"][both].astype(np.float64)
                                  - ref.cache_values[both].astype(np.float64)),
                           initial=0.0))
    if not math.isfinite(teacher):
        teacher = math.inf
    val_loss = 0.0
    if set(prog["evals"]) != set(ref.evals):
        val_loss = math.inf
    else:
        for t, row in ref.evals.items():
            for key in ("server_val_loss", "client_val_loss"):
                p = prog["evals"][t].get(key, math.nan)
                gap = abs(p - row[key]) / max(abs(row[key]), 1e-12)
                val_loss = max(val_loss, gap if math.isfinite(gap) else math.inf)
    return {"ledger": ledger, "cache_state": cache_state, "teacher": teacher,
            "server_step": step_gap(prog["server_change"], ref.server_change),
            "client_step": step_gap(prog["client_change"], ref.client_change),
            "val_loss": val_loss}


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]], List[str]]:
    """``(correct, {name: {value, limit}}, lines)``: every number at or
    under its limit.  A number that is not finite fails."""
    table, lines, ok = {}, [], True
    for name in NAMES:
        v, lim = float(values[name]), float(limits[name])
        good = math.isfinite(v) and v <= lim
        ok &= good
        table[name] = {"value": v, "limit": lim}
        lines.append(f"check {name}: {v!r} limit {lim!r} {'ok' if good else 'FAIL'}")
    return ok, table, lines
