"""Pass 4: telemetry-plane contracts for the scanned engines.

``FLConfig.telemetry`` adds a ``RoundTelemetry`` row to the outputs of
the ``lax.scan`` round body.  Two things must stay true, and neither is
checked anywhere at runtime:

1. **Scan safety**: the instrumented round body (including any
   ``telemetry_hook`` an experiment installs) must stay free of
   host-callback primitives and host RNG — one smuggled
   ``debug_callback`` silently turns the single-compilation engine
   into a per-round host round-trip.  This pass traces the telemetry-
   enabled round body of representative engine variants on abstract
   shapes and fails on any :data:`~repro.analysis.traceutil.
   CALLBACK_PRIMITIVES` hit.
2. **Off-path inertness**: the round body's carry tree must be the
   same with telemetry on and off (telemetry is an output, never
   state), the telemetry-off ``ys`` tree must not mention telemetry,
   and the telemetry-on ``ys`` tree must differ from the off tree by
   EXACTLY the ``telemetry`` entry — the structural form of the "off
   path leaves golden ledgers byte-identical" guarantee.

Everything is trace-only (``jax.make_jaxpr`` / ``jax.eval_shape`` on
``ShapeDtypeStruct``): no training runs.
"""
from __future__ import annotations

from typing import List, Tuple

from repro.analysis.report import Finding

# engine variants traced with telemetry on: strategy name, constructor
# kwargs, engine kwargs, uplink codec — chosen so the instrumented
# graph covers the distinct telemetry paths (static vs adaptive beta
# gauge, identity vs delta+quant codec-error path, cache on/off)
ANALYSIS_VARIANTS: Tuple[Tuple[str, dict, dict, str], ...] = (
    ("scarlet", {}, {"cache_duration": 2}, "identity"),
    ("scarlet", {"beta": "adaptive"}, {"cache_duration": 2}, "identity"),
    ("scarlet", {}, {"cache_duration": 2}, "cache_delta+quant8"),
    ("dsfl", {}, {}, "identity"),
)


def _build_engine(strategy: str, strat_kw: dict, eng_kw: dict,
                  codec: str, telemetry: bool):
    from repro.fl.config import FLConfig
    from repro.fl.scan_engine import ScannedFederatedDistillation
    from repro.fl.strategies import STRATEGIES

    cfg = FLConfig(n_clients=4, rounds=2, public_size=32, public_per_round=8,
                   n_classes=4, dim=8, hidden=8, private_size=32,
                   local_steps=1, distill_steps=1, seed=0,
                   uplink_codec=codec, telemetry=telemetry)
    return ScannedFederatedDistillation(cfg, STRATEGIES[strategy](**strat_kw),
                                        **eng_kw)


def _round_abstract(eng):
    """Abstract (carry, xs) for one ``_round_device`` invocation."""
    import jax
    import jax.numpy as jnp

    concrete = (eng._initial_carry(),
                (jnp.int32(1), jnp.zeros(eng.cfg.n_clients, bool),
                 jnp.asarray(False)))
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
        concrete)


def check_round_body(subject: str, eng) -> List[Finding]:
    """Scan-safety of one engine's (telemetry-instrumented) round body."""
    from repro.analysis import traceutil

    carry, xs = _round_abstract(eng)
    tr = traceutil.trace(lambda c, x: eng._round_device(c, x), carry, xs)
    violations = tr.scan_safety_violations()
    if violations:
        return [Finding("error", "obs", subject, v) for v in violations]
    return [Finding("ok", "obs", subject,
                    "telemetry round body is scan-safe "
                    "(no callbacks, no host RNG)")]


def check_off_on_structure(subject: str, make) -> List[Finding]:
    """Telemetry must be structurally additive: the carry trees are
    identical with telemetry on and off, the off ``ys`` tree contains
    no telemetry entry, and the on ``ys`` tree differs from the off one
    by exactly that entry."""
    import jax

    findings: List[Finding] = []
    shapes = {}
    for tel in (False, True):
        eng = make(tel)
        carry, xs = _round_abstract(eng)
        out_carry, ys = jax.eval_shape(
            lambda c, x: eng._round_device(c, x), carry, xs)
        shapes[tel] = (carry, out_carry, dict(ys))
    for i, tree_name in ((0, "carry in"), (1, "carry out")):
        off, on = shapes[False][i], shapes[True][i]
        if (jax.tree_util.tree_structure(off)
                != jax.tree_util.tree_structure(on)
                or jax.tree_util.tree_leaves(off)
                != jax.tree_util.tree_leaves(on)):
            findings.append(Finding(
                "error", "obs", subject,
                f"telemetry changes the {tree_name} tree "
                f"(off={sorted(off)}, on={sorted(on)}) — telemetry must "
                "leave the round's state untouched"))
    off, on = shapes[False][2], shapes[True][2]
    if "telemetry" in off:
        findings.append(Finding(
            "error", "obs", subject,
            "telemetry-OFF round body emits a telemetry entry in ys — "
            "the off path must be untouched"))
    if "telemetry" not in on:
        findings.append(Finding(
            "error", "obs", subject,
            "telemetry-ON round body missing the telemetry entry in ys"))
    on_rest = {k: v for k, v in on.items() if k != "telemetry"}
    if off != on_rest:
        findings.append(Finding(
            "error", "obs", subject,
            f"telemetry changes the ys structure beyond its own entry "
            f"(off={sorted(off)}, on-minus-telemetry={sorted(on_rest)}) "
            "— the off-path byte-identity guarantee is at risk"))
    if not findings:
        findings.append(Finding(
            "ok", "obs", subject,
            "telemetry is structurally additive (carry trees identical; "
            "ys trees differ by exactly the telemetry entry)"))
    return findings


def run() -> List[Finding]:
    findings: List[Finding] = []
    for strategy, strat_kw, eng_kw, codec in ANALYSIS_VARIANTS:
        label = strategy + ("+" + "adaptive" if strat_kw.get("beta") ==
                            "adaptive" else "") + (
            f"+{codec}" if codec != "identity" else "")
        eng = _build_engine(strategy, strat_kw, eng_kw, codec, telemetry=True)
        findings.extend(check_round_body(f"telemetry[{label}]", eng))
    # one structural off/on diff is enough: the wiring is shared
    findings.extend(check_off_on_structure(
        "telemetry[structure]",
        lambda tel: _build_engine("scarlet", {}, {"cache_duration": 2},
                                  "identity", telemetry=tel)))
    return findings
