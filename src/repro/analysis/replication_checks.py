"""Pass 2: prove shard_map replication claims by axis taint analysis.

The client-sharded engine wraps its round body in ``shard_map(...,
check_vma=False)`` — the scan carry defeats the partitioner's own
replication inference, so *nothing* verifies that carry leaves declared
``P()`` (replicated) really are bit-identical across shards.  A leaf
that silently varies per shard (the PR 5 ``last_sync`` bug: an update
keyed on the shard-local participation slice) corrupts state on the
gather at scan exit.

This pass walks the shard_map body jaxpr with a standard taint
interpreter over mesh axis names:

- an input sharded over axis ``a`` (``in_specs`` mention ``a``) is
  tainted by ``a`` — its values differ across ``a``-shards;
- ``axis_index(a)`` introduces taint ``{a}`` from nothing;
- reducing collectives over ``a`` (``psum``/``pmax``/``pmin``/
  ``all_gather``) *clear* ``a``-taint — after the reduction every
  ``a``-shard holds the same value;
- everything else unions its input taints; control flow recurses
  (scan/while to fixpoint, cond unions branches + predicate taint).

An output whose ``out_specs`` omit axis ``a`` (claiming replication
over ``a``) but whose taint contains ``a`` is a proven contract
violation: **error**.
"""
from __future__ import annotations

from typing import Dict, FrozenSet, List, Sequence, Tuple

import jax
from jax.extend import core as jcore

from repro.analysis.report import Finding

Taint = FrozenSet[str]
_EMPTY: Taint = frozenset()

# collective -> (axis param name, clears taint?)
_COLLECTIVES = {
    "psum": ("axes", True),
    "pmax": ("axes", True),
    "pmin": ("axes", True),
    "all_gather": ("axis_name", True),
    # outputs still differ per shard: the axis taint must survive
    "psum_scatter": ("axes", False),
    "ppermute": ("axis_name", False),
    "all_to_all": ("axis_name", False),
    "pbroadcast": ("axes", False),
}


def _axes_param(v) -> Tuple[str, ...]:
    if isinstance(v, (tuple, list)):
        return tuple(str(a) for a in v)
    return (str(v),)


def _read(env: Dict, atom) -> Taint:
    if isinstance(atom, jcore.Literal):
        return _EMPTY
    return env.get(atom, _EMPTY)


def taint_jaxpr(jaxpr: jcore.Jaxpr,
                in_taints: Sequence[Taint]) -> List[Taint]:
    """Propagate axis taints through ``jaxpr``; returns output taints."""
    env: Dict = {}
    for v, t in zip(jaxpr.invars, in_taints):
        env[v] = frozenset(t)
    for v in jaxpr.constvars:
        env[v] = _EMPTY

    for e in jaxpr.eqns:
        prim = e.primitive.name
        ins = [_read(env, a) for a in e.invars]
        base: Taint = frozenset().union(*ins) if ins else _EMPTY

        if prim == "axis_index":
            outs = [frozenset({str(e.params["axis_name"])})]
        elif prim in _COLLECTIVES:
            pname, clears = _COLLECTIVES[prim]
            axes = frozenset(_axes_param(e.params[pname]))
            outs = [(base - axes) if clears else (base | axes)
                    for _ in e.outvars]
        elif prim in ("pjit", "closed_call", "core_call", "remat",
                      "checkpoint", "custom_jvp_call", "custom_vjp_call",
                      "custom_vjp_call_jaxpr", "shard_map"):
            inner = _inner_jaxpr(e)
            if inner is None:
                outs = [base for _ in e.outvars]
            else:
                outs = taint_jaxpr(inner, ins[:len(inner.invars)])
        elif prim == "scan":
            outs = _taint_scan(e, ins)
        elif prim == "while":
            outs = _taint_while(e, ins)
        elif prim == "cond":
            outs = _taint_cond(e, ins)
        else:
            outs = [base for _ in e.outvars]

        for v, t in zip(e.outvars, outs):
            env[v] = t
    return [_read(env, v) for v in jaxpr.outvars]


def _inner_jaxpr(e):
    j = e.params.get("jaxpr") or e.params.get("call_jaxpr")
    if isinstance(j, jcore.ClosedJaxpr):
        return j.jaxpr
    return j


def _taint_scan(e, ins: List[Taint]) -> List[Taint]:
    body = e.params["jaxpr"].jaxpr
    nc, ncarry = e.params["num_consts"], e.params["num_carry"]
    consts, carry, xs = ins[:nc], ins[nc:nc + ncarry], ins[nc + ncarry:]
    carry = list(carry)
    # fixpoint: a taint acquired in round t contaminates round t+1's carry
    for _ in range(len(carry) + 1):
        outs = taint_jaxpr(body, consts + carry + xs)
        new_carry = [c | o for c, o in zip(carry, outs[:ncarry])]
        if new_carry == carry:
            break
        carry = new_carry
    outs = taint_jaxpr(body, consts + carry + xs)
    return list(outs[:ncarry]) + list(outs[ncarry:])


def _taint_while(e, ins: List[Taint]) -> List[Taint]:
    cj, bj = e.params["cond_jaxpr"].jaxpr, e.params["body_jaxpr"].jaxpr
    cn, bn = e.params["cond_nconsts"], e.params["body_nconsts"]
    cconsts, bconsts, carry = ins[:cn], ins[cn:cn + bn], list(ins[cn + bn:])
    for _ in range(len(carry) + 1):
        pred = taint_jaxpr(cj, cconsts + carry)[0]
        outs = taint_jaxpr(bj, bconsts + carry)
        # a shard-varying predicate varies the trip count per shard:
        # every carry leaf inherits its taint
        new_carry = [c | o | pred for c, o in zip(carry, outs)]
        if new_carry == carry:
            break
        carry = new_carry
    return carry


def _taint_cond(e, ins: List[Taint]) -> List[Taint]:
    branches = e.params["branches"]
    pred, ops = ins[0], ins[1:]
    per_branch = [taint_jaxpr(b.jaxpr, ops) for b in branches]
    return [frozenset().union(pred, *[br[i] for br in per_branch])
            for i in range(len(per_branch[0]))]


# ---------------------------------------------------------------------------
# shard_map-level check
# ---------------------------------------------------------------------------

def _spec_taint(spec) -> Taint:
    """in_specs/out_specs entry (a PartitionSpec) -> axes the value
    varies over."""
    out = set()
    for axes in spec:
        if axes is None:
            continue
        out.update(str(a) for a in
                   (axes if isinstance(axes, (tuple, list)) else (axes,)))
    return frozenset(out)


def check_shard_map_fn(fn, abstract_args, pass_name: str = "replication",
                       subject_prefix: str = "") -> List[Finding]:
    """Trace ``fn`` (must contain exactly one shard_map) and verify every
    output's declared replication against its taint."""
    findings: List[Finding] = []
    closed = jax.make_jaxpr(fn)(*abstract_args)
    eqns = [e for e in closed.jaxpr.eqns if e.primitive.name == "shard_map"]
    # shard_map may sit under a pjit wrapper
    if not eqns:
        from repro.analysis.traceutil import find_eqns
        eqns = find_eqns(closed.jaxpr, "shard_map")
    if len(eqns) != 1:
        findings.append(Finding(
            "error", pass_name, subject_prefix or "shard_map",
            f"expected exactly one shard_map equation, found {len(eqns)}"))
        return findings
    e = eqns[0]
    inner = e.params["jaxpr"]
    in_specs, out_specs = e.params["in_specs"], e.params["out_specs"]
    mesh_axes = tuple(str(a) for a in e.params["mesh"].shape)

    in_taints = [_spec_taint(s) for s in in_specs]
    out_taints = taint_jaxpr(inner, in_taints)

    labels = _output_labels(fn, abstract_args, len(out_taints))
    ok = True
    for i, (taint, spec) in enumerate(zip(out_taints, out_specs)):
        declared = _spec_taint(spec)
        leaked = (taint - declared) & frozenset(mesh_axes)
        if leaked:
            ok = False
            findings.append(Finding(
                "error", pass_name, f"{subject_prefix}{labels[i]}",
                f"declared replicated over axes {sorted(leaked)} but the "
                f"carry update is tainted by them (taint={sorted(taint)}, "
                f"out_specs={spec}) — shards will disagree at the gather"))
    if ok:
        findings.append(Finding(
            "ok", pass_name, subject_prefix or "shard_map",
            f"all {len(out_taints)} outputs replicated as declared over "
            f"mesh axes {mesh_axes}"))
    return findings


def _output_labels(fn, abstract_args, n: int) -> List[str]:
    """Pytree paths for the flat shard_map outputs (best effort)."""
    try:
        out = jax.eval_shape(fn, *abstract_args)
        leaves = jax.tree_util.tree_flatten_with_path(out)[0]
        if len(leaves) == n:
            return [jax.tree_util.keystr(path) for path, _ in leaves]
    except Exception:  # noqa: BLE001 — labels are cosmetic
        pass
    return [f"out[{i}]" for i in range(n)]


def check_engine(mesh: str = "2x4", n_clients: int = 8) -> List[Finding]:
    """Build a small client-sharded engine and prove its carry-update
    replication claims (the repo-level entry point for this pass)."""
    from repro.fl.config import FLConfig
    from repro.fl.shard_engine import ShardedFederatedDistillation
    from repro.fl.strategies import STRATEGIES

    findings: List[Finding] = []
    # telemetry=True variants prove the RoundTelemetry rows — declared
    # replicated (P()) like the other per-round outputs — really are
    # shard-invariant: counters from the replicated full-width draw,
    # gauges psum'd over the client axis before entering the row
    for name in ("scarlet", "mean"):
        for telemetry in (False, True):
            cfg = FLConfig(n_clients=n_clients, rounds=1, public_size=32,
                           public_per_round=8, n_classes=4, seed=0,
                           telemetry=telemetry)
            eng = ShardedFederatedDistillation(cfg, STRATEGIES[name](),
                                               mesh=mesh)
            fn, abstract = eng.carry_update_fn()
            label = name + ("+telemetry" if telemetry else "")
            findings.extend(check_shard_map_fn(
                fn, abstract, subject_prefix=f"engine[{label}]:"))
    return findings


def run() -> List[Finding]:
    return check_engine()
