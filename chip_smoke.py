#!/usr/bin/env python3
"""Smoke test of the SCARLET round loop on a TPU, through ``run_method``.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the shard engine across four chips

One chip runs, in order:

1. device check: a TPU, or exit non-zero naming the platform found
   (there is no CPU or interpret-mode fallback);
2. the golden configuration (``tests/test_golden_ledgers.py``) for all
   six method x codec cells, plus SCARLET's fused round: every ledger
   must equal ``tests/golden/*.json`` byte for byte;
3. SCARLET at paper scale (``FLConfig`` defaults with K=100 clients,
   Bernoulli participation 0.5, ``cache_delta+quant8`` uplink) for ten
   rounds as per-op scan, fused scan and active: the three ledgers must
   be byte-identical, accuracies finite, and both compiled scan
   programs must hold native kernels (``tpu_custom_call``); on its
   first round, fused scan and active must each meet the cross-engine
   conformance contract (``tests/test_engine_conformance.py``) against
   per-op scan.

``--chips 4`` runs only the shard engine on a 4-device mesh against
the scan engine on one device: byte-identical ten-round ledgers, client
state that spans all 4 devices, and the conformance contract on the
first round.

Lines tagged ``smoke-timing`` are smoke timings, not a benchmark.  The
last line is one JSON object naming the device; any failed check ends
the run non-zero before it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ROUNDS = 10
PAPER_KW = dict(cache_duration=25, beta=1.5, codec="cache_delta+quant8")
ONE_CHIP_WAYS = {
    "scan": dict(engine="scan"),
    "scan-fused": dict(engine="scan", fused_round=True),
    "active": dict(engine="active"),
}


def _paper_config(**kw):
    from repro.fl import FLConfig
    # FLConfig defaults (dim 32, hidden 64, depth 2, N=10, |P|=1000,
    # |P^t|=100, private 2000) at the paper's K=100; the default
    # eval_every=10 evaluates once, on the last round
    return FLConfig(n_clients=100, rounds=ROUNDS, **kw)


def _scenario():
    from repro.fl import Scenario, bernoulli_participation
    return Scenario(participation=bernoulli_participation(0.5))


def _engine(cfg, engine: str, fused_round: bool = False):
    """The engine ``run_method`` builds for ``PAPER_KW``, by hand: the
    smoke needs its compiled program and its device placement."""
    from repro.fl import (
        ActiveSetFederatedDistillation,
        ScannedFederatedDistillation,
        ShardedFederatedDistillation,
    )
    from repro.fl.strategies import STRATEGIES
    cls = {"scan": ScannedFederatedDistillation,
           "shard": ShardedFederatedDistillation,
           "active": ActiveSetFederatedDistillation}[engine]
    cfg = dataclasses.replace(cfg, uplink_codec=PAPER_KW["codec"],
                              fused_round=fused_round)
    return cls(cfg, STRATEGIES["scarlet"](beta=PAPER_KW["beta"]),
               cache_duration=PAPER_KW["cache_duration"],
               scenario=_scenario())


def _ledger_bytes(hist) -> bytes:
    import numpy as np
    return np.asarray([(r.uplink, r.downlink) for r in hist.ledger.rounds],
                      np.float64).tobytes()


def _check_finite(label: str, hist) -> None:
    accs = hist.server_acc + hist.client_acc
    assert accs and all(math.isfinite(a) for a in accs), (
        f"{label}: non-finite or missing accuracies {accs}")


def _timed(fn):
    from repro.obs.trace import now
    t0 = now()
    out = fn()  # every caller's result is read back to the host
    return out, now() - t0


def _run(label: str, cfg, way: dict):
    """One paper-scale run through ``run_method`` and one through its
    engine.  The scan and shard programs are compiled ahead of time
    first, so ``compile_s`` is the first compile in the process (a cold
    or a persistent-cache hit); active compiles its small programs on
    its first run and is timed again on a second."""
    from repro.fl import run_method
    eng = _engine(cfg, way["engine"], way.get("fused_round", False))
    timing = {}
    if way["engine"] != "active":  # one whole-run program
        compiled, timing["compile_s"] = _timed(
            lambda: eng.aot_lower(cfg.rounds).compile())
        assert "tpu_custom_call" in compiled.as_text(), (
            f"{label}: compiled round program holds no native kernel")
        print(f"check {label}: compiled program holds tpu_custom_call")
    hist, timing["run_method_wall_s"] = _timed(lambda: run_method(
        "scarlet", cfg, scenario=_scenario(), **PAPER_KW, **way))
    _check_finite(label, hist)
    if way["engine"] == "active":
        _, timing["first_run_s"] = _timed(eng.run)
    again, run_s = _timed(eng.run)
    timing["s_per_round"] = run_s / cfg.rounds
    if way["engine"] != "active":  # same rounds as run_method's run
        assert _ledger_bytes(again) == _ledger_bytes(hist), (
            f"{label}: engine ledger differs from run_method's")
    print(f"smoke-timing (not a benchmark) {label}: "
          + " ".join(f"{k}={v:.3f}" for k, v in timing.items()))
    return hist, eng


def golden_phase() -> None:
    from test_golden_ledgers import CASES, GOLDEN_DIR, summary_text
    runs = [(m, c, {}) for m, c in CASES]
    runs += [("scarlet", c, {"fused_round": True})
             for m, c in CASES if m == "scarlet"]
    for method, codec, kw in runs:
        label = f"{method}-{codec}" + ("-fused" if kw else "")
        want = (GOLDEN_DIR / f"{method}-{codec}.json").read_text()
        got = summary_text(method, codec, **kw)
        assert got == want, (
            f"golden {label}: chip ledger differs\nwant:\n{want}\ngot:\n{got}")
        print(f"check golden {label}: ledger equals tests/golden byte for byte")


def _round1_parity(cfg, ref: tuple, *others: tuple) -> None:
    """The cross-engine conformance contract (``assert_parity``) on the
    first round of ``cfg``, evaluated, between the ``ref`` way and each
    of ``others`` (``(engine, fused_round)`` pairs).  All start from the
    same state, so only the programs' rounding differs.  Over ten rounds
    that difference compounds through training past the conformance
    tolerance (it does on the CPU too, and between two per-op engines),
    so the ten-round runs are held to byte-identical ledgers instead."""
    from test_engine_conformance import assert_parity, fused_cache_atol
    cfg1 = dataclasses.replace(cfg, rounds=1, eval_every=1)
    runs = {}
    for engine, fused in (ref, *others):
        eng = _engine(cfg1, engine, fused)
        runs[engine + ("-fused" if fused else "")] = (eng, eng.run())
    (ref_label, (ref_eng, ref_hist)), *rest = runs.items()
    for label, (eng, hist) in rest:
        assert_parity(ref_eng, ref_hist, eng, hist, ledger="exact",
                      cache_atol=fused_cache_atol(PAPER_KW["codec"]))
        print(f"check {label} vs {ref_label}, round 1: conformance parity "
              f"holds (server acc {hist.server_acc[0]:.6f}/"
              f"{ref_hist.server_acc[0]:.6f}, client acc "
              f"{hist.client_acc[0]:.6f}/{ref_hist.client_acc[0]:.6f})")


def _gap(label: str, ha, hb) -> None:
    print(f"gap after {len(ha.ledger.rounds)} rounds, {label}: "
          f"server acc {ha.final_server_acc - hb.final_server_acc:+.6f}, "
          f"client acc {ha.final_client_acc - hb.final_client_acc:+.6f}")


def one_chip_phase() -> None:
    cfg = _paper_config()
    hists = {label: _run(label, cfg, way)[0]
             for label, way in ONE_CHIP_WAYS.items()}
    ref = _ledger_bytes(hists["scan"])
    for label, hist in hists.items():
        assert _ledger_bytes(hist) == ref, f"{label}: ledger differs from scan"
    print(f"check ledgers: {', '.join(hists)} byte-identical "
          f"({len(hists['scan'].ledger.rounds)} rounds, "
          f"{hists['scan'].ledger.summary()['cumulative_total']:.0f} bytes); "
          + " ".join(f"{k}: server={h.final_server_acc:.4f} "
                     f"client={h.final_client_acc:.4f}"
                     for k, h in hists.items()))
    for label in ("scan-fused", "active"):
        _gap(f"{label} vs scan", hists[label], hists["scan"])
    _round1_parity(cfg, ("scan", False), ("scan", True), ("active", False))


def four_chip_phase() -> None:
    import jax
    assert jax.device_count() == 4, (
        f"--chips 4 needs 4 devices, found {jax.device_count()}")
    cfg = _paper_config(mesh_spec="4")
    scan, _ = _run("scan-1dev", cfg, {"engine": "scan"})
    shard, eng = _run("shard-4dev", cfg, {"engine": "shard"})
    assert _ledger_bytes(shard) == _ledger_bytes(scan), (
        "shard ledger differs from scan")
    print("check shard vs scan: ledgers byte-identical "
          f"(shard server={shard.final_server_acc:.4f} "
          f"client={shard.final_client_acc:.4f})")
    _gap("shard vs scan", shard, scan)
    mesh_devices = set(eng.mesh.devices.flat)
    assert len(mesh_devices) == 4, f"mesh spans {len(mesh_devices)} devices"
    for leaf in jax.tree_util.tree_leaves(eng.client_params):
        held = {s.device for s in leaf.addressable_shards}
        assert held == mesh_devices, (
            f"client params {leaf.shape} live on {len(held)} devices")
        assert leaf.sharding.shard_shape(leaf.shape)[0] * 4 == leaf.shape[0], (
            f"client params {leaf.shape} not split 4 ways over clients")
    print("check shard placement: mesh and client parameters span 4 devices, "
          "clients split 4 ways")
    _round1_parity(cfg, ("scan", False), ("shard", False))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind})")
    print(f"device: kind={dev.device_kind} count={jax.device_count()} "
          f"jax={jax.__version__}", flush=True)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "tests")]
    from repro import compile_cache
    from repro.kernels.runtime import default_interpret
    print(f"compile cache: {compile_cache.enable()}")
    assert not default_interpret(), "Pallas kernels would run interpreted"

    if args.chips == 4:
        four_chip_phase()
    else:
        golden_phase()
        one_chip_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
