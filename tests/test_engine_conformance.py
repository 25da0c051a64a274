"""Cross-engine conformance matrix: host x scan x shard.

All three engines draw subsets/participation from the identical jax key
stream, so for every (strategy, participation, codec) cell of the matrix
the same rounds run with the same cohorts.  Each cell is one test item
that runs every engine exactly once and asserts both pairwise
contracts (one item per cell also keeps xdist from recomputing cells):

- **host vs scan** — ledger allclose at float32 exactness (the host loop
  computes costs in python float64, the device engines in float32),
  metrics/cache allclose;
- **scan vs shard** — ledger **byte-identical** (both engines derive
  every cost from exact small-integer counts in float32; the shard
  engine's psum reductions of exact integers are order-independent),
  metrics/cache allclose (aggregation reduction order differs).

The shard runs use ``make_test_mesh``-shaped meshes on the 8 forced
host devices (see ``conftest.py``), so the ``shard_map`` paths —
two-phase aggregation psum, shard-aware byte accounting, conscription
slicing — execute for real in every environment.

A second, cohort matrix re-asserts both contracts for heterogeneous
client-model cohorts (``repro.fl.cohorts``): different architectures
per client block, identical ledger/cache/metric guarantees, plus
per-cohort accuracy columns allclose across engines.
"""
import dataclasses

import numpy as np
import pytest

from repro.fl import (
    ActiveSetFederatedDistillation,
    AsyncFederatedDistillation,
    CohortSpec,
    FederatedDistillation,
    FLConfig,
    Outage,
    Scenario,
    ScannedFederatedDistillation,
    ShardedFederatedDistillation,
    bernoulli_participation,
    fixed_fraction,
    full_participation,
)
from repro.fl.strategies import STRATEGIES

CFG = FLConfig(
    n_clients=4, n_classes=4, dim=8, rounds=3, local_steps=2,
    distill_steps=2, public_size=48, public_per_round=10,
    private_size=64, alpha=0.5, eval_every=2, seed=0, hidden=12,
    mesh_spec="2x4",
)

STRATEGY_KW = {
    "scarlet": dict(beta=1.5),
    "dsfl": dict(T=0.1),
    "mean": dict(),
}
# scarlet runs with its synchronized cache so cache_delta coding and
# catch-up packages are exercised against real cache state
CACHE_D = {"scarlet": 3, "dsfl": 0, "mean": 0}

PARTICIPATIONS = {
    "full": Scenario(participation=full_participation()),
    "bernoulli": Scenario(participation=bernoulli_participation(0.5)),
    # outage windows + fixed-fraction sampling: returning stragglers
    # exercise the catch-up byte accounting (dense/psum'd vs per-package)
    "outage": Scenario(participation=fixed_fraction(0.5),
                       outages=(Outage(0, 2, 3), Outage(2, 1, 2))),
}

CODECS = ("identity", "quant8", "cache_delta+quant8")

MATRIX = [(s, p, c) for s in sorted(STRATEGY_KW)
          for p in sorted(PARTICIPATIONS) for c in CODECS]


# ---------------------------------------------------------------------------
# Parity assertion, shared with tests/test_scan_parity.py
# ---------------------------------------------------------------------------

def assert_parity(eng_a, hist_a, eng_b, hist_b, *, ledger="close",
                  cache_atol=1e-5):
    """Engine/History pair parity.  ``ledger="exact"`` demands bitwise
    byte-identity (device engine vs device engine); ``"close"`` allows
    float32-level rounding (host float64 vs device float32).

    ``cache_atol`` bounds the cached teacher values.  Cells with a
    *lossy* wire codec pass one quantization step here: a sub-ulp
    cross-engine difference in the pre-codec soft-labels can flip a
    quantization bucket, which the decode amplifies to a full step
    (~range/255 for quant8) — inherent to lossy codecs, not drift."""
    up_a = [r.uplink for r in hist_a.ledger.rounds]
    up_b = [r.uplink for r in hist_b.ledger.rounds]
    down_a = [r.downlink for r in hist_a.ledger.rounds]
    down_b = [r.downlink for r in hist_b.ledger.rounds]
    assert len(up_a) == len(up_b)
    if ledger == "exact":
        np.testing.assert_array_equal(up_a, up_b)
        np.testing.assert_array_equal(down_a, down_b)
    else:
        np.testing.assert_allclose(up_a, up_b, rtol=1e-7)
        np.testing.assert_allclose(down_a, down_b, rtol=1e-7)
    # --- History metrics ----------------------------------------------
    assert hist_a.rounds == hist_b.rounds
    np.testing.assert_allclose(hist_a.server_acc, hist_b.server_acc, atol=1e-4)
    np.testing.assert_allclose(hist_a.client_acc, hist_b.client_acc, atol=1e-4)
    np.testing.assert_allclose(hist_a.cumulative_mb, hist_b.cumulative_mb,
                               rtol=1e-7)
    np.testing.assert_allclose(hist_a.server_val_loss, hist_b.server_val_loss,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(hist_a.client_val_loss, hist_b.client_val_loss,
                               rtol=1e-4, atol=1e-5)
    # per-cohort client accuracy (one column per model cohort; a single
    # column for homogeneous runs)
    np.testing.assert_allclose(hist_a.cohort_client_acc,
                               hist_b.cohort_client_acc, atol=1e-4)
    # --- cache state + sync bookkeeping -------------------------------
    np.testing.assert_array_equal(np.asarray(eng_a.cache_g.present),
                                  np.asarray(eng_b.cache_g.present))
    np.testing.assert_array_equal(np.asarray(eng_a.cache_g.ts),
                                  np.asarray(eng_b.cache_g.ts))
    np.testing.assert_allclose(np.asarray(eng_a.cache_g.values),
                               np.asarray(eng_b.cache_g.values),
                               rtol=0, atol=cache_atol)
    np.testing.assert_array_equal(eng_a.last_sync, eng_b.last_sync)


def _build(engine_cls, name, participation, codec, **kw):
    cfg = dataclasses.replace(CFG, uplink_codec=codec)
    eng = engine_cls(cfg, STRATEGIES[name](**STRATEGY_KW[name]),
                     cache_duration=CACHE_D[name],
                     scenario=PARTICIPATIONS[participation], **kw)
    return eng, eng.run()


@pytest.mark.parametrize("name,participation,codec", MATRIX,
                         ids=["-".join(p) for p in MATRIX])
def test_engine_conformance_cell(name, participation, codec):
    """One matrix cell: each engine runs once, then both pairwise parity
    contracts are asserted.  A single test item per cell keeps the
    three engine runs computed exactly once per pytest/xdist worker."""
    host = _build(FederatedDistillation, name, participation, codec,
                  rng_backend="jax")
    scan = _build(ScannedFederatedDistillation, name, participation, codec)
    shard = _build(ShardedFederatedDistillation, name, participation, codec)
    assert_parity(*host, *scan, ledger="close")
    assert_parity(*scan, *shard, ledger="exact")


# ---------------------------------------------------------------------------
# Heterogeneous client-model cohorts (repro.fl.cohorts): host x scan x
# shard over {scarlet, dsfl} x {2-cohort, 3-cohort} x {identity,
# cache_delta+quant8}.  Soft-label shapes are architecture-independent,
# so the exact engine contracts must hold unchanged: scan<->shard
# ledgers byte-identical, host<->scan allclose at float32 exactness,
# per-cohort metrics allclose everywhere.  K=8 so every cohort block
# splits evenly over the 2-way "data" axis of the 2x4 mesh.
# ---------------------------------------------------------------------------

COHORTS = {
    "2cohort": (CohortSpec(4, 16, 2), CohortSpec(4, 8, 1)),
    "3cohort": (CohortSpec(4, 16, 2), CohortSpec(2, 8, 1),
                CohortSpec(2, 24, 3)),
}
COHORT_CODECS = ("identity", "cache_delta+quant8")
COHORT_MATRIX = [(s, co, c) for s in ("dsfl", "scarlet")
                 for co in sorted(COHORTS) for c in COHORT_CODECS]


@pytest.mark.parametrize("name,cohort,codec", COHORT_MATRIX,
                         ids=["-".join(p) for p in COHORT_MATRIX])
def test_cohort_conformance_cell(name, cohort, codec):
    cfg = dataclasses.replace(CFG, n_clients=8, cohorts=COHORTS[cohort],
                              uplink_codec=codec)
    sc = PARTICIPATIONS["bernoulli"]

    def build(engine_cls, **kw):
        eng = engine_cls(cfg, STRATEGIES[name](**STRATEGY_KW[name]),
                         cache_duration=CACHE_D[name], scenario=sc, **kw)
        return eng, eng.run()

    host = build(FederatedDistillation, rng_backend="jax")
    scan = build(ScannedFederatedDistillation)
    shard = build(ShardedFederatedDistillation)
    assert len(host[1].cohort_client_acc[0]) == len(COHORTS[cohort])
    # lossy cells tolerate one quant8 step on the widest possible row
    # (range ~1 -> 1/255 ~ 3.9e-3); identity cells stay tight
    cache_atol = 1e-5 if codec == "identity" else 5e-3
    assert_parity(*host, *scan, ledger="close", cache_atol=cache_atol)
    assert_parity(*scan, *shard, ledger="exact", cache_atol=cache_atol)


def test_shard_engine_rejects_indivisible_cohorts():
    """Every cohort block must split evenly over the client axis — a
    5+3 split cannot shard 2-ways even though K=8 can."""
    cfg = dataclasses.replace(
        CFG, n_clients=8, cohorts=(CohortSpec(5, 16, 2), CohortSpec(3, 8, 1)))
    with pytest.raises(ValueError, match="not divisible over"):
        ShardedFederatedDistillation(
            cfg, STRATEGIES["scarlet"](**STRATEGY_KW["scarlet"]),
            cache_duration=3)


# ---------------------------------------------------------------------------
# Fused round fast path (FLConfig.fused_round): the per-op conformance
# matrix above stays untouched; these cells assert that turning the
# fused kernel on changes NOTHING observable — ledgers byte-identical
# to the per-op scan run (comm accounting is analytic, counts are
# unaffected) and metrics/cache allclose (on CPU the interpreter runs
# the identical f32 expression sequence, so they are in fact equal) —
# across scarlet x {bernoulli, outage} x every fusable codec, on both
# device engines.
# ---------------------------------------------------------------------------

FUSED_CODECS = ("identity", "quant8", "cache_delta", "cache_delta+quant8")
FUSED_MATRIX = [(p, c) for p in ("bernoulli", "outage") for c in FUSED_CODECS]


def fused_cache_atol(codec: str) -> float:
    """Cache band for fused vs per-op runs: one quant step for lossy
    codecs (native-TPU headroom; interpret mode is exact).
    ``chip_smoke.py`` holds its chip runs to the same band."""
    return 1e-5 if "quant" not in codec else 5e-3


@pytest.mark.parametrize("participation,codec", FUSED_MATRIX,
                         ids=["-".join(p) for p in FUSED_MATRIX])
def test_fused_round_conformance_cell(participation, codec):
    perop = _build(ScannedFederatedDistillation, "scarlet", participation,
                   codec)
    fused_cfg = dataclasses.replace(CFG, uplink_codec=codec, fused_round=True)

    def build_fused(engine_cls):
        eng = engine_cls(fused_cfg, STRATEGIES["scarlet"](beta=1.5),
                         cache_duration=CACHE_D["scarlet"],
                         scenario=PARTICIPATIONS[participation])
        return eng, eng.run()

    fused_scan = build_fused(ScannedFederatedDistillation)
    fused_shard = build_fused(ShardedFederatedDistillation)
    cache_atol = fused_cache_atol(codec)
    assert_parity(*perop, *fused_scan, ledger="exact", cache_atol=cache_atol)
    assert_parity(*fused_scan, *fused_shard, ledger="exact",
                  cache_atol=cache_atol)


def test_host_engine_ignores_fused_flag():
    """The host loop is the per-op reference: FLConfig.fused_round must
    not change its behavior (it has no fused path to take)."""
    cfg = dataclasses.replace(CFG, uplink_codec="quant8")
    on = FederatedDistillation(
        dataclasses.replace(cfg, fused_round=True),
        STRATEGIES["scarlet"](beta=1.5), cache_duration=3,
        scenario=PARTICIPATIONS["bernoulli"], rng_backend="jax")
    off = FederatedDistillation(
        cfg, STRATEGIES["scarlet"](beta=1.5), cache_duration=3,
        scenario=PARTICIPATIONS["bernoulli"], rng_backend="jax")
    assert_parity(on, on.run(), off, off.run(), ledger="exact")


# ---------------------------------------------------------------------------
# Active-set engine (repro.fl.active_engine): host-resident client
# store, O(m) gathered device compute.  Contract: ledger **byte-
# identical** to the scan engine (every cost input is an exact
# small-integer count evaluated by the same f32 expression) and
# float32-exact against the host loop; metrics/cache allclose (the
# gathered stack sums m rows where the dense engines sum K masked
# rows).  {scarlet, dsfl} x {bernoulli, outage} x {identity,
# cache_delta+quant8} per the engine's acceptance matrix.
# ---------------------------------------------------------------------------

ACTIVE_MATRIX = [(s, p, c) for s in ("dsfl", "scarlet")
                 for p in ("bernoulli", "outage")
                 for c in ("identity", "cache_delta+quant8")]


@pytest.mark.parametrize("name,participation,codec", ACTIVE_MATRIX,
                         ids=["-".join(p) for p in ACTIVE_MATRIX])
def test_active_engine_conformance_cell(name, participation, codec):
    host = _build(FederatedDistillation, name, participation, codec,
                  rng_backend="jax")
    scan = _build(ScannedFederatedDistillation, name, participation, codec)
    active = _build(ActiveSetFederatedDistillation, name, participation,
                    codec)
    cache_atol = 1e-5 if codec == "identity" else 5e-3
    assert_parity(*active, *scan, ledger="exact", cache_atol=cache_atol)
    assert_parity(*active, *host, ledger="close", cache_atol=cache_atol)


def test_active_engine_cohort_conformance():
    """Heterogeneous model cohorts: gather/scatter is per-cohort, so the
    mixed-architecture path must keep the byte-exact ledger contract."""
    cfg = dataclasses.replace(CFG, n_clients=8, cohorts=COHORTS["2cohort"])
    sc = PARTICIPATIONS["bernoulli"]

    def build(engine_cls):
        eng = engine_cls(cfg, STRATEGIES["scarlet"](beta=1.5),
                         cache_duration=3, scenario=sc)
        return eng, eng.run()

    scan = build(ScannedFederatedDistillation)
    active = build(ActiveSetFederatedDistillation)
    assert len(active[1].cohort_client_acc[0]) == 2
    assert_parity(*active, *scan, ledger="exact")


def test_active_engine_heterogeneous_schedules():
    """Per-client lr/step schedules are gathered rows, not K-stacks:
    the scheduled cells must still agree byte-exactly on the ledger."""
    from repro.fl import Heterogeneity

    het = Heterogeneity(local_steps=(1, 2, 3, 2),
                        lr_scale=(1.0, 0.5, 2.0, 1.0), lr_decay=0.9)
    sc = Scenario(participation=bernoulli_participation(0.7),
                  heterogeneity=het)
    scan = ScannedFederatedDistillation(
        CFG, STRATEGIES["scarlet"](beta=1.5), cache_duration=3, scenario=sc)
    active = ActiveSetFederatedDistillation(
        CFG, STRATEGIES["scarlet"](beta=1.5), cache_duration=3, scenario=sc)
    assert_parity(scan, scan.run(), active, active.run(), ledger="exact")


def test_active_engine_memmap_backing(tmp_path):
    """The memory-mapped store is an I/O detail: a memmap-backed run is
    byte-identical to the default RAM-backed run."""
    def build(**kw):
        eng = ActiveSetFederatedDistillation(
            CFG, STRATEGIES["scarlet"](beta=1.5), cache_duration=3,
            scenario=PARTICIPATIONS["bernoulli"], **kw)
        return eng, eng.run()

    ram = build()
    mm = build(store_backing="memmap", store_dir=str(tmp_path))
    assert_parity(*ram, *mm, ledger="exact")


def test_active_engine_telemetry_matches_scan():
    """Telemetry rows: exact counters byte-equal, gauges allclose."""
    from repro.obs.device import EXACT_FIELDS, GAUGE_FIELDS

    cfg = dataclasses.replace(CFG, telemetry=True)

    def build(engine_cls):
        eng = engine_cls(cfg, STRATEGIES["scarlet"](beta=1.5),
                         cache_duration=3,
                         scenario=PARTICIPATIONS["outage"])
        return eng.run()

    ts = build(ScannedFederatedDistillation).telemetry.stacks()
    ta = build(ActiveSetFederatedDistillation).telemetry.stacks()
    for f in EXACT_FIELDS:
        np.testing.assert_array_equal(ta[f], ts[f], err_msg=f)
    for f in GAUGE_FIELDS:
        np.testing.assert_allclose(ta[f], ts[f], atol=1e-5, err_msg=f)


def test_active_engine_rejects_bad_store_config():
    strat = STRATEGIES["scarlet"](beta=1.5)
    with pytest.raises(ValueError, match="directory"):
        ActiveSetFederatedDistillation(CFG, strat, cache_duration=3,
                                       store_backing="memmap")
    with pytest.raises(ValueError, match="backing"):
        ActiveSetFederatedDistillation(CFG, strat, cache_duration=3,
                                       store_backing="tape")


# ---------------------------------------------------------------------------
# Async engine (repro.fl.async_engine): buffered aggregation under a
# traffic model.  Conformance anchor: under the DEFAULT traffic model
# (always-on arrivals, zero latency, full windows, unit staleness) the
# async engine must be **byte-identical** to the scan engine on the
# ledger — dispatch and arrival coincide every round, so the split
# catch-up charge collapses to scan's single dispatch-time charge and
# the staleness hook is statically skipped — and allclose on metrics.
# {scarlet, dsfl, mean} x {full, bernoulli, outage} x {identity,
# quant8, cache_delta+quant8}, same cells as the host/scan/shard
# matrix.
# ---------------------------------------------------------------------------

ASYNC_MATRIX = [(s, p, c) for s in sorted(STRATEGY_KW)
                for p in sorted(PARTICIPATIONS)
                for c in ("identity", "quant8", "cache_delta+quant8")]


@pytest.mark.parametrize("name,participation,codec", ASYNC_MATRIX,
                         ids=["-".join(p) for p in ASYNC_MATRIX])
def test_async_engine_zero_delay_conformance_cell(name, participation, codec):
    scan = _build(ScannedFederatedDistillation, name, participation, codec)
    asyn = _build(AsyncFederatedDistillation, name, participation, codec)
    assert_parity(*asyn, *scan, ledger="exact")


def test_async_engine_telemetry_matches_scan():
    """Zero-delay async telemetry rows: exact counters byte-equal to
    scan (including the staleness histogram — arrive == participate),
    gauges allclose."""
    from repro.obs.device import EXACT_FIELDS, GAUGE_FIELDS

    cfg = dataclasses.replace(CFG, telemetry=True)

    def build(engine_cls):
        eng = engine_cls(cfg, STRATEGIES["scarlet"](beta=1.5),
                         cache_duration=3,
                         scenario=PARTICIPATIONS["outage"])
        return eng.run()

    ts = build(ScannedFederatedDistillation).telemetry.stacks()
    ta = build(AsyncFederatedDistillation).telemetry.stacks()
    for f in EXACT_FIELDS:
        np.testing.assert_array_equal(ta[f], ts[f], err_msg=f)
    for f in GAUGE_FIELDS:
        np.testing.assert_allclose(ta[f], ts[f], atol=1e-5, err_msg=f)


# ---------------------------------------------------------------------------
# Shard-engine specifics not covered by the matrix
# ---------------------------------------------------------------------------

def test_shard_engine_data_only_mesh():
    """A 4x1 mesh (one client per shard, no model axis) must agree with
    the 2x4 matrix mesh — the shard count is an implementation detail."""
    a, ha = _build(ShardedFederatedDistillation, "scarlet", "bernoulli",
                   "identity")
    cfg = dataclasses.replace(CFG, uplink_codec="identity", mesh_spec="4")
    b = ShardedFederatedDistillation(
        cfg, STRATEGIES["scarlet"](**STRATEGY_KW["scarlet"]),
        cache_duration=CACHE_D["scarlet"],
        scenario=PARTICIPATIONS["bernoulli"])
    hb = b.run()
    assert_parity(a, ha, b, hb, ledger="exact")


def test_shard_engine_heterogeneous_schedules():
    """Per-client local-step counts / lr scales ride the client shard
    (``lr_k``/``steps_k`` consts are partitioned): sharded and scanned
    runs must still agree byte-exactly on the ledger."""
    from repro.fl import Heterogeneity

    het = Heterogeneity(local_steps=(1, 2, 3, 2), lr_scale=(1.0, 0.5, 2.0, 1.0),
                        lr_decay=0.9)
    sc = Scenario(participation=bernoulli_participation(0.7),
                  heterogeneity=het)
    scan = ScannedFederatedDistillation(
        CFG, STRATEGIES["scarlet"](beta=1.5), cache_duration=3, scenario=sc)
    shard = ShardedFederatedDistillation(
        CFG, STRATEGIES["scarlet"](beta=1.5), cache_duration=3, scenario=sc)
    assert_parity(scan, scan.run(), shard, shard.run(), ledger="exact")


def test_shard_engine_rejects_bad_meshes():
    strat = STRATEGIES["scarlet"](beta=1.5)
    with pytest.raises(ValueError, match="divide evenly"):
        ShardedFederatedDistillation(
            dataclasses.replace(CFG, n_clients=6), strat, cache_duration=3,
            mesh="4x2")
    with pytest.raises(ValueError, match="unknown mesh_spec"):
        ShardedFederatedDistillation(CFG, strat, cache_duration=3,
                                     mesh="not-a-mesh")
    with pytest.raises(ValueError):  # scan-engine mode checks inherited
        ShardedFederatedDistillation(CFG, STRATEGIES["comet"](), mesh="2x4")


def test_run_method_shard_engine():
    from repro.fl import run_method

    cfg = dataclasses.replace(CFG, mesh_spec="4x2")
    h_scan = run_method("scarlet", cfg, cache_duration=3, beta=1.5,
                        engine="scan", rounds=2)
    h_shard = run_method("scarlet", cfg, cache_duration=3, beta=1.5,
                        engine="shard", rounds=2)
    np.testing.assert_array_equal(
        [r.uplink for r in h_scan.ledger.rounds],
        [r.uplink for r in h_shard.ledger.rounds])
    np.testing.assert_allclose(h_scan.server_acc, h_shard.server_acc,
                               atol=1e-4)
