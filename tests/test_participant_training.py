"""Local training of the round's participants only.

``FederatedDistillation._train_selected`` trains at most
``Scenario.max_participants(K)`` client slots per cohort: the scan and
async engines gather the participants into a block of that many slots,
train it and scatter it back, while the host loop trains every client
and keeps the participants' results.  Local training draws no random
numbers, so both give each participant the same update, and a left-out
client keeps its params bit for bit.  Where the capacity is the whole
cohort the helper traces the train-all program unchanged.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fl import (
    AsyncFederatedDistillation,
    CohortSpec,
    FederatedDistillation,
    FLConfig,
    Heterogeneity,
    Outage,
    Participation,
    Scenario,
    ScannedFederatedDistillation,
    bernoulli_participation,
    fixed_fraction,
    full_participation,
)
from repro.fl.rounds import _select_cohorts
from repro.fl.strategies import STRATEGIES
from test_mlp_distill_kernel import ENGINE_CFG, _engine_run_span

K = 20
ROUNDS = 6
CFG = FLConfig(
    n_clients=K, n_classes=4, dim=8, rounds=ROUNDS, local_steps=2,
    distill_steps=2, public_size=48, public_per_round=10,
    private_size=200, alpha=0.5, eval_every=3, seed=0, hidden=12,
    cohorts=(CohortSpec(12, 12, 2), CohortSpec(8, 8, 1)),
)
HET = Heterogeneity(local_steps=tuple(1 + k % 3 for k in range(K)),
                    lr_scale=tuple(1.0 + 0.25 * (k % 2) for k in range(K)),
                    lr_decay=0.9)
# 4 drawn a round, conscription lifts every round to 6 (the capacity);
# rounds 3-4 leave only clients 16-19 reachable, so they run short
SCENARIO = Scenario(participation=fixed_fraction(0.2),
                    outages=tuple(Outage(k, 3, 4) for k in range(16)),
                    heterogeneity=HET, min_participants=6)
CAP = 6


def _leaves(params):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(params)]


def _rows(params, k):
    """Client ``k``'s params, from the per-cohort stacks."""
    sizes = [c.n_clients for c in CFG.cohorts]
    i = int(np.searchsorted(np.cumsum(sizes), k, side="right"))
    j = k - sum(sizes[:i])
    return [a[j] for a in _leaves(params[i])]


@pytest.mark.parametrize("engine_cls", [ScannedFederatedDistillation,
                                        AsyncFederatedDistillation],
                         ids=["scan", "async"])
def test_gathered_training_matches_host_loop(engine_cls):
    """One round per call of the device engine against a host-loop run:
    the same ledger to the byte, client params allclose, and every
    client left out of a round unchanged bit for bit."""
    strategy = STRATEGIES["scarlet"](beta=1.5)
    host = FederatedDistillation(CFG, strategy, cache_duration=3,
                                 scenario=SCENARIO, rng_backend="jax")
    dev = engine_cls(CFG, STRATEGIES["scarlet"](beta=1.5), cache_duration=3,
                     scenario=SCENARIO)
    h_host = host.run()

    counts, up, down = [], [], []
    for t in range(1, ROUNDS + 1):
        before = [_rows(dev.client_params, k) for k in range(K)]
        h = dev.run(1)
        up += [r.uplink for r in h.ledger.rounds]
        down += [r.downlink for r in h.ledger.rounds]
        part = host._draw_round(t)[0]
        counts.append(int(part.sum()))
        for k in np.nonzero(~part)[0]:
            for a, b in zip(_rows(dev.client_params, k), before[k]):
                np.testing.assert_array_equal(a, b)
    # the gather ran, at capacity and short of it, with conscription
    assert dev.local_train_clients == 2 * CAP
    assert max(counts) == CAP and min(counts) < CAP, counts

    np.testing.assert_array_equal(up, [r.uplink for r in h_host.ledger.rounds])
    np.testing.assert_array_equal(
        down, [r.downlink for r in h_host.ledger.rounds])
    for got, want in zip(dev.client_params, host.client_params):
        for a, b in zip(_leaves(got), _leaves(want)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("scenario,want", [
    (Scenario(participation=full_participation()), K),
    (Scenario(participation=bernoulli_participation(0.3)), K),
    (Scenario(participation=fixed_fraction(0.2)), 4),
    (Scenario(participation=fixed_fraction(0.01)), 1),
    (Scenario(participation=fixed_fraction(0.2), min_participants=6), 6),
    (Scenario(participation=fixed_fraction(0.5), min_participants=0), 10),
    (Scenario(participation=full_participation(), min_participants=50), K),
    (Scenario(participation=fixed_fraction(0.2), min_participants=50), K),
], ids=["full", "bernoulli", "fraction", "fraction-floor", "conscription",
        "no-minimum", "full-minimum", "minimum-over-K"])
def test_max_participants(scenario, want):
    """The bound, and no round's draw above it, under random outages."""
    assert scenario.max_participants(K) == want
    for s in range(16):
        key = jax.random.PRNGKey(s)
        offline = jax.random.uniform(jax.random.fold_in(key, 1), (K,)) < 0.3
        mask = scenario.participation_mask_device(key, offline)
        assert int(jnp.sum(mask)) <= want


def test_max_participants_rejects_unknown_kind():
    with pytest.raises(ValueError):
        Participation("everyone").max_participants(K)


@pytest.mark.parametrize("cohorts,het", [
    (None, None), (CFG.cohorts, HET)], ids=["homogeneous", "cohorts-het"])
@pytest.mark.parametrize("participation", [full_participation(),
                                           bernoulli_participation(0.5)],
                         ids=["full", "bernoulli"])
def test_full_capacity_traces_train_all(cohorts, het, participation):
    """Where every client can take part, the helper's program is the
    train-all-then-select program, equation for equation."""
    cfg = dataclasses.replace(CFG, cohorts=cohorts)
    eng = ScannedFederatedDistillation(
        cfg, STRATEGIES["scarlet"](beta=1.5), cache_duration=3,
        scenario=Scenario(participation=participation, heterogeneity=het))
    args = (eng.client_params, jnp.int32(3), jnp.ones(K, bool))
    got = jax.make_jaxpr(eng._train_selected)(*args)
    want = jax.make_jaxpr(lambda p, t, keep: _select_cohorts(
        eng._local_train_all(p, t), p, eng.models.split(keep)))(*args)
    assert str(got) == str(want)
    assert eng.local_train_clients == K


def test_partial_capacity_gathers():
    """Under a fixed fraction the helper trains a gathered block and
    scatters it back, one scatter per leaf (the gradients' scatter-adds
    aside), in place of the train-all program."""
    eng = ScannedFederatedDistillation(
        CFG, STRATEGIES["scarlet"](beta=1.5), cache_duration=3,
        scenario=SCENARIO)
    args = (eng.client_params, jnp.int32(3), jnp.ones(K, bool))
    jaxpr = str(jax.make_jaxpr(eng._train_selected)(*args))
    train_all = str(jax.make_jaxpr(lambda p, t, keep: _select_cohorts(
        eng._local_train_all(p, t), p, eng.models.split(keep)))(*args))
    n_leaves = len(jax.tree_util.tree_leaves(eng.client_params))
    assert jaxpr.count("scatter[") == n_leaves
    assert train_all.count("scatter[") == 0
    assert eng.local_train_clients == 2 * CAP


@pytest.mark.parametrize("participation,want", [(1.0, 8), (0.6, 5)],
                         ids=["full", "fraction"])
def test_engine_run_span_counts_local_train_clients(tmp_path, participation,
                                                    want):
    cfg = FLConfig(**dict(ENGINE_CFG, participation=participation))
    eng = ScannedFederatedDistillation(cfg, STRATEGIES["scarlet"](beta=1.5),
                                       cache_duration=2)
    with jax.profiler.trace(str(tmp_path)):  # the first call traces
        eng.run(2)
    assert _engine_run_span(tmp_path)["local_train_clients"] == want
