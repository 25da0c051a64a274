"""The client-distillation VMEM kernel (``repro.kernels.mlp_distill_kernel``)
in interpret mode: it matches its bfloat16-operand jnp oracle tightly and
``rounds.distill_v`` (float32 on the CPU) within bfloat16 rounding; the
engines' dispatch takes it only on TPU for shapes that fit, and the
scan engine reports how many clients it takes."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fl.config import FLConfig
from repro.fl.rounds import distill_v
from repro.fl.scan_engine import ScannedFederatedDistillation
from repro.fl.strategies import STRATEGIES
from repro.kernels import mlp_distill_kernel as mk
from repro.kernels import ref
from repro.models.resnet import init_mlp

K, DIM, N_CLS, LR = 3, 32, 10, 0.1
KEEP = jnp.array([True, False, True])
# bfloat16's unit roundoff: each matmul operand is rounded to 8 bits
BF16_U = 2.0 ** -8


def _problem(depth, hidden, m, per_client, zeros, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), K)
    params = jax.vmap(lambda k: init_mlp(k, DIM, N_CLS, hidden, depth))(keys)
    params = jax.tree_util.tree_map(lambda a: a + 0.01, params)  # nonzero b
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (m, DIM))
    shape = (K, m, N_CLS) if per_client else (m, N_CLS)
    t = jax.nn.softmax(2 * jax.random.normal(jax.random.PRNGKey(seed + 2), shape))
    if zeros:
        # exact zeros: the teacher's clip path, and pre-activations of
        # exactly 0 (zero rows into units with zero bias), where relu'
        # is 0
        t = t.at[..., : m // 2 + 1, 3].set(0.0)
        x = x.at[: m // 4 + 1].set(0.0)
        params["b0"] = params["b0"].at[:, ::2].set(0.0)
    return params, x, t


def _change_gap(got, want, p0):
    """Per leaf: norm of the gap between two updates over the norm of
    ``want``'s change from ``p0``."""
    return {k: float(jnp.linalg.norm(got[k] - want[k])
                     / jnp.linalg.norm(want[k] - p0[k])) for k in p0}


# every value of each axis appears: depth 1-3, hidden 12 and 200, m 8 and
# 1000, shared and per-client teacher, a teacher with exact zeros, 1 and
# 5 steps
CASES = [
    (1, 12, 8, False, False, 1),
    (2, 12, 8, True, True, 5),
    (3, 12, 1000, False, True, 1),
    (2, 200, 1000, False, False, 5),
    (1, 200, 8, True, True, 5),
    (3, 200, 8, False, False, 1),
    (2, 12, 1000, True, False, 5),
    (1, 200, 1000, True, True, 1),
]


@pytest.mark.parametrize("depth,hidden,m,per_client,zeros,steps", CASES)
def test_kernel_matches_oracle_and_xla(depth, hidden, m, per_client, zeros,
                                       steps):
    params, x, t = _problem(depth, hidden, m, per_client, zeros)
    got = mk.mlp_distill(params, x, t, KEEP, lr=LR, steps=steps,
                         interpret=True)
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(params)
    # clients left out keep their params bit for bit
    for k in params:
        np.testing.assert_array_equal(got[k][1], params[k][1])
    kept = jnp.asarray([0, 2])
    sub = lambda p: {k: v[kept] for k, v in p.items()}  # noqa: E731
    oracle = ref.mlp_distill(params, x, t, KEEP, LR, steps)
    # Sums taken in another order flip a few bfloat16 roundings, and
    # steps amplify the flips: the oracle on its rows in another order
    # is as far from itself (about 1e-3 after five steps on 1,000 rows
    # at width 200), so that distance widens the tight bound.
    perm = jax.random.permutation(jax.random.PRNGKey(9), m)
    shuffled = ref.mlp_distill(params, x[perm], t[..., perm, :], KEEP, LR,
                               steps)
    floor = max(_change_gap(sub(shuffled), sub(oracle), sub(params)).values())
    gaps = _change_gap(sub(got), sub(oracle), sub(params))
    assert max(gaps.values()) < 1e-5 + 2 * floor, (gaps, floor)
    # Against the float32 XLA path (the CPU's): rounding each matmul's
    # operands to bfloat16, with up to eight matmuls chained into a
    # gradient, cancelling sums amplifying it, reads up to 17 units of
    # bfloat16 roundoff here; a wrong formula reads hundreds.
    tk = t if t.ndim == 3 else jnp.broadcast_to(t, (K,) + t.shape)
    xla = distill_v(params, x, tk, LR, steps)
    gaps = _change_gap(sub(got), sub(xla), sub(params))
    assert max(gaps.values()) < 32 * BF16_U, gaps


# -- dispatch --------------------------------------------------------------

CELL = (784, 200, 200, 10)  # FedAvg's 2NN on 1,000 public rows


def _stack(widths, k=100):
    return {f"{p}{i}": jax.ShapeDtypeStruct(
                (k, a, c) if p == "w" else (k, c), jnp.float32)
            for i, (a, c) in enumerate(zip(widths[:-1], widths[1:]))
            for p in "wb"}


@pytest.mark.parametrize("widths,m,on_tpu,want", [
    (CELL, 1000, True, True),
    (CELL, 1000, False, False),                   # CPU: the XLA path
    ((784, 2048, 2048, 10), 1000, True, False),   # over the VMEM budget
    ((784, 200, 200, 10), 40_000, True, False),   # too many rows
    ((8, 12, 12, 4), 64, True, False),            # golden widths: unaligned
    ((784, 10), 1000, True, False),               # linear: W0^T sublanes 10
    ((32, 16, 10), 24, True, True),
], ids=["cell", "cell-cpu", "wide", "rows", "golden", "linear", "toy"])
def test_dispatch_predicate(monkeypatch, widths, m, on_tpu, want):
    monkeypatch.setattr(mk, "default_interpret", lambda: not on_tpu)
    assert mk.use_kernel(_stack(widths), m) is want
    assert mk.mlp_widths(_stack(widths)) == widths


def test_cell_working_set_fits_the_budget():
    assert mk.working_set_bytes(CELL, 1000) <= mk.VMEM_BUDGET
    assert mk.working_set_bytes((784, 2048, 2048, 10), 1000) > mk.VMEM_BUDGET


ENGINE_CFG = dict(n_clients=8, rounds=3, public_size=64, public_per_round=16,
                  n_classes=4, dim=16, hidden=16, private_size=64,
                  local_steps=1, distill_steps=2, seed=0, participation=0.6,
                  uplink_codec="cache_delta+quant8")


def _scan_run(monkeypatch, kernel, rounds=3):
    if kernel:  # the TPU decision, with the kernel interpreted on the CPU
        monkeypatch.setattr(mk, "use_kernel", lambda p, m: True)
    eng = ScannedFederatedDistillation(FLConfig(**ENGINE_CFG),
                                       STRATEGIES["scarlet"](beta=1.5),
                                       cache_duration=2)
    p0 = eng.client_params[0]
    hist = eng.run(rounds)
    monkeypatch.undo()
    return eng, hist, p0


def test_scan_engine_kernel_path_matches_xla_path(monkeypatch):
    xla, hx, p0 = _scan_run(monkeypatch, kernel=False)
    ker, hk, _ = _scan_run(monkeypatch, kernel=True)
    assert (xla.distill_kernel_clients, ker.distill_kernel_clients) == (0, 8)
    assert ([(r.uplink, r.downlink) for r in hk.ledger.rounds]
            == [(r.uplink, r.downlink) for r in hx.ledger.rounds])
    for name in ("present", "ts"):
        np.testing.assert_array_equal(np.asarray(getattr(ker.cache_g, name)),
                                      np.asarray(getattr(xla.cache_g, name)))
    # two rounds distill (the first has no teacher); local training and
    # the teachers carry the bfloat16 rounding on (10 units here)
    gaps = _change_gap(ker.client_params[0], xla.client_params[0], p0)
    assert max(gaps.values()) < 16 * BF16_U, gaps


def _engine_run_span(logdir):
    path = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))[-1]
    prof = jax.profiler.ProfileData.from_file(path)
    (run,) = [dict(e.stats) for plane in prof.planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name == "engine.run"]
    return run


@pytest.mark.parametrize("kernel,want", [(False, 0), (True, 8)],
                         ids=["xla", "kernel"])
def test_engine_run_span_counts_kernel_clients(monkeypatch, tmp_path, kernel,
                                               want):
    if kernel:
        monkeypatch.setattr(mk, "use_kernel", lambda p, m: True)
    eng = ScannedFederatedDistillation(FLConfig(**ENGINE_CFG),
                                       STRATEGIES["scarlet"](beta=1.5),
                                       cache_duration=2)
    with jax.profiler.trace(str(tmp_path)):  # the first call traces
        eng.run(2)
    assert _engine_run_span(tmp_path)["distill_kernel_clients"] == want
