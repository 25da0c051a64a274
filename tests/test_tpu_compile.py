"""Compile the main path's Pallas kernels for a TPU v5e chip.

No chip is needed: the TPU compiler is installed with JAX and compiles
for a chip that is described, not attached.  Interpret mode (what every
other kernel test runs on the CPU) cannot see what this sees: Mosaic
refuses a kernel whose live set overflows the core's scoped VMEM, as
``fused_round`` in ``quant``/``delta`` mode did at K >= 100.  Each case
asserts that the compiled program holds the native kernel
(``tpu_custom_call``).

The topology is described in a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all
import this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import era_kernel, mlp_distill_kernel, quant_kernel, round_kernel


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("K,B,N", [(100, 100, 10), (1000, 1000, 100)])
def test_enhanced_era_fused_compiles(one_chip, K, B, N):
    text = _compile_text(
        lambda z: era_kernel.enhanced_era_fused(z, 1.5, interpret=False),
        one_chip, (K, B, N))
    assert "tpu_custom_call" in text


def test_quant_kernel_compiles(one_chip):
    text = _compile_text(
        lambda z: quant_kernel.quantize_dequantize(z, 8, interpret=False),
        one_chip, (10_000, 10))
    assert "tpu_custom_call" in text


FUSED_MODES = [("identity", None), ("quant", 8), ("delta", 8)]


@pytest.mark.parametrize("K", [100, 1000])
@pytest.mark.parametrize("mode,bits", FUSED_MODES,
                         ids=[f"{m}{b or ''}" for m, b in FUSED_MODES])
def test_fused_round_compiles(one_chip, mode, bits, K):
    M, N = 100, 10
    if mode == "delta":
        fn = lambda z, w, b: round_kernel.fused_round(  # noqa: E731
            z, w, 1.5, b, mode=mode, bits=bits, interpret=False)
        shapes = ((K, M, N), (K,), (M, N))
    else:
        fn = lambda z, w: round_kernel.fused_round(  # noqa: E731
            z, w, 1.5, mode=mode, bits=bits, interpret=False)
        shapes = ((K, M, N), (K,))
    assert "tpu_custom_call" in _compile_text(fn, one_chip, *shapes)


@pytest.mark.parametrize("per_client", [False, True], ids=["shared", "per_client"])
def test_mlp_distill_compiles(one_chip, per_client):
    """Client distillation at FedAvg's 2NN widths on 1,000 public rows:
    the working set a client holds in VMEM fits, and layer 0's weight
    stack enters the kernel with no layout copy (its (K, 784, 200) stack
    keeps 784 minor, which is the kernel's W0^T block)."""
    K, m, widths = 100, 1000, (784, 200, 200, 10)
    S = lambda s, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        s, dt, sharding=one_chip)
    params = {}
    for i, (a, c) in enumerate(zip(widths[:-1], widths[1:])):
        params[f"w{i}"], params[f"b{i}"] = S((K, a, c)), S((K, c))
    t = S((K, m, 10) if per_client else (m, 10))
    text = jax.jit(
        lambda p, x, t, keep: mlp_distill_kernel.mlp_distill(
            p, x, t, keep, lr=0.1, steps=5, interpret=False),
        donate_argnums=0).lower(params, S((m, 784)), t,
                                S((K,), jnp.bool_)).compile().as_text()
    assert "tpu_custom_call" in text
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and "f32[100,784,200]" in ln.split(" copy(")[0]]


def _stack_copies(text, stack):
    return [ln for ln in text.splitlines()
            if (" copy(" in ln or " copy-start(" in ln)
            and stack in ln.split("=", 1)[1].split("(")[0]]


def test_gathered_round_adds_no_stack_copy(one_chip, monkeypatch):
    """The scan engine's 20-round program at FedAvg's 2NN widths, 10 of
    100 clients a round (local training of a gathered block, scattered
    back into the stack that ``mlp_distill`` updates in place), holds no
    more layout copies of the f32[100,784,200] first-layer stack than
    the same round that trains every client and selects.  The private
    and public sets are cut, which the stack's layout does not see."""
    import numpy as np
    from repro.fl import (FLConfig, Scenario, ScannedFederatedDistillation,
                          fixed_fraction)
    from repro.fl.rounds import _select_cohorts
    from repro.fl.scan_engine import compiler_options
    from repro.fl.strategies import STRATEGIES

    cfg = FLConfig(n_clients=100, n_classes=10, dim=784, hidden=200,
                   mlp_depth=2, public_size=1000, public_per_round=1000,
                   private_size=2000, rounds=20, eval_every=10,
                   participation=0.1, fused_round=True,
                   uplink_codec="cache_delta+quant8", seed=1)
    eng = ScannedFederatedDistillation(
        cfg, STRATEGIES["scarlet"](beta=1.5), cache_duration=50,
        scenario=Scenario(participation=fixed_fraction(0.1)))
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        eng._call_args(np.zeros(20, bool)))
    # the backend the round is traced for decides the kernels' native
    # lowering and the TPU compiler options
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def stack_copies():
        fn = jax.jit(lambda carry, xs: jax.lax.scan(eng._round_device,
                                                    carry, xs),
                     compiler_options=compiler_options())
        text = fn.lower(*args).compile().as_text()
        assert "tpu_custom_call" in text
        return _stack_copies(text, "f32[100,784,200]")

    gathered = stack_copies()
    assert eng.local_train_clients == 10
    eng._train_selected = lambda p, t, keep: _select_cohorts(
        eng._local_train_all(p, t), p, eng.models.split(keep))
    assert len(gathered) <= len(stack_copies()), gathered
