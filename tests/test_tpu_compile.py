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

from repro.kernels import era_kernel, quant_kernel, round_kernel


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
