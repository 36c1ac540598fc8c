"""Compile-only checks of the ADC kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed next to jax, lowers each
kernel for a chip it is only told about, and refuses what the chip would
refuse (scoped VMEM, tiling). Interpret-mode parity tests cannot see those
refusals. ``interpret=False`` is passed explicitly because the host
platform is CPU, where the kernels would otherwise be interpreted.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and pytest-xdist
workers all import this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.pq_adc import (pq_adc_gather_scores_onehot,
                                  pq_adc_gather_topk_pallas,
                                  pq_adc_topk_pallas)

# the README pipeline's code stage, pq16x256, at a serving batch
Q, M, K = 256, 16, 256
N_SHARED = 1 << 16          # shared-codes rows
C_GATHER = 8192             # gathered candidates per query
TOP = 64                    # the rr64 candidate budget


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                     # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back from the persistent
    # cache without a chip; keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("lut_dtype", ["f32", "bf16", "int8"])
def test_shared_kernel_compiles_for_v5e(one_chip, lut_dtype):
    f = jax.jit(lambda t, c: pq_adc_topk_pallas(
        t, c, TOP, interpret=False, lut_dtype=lut_dtype))
    compiled = f.lower(_shape(one_chip, (Q, M, K), jnp.float32),
                       _shape(one_chip, (N_SHARED, M), jnp.uint8)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("lut_dtype", ["f32", "bf16", "int8"])
def test_gather_kernel_compiles_for_v5e(one_chip, lut_dtype):
    """Default blocks at M=16: an unrolled subspace loop needed 16.2 MiB
    (f32) to 29.3 MiB (int8) of the 16 MiB scoped VMEM."""
    f = jax.jit(lambda t, c, b: pq_adc_gather_topk_pallas(
        t, c, b, TOP, interpret=False, lut_dtype=lut_dtype))
    compiled = f.lower(_shape(one_chip, (Q, M, K), jnp.float32),
                       _shape(one_chip, (Q, C_GATHER, M), jnp.uint8),
                       _shape(one_chip, (Q, C_GATHER), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("lut_dtype", ["f32", "bf16", "int8"])
def test_onehot_scorer_compiles_for_v5e_without_gather(one_chip, lut_dtype):
    """The jnp scorer's TPU lowering: no element gather from the tables,
    and no one-hot of Q*C*K bytes or more held in memory (the gather held
    ~1.2 GB of temporaries at this shape)."""
    f = jax.jit(lambda t, c, b: pq_adc_gather_scores_onehot(
        t, c, b, lut_dtype=lut_dtype))
    compiled = f.lower(_shape(one_chip, (Q, M, K), jnp.float32),
                       _shape(one_chip, (Q, C_GATHER, M), jnp.uint8),
                       _shape(one_chip, (Q, C_GATHER), jnp.float32)).compile()
    assert "gather(" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < Q * C_GATHER * K
