"""Compile the decode kernel for a described TPU v5e chip at real widths.

Nothing runs: the TPU compiler is installed without a chip attached and
compiles against a described topology, so block-tiling and VMEM errors
that interpret mode cannot see fail here. The topology is described
inside a fixture — never while a module is imported — so every pytest
worker collects the same tests and only the worker given this file
loads the TPU library.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.kernel import decode_attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip executable is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("two_pass", [False, True],
                         ids=["fused", "two_pass"])
@pytest.mark.parametrize("hq,hkv,d", [(24, 8, 128), (32, 8, 80)],
                         ids=["phi4_mini", "h2o_danube"])
def test_decode_kernel_compiles_for_v5e(one_chip, hq, hkv, d, two_pass):
    b, s = 4, 2048

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = jax.jit(lambda q, k, v, m: decode_attention(
        q, k, v, m, threshold=3.0, exact_two_pass=two_pass))
    compiled = fn.lower(spec((b, hq, d), jnp.bfloat16),
                        spec((b, hkv, s, d), jnp.bfloat16),
                        spec((b, hkv, s, d), jnp.bfloat16),
                        spec((b, hq, s), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
