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


# Small widths with phi4-mini's head size: 4 layers, 12 q / 4 kv heads of
# 128, 4 slots x 256 ring rows.
_SMALL = dict(num_layers=4, d_model=512, num_heads=12, num_kv_heads=4,
              d_ff=1024, vocab_size=1024, head_dim=128)


@pytest.mark.parametrize("variant", ["dense", "sliding", "a3_probe"])
def test_decode_block_updates_cache_in_place_for_v5e(one_chip, variant):
    """The donated cache updates in its own buffers: the decode layer
    scan carries the mutable leaves and writes each layer's slice back,
    so the compiled ``jit_decode_block`` holds no copy of a whole cache
    leaf (scanned as xs -> ys, the K and V leaves each came back through
    a fresh buffer and a whole-leaf copy into the donated one)."""
    from repro.config import A3Config, AttentionKind, ModelConfig
    from repro.models import decoder as dec
    from repro.serve.engine import CTRL_COLS, make_decode_block_step

    b, s = 4, 256
    extra = ({"attention_kind": AttentionKind.SLIDING, "window_size": 128}
             if variant == "sliding" else {})
    cfg = ModelConfig("small", "dense", **_SMALL, **extra)
    use_a3 = variant == "a3_probe"
    a3 = A3Config.conservative() if use_a3 else A3Config()
    params = jax.eval_shape(
        lambda: dec.init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: dec.init_cache(cfg, b, s, a3=use_a3))

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    ints = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
    ctrl = jax.ShapeDtypeStruct((b, CTRL_COLS), jnp.int32, sharding=one_chip)
    # resort_every=0 leaves out the in-graph A^3 re-sort: a conditional,
    # taken only when a lane is due, whose sort lays whole sorted-key
    # leaves out anew; the layer scan under test is the same either way.
    fn = jax.jit(make_decode_block_step(cfg, a3, probe=use_a3),
                 donate_argnums=(1,))
    text = fn.lower(jax.tree.map(spec, params), jax.tree.map(spec, cache),
                    ints, ints, ctrl).compile().as_text()
    leaf_shapes = {",".join(map(str, x.shape))
                   for x in jax.tree.leaves(cache) if x.ndim >= 3}
    assert leaf_shapes
    copies = [ln.strip() for ln in text.splitlines()
              if " copy(" in ln
              and any(f"[{sh}]" in ln.split(" copy(")[0]
                      for sh in leaf_shapes)]
    assert not copies, copies
