"""Compile the main path's device programs for a described v5e, no chip.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (on-chip-measurement guide §2): it refuses what
interpret mode accepts, such as a kernel whose blocks overflow fast
memory. The topology is described inside a fixture, never at import, so
every xdist worker collects the same tests and only the worker given this
file loads libtpu.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tensorframes_tpu as tft
from tensorframes_tpu.engine.ops import cached_map_computation
from tensorframes_tpu.ops import flash_attention, segment_sum
from tensorframes_tpu.ops.segment_reduce import pallas_fits


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev_cache = jax.config.jax_enable_compilation_cache
    prev_x64 = jax.config.jax_enable_x64
    # a compile for a described chip cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    # the chip runs with x64 off (conftest turns it on for exact CPU
    # tests); Mosaic refuses the i64 block indices x64 would give
    jax.config.update("jax_enable_x64", False)
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - no libtpu: nothing to test
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_x64", prev_x64)
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        cc.reset_cache()
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("groups,pallas,dtype", [
    (1_000, True, jnp.float32), (100_000, False, jnp.float32),
    (1_000, True, jnp.int32)])
def test_segment_sum_routes_and_compiles(one_chip, groups, pallas, dtype):
    # the route is segment_sum's own rule on what the kernel holds in
    # fast memory; 100k groups would not compile in minutes as a kernel
    n = 1 << 20
    assert pallas_fits(groups, 1) is pallas
    impl = "pallas" if pallas else "xla"
    compiled = jax.jit(
        lambda v, i: segment_sum(v, i, groups, impl=impl)).lower(
            _spec((n, 1), dtype, one_chip),
            _spec((n,), jnp.int32, one_chip)).compile()
    assert ("tpu_custom_call" in compiled.as_text()) is pallas
    # rows ride the lanes: no [N, 1] -> [N, 128] padded copy in HBM
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 64 * n
    if dtype == jnp.int32:
        # the kernel splits limbs from the block it reads: no [4, N] limb
        # copy, nor even one int32 copy of the column
        assert temp < 4 * n
    assert compiled.out_info.dtype == dtype


def test_flash_attention_compiles(one_chip):
    q = _spec((4, 2048, 16, 128), jnp.bfloat16, one_chip)
    compiled = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, impl="pallas")).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_map_blocks_block_program_compiles(one_chip):
    df = tft.frame({"x": np.arange(8.0)})
    comp = cached_map_computation(lambda x: {"z": x + 3.0}, df.schema,
                                  block_level=True)
    n = 1 << 23
    compiled = jax.jit(comp.fn).lower(
        {"x": _spec((n,), jnp.float32, one_chip)}).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == 4 * n
    assert mem.output_size_in_bytes == 4 * n
