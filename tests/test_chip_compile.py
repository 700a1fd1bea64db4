"""Compile the main path's device programs for a described v5e, no chip.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (on-chip-measurement guide §2): it refuses what
interpret mode accepts, such as a kernel whose blocks overflow fast
memory. The topology is described inside a fixture, never at import, so
every xdist worker collects the same tests and only the worker given this
file loads libtpu.
"""

import collections
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tensorframes_tpu as tft
from tensorframes_tpu.engine.ops import cached_map_computation
from tensorframes_tpu.ops import flash_attention, segment_sum
from tensorframes_tpu.ops.segment_reduce import pallas_fits
from tensorframes_tpu.parallel import distributed as D
from tensorframes_tpu.parallel.mesh import DeviceMesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

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
        yield topo
    finally:
        jax.config.update("jax_enable_x64", prev_x64)
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        cc.reset_cache()
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _memory(name, compiled) -> dict:
    m = compiled.memory_analysis()
    got = {k: getattr(m, k) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes")}
    print(f"MEMORY {name} {json.dumps(got)}")
    return got


def _ops(compiled) -> collections.Counter:
    """Each (result shape, opcode) of a compiled program, counted."""
    return collections.Counter(
        re.findall(r"= (\S+) ([a-z][a-z0-9-]*)\(", compiled.as_text()))


def _cell_sizes():
    """Rows and group cap of the h2o1e9.q5_k10m_4chip cell."""
    with open(os.path.join(ROOT, "cellbench", "configs",
                           "h2o_groupby_g1e9_k100.json")) as f:
        rows = json.load(f)["rows"]
    with open(os.path.join(ROOT, "cellbench", "traffic",
                           "q5_k10m.json")) as f:
        return rows, json.load(f)["request"]["max_groups"]


def test_four_chip_group_ids_and_aggregate_fit(topo, monkeypatch):
    # 1e9 rows over a 2x2 mesh, 1e7 groups: the per-chip id build (the
    # one-program build asks 15.8 GB of temp a chip and is refused), the
    # window span bound and the aggregate program, on the sorted-window
    # route and on its scatter-add fallback, each fit a chip's 16 GB,
    # arguments and temp; the window route's temp stays within the 6.0 GB
    # a chip the scatter-add program took
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from tensorframes_tpu.ops.segment_reduce import window_block_rows

    n, cap = _cell_sizes()
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    rows = NamedSharding(mesh, P("data"))
    keys = _spec((n,), jnp.int32, rows)
    valid = _spec((n,), jnp.bool_, rows)
    tables = _spec((4 * (cap + 1),), jnp.int32, rows)
    local, merge = D._per_chip_id_programs(mesh, "data", cap + 1)
    span = D._window_span_program(mesh, "data", cap + 1,
                                  window_block_rows(n // 4, cap + 1))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    names = ["v1", "v2", "v3"]

    def agg(window):
        return jax.jit(shard_map(
            D._monoid_agg_shard_fn(names, {c: "sum" for c in names}, "data",
                                   cap + 1, window=window),
            mesh=mesh, in_specs=(P("data"),) * 4,
            out_specs=tuple(P() for _ in names)))

    # a block of the cell's sorted ids spans about 25 rows a group into
    # 1,024 rows: ~41 groups; 128 is the window's whole width
    window = (window_block_rows(n // 4, cap + 1), 128)
    cols = (keys, keys, keys, _spec((n,), jnp.float32, rows))
    for name, fn, args in (
            ("device_ids_local", local, (keys, valid)),
            ("device_ids_merge", merge, (keys, valid, tables)),
            ("window_span", span, (keys,)),
            ("aggregate_scatter", agg(None), cols),
            ("aggregate_window", agg(window), cols)):
        compiled = fn.lower(*args).compile()
        mem = _memory(name, compiled)
        assert mem["argument_size_in_bytes"] + mem["temp_size_in_bytes"] < HBM
        if name == "aggregate_window":
            assert mem["temp_size_in_bytes"] <= 6.0e9
            assert "segment_sum_window" in compiled.as_text()
        if name not in ("device_ids_local", "window_span"):
            assert "all-reduce" in compiled.as_text()


def test_one_chip_id_program_is_unchanged(topo):
    # on a one-shard mesh the routed build compiles to the one program
    dm = DeviceMesh(jax.sharding.Mesh(np.array(topo.devices[:1]), ("data",)))
    n, cap = 1 << 20, 1000
    keys = _spec((n,), jnp.int32, dm.row_sharding(1))
    valid = _spec((n,), jnp.bool_, dm.row_sharding(1))
    direct = D._build_device_ids.lower(keys, valid, cap).compile()
    routed = jax.jit(lambda k, v: D._device_ids(dm, k, v, cap)).lower(
        keys, valid).compile()
    assert _ops(routed) == _ops(direct)
    assert "all-reduce" not in routed.as_text()


@pytest.mark.parametrize("groups,route,dtype,n", [
    (1_000, "onehot", jnp.float32, 1 << 20),
    (100_000, "scatter", jnp.float32, 1 << 20),
    (1_000, "onehot", jnp.int32, 1 << 20),
    (1_000_000, "window", None, 100_000_000),
    (1_000_000, "window_packed", None, 100_000_000)])
def test_segment_sum_routes_and_compiles(one_chip, groups, route, dtype, n):
    # the route is segment_sum's own rule on what the kernel holds in
    # fast memory; 100k groups would not compile in minutes as a kernel.
    # Past it, q5's columns (two int32, one f32) at its 1e8 rows and 1e6
    # groups compile on the sorted-window route, with v1 (1..5) and v2
    # (1..15) packed into the sort key or not
    from tensorframes_tpu.ops.segment_reduce import (segment_sums,
                                                     sort_key_plan,
                                                     window_block_rows)

    assert pallas_fits(groups, 1) is (route == "onehot")
    if route.startswith("window"):
        window = (window_block_rows(n, groups), 128)
        mins = None
        if route == "window_packed":
            window += (sort_key_plan(groups, [(1, 5), (1, 15), None]),)
            mins = _spec((2,), jnp.uint32, one_chip)
        ints = _spec((n,), jnp.int32, one_chip)
        compiled = jax.jit(lambda i, a, b, c, m: segment_sums(
            [a, b, c], i, groups, window=window, impl="pallas",
            mins=m)).lower(ints, ints, ints,
                           _spec((n,), jnp.float32, one_chip),
                           mins).compile()
        text = compiled.as_text()
        assert "segment_sum_window" in text
        # one sort carries the ids and every column: no per-column scatter
        # of the rows; packed, it carries the key and v3 alone
        sorts = re.findall(r"\) sort\(([^)]*)\)", text)
        assert len(sorts) == 1
        assert sorts[0].count(",") + 1 == (2 if mins else 4)
        assert [o.dtype for o in compiled.out_info] == [
            jnp.int32, jnp.int32, jnp.float32]
        # the sorted rows are the temp; packed, the kernel reads the key
        # and unpacks it in its input fusions: no unpacked row is kept
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < (9 if mins else 17) * n
        if mins:
            assert temp <= H2O_TEMP_BYTES["q5_packed"]
        return
    impl = "pallas" if route == "onehot" else "xla"
    compiled = jax.jit(
        lambda v, i: segment_sum(v, i, groups, impl=impl)).lower(
            _spec((n, 1), dtype, one_chip),
            _spec((n,), jnp.int32, one_chip)).compile()
    assert ("tpu_custom_call" in compiled.as_text()) is (route == "onehot")
    # rows ride the lanes: no [N, 1] -> [N, 128] padded copy in HBM
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 64 * n
    if dtype == jnp.int32:
        # the kernel splits limbs from the block it reads: no [4, N] limb
        # copy, nor even one int32 copy of the column
        assert temp < 4 * n
    assert compiled.out_info.dtype == dtype


# temp bytes of the h2o cells' kernel programs when their kernels made a
# dot a byte limb and a HIGHEST f32 dot: one stacked bf16 dot a block
# step may take no more
H2O_TEMP_BYTES = {"q4_int32": 800_091_648, "q4_float32": 800_091_648,
                  "q5_packed": 825_219_584}


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_q4_onehot_program_compiles_within_its_temp(one_chip, dtype):
    # q4: one column of 1e8 rows summed into 100 groups by the one-hot
    # kernel
    n = 100_000_000
    compiled = jax.jit(
        lambda v, i: segment_sum(v, i, 100, impl="pallas")).lower(
            _spec((n, 1), dtype, one_chip),
            _spec((n,), jnp.int32, one_chip)).compile()
    assert "segment_sum_onehot" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= H2O_TEMP_BYTES[f"q4_{jnp.dtype(dtype).name}"]
    assert compiled.out_info.dtype == dtype


def test_flash_attention_compiles(one_chip):
    q = _spec((4, 2048, 16, 128), jnp.bfloat16, one_chip)
    compiled = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, impl="pallas")).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_latent_attention_compiles(one_chip):
    # the dsv2lite cell's attention: q and k 192 wide, v 128, causal over
    # 4,096 tokens in 512-row blocks
    q = _spec((4, 4096, 16, 192), jnp.bfloat16, one_chip)
    v = _spec((4, 4096, 16, 128), jnp.bfloat16, one_chip)
    compiled = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=512, block_k=512, scale=0.11472,
        impl="pallas")).lower(q, q, v).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (4, 4096, 16, 128)


@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)])
def test_moe_gmm_compiles_at_the_expert_widths(one_chip, k, n):
    # the dsv2lite cell's grouped matmuls: 16,384 tokens x 6 experts in
    # 256-row tiles over 64 experts, gate/up (2048 -> 1408) and down
    from tensorframes_tpu.ops import moe_gmm
    from tensorframes_tpu.ops.moe_gmm import max_tiles

    tiles = max_tiles(16384 * 6, 64, 256)
    x = _spec((tiles * 256, k), jnp.bfloat16, one_chip)
    w = _spec((64, k, n), jnp.bfloat16, one_chip)
    te = _spec((tiles,), jnp.int32, one_chip)
    used = _spec((), jnp.int32, one_chip)
    compiled = jax.jit(lambda x, w, te, used: moe_gmm(
        x, w, te, used, tm=256)).lower(x, w, te, used).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


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
