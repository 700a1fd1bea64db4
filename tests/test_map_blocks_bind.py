"""map_blocks(bind=...): arrays passed to the block program as an argument."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tensorframes_tpu as tft
from tensorframes_tpu.engine.ops import cached_map_computation
from tensorframes_tpu.utils import tracing
from tensorframes_tpu.utils.tracing import counters


def affine(p, x):
    return {"y": x @ p["w"] + p["b"]}


def weights(seed, n=512, m=512):
    k = jax.random.PRNGKey(seed)
    return {"w": jax.random.normal(k, (n, m), jnp.float32),
            "b": jnp.full((m,), float(seed), jnp.float32)}


def frame(n=512):
    x = np.random.default_rng(0).standard_normal((8, n)).astype(np.float32)
    return tft.frame({"x": x}, num_partitions=2), x


def test_two_weight_sets_of_one_shape_compile_once():
    df, x = frame()
    misses = counters.get("compile_cache.misses")
    dispatches = counters.get("map_blocks.bound_dispatches")
    for seed in (1, 2):
        p = weights(seed)
        got = np.concatenate([b.dense("y") for b in
                              df.map_blocks(affine, trim=True,
                                            bind=p).blocks()])
        np.testing.assert_allclose(got, x @ np.asarray(p["w"]) + seed,
                                   rtol=1e-4, atol=1e-3)
    assert counters.get("compile_cache.misses") - misses == 1
    assert counters.get("map_blocks.bound_dispatches") - dispatches == 4


def test_the_block_program_holds_no_weight_constants():
    df, _ = frame()
    p = weights(3)
    nbytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(p))
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), p)
    comp = cached_map_computation(affine, df.schema, True, bound=avals)
    block = {"x": jax.ShapeDtypeStruct((4, 512), jnp.float32)}
    bound_hlo = jax.jit(comp.fn).lower(block, avals).as_text()
    assert len(bound_hlo) < nbytes / 100
    # the closed-over route embeds the weights as constants
    closed_hlo = jax.jit(lambda d: affine(p, d["x"])).lower(block).as_text()
    assert len(closed_hlo) > nbytes


def test_bound_values_of_other_shapes_are_refused():
    df, _ = frame()
    p = weights(1)
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), p)
    comp = cached_map_computation(affine, df.schema, True, bound=avals)
    with pytest.raises(ValueError, match="bound leaf"):
        comp.bind(weights(1, m=256))
    with pytest.raises(ValueError, match="structure"):
        comp.bind({"w": p["w"]})


def test_bind_span_counts_the_bytes_placed():
    df, _ = frame()
    host = {"w": np.ones((512, 512), np.float32),
            "b": np.zeros((512,), np.float32)}
    tracing.enable()
    try:
        tracing.timings.reset()
        df.map_blocks(affine, trim=True, bind=host).blocks()
        df.map_blocks(affine, trim=True, bind=weights(2)).blocks()
        recs = [r for r in tracing.span_records()
                if r.name == "map_blocks.bind"]
    finally:
        tracing.disable()
    assert [r.attrs for r in recs] == [
        {"bytes": 512 * 513 * 4, "leaves": 2}, {"bytes": 0, "leaves": 2}]


def double(y):
    return {"z": y * 2.0}


def test_a_bound_map_blocks_fuses_with_the_next_op():
    df, x = frame()
    df.cache()
    misses = counters.get("compile_cache.misses")
    hits = counters.get("plan.result_cache_hits")
    for seed in (1, 1, 1, 2):
        p = weights(seed)
        chain = df.map_blocks(affine, bind=p).map_blocks(double)
        assert chain._plan_node.input.kind == "map_blocks"
        got = np.concatenate([b.dense("z") for b in chain.blocks()])
        assert chain._plan_info, "expected the fused plan to execute"
        np.testing.assert_allclose(got, 2 * (x @ np.asarray(p["w"]) + seed),
                                   rtol=1e-4, atol=2e-3)
    # one fused program serves both weight sets, and no forcing is
    # answered from the result cache, whose key holds no bound values
    assert counters.get("compile_cache.misses") - misses == 1
    assert counters.get("plan.result_cache_hits") == hits
