"""Grouped matmul over expert-sorted rows, as a Pallas TPU kernel.

A mixture-of-experts layer sends each token to k of E experts. With the
token-expert pairs sorted by expert, and each expert's run of rows padded to
a whole number of ``tm``-row tiles (:func:`tile_layout`), every tile belongs
to one expert, and the grouped product is one matmul a tile against that
expert's weights (:func:`moe_gmm`). The tiles of one expert follow one
another, so its weights are fetched once per run, not once per tile. An
expert with no pairs has no tiles; one with every pair has them all. Padded
rows are whatever the caller put there (zeros, in practice) and their
results are never read; tiles past the last in use compute nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["moe_gmm", "tile_layout", "max_tiles"]


def max_tiles(pairs: int, num_experts: int, tm: int) -> int:
    """The most tiles ``pairs`` rows can take over ``num_experts`` runs,
    each padded to a multiple of ``tm``: the static row count of the
    padded layout is this times ``tm``."""
    return (pairs + num_experts * (tm - 1)) // tm


def tile_layout(experts: jax.Array, num_experts: int, tm: int):
    """Where each token-expert pair goes in the padded, expert-sorted rows.

    ``experts``: [P] int32, the expert of each pair. Returns

    - ``slot`` [P] int32: the row of each pair in the padded layout;
    - ``tile_expert`` [max_tiles] int32: the expert of each tile (tiles past
      the last in use repeat its expert);
    - ``tiles_used`` [] int32.
    """
    p = experts.shape[0]
    n_tiles = max_tiles(p, num_experts, tm)
    counts = jnp.zeros((num_experts,), jnp.int32).at[experts].add(1)
    tiles = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(tiles, dtype=jnp.int32)
    first = jnp.cumsum(counts, dtype=jnp.int32) - counts
    order = jnp.argsort(experts, stable=True)
    sorted_e = experts[order]
    rank = jnp.arange(p, dtype=jnp.int32) - first[sorted_e]
    slot = jnp.zeros((p,), jnp.int32).at[order].set(
        (tile_end - tiles)[sorted_e] * tm + rank)
    tiles_used = tile_end[-1]
    t = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32), tiles_used - 1)
    tile_expert = jnp.searchsorted(tile_end, t, side="right").astype(
        jnp.int32)
    return slot, tile_expert, tiles_used


def _kernel(tile_expert_ref, tiles_used_ref, x_ref, w_ref, o_ref):
    del tile_expert_ref

    @pl.when(pl.program_id(0) < tiles_used_ref[0])
    def _():
        o_ref[...] = jnp.dot(x_ref[...], w_ref[...],
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)


def moe_gmm(x: jax.Array, w: jax.Array, tile_expert: jax.Array,
            tiles_used: jax.Array, *, tm: int,
            interpret: bool = False) -> jax.Array:
    """``out[r] = x[r] @ w[tile_expert[r // tm]]`` for the rows of the
    tiles in use, f32 accumulation, ``x.dtype`` out.

    ``x`` [M, K] with M a multiple of ``tm``; ``w`` [E, K, N]; the tile
    arrays from :func:`tile_layout`. Rows of unused tiles are left
    unwritten. A tile's step reads the tile's rows and, when the expert
    changes, that expert's whole [K, N] weight.
    """
    m, kdim = x.shape
    n = w.shape[2]
    if m % tm:
        raise ValueError(f"{m} rows are not a whole number of {tm}-row tiles")

    def rows(t, tile_expert, tiles_used):
        return jnp.minimum(t, tiles_used[0] - 1), 0

    def weights(t, tile_expert, tiles_used):
        return tile_expert[jnp.minimum(t, tiles_used[0] - 1)], 0, 0

    item = x.dtype.itemsize
    # double-buffered rows, weights and output, and the f32 product
    vmem = 2 * (tm * kdim + kdim * n + tm * n) * item + tm * n * 4
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m // tm,),
            in_specs=[pl.BlockSpec((tm, kdim), rows),
                      pl.BlockSpec((None, kdim, n), weights)],
            out_specs=pl.BlockSpec((tm, n), rows)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem + (8 << 20)),
        interpret=interpret,
        name="moe_gmm",                  # the kernel's name in a trace
    )(tile_expert, jnp.reshape(tiles_used, (1,)).astype(jnp.int32), x, w)
