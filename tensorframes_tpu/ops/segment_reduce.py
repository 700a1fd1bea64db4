"""Keyed segment reduction as a Pallas TPU kernel.

``segment_sum(values, segment_ids, num_segments)`` is the device-side core
of keyed aggregation — the TPU-native answer to the reference's
``unsorted_segment_sum`` k-means pattern (``kmeans_demo.py:128-140``) and
the UDAF shuffle+reduce (``DebugRowOps.scala:533-578``).

XLA lowers ``jax.ops.segment_sum`` to scatter-add, which serializes on the
TPU. This kernel instead expresses the reduction as a **one-hot matmul**:
for each row-block, build the ``[num_segments, block_rows]`` one-hot matrix
of segment ids and contract it against the values block on the MXU —
``[d, bn] @ [bn, S] -> [d, S]`` — accumulating partials into the output
block across the sequential grid. Rows ride the lane axis (ids ``[1, N]``,
values ``[d, N]``), so a scalar column is not padded to 128 lanes in HBM.
Out-of-range ids (e.g. -1 pad rows) produce an all-zero one-hot column and
contribute nothing, for free.

Route: the kernel holds the one-hot block and the whole ``[d, S]`` output
in fast memory, so it is picked on TPU only where that footprint fits
(:func:`pallas_fits`); larger group counts take ``jax.ops.segment_sum``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_mesh import interpret_blocked_by_vma, vma_union

__all__ = ["segment_sum", "pallas_fits"]

# v5e's default scoped VMEM limit; the kernel's blocks must fit inside it
VMEM_BUDGET_BYTES = 16 << 20


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _vmem_bytes(num_segments: int, d: int, block_rows: int) -> int:
    """Fast-memory footprint of one grid step, in (8, 128) f32 tiles:
    double-buffered id and value blocks, the compare mask and the f32
    one-hot ``[S, bn]``, and the ``[d, S]`` accumulator plus its partial."""
    bn = _round_up(block_rows, 128)
    inputs = 2 * (8 + _round_up(d, 8)) * bn * 4
    onehot = 2 * _round_up(num_segments, 8) * bn * 4
    out = 2 * _round_up(d, 8) * _round_up(num_segments, 128) * 4
    return inputs + onehot + out


def pallas_fits(num_segments: int, d: int, block_rows: int = 512) -> bool:
    """Whether the one-hot kernel's blocks fit :data:`VMEM_BUDGET_BYTES`."""
    return _vmem_bytes(num_segments, d, block_rows) <= VMEM_BUDGET_BYTES


def _kernel(ids_ref, vals_ref, out_ref, *, block_rows: int,
            num_segments: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    ids = ids_ref[:]                       # [1, bn] int32
    vals = vals_ref[:]                     # [d, bn]
    seg = jax.lax.broadcasted_iota(jnp.int32, (num_segments, block_rows), 0)
    onehot = (ids == seg).astype(jnp.float32)            # [S, bn]
    partial = jax.lax.dot_general(
        vals.astype(jnp.float32), onehot,
        (((1,), (1,)), ((), ())),          # contract the row dim: [d, S]
        precision=jax.lax.Precision.HIGHEST,  # exact f32: this is an
        preferred_element_type=jnp.float32)   # aggregation, not attention
    out_ref[:] = out_ref[:] + partial.astype(out_ref.dtype)


def _pallas_segment_sum(values, segment_ids, num_segments: int,
                        block_rows: int, interpret: bool):
    n, d = values.shape
    # callers guarantee floating values (segment_sum routes ints to XLA)
    acc_dtype = jnp.float32
    if n == 0:
        return jnp.zeros((num_segments, d), values.dtype)
    block_rows = min(block_rows, n)
    pad = (-n) % block_rows
    vals_t = values.T                      # rows on lanes: [d, N]
    ids = segment_ids.astype(jnp.int32).reshape(1, -1)
    if pad:
        vals_t = jnp.pad(vals_t, ((0, 0), (0, pad)))
        # pad ids with -1: matches no segment, so pad rows vanish
        ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
    nblocks = vals_t.shape[1] // block_rows

    # under shard_map(check_vma=True) the out_shape must declare which mesh
    # axes it varies over; the reduction output varies wherever its inputs do
    vma = vma_union(values, segment_ids)
    kern = functools.partial(_kernel, block_rows=block_rows,
                             num_segments=num_segments)
    out = pl.pallas_call(
        kern,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((1, block_rows), lambda i: (0, i)),
            pl.BlockSpec((d, block_rows), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((d, num_segments), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((d, num_segments), acc_dtype,
                                       vma=vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(ids, vals_t)
    return out.T.astype(values.dtype)


def segment_sum(values: jax.Array, segment_ids: jax.Array,
                num_segments: int, block_rows: int = 512,
                impl: Optional[str] = None) -> jax.Array:
    """Sum ``values`` rows into ``num_segments`` buckets by ``segment_ids``.

    ``values``: [N, ...] (trailing dims flattened for the kernel and
    restored); ``segment_ids``: [N] ints in [0, num_segments) — rows with
    out-of-range ids are dropped. Returns [num_segments, ...].

    ``impl``: ``"pallas"`` / ``"xla"`` / ``"interpret"``; None picks Pallas
    on TPU where :func:`pallas_fits`, else XLA.
    """
    if impl not in (None, "pallas", "interpret", "xla"):
        raise ValueError(f"Unknown segment_sum impl {impl!r}")
    values = jnp.asarray(values)
    segment_ids = jnp.asarray(segment_ids)
    tail = values.shape[1:]
    d = 1
    for t in tail:
        d *= t
    if not jnp.issubdtype(values.dtype, jnp.floating):
        # the one-hot matmul accumulates in f32, which is only exact to
        # 2^24 — integer aggregation must stay exact, so it always takes
        # the scatter-add path
        if impl in ("pallas", "interpret"):
            raise ValueError(
                f"segment_sum impl={impl!r} accumulates in f32 and is "
                "inexact for integer values; use impl='xla'")
        impl = "xla"
    elif impl is None:
        impl = ("pallas" if jax.default_backend() == "tpu"
                and pallas_fits(num_segments, d, block_rows) else "xla")
    if impl == "interpret" and interpret_blocked_by_vma(values, segment_ids):
        impl = "xla"  # see ops/_pallas_mesh.py: interpreter can't do vma
    if impl == "xla":
        valid = (segment_ids >= 0) & (segment_ids < num_segments)
        shaped = jnp.where(
            valid.reshape((-1,) + (1,) * (values.ndim - 1)), values, 0)
        ids = jnp.where(valid, segment_ids, 0)
        return jax.ops.segment_sum(shaped, ids, num_segments=num_segments)
    flat = values.reshape(values.shape[0], d)
    out = _pallas_segment_sum(flat, segment_ids, num_segments,
                              block_rows, interpret=(impl == "interpret"))
    return out.reshape((num_segments,) + tail)
