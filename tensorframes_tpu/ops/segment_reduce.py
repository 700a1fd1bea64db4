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

Each block step makes one MXU dot, in bf16 with f32 accumulation: the
one-hot, exact in bf16, against one stacked operand whose rows are all
exact in bf16 too (:func:`_block_sums`). Integer values of 32 bits or
fewer are summed exactly. Each value, read as int32, is split into byte
limbs, each a row: the low limbs are the unsigned bytes ``(w >> 8l) &
0xFF``, the top limb the arithmetic ``w >> 8(L-1)``. A limb's partial is
exact in f32 while ``block_rows * 255 < 2^24``; it is converted to int32,
shifted by ``8l`` and added into an int32 output block. These operations
wrap modulo 2^32 as the sum does, so the result equals the int32
scatter-add bit for bit, overflow included. A float value, read as f32,
is split by truncation into three pieces exact in bf16 whose sum is the
value (:func:`_split3`), each a row; the three f32 partials are added
after the dot. The products are exact, so float sums are f32 sums of the
values, as scatter-add's are, in another order; a value below 2^-103
loses bits below f32's smallest normal, as the TPU flushes denormals.
64-bit integers and bools take ``jax.ops.segment_sum``. Each program
built counts the stacked operand's rows, before padding, in
``segment_sum.dot_rows``.

Three routes, by the group count:

- **one-hot** (``segment_sum_onehot``): the kernel holds the one-hot block
  and the whole ``[d, S]`` output in fast memory, so :func:`segment_sum`
  picks it on TPU only where that footprint fits (:func:`pallas_fits`,
  about 4,000 groups). Each program built with the integer kernel counts
  ``segment_sum.onehot_int``.
- **sorted window** (``segment_sum_window``, :func:`segment_sums` given a
  ``window``): past ``pallas_fits``, one ``lax.sort`` keyed on the ids
  carries every column into key order. A block of ``bn`` sorted rows then
  spans a few groups from its first valid id, so the kernel sums it into
  a ``[d, W]`` window of lanes, ``W`` a multiple of 128, one partial per
  block; one scatter-add per column adds the ``nblocks x span``
  partials into the table, a few entries a group in place of one a row.
  ``bn`` follows from the rows and the group count
  (:func:`window_block_rows`). The window must hold the largest block
  span of the sorted ids (:func:`sorted_block_span`), which depends on
  the ids alone, so a caller computes it once per id set
  (``daggregate`` caches it beside its group ids, on TPU only:
  :func:`window_wanted`). Floats and integers of 32 bits or fewer take
  it, each as in the one-hot kernel. Integer columns of one value a row
  whose range is known and narrow ride in the sort key beside the id
  (:func:`sort_key_plan`): the sort carries fewer operands, and each
  packed value unpacks to itself, so integer sums stay exact.
- **scatter-add** (``jax.ops.segment_sum``): off the TPU, for 64-bit
  integers and bools, past ``pallas_fits`` without a window, and where
  the span is wider than a window may be (:func:`window_fits`: skew, or
  groups absent from a shard).

Both kernels sum only the rows whose ids are in range, and leave the
values that are not finite out of their one-hot dot, where 0 x NaN would
make every group of the block NaN; a block that holds one adds NaN or
inf to that value's own group alone, as scatter-add does.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.tracing import counters
from ._pallas_mesh import interpret_blocked_by_vma, vma_union

__all__ = ["segment_sum", "segment_sums", "pallas_fits", "sorted_block_span",
           "window_block_rows", "window_fits", "window_wanted",
           "packable", "sort_key_plan", "SortKeyPlan"]

# v5e's default scoped VMEM limit; the kernel's blocks must fit inside it
VMEM_BUDGET_BYTES = 16 << 20

# the integer route's block partials stay exact in f32 while
# block_rows * 255 < 2^24
INT_MAX_BLOCK_ROWS = 1 << 16


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _vmem_bytes(num_segments: int, d: int, block_rows: int) -> int:
    """Fast-memory footprint of one grid step, in (8, 128) f32 tiles:
    double-buffered id and value blocks, the compare mask and the bf16
    one-hot ``[S, bn]`` (each counted at 4 bytes), and the ``[d, S]``
    accumulator plus its partial."""
    bn = _round_up(block_rows, 128)
    inputs = 2 * (8 + _round_up(d, 8)) * bn * 4
    onehot = 2 * _round_up(num_segments, 8) * bn * 4
    out = 2 * _round_up(d, 8) * _round_up(num_segments, 128) * 4
    return inputs + onehot + out


def pallas_fits(num_segments: int, d: int, block_rows: int = 512) -> bool:
    """Whether the one-hot kernel's blocks fit :data:`VMEM_BUDGET_BYTES`."""
    return _vmem_bytes(num_segments, d, block_rows) <= VMEM_BUDGET_BYTES


# the stacked operand's rows are padded to bf16's (16, 128) tile
_BF16_SUBLANES = 16
# the rows a float column takes in the stacked operand: three pieces of
# its finite values, then its NaN, inf and -inf counts
_FLOAT_ROWS = 6


def _dot_rows(n_int: int, limbs: int, n_float: int) -> int:
    """Rows of :func:`_block_sums`' stacked operand, before padding: one a
    byte limb of each int column, six a float column."""
    return n_int * limbs + _FLOAT_ROWS * n_float


def _top16(x):
    """f32 ``x`` with the low 16 bits of its word cleared: ``x`` truncated
    toward 0 to bf16's 8 significant bits, exact in bf16."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jax.lax.bitcast_convert_type(bits & jnp.int32(-65536),
                                        jnp.float32)


def _split3(x):
    """Three pieces of finite f32 ``x``, each exact in bf16, with ``(b0 +
    b1) + b2 == x``: ``b0`` is ``x`` truncated to bf16, ``b1`` the residual
    truncated, ``b2`` the rest (at most 8 significant bits). Truncation,
    not rounding, so that no piece overflows: a value past bf16's largest
    would round to inf. Where denormals flush to 0, as on the TPU, a piece
    below f32's smallest normal 2^-126 (only of a value below 2^-103) is
    lost: an absolute error under 2^-126."""
    b0 = _top16(x)
    r = x - b0
    b1 = _top16(r)
    return b0, b1, r - b1


def _block_sums(hit, *, limbs: int = 0, ints=None, floats=None,
                valid=None):
    """One block's sums over the one-hot ``hit`` ``[S, bn]`` in one MXU
    dot: ``([d_int, S] int32, [d_float, S] f32)`` sums of the int32
    ``ints`` and of the ``valid`` rows of the f32 ``floats`` (each ``[d,
    bn]`` or None, which gives None).

    The one-hot is exact in bf16, and so is every row of the stacked
    operand ``[R, bn]`` (:func:`_dot_rows`) dotted against it with f32
    accumulation:

    - an int column's ``limbs`` byte limbs, the unsigned ``(w >> 8l) &
      0xFF`` below the arithmetic top ``w >> 8(L-1)``: exact in f32 while
      ``bn * 255 < 2^24``, then converted to int32, shifted by ``8l`` and
      summed in wrapping int32, as the scatter-add wraps;
    - a float column's three pieces of its finite valid values
      (:func:`_split3`), whose f32 sums are added after the dot; a 0 x inf
      or 0 x NaN term would make every group of the block NaN, so the
      pieces take such a value as 0, and three 0/1 rows count each
      group's NaN, inf and -inf; each kind found is added to that group
      alone, where IEEE addition gives what a sum in row order gives (inf
      + -inf is NaN)."""
    rows = []
    if ints is not None:
        for limb in range(limbs):
            part = ints >> (8 * limb)
            if limb < limbs - 1:
                part = part & 0xFF
            rows.append(part.astype(jnp.float32))
    kinds = ()
    if floats is not None:
        finite = jnp.abs(floats) < jnp.inf    # false for inf and NaN
        kinds = ((floats != floats, jnp.nan), (floats == jnp.inf, jnp.inf),
                 (floats == -jnp.inf, -jnp.inf))
        rows.extend(_split3(jnp.where(valid & finite, floats, 0.0)))
        rows.extend(jnp.where(valid & kind, 1.0, 0.0) for kind, _ in kinds)
    n = sum(r.shape[0] for r in rows)
    pad = (-n) % _BF16_SUBLANES
    if pad:
        rows.append(jnp.zeros((pad, hit.shape[1]), jnp.float32))
    out = jax.lax.dot_general(
        jnp.concatenate(rows, axis=0).astype(jnp.bfloat16),
        hit.astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())),              # contract the rows: [R, S]
        preferred_element_type=jnp.float32)
    int_sums = float_sums = None
    at = 0
    if ints is not None:
        d = ints.shape[0]
        for limb in range(limbs):
            term = out[at:at + d].astype(jnp.int32) << (8 * limb)
            int_sums = term if int_sums is None else int_sums + term
            at += d
    if floats is not None:
        d = floats.shape[0]
        piece = [out[at + k * d:at + (k + 1) * d]
                 for k in range(_FLOAT_ROWS)]
        float_sums = piece[0] + (piece[1] + piece[2])
        for count, (_, fill) in zip(piece[3:], kinds):
            float_sums = float_sums + jnp.where(count > 0, fill, 0.0)
    return int_sums, float_sums


def _kernel(ids_ref, vals_ref, out_ref, *, block_rows: int,
            num_segments: int, limbs: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    ids = ids_ref[:]                       # [1, bn] int32
    vals = vals_ref[:]                     # [d, bn]
    seg = jax.lax.broadcasted_iota(jnp.int32, (num_segments, block_rows), 0)
    hit = ids == seg                       # [S, bn]
    if limbs:
        sums, _ = _block_sums(hit, limbs=limbs, ints=vals)
    else:
        valid = (ids >= 0) & (ids < num_segments)
        _, sums = _block_sums(hit, floats=vals.astype(jnp.float32),
                              valid=valid)
    out_ref[:] = out_ref[:] + sums


def _pallas_segment_sum(values, segment_ids, num_segments: int,
                        block_rows: int, interpret: bool):
    n, d = values.shape
    out_dtype = values.dtype
    if n == 0:
        return jnp.zeros((num_segments, d), out_dtype)
    # floats accumulate in f32; integers of 32 bits or fewer are read as
    # int32 (unsigned ones bit for bit) and summed limb by limb
    limbs = 0
    acc_dtype = jnp.float32
    if jnp.issubdtype(out_dtype, jnp.integer):
        limbs = out_dtype.itemsize
        acc_dtype = jnp.int32
        values = (jax.lax.bitcast_convert_type(values, jnp.int32)
                  if limbs == 4 else values.astype(jnp.int32))
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
    counters.inc("segment_sum.dot_rows",
                 _dot_rows(d if limbs else 0, limbs, 0 if limbs else d))
    kern = functools.partial(_kernel, block_rows=block_rows,
                             num_segments=num_segments, limbs=limbs)
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
        name="segment_sum_onehot",       # the kernel's name in a trace
    )(ids, vals_t)
    out = out.T
    if limbs == 4:
        return jax.lax.bitcast_convert_type(out, out_dtype)
    return out.astype(out_dtype)       # narrow ints wrap as their sums do


def segment_sum(values: jax.Array, segment_ids: jax.Array,
                num_segments: int, block_rows: int = 512,
                impl: Optional[str] = None) -> jax.Array:
    """Sum ``values`` rows into ``num_segments`` buckets by ``segment_ids``.

    ``values``: [N, ...] (trailing dims flattened for the kernel and
    restored); ``segment_ids``: [N] ints in [0, num_segments) — rows with
    out-of-range ids are dropped. Returns [num_segments, ...].

    ``impl``: ``"pallas"`` / ``"xla"`` / ``"interpret"``; None picks Pallas
    on TPU where :func:`pallas_fits`, else XLA. Floats and integers of 32
    bits or fewer take the kernel (integers exactly, with the XLA route's
    wraparound); 64-bit integers and bools always take XLA.
    """
    if impl not in (None, "pallas", "interpret", "xla"):
        raise ValueError(f"Unknown segment_sum impl {impl!r}")
    values = jnp.asarray(values)
    segment_ids = jnp.asarray(segment_ids)
    tail = values.shape[1:]
    d = _width(values.shape)
    exact_int = (jnp.issubdtype(values.dtype, jnp.integer)
                 and values.dtype.itemsize <= 4)
    if not (exact_int or jnp.issubdtype(values.dtype, jnp.floating)):
        # 64-bit integers and bools have no exact limb route: scatter-add
        if impl in ("pallas", "interpret"):
            raise ValueError(
                f"segment_sum impl={impl!r} sums integers of 32 bits or "
                f"fewer exactly, not {values.dtype}; use impl='xla'")
        impl = "xla"
    elif impl is None:
        impl = ("pallas" if jax.default_backend() == "tpu"
                and pallas_fits(num_segments, d, block_rows) else "xla")
    if exact_int and impl != "xla" and block_rows > INT_MAX_BLOCK_ROWS:
        raise ValueError(
            f"segment_sum on integers takes block_rows <= "
            f"{INT_MAX_BLOCK_ROWS} (f32 block partials are exact below "
            f"2^24), got {block_rows}")
    if impl == "interpret" and interpret_blocked_by_vma(values, segment_ids):
        impl = "xla"  # see ops/_pallas_mesh.py: interpreter can't do vma
    if exact_int and impl != "xla":
        counters.inc("segment_sum.onehot_int")
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


# -- the sorted-window route ------------------------------------------------

# a block of key-sorted rows is sized to span about this many groups, half
# of a 128-lane window
WINDOW_EXPECTED_SPAN = 64
# a 1-D block of 32-bit rows is whole (8, 128) tiles: 1,024 rows or more
WINDOW_MIN_BLOCK_ROWS = 1024
WINDOW_MAX_BLOCK_ROWS = 4096
# the widest window the route takes; a larger block span takes scatter-add
WINDOW_MAX = 512


def window_block_rows(rows: int, num_segments: int) -> int:
    """Rows a block of the sorted-window route holds, for ``rows`` rows
    (one shard's) over ``num_segments`` groups: the largest power of two
    in [1024, 4096] whose expected span, ``block_rows * num_segments /
    rows`` groups, stays within :data:`WINDOW_EXPECTED_SPAN`."""
    bn = WINDOW_MIN_BLOCK_ROWS
    while (bn < WINDOW_MAX_BLOCK_ROWS
           and 2 * bn * num_segments <= WINDOW_EXPECTED_SPAN * rows):
        bn *= 2
    return bn


def _window_lanes(span: int) -> int:
    return _round_up(max(span, 1), 128)


def _window_vmem_bytes(span: int, d: int, block_rows: int) -> int:
    """Fast-memory footprint of one window-kernel step, generously:
    double-buffered id and value row blocks (each counted at 8 sublanes),
    the values stacked, the compare mask and its bf16 one-hot ``[W, bn]``
    (three copies at 4 bytes), and the double-buffered ``[d, W]``
    partials."""
    w = _window_lanes(span)
    rows = 2 * (1 + d) * 8 * block_rows * 4
    stacked = _round_up(d, 8) * block_rows * 4
    onehot = 3 * w * block_rows * 4
    out = 2 * _round_up(d, 8) * w * 4
    return rows + stacked + onehot + out


def _window_dtype(dtype) -> Optional[str]:
    """The window kernel's route for a column dtype: ``"int"`` (exact byte
    limbs), ``"float"`` (f32) or None (scatter-add: 64-bit and bool)."""
    if dtype.itemsize > 4:
        return None
    if jnp.issubdtype(dtype, jnp.integer):
        return "int"
    if jnp.issubdtype(dtype, jnp.floating):
        return "float"
    return None


def window_wanted(num_segments: int, columns,
                  backend: Optional[str] = None) -> bool:
    """Whether the sorted-window route would sum some of ``columns``
    (arrays or shape structs): on a TPU (``backend``, by default JAX's;
    elsewhere the kernel runs only in the interpreter and scatter-add is
    faster), past :func:`pallas_fits`, a column of a dtype the window
    kernel takes."""
    return ((backend or jax.default_backend()) == "tpu"
            and not pallas_fits(num_segments, 1)
            and any(_window_dtype(c.dtype) is not None for c in columns))


def window_fits(span: int, block_rows: int, columns) -> bool:
    """Whether the sorted-window route takes ``columns`` (arrays or shape
    structs) whose largest block span is ``span``: the window is at most
    :data:`WINDOW_MAX` lanes and one step's blocks fit
    :data:`VMEM_BUDGET_BYTES`."""
    d = {"int": 0, "float": 0}
    for c in columns:
        route = _window_dtype(c.dtype)
        if route is not None:
            d[route] += _width(c.shape)
    return (span <= WINDOW_MAX and _window_vmem_bytes(
        span, max(d.values()), block_rows) <= VMEM_BUDGET_BYTES)


def packable(column) -> bool:
    """Whether ``column`` (an array or shape struct) may ride in the
    sorted-window route's sort key, given its range: an integer of 32
    bits or fewer, one value a row."""
    return _window_dtype(column.dtype) == "int" and _width(column.shape) == 1


# the sort key is one 32-bit word
SORT_KEY_BITS = 32


class SortKeyPlan(NamedTuple):
    """How the sorted-window route packs its sort key: ``id + 1`` (-1,
    invalid, becomes 0 and leads) in the top ``id_bits`` bits, then each
    packed column's ``value - min`` in ``widths[k]`` bits, in column
    order. ``widths`` holds one entry a column: its bits, or None where
    the column stays a payload operand of the sort. Widths, not the
    minimums, so that frames whose ranges differ only in where they
    start share one compiled program; the minimums are operands."""

    id_bits: int
    widths: tuple

    @property
    def packed(self) -> int:
        return sum(w is not None for w in self.widths)

    @property
    def key_bits(self) -> int:
        return self.id_bits + sum(w for w in self.widths if w is not None)

    @property
    def dtype(self):
        """uint32 where every bit is taken, else int32."""
        return jnp.uint32 if self.key_bits == SORT_KEY_BITS else jnp.int32


def sort_key_plan(num_segments: int, ranges) -> SortKeyPlan:
    """Pack integer columns into the sort key by their ranges.

    ``ranges`` holds one entry a column: its exact ``(min, max)`` over the
    rows that count, or None for a column that cannot ride in the key
    (:func:`packable`). The id takes ``bit_length(num_segments)`` bits;
    each column, in order, takes ``bit_length(max - min)`` bits (0 for a
    constant column) where the key's total stays within 32, and stays a
    payload operand where it would not."""
    id_bits = int(num_segments).bit_length()
    bits, widths = id_bits, []
    for r in ranges:
        w = None if r is None else (int(r[1]) - int(r[0])).bit_length()
        if w is not None and bits + w <= SORT_KEY_BITS:
            bits += w
        else:
            w = None
        widths.append(w)
    return SortKeyPlan(id_bits, tuple(widths))


def _as_u32(rows):
    """An integer row of 32 bits or fewer as uint32 bits: signed values
    sign-extended, so that ``value - min`` wraps to the true distance."""
    if jnp.issubdtype(rows.dtype, jnp.signedinteger):
        return jax.lax.bitcast_convert_type(rows.astype(jnp.int32),
                                            jnp.uint32)
    return rows.astype(jnp.uint32)


def _packed_key(ids, packed_rows, mins, plan: SortKeyPlan):
    """The sort key of :class:`SortKeyPlan`: ``ids`` clean (-1 invalid),
    ``packed_rows`` the packed columns' rows and ``mins`` their uint32
    minimums. An invalid row's fields are 0, whatever its values."""
    valid = ids >= 0
    key = (ids + 1).astype(jnp.uint32)
    widths = [w for w in plan.widths if w is not None]
    for k, (rows, w) in enumerate(zip(packed_rows, widths)):
        field = jnp.where(valid, _as_u32(rows) - mins[k], jnp.uint32(0))
        key = (key << w) | field
    return jax.lax.bitcast_convert_type(key, plan.dtype)


def _unpack_key(key, mins, plan: SortKeyPlan):
    """The ids (-1 where invalid) and each packed column's int32 rows
    (the value's int32 bits) of sorted keys of :func:`_packed_key`."""
    key = jax.lax.bitcast_convert_type(key, jnp.uint32)
    widths = [w for w in plan.widths if w is not None]
    shift = sum(widths)
    ids = (key >> shift).astype(jnp.int32) - 1
    rows = []
    for k, w in enumerate(widths):
        shift -= w
        field = (key >> shift) & jnp.uint32((1 << w) - 1)
        rows.append(jax.lax.bitcast_convert_type(field + mins[k],
                                                 jnp.int32))
    return ids, rows


def _width(shape) -> int:
    d = 1
    for t in shape[1:]:
        d *= t
    return d


def _clean_ids(segment_ids, num_segments: int):
    """int32 ids with every id outside ``[0, num_segments)`` made -1."""
    ids = segment_ids.astype(jnp.int32)
    return jnp.where((ids >= 0) & (ids < num_segments), ids, -1)


def _block_ends(sorted_ids, block_rows: int):
    """First valid id and last id of each block of ascending ids whose
    invalid rows (-1) lead. A block with no valid row has a negative last
    id."""
    n = sorted_ids.shape[0]
    nblocks = -(-n // block_rows)
    npad = jnp.sum(sorted_ids < 0, dtype=jnp.int32)
    starts = jnp.arange(nblocks, dtype=jnp.int32) * block_rows
    first = sorted_ids[jnp.minimum(jnp.maximum(starts, npad), n - 1)]
    last = sorted_ids[jnp.minimum(starts + block_rows, n) - 1]
    return first, last


def sorted_block_span(segment_ids: jax.Array, num_segments: int,
                      block_rows: int) -> jax.Array:
    """The largest span, last id - first id + 1, of any ``block_rows``
    block of the valid ``segment_ids`` in ascending order: the window
    the sorted-window route needs for these ids. A scalar int32; 0 where
    no id is valid. It depends on the ids alone."""
    n = segment_ids.shape[0]
    if n == 0:
        return jnp.zeros((), jnp.int32)
    srt = jnp.sort(_clean_ids(segment_ids, num_segments), stable=False)
    first, last = _block_ends(srt, block_rows)
    return jnp.max(jnp.where(last >= 0, last - first + 1, 0))


def _window_kernel(ids_ref, *refs, rows: int, block_rows: int, lanes: int,
                   n_int: int, n_float: int, limbs: int):
    """Block sums of key-sorted rows into a ``lanes``-wide window that
    starts at the block's first valid id. Refs: the sorted ids ``[bn]``,
    ``n_int`` int32 then ``n_float`` f32 value rows ``[bn]``, then the int
    and/or float ``[d, W]`` partials of this block. The rows are read as
    1-D blocks, the layout the sort leaves them in, and ride the lanes."""
    step = pl.program_id(0)
    ids = ids_ref[:].reshape(1, block_rows)              # [1, bn] int32
    valid = ids >= 0
    if rows % block_rows:
        # the last block reads past the rows: unspecified data there
        row = step * block_rows + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_rows), 1)
        valid = valid & (row < rows)
    base = jnp.min(jnp.where(valid, ids, jnp.iinfo(jnp.int32).max),
                   axis=1, keepdims=True)
    local = jnp.where(valid, ids - base, -1)
    seg = jax.lax.broadcasted_iota(jnp.int32, (lanes, block_rows), 0)
    hit = local == seg                                   # [W, bn]
    ins, outs = refs[:n_int + n_float], refs[n_int + n_float:]

    def stacked(group):
        vals = [r[:].reshape(1, block_rows) for r in group]
        return vals[0] if len(vals) == 1 else jnp.concatenate(vals, axis=0)

    # invalid rows, and garbage past the rows, have a zero one-hot column
    # and finite limbs; pad rows (id -1) may hold any float, NaN included
    int_sums, float_sums = _block_sums(
        hit, limbs=limbs, ints=stacked(ins[:n_int]) if n_int else None,
        floats=stacked(ins[n_int:]) if n_float else None, valid=valid)
    if n_int:
        outs[0][:] = int_sums
    if n_float:
        outs[-1][:] = float_sums


def _window_partials(sorted_ids, int_rows, float_rows, *, block_rows: int,
                     lanes: int, limbs: int, interpret: bool,
                     fuse_inputs: bool = False):
    """Per-block window partials of sorted rows by the
    ``segment_sum_window`` kernel: ``[d, nblocks * W]`` for the int32 rows
    and for the f32 rows (None where there are none). ``interpret`` runs
    it in Pallas's TPU interpreter, which also runs inside a mesh."""
    n = sorted_ids.shape[0]
    nblocks = -(-n // block_rows)
    args = [sorted_ids] + list(int_rows) + list(float_rows)
    vma = vma_union(*args)
    out_shape, out_specs = [], []
    for rows_, dtype in ((int_rows, jnp.int32), (float_rows, jnp.float32)):
        if rows_:
            out_shape.append(jax.ShapeDtypeStruct(
                (len(rows_), nblocks * lanes), dtype, vma=vma))
            out_specs.append(pl.BlockSpec((len(rows_), lanes),
                                          lambda i: (0, i)))
    counters.inc("segment_sum.dot_rows",
                 _dot_rows(len(int_rows), limbs, len(float_rows)))
    kern = functools.partial(
        _window_kernel, rows=n, block_rows=block_rows, lanes=lanes,
        n_int=len(int_rows), n_float=len(float_rows), limbs=limbs)
    outs = pl.pallas_call(
        kern,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((block_rows,), lambda i: (i,))
                  for _ in args],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            allow_input_fusion=[True] * len(args) if fuse_inputs else None),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="segment_sum_window",       # the kernel's name in a trace
    )(*args)
    outs = list(outs)
    return (outs.pop(0) if int_rows else None,
            outs.pop(0) if float_rows else None)


def _combine(partials, first, span: int, lanes: int, num_segments: int):
    """Add block partials ``[d, nblocks * W]`` into ``d`` tables of
    ``[num_segments]`` at ``first_b + j`` for ``j < span``: a 1-D
    scatter-add per row (XLA's scatter of ``d``-wide rows runs several
    times slower). Lanes past a block's own span hold zeros, and targets
    past the table drop."""
    d = partials.shape[0]
    nblocks = first.shape[0]
    part = partials.reshape(d, nblocks, lanes)[:, :, :span]
    tgt = (jnp.maximum(first, 0)[:, None]
           + jnp.arange(span, dtype=jnp.int32)).reshape(-1)
    return [jax.ops.segment_sum(part[k].reshape(-1), tgt,
                                num_segments=num_segments)
            for k in range(d)]


def _sorted_window_sums(columns, segment_ids, num_segments: int,
                        block_rows: int, span: int, interpret: bool,
                        plan: Optional[SortKeyPlan] = None, mins=None):
    """:func:`segment_sums` on the sorted-window route, for columns whose
    dtypes the kernel takes; ``plan`` and ``mins`` pack columns into the
    sort key (:func:`sort_key_plan`)."""
    n = segment_ids.shape[0]
    if n == 0:
        return [jnp.zeros((num_segments,) + c.shape[1:], c.dtype)
                for c in columns]
    widths = plan.widths if plan is not None else (None,) * len(columns)
    # one sort, keyed on the ids and the packed columns, carries the
    # other columns' rows; pad and out-of-range rows (-1) lead
    rows_of = [c.reshape(n, _width(c.shape)) for c in columns]
    ids = _clean_ids(segment_ids, num_segments)
    packed = [r[:, 0] for r, w in zip(rows_of, widths) if w is not None]
    operands = [_packed_key(ids, packed, mins, plan) if packed else ids]
    for r, w in zip(rows_of, widths):
        if w is None:
            operands.extend(r[:, k] for k in range(r.shape[1]))
    srt = jax.lax.sort(operands, num_keys=1, is_stable=False)
    if packed:
        sorted_ids, unpacked = _unpack_key(srt[0], mins, plan)
    else:
        sorted_ids, unpacked = srt[0], []
    it, unpacked = iter(srt[1:]), iter(unpacked)
    int_rows, float_rows, slots = [], [], []
    limbs = 0
    for c, r, w in zip(columns, rows_of, widths):
        if w is not None:
            sub = [next(unpacked)]           # already the value's int32
        else:
            sub = [next(it) for _ in range(r.shape[1])]
        if _window_dtype(c.dtype) == "int":
            limbs = max(limbs, c.dtype.itemsize)
            slots.append(("int", len(int_rows), len(sub)))
            if w is None:
                sub = [jax.lax.bitcast_convert_type(s, jnp.int32)
                       if c.dtype.itemsize == 4 else s.astype(jnp.int32)
                       for s in sub]
            int_rows.extend(sub)
        else:
            slots.append(("float", len(float_rows), len(sub)))
            float_rows.extend(s.astype(jnp.float32) for s in sub)
    lanes = _window_lanes(span)
    first, _ = _block_ends(sorted_ids, block_rows)
    part_int, part_float = _window_partials(
        sorted_ids, int_rows, float_rows, block_rows=block_rows,
        lanes=lanes, limbs=limbs, interpret=interpret,
        fuse_inputs=bool(packed))
    tables = {
        route: _combine(p, first, span, lanes, num_segments)
        for route, p in (("int", part_int), ("float", part_float))
        if p is not None}
    out = []
    for c, (route, start, width) in zip(columns, slots):
        t = jnp.stack(tables[route][start:start + width], axis=1)
        if route == "int" and c.dtype.itemsize == 4:
            t = jax.lax.bitcast_convert_type(t, c.dtype)
        else:
            t = t.astype(c.dtype)      # narrow ints wrap as their sums do
        out.append(t.reshape((num_segments,) + c.shape[1:]))
    return out


def segment_sums(columns, segment_ids: jax.Array, num_segments: int,
                 window: Optional[tuple] = None,
                 impl: Optional[str] = None, mins=None) -> list:
    """:func:`segment_sum` of each of ``columns`` by the same ids.

    ``window``: ``(block_rows, span)``, with ``span`` at least
    :func:`sorted_block_span` of these ids at ``block_rows``, takes the
    sorted-window route for every column of 32 bits or fewer: one sort
    keyed on the ids carries them all, the ``segment_sum_window`` kernel
    sums each block of sorted rows into a window of ``span`` groups from
    the block's first id, and one scatter-add per column adds the block
    partials into the table. A span short of the ids' true span drops
    rows: it must come from :func:`sorted_block_span`, and
    :func:`window_fits` must hold. Without a window, with ``impl="xla"``,
    or for 64-bit and bool columns, each column takes :func:`segment_sum`
    with ``impl``.

    ``window`` may add a third entry, a :class:`SortKeyPlan` over
    ``columns``: the columns it packs ride in the sort key, and ``mins``
    (uint32, one a packed column, in order; a signed minimum as its
    int32 bits) are their minimums. Every row with a valid id must hold a
    value in its column's range; the others may hold anything.

    ``impl`` on the window route: ``"pallas"`` compiles the kernel for the
    TPU, ``"interpret"`` runs it in Pallas's TPU interpreter (inside a
    mesh too), None picks the first on TPU and the second elsewhere.
    """
    if impl not in (None, "pallas", "interpret", "xla"):
        raise ValueError(f"Unknown segment_sum impl {impl!r}")
    columns = [jnp.asarray(c) for c in columns]
    segment_ids = jnp.asarray(segment_ids)
    take = [window is not None and impl != "xla"
            and _window_dtype(c.dtype) is not None for c in columns]
    out = [None if t else segment_sum(c, segment_ids, num_segments,
                                      impl=impl)
           for c, t in zip(columns, take)]
    picked = [c for c, t in zip(columns, take) if t]
    if picked:
        if impl is None:
            impl = ("pallas" if jax.default_backend() == "tpu"
                    else "interpret")
        block_rows, span = window[:2]
        plan = window[2] if len(window) > 2 else None
        if impl == "pallas" and block_rows % WINDOW_MIN_BLOCK_ROWS:
            raise ValueError(
                f"the window kernel reads 1-D blocks of whole (8, 128) "
                f"tiles: block_rows must be a multiple of "
                f"{WINDOW_MIN_BLOCK_ROWS}, got {block_rows}")
        if plan is not None:
            if any(w is not None and not packable(c)
                   for c, w in zip(columns, plan.widths)):
                raise ValueError(
                    "a sort key plan packs only integer columns of 32 "
                    "bits or fewer, one value a row")
            plan = plan._replace(widths=tuple(
                w for w, t in zip(plan.widths, take) if t))
        sums = iter(_sorted_window_sums(
            picked, segment_ids, num_segments, block_rows, span,
            interpret=(impl == "interpret"), plan=plan,
            mins=None if mins is None else jnp.asarray(mins, jnp.uint32)))
        out = [next(sums) if t else o for o, t in zip(out, take)]
    return out
