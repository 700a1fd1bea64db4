"""Distributed frames: mesh-sharded columns and mesh-level map/reduce.

The TPU-native re-expression of the reference's executor-side distribution
(SURVEY.md §2.3). A :class:`DistributedFrame` holds each column as ONE global
``jax.Array`` row-sharded over the mesh's data axis — partitions become
shards, the broadcast-the-graph step becomes XLA program replication, and:

- :func:`dmap_blocks` — the ``rdd.mapPartitions`` analogue
  (``DebugRowOps.scala:372-386``): one jit dispatch executes every shard in
  parallel with no cross-device traffic;
- :func:`dreduce_blocks` — the block-reduce + Spark-tree-combine analogue
  (``DebugRowOps.scala:490-513``). For the associative monoid combiners
  (sum/min/max/prod) it lowers to one ``shard_map`` program whose
  cross-shard combine is a ``psum``-family ICI collective, with pad rows
  masked to the combiner's neutral element; arbitrary user computations
  take the per-device path — one async jit dispatch per shard device (JAX's
  async dispatch overlaps them), partials stacked and reduced once, which
  preserves the reference's "combine order unspecified" contract exactly.

Multi-host: build the mesh over ``jax.devices()`` after
``jax.distributed.initialize`` and the same code spans hosts — data-axis
collectives ride ICI within a slice and DCN across slices.
"""

from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Dict, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map

from .. import dtypes as _dt
from .. import memory as _memory
from ..engine import ops as _ops
from ..frame import Block, TensorFrame
from ..resilience import default_policy as _default_policy, faults as _faults
from ..schema import Schema
from .collectives import COMBINERS
from .mesh import DeviceMesh
from . import elastic as _elastic
from ..observability.events import (DEVICE_TRACK_BASE, current_trace,
                                    traced_query)
from ..utils.logging import get_logger
from ..utils.tracing import counters, span

__all__ = ["DistributedFrame", "distribute", "dmap_blocks", "dfilter",
           "dsort", "dreduce_blocks", "daggregate"]


def _lazy_input(dist):
    """The lazy recording view when ``dist`` is one (``frame.lazy()``),
    else None — the d-op entry points continue recorded chains instead
    of forcing them (``plan/dist.py``)."""
    return dist if getattr(dist, "_tft_lazy_dist", False) else None

_cached_reduce_computation = _ops.cached_reduce_computation


def _jitted(comp):
    """One jitted wrapper per live Computation, stored on the object so it
    is collected with it: repeated dmap/dreduce calls on the same
    computation reuse the trace instead of re-wrapping jax.jit."""
    fn = getattr(comp, "_tft_jitted", None)
    if fn is None:
        fn = jax.jit(comp.fn)
        comp._tft_jitted = fn
    return fn


# ---------------------------------------------------------------------------
# mesh-level trace instrumentation (zero-cost-when-off: every helper is
# called only behind a `trace is not None` check — no events, no
# per-shard introspection, and no extra readiness barriers otherwise)
# ---------------------------------------------------------------------------

def _fetch_names(fetches):
    """Best-effort fetch names for self-describing trace metadata: a
    Computation's declared outputs or a mapping's keys; ``None`` for an
    untraced callable (its outputs exist only after tracing)."""
    names = getattr(fetches, "output_names", None)
    if names:
        return sorted(names)
    if isinstance(fetches, Mapping):
        return sorted(str(n) for n in fetches)
    return None


def _mesh_meta(dist) -> Dict:
    m = dist.mesh
    return {"mesh_shape": dict(m.mesh.shape), "shards": m.num_data_shards,
            "devices": m.num_devices, "rows": dist.num_rows,
            "padded_rows": dist.padded_rows}


def _meta_with_fetches(fetches=None, dist=None, *a, **k):
    dist = k.get("dist", dist)
    fetches = k.get("fetches", fetches)
    if dist is None:
        return {}
    meta = _mesh_meta(dist)
    names = _fetch_names(fetches)
    if names is not None:
        meta["fetches"] = names
    return meta


def _meta_dfilter(predicate=None, dist=None, *a, **k):
    dist = k.get("dist", dist)
    return _mesh_meta(dist) if dist is not None else {}


def _meta_dsort(keys=None, dist=None, *a, **k):
    dist = k.get("dist", dist)
    keys = k.get("keys", keys)
    if dist is None:
        return {}
    meta = _mesh_meta(dist)
    meta["keys"] = [keys] if isinstance(keys, str) else list(keys or ())
    return meta


def _meta_daggregate(fetches=None, dist=None, keys=None, *a, **k):
    meta = _meta_with_fetches(fetches, dist, **k)
    keys = k.get("keys", keys)
    if meta and keys is not None:
        meta["keys"] = [keys] if isinstance(keys, str) else list(keys)
    return meta


def _meta_distribute(df=None, mesh=None, *a, **k):
    mesh = k.get("mesh", mesh)
    if mesh is None:
        return {}
    return {"mesh_shape": dict(mesh.mesh.shape),
            "shards": mesh.num_data_shards, "devices": mesh.num_devices}


def _trace_shards(trace, op: str, dist=None, mesh=None,
                  arrays=None) -> float:
    """Record one ``shard`` event per data shard (rows where known, an
    even-split byte estimate) on the device tracks; returns the dispatch
    start timestamp for :func:`_trace_mesh_done`."""
    if dist is not None:
        mesh = dist.mesh
        arrays = list(dist.columns.values())
        try:
            rows = dist.per_shard_valid()
        except Exception:
            rows = None
    else:
        rows = None
    S = mesh.num_data_shards
    nbytes = 0
    for a in arrays or ():
        nb = getattr(a, "nbytes", None)
        if nb:
            nbytes += int(nb)
    per_dev = nbytes // S if S else 0
    for i in range(S):
        trace.add("shard", name=f"{op} shard {i}", device=i,
                  rows=(int(rows[i]) if rows is not None else None),
                  bytes=per_dev, track=DEVICE_TRACK_BASE + i)
    return trace.clock()


def _trace_mesh_done(trace, outs, t0: float, op: str,
                     native: bool = False, mesh=None) -> None:
    """Per-device readiness timings + the op-level mesh dispatch span.

    Readiness is measured by waiting on each device's output shard in
    data-shard order, so a measured duration is the time until that
    device AND every earlier one were ready — the max (the straggler) is
    exact, earlier devices' times are conservative upper bounds. Only
    runs with tracing on; the untraced path keeps jax's async dispatch
    barrier-free. When ``mesh`` is given, the measured durations also
    feed the elastic layer's skew tracker (the signal behind
    skew-adaptive repartitioning, ``parallel/elastic.py``).
    """
    if not native:
        try:
            arr = next((a for a in outs
                        if hasattr(a, "addressable_shards")), None)
            if arr is not None:
                shards = list(arr.addressable_shards)
                by_start = {}
                for sh in shards:
                    idx = sh.index
                    sl = idx[0] if idx else None
                    start = (sl.start or 0) if isinstance(sl, slice) else 0
                    by_start.setdefault(start, sh)
                if len(by_start) > 1:  # row-sharded: data-shard order
                    ordered = [by_start[k] for k in sorted(by_start)]
                else:  # replicated result: one copy per device
                    ordered = sorted(
                        shards, key=lambda sh: getattr(sh.device, "id", 0))
                durs = []
                for i, sh in enumerate(ordered):
                    jax.block_until_ready(sh.data)
                    t = trace.clock()
                    durs.append(max(t - t0, 0.0))
                    trace.add("shard_compute", name=f"{op} d{i}", ts=t0,
                              dur=durs[-1], device=i,
                              track=DEVICE_TRACK_BASE + i)
                if mesh is not None and len(durs) >= 2:
                    _elastic.note_dispatch(mesh, op, durs)
        except Exception as e:
            get_logger("distributed").debug(
                "per-device readiness trace failed for %s: %s", op, e)
    trace.add("mesh_dispatch", name=op, ts=t0,
              dur=max(trace.clock() - t0, 0.0), native=native)


class DistributedFrame:
    """Columns as global row-sharded jax Arrays + the true row count.

    ``num_rows`` is the un-padded row count; rows are padded up to a
    multiple of the data-axis size so every shard is equal (XLA's static
    world), and consumers mask or slice the pad away.

    ``shard_valid`` (multi-host frames, from ``cluster.distribute_local``):
    per-data-shard valid-row counts, for frames whose pad rows are NOT a
    global suffix — each process padded its own block. ``None`` means
    prefix semantics (single-host ``distribute``): the first ``num_rows``
    rows are the real ones.
    """

    def __init__(self, mesh: DeviceMesh, schema: Schema,
                 columns: Dict[str, jax.Array], num_rows: int,
                 shard_valid: Optional[np.ndarray] = None):
        self.mesh = mesh
        self.schema = schema
        self.columns = columns
        self.num_rows = num_rows
        self.shard_valid = shard_valid
        # group-id factorizations memoized per key tuple: frames are
        # immutable (every op returns a new frame), so repeated
        # aggregations over the same keys skip the host transfer +
        # lexsort (host path) / sort-unique program (device path).
        # LRU-capped: entries hold device arrays sized like the frame, so
        # a long-lived frame swept over many key tuples / caps must not
        # retain HBM indefinitely (same policy as _dsort_cache).
        self._group_ids_cache: "OrderedDict[tuple, tuple]" = OrderedDict()

    @property
    def padded_rows(self) -> int:
        # shape metadata must NOT fault a spilled frame back to the
        # device (collect_frame/valid_row_mask route through here; a
        # larger-than-budget collect would re-resident the whole frame)
        cols = self.columns
        if isinstance(cols, _memory.SpillableColumns):
            return cols.leading_rows()
        first = next(iter(cols.values()))
        return first.shape[0]

    def per_shard_valid(self) -> np.ndarray:
        """Valid-row count of every data shard, [num_data_shards]."""
        S = self.mesh.num_data_shards
        if self.shard_valid is not None:
            return np.asarray(self.shard_valid, np.int64)
        rows_per = self.padded_rows // S
        if rows_per * S != self.padded_rows:
            # a global (row_aligned=False) result need not tile the data
            # axis (e.g. ONE summary row on an 8-shard mesh); such frames
            # carry no pad rows, and XLA lays the array out in ceil-div
            # chunks
            if self.num_rows != self.padded_rows:
                raise ValueError(
                    f"frame rows ({self.padded_rows}) do not tile the "
                    f"{S}-shard data axis yet only {self.num_rows} are "
                    f"valid — pad to a multiple of the shard count")
            chunk = -(-self.padded_rows // S)
            starts = np.minimum(np.arange(S) * chunk, self.padded_rows)
            ends = np.minimum(starts + chunk, self.padded_rows)
            return (ends - starts).astype(np.int64)
        out = np.full(S, rows_per, np.int64)
        full, tail = divmod(self.num_rows, rows_per)
        out[full:] = 0
        if full < S:
            out[full] = tail
        return out

    def valid_row_mask(self) -> np.ndarray:
        """Host bool mask [padded_rows]: True where the row is real."""
        S = self.mesh.num_data_shards
        rows_per = self.padded_rows // S
        if rows_per * S != self.padded_rows:
            self.per_shard_valid()  # validates num_rows == padded_rows
            return np.ones(self.padded_rows, bool)
        idx = np.arange(self.padded_rows) % rows_per
        return idx < np.repeat(self.per_shard_valid(), rows_per)

    def host_read_padded(self, name: str) -> np.ndarray:
        """The full padded global column on THIS host.

        Fully-addressable arrays read directly; multi-host arrays gather
        the process-local blocks (process-contiguous row layout, the
        ``cluster.distribute_local`` invariant) with one allgather.
        Spilled columns (``docs/memory.md``) are served from their
        pinned host buffers WITHOUT faulting back to the device — a
        larger-than-budget frame can be collected without ever being
        device-resident again.
        """
        if isinstance(self.columns, _memory.SpillableColumns) \
                and self.columns.mem_is_spilled():
            return self.columns.host_value(name)
        return _read_global(self.columns[name])

    def collect_frame(self, num_partitions: Optional[int] = None) -> TensorFrame:
        """Bring the data back to the host as a TensorFrame (pad dropped).

        Multi-host frames gather every process's rows — each host gets the
        FULL frame (the driver-collect contract of the reference,
        ``ExperimentalOperations.scala:91``)."""
        mask = self.valid_row_mask()
        host_cols = {}
        for f in self.schema:
            a = self.host_read_padded(f.name)
            a = a[mask] if self.shard_valid is not None else a[: self.num_rows]
            if a.dtype != f.dtype.np_storage and f.dtype is not _dt.bfloat16:
                a = a.astype(f.dtype.np_storage)
            host_cols[f.name] = a
        return TensorFrame.from_columns(
            host_cols, schema=self.schema,
            num_partitions=num_partitions or self.mesh.num_data_shards)

    def select(self, names) -> "DistributedFrame":
        """A view with only ``names`` (no data movement — the reduce ops
        require every column to back a fetch, so dropping ride-along
        columns first is the normal prelude)."""
        if isinstance(names, str):
            names = [names]
        names = list(names)
        missing = [n for n in names if n not in self.schema]
        if missing:
            raise KeyError(
                f"No column(s) {missing}; columns: {self.schema.names}")
        return DistributedFrame(self.mesh, self.schema.select(names),
                                {n: self.columns[n] for n in names},
                                self.num_rows, shard_valid=self.shard_valid)

    def count(self) -> int:
        """True (un-padded) global row count."""
        return self.num_rows

    def lazy(self):
        """A RECORDING view of this frame: subsequent ``dmap_blocks`` /
        ``dfilter`` / ``select`` calls record distributed plan nodes
        instead of dispatching, and the chain forces as ONE fused GSPMD
        program per mesh stage with shard intermediates staying
        device-resident (terminal monoid ``dreduce_blocks`` /
        ``daggregate`` fold into the same program). Returns ``self``
        when fusion cannot apply — ``TFT_FUSE=0``, the native ``pjrt``
        executor, multi-process meshes, frames whose rows do not tile
        the data axis — so chains then run eagerly per-op,
        bit-identical by construction. See ``docs/plan.md``.
        """
        from ..plan import dist as _dplan
        return _dplan.lazy_frame(self)

    def explain(self) -> str:
        """Schema + placement report (the mesh-side ``explain`` /
        ``print_schema`` analogue): per-column dtype, declared shape,
        device sharding, plus mesh/pad layout."""
        lines = [f"DistributedFrame: {self.num_rows} rows "
                 f"(padded {self.padded_rows}) on {self.mesh!r}",
                 f"  validity: "
                 + ("prefix" if self.shard_valid is None
                    else f"per-shard {list(map(int, self.shard_valid))}")]
        rb = getattr(self, "_rebalance", None)
        if rb:
            lines.append(
                f"  rebalance: skew {rb['ratio']:.2f} during {rb['op']}; "
                f"per-shard rows {rb['before']} -> {rb['after']} "
                f"(proportional to observed device throughput)")
        ex = getattr(self, "_exchange", None)
        if ex:
            flag = (" OVER TFT_SKEW_WARN"
                    if ex["ratio"] > ex["threshold"] else "")
            lines.append(
                f"  exchange: partition imbalance {ex['ratio']:.2f} "
                f"(threshold {ex['threshold']:.2f}{flag}); per-shard "
                f"rows {ex['per_shard']}")
        for f in self.schema:
            col = self.columns[f.name]
            if isinstance(col, np.ndarray):
                place = "host (ride-along)"
            else:
                try:
                    place = str(col.sharding.spec)
                except Exception:
                    place = type(col).__name__
            lines.append(f"  {f.describe()} sharding={place}")
        info = getattr(self, "_dplan_info", None)
        if info:
            # the distributed plan section (docs/plan.md): fused stage
            # layout, resident shard edges, fallback reasons — set by
            # plan.dist when this frame came out of a lazy chain
            lines.extend(info)
        return "\n".join(lines)

    def __repr__(self):
        return (f"DistributedFrame[{', '.join(self.schema.names)}] "
                f"rows={self.num_rows} mesh={self.mesh!r}")


def _host_side_column(a: np.ndarray, field, padded_rows: int) -> np.ndarray:
    """Pad a non-tensor (string) column for the host-side ride-along.

    Such columns cannot live in device memory; they travel in the same
    padded global layout as the device columns — pass-through / group-key
    only, exactly the host engine's contract for them (dtypes.py:
    tensor=False). Stored as the schema's np_storage (object), so
    downstream dtype guards never mistake a '<U1' numpy view for device
    narrowing. Host-side columns are process-local, so THIS helper
    rejects them in multi-process runs (both distribute entry points
    route through here).
    """
    if jax.process_count() > 1:
        raise ValueError(
            f"column {field.name!r}: non-tensor (string) columns are not "
            f"supported across processes — drop them with select() or key "
            f"on an integer column")
    a = np.asarray(a, field.dtype.np_storage)
    if a.shape[0] != padded_rows:
        a = np.concatenate(
            [a, np.full(padded_rows - a.shape[0], None, a.dtype)])
    return a


def _read_global(a) -> np.ndarray:
    """A (possibly multi-host) row-sharded global array as host numpy.

    Fully-addressable arrays read directly; otherwise each process
    concatenates its distinct row blocks and one allgather assembles the
    global array (row-contiguous process layout, the
    ``cluster.distribute_local`` invariant).
    """
    if getattr(a, "is_fully_addressable", True):
        return np.asarray(a)
    from jax.experimental import multihost_utils

    def start(s):
        sl = s.index[0]
        return 0 if sl.start is None else sl.start

    # replication over non-data mesh axes repeats each row block across
    # devices; keep one shard per distinct row range
    by_start = {}
    for s in a.addressable_shards:
        by_start.setdefault(start(s), s)
    shards = [by_start[k] for k in sorted(by_start)]
    local = np.concatenate([np.asarray(s.data) for s in shards])
    gathered = np.asarray(multihost_utils.process_allgather(local))
    return gathered.reshape((-1,) + tuple(a.shape[1:]))


@traced_query("distribute", _meta_distribute)
def distribute(df: TensorFrame, mesh: DeviceMesh) -> DistributedFrame:
    """Shard a host frame over the mesh's data axis.

    The analogue of Spark scattering partitions to executors — except the
    placement is an explicit ``device_put`` with a ``NamedSharding``, and
    the "partitions" are equal shards of one global array (pad rows, zero
    filled, make up the remainder; ``num_rows`` remembers the truth).
    """
    with span("distribute.concat"):
        merged = Block.concat(df.blocks(), df.schema)
    n = merged.num_rows
    shards = mesh.num_data_shards
    padded = ((n + shards - 1) // shards) * shards if n else shards
    mem_mgr = _memory.active()
    cols: Dict[str, jax.Array] = {}
    for f in df.schema:
        a = merged.dense(f.name)
        if not f.dtype.tensor:
            cols[f.name] = _host_side_column(a, f, padded)
            continue
        dd = _dt.device_dtype(f.dtype)
        if padded != n:
            # one allocation pads AND casts (assignment casting); empty +
            # explicit tail zero writes each byte once, where zeros-then-
            # assign wrote the data region twice
            with span("distribute.convert_pad"):
                out = np.empty((padded,) + a.shape[1:], dd)
                out[:n] = a
                out[n:] = 0
            a = out
        elif a.dtype != dd:
            # cast-only: the native kernel threads large buffers
            with span("distribute.convert_pad"):
                from .. import native as _native
                a = _native.convert(a, dd)
        if mem_mgr is not None:
            # spill colder frames before placing this column (a single
            # column larger than the whole budget still proceeds —
            # docs/memory.md degradation matrix)
            mem_mgr.make_room(int(a.nbytes))
        with span("distribute.device_put"):
            cols[f.name] = jax.device_put(a, mesh.row_sharding(a.ndim))
    if mem_mgr is not None and mem_mgr.spill_enabled:
        # the frame's device columns become one LRU spill candidate:
        # cold mesh frames move to pinned host buffers under pressure
        # and fault back transparently on the next column access
        cols = _memory.spillable_columns(
            f"distribute:{df._plan.split('(', 1)[0]}@{id(df):x}", cols,
            mem_mgr)
    result = DistributedFrame(mesh, df.schema, cols, n)
    trace = current_trace()
    if trace is not None:
        t0 = _trace_shards(trace, "distribute", dist=result)
        _trace_mesh_done(trace, [c for c in cols.values()
                                 if not isinstance(c, np.ndarray)],
                         t0, "distribute")
    return result


def dmap_blocks(fetches, dist: DistributedFrame, trim: bool = False,
                row_aligned: Optional[bool] = None) -> DistributedFrame:
    """Mesh-parallel map: one jit dispatch, all shards in parallel.

    Without ``trim``, outputs ride alongside the inputs and must be
    row-local (each output row depends on its input row and replicated
    constants); pad rows flow through and are dropped at collect. With
    ``trim=True`` the computation sees the GLOBAL padded array and may
    change the row count (e.g. an in-graph pre-aggregation emitting one
    global row — the ``kmeans_demo.py:128-140`` pattern at mesh scale);
    XLA/GSPMD inserts whatever cross-shard collectives the program needs.
    Such computations must mask pad rows themselves (``dist.num_rows`` is
    the true count; ``padded_rows`` what they will see).

    ``row_aligned`` declares how a trim output relates to the input rows:
    ``True`` — output rows correspond 1:1 to input rows (pad structure
    survives, dropped at collect); ``False`` — the output is a fresh global
    result (every emitted row is real). Default ``None`` infers from the
    row count (equal to ``padded_rows`` -> aligned); pass the flag
    explicitly when the sizes could coincide.

    Like every mesh op, the dispatch runs through the elastic boundary
    (``parallel/elastic.py``): a classified device loss shrinks the mesh,
    re-shards, and re-runs; persistent skew re-partitions first.

    On a LAZY frame (:meth:`DistributedFrame.lazy`) a proven
    row-preserving non-trim map RECORDS a plan node and defers — the
    chain forces as one fused GSPMD program (``docs/plan.md``); trim /
    unprovable computations materialize the chain and dispatch eagerly.
    """
    lz = _lazy_input(dist)
    if lz is not None:
        from ..plan import dist as _dplan
        out = _dplan.record_map(fetches, lz, trim, row_aligned)
        if out is not None:
            return out
        dist = _dplan.materialize(lz)
    return _dmap_blocks_eager(fetches, dist, trim, row_aligned)


@traced_query("dmap_blocks", _meta_with_fetches)
def _dmap_blocks_eager(fetches, dist: DistributedFrame, trim: bool = False,
                       row_aligned: Optional[bool] = None
                       ) -> DistributedFrame:
    return _elastic.elastic_call(
        "dmap_blocks", dist,
        lambda d: _dmap_blocks(fetches, d, trim, row_aligned))


def _dmap_blocks(fetches, dist: DistributedFrame, trim: bool,
                 row_aligned: Optional[bool]) -> DistributedFrame:
    schema = dist.schema
    if row_aligned is False and not trim:
        raise ValueError(
            "row_aligned=False only makes sense for trim=True outputs: "
            "without trim the untrimmed input columns ride along and still "
            "contain pad rows, which declaring every output row real would "
            "surface as data")
    comp = _ops._map_computation(fetches, schema, block_level=True)
    out_schema = _ops._validate_map(comp, schema, block_level=True, trim=trim)
    mesh = dist.mesh

    # TFT_EXECUTOR=pjrt: row-aligned maps run as ONE GSPMD-partitioned
    # executable inside the native C++ core (trim/global programs and
    # unsupported dtypes fall back to the jax dispatch below)
    nm = _native_mesh(mesh) if not trim else None
    if nm is not None:
        try:
            outs_np = nm.dmap(comp, dist)
        except Exception as e:
            _native_mesh_fallback(e)
            outs_np = None
        if outs_np is not None:
            counters.inc("mesh.dispatches")
            # per-key copy through __getitem__: dict()'s raw fast-path
            # copy would bypass SpillableColumns' fault-back and hand a
            # concurrently-spilled frame's None placeholders downstream
            cols = {n: dist.columns[n] for n in dist.columns}
            for spec in comp.outputs:
                a = outs_np[spec.name]
                cols[spec.name] = jax.device_put(
                    a, mesh.row_sharding(a.ndim))
            return DistributedFrame(mesh, out_schema, cols, dist.num_rows,
                                    shard_valid=dist.shard_valid)

    jitted = _jitted(comp)
    policy = _default_policy()

    def _dispatch():
        _faults.check("dmap")
        with span("dmap_blocks.dispatch"):
            out = jitted({n: dist.columns[n] for n in comp.input_names})
            if policy.max_attempts > 1:
                # jax dispatch is async: without this barrier an
                # execution failure would surface at a later consumption
                # of `out`, outside the retry. TFT_RETRY_MAX_ATTEMPTS=1
                # restores fire-and-forget pipelining for hot loops.
                jax.block_until_ready(out)
            return out

    # one jit dispatch covers every shard: a transient PJRT failure here
    # would otherwise kill the whole mesh map
    trace = current_trace()
    t0 = (_trace_shards(trace, "dmap_blocks", dist=dist)
          if trace is not None else 0.0)
    out = policy.call(_dispatch, op="dmap_blocks.dispatch")
    counters.inc("mesh.dispatches")
    if trace is not None:
        _trace_mesh_done(trace, [out[s.name] for s in comp.outputs], t0,
                         "dmap_blocks", mesh=mesh)
    leads = {out[s.name].shape[0] for s in comp.outputs}
    if len(leads) > 1:
        raise ValueError(
            f"Distributed map fetches disagree on output row count: "
            f"{ {s.name: out[s.name].shape[0] for s in comp.outputs} }")
    n_out = leads.pop() if leads else dist.padded_rows
    if n_out != dist.padded_rows and not trim:
        raise ValueError(
            f"Distributed map output changed the row count ({n_out} vs "
            f"{dist.padded_rows}); use trim=True for row-count-changing "
            f"(global) computations")
    if row_aligned is None:
        row_aligned = n_out == dist.padded_rows
    elif row_aligned and n_out != dist.padded_rows:
        raise ValueError(
            f"row_aligned=True but the output has {n_out} rows and the "
            f"input {dist.padded_rows}")
    # per-key copy through __getitem__ (see the native-mesh branch):
    # dict() would bypass a spilled SpillableColumns' fault-back
    cols = {} if trim else {n: dist.columns[n] for n in dist.columns}
    for spec in comp.outputs:
        cols[spec.name] = out[spec.name]
    num_rows = dist.num_rows if row_aligned else n_out
    # row-aligned outputs keep the input's pad layout; a fresh global
    # result (row_aligned=False) has no pad rows at all
    return DistributedFrame(mesh, out_schema, cols, num_rows,
                            shard_valid=(dist.shard_valid if row_aligned
                                         else None))


def dfilter(predicate, dist: DistributedFrame) -> DistributedFrame:
    """Mesh filter: keep the rows where ``predicate`` holds (nonzero).

    The TPU-first shape of a row filter: global array shapes cannot
    change per data (XLA's static world), so one ``shard_map`` program
    computes the mask per shard, stably compacts each shard's kept rows
    to the front (argsort on the negated mask + gather), and reports the
    per-shard survivor counts — the padded global layout is untouched and
    the result's validity becomes per-shard (``shard_valid`` semantics,
    exactly the multi-host frame layout every consumer already handles).
    Host-side ride-along columns (strings) replay the same per-shard
    permutation on the host from the returned mask.

    ``predicate`` follows :func:`tensorframes_tpu.filter_rows`'s
    contract: named args select columns, one rank-1 boolean/integer
    fetch.

    On a LAZY frame the filter RECORDS: its compaction fragment runs
    INSIDE the chain's fused program and the survivor counts stay
    traced between ops (no host readback until the chain forces).
    """
    lz = _lazy_input(dist)
    if lz is not None:
        from ..plan import dist as _dplan
        out = _dplan.record_filter(predicate, lz)
        if out is not None:
            return out
        dist = _dplan.materialize(lz)
    return _dfilter_eager(predicate, dist)


@traced_query("dfilter", _meta_dfilter)
def _dfilter_eager(predicate, dist: DistributedFrame) -> DistributedFrame:
    return _elastic.elastic_call("dfilter", dist,
                                 lambda d: _dfilter(predicate, d))


def _dfilter(predicate, dist: DistributedFrame) -> DistributedFrame:
    schema = dist.schema
    comp = _ops._filter_computation(predicate, schema)
    bad = [n for n in comp.input_names
           if (f := schema.get(n)) is not None and not f.dtype.tensor]
    if bad:
        raise _ops.InvalidTypeError(
            f"dfilter predicate reads host-side (non-tensor) column(s) "
            f"{bad}: string columns ride along on the mesh but cannot "
            f"enter the sharded program. Filter on the host instead "
            f"(tensorframes_tpu.filter_rows / TensorFrame.filter) before "
            f"distribute().")
    pname = comp.output_names[0]
    mesh = dist.mesh
    axis = mesh.data_axis
    S = mesh.num_data_shards
    rows_per = dist.padded_rows // S
    in_names = comp.input_names
    tensor_names = [f.name for f in schema if f.dtype.tensor]
    host_names = [f.name for f in schema if not f.dtype.tensor]

    counts_host = dist.per_shard_valid().astype(np.int32)
    cnt_dev = jax.make_array_from_callback(
        (S,), mesh.row_sharding(1), lambda idx: counts_host[idx])
    arrays = [dist.columns[n] for n in tensor_names]

    cache = getattr(comp, "_tft_dfilter_cache", None)
    if cache is None:
        cache = comp._tft_dfilter_cache = {}
    key = (mesh.mesh, axis, rows_per,
           tuple((n, a.shape, str(a.dtype))
                 for n, a in zip(tensor_names, arrays)))

    in_specs = (P(axis),) + tuple(
        P(axis, *([None] * (a.ndim - 1))) for a in arrays)
    out_specs = tuple(
        P(axis, *([None] * (a.ndim - 1))) for a in arrays
    ) + (P(axis), P(axis))

    def build_prog():
        def shard_fn(cnt, *cols):
            local = dict(zip(tensor_names, cols))
            m = comp.fn({n: local[n] for n in in_names})[pname]
            rowid = jnp.arange(rows_per)
            keep = (m != 0) & (rowid < cnt[0])
            order = jnp.argsort((~keep).astype(jnp.int8), stable=True)
            permuted = tuple(jnp.take(c, order, axis=0) for c in cols)
            return permuted + (jnp.sum(keep, dtype=jnp.int32)[None], keep)

        return shard_map(shard_fn, mesh=mesh.mesh, in_specs=in_specs,
                         out_specs=out_specs)

    # TFT_EXECUTOR=pjrt: per-shard mask + compaction as one GSPMD
    # executable in the native core
    outs = None
    nm = _native_mesh(mesh)
    if nm is not None:
        in_shardings = (mesh.row_sharding(1),) + tuple(
            mesh.row_sharding(a.ndim) for a in arrays)
        out_shardings = tuple(
            mesh.row_sharding(a.ndim) for a in arrays
        ) + (mesh.row_sharding(1), mesh.row_sharding(1))
        try:
            outs_np = nm.run_sharded(
                ("dfilter",) + key, build_prog,
                [cnt_dev] + list(arrays), in_shardings,
                list(out_shardings), mesh, owner=comp)
        except Exception as e:
            _native_mesh_fallback(e)
            outs_np = None
        if outs_np is not None:
            outs = [jax.device_put(a, s)
                    for a, s in zip(outs_np, out_shardings)]
    if outs is None:
        fn = cache.get(key)
        if fn is None:
            fn = jax.jit(build_prog())
            cache[key] = fn
        trace = current_trace()
        t0 = (_trace_shards(trace, "dfilter", dist=dist)
              if trace is not None else 0.0)
        with span("dfilter.dispatch"):
            outs = fn(cnt_dev, *arrays)
        if trace is not None:
            _trace_mesh_done(trace, list(outs), t0, "dfilter", mesh=mesh)
    counters.inc("mesh.dispatches")
    new_cols: Dict[str, jax.Array] = dict(zip(tensor_names, outs))
    counts = _read_global(outs[len(tensor_names)]).astype(np.int64)
    # the survivor counts (and, with host ride-alongs, the keep mask)
    # cross to the host between this op and the next — the inter-stage
    # transfer the fused plan keeps traced (docs/plan.md)
    counters.inc("mesh.interstage_host_bytes", 4 * S)
    # feedback selectivity (ROADMAP 2a): observed rows-in/rows-out
    # sharpen estimates for later plans over the same predicate
    from ..plan.nodes import record_selectivity
    record_selectivity(comp, dist.num_rows, int(counts.sum()))
    if host_names:
        counters.inc("mesh.interstage_host_bytes", dist.padded_rows)
        keep_host = _read_global(outs[len(tensor_names) + 1])
        for n in host_names:
            a = dist.columns[n]
            out_a = np.empty_like(a)
            for s in range(S):
                sl = slice(s * rows_per, (s + 1) * rows_per)
                order = np.argsort(~keep_host[sl], kind="stable")
                out_a[sl] = a[sl][order]
            new_cols[n] = out_a
    return DistributedFrame(mesh, schema, new_cols, int(counts.sum()),
                            shard_valid=counts)


_dsort_cache: "OrderedDict[tuple, object]" = OrderedDict()
_DSORT_CACHE_CAP = 32


def dsort(keys, dist: DistributedFrame, descending: bool = False
          ) -> DistributedFrame:
    """Rows globally sorted by scalar key column(s), on the mesh.

    Multi-shard frames sort by **columnsort** (Leighton's 8-step
    sorting-network generalization): four LOCAL per-shard sorts
    interleaved with three static exchanges (two ``all_to_all`` reshuffles
    and a half-block ``ppermute`` shift). Every step has static shapes
    and per-device O(m log m) work — no shard ever sorts (or even holds)
    the global array, unlike a GSPMD-partitioned global ``argsort``,
    which gathers the key column to every device and replicates the full
    n·log n sort. Stability and pad handling ride in the sort key itself:
    a validity flag is the most significant key (frame pads and the
    internal columnsort padding sink to the global tail), the original
    row id is the least significant (stable ties), and each user key is
    order-transformed for ``descending`` (float negation; bitwise-not
    for ints, which never overflows). Correctness needs
    rows-per-shard ≥ 2(S-1)² and divisibility by 2S, achieved by padding
    inside the program, with the final global slice restoring the frame's
    layout. Single-shard meshes and frames whose rows do not tile the
    data axis use a plain local-sort program instead.

    The result has prefix validity: pad rows are all at the tail,
    whatever the input layout (so ``dsort`` also normalizes a
    ``dfilter``/multi-host mask layout back to prefix semantics).

    Keys must be device (numeric) columns; sort by a string key on the
    host via ``TensorFrame.order_by`` instead. Host-side string
    ride-along columns are permuted on the host from the same order.

    A LAZY frame materializes first (its pending chain forces fused;
    the sort consumes the still-device-resident result — the resident
    shard edge between mesh stages).
    """
    lz = _lazy_input(dist)
    if lz is not None:
        from ..plan import dist as _dplan
        dist = _dplan.materialize(lz)
    if isinstance(keys, str):
        keys = [keys]
    keys = list(keys)
    return _dsort_eager(keys, dist, descending)


@traced_query("dsort", _meta_dsort)
def _dsort_eager(keys, dist: DistributedFrame, descending: bool = False
                 ) -> DistributedFrame:
    ext = _dsort_external_if_needed(keys, dist, descending)
    if ext is not None:
        return ext
    return _elastic.elastic_call("dsort", dist,
                                 lambda d: _dsort(keys, d, descending))


def _validate_dsort_keys(schema: Schema, keys) -> None:
    for k in keys:
        f = schema.get(k)
        if f is None:
            raise KeyError(f"No key column {k!r}; columns: {schema.names}")
        if not f.dtype.tensor:
            raise _ops.InvalidTypeError(
                f"dsort key {k!r} is a host-side (string) column; sort on "
                f"the host with order_by, or key on a numeric column")
        if f.block_shape is not None and len(f.block_shape.dims) != 1:
            raise _ops.InvalidShapeError(
                f"dsort key {k!r} must be a scalar column")


def _dsort_external_if_needed(keys, dist: DistributedFrame,
                              descending: bool
                              ) -> Optional[DistributedFrame]:
    """Route a larger-than-budget frame to the external-memory sort.

    Engages only under an active device budget
    (``TFT_MEM_LIMIT_BYTES`` / the derived HBM budget) when the frame's
    tensor columns exceed ``TFT_MEM_SORT_FRACTION`` of it — the
    in-device columnsort would hold input + exchange buffers resident
    at once. Sizes are read WITHOUT faulting spilled columns back.
    """
    mgr = _memory.active()
    if mgr is None or not mgr.spill_enabled:
        return None
    threshold = mgr.external_sort_threshold()
    if threshold is None:
        return None
    tensor_names = [f.name for f in dist.schema if f.dtype.tensor]
    total = sum(_memory.value_nbytes(dist.columns, n)
                for n in tensor_names)
    if total <= threshold:
        return None
    _validate_dsort_keys(dist.schema, keys)
    return _dsort_external(keys, dist, descending, mgr)


def _dsort_external(keys, dist: DistributedFrame, descending: bool,
                    mgr) -> DistributedFrame:
    """Out-of-core dsort: budget-sized device runs + host k-way merge
    (``memory.external_sort``), result bit-identical to the in-memory
    path — stable by original row order, pads at the global tail
    (prefix validity), host ride-along columns permuted alongside.
    """
    mesh = dist.mesh
    schema = dist.schema
    tensor_names = [f.name for f in schema if f.dtype.tensor]
    host_names = [f.name for f in schema if not f.dtype.tensor]
    with span("dsort.external"):
        mask = dist.valid_row_mask()
        valid_idx = np.flatnonzero(mask)
        host_cols = {}
        for n in tensor_names:
            a = _memory.host_value(dist.columns, n)
            host_cols[n] = a[mask]
        sorted_cols, order, stats = _memory.external_sort(
            host_cols, keys, descending=descending, manager=mgr)
        trace = current_trace()
        if trace is not None:
            trace.add("external_sort", rows=stats["rows"],
                      runs=stats["runs"], bytes=stats["bytes"])
        padded = dist.padded_rows
        n_valid = len(valid_idx)
        new_cols: Dict[str, jax.Array] = {}
        for n in tensor_names:
            s = sorted_cols[n]
            if padded != n_valid:
                out = np.zeros((padded,) + s.shape[1:], s.dtype)
                out[:n_valid] = s
                s = out
            mgr.make_room(int(s.nbytes))
            with span("dsort.external_put"):
                new_cols[n] = jax.device_put(s, mesh.row_sharding(s.ndim))
        for n in host_names:
            col = np.asarray(dist.columns[n], object)
            g = col[valid_idx[order]]
            if padded != n_valid:
                g = np.concatenate(
                    [g, np.full(padded - n_valid, None, object)])
            new_cols[n] = g
        cols = _memory.spillable_columns(
            f"dsort.external@{id(dist):x}", new_cols, mgr)
        get_logger("dsort").info(
            "dsort took the external-memory path: %d rows (%d B) in %d "
            "run(s), k-way merged on the host", stats["rows"],
            stats["bytes"], stats["runs"])
        return DistributedFrame(mesh, schema, cols, dist.num_rows)


def _dsort(keys, dist: DistributedFrame, descending: bool
           ) -> DistributedFrame:
    schema = dist.schema
    _validate_dsort_keys(schema, keys)
    mesh = dist.mesh
    S = mesh.num_data_shards
    tensor_names = [f.name for f in schema if f.dtype.tensor]
    host_names = [f.name for f in schema if not f.dtype.tensor]
    arrays = [dist.columns[n] for n in tensor_names]

    valid_host = dist.valid_row_mask()
    if dist.padded_rows % S == 0:
        valid_dev = jax.make_array_from_callback(
            (dist.padded_rows,), mesh.row_sharding(1),
            lambda idx: valid_host[idx])
    else:
        # non-tiling (trim/global-result) frames cannot carry an evenly
        # row-sharded mask; the local program runs replicated for them
        valid_dev = jax.device_put(valid_host, mesh.replicated())

    want_order = bool(host_names)
    if S > 1 and dist.padded_rows % S == 0:
        outs = _dsort_columnsort(dist, keys, descending, tensor_names,
                                 arrays, valid_dev, want_order)
    else:
        if S > 1:
            _warn_dsort_gather(dist, S)
        outs = _dsort_local(dist, keys, descending, tensor_names, arrays,
                            valid_dev, want_order)
    new_cols: Dict[str, jax.Array] = dict(zip(tensor_names, outs))
    if want_order:
        order_host = _read_global(outs[len(tensor_names)])
        for n in host_names:
            new_cols[n] = dist.columns[n][order_host]
    return DistributedFrame(mesh, schema, new_cols, dist.num_rows)


_dsort_gather_warned = False


def _warn_dsort_gather(dist, S: int):
    """Warn ONCE when a multi-shard frame takes the local-argsort program.

    The local program's GSPMD lowering gathers the key column to every
    device — the exact pathology columnsort exists to kill — so its
    silent return on an S>1 mesh (rows not tiling the data axis, e.g. a
    trim/global map result) must be visible. One warning per process,
    like the native-mesh fallback."""
    global _dsort_gather_warned
    if _dsort_gather_warned:
        return
    get_logger("dsort").warning(
        "dsort on a %d-shard mesh fell back to the global-argsort program "
        "because the frame's %d rows do not tile the data axis — GSPMD "
        "will gather the key column to every device. Pad or repartition "
        "the frame to a multiple of the shard count to get columnsort "
        "(warned once)", S, dist.padded_rows)
    _dsort_gather_warned = True


def _key_transform(kv, descending: bool):
    """Order-reversing transforms with no overflow for descending: float
    negation, and bitwise-not for ints (~k = -k-1 is strictly decreasing
    for signed AND unsigned — raw negation wraps uint 0 onto itself and
    overflows iinfo.min)."""
    if not descending:
        return kv
    return -kv if jnp.issubdtype(kv.dtype, jnp.floating) else ~kv


def _dsort_local(dist, keys, descending, tensor_names, arrays, valid_dev,
                 want_order):
    """Fallback sort program (single-shard meshes / non-tiling frames):
    one jit, global stable argsort chain; on a multi-shard mesh GSPMD
    would gather the key column, which is why multi-shard frames take
    :func:`_dsort_columnsort` instead."""
    mesh = dist.mesh
    ckey = ("local", mesh.mesh, tuple(keys), descending, want_order,
            tuple((n, a.shape, str(a.dtype))
                  for n, a in zip(tensor_names, arrays)))
    fn = _dsort_cache.get(ckey)
    if fn is None:
        def program(valid, *cols):
            named = dict(zip(tensor_names, cols))
            n = valid.shape[0]
            # ONE fused lexicographic lax.sort: (invalid, keys...,
            # original position). The validity flag is the primary key so
            # pad/invalid rows sink stably to the tail with no value
            # sentinel — real rows keyed NaN / +inf / iinfo.max cannot be
            # displaced into the pad region (NaNs end up last WITHIN the
            # valid prefix, XLA's float total order), pads strictly
            # after. The position key makes the tuple a total order, so
            # ties keep original order (stable).
            pos = jnp.arange(n)
            ops = ((~valid).astype(jnp.int8),) + tuple(
                _key_transform(named[k], descending) for k in keys
            ) + (pos,)
            sorted_ops = jax.lax.sort(ops, num_keys=len(ops))
            order = sorted_ops[-1]
            outs = tuple(jnp.take(c, order, axis=0) for c in cols)
            return outs + ((order,) if want_order else ())

        if dist.padded_rows % mesh.num_data_shards == 0:
            shard_of = mesh.row_sharding
        else:
            # uneven row counts cannot be expressed as a row sharding
            # (jit rejects non-divisible out_shardings); these frames are
            # small global results, so replication is the honest layout
            def shard_of(_ndim):
                return mesh.replicated()
        shardings = tuple(shard_of(a.ndim) for a in arrays)
        if want_order:
            shardings = shardings + (shard_of(1),)
        fn = jax.jit(program, out_shardings=shardings)
        _dsort_cache[ckey] = fn
        while len(_dsort_cache) > _DSORT_CACHE_CAP:
            _dsort_cache.popitem(last=False)
    else:
        _dsort_cache.move_to_end(ckey)

    trace = current_trace()
    t0 = (_trace_shards(trace, "dsort", dist=dist)
          if trace is not None else 0.0)
    with span("dsort.dispatch"):
        outs = fn(valid_dev, *arrays)
    counters.inc("mesh.dispatches")
    if trace is not None:
        _trace_mesh_done(trace, list(outs), t0, "dsort", mesh=mesh)
    return outs


def _dsort_columnsort(dist, keys, descending, tensor_names, arrays,
                      valid_dev, want_order):
    """Columnsort over the data axis (see :func:`dsort` docstring).

    Shards are the matrix "columns" (r rows each); the 8 steps:
    1. sort columns; 2. deal rows round-robin across shards
    (``all_to_all``); 3. sort; 4. inverse deal (contiguous chunks out,
    interleave in); 5. sort; 6. shift half-blocks to the next shard
    (``ppermute``); 7. sort the shifted column (the conceptual extra
    column s is ``[last shard's bottom, +inf]``, already sorted — free);
    8. unshift. Requires r ≥ 2(S-1)² and 2S | r, met by padding each
    shard with flag-2 sentinel rows inside the program; the final global
    slice drops them (they sort strictly after the frame's own pad
    rows). A flag column (−5 min-sentinel < 0 real < 1 frame-pad <
    2 internal-pad < 9 max-sentinel) is the most significant sort key
    and the original global row id the least, so the whole pipeline is
    stable and pad-safe; row ids double as the host-column permutation.
    """
    mesh = dist.mesh
    axis = mesh.data_axis
    S = mesh.num_data_shards
    padded = dist.padded_rows
    r = padded // S
    # internal per-shard row count: multiple of 2S, >= 2(S-1)^2 (Leighton's
    # validity condition), >= r
    need = max(r, 2 * (S - 1) * (S - 1))
    rp = ((need + 2 * S - 1) // (2 * S)) * (2 * S)
    h = rp // 2
    idx_dt = jnp.int32 if padded < 2 ** 31 else jnp.int64

    ckey = ("columnsort", mesh.mesh, tuple(keys), descending, want_order,
            rp, tuple((n, a.shape, str(a.dtype))
                      for n, a in zip(tensor_names, arrays)))

    def build_full():
        key_idx = [tensor_names.index(k) for k in keys]

        def colsort(flag, rowid, cols):
            """One column (shard-local) sort by (flag, keys..., rowid).

            ONE fused ``lax.sort`` with ``num_keys`` (XLA sorts the
            lexicographic tuple in a single pass) instead of a stable
            argsort-per-key chain — the chain cost K+2 sorts plus
            gathers per step and dominated the columnsort wall. rowid is
            unique, so the tuple is a total order and stability is
            implied. Payload columns (incl. vector cells, which XLA Sort
            cannot carry alongside rank-1 keys) gather through the
            sorted positions."""
            m = flag.shape[0]
            ops = (flag,) + tuple(
                _key_transform(cols[ki], descending) for ki in key_idx
            ) + (rowid, jnp.arange(m, dtype=rowid.dtype))
            sorted_ops = jax.lax.sort(ops, num_keys=len(ops) - 1)
            order = sorted_ops[-1]
            return (sorted_ops[0], sorted_ops[-2],
                    [jnp.take(c, order, axis=0) for c in cols])

        def deal(a):
            # step 2: row i -> shard i%S, landing at j*(rp/S) + i//S from
            # source shard j (column-major read, row-major reshape)
            a2 = a.reshape((rp // S, S) + a.shape[1:]).swapaxes(0, 1)
            a2 = jax.lax.all_to_all(a2, axis, 0, 0, tiled=False)
            return a2.reshape((rp,) + a.shape[1:])

        def undeal(a):
            # step 4: contiguous chunk c -> shard c, received rows
            # interleave back (row-major read, column-major reshape)
            a2 = a.reshape((S, rp // S) + a.shape[1:])
            a2 = jax.lax.all_to_all(a2, axis, 0, 0, tiled=False)
            return a2.swapaxes(0, 1).reshape((rp,) + a.shape[1:])

        fwd = [(j, j + 1) for j in range(S - 1)]
        bwd = [(j + 1, j) for j in range(S - 1)]

        def shard_fn(valid, *cols):
            me = jax.lax.axis_index(axis)
            # flags: 0 real, 1 frame pad; internal pad rows (flag 2) are
            # appended to reach rp
            flag = jnp.where(valid, jnp.int8(0), jnp.int8(1))
            # widen axis_index before the multiply: me*r in int32 wraps
            # for frames at/above 2^31 padded rows (idx_dt is int64 then)
            rowid = me.astype(idx_dt) * r + jnp.arange(r, dtype=idx_dt)
            pad_n = rp - r
            flag = jnp.concatenate([flag, jnp.full(pad_n, 2, jnp.int8)])
            rowid = jnp.concatenate(
                [rowid, jnp.zeros(pad_n, idx_dt)])
            cs = [jnp.concatenate(
                [c, jnp.zeros((pad_n,) + c.shape[1:], c.dtype)])
                for c in cols]

            # named_scope per step: the whole pipeline is ONE compiled
            # program, so host spans cannot see the rounds — the scopes
            # label them in jax profiler traces instead (the measured
            # per-step costs live in benchmarks/dsort_steps_bench.py)
            with jax.named_scope("columnsort.s1_sort"):
                flag, rowid, cs = colsort(flag, rowid, cs)      # 1
            with jax.named_scope("columnsort.s2_deal"):
                flag, rowid = deal(flag), deal(rowid)           # 2
                cs = [deal(c) for c in cs]
            with jax.named_scope("columnsort.s3_sort"):
                flag, rowid, cs = colsort(flag, rowid, cs)      # 3
            with jax.named_scope("columnsort.s4_undeal"):
                flag, rowid = undeal(flag), undeal(rowid)       # 4
                cs = [undeal(c) for c in cs]
            with jax.named_scope("columnsort.s5_sort"):
                flag, rowid, cs = colsort(flag, rowid, cs)      # 5

            # 6: shifted column = [prev shard's bottom | own top]. Shard 0
            # receives no message and must see a MIN sentinel half: flags
            # travel offset by +16, so ppermute's zero-fill decodes to -16
            # (< every real flag) while real flags restore exactly. The
            # sentinel rows sort to shard 0's B1 top, which step 8 never
            # reads (only B1 bottoms and RIGHTWARD-shifted tops survive).
            with jax.named_scope("columnsort.s6_shift"):
                prev_flag = (jax.lax.ppermute(
                    flag[h:] + jnp.int8(16), axis, fwd) - jnp.int8(16))
                b1_flag = jnp.concatenate([prev_flag, flag[:h]])
                b1_rowid = jnp.concatenate(
                    [jax.lax.ppermute(rowid[h:], axis, fwd), rowid[:h]])
                b1_cs = [jnp.concatenate(
                    [jax.lax.ppermute(c[h:], axis, fwd), c[:h]])
                    for c in cs]
            with jax.named_scope("columnsort.s7_sort"):
                b1_flag, b1_rowid, b1_cs = colsort(
                    b1_flag, b1_rowid, b1_cs)                   # 7
            # the conceptual extra column S is [last shard's bottom | +inf
            # sentinel] — both parts already sorted, so it needs no sort

            # 8: unshift — own top = B1 bottom; own bottom = next shard's
            # B1 top (last shard: the extra column's top = its own step-5
            # bottom). ppermute zero-fill is overwritten by the where.
            last = me == S - 1

            def unshift(b1, own_step5):
                nxt = jax.lax.ppermute(b1[:h], axis, bwd)
                bottom = jnp.where(last, own_step5[h:], nxt)
                return jnp.concatenate([b1[h:], bottom])

            with jax.named_scope("columnsort.s8_unshift"):
                out_flag = unshift(b1_flag, flag)
                out_rowid = unshift(b1_rowid, rowid)
                out_cs = [unshift(b, c) for b, c in zip(b1_cs, cs)]
            del out_flag  # flags exist only to steer the sort
            return tuple(out_cs) + ((out_rowid,) if want_order else ())

        in_specs = (P(axis),) + tuple(
            P(axis, *([None] * (a.ndim - 1))) for a in arrays)
        out_specs = tuple(
            P(axis, *([None] * (a.ndim - 1))) for a in arrays)
        if want_order:
            out_specs = out_specs + (P(axis),)
        prog = shard_map(shard_fn, mesh=mesh.mesh, in_specs=in_specs,
                         out_specs=out_specs)

        def full(valid, *cols):
            outs = prog(valid, *cols)
            # drop the internal padding: the global [S*rp] result is
            # sorted with flag-2 rows strictly after the frame's own pad
            # rows, so the first `padded` rows ARE the frame's layout
            return tuple(o[:padded] for o in outs)

        return full

    out_shardings = tuple(mesh.row_sharding(a.ndim) for a in arrays)
    if want_order:
        out_shardings = out_shardings + (mesh.row_sharding(1),)

    # TFT_EXECUTOR=pjrt: the whole columnsort pipeline — local sorts AND
    # the all_to_all/ppermute exchanges — compiles as one GSPMD
    # executable in the native C++ core
    nm = _native_mesh(mesh)
    if nm is not None:
        in_shardings = (mesh.row_sharding(1),) + tuple(
            mesh.row_sharding(a.ndim) for a in arrays)
        try:
            outs_np = nm.run_sharded(
                ("dsort",) + ckey[1:], build_full,
                [valid_dev] + list(arrays), in_shardings,
                list(out_shardings), mesh)
        except Exception as e:
            _native_mesh_fallback(e)
            outs_np = None
        if outs_np is not None:
            return tuple(jax.device_put(a, s)
                         for a, s in zip(outs_np, out_shardings))

    fn = _dsort_cache.get(ckey)
    if fn is None:
        fn = jax.jit(build_full(), out_shardings=out_shardings)
        _dsort_cache[ckey] = fn
        while len(_dsort_cache) > _DSORT_CACHE_CAP:
            _dsort_cache.popitem(last=False)
    else:
        _dsort_cache.move_to_end(ckey)

    trace = current_trace()
    t0 = 0.0
    if trace is not None:
        t0 = _trace_shards(trace, "dsort", dist=dist)
        # the compiled pipeline's static exchange schedule (steps 2/4/6/8)
        trace.add("collective", name="all_to_all", ts=t0, count=2,
                  op="dsort.columnsort")
        trace.add("collective", name="ppermute", ts=t0, count=2,
                  op="dsort.columnsort")
    with span("dsort.columnsort_dispatch"):
        outs = fn(valid_dev, *arrays)
    counters.inc("mesh.dispatches")
    if trace is not None:
        _trace_mesh_done(trace, list(outs), t0, "dsort", mesh=mesh)
    return outs


def dreduce_blocks(fetches, dist: DistributedFrame):
    """Mesh-parallel reduce to one row.

    Two strategies:

    - ``fetches`` is a mapping ``{column: combiner-name}`` (sum/min/max/
      prod): ONE compiled ``shard_map`` program — local shard reduce, pad
      rows masked to the combiner's neutral element, cross-shard combine as
      an ICI collective (``lax.psum``/``pmin``/``pmax``). This is the
      BASELINE north-star path.
    - ``fetches`` is a computation (z/z_input contract): generic combine —
      per-shard async jit dispatches, partials stacked, one final reduce.

    On a LAZY frame a monoid reduce FOLDS into the pending chain's
    fused program as the terminal combiner (one mesh dispatch for chain
    + reduction, DrJAX-style); generic computations materialize the
    chain and run the eager path.
    """
    lz = _lazy_input(dist)
    if lz is not None:
        from ..plan import dist as _dplan
        out = _dplan.record_reduce(fetches, lz)
        if out is not None:
            return out
        dist = _dplan.materialize(lz)
    return _dreduce_blocks_eager(fetches, dist)


@traced_query("dreduce_blocks", _meta_with_fetches)
def _dreduce_blocks_eager(fetches, dist: DistributedFrame):
    if isinstance(fetches, Mapping) and all(
            isinstance(v, str) for v in fetches.values()):
        return _elastic.elastic_call(
            "dreduce_blocks", dist,
            lambda d: _collective_reduce(fetches, d))
    return _elastic.elastic_call(
        "dreduce_blocks", dist, lambda d: _generic_reduce(fetches, d))


# Compiled collective-reduce programs, keyed by everything that shapes the
# program (mesh, axis, column names/padded shapes/dtypes, combiners). The
# valid-row count is a traced scalar argument, not baked in, so frames whose
# padded global shapes coincide share one executable. LRU-bounded: distinct
# padded shapes otherwise accumulate executables without limit.
from collections import OrderedDict

_collective_cache: "OrderedDict[tuple, object]" = OrderedDict()
_COLLECTIVE_CACHE_CAP = 64

_native_mesh_warned = False


def _native_mesh(mesh: DeviceMesh):
    """The native GSPMD mesh executor when ``TFT_EXECUTOR=pjrt`` routes
    mesh ops through the C++ core, else ``None`` (the jax path)."""
    import os

    if os.environ.get("TFT_EXECUTOR") != "pjrt":
        return None
    from . import native_mesh

    return native_mesh.executor_for(mesh)


def _native_mesh_fallback(e: Exception):
    global _native_mesh_warned
    if not _native_mesh_warned:
        from ..utils.logging import get_logger

        get_logger("native_mesh").warning(
            "native mesh dispatch failed (%s); falling back to the jax "
            "path for this and subsequent calls that hit the same error",
            e)
        _native_mesh_warned = True


def _collective_shard_fn(names, combs, axis):
    """The per-shard masked-reduce + collective program — ONE source of
    truth shared by the jax ``shard_map`` path and the native GSPMD path."""

    def shard_fn(nv, *shards):
        outs = []
        rows = shards[0].shape[0]
        valid = jnp.arange(rows) < nv[0]
        for name, s in zip(names, shards):
            c = combs[name]
            mask = valid.reshape((rows,) + (1,) * (s.ndim - 1))
            neutral = jnp.asarray(c.neutral(s.dtype))
            masked = jnp.where(mask, s, neutral)
            local = c.local(masked, 0)
            outs.append(c.collective(local, axis))
        return tuple(outs)

    return shard_fn


def _collective_reduce(col_combiners: Mapping[str, str],
                       dist: DistributedFrame) -> Dict[str, np.ndarray]:
    mesh = dist.mesh
    axis = mesh.data_axis
    if dist.num_rows == 0:
        raise ValueError("reduce on an empty distributed frame")
    combs = {}
    for name, cname in col_combiners.items():
        if name not in dist.schema:
            raise KeyError(f"No column {name!r}")
        if cname not in COMBINERS:
            raise KeyError(
                f"Unknown combiner {cname!r}; known: {sorted(COMBINERS)}")
        combs[name] = COMBINERS[cname]

    names = sorted(col_combiners)
    arrays = [dist.columns[n] for n in names]
    key = (mesh.mesh, axis,
           tuple((n, col_combiners[n], a.shape, str(a.dtype))
                 for n, a in zip(names, arrays)))
    # per-shard valid-row counts ride in sharded over the axis: pads are
    # masked wherever they fall (a multi-host frame pads per process,
    # not in a global suffix)
    in_specs = (P(axis),) + tuple(
        P(axis, *([None] * (a.ndim - 1))) for a in arrays)

    outs = None
    nm = _native_mesh(mesh)
    if nm is not None:
        try:
            outs = nm.dreduce_collective(
                _collective_shard_fn(names, combs, axis), in_specs, names,
                dist, dist.per_shard_valid(), key)
        except Exception as e:
            _native_mesh_fallback(e)
            outs = None
    if outs is None:
        fn = _collective_cache.get(key)
        if fn is not None:
            _collective_cache.move_to_end(key)
        else:
            out_specs = tuple(P() for _ in arrays)
            fn = jax.jit(shard_map(
                _collective_shard_fn(names, combs, axis), mesh=mesh.mesh,
                in_specs=in_specs, out_specs=out_specs))
            _collective_cache[key] = fn
            while len(_collective_cache) > _COLLECTIVE_CACHE_CAP:
                _collective_cache.popitem(last=False)
        nv_dev = jax.make_array_from_callback(
            (mesh.num_data_shards,), mesh.row_sharding(1),
            lambda idx: dist.per_shard_valid().astype(np.int32)[idx])
        trace = current_trace()
        t0 = 0.0
        if trace is not None:
            t0 = _trace_shards(trace, "dreduce_blocks", dist=dist)
            for name in names:
                trace.add("collective", name=combs[name].ici, ts=t0,
                          column=name, op="dreduce_blocks")
        with span("dreduce_blocks.collective_dispatch"):
            outs = fn(nv_dev, *arrays)
        if trace is not None:
            _trace_mesh_done(trace, list(outs), t0, "dreduce_blocks",
                             mesh=mesh)
    counters.inc("mesh.dispatches")
    result = {}
    for name, a in zip(names, outs):
        v = np.asarray(a)
        f = dist.schema[name]
        if v.dtype != f.dtype.np_storage and f.dtype is not _dt.bfloat16:
            v = v.astype(f.dtype.np_storage)
        result[name] = v
    return result


def _cached_group_ids(dist: DistributedFrame, keys, max_groups):
    """Memoized key factorization (see ``DistributedFrame._group_ids_cache``).

    Returns ``(ids_dev, uniques, uniq_dev, count_dev, num_groups)`` —
    ``uniques`` is None on the device path, ``uniq_dev``/``count_dev``
    are None on the host path.
    """
    if max_groups is not None:
        ckey = ("device", tuple(keys), max_groups)
        hit = _group_ids_cache_get(dist, ckey)
        if hit is None:
            hit = _device_key_ids(dist, keys, max_groups)
            _group_ids_cache_put(dist, ckey, hit)
        ids_dev, uniq_dev, count_dev, num_groups = hit
        return ids_dev, None, uniq_dev, count_dev, num_groups
    ckey = ("host", tuple(keys))
    hit = _group_ids_cache_get(dist, ckey)
    if hit is None:
        hit = _host_group_ids(dist, keys)
        _group_ids_cache_put(dist, ckey, hit)
    ids_dev, uniques, num_groups = hit
    return ids_dev, uniques, None, None, num_groups


_GROUP_IDS_CACHE_CAP = 8


def _group_ids_cache_get(dist: DistributedFrame, ckey: tuple):
    hit = dist._group_ids_cache.get(ckey)
    if hit is not None:
        dist._group_ids_cache.move_to_end(ckey)
    return hit


def _group_ids_cache_put(dist: DistributedFrame, ckey: tuple, hit: tuple):
    dist._group_ids_cache[ckey] = hit
    while len(dist._group_ids_cache) > _GROUP_IDS_CACHE_CAP:
        dist._group_ids_cache.popitem(last=False)


def _monoid_group_plan(dist: DistributedFrame, keys):
    """Host-key group ids + the hot-key salt plan for a monoid
    aggregation — ONE definition shared by ``_daggregate``'s jax path
    and the fused distributed plan's folded ``daggregate``
    (``plan/dist.py``), so the two can never drift.

    Returns ``(ids_dev, uniques, num_groups, salt_plan)``; salting is
    cached per (frame, keys, threshold) like the group ids themselves.
    """
    ids_dev, uniques, _, _, num_groups = _cached_group_ids(
        dist, keys, None)
    salt_plan = None
    if dist.mesh.num_data_shards > 1:
        frac = _elastic.salt_fraction()
        if frac is not None:
            skey = ("salt", tuple(keys), frac)
            cached = _group_ids_cache_get(dist, skey)
            if cached is None:
                cached = (_elastic.plan_key_salt(
                    dist, ids_dev, num_groups,
                    dist.mesh.num_data_shards),)
                _group_ids_cache_put(dist, skey, cached)
            salt_plan = cached[0]
    return ids_dev, uniques, num_groups, salt_plan


def _monoid_agg_shard_fn(fetch_names, col_combiners, axis,
                         prog_groups: int, seg_impl=None):
    """The per-shard monoid segment-reduce + collective fragment — ONE
    definition shared by ``_daggregate`` (jax AND native routes), the
    fused distributed plan's folded ``daggregate``, and the streaming
    mesh fold (``plan/dist.py``), so the four dispatch paths can never
    drift."""
    from ..ops.segment_reduce import segment_sum as _segsum

    def shard_fn(ids_local, *vals_local):
        outs = []
        for f, v in zip(fetch_names, vals_local):
            cname = col_combiners[f]
            if cname == "sum":
                local = _segsum(v, ids_local, prog_groups,
                                impl=seg_impl)
            else:
                # mask pad/out-of-range rows to the combiner's neutral
                # and clamp their id to 0 so XLA's segment primitive
                # sees only in-range indices
                c = COMBINERS[cname]
                valid = ids_local >= 0
                vmask = valid.reshape((-1,) + (1,) * (v.ndim - 1))
                neutral = jnp.asarray(c.neutral(v.dtype))
                masked = jnp.where(vmask, v, neutral)
                safe_ids = jnp.where(valid, ids_local, 0)
                seg = {"min": jax.ops.segment_min,
                       "max": jax.ops.segment_max,
                       "prod": jax.ops.segment_prod}[cname]
                local = seg(masked, safe_ids,
                            num_segments=prog_groups)
                # a group absent from this shard holds the identity;
                # for min/max that identity is +-inf, which the
                # cross-shard collective absorbs (every group exists
                # somewhere)
            outs.append(COMBINERS[cname].collective(local, axis))
        return tuple(outs)

    return shard_fn


def _monoid_agg_result(schema: Schema, keys, fetch_names, tables,
                       key_cols, num_out: int) -> TensorFrame:
    """Host assembly of a monoid aggregation's result frame (key
    columns + sliced/cast fetch tables) — shared by ``_daggregate``
    and the fused plan's folded ``daggregate``."""
    from ..schema import Field
    from ..shape import Unknown

    cols = dict(key_cols)
    for f, t in zip(fetch_names, tables):
        v = np.asarray(t)[:num_out]
        fld = schema[f]
        if v.dtype != fld.dtype.np_storage and fld.dtype is not _dt.bfloat16:
            v = v.astype(fld.dtype.np_storage)
        cols[f] = v
    out_fields = [schema[k] for k in keys] + [
        Field(f, schema[f].dtype,
              block_shape=(schema[f].block_shape.with_lead(Unknown)
                           if schema[f].block_shape is not None else None),
              sql_rank=schema[f].sql_rank)
        for f in fetch_names]
    return TensorFrame.from_blocks([Block(cols, num_out)],
                                   Schema(out_fields))


def _host_group_ids(dist: DistributedFrame, keys):
    """Key columns → dense group ids on the mesh (host factorization).

    Only the scalar KEY columns visit the host; ids come back row-sharded
    with pad rows marked ``-1`` (dropped by every consumer). Returns
    ``(ids_dev, uniques, num_groups)``.
    """
    from ..engine.ops import InvalidTypeError, _factorize_keys

    mesh = dist.mesh
    schema = dist.schema
    mask = dist.valid_row_mask()
    key_host = []
    for k in keys:
        fld = schema[k]
        a = dist.host_read_padded(k)
        a = a[mask] if dist.shard_valid is not None else a[: dist.num_rows]
        if a.ndim != 1:
            raise InvalidTypeError(f"Key column {k!r} must be scalar-typed")
        if a.dtype != fld.dtype.np_storage and fld.dtype is not _dt.bfloat16:
            # distribute() stored this column in its device dtype; if that
            # narrowed the storage type (long->int / double->float with x64
            # off), distinct keys may already have collapsed on device —
            # group identity is unrecoverable, so fail loudly instead of
            # silently merging groups
            if np.dtype(a.dtype).itemsize < np.dtype(fld.dtype.np_storage).itemsize:
                raise InvalidTypeError(
                    f"Key column {k!r} ({fld.dtype.name}) was narrowed to "
                    f"{a.dtype} on device, which can merge distinct keys; "
                    f"cast the key to a device-exact type (e.g. int) before "
                    f"distribute(), or enable x64")
            a = a.astype(fld.dtype.np_storage)
        key_host.append(a)
    fact = _factorize_keys(key_host)
    ids_padded = np.full(dist.padded_rows, -1, np.int32)  # -1: pad, dropped
    if dist.shard_valid is not None:
        ids_padded[mask] = fact.ids
    else:
        ids_padded[: dist.num_rows] = fact.ids
    ids_dev = jax.make_array_from_callback(
        (dist.padded_rows,), mesh.row_sharding(1),
        lambda idx: ids_padded[idx])
    return ids_dev, fact.uniques, fact.num_groups


def _device_group_ids(dist: DistributedFrame, key: str, max_groups: int,
                      valid=None):
    """Dense group ids computed ON DEVICE for a single integer key column.

    The host-factorization path ships the whole key column driver-side per
    call (the reference's Catalyst groupBy did the same in the JVM,
    ``DebugRowOps.scala:533-578``); at 100k+ groups that transfer and the
    host lexsort dominate. Here the key column never leaves the mesh: a
    device sort-unique (``jnp.unique`` with a static size cap) builds the
    group table and a ``searchsorted`` maps rows to ids — XLA inserts the
    cross-shard gather for the sort, which IS the shuffle, on ICI.

    ``max_groups`` caps the static table size (XLA needs static shapes);
    ``valid`` (row-sharded bool [padded]) is built when absent so
    composite-key callers upload it once. Returns the raw
    ``(ids_dev, uniques_dev, count_dev, sentinel_hit)`` from
    :func:`_build_device_ids` — ids are ``-1`` for pad rows; cap overflow
    and the sentinel flag are the CALLER's to read back and raise on.
    """
    kcol = dist.columns[key]
    if not jnp.issubdtype(kcol.dtype, jnp.integer):
        raise _ops.InvalidTypeError(
            f"device-side aggregation needs an integer key column; {key!r} "
            f"is {kcol.dtype} (use the host path)")
    fld = dist.schema[key]
    if np.dtype(kcol.dtype).itemsize < np.dtype(fld.dtype.np_storage).itemsize:
        # same hazard _host_group_ids guards: device narrowing (long->int
        # with x64 off) can merge distinct keys — unrecoverable, so fail
        raise _ops.InvalidTypeError(
            f"Key column {key!r} ({fld.dtype.name}) was narrowed to "
            f"{kcol.dtype} on device, which can merge distinct keys; cast "
            f"the key to a device-exact type (e.g. int) before "
            f"distribute(), or enable x64")
    if valid is None:
        valid = _valid_dev(dist)
    # NB: returns traced/async values incl. the sentinel flag — callers
    # read back and raise (lets the composite path dispatch every key's
    # program before the first synchronization)
    return _build_device_ids(kcol, valid, max_groups)


def _sentinel_check(sentinel_hit, key: str) -> None:
    if bool(sentinel_hit):
        raise _ops.InvalidTypeError(
            f"key column {key!r} contains the dtype's max value, which the "
            f"device path reserves as its pad sentinel; use the host path "
            f"(max_groups=None) for such keys")


def _valid_dev(dist: DistributedFrame):
    valid_host = dist.valid_row_mask()
    return jax.make_array_from_callback(
        (dist.padded_rows,), dist.mesh.row_sharding(1),
        lambda idx: valid_host[idx])


@functools.partial(jax.jit, static_argnums=(2,))
def _build_device_ids(kc, vm, max_groups: int):
    """Sort-unique group table + per-row dense ids, one compiled program
    (module-level jit: re-invocations with the same shapes/cap reuse it)."""
    sentinel = jnp.iinfo(kc.dtype).max
    sentinel_hit = jnp.any(vm & (kc == sentinel))
    masked = jnp.where(vm, kc, sentinel)
    uniq = jnp.unique(masked, size=max_groups + 1, fill_value=sentinel)
    count = jnp.sum(uniq != sentinel)
    ids = jnp.searchsorted(uniq, masked).astype(jnp.int32)
    ids = jnp.where(vm, ids, -1)
    return ids, uniq, count, sentinel_hit


@functools.partial(jax.jit, static_argnums=(2,))
def _combine_ids(acc, ids_k, radix: int):
    """Mixed-radix combination of dense per-key ids (int32 throughout —
    the device path must work with x64 disabled, so no int64 packing)."""
    return acc * np.int32(radix) + ids_k


def _device_key_ids(dist: DistributedFrame, keys, max_groups: int):
    """Shared entry to the device-keys path (monoid + generic daggregate).

    One key: sort-unique + searchsorted on the mesh (the key never visits
    the host). Composite keys: each key column factorizes to dense ids the
    same way, the ids combine into one mixed-radix int32 id space
    (``radix = max_groups + 1`` per position — every key's distinct count
    is bounded by the final group count, so one cap serves all), and one
    more sort-unique over the combined ids yields the dense group table.
    All arithmetic stays int32: ``(cap+1)^k`` must fit, which bounds the
    cap at ~46k for two keys (checked loudly; the host path has no cap).

    Returns ``(ids_dev, key_table, count_dev, table_groups)`` where
    ``key_table`` carries what :func:`_device_key_columns` needs to
    rebuild the key columns and ``table_groups`` is the static table size
    (cap + sentinel slot)."""
    if len(keys) == 1:
        ids_dev, uniq_dev, count_dev, sent = _device_group_ids(
            dist, keys[0], max_groups)
        _sentinel_check(sent, keys[0])
        return ids_dev, ("single", uniq_dev), count_dev, max_groups + 1

    radix = max_groups + 1
    if radix ** len(keys) >= 2 ** 31 - 1:
        raise ValueError(
            f"max_groups={max_groups} with {len(keys)} key columns "
            f"overflows the int32 combined-id space ((cap+1)^k must stay "
            f"below 2^31); lower the cap or use the host path "
            f"(max_groups=None)")
    # one valid-mask upload serves every per-key program and the final
    # combine; all dispatches go out before the first readback
    valid = _valid_dev(dist)
    per = [_device_group_ids(dist, k, max_groups, valid=valid)
           for k in keys]
    combined = None
    for ids_k, _, _, _ in per:
        combined = (ids_k if combined is None
                    else _combine_ids(combined, ids_k, radix))
    ids, uniq_c, count, _ = _build_device_ids(combined, valid, max_groups)
    for k, (_, _, count_k, sent_k) in zip(keys, per):
        _sentinel_check(sent_k, k)
        if int(count_k) > max_groups:
            # a truncated per-key table would silently merge distinct
            # keys before the final overflow check could see them
            raise ValueError(
                f"more than max_groups={max_groups} distinct values in "
                f"key column {k!r}; raise max_groups (the static table "
                f"cap)")
    per_uniq = [u for _, u, _, _ in per]
    return ids, ("multi", uniq_c, per_uniq, radix), count, max_groups + 1


def _device_key_columns(dist: DistributedFrame, keys, key_table,
                        count_dev, max_groups: int):
    """Overflow check + host materialization of the device group table(s).
    Returns ``({key name: values}, num_groups)``."""
    count = int(count_dev)
    if count > max_groups:
        raise ValueError(
            f"more than max_groups={max_groups} distinct keys in "
            f"{keys}; raise max_groups (the static table cap)")

    def cast(vals, key):
        kfld = dist.schema[key]
        if vals.dtype != kfld.dtype.np_storage:  # integer keys only
            vals = vals.astype(kfld.dtype.np_storage)
        return vals

    if key_table[0] == "single":
        return {keys[0]: cast(np.asarray(key_table[1])[:count],
                              keys[0])}, count
    _, uniq_c, per_uniq, radix = key_table
    comb = np.asarray(uniq_c)[:count].astype(np.int64)
    digits = []
    for _ in keys:                       # least-significant digit first
        digits.append(comb % radix)
        comb = comb // radix
    return {k: cast(np.asarray(per_uniq[i])[digits[len(keys) - 1 - i]], k)
            for i, k in enumerate(keys)}, count


def daggregate(fetches, dist: DistributedFrame, keys,
               max_groups: Optional[int] = None) -> TensorFrame:
    """Mesh-distributed keyed aggregation.

    The reference's Catalyst shuffle + UDAF (``DebugRowOps.scala:533-681``)
    re-expressed TPU-first: instead of moving rows between workers by key,
    each shard reduces its LOCAL rows into a dense ``[groups, ...]`` table
    and the tables are combined across the data axis — the shuffle becomes
    an ICI collective over a small table. Only the scalar KEY columns visit
    the host (to build dense group ids); the values never leave their
    shards.

    Two paths, mirroring :func:`~tensorframes_tpu.api.aggregate`:

    - ``fetches`` is a mapping ``{column: combiner-name}`` (sum/min/max/
      prod): one segment-reduce launch per column (the Pallas one-hot
      matmul for float sums) + one ``psum``-family collective;
    - ``fetches`` is a computation (block-level ``<col>_input`` reduce,
      the UDAF contract): per-shard sort-by-id + segmented
      ``associative_scan`` whose pair-combiner IS the user computation on
      two-row blocks, segment tails scattered into a ``[groups, ...]``
      partial table, then a cross-shard masked fold of the stacked tables
      with the same combiner. Combine order is contractually unspecified
      (the compaction contract — the computation must tolerate arbitrary
      regrouping, ``core.py:96-97``), which is exactly what makes the
      O(log rows) scan legal.

    ``keys``: key column name or list of names. Returns a host
    :class:`TensorFrame` of one row per group (keys + fetches, fetches
    sorted by name), like :func:`~tensorframes_tpu.api.aggregate`.

    ``max_groups``: opt into DEVICE-side group ids for integer key(s)
    (``_device_key_ids``): the key columns never visit the host — at
    100k+ groups the host path's driver-side transfer + lexsort dominate
    (``benchmarks/daggregate_bench.py`` measures both). The value caps
    the static group-table size; exceeding it raises. Composite keys
    combine per-key dense ids in a mixed-radix int32 space, which bounds
    the cap at ``(cap+1)^k < 2^31``.

    Under ``TFT_EXECUTOR=pjrt`` the aggregation program runs in the
    native C++ core, whose dispatch marshals ids and value columns
    through host numpy per call (the documented correctness-proof
    trade, ``native_mesh`` module docstring) — so the device-residency
    promises above (values stay on their shards; ``max_groups`` keys
    never visit the host) hold on the default jax dispatch, not on the
    native route. Latency-sensitive iterative workloads should keep the
    jax path for this op.

    Skew: on the monoid host-key jax path, a key group holding more
    than ``TFT_HOT_KEY_FRACTION`` of the rows is **salted** across the
    data shards (``parallel/elastic.py``) — per-salt partials fold back
    on the host, so results keep the same groups and order (float sums
    may reassociate, like any resharding).
    """
    if isinstance(keys, str):
        keys = [keys]
    keys = list(keys)
    if not keys:
        raise ValueError("daggregate needs at least one key column")
    lz = _lazy_input(dist)
    if lz is not None:
        # a monoid host-key aggregation over a filter-free chain whose
        # keys pass through untouched FOLDS into the fused program as
        # the terminal combiner; anything else materializes the chain
        # (still fused among itself) and runs the eager op on the
        # device-resident result
        from ..plan import dist as _dplan
        out = _dplan.record_aggregate(fetches, lz, keys, max_groups)
        if out is not None:
            return out
        dist = _dplan.materialize(lz)
    return _daggregate_eager(fetches, dist, keys, max_groups)


@traced_query("daggregate", _meta_daggregate)
def _daggregate_eager(fetches, dist: DistributedFrame, keys,
                      max_groups: Optional[int] = None) -> TensorFrame:
    return _elastic.elastic_call(
        "daggregate", dist,
        lambda d: _daggregate(fetches, d, keys, max_groups))


def _daggregate(fetches, dist: DistributedFrame, keys,
                max_groups: Optional[int]) -> TensorFrame:
    schema = dist.schema
    for k in keys:
        if k not in schema:
            raise KeyError(f"No key column {k!r}; columns: {schema.names}")
    from ..engine.ops import _is_sketch, _monoid_mapping
    if not _monoid_mapping(fetches):
        return _generic_daggregate(fetches, dist, keys,
                                   max_groups=max_groups)
    if any(_is_sketch(v) for v in fetches.values()):
        return _daggregate_sketch(fetches, dist, keys, max_groups)
    col_combiners = fetches

    from ..engine.ops import _validate_monoid_fetches

    mesh = dist.mesh
    axis = mesh.data_axis
    value_names = [n for n in schema.names if n not in keys]
    _validate_monoid_fetches(col_combiners, value_names,
                             "before distribute()")
    n = dist.num_rows
    if n == 0:
        raise ValueError("aggregate on an empty distributed frame")

    device_keys = max_groups is not None
    if device_keys:
        ids_dev, uniques, uniq_dev, count_dev, num_groups = \
            _cached_group_ids(dist, keys, max_groups)
        salt_plan = None
    else:
        ids_dev, uniques, num_groups, salt_plan = _monoid_group_plan(
            dist, keys)
        uniq_dev = count_dev = None
        # high-cardinality keys: the dense per-shard tables below hold
        # EVERY group on EVERY shard — beyond TFT_SHUFFLE_AGG_GROUPS,
        # hash-repartition instead so each device aggregates only its
        # own key range (O(groups/shards) state; parallel/exchange.py)
        from .exchange import (shuffle_agg_groups_threshold,
                               shuffle_enabled)
        thr = shuffle_agg_groups_threshold()
        if (thr is not None and shuffle_enabled()
                and num_groups > thr and mesh.num_data_shards > 1):
            from .exchange import _shuffle_daggregate_impl
            counters.inc("mesh.shuffle_agg_routes")
            return _shuffle_daggregate_impl(fetches, dist, keys)
    if salt_plan is not None:
        prog_ids, prog_groups = salt_plan[0], salt_plan[1]
    else:
        prog_ids, prog_groups = ids_dev, num_groups

    fetch_names = sorted(col_combiners)
    arrays = [dist.columns[f] for f in fetch_names]
    in_specs = (P(axis),) + tuple(
        P(axis, *([None] * (a.ndim - 1))) for a in arrays)
    out_specs = tuple(P() for _ in fetch_names)

    # TFT_EXECUTOR=pjrt: the per-shard segment reduce + collective runs as
    # ONE GSPMD executable in the native C++ core (the last mesh op to
    # gain the route — reference property: every UDAF compaction ran in
    # the C++ session, DebugRowOps.scala:617-662). The XLA scatter-add
    # segment_sum flavor is forced: the Pallas flavor lowers to Mosaic
    # custom calls the native core's backends cannot compile.
    pkey = ("daggregate", mesh.mesh, axis, prog_groups,
            tuple((f, col_combiners[f]) for f in fetch_names),
            tuple((a.shape, str(a.dtype)) for a in arrays))
    tables = None
    # salted programs stay on the jax path: the host-side fold below is
    # the salting's second half, and the native route re-marshals anyway
    nm = None if salt_plan is not None else _native_mesh(mesh)
    if nm is not None:
        def build_prog():
            return shard_map(
                _monoid_agg_shard_fn(fetch_names, col_combiners, axis,
                                     prog_groups, seg_impl="xla"),
                mesh=mesh.mesh, in_specs=in_specs, out_specs=out_specs)

        in_shardings = [mesh.row_sharding(1)] + [
            mesh.row_sharding(a.ndim) for a in arrays]
        out_shardings = [mesh.replicated() for _ in fetch_names]
        try:
            tables = nm.run_sharded(pkey, build_prog,
                                    [ids_dev] + list(arrays),
                                    in_shardings, out_shardings, mesh)
        except Exception as e:
            _native_mesh_fallback(e)
            tables = None
    if tables is None:
        # cache the jitted program (the closure is fresh per call, so
        # jax's own jit cache would miss and retrace every dispatch)
        fn = _collective_cache.get(pkey)
        if fn is not None:
            _collective_cache.move_to_end(pkey)
        else:
            fn = jax.jit(shard_map(
                _monoid_agg_shard_fn(fetch_names, col_combiners, axis,
                                     prog_groups),
                mesh=mesh.mesh, in_specs=in_specs, out_specs=out_specs))
            _collective_cache[pkey] = fn
            while len(_collective_cache) > _COLLECTIVE_CACHE_CAP:
                _collective_cache.popitem(last=False)
        trace = current_trace()
        t0 = 0.0
        if trace is not None:
            t0 = _trace_shards(trace, "daggregate", dist=dist)
            for f in fetch_names:
                trace.add("collective", name=COMBINERS[col_combiners[f]].ici,
                          ts=t0, column=f, op="daggregate")
        with span("daggregate.dispatch"):
            tables = fn(prog_ids, *arrays)
        if trace is not None:
            _trace_mesh_done(trace, list(tables), t0, "daggregate",
                             mesh=mesh)
    counters.inc("mesh.dispatches")

    if salt_plan is not None:
        tables = [_elastic.fold_salted(t, salt_plan[2], col_combiners[f])
                  for f, t in zip(fetch_names, tables)]
    if device_keys:
        key_cols, num_out = _device_key_columns(dist, keys, uniq_dev,
                                                count_dev, max_groups)
    else:
        key_cols = {k: u for k, u in zip(keys, uniques)}
        num_out = num_groups
    out = _monoid_agg_result(schema, keys, fetch_names, tables,
                             key_cols, num_out)
    if salt_plan is not None:
        attach_hot_keys(out, keys, uniques, salt_plan)
    return out


def attach_hot_keys(frame: TensorFrame, keys, uniques,
                    salt_plan) -> None:
    """Record the hot-key OBSERVATIONS that triggered salting on the
    result frame — the public surface is ``frame.hot_keys()`` and an
    ``explain()`` line (the PR 7 salting decisions were previously
    visible only as counters/log lines). Shared by the eager
    ``_daggregate`` and the fused distributed plan's folded daggregate.
    """
    hot, K = salt_plan[2]
    fracs = salt_plan[3] if len(salt_plan) > 3 else None
    records = []
    for j, g in enumerate(hot):
        kv = {}
        for k, u in zip(keys, uniques):
            v = u[int(g)]
            kv[k] = v.item() if hasattr(v, "item") else v
        records.append({
            "keys": kv,
            "fraction": (float(fracs[j]) if fracs is not None
                         else None),
            "salt_slots": int(K),
        })
    frame._hot_keys = records


def _daggregate_sketch(fetches, dist: DistributedFrame, keys,
                       max_groups: Optional[int]) -> TensorFrame:
    """The sketch half of a mesh aggregation (``docs/joins.md``).

    Sketch combiners hash/bucket on the HOST in float64 (the
    determinism contract that makes aggregate == daggregate == stream
    bit-identical), so their partials fold from the host copies of the
    value columns — read per shard layout under the surrounding
    ``elastic_call`` (a device lost mid-read shrinks/reshards/retries
    like any mesh op). Scalar combiners mixed into the same mapping
    keep the full device segment-reduce + collective path; both halves
    share ONE cached group factorization, so their group order is
    identical by construction.
    """
    from ..engine.ops import (_is_sketch, _validate_monoid_fetches)
    from ..schema import Schema as _Schema

    schema = dist.schema
    if max_groups is not None:
        raise ValueError(
            "max_groups= (device-side group ids) does not compose with "
            "sketch combiners — sketches hash on the host; drop "
            "max_groups or the sketch fetches")
    value_names = [n for n in schema.names if n not in keys]
    _validate_monoid_fetches(fetches, value_names,
                             "before distribute()", schema=schema)
    if dist.num_rows == 0:
        raise ValueError("aggregate on an empty distributed frame")
    scalars = {f: c for f, c in fetches.items() if not _is_sketch(c)}
    sketches = {f: c for f, c in fetches.items() if _is_sketch(c)}

    ids_dev, uniques, num_groups, salt_plan = _monoid_group_plan(
        dist, keys)
    # the scalar half sees only its own columns (no spurious
    # ride-along warnings about the sketch fetches); the group order
    # is identical by construction — same key data, same deterministic
    # host factorization
    scalar_out = (_daggregate(
        scalars, dist.select(list(keys) + sorted(scalars)), keys, None)
        if scalars else None)

    ids_host = np.asarray(ids_dev)
    valid = ids_host >= 0
    ids = ids_host[valid].astype(np.int64)
    mask = dist.valid_row_mask()
    sketch_cols: Dict[str, np.ndarray] = {}
    with span("daggregate.sketch_fold"):
        for f in sorted(sketches):
            sk = sketches[f]
            a = _memory.host_value(dist.columns, f)
            vals = a[mask] if dist.shard_valid is not None \
                else a[: dist.num_rows]
            table = sk.block_partial(np.asarray(vals), ids, num_groups)
            counters.inc("relational.sketch_folds")
            sketch_cols.update(sk.finalize(f, table))

    # assemble: keys + sorted fetch columns (sketch multi-outputs
    # inline after their fetch name)
    out_fields = [schema[k] for k in keys]
    cols: Dict[str, np.ndarray] = {}
    if scalar_out is not None:
        sb = Block.concat(scalar_out.blocks(), scalar_out.schema)
        for k in keys:
            cols[k] = sb.columns[k]
    else:
        for k, u in zip(keys, uniques):
            cols[k] = np.asarray(u)
    for f in sorted(fetches):
        if f in sketches:
            for fld in sketches[f].out_fields(f, schema[f]):
                out_fields.append(fld)
                cols[fld.name] = sketch_cols[fld.name]
        else:
            out_fields.append(scalar_out.schema[f])
            cols[f] = sb.columns[f]
    out = TensorFrame.from_blocks(
        [Block(cols, num_groups)], _Schema(out_fields))
    if salt_plan is not None:
        attach_hot_keys(out, keys, uniques, salt_plan)
    return out


def _segmented_fold(comp, names, mesh: DeviceMesh, arrays, ids_dev,
                    G: int) -> Dict[str, jax.Array]:
    """Per-group fold of an arbitrary reduce computation on the mesh.

    Requires a vmappable computation: deserialized (``exported.call``)
    computations have no batching rule and are rejected with a clear
    error at trace time by jax.

    ``ids_dev``: row-sharded dense group ids ([padded_rows] int32, ``-1``
    for pad rows). Per shard: stable sort by id, segmented
    ``associative_scan`` whose operator applies ``comp`` to a stacked
    two-row block when both elements share an id, segment tails scattered
    into a ``[G, ...]`` table + presence mask; the stacked per-shard
    tables are folded pairwise with the same combiner, and ``comp`` is
    applied once more over each group's single-row block (at-least-once
    parity with the host ``CompactionBuffer.evaluate``). Returns
    ``{fetch: [G, ...cell]}`` device arrays. The jitted program is cached
    on ``comp`` keyed by (mesh, G, shapes).
    """
    axis = mesh.data_axis

    def pair(av, bv):
        """User computation over the stacked two-row block {a; b}."""
        out = comp.fn({f + "_input": jnp.stack([av[f], bv[f]])
                       for f in names})
        return {f: out[f] for f in names}

    def single(av):
        out = comp.fn({f + "_input": av[f][None] for f in names})
        return {f: out[f] for f in names}

    pair_v = jax.vmap(pair)
    single_v = jax.vmap(single)

    in_specs = (P(axis),) + tuple(
        P(axis, *([None] * (a.ndim - 1))) for a in arrays)
    # each shard emits its [1, G, ...] table slice; stacking over the data
    # axis yields the global [shards, G, ...] partials
    out_specs = (tuple(P(axis) for _ in names), P(axis))

    def shard_fn(ids_local, *vals_local):
        R = ids_local.shape[0]
        # pad rows (-1) sort to the end as group G and are dropped by the
        # mode="drop" scatter below
        sort_ids = jnp.where(ids_local < 0, G, ids_local)
        order = jnp.argsort(sort_ids, stable=True)
        sid = sort_ids[order]
        svals = {f: v[order] for f, v in zip(names, vals_local)}

        def op(a, b):
            a_id, a_v = a
            b_id, b_v = b
            same = a_id == b_id
            comb = pair_v(a_v, b_v)
            out_v = {}
            for f in names:
                m = same.reshape((-1,) + (1,) * (comb[f].ndim - 1))
                out_v[f] = jnp.where(m, comb[f], b_v[f])
            return (b_id, out_v)

        _, scanned = jax.lax.associative_scan(op, (sid, svals), axis=0)
        tail = jnp.concatenate(
            [sid[1:] != sid[:-1], jnp.ones((1,), bool)])
        target = jnp.where(tail & (sid < G), sid, G)  # G → dropped
        table = {}
        for f in names:
            z = jnp.zeros((G,) + scanned[f].shape[1:], scanned[f].dtype)
            table[f] = z.at[target].set(scanned[f], mode="drop")
        present = jnp.zeros((G,), bool).at[target].set(
            jnp.ones((R,), bool), mode="drop")
        return tuple(table[f][None] for f in names), present[None]

    def program(ids, *cols):
        stacked, present = shard_map(
            shard_fn, mesh=mesh.mesh, in_specs=in_specs,
            out_specs=out_specs)(ids, *cols)
        tabs = dict(zip(names, stacked))  # each [S, G, ...cell]
        S = present.shape[0]
        acc = {f: tabs[f][0] for f in names}
        acc_p = present[0]
        for s in range(1, S):
            comb = pair_v({f: acc[f] for f in names},
                          {f: tabs[f][s] for f in names})
            both = acc_p & present[s]
            for f in names:
                m_both = both.reshape((-1,) + (1,) * (acc[f].ndim - 1))
                m_new = present[s].reshape(
                    (-1,) + (1,) * (acc[f].ndim - 1))
                acc[f] = jnp.where(m_both, comb[f],
                                   jnp.where(m_new, tabs[f][s], acc[f]))
            acc_p = acc_p | present[s]
        # at-least-once application of the computation (host parity for
        # single-row groups, where the scan never ran the combiner)
        return single_v(acc)

    # TFT_EXECUTOR=pjrt: the whole generic-aggregation program — per-shard
    # sort + segmented scan + scatter AND the cross-shard masked fold —
    # compiles as one GSPMD executable in the native C++ core (cached on
    # the Computation; un-routable programs latch to the jax path)
    nm = _native_mesh(mesh)
    if nm is not None:
        def build_prog():
            def prog(ids, *cols):
                out = program(ids, *cols)
                return tuple(out[f] for f in names)
            return prog

        in_shardings = [mesh.row_sharding(1)] + [
            mesh.row_sharding(a.ndim) for a in arrays]
        out_shardings = [mesh.replicated() for _ in names]
        nkey = ("dagg_generic", mesh.mesh, axis, G,
                tuple((f, a.shape, str(a.dtype))
                      for f, a in zip(names, arrays)))
        try:
            outs = nm.run_sharded(nkey, build_prog,
                                  [ids_dev] + list(arrays),
                                  in_shardings, out_shardings, mesh,
                                  owner=comp)
        except Exception as e:
            _native_mesh_fallback(e)
            outs = None
        if outs is not None:
            return dict(zip(names, outs))

    cache = getattr(comp, "_tft_segfold_cache", None)
    if cache is None:
        cache = comp._tft_segfold_cache = OrderedDict()
    key = (mesh.mesh, axis, G,
           tuple((f, a.shape, str(a.dtype)) for f, a in zip(names, arrays)))
    fn = cache.get(key)
    if fn is not None:
        cache.move_to_end(key)
    else:
        fn = cache[key] = jax.jit(program)
        # G is data-dependent (distinct group counts), so bound the cache
        # like _collective_cache does
        while len(cache) > 16:
            cache.popitem(last=False)
    trace = current_trace()
    t0 = (_trace_shards(trace, "daggregate", mesh=mesh, arrays=arrays)
          if trace is not None else 0.0)
    with span("daggregate.segmented_fold_dispatch"):
        outs = fn(ids_dev, *arrays)
    counters.inc("mesh.dispatches")
    if trace is not None:
        _trace_mesh_done(trace, [outs[f] for f in names], t0,
                         "daggregate", mesh=mesh)
    return outs


def _generic_daggregate(fetches, dist: DistributedFrame, keys,
                        max_groups: Optional[int] = None) -> TensorFrame:
    """Arbitrary-computation keyed aggregation on the mesh.

    The distributed form of the reference's UDAF-inside-the-shuffle
    (``DebugRowOps.scala:587-681``), built from compiler-friendly pieces
    instead of a row shuffle:

    1. per shard (SPMD, inside one ``shard_map``): stable-sort local rows
       by group id (pad rows to the end), then one segmented
       ``jax.lax.associative_scan`` whose operator applies the user
       computation to a stacked two-row block when both elements share a
       group id — the fold of each contiguous segment lands on its last
       row (O(log rows) combiner applications, all vmapped);
    2. scatter each segment tail into a dense ``[groups, ...cell]`` partial
       table (+ a presence mask for groups absent on the shard);
    3. stack the tables over the data axis and fold them pairwise with the
       same two-row combiner, masked by presence;
    4. apply the computation once more over each group's single-row block —
       the host path's ``CompactionBuffer.evaluate`` always applies the
       computation at least once, so single-row groups must see it too.

    Legal for exactly the computations the host compaction path accepts:
    the combine must tolerate arbitrary regrouping of rows and partials
    (the UDAF contract, ``core.py:96-97``).
    """
    from ..schema import Field
    from ..shape import Unknown

    schema = dist.schema
    mesh = dist.mesh
    if dist.num_rows == 0:
        raise ValueError("aggregate on an empty distributed frame")
    value_schema = schema.select([m for m in schema.names if m not in keys])
    comp = _cached_reduce_computation(fetches, value_schema, ("_input",),
                                      block_level=True)
    _ops._validate_reduce(comp, value_schema, ("_input",), rank_delta=1)
    names = sorted(comp.output_names)

    # device-side keys (max_groups=): ids + group table built on the
    # mesh, the key column(s) never visit the host (composite keys
    # combine in the mixed-radix id space, _device_key_ids)
    ids_dev, uniques, uniq_dev, count_dev, table_groups = _cached_group_ids(
        dist, keys, max_groups)
    final = _segmented_fold(comp, names, mesh,
                            [dist.columns[f] for f in names],
                            ids_dev, table_groups)

    if max_groups is not None:
        cols, num_groups = _device_key_columns(dist, keys, uniq_dev,
                                               count_dev, max_groups)
    else:
        num_groups = table_groups
        cols = {k: u for k, u in zip(keys, uniques)}
    for f in names:
        v = np.asarray(final[f])[:num_groups]
        fld = schema[f]
        if v.dtype != fld.dtype.np_storage and fld.dtype is not _dt.bfloat16:
            v = v.astype(fld.dtype.np_storage)
        cols[f] = v
    out_fields = [schema[k] for k in keys] + [
        Field(s.name, s.dtype, block_shape=s.shape.prepend(Unknown),
              sql_rank=s.shape.ndim)
        for s in comp.outputs]
    return TensorFrame.from_blocks([Block(cols, num_groups)],
                                   Schema(out_fields))


def _generic_reduce(fetches, dist: DistributedFrame) -> Dict[str, np.ndarray]:
    """Generic (arbitrary-computation) mesh reduce, entirely on device.

    One compiled program: a ``shard_map`` stage runs the user block-reduce
    on every shard's local rows in parallel (SPMD — pad-only shards compute
    a garbage partial that is statically sliced away), the ragged tail
    shard's valid prefix is re-reduced on its own, and the partials are
    combined with one final stacked block-reduce. On the default jax
    dispatch the only host transfer is the final one-cell result — the
    reference's driver-collect analogue (``DebugRowOps.scala:511-512``),
    with the per-shard data never leaving its device. (Under
    ``TFT_EXECUTOR=pjrt`` the native route marshals the columns through
    host numpy per call — the documented correctness-proof trade,
    ``native_mesh`` module docstring.)
    """
    schema = dist.schema
    comp = _cached_reduce_computation(fetches, schema, ("_input",),
                                      block_level=True)
    _ops._validate_reduce(comp, schema, ("_input",), rank_delta=1)
    fetch_names = comp.output_names
    mesh = dist.mesh
    axis = mesh.data_axis
    shards = mesh.num_data_shards
    n = dist.num_rows
    if n == 0:
        raise ValueError("reduce on an empty distributed frame")
    rows_per = dist.padded_rows // shards
    full = n // rows_per          # shards whose rows are all valid
    tail = n - full * rows_per    # valid rows in the boundary shard

    names = sorted(fetch_names)
    arrays = [dist.columns[f] for f in names]

    if dist.shard_valid is not None:
        # multi-host frames pad per process, not in a global suffix — the
        # prefix slicing below cannot express that. Fold every valid row
        # into one group through the segmented-scan machinery instead.
        ids_host = np.where(dist.valid_row_mask(), 0, -1).astype(np.int32)
        ids_dev = jax.make_array_from_callback(
            (dist.padded_rows,), mesh.row_sharding(1),
            lambda idx: ids_host[idx])
        final_t = _segmented_fold(comp, names, mesh, arrays, ids_dev, 1)
        out = {}
        for f in fetch_names:
            v = np.asarray(final_t[f][0])
            fld = schema.get(f)
            if fld is not None and v.dtype != fld.dtype.np_storage \
                    and fld.dtype is not _dt.bfloat16:
                v = v.astype(fld.dtype.np_storage)
            out[f] = v
        return out
    cache = getattr(comp, "_tft_dreduce_cache", None)
    if cache is None:
        cache = comp._tft_dreduce_cache = {}
    key = (mesh.mesh, axis, n,
           tuple((f, a.shape, str(a.dtype)) for f, a in zip(names, arrays)))
    in_specs = tuple(P(axis, *([None] * (a.ndim - 1))) for a in arrays)
    # each shard emits its partial with a unit lead axis; stacking over
    # the data axis yields a (shards, *cell) global array
    out_specs = tuple(P(axis) for _ in names)

    def make_program():
        def shard_fn(*local):
            out = comp.fn(
                {f + "_input": s for f, s in zip(names, local)})
            return tuple(out[f][None] for f in names)

        def program(*cols):
            stacked = shard_map(shard_fn, mesh=mesh.mesh,
                                in_specs=in_specs,
                                out_specs=out_specs)(*cols)
            parts = {f: st[:full] for f, st in zip(names, stacked)}
            if tail:
                t = comp.fn({
                    f + "_input":
                        jax.lax.slice_in_dim(c, full * rows_per,
                                             full * rows_per + tail, axis=0)
                    for f, c in zip(names, cols)})
                parts = ({f: t[f][None] for f in names} if full == 0 else
                         {f: jnp.concatenate([parts[f], t[f][None]])
                          for f in names})
            return comp.fn({f + "_input": parts[f] for f in names})

        return program

    # TFT_EXECUTOR=pjrt: the whole generic reduce — per-shard partials,
    # the ragged-tail re-reduce, and the final stacked combine — compiles
    # as one GSPMD executable in the native C++ core
    final = None
    nm = _native_mesh(mesh)
    if nm is not None:
        def build_prog():
            program = make_program()

            def prog(*cols):
                out = program(*cols)
                return tuple(out[f] for f in names)
            return prog

        in_shardings = [mesh.row_sharding(a.ndim) for a in arrays]
        out_shardings = [mesh.replicated() for _ in names]
        try:
            outs = nm.run_sharded(("dreduce_generic",) + key, build_prog,
                                  arrays, in_shardings, out_shardings,
                                  mesh, owner=comp)
        except Exception as e:
            _native_mesh_fallback(e)
            outs = None
        if outs is not None:
            final = dict(zip(names, outs))
    if final is None:
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = jax.jit(make_program())
        trace = current_trace()
        t0 = (_trace_shards(trace, "dreduce_blocks", dist=dist)
              if trace is not None else 0.0)
        with span("dreduce_blocks.generic_dispatch"):
            final = fn(*arrays)
        counters.inc("mesh.dispatches")
        if trace is not None:
            _trace_mesh_done(trace, [final[f] for f in names], t0,
                             "dreduce_blocks", mesh=mesh)
    out = {}
    for f in fetch_names:
        v = np.asarray(final[f])
        fld = schema.get(f)
        if fld is not None and v.dtype != fld.dtype.np_storage \
                and fld.dtype is not _dt.bfloat16:
            v = v.astype(fld.dtype.np_storage)
        out[f] = v
    return out
