"""Incremental keyed aggregation: windows, watermarks, bounded state.

The streaming counterpart of ``aggregate``'s monoid path
(``engine.ops._monoid_aggregate``) and the mesh ``daggregate``: fetches
are a ``{column: combiner-name}`` mapping over the associative monoids
(sum / min / max / prod — ``parallel.collectives.COMBINERS``), so a
batch folds into running state EXACTLY (combine order is free).

Per batch, per live window present in the batch:

1. the batch rows' keys factorize to dense ids on the host
   (``engine.ops._factorize_keys`` — the same key→id shuffle
   replacement the finite aggregate uses);
2. each fetch column reduces in ONE device dispatch through
   ``engine.ops._segment_reduce`` — the same kernels the finite
   ``aggregate`` and the mesh ``daggregate`` program dispatch (the
   one-hot-matmul Pallas ``segment_sum`` for float and 32-bit-or-narrower
   integer sums on TPU, XLA segment primitives otherwise);
3. the per-batch partial merges into the window's **device-resident
   state table** with one cached compiled merge program (scatter-set of
   the old table + scatter-combine of the partial into the key-union
   table). Merge programs are jit-cached by signature — steady-state
   batches (same key universe, same batch profile) are pure cache hits,
   no retracing (``stream.merge_compiles`` counts builds).

**Windows & watermarks**: rows are assigned to tumbling or sliding
windows by an event-time column; the watermark trails the maximum
event time seen by ``watermark_delay``. A window whose end falls at or
below the watermark EMITS (one output frame: window_start + keys +
aggregates, keys lexicographically sorted) and its state is evicted —
state is bounded by the number of windows the watermark keeps open
times the live key cardinality. Rows for an already-closed window are
**late**: counted (``stream.late_rows``) and dropped, never resurrect
state. ``max_state_rows`` adds a hard cap on DEVICE-resident state
rows: under an active memory manager (``docs/memory.md``) the oldest
window SPILLS to pinned host buffers (``stream.state_spills``) —
staying logically live, faulting back on its next touch — and only
without a budget does it force-emit early
(``stream.state_evictions``), the pre-spill behavior.

Without a window the aggregation runs in **update mode**: one global
state table, and each batch emits the updated rows for the keys it
touched (the dashboard delta feed).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Dict, List, Mapping, NamedTuple, Optional, Tuple

import numpy as np

from .. import dtypes as _dt
from ..engine.ops import (InvalidTypeError, _factorize_keys, _field_spec,
                          _segment_reduce, _validate_monoid_fetches)
from ..frame import Block, TensorFrame
from ..observability import events as _obs
from ..schema import Field, Schema
from ..shape import Shape, Unknown
from ..utils.logging import get_logger
from ..utils.tracing import counters, span

__all__ = ["Window", "tumbling", "sliding", "StreamingAggregation",
           "WINDOW_COL"]

_log = get_logger("stream.aggregate")

# the window-start column prepended to every windowed emission
WINDOW_COL = "window_start"


class Window(NamedTuple):
    """An event-time window spec: ``size`` seconds (or whatever unit the
    time column carries) advancing every ``slide``. ``slide == size`` is
    tumbling; ``slide < size`` is sliding (each row lands in
    ``ceil(size/slide)`` windows). Window starts align to multiples of
    ``slide``; a row at time t belongs to windows with
    ``start <= t < start + size``."""

    size: float
    slide: float


def tumbling(size: float) -> Window:
    """Non-overlapping windows of ``size`` event-time units."""
    if size <= 0:
        raise ValueError(f"window size must be > 0, got {size}")
    return Window(float(size), float(size))


def sliding(size: float, slide: float) -> Window:
    """Overlapping windows: ``size`` long, a new one every ``slide``."""
    if size <= 0 or slide <= 0:
        raise ValueError(
            f"window size/slide must be > 0, got {size}/{slide}")
    if slide > size:
        raise ValueError(
            f"slide {slide} > size {size} would drop rows between "
            f"windows; use tumbling({slide}) or shrink the slide")
    return Window(float(size), float(slide))


class _WState:
    """One window's live state: host key table + device value tables.

    ``spilled`` state holds its value tables as pinned host numpy
    instead (``spill()``): logically identical — the merge programs
    accept host arrays and re-place them on the device at the next fold
    (the transparent fault-back) — but costing zero device bytes, which
    is what lets ``max_state_rows`` bound DEVICE state without
    force-emitting incomplete windows (``docs/memory.md``).

    A state is also a duck-typed entry in the global memory manager's
    LRU (the ``mem_*`` protocol, ``memory/manager.py``): under an active
    budget the LEDGER drives spills too — an admission squeeze anywhere
    in the process can push the coldest window to host, not just the
    stream's own ``max_state_rows`` cap. Registered at commit (weakly
    held: an emitted window's entry dies with the object).
    """

    __slots__ = ("keys_u", "values", "rows", "spilled", "on_spill",
                 "on_fault", "_spill_lock", "__weakref__")

    def __init__(self, keys_u: List[np.ndarray], values: Dict[str, object],
                 rows: int):
        self.keys_u = keys_u        # per key column: sorted unique values
        self.values = values        # fetch -> device array [rows, ...]
        self.rows = rows
        self.spilled = False
        # per-stream spill/fault accounting hooks (set when
        # ledger-registered)
        self.on_spill = None
        self.on_fault = None
        # the ledger LRU (its own lock) and the max_state_rows eviction
        # path (the aggregation's state lock) can both pick this state;
        # a per-state lock + re-check keeps one spill from counting (or
        # copying) twice
        self._spill_lock = threading.Lock()

    def spill(self) -> int:
        """Move the device value tables to pinned host buffers; returns
        the device bytes freed (0 when a concurrent spill won the
        race). Bit-identical round trip (the host view keeps the device
        dtype, bfloat16 included)."""
        from .. import memory as _memory
        with self._spill_lock:
            if self.spilled:
                return 0
            freed = 0
            for f, v in list(self.values.items()):
                if _memory.is_device_value(v):
                    freed += _memory.array_nbytes(v)
                    self.values[f] = _memory.to_pinned_host(v)
            self.spilled = True
        return freed

    # -- memory-ledger entry protocol (docs/memory.md) ---------------------
    def mem_name(self) -> str:
        return "stream-window"

    def mem_is_spilled(self) -> bool:
        return self.spilled

    def mem_device_bytes(self) -> int:
        if self.spilled:
            return 0
        from .. import memory as _memory
        return sum(_memory.array_nbytes(v) for v in self.values.values()
                   if _memory.is_device_value(v))

    def mem_host_bytes(self) -> int:
        if not self.spilled:
            return 0
        return sum(int(v.nbytes) for v in self.values.values()
                   if isinstance(v, np.ndarray))

    def mem_spill(self) -> int:
        """Ledger-driven spill (called under the ledger lock). The
        counters the manual ``max_state_rows`` path increments by hand
        come from the ``on_spill`` hook + the ledger's own accounting.
        ``spill()`` is race-guarded: a loser returns 0 and counts
        nothing."""
        freed = self.spill()
        if freed:
            cb = self.on_spill
            if cb is not None:
                cb(freed)
        return freed

    def mem_fault(self) -> int:
        """Restore the value tables to the device. The fold path faults
        lazily on its own (the merge programs accept host arrays), so
        this only runs when the ledger explicitly touches the entry.
        Same race guard as :meth:`spill` — a fault interleaving with a
        concurrent spill must not leave device arrays behind a
        ``spilled=True`` flag (the ledger would under-count them)."""
        import jax
        with self._spill_lock:
            if not self.spilled:
                return 0
            restored = 0
            for f, v in list(self.values.items()):
                if isinstance(v, np.ndarray):
                    self.values[f] = jax.device_put(v)
                    restored += int(v.nbytes)
            self.spilled = False
        if restored:
            cb = self.on_fault  # symmetric with the spill-side hook
            if cb is not None:
                cb(restored)
        return restored

    @property
    def nbytes(self) -> int:
        n = sum(int(np.asarray(k).nbytes) for k in self.keys_u)
        for v in self.values.values():
            nb = getattr(v, "nbytes", None)
            n += int(nb) if nb is not None else 0
        return n


# cached compiled merge programs: (combiner, M, G, H, tail, dtype) ->
# jitted fn. LRU-capped; every touch under the lock (jit itself is not).
_merge_cache: "OrderedDict[Tuple, object]" = OrderedDict()
_merge_lock = threading.Lock()
_MERGE_CACHE_CAP = 128


def _merge_program(cname: str, m: int, g: int, h: int,
                   tail: Tuple[int, ...], dtype):
    """The cached scatter-merge: old state [g,...] + batch partial
    [h,...] -> union table [m,...]. Every union position receives the
    old value (set) and/or the partial (combine against the monoid's
    neutral — the same per-combiner identity COMBINERS serves the mesh
    padding path), so overlap, old-only, and new-only keys are all
    exact."""
    import jax
    import jax.numpy as jnp

    from ..parallel.collectives import COMBINERS

    key = (cname, m, g, h, tail, str(dtype))
    with _merge_lock:
        fn = _merge_cache.get(key)
        if fn is not None:
            _merge_cache.move_to_end(key)
            return fn

    neutral = COMBINERS[cname].neutral(dtype)

    def prog(old, old_idx, new, new_idx):
        out = jnp.full((m,) + tail, neutral, dtype=old.dtype)
        out = out.at[old_idx].set(old)
        if cname == "sum":
            return out.at[new_idx].add(new)
        if cname == "prod":
            return out.at[new_idx].multiply(new)
        if cname == "min":
            return out.at[new_idx].min(new)
        return out.at[new_idx].max(new)

    fn = jax.jit(prog)
    with _merge_lock:
        fn = _merge_cache.setdefault(key, fn)
        _merge_cache.move_to_end(key)
        if len(_merge_cache) > _MERGE_CACHE_CAP:
            _merge_cache.popitem(last=False)
    counters.inc("stream.merge_compiles")
    return fn


class StreamingAggregation:
    """The terminal operator a :class:`~.frame.GroupedStream` builds —
    see the module docstring for semantics, ``docs/streaming.md`` for
    the user guide. Drive it with :meth:`start` (a
    :class:`~.runtime.StreamHandle` whose per-batch outputs are the
    emitted window frames)."""

    def __init__(self, upstream, keys: List[str],
                 col_combiners: Mapping[str, str],
                 window: Optional[Window] = None,
                 time_col: Optional[str] = None,
                 watermark_delay: float = 0.0,
                 max_state_rows: Optional[int] = None,
                 mesh=None):
        from ..engine.ops import _is_sketch
        if not (isinstance(col_combiners, Mapping) and col_combiners
                and all(isinstance(v, str) or _is_sketch(v)
                        for v in col_combiners.values())):
            raise TypeError(
                "streaming aggregate fetches must be a non-empty "
                "{column: combiner} mapping (the monoid form — "
                "sum/min/max/prod names or relational sketch "
                "combiners; arbitrary reduce computations cannot fold "
                "incrementally)")
        schema = upstream.schema
        self.upstream = upstream
        self.keys = list(keys)
        self.window = window
        self.time_col = time_col
        self.watermark_delay = float(watermark_delay)
        self.max_state_rows = max_state_rows
        # mesh=: per-batch window folds ride the fused mesh path — each
        # batch's keyed partial tables compute as ONE GSPMD program
        # (per-shard segment reduce + psum-family collective, the
        # daggregate fragment) over the mesh's data axis, so one
        # windowed stream scales past one device. The [groups, ...]
        # partial then merges into the same device-resident window
        # state. Float sums may reassociate across shards, like any
        # daggregate; integer folds stay exact. A 1-shard mesh (or
        # None) keeps the single-device segment-reduce dispatch, and so
        # do multi-process meshes — the batch arrays are process-local,
        # so sharding them as if they were the global rows would be
        # wrong (the same guard the lazy d-op recorder applies).
        import jax as _jax
        self.mesh = mesh if (mesh is not None
                             and mesh.num_data_shards > 1
                             and _jax.process_count() == 1) else None
        if watermark_delay < 0:
            raise ValueError(
                f"watermark_delay must be >= 0, got {watermark_delay}")
        if window is not None:
            if time_col is None:
                raise ValueError(
                    "windowed aggregation needs time_col= (the event-"
                    "time column windows and the watermark read)")
            f = schema.get(time_col)
            if f is None:
                raise KeyError(f"No time column {time_col!r}; columns: "
                               f"{schema.names}")
            if f.sql_rank != 0 or not f.dtype.tensor or \
                    np.dtype(f.dtype.np_storage).kind not in "iuf":
                raise InvalidTypeError(
                    f"time_col {time_col!r} must be a numeric scalar "
                    f"column, got {f.type_string()}")
            if WINDOW_COL in schema:
                raise ValueError(
                    f"column {WINDOW_COL!r} already exists; windowed "
                    f"emission needs that name for the window-start "
                    f"column")
        else:
            if time_col is not None:
                raise ValueError("time_col= only applies with window=")
            if max_state_rows is not None:
                raise ValueError(
                    "max_state_rows bounds WINDOW state; update-mode "
                    "(window=None) state is the live key cardinality — "
                    "cap the key universe upstream instead")
        if max_state_rows is not None and max_state_rows < 1:
            raise ValueError(
                f"max_state_rows must be >= 1, got {max_state_rows}")
        value_names = [n for n in schema.names
                       if n not in self.keys and n != time_col]
        _validate_monoid_fetches(col_combiners, value_names,
                                 "upstream with select()", schema=schema)
        self.col_combiners = dict(col_combiners)
        self.fetch_names = sorted(col_combiners)
        # sketch combiners (docs/joins.md): their per-window state
        # folds through the SAME scatter-merge machinery when the
        # sketch merges elementwise (HLL registers: max; quantile
        # bucket counts: sum); host-merged sketches (top-k) keep host
        # state tables — zero device bytes by construction
        self.sketches = {f: c for f, c in self.col_combiners.items()
                         if _is_sketch(c)}
        fields: List[Field] = []
        if window is not None:
            # window starts are always float64 (event-time arithmetic
            # happens in f64 regardless of the time column's storage)
            fields.append(Field(WINDOW_COL, _dt.double,
                                block_shape=Shape(Unknown), sql_rank=0))
        fields += [schema[k] for k in self.keys]
        for f in self.fetch_names:
            sk = self.sketches.get(f)
            if sk is not None:
                fields.extend(sk.out_fields(f, schema[f]))
            else:
                fields.append(Field(
                    f, schema[f].dtype,
                    block_shape=_field_spec(schema[f], True,
                                            "stream aggregate")
                    .with_lead(Unknown),
                    sql_rank=schema[f].sql_rank))
        self.out_schema = Schema(fields)
        # -- live state ----------------------------------------------------
        # _windows is read by metrics scrapes on other threads while the
        # pump folds batches: every structural mutation (commit, emit
        # pop) and every introspection snapshot happens under this lock
        self._state_lock = threading.Lock()
        self._windows: Dict[Optional[float], _WState] = {}
        self._max_ts = -np.inf
        # windows with start <= this are closed: emitted (watermark) or
        # force-evicted; rows mapping into them are late
        self._closed_through = -np.inf
        # emitted-but-not-yet-returned window frames: _emit appends
        # here the moment a window is popped, and ingest/finalize drain
        # it as their return value — so an exception AFTER some windows
        # of a batch emitted (a later window's D2H failing) can never
        # lose the already-popped ones; they ride out on the next
        # successful batch. Pump-thread only.
        self._emitted_backlog: List[TensorFrame] = []
        # per-instance twins of the global counters (the stream handle's
        # metrics are per-stream, the flat counters process-wide)
        self.late_rows = 0
        self.windows_emitted = 0
        self.state_evictions = 0
        self.state_spills = 0
        self.state_faults = 0

    # -- introspection (the runtime's metrics read these) -----------------
    @property
    def schema(self) -> Schema:
        return self.out_schema

    @property
    def state_rows(self) -> int:
        with self._state_lock:
            return sum(w.rows for w in self._windows.values())

    @property
    def state_bytes(self) -> int:
        with self._state_lock:
            return sum(w.nbytes for w in self._windows.values())

    @property
    def live_windows(self) -> int:
        with self._state_lock:
            return len(self._windows)

    @property
    def watermark(self) -> Optional[float]:
        if self.window is None or self._max_ts == -np.inf:
            return None
        return self._max_ts - self.watermark_delay

    # -- ingestion ---------------------------------------------------------
    def ingest(self, df: TensorFrame) -> List[TensorFrame]:
        """Fold one batch into state; returns the frames this batch
        caused to emit (closed windows, or the update-mode delta).

        ALL-OR-NOTHING: the batch folds into fresh staging state
        (:meth:`_fold` never mutates a live ``_WState``) and commits in
        one locked update at the end — an exception anywhere mid-fold
        (a failed dispatch, a bad column) leaves the live state exactly
        as it was, so the runtime's skip-and-count path drops the WHOLE
        batch and a retried batch can never double-count
        (``runtime.StreamHandle`` relies on this: the retry policy
        wraps only the forcing, and ingest runs exactly once after it).
        """
        blocks = df.blocks()
        merged = blocks[0] if len(blocks) == 1 \
            else Block.concat(blocks, df.schema)
        if merged.num_rows == 0:
            return []
        for k in self.keys:
            if merged.is_ragged(k) or merged.dense(k).ndim != 1:
                raise InvalidTypeError(
                    f"Key column {k!r} must be scalar-typed")
        key_arrays = [merged.dense(k) for k in self.keys]
        val_arrays = {f: merged.dense(f) for f in self.fetch_names}
        if self.window is None:
            state, touched = self._fold(self._windows.get(None),
                                        key_arrays, val_arrays)
            with self._state_lock:
                self._windows[None] = state
            self._register_state(state)
            return [self._update_frame(touched)]
        ts = np.asarray(merged.dense(self.time_col), np.float64)
        if ts.ndim != 1:
            raise InvalidTypeError(
                f"time_col {self.time_col!r} must be scalar per row")
        new_max = max(self._max_ts, float(ts.max()))
        size, slide = self.window.size, self.window.slide
        n_off = int(np.ceil(size / slide))
        q = np.floor(ts / slide)
        late = 0
        pending: Dict[Optional[float], _WState] = {}
        with span("stream.aggregate.ingest"):
            for i in range(n_off):
                starts = (q - i) * slide
                valid = ts < starts + size
                if not valid.any():
                    continue
                for s in np.unique(starts[valid]):
                    m = valid & (starts == s)
                    if s <= self._closed_through:
                        late += int(m.sum())
                        continue
                    s = float(s)
                    # a sliding batch can hit the same window from two
                    # offsets (disjoint row subsets): chain through the
                    # staged state
                    base = pending.get(s, self._windows.get(s))
                    pending[s], _ = self._fold(
                        base, [a[m] for a in key_arrays],
                        {f: v[m] for f, v in val_arrays.items()})
        # commit point: live state changes only once the WHOLE batch
        # folded cleanly
        with self._state_lock:
            self._windows.update(pending)
        for st in pending.values():
            self._register_state(st)
        self._max_ts = new_max
        if late:
            self.late_rows += late
            counters.inc("stream.late_rows", late)
            _obs.add_event("late_rows", rows=late,
                           watermark=self.watermark)
        self._emit_ready()
        self._evict_over_cap()
        return self._drain_backlog()

    def finalize(self) -> List[TensorFrame]:
        """Flush every live window (finite source drained): emitted in
        window order; update mode emits one full-table snapshot."""
        if self.window is None:
            with self._state_lock:
                state = self._windows.get(None)
            if state is None:
                return []
            return [self._update_frame(np.arange(state.rows))]
        with self._state_lock:
            remaining = sorted(k for k in self._windows)
        for s in remaining:
            self._emit(s)
            self._closed_through = max(self._closed_through, s)
        return self._drain_backlog()

    # -- internals ---------------------------------------------------------
    def _register_state(self, state: _WState) -> None:
        """Join the global memory LRU (PR 8 follow-on): the ledger —
        not just ``max_state_rows`` — drives this window's spills once
        a device budget is active. Registered OUTSIDE ``_state_lock``
        (the ledger takes its own lock and may spill immediately)."""
        from .. import memory as _memory
        mgr = _memory.active()
        if mgr is not None and mgr.spill_enabled:
            state.on_spill = self._note_ledger_spill
            state.on_fault = self._note_ledger_fault
            mgr.register(state)

    def _note_ledger_spill(self, freed: int) -> None:
        self.state_spills += 1
        counters.inc("stream.state_spills")
        _log.debug("memory ledger spilled a stream window (%d B) to "
                   "host; it stays live and faults back on its next "
                   "touch", freed)

    def _note_ledger_fault(self, restored: int) -> None:
        self.state_faults += 1
        counters.inc("stream.state_faults")

    def _fold(self, base: Optional[_WState],
              key_arrays: List[np.ndarray],
              val_arrays: Dict[str, np.ndarray]
              ) -> Tuple[_WState, np.ndarray]:
        """Fold one window's batch rows against ``base`` (possibly
        None), returning a FRESH ``_WState`` plus the union-table
        positions the batch touched (update mode reads them). Pure with
        respect to ``base`` — the merge programs write new device
        arrays — which is what makes :meth:`ingest` transactional."""
        import jax.numpy as jnp

        from .. import native as _native

        schema = self.upstream.schema
        fact = _factorize_keys(key_arrays)
        scalar_names = [f for f in self.fetch_names
                        if f not in self.sketches]
        converted = {}
        for f in scalar_names:
            v = val_arrays[f]
            dd = _dt.device_dtype(schema[f].dtype)
            if v.dtype != dd:
                v = _native.convert(v, dd)
            converted[f] = v
        parts = {}
        if self.mesh is not None and scalar_names:
            # the distributed-plan path: one fused GSPMD program per
            # batch (rows shard over the data axis, partial tables
            # combine with one collective) — docs/plan.md
            from ..plan import dist as _dplan
            mesh_parts = _dplan.mesh_segment_partial(
                self.mesh,
                {f: self.col_combiners[f] for f in scalar_names},
                fact.ids.astype(np.int32), converted, fact.num_groups)
            parts = {f: jnp.asarray(mesh_parts[f])
                     for f in scalar_names}
        elif scalar_names:
            with span("stream.aggregate.segment_reduce"):
                for f in scalar_names:
                    parts[f] = jnp.asarray(_segment_reduce(
                        self.col_combiners[f], converted[f], fact.ids,
                        fact.num_groups))
        if self.sketches:
            # sketch partials bucket/hash on the host (the cross-path
            # determinism contract, docs/joins.md); elementwise states
            # join the device-resident tables, host-merged states
            # (top-k) stay host numpy
            with span("stream.aggregate.sketch_fold"):
                for f, sk in self.sketches.items():
                    part = sk.block_partial(
                        np.asarray(val_arrays[f]), fact.ids,
                        fact.num_groups)
                    counters.inc("relational.sketch_folds")
                    parts[f] = (jnp.asarray(part)
                                if sk.elementwise is not None else part)
        if base is None:
            return _WState([np.asarray(u) for u in fact.uniques], parts,
                           fact.num_groups), np.arange(fact.num_groups)
        if base.spilled:
            # transparent fault-back: the merge programs re-place the
            # host tables on the device as part of the fold (the result
            # state is device-resident again)
            from .. import memory as _memory
            self.state_faults += 1
            counters.inc("stream.state_faults")
            mgr = _memory.active()
            if mgr is not None:
                mgr.note_fault(
                    sum(_memory.array_nbytes(v)
                        for v in base.values.values()),
                    name="stream-window")
        g, h = base.rows, fact.num_groups
        cat = [np.concatenate([o, n])
               for o, n in zip(base.keys_u, fact.uniques)]
        gf = _factorize_keys(cat)
        m = gf.num_groups
        idx_dt = np.int32 if m < 2 ** 31 else np.int64
        idx_old = gf.ids[:g].astype(idx_dt)
        idx_new = gf.ids[g:].astype(idx_dt)
        values: Dict[str, object] = {}
        with span("stream.aggregate.merge"):
            for f in self.fetch_names:
                old = base.values[f]
                sk = self.sketches.get(f)
                if sk is not None and sk.elementwise is None:
                    # host-merged sketch state (top-k): the union-table
                    # fold runs in numpy — never device-resident
                    values[f] = sk.merge_tables(
                        np.asarray(old), idx_old,
                        np.asarray(parts[f]), idx_new, m)
                    continue
                cname = (sk.elementwise if sk is not None
                         else self.col_combiners[f])
                # .shape/.dtype read device metadata only — never
                # np.asarray the state here, which would drag the whole
                # device-resident table to host every batch
                fn = _merge_program(cname, m, g, h,
                                    tuple(old.shape[1:]), old.dtype)
                values[f] = fn(old, idx_old, parts[f], idx_new)
        return _WState([np.asarray(u) for u in gf.uniques], values,
                       m), idx_new

    def _drain_backlog(self) -> List[TensorFrame]:
        out, self._emitted_backlog = self._emitted_backlog, []
        return out

    def _emit_ready(self) -> None:
        wm = self.watermark
        if wm is None:
            return
        size = self.window.size
        with self._state_lock:
            ready = sorted(k for k in self._windows if k + size <= wm)
        for s in ready:
            self._emit(s)
        self._closed_through = max(self._closed_through, wm - size)

    def _evict_over_cap(self) -> None:
        """Bound live DEVICE state to ``max_state_rows``.

        Under an active memory manager the oldest window SPILLS to
        pinned host buffers instead of force-emitting — the window
        stays logically live (late rows keep folding in after a
        transparent fault-back at the next touch) and only stops
        costing device bytes (``stream.state_spills``). Without a
        budget, the pre-spill behavior stands: the oldest window
        force-emits early (``stream.state_evictions``)."""
        if self.max_state_rows is None:
            return
        from .. import memory as _memory
        mgr = _memory.active()
        spill_ok = mgr is not None and mgr.spill_enabled
        while True:
            with self._state_lock:
                live = [(k, w) for k, w in self._windows.items()
                        if not w.spilled]
                total = sum(w.rows for _, w in live)
                if total <= self.max_state_rows or not live:
                    return
                oldest = min(k for k, _ in live)
                state = self._windows[oldest]
                rows = state.rows
                if spill_ok:
                    freed = state.spill()
            if spill_ok:
                if freed:  # a concurrent ledger spill may have won
                    self.state_spills += 1
                    counters.inc("stream.state_spills")
                    mgr.note_spill(freed, name=f"stream-window@{oldest}")
                    _log.debug(
                        "stream state over max_state_rows=%d; spilled "
                        "window %s (%d rows, %d B) to host — it stays "
                        "live and faults back on the next touch",
                        self.max_state_rows, oldest, rows, freed)
                continue
            self.state_evictions += 1
            counters.inc("stream.state_evictions")
            _obs.add_event("state_eviction", window=oldest, rows=rows)
            _log.warning(
                "stream state over max_state_rows=%d; force-emitting "
                "window %s early (%d rows) — widen the cap or shrink "
                "the watermark delay if this is not intended",
                self.max_state_rows, oldest, rows)
            self._emit(oldest)
            self._closed_through = max(self._closed_through, oldest)

    def _values_to_host(self, state: _WState,
                        sel: Optional[np.ndarray] = None
                        ) -> Dict[str, np.ndarray]:
        schema = self.upstream.schema
        cols = {}
        for f in self.fetch_names:
            v = np.asarray(state.values[f])
            if sel is not None:
                v = v[sel]
            sk = self.sketches.get(f)
            if sk is not None:
                # sketch states finalize into their estimate columns
                # at emission (the state itself never leaves the fold)
                cols.update(sk.finalize(f, v))
                continue
            fld = schema[f]
            if v.dtype != fld.dtype.np_storage \
                    and fld.dtype is not _dt.bfloat16:
                v = v.astype(fld.dtype.np_storage)
            cols[f] = v
        return cols

    def _emit(self, s: float) -> None:
        # build the output frame BEFORE popping: a failed D2H
        # conversion must leave the window's accumulated state live
        # (the batch that triggered the emit skips; the window emits on
        # a later batch) — the same all-or-nothing contract as ingest.
        # The finished frame lands in the backlog the moment the pop
        # commits, so a failure on a LATER window cannot lose it.
        with self._state_lock:
            state = self._windows[s]
        cols: Dict[str, np.ndarray] = {
            WINDOW_COL: np.full(state.rows, s, np.float64)}
        for k, u in zip(self.keys, state.keys_u):
            cols[k] = u
        cols.update(self._values_to_host(state))
        frame = TensorFrame.from_blocks(
            [Block({f.name: cols[f.name] for f in self.out_schema},
                   state.rows)], self.out_schema)
        with self._state_lock:
            self._windows.pop(s, None)
        self._emitted_backlog.append(frame)
        self.windows_emitted += 1
        counters.inc("stream.windows_emitted")
        counters.inc("stream.rows_emitted", state.rows)
        _obs.add_event("window_emit", window=s, rows=state.rows)

    def _update_frame(self, touched: np.ndarray) -> TensorFrame:
        with self._state_lock:
            state = self._windows[None]
        sel = np.sort(np.asarray(touched))
        cols: Dict[str, np.ndarray] = {}
        for k, u in zip(self.keys, state.keys_u):
            cols[k] = u[sel]
        cols.update(self._values_to_host(state, sel))
        counters.inc("stream.rows_emitted", len(sel))
        return TensorFrame.from_blocks(
            [Block({f.name: cols[f.name] for f in self.out_schema},
                   len(sel))], self.out_schema)

    # -- execution ---------------------------------------------------------
    def start(self, sink=None, on_update=None, name: Optional[str] = None,
              max_buffered: Optional[int] = None, batch_rows=None):
        """A :class:`~.runtime.StreamHandle` pumping the upstream and
        folding each batch into this aggregation; emitted window frames
        flow to ``collect_updates()`` / ``sink`` / ``on_update``.
        ``batch_rows`` sizes batches (``docs/adaptive.md``): the fold
        is a keyed monoid, so coalesced batches combine to the same
        state as the per-block ones; with out-of-order event times the
        per-merged-batch watermark can only ADMIT rows the per-block
        cadence would have dropped late, never the reverse
        (``docs/streaming.md``)."""
        from .runtime import StreamHandle
        return StreamHandle(self.upstream, aggregation=self, sink=sink,
                            on_update=on_update, name=name,
                            max_buffered=max_buffered,
                            batch_rows=batch_rows)

    def __repr__(self):
        w = (f"window={self.window.size}/{self.window.slide}"
             if self.window else "update-mode")
        return (f"StreamingAggregation(keys={self.keys}, "
                f"fetches={self.col_combiners}, {w}, "
                f"state_rows={self.state_rows})")
