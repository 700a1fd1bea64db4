"""Distributed logical plan: lazy d-op chains fused into ONE GSPMD
program per mesh stage, with device-resident shard intermediates.

PR 10's plan IR stops at the single-device boundary: ``dmap_blocks`` /
``dfilter`` / ``dreduce_blocks`` / ``daggregate`` dispatch eagerly,
per-op — a chain of N row-local mesh ops costs N compiled dispatches
(and, for ``dfilter``, a host readback of the per-shard survivor counts
between every pair of ops). This module is the distributed twin of the
``keep_device`` edges: a chain recorded on a lazy
:class:`LazyDistributedFrame` forces as ONE ``jax.jit`` program whose
body is the per-op program fragments composed verbatim —

- row-preserving ``dmap_blocks`` computations run on the GLOBAL sharded
  arrays exactly as their per-op jit would (GSPMD inserts the same
  collectives for cross-row programs);
- each ``dfilter`` embeds the per-op ``shard_map`` compaction fragment
  (mask, per-shard stable compaction, survivor counts) — the counts stay
  TRACED between ops instead of round-tripping through the host;
- a terminal monoid ``dreduce_blocks`` / ``daggregate`` folds INTO the
  program as its last fragment (the DrJAX-style in-jaxpr reduction),
  instead of cutting a stage at the reduction;

so shard intermediates never leave their devices and the producer's
output sharding IS the consumer's input sharding (the SNIPPETS.md pjit
rule: matching ``out_axis_resources``/``in_axis_resources`` skip the
repartition entirely).

Legality is proof-driven like PR 10: a map records only when its
computation is PROVEN row-preserving (symbolic eval under the shared
row symbol, ``optimize._row_preserving``), a filter only when its mask
provably has block length. Anything else — trim/global maps, generic
(non-monoid) reductions, ``dsort``, multi-process meshes — materializes
the pending chain and takes the unchanged per-op path. ``TFT_FUSE=0``
makes ``lazy()`` the identity, so the kill switch is bit-identical by
construction; a fused execution failure the elastic layer cannot
recover (an OOM, a permanent fault) replays the chain per-op
(``dplan.fallbacks``) — fused execution never fails a query the per-op
d-ops survive.

The elastic machinery applies at the FUSED boundary: the whole forcing
runs through :func:`~..parallel.elastic.elastic_call`, so a classified
device loss mid-program shrinks the mesh, re-shards the SOURCE frame,
and re-runs the entire fused program on the survivors — bit-identical
for row-local ops and integer reductions, exactly the per-op contract.
The memory ledger admits the fused dispatch (``make_room`` on the plan's
output estimate) and the forced result's columns register as ONE LRU
spill candidate, so resident shard edges spill to pinned host under
pressure and fault back transparently.

See ``docs/plan.md`` (distributed fusion section).
"""

from __future__ import annotations

import threading
import weakref
from collections import OrderedDict
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..observability import flight as _flight
from ..observability.events import add_event, current_trace, traced_query
from jax import shard_map
from ..utils.logging import get_logger
from ..utils.tracing import counters, span
from .adaptive import record_stream_feedback, stream_feedback
from .nodes import _cell_bytes, observed_selectivity, record_selectivity
from .optimize import _mask_shaped, _row_preserving
from .optimize import enabled as fuse_enabled

__all__ = ["LazyDistributedFrame", "lazy_frame", "record_map",
           "record_filter", "record_reduce", "record_aggregate",
           "materialize", "mesh_segment_partial"]

_log = get_logger("plan.dist")


class _Unfusable(RuntimeError):
    """A runtime condition the recorder could not see; the caller
    replays the chain per-op (unplanned, not failed)."""


class _EmptyReduceError(ValueError):
    """The per-op "reduce on an empty distributed frame" contract,
    discovered POST-dispatch (a filter emptied the frame). A sentinel
    subclass so the fallback handler can re-raise exactly this while
    any other ``ValueError`` out of the fused program still replays
    per-op — fused execution must never fail a query the per-op d-ops
    survive."""


# ---------------------------------------------------------------------------
# plan nodes (the distributed chain IR)
# ---------------------------------------------------------------------------

class DistNode:
    """One recorded d-op (or the source leaf) of a lazy mesh chain."""

    kind = "dnode"

    def __init__(self, input: Optional["DistNode"], schema):
        self.input = input
        self.schema = schema

    def describe(self) -> str:
        return self.kind

    def estimate(self) -> Tuple[Optional[float], Optional[Dict[str, int]]]:
        """``(rows, {column: device bytes})`` — the distributed twin of
        :meth:`~.nodes.PlanNode.estimate`, consumed by the fused
        dispatch's ledger admission and ``memory.estimate``."""
        return None, None


class DSourceNode(DistNode):
    kind = "dsource"

    def __init__(self, frame):
        super().__init__(None, frame.schema)
        self.frame_ref = weakref.ref(frame)

    def describe(self) -> str:
        f = self.frame_ref()
        return (f"dsource[{f.num_rows} rows]" if f is not None
                else "dsource[collected]")

    def estimate(self):
        f = self.frame_ref()
        if f is None:
            return None, None
        from .. import memory as _memory
        cols: Dict[str, int] = {}
        for fl in f.schema:
            try:
                cols[fl.name] = int(_memory.value_nbytes(f.columns, fl.name))
            except Exception:
                cols[fl.name] = 0
        return float(f.num_rows), cols


class DMapNode(DistNode):
    """A proven row-preserving (non-trim) ``dmap_blocks``."""

    kind = "dmap"

    def __init__(self, input, schema, comp):
        super().__init__(input, schema)
        self.comp = comp

    def describe(self) -> str:
        return "dmap_blocks"

    def estimate(self):
        rows, cols = self.input.estimate()
        if rows is None or cols is None:
            return rows, cols
        out = dict(cols)
        for s in self.comp.outputs:
            out[s.name] = int(rows * _cell_bytes(s.dtype, s.shape.dims[1:]))
        return rows, out


class DFilterNode(DistNode):
    kind = "dfilter"

    def __init__(self, input, schema, comp):
        super().__init__(input, schema)
        self.comp = comp

    def describe(self) -> str:
        sel = observed_selectivity(self.comp)
        return ("dfilter" if sel is None
                else f"dfilter[sel~{sel:.2f} observed]")

    def estimate(self):
        # feedback selectivity (ROADMAP 2a): once any forcing of this
        # predicate observed rows-in/rows-out, estimate with the
        # observed ratio instead of the upper bound
        rows, cols = self.input.estimate()
        sel = observed_selectivity(self.comp)
        if sel is None or rows is None:
            return rows, cols
        return rows * sel, ({n: int(b * sel) for n, b in cols.items()}
                            if cols is not None else None)


class DSelectNode(DistNode):
    kind = "dselect"

    def __init__(self, input, schema, names: Sequence[str]):
        super().__init__(input, schema)
        self.names = tuple(names)

    def describe(self) -> str:
        return f"dselect{list(self.names)}"

    def estimate(self):
        rows, cols = self.input.estimate()
        if cols is None:
            return rows, cols
        return rows, {n: cols[n] for n in self.names if n in cols}


# ---------------------------------------------------------------------------
# the lazy frame
# ---------------------------------------------------------------------------

def _dist():
    from ..parallel import distributed
    return distributed


class LazyDistributedFrame:
    """A :class:`~..parallel.distributed.DistributedFrame` whose columns
    are a RECORDED d-op chain, not materialized arrays.

    Built by :meth:`DistributedFrame.lazy`; every further
    ``dmap_blocks`` / ``dfilter`` / ``select`` on it records a node and
    stays lazy. Any access to data (``columns`` / ``num_rows`` /
    ``collect_frame`` / an unfusable op) FORCES the chain: the optimizer
    fuses it into one GSPMD program (module docstring); ``TFT_FUSE=0``
    and unsupported shapes replay the recorded ops per-op,
    bit-identical. Thread-safe: concurrent forcings converge on one
    result.
    """

    _tft_lazy_dist = True

    def __init__(self, source, node: DistNode, chain: Tuple[DistNode, ...],
                 schema):
        self._source = source          # the materialized chain root
        self._dplan_node = node
        self._chain = chain            # op nodes, leaf -> final order
        self._mesh = source.mesh
        self.schema = schema
        self._forced = None
        self._force_lock = threading.Lock()
        self._dplan_info: Optional[List[str]] = None
        self._group_ids_cache: "OrderedDict" = OrderedDict()

    # -- laziness ----------------------------------------------------------
    def lazy(self):
        return self

    def _force(self):
        f = self._forced
        if f is not None:
            return f
        with self._force_lock:
            if self._forced is None:
                self._forced = _force_chain(self)
            return self._forced

    @property
    def mesh(self):
        # a forced chain may have recovered onto a SHRUNKEN mesh; the
        # record-time mesh stands until then
        f = self._forced
        return f.mesh if f is not None else self._mesh

    @property
    def columns(self):
        return self._force().columns

    @property
    def num_rows(self) -> int:
        return self._force().num_rows

    @property
    def shard_valid(self):
        return self._force().shard_valid

    # -- recorded ops ------------------------------------------------------
    def select(self, names) -> "LazyDistributedFrame":
        if isinstance(names, str):
            names = [names]
        names = list(names)
        missing = [n for n in names if n not in self.schema]
        if missing:
            raise KeyError(
                f"No column(s) {missing}; columns: {self.schema.names}")
        out_schema = self.schema.select(names)
        node = DSelectNode(self._dplan_node, out_schema, names)
        return LazyDistributedFrame(self._source, node,
                                    self._chain + (node,), out_schema)

    # -- estimates (no forcing) -------------------------------------------
    def estimated_rows(self):
        """Plan-derived row estimate WITHOUT forcing (filters priced at
        their observed selectivity once recorded) — the distributed
        twin of ``TensorFrame.estimated_rows``."""
        from ..memory.estimate import dist_frame_estimate
        return dist_frame_estimate(self)[0]

    def estimated_bytes(self):
        from ..memory.estimate import dist_frame_estimate
        return dist_frame_estimate(self)[1]

    # -- forwarding (everything else behaves like the forced frame) -------
    def count(self) -> int:
        return self.num_rows

    def explain(self) -> str:
        forced = self._force()
        report = forced.explain()
        if self._dplan_info and getattr(forced, "_dplan_info", None) \
                != self._dplan_info:
            report += "\n" + "\n".join(self._dplan_info)
        return report

    def __getattr__(self, name):
        # anything not defined here (collect_frame, per_shard_valid,
        # host_read_padded, valid_row_mask, padded_rows, ...) forces and
        # delegates — the forced frame IS this frame's value
        if name.startswith("_"):
            raise AttributeError(name)
        return getattr(self._force(), name)

    def __repr__(self):
        state = ("forced" if self._forced is not None
                 else f"{len(self._chain)} pending op(s)")
        return (f"LazyDistributedFrame[{', '.join(self.schema.names)}] "
                f"({state}) mesh={self._mesh!r}")


def lazy_frame(dist):
    """``DistributedFrame.lazy()`` backend: a recording view over
    ``dist``, or ``dist`` itself when recording cannot help
    (``TFT_FUSE=0``, multi-process meshes, frames whose rows do not
    tile the data axis)."""
    if getattr(dist, "_tft_lazy_dist", False):
        return dist
    if not fuse_enabled():
        return dist
    if jax.process_count() > 1:
        return dist
    S = dist.mesh.num_data_shards
    if S < 1 or dist.padded_rows % S != 0:
        return dist  # non-tiling (global-result) frames stay per-op
    node = DSourceNode(dist)
    return LazyDistributedFrame(dist, node, (), dist.schema)


def materialize(dist):
    """The materialized frame behind ``dist`` (forcing a lazy chain)."""
    if getattr(dist, "_tft_lazy_dist", False):
        return dist._force()
    return dist


# ---------------------------------------------------------------------------
# recording (called by the d-op entry points on lazy inputs)
# ---------------------------------------------------------------------------

def record_map(fetches, lazy: LazyDistributedFrame, trim: bool,
               row_aligned) -> Optional[LazyDistributedFrame]:
    """Record a ``dmap_blocks`` on a lazy frame, or ``None`` when the op
    must materialize + run per-op (trim/global programs, unprovable
    row preservation, foreign/static computations)."""
    from ..engine import ops as _ops

    if row_aligned is False and not trim:
        # the eager op's argument validation, raised at RECORD time — a
        # bad call must not first execute the whole pending chain
        raise ValueError(
            "row_aligned=False only makes sense for trim=True outputs: "
            "without trim the untrimmed input columns ride along and "
            "still contain pad rows, which declaring every output row "
            "real would surface as data")
    if trim or not fuse_enabled():
        return None
    comp = _ops.cached_map_computation(fetches, lazy.schema,
                                       block_level=True)
    # record-time validation: the same errors the eager op raises at
    # call time (schema mismatches must not move to force time)
    out_schema = _ops._validate_map(comp, lazy.schema, block_level=True,
                                    trim=False)
    if comp.opaque:
        return None
    if not _row_preserving(comp):
        return None  # the per-op runtime row-count check owns this
    counters.inc("dplan.recorded_ops")
    node = DMapNode(lazy._dplan_node, out_schema, comp)
    return LazyDistributedFrame(lazy._source, node, lazy._chain + (node,),
                                out_schema)


def record_filter(predicate,
                  lazy: LazyDistributedFrame
                  ) -> Optional[LazyDistributedFrame]:
    from ..engine import ops as _ops

    if not fuse_enabled():
        return None
    comp = _ops._filter_computation(predicate, lazy.schema)
    bad = [n for n in comp.input_names
           if (f := lazy.schema.get(n)) is not None and not f.dtype.tensor]
    if bad:
        # the eager op's error, raised at record time (error parity
        # without forcing the pending chain first)
        raise _ops.InvalidTypeError(
            f"dfilter predicate reads host-side (non-tensor) column(s) "
            f"{bad}: string columns ride along on the mesh but cannot "
            f"enter the sharded program. Filter on the host instead "
            f"(tensorframes_tpu.filter_rows / TensorFrame.filter) before "
            f"distribute().")
    if not _mask_shaped(comp):
        return None
    counters.inc("dplan.recorded_ops")
    node = DFilterNode(lazy._dplan_node, lazy.schema, comp)
    return LazyDistributedFrame(lazy._source, node, lazy._chain + (node,),
                                lazy.schema)


# ---------------------------------------------------------------------------
# chain planning
# ---------------------------------------------------------------------------

class _DPlan:
    """The fused-stage layout of one recorded chain (+ optional folded
    terminal reduction)."""

    __slots__ = ("ops", "members", "in_names", "out_names", "passthrough",
                 "host_names", "has_filter", "n_filters", "final_schema",
                 "reduce_names", "reduce_combs", "agg_combiners", "labels",
                 "filter_nodes", "est_bytes")

    def __init__(self):
        self.est_bytes = None  # plan-derived result size (ledger admission)
        self.ops = []
        self.members = []
        self.in_names = ()
        self.out_names = ()
        self.passthrough = ()
        self.host_names = ()
        self.has_filter = False
        self.n_filters = 0
        self.final_schema = None
        self.reduce_names = None   # sorted fetch names of a folded reduce
        self.reduce_combs = None   # {name: Combiner}
        self.agg_combiners = None  # {name: combiner-name} of a folded agg
        self.labels = []
        self.filter_nodes = []

    @property
    def n_ops(self) -> int:
        return len(self.ops) + (1 if (self.reduce_names is not None
                                      or self.agg_combiners) else 0)

    def describe(self, executed: Optional[str] = None) -> List[str]:
        term = ""
        if self.reduce_names is not None:
            term = " + dreduce_blocks[folded]"
        elif self.agg_combiners:
            term = " + daggregate[folded]"
        state = executed or "planned"
        lines = [f"  dplan    : {self.n_ops} op(s) -> 1 fused GSPMD "
                 f"program ({state})",
                 f"    stage 0: {'+'.join(self.labels) or 'pass'}{term} "
                 f"-> 1 mesh dispatch"]
        if self.passthrough:
            lines.append(f"    resident: {list(self.passthrough)} "
                         f"pass through device-resident (no program I/O)")
        if self.has_filter:
            lines.append(
                f"    filters : {self.n_filters} compacted in-program "
                f"(survivor counts stay traced; no inter-op host "
                f"readback)")
        return lines


def _plan_chain(source_schema, ops: Sequence[DistNode], final_schema,
                reduce_spec: Optional[Mapping[str, str]] = None,
                agg_value_names: Optional[Sequence[str]] = None
                ) -> Optional[_DPlan]:
    """Lay one fused stage out of the recorded ``ops``; ``None`` means
    the chain has nothing to fuse (select-only, no terminal)."""
    from ..parallel.collectives import COMBINERS

    plan = _DPlan()
    plan.ops = list(ops)
    plan.final_schema = final_schema

    # backward need pass (column pruning): a column is read/carried only
    # when it feeds a computation or survives to the final schema
    if reduce_spec is not None:
        need = set(reduce_spec)
    elif agg_value_names is not None:
        need = set(agg_value_names)
    else:
        need = {f.name for f in final_schema}
    for o in reversed(ops):
        if o.kind == "dmap":
            need = (need - set(o.comp.output_names)) \
                | set(o.comp.input_names)
        elif o.kind == "dfilter":
            need = need | set(o.comp.input_names)
        # select: need is already a subset of the selected names

    leaf_required = [f.name for f in source_schema
                     if f.dtype.tensor and f.name in need]
    plan.host_names = tuple(
        f.name for f in final_schema
        if not f.dtype.tensor) if reduce_spec is None \
        and agg_value_names is None else ()
    plan.in_names = tuple(leaf_required)

    # forward simulation: compose members, track the live tensor env in
    # deterministic order (leaf order, then map outputs by name)
    order: List[str] = list(leaf_required)
    env = set(order)
    produced: set = set()
    for o in ops:
        if o.kind == "dselect":
            keep = set(o.names)
            order = [n for n in order if n in keep]
            env &= keep
            produced &= keep
            plan.members.append(("sel", tuple(order)))
        elif o.kind == "dmap":
            if not set(o.comp.input_names) <= env:
                return None  # defensive: recorder guarantees this
            plan.members.append(("map", o.comp))
            plan.labels.append("dmap_blocks")
            for s in o.comp.outputs:
                if s.name not in env:
                    order.append(s.name)
                env.add(s.name)
                produced.add(s.name)
        else:  # dfilter
            if not set(o.comp.input_names) <= env:
                return None
            plan.members.append(("filter", o.comp, tuple(order)))
            plan.labels.append("dfilter")
            plan.has_filter = True
            plan.n_filters += 1
            plan.filter_nodes.append(o)
            produced = set(order)  # everything is permuted now

    if reduce_spec is not None:
        plan.reduce_names = sorted(reduce_spec)
        plan.reduce_combs = {n: COMBINERS[reduce_spec[n]]
                             for n in plan.reduce_names}
        if not set(plan.reduce_names) <= env:
            return None
        return plan
    if agg_value_names is not None:
        if not set(agg_value_names) <= env:
            return None
        return plan

    final_tensor = [f.name for f in final_schema if f.dtype.tensor]
    if not set(final_tensor) <= env:
        return None
    if plan.has_filter:
        # a filter permutes every live column: all survivors come out of
        # the program
        plan.out_names = tuple(n for n in order if n in set(final_tensor))
        plan.passthrough = ()
    else:
        plan.out_names = tuple(n for n in order
                               if n in produced and n in set(final_tensor))
        plan.passthrough = tuple(n for n in final_tensor
                                 if n not in produced)
    if not any(m[0] in ("map", "filter") for m in plan.members):
        return None  # select-only: no program needed
    return plan


# ---------------------------------------------------------------------------
# the fused program (per-op fragments composed inside ONE jit)
# ---------------------------------------------------------------------------

_fused_cache: "OrderedDict[tuple, tuple]" = OrderedDict()
_FUSED_CACHE_CAP = 64
_fused_lock = threading.Lock()


def _member_key(m) -> tuple:
    if m[0] == "map":
        return ("map", id(m[1]))
    if m[0] == "filter":
        return ("filter", id(m[1]), m[2])
    return m


def _filter_fragment(comp, alive: Tuple[str, ...], mesh, cnt, env):
    """The per-op ``_dfilter`` shard program, embedded: mask, per-shard
    stable compaction, survivor counts — counts stay traced."""
    axis = mesh.data_axis
    arrs = [env[n] for n in alive]
    in_specs = (P(axis),) + tuple(
        P(axis, *([None] * (a.ndim - 1))) for a in arrs)
    out_specs = tuple(
        P(axis, *([None] * (a.ndim - 1))) for a in arrs
    ) + (P(axis), P(axis))
    in_names = comp.input_names
    pname = comp.output_names[0]

    def filter_shard(cnt_l, *cols_l):
        local = dict(zip(alive, cols_l))
        m = comp.fn({n: local[n] for n in in_names})[pname]
        rows = cols_l[0].shape[0]
        rowid = jnp.arange(rows)
        keep = (m != 0) & (rowid < cnt_l[0])
        order = jnp.argsort((~keep).astype(jnp.int8), stable=True)
        permuted = tuple(jnp.take(c, order, axis=0) for c in cols_l)
        return permuted + (jnp.sum(keep, dtype=jnp.int32)[None], keep)

    outs = shard_map(filter_shard, mesh=mesh.mesh, in_specs=in_specs,
                     out_specs=out_specs)(cnt, *arrs)
    new_env = dict(zip(alive, outs[:len(alive)]))
    return new_env, outs[len(alive)], outs[len(alive) + 1]


def _agg_shard_fn(fetch_names, col_combiners, axis, prog_groups: int):
    """The per-shard monoid segment-reduce + collective — literally
    ``_daggregate``'s own fragment (``_monoid_agg_shard_fn``, one
    definition for the eager, fused, and streaming routes)."""
    return _dist()._monoid_agg_shard_fn(fetch_names, dict(col_combiners),
                                        axis, prog_groups)


def _build_fused_fn(plan: _DPlan, mesh, want_keeps: bool,
                    agg_groups: Optional[int] = None):
    """The whole chain as one function of ``(cnt[, ids], *cols)`` —
    map fragments on the global sharded arrays (per-op jit semantics),
    filter/reduce fragments as embedded ``shard_map`` regions."""
    from ..parallel.distributed import _collective_shard_fn

    axis = mesh.data_axis
    members = tuple(plan.members)
    in_names = plan.in_names
    out_names = plan.out_names
    has_filter = plan.has_filter
    reduce_names = plan.reduce_names
    reduce_combs = plan.reduce_combs
    agg = plan.agg_combiners

    def fused(cnt, *arrs):
        if agg_groups is not None:
            ids, cols = arrs[0], arrs[1:]
        else:
            ids, cols = None, arrs
        env = dict(zip(in_names, cols))
        keeps = []
        for m in members:
            if m[0] == "map":
                comp = m[1]
                out = comp.fn({n: env[n] for n in comp.input_names})
                env.update(out)
            elif m[0] == "sel":
                keep = set(m[1])
                env = {n: v for n, v in env.items() if n in keep}
            else:
                env, cnt, kp = _filter_fragment(m[1], m[2], mesh, cnt, env)
                keeps.append(kp)
        if reduce_names is not None:
            rarrs = [env[n] for n in reduce_names]
            in_specs = (P(axis),) + tuple(
                P(axis, *([None] * (a.ndim - 1))) for a in rarrs)
            out_specs = tuple(P() for _ in rarrs)
            red = shard_map(
                _collective_shard_fn(reduce_names, reduce_combs, axis),
                mesh=mesh.mesh, in_specs=in_specs,
                out_specs=out_specs)(cnt, *rarrs)
            return tuple(red) + ((cnt,) if has_filter else ())
        if agg is not None:
            fetch_names = sorted(agg)
            aarrs = [env[n] for n in fetch_names]
            in_specs = (P(axis),) + tuple(
                P(axis, *([None] * (a.ndim - 1))) for a in aarrs)
            out_specs = tuple(P() for _ in fetch_names)
            tables = shard_map(
                _agg_shard_fn(fetch_names, agg, axis, agg_groups),
                mesh=mesh.mesh, in_specs=in_specs,
                out_specs=out_specs)(ids, *aarrs)
            return tuple(tables)
        res = tuple(env[n] for n in out_names)
        if has_filter:
            res = res + (cnt,)
        if want_keeps:
            res = res + tuple(keeps)
        return res

    return fused


def _fused_program(plan: _DPlan, d, want_keeps: bool,
                   agg_groups: Optional[int] = None):
    """The cached jitted program for ``plan`` over ``d``'s mesh/shapes
    (a shrink/reshard changes both and rebuilds; comps are held strongly
    by the entry so their ids stay valid for the key's lifetime)."""
    mesh = d.mesh
    arrays = [d.columns[n] for n in plan.in_names]
    key = (mesh.mesh, mesh.data_axis,
           tuple(_member_key(m) for m in plan.members),
           tuple((n, a.shape, str(a.dtype))
                 for n, a in zip(plan.in_names, arrays)),
           plan.out_names, want_keeps, agg_groups,
           tuple(sorted(plan.reduce_combs))
           if plan.reduce_combs is not None else None,
           tuple(sorted(plan.agg_combiners.items()))
           if plan.agg_combiners else None)
    with _fused_lock:
        hit = _fused_cache.get(key)
        if hit is not None:
            _fused_cache.move_to_end(key)
            return hit[0], arrays
    fn = jax.jit(_build_fused_fn(plan, mesh, want_keeps, agg_groups))
    strong = [m[1] for m in plan.members if m[0] in ("map", "filter")]
    with _fused_lock:
        hit = _fused_cache.setdefault(key, (fn, strong))
        _fused_cache.move_to_end(key)
        while len(_fused_cache) > _FUSED_CACHE_CAP:
            _fused_cache.popitem(last=False)
    counters.inc("dplan.fused_programs")
    return hit[0], arrays


# ---------------------------------------------------------------------------
# execution
# ---------------------------------------------------------------------------

def _cnt_dev(d):
    mesh = d.mesh
    S = mesh.num_data_shards
    counts = d.per_shard_valid().astype(np.int32)
    return jax.make_array_from_callback(
        (S,), mesh.row_sharding(1), lambda idx: counts[idx])


def _admit(plan: _DPlan, d) -> None:
    """Ledger admission for the fused dispatch: spill colder residents
    before the program's outputs land (the per-op ``distribute`` /
    executor admission pattern). The plan-derived estimate
    (``memory.estimate.dist_frame_estimate`` — observed filter
    selectivities included) prices the result when available; the raw
    per-output sum is the fallback."""
    from .. import memory as _memory
    mgr = _memory.active()
    if mgr is None:
        return
    est = plan.est_bytes
    if est is None:
        rows = float(d.padded_rows)
        est = 0
        for o in plan.ops:
            if o.kind == "dmap":
                for s in o.comp.outputs:
                    est += int(rows * _cell_bytes(s.dtype,
                                                  s.shape.dims[1:]))
    if est:
        mgr.make_room(int(est))


def _register_result(cols: Dict, mesh_tag: str):
    """Resident shard edges join the memory LRU: the forced chain's
    columns spill to pinned host under ledger pressure and fault back
    on the next access, like any distributed frame."""
    from .. import memory as _memory
    mgr = _memory.active()
    if mgr is not None and mgr.spill_enabled:
        return _memory.spillable_columns(mesh_tag, cols, mgr)
    return cols


def _feedback_key(plan: _DPlan) -> str:
    """The fused stage's identity in the adaptive feedback registry
    (``docs/adaptive.md``): one record per plan shape, accumulated
    across forcings."""
    return (f"dplan[{','.join(o.kind for o in plan.ops)}]"
            f"({plan.final_schema.names})")


def _feedback_lines(plan: _DPlan) -> List[str]:
    """The per-stage shard-time line ``explain()`` renders from the
    feedback registry — the recorded-but-previously-unread half of the
    ROADMAP item 2 follow-on, surfaced so the data is visible before a
    future adaptive pass acts on it."""
    fb = stream_feedback(_feedback_key(plan))
    if fb is None or not fb.forcings:
        return []
    shards = max(fb.blocks // max(fb.forcings, 1), 1)
    return [f"    feedback: {fb.forcings} fused forcing(s) · "
            f"{shards} shard(s)/stage · mean stage wall "
            f"{fb.wall_s / fb.forcings * 1e3:.2f} ms · "
            f"{fb.rows} row(s) total (feedback registry; unused for "
            f"sizing today)"]


def _record_fallback(e: BaseException) -> None:
    """Always-on bookkeeping of a fused-chain fallback to the per-op
    path: the counter pair plus the flight-recorder decision (with the
    classified kind — fallbacks are rare enough to classify)."""
    from ..resilience import error_kind, is_oom
    counters.inc("dplan.fallbacks")
    if is_oom(e):
        counters.inc("dplan.oom_fallbacks")
    _flight.record("dplan.fallback", error=type(e).__name__,
                   error_kind=error_kind(e))


def _dispatch(plan: _DPlan, d, want_keeps: bool,
              agg_groups: Optional[int] = None, ids_dev=None):
    """One fused mesh dispatch over ``d`` through the resilient policy
    (transient retry with an async-failure barrier) + trace plumbing."""
    from ..resilience import default_policy as _default_policy
    from ..resilience import faults as _faults

    D = _dist()
    mesh = d.mesh
    if d.padded_rows % max(mesh.num_data_shards, 1) != 0:
        raise _Unfusable("frame rows do not tile the data axis")
    fn, arrays = _fused_program(plan, d, want_keeps, agg_groups)
    cnt = _cnt_dev(d)
    _admit(plan, d)
    policy = _default_policy()
    ins = (cnt,) + ((ids_dev,) if ids_dev is not None else ()) \
        + tuple(arrays)

    def _go():
        _faults.check("dmap")
        with span("dfused.dispatch"):
            out = fn(*ins)
            if policy.max_attempts > 1:
                jax.block_until_ready(out)
            return out

    trace = current_trace()
    t0 = (D._trace_shards(trace, "dfused", dist=d)
          if trace is not None else 0.0)
    import time as _time
    w0 = _time.perf_counter()
    # the regression drill's deterministic slowdown lands INSIDE the
    # measured stage wall, so the sentinel attributes it to stage_wall_s
    _faults.slowdown("perf")
    outs = policy.call(_go, op="dfused.dispatch")
    wall = _time.perf_counter() - w0
    counters.inc("mesh.dispatches")
    if trace is not None:
        add_event("fused_stage", name="+".join(plan.labels) or "pass",
                  ops=plan.n_ops, filters=plan.n_filters,
                  resident=len(plan.passthrough), wall_s=wall,
                  shards=mesh.num_data_shards)
        D._trace_mesh_done(trace, list(outs), t0, "dfused", mesh=mesh)
    return outs, wall


def _permute_host(a: np.ndarray, keep: np.ndarray, S: int) -> np.ndarray:
    """Replay one filter's per-shard compaction on a host (string)
    ride-along column — the exact ``_dfilter`` host-side rule."""
    rows_per = a.shape[0] // S
    out = np.empty_like(a)
    for s in range(S):
        sl = slice(s * rows_per, (s + 1) * rows_per)
        order = np.argsort(~keep[sl], kind="stable")
        out[sl] = a[sl][order]
    return out


def _meta_dfused(plan=None, source=None, *a, **k):
    source = k.get("source", source)
    plan = k.get("plan", plan)
    if source is None:
        return {}
    D = _dist()
    meta = D._mesh_meta(source)
    if plan is not None:
        meta["fused_ops"] = plan.n_ops
    return meta


@traced_query("dfused", _meta_dfused)
def _run_fused_frame(plan: _DPlan, source):
    from ..parallel import elastic as _elastic

    return _elastic.elastic_call("dfused", source,
                                 lambda d: _exec_frame(plan, d))


def _exec_frame(plan: _DPlan, d):
    D = _dist()
    S = d.mesh.num_data_shards
    want_keeps = plan.has_filter and bool(plan.host_names)
    outs, wall = _dispatch(plan, d, want_keeps)
    cols: Dict[str, object] = {}
    # resident passthrough: untouched source columns chain buffer-to-
    # buffer (matching shardings — no repartition, no program I/O);
    # per-key access through __getitem__ keeps SpillableColumns'
    # fault-back live
    for n in plan.passthrough:
        cols[n] = d.columns[n]
    for n, arr in zip(plan.out_names, outs[:len(plan.out_names)]):
        cols[n] = arr
    idx = len(plan.out_names)
    if plan.has_filter:
        counts = D._read_global(outs[idx]).astype(np.int64)
        idx += 1
        num_rows = int(counts.sum())
        shard_valid = counts
        if plan.n_filters == 1:
            # single-filter chains attribute the observed selectivity
            # to their predicate (row-preserving maps keep the count)
            record_selectivity(plan.filter_nodes[0].comp, d.num_rows,
                               num_rows)
    else:
        num_rows = d.num_rows
        shard_valid = d.shard_valid
    if want_keeps:
        keeps = [D._read_global(k) for k in outs[idx:idx + plan.n_filters]]
        for n in plan.host_names:
            a = np.asarray(d.columns[n], object)
            for keep in keeps:
                a = _permute_host(a, keep, S)
            cols[n] = a
    elif plan.host_names:
        for n in plan.host_names:
            cols[n] = d.columns[n]
    if not plan.passthrough:
        # every column is a FRESH program output: register the result
        # as one LRU spill candidate (the resident shard edge).
        # Passthrough columns are the SOURCE's own device buffers — its
        # registration already accounts them, and a second wrapper over
        # the same buffers would double-count resident bytes and make a
        # spill of either wrapper free nothing.
        cols = _register_result(cols, f"dfused@{id(plan):x}")
    # adaptive feedback (docs/adaptive.md): fused mesh stages record
    # their observed shard-stream shape AND the measured stage wall —
    # unused for sizing today (mesh shards are fixed by the mesh, not
    # the layout pass), but surfaced as the per-stage shard-time line
    # in DistributedFrame.explain()/last_query_report() so the record
    # is visible before a future PR acts on it (ROADMAP 2 follow-on)
    record_stream_feedback(_feedback_key(plan), blocks=S,
                           rows=num_rows, wall_s=wall)
    return D.DistributedFrame(d.mesh, plan.final_schema, cols, num_rows,
                              shard_valid=shard_valid)


def _replay_per_op(source, ops: Sequence[DistNode]):
    """The recorded chain re-run through the UNCHANGED eager d-op
    dispatches — the ``TFT_FUSE=0`` path and the unrecoverable-failure
    fallback, bit-identical to never having recorded at all."""
    D = _dist()
    cur = source
    for o in ops:
        if o.kind == "dmap":
            cur = D.dmap_blocks(o.comp, cur)
        elif o.kind == "dfilter":
            cur = D.dfilter(o.comp, cur)
        else:
            cur = cur.select(list(o.names))
    return cur


def _force_chain(lazy: LazyDistributedFrame):
    source, ops = lazy._source, list(lazy._chain)
    if not ops:
        lazy._dplan_info = ["  dplan    : empty chain (source frame)"]
        return source
    if not fuse_enabled():
        lazy._dplan_info = [
            "  dplan    : TFT_FUSE=0 — recorded chain replayed through "
            "the per-op d-op dispatches"]
        result = _replay_per_op(source, ops)
        result._dplan_info = lazy._dplan_info
        return result
    plan = _plan_chain(source.schema, ops, lazy.schema)
    if plan is None:
        # select-only chains: pure views, no dispatch at all
        cur = source
        for o in ops:
            if o.kind == "dselect":
                cur = cur.select(list(o.names))
            else:  # defensive: unplanned, not failed
                lazy._dplan_info = [
                    "  dplan    : chain not plannable — per-op replay"]
                return _replay_per_op(source, ops)
        lazy._dplan_info = [
            "  dplan    : projection-only chain (0 mesh dispatches)"]
        return cur
    from ..memory.estimate import dist_frame_estimate
    plan.est_bytes = dist_frame_estimate(lazy)[1]
    try:
        result = _run_fused_frame(plan, source)
    except Exception as e:  # noqa: BLE001 - reclassified below
        from ..resilience import is_device_lost
        if is_device_lost(e):
            raise  # elastic recovery exhausted: per-op parity is to raise
        _record_fallback(e)
        _log.warning(
            "fused mesh program failed (%s: %s); re-running the recorded "
            "chain through the per-op d-op dispatches", type(e).__name__,
            e)
        lazy._dplan_info = plan.describe(
            executed=f"FELL BACK per-op: {type(e).__name__}")
        result = _replay_per_op(source, ops)
        result._dplan_info = lazy._dplan_info
        return result
    counters.inc("dplan.fused_forcings")
    lazy._dplan_info = plan.describe(executed="executed") \
        + _feedback_lines(plan)
    # explain() on the FORCED frame renders the same plan section
    result._dplan_info = lazy._dplan_info
    return result


# ---------------------------------------------------------------------------
# folded terminal reductions
# ---------------------------------------------------------------------------

def record_reduce(fetches, lazy: LazyDistributedFrame
                  ) -> Optional[Dict[str, np.ndarray]]:
    """Fold a monoid ``dreduce_blocks`` into the pending chain's fused
    program as the terminal combiner; ``None`` defers to materialize +
    the eager op (generic computations, fusion off)."""
    from ..parallel.collectives import COMBINERS

    if not (isinstance(fetches, Mapping) and fetches and all(
            isinstance(v, str) for v in fetches.values())):
        return None
    if not fuse_enabled() or not lazy._chain:
        return None
    # the eager op's validation errors, raised before any work
    for name, cname in fetches.items():
        if name not in lazy.schema:
            raise KeyError(f"No column {name!r}")
        if cname not in COMBINERS:
            raise KeyError(
                f"Unknown combiner {cname!r}; known: {sorted(COMBINERS)}")
    source, ops = lazy._source, list(lazy._chain)
    plan = _plan_chain(source.schema, ops, lazy.schema,
                       reduce_spec=dict(fetches))
    if plan is None:
        return None
    if not plan.has_filter and source.num_rows == 0:
        raise ValueError("reduce on an empty distributed frame")
    try:
        result = _run_fused_reduce(plan, source)
    except _EmptyReduceError:
        raise  # the empty-after-filter contract (per-op parity)
    except Exception as e:  # noqa: BLE001 - reclassified below
        from ..resilience import is_device_lost
        if is_device_lost(e):
            raise
        _record_fallback(e)
        _log.warning(
            "fused mesh reduce failed (%s: %s); re-running per-op",
            type(e).__name__, e)
        D = _dist()
        return D.dreduce_blocks(fetches, _replay_per_op(source, ops))
    counters.inc("dplan.fused_forcings")
    lazy._dplan_info = plan.describe(executed="executed") \
        + _feedback_lines(plan)
    return result


@traced_query("dfused", _meta_dfused)
def _run_fused_reduce(plan: _DPlan, source):
    from ..parallel import elastic as _elastic

    return _elastic.elastic_call(
        "dfused", source, lambda d: _exec_reduce(plan, d))


def _exec_reduce(plan: _DPlan, d) -> Dict[str, np.ndarray]:
    from .. import dtypes as _dt

    D = _dist()
    outs, wall = _dispatch(plan, d, want_keeps=False)
    record_stream_feedback(_feedback_key(plan),
                           blocks=d.mesh.num_data_shards,
                           rows=d.num_rows, wall_s=wall)
    names = plan.reduce_names
    if plan.has_filter:
        counts = D._read_global(outs[len(names)]).astype(np.int64)
        num_rows = int(counts.sum())
        if plan.n_filters == 1:
            record_selectivity(plan.filter_nodes[0].comp, d.num_rows,
                               num_rows)
        if num_rows == 0:
            # the eager op raises before dispatching; here emptiness is
            # only knowable after — same exception type/text either way
            raise _EmptyReduceError(
                "reduce on an empty distributed frame")
    result = {}
    for name, a in zip(names, outs):
        v = np.asarray(a)
        f = plan.final_schema[name]
        if v.dtype != f.dtype.np_storage and f.dtype is not _dt.bfloat16:
            v = v.astype(f.dtype.np_storage)
        result[name] = v
    return result


def record_aggregate(fetches, lazy: LazyDistributedFrame, keys,
                     max_groups):
    """Fold a monoid host-key ``daggregate`` into the fused program
    (chain values segment-reduce per shard + one collective, DrJAX
    style). ``None`` defers to materialize + the eager op: device-key
    (``max_groups``) aggregations, generic computations, chains with a
    filter (the key→id factorization reads the SOURCE layout, which a
    filter invalidates), or keys produced/renamed by the chain."""
    if not fuse_enabled() or not lazy._chain:
        return None
    if max_groups is not None:
        return None
    if not (isinstance(fetches, Mapping) and fetches and all(
            isinstance(v, str) for v in fetches.values())):
        return None
    source, ops = lazy._source, list(lazy._chain)
    if any(o.kind == "dfilter" for o in ops):
        return None
    for k in keys:
        if k not in lazy.schema or k not in source.schema:
            return None
        if any(o.kind == "dmap" and k in o.comp.output_names for o in ops):
            return None  # a computed key column needs the chain's values
    from ..engine.ops import _validate_monoid_fetches

    value_names = [n for n in lazy.schema.names if n not in keys]
    _validate_monoid_fetches(fetches, value_names, "before distribute()")
    if source.num_rows == 0:
        raise ValueError("aggregate on an empty distributed frame")
    plan = _plan_chain(source.schema, ops, lazy.schema,
                       agg_value_names=sorted(fetches))
    if plan is None:
        return None
    plan.agg_combiners = dict(fetches)
    try:
        result = _run_fused_aggregate(plan, source, list(keys))
    except Exception as e:  # noqa: BLE001 - reclassified below
        from ..resilience import is_device_lost
        if is_device_lost(e):
            raise
        _record_fallback(e)
        _log.warning(
            "fused mesh aggregate failed (%s: %s); re-running per-op",
            type(e).__name__, e)
        D = _dist()
        return D.daggregate(fetches, _replay_per_op(source, ops), keys)
    counters.inc("dplan.fused_forcings")
    lazy._dplan_info = plan.describe(executed="executed") \
        + _feedback_lines(plan)
    return result


@traced_query("dfused", _meta_dfused)
def _run_fused_aggregate(plan: _DPlan, source, keys):
    from ..parallel import elastic as _elastic

    return _elastic.elastic_call(
        "dfused", source, lambda d: _exec_aggregate(plan, d, keys))


def _exec_aggregate(plan: _DPlan, d, keys):
    """Key ids factorize from the SOURCE frame (the chain is filter-free
    and the keys pass through untouched, so the row↔id layout is
    identical) — hot-key salting, the group-ids cache, and the host
    fold-back all ride the eager op's own helpers."""
    D = _dist()
    ids_dev, uniques, num_groups, salt_plan = D._monoid_group_plan(d, keys)
    if salt_plan is not None:
        prog_ids, prog_groups = salt_plan[0], salt_plan[1]
    else:
        prog_ids, prog_groups = ids_dev, num_groups
    fetch_names = sorted(plan.agg_combiners)
    outs, wall = _dispatch(plan, d, want_keeps=False,
                           agg_groups=prog_groups, ids_dev=prog_ids)
    record_stream_feedback(_feedback_key(plan),
                           blocks=d.mesh.num_data_shards,
                           rows=d.num_rows, wall_s=wall)
    tables = list(outs)
    if salt_plan is not None:
        from ..parallel import elastic as _elastic
        tables = [_elastic.fold_salted(t, salt_plan[2],
                                       plan.agg_combiners[f])
                  for f, t in zip(fetch_names, tables)]
    key_cols = {k: u for k, u in zip(keys, uniques)}
    out = D._monoid_agg_result(plan.final_schema, keys, fetch_names,
                               tables, key_cols, num_groups)
    if salt_plan is not None:
        # the fused fold surfaces its hot-key observations like the
        # eager op (frame.hot_keys() / explain() — docs/joins.md)
        D.attach_hot_keys(out, keys, uniques, salt_plan)
    return out


# ---------------------------------------------------------------------------
# streaming: per-batch window folds on the mesh
# ---------------------------------------------------------------------------

_stream_cache: "OrderedDict[tuple, object]" = OrderedDict()
_STREAM_CACHE_CAP = 32
_stream_lock = threading.Lock()


def mesh_segment_partial(mesh, col_combiners: Mapping[str, str],
                         ids: np.ndarray, vals: Mapping[str, np.ndarray],
                         num_groups: int) -> Dict[str, object]:
    """One batch's keyed partial tables computed as ONE fused GSPMD
    program on ``mesh`` — the streaming window fold riding the
    ``daggregate`` path: rows shard over the data axis, each shard
    segment-reduces its local rows, one ``psum``-family collective
    yields the replicated ``[groups, ...]`` tables the window state
    merges. Steady-state batches (same padded size / key cardinality)
    are pure program-cache hits."""
    S = mesh.num_data_shards
    fetch_names = sorted(col_combiners)
    n = int(ids.shape[0])
    padded = max(((n + S - 1) // S) * S, S)
    ids_p = np.full(padded, -1, np.int32)
    ids_p[:n] = ids
    ids_dev = jax.make_array_from_callback(
        (padded,), mesh.row_sharding(1), lambda idx: ids_p[idx])
    arrs = []
    for f in fetch_names:
        v = np.asarray(vals[f])
        if padded != n:
            out = np.zeros((padded,) + v.shape[1:], v.dtype)
            out[:n] = v
            v = out
        arrs.append(jax.device_put(v, mesh.row_sharding(v.ndim)))
    key = (mesh.mesh, mesh.data_axis, padded, num_groups,
           tuple((f, col_combiners[f], a.shape, str(a.dtype))
                 for f, a in zip(fetch_names, arrs)))
    with _stream_lock:
        fn = _stream_cache.get(key)
        if fn is not None:
            _stream_cache.move_to_end(key)
    if fn is None:
        axis = mesh.data_axis
        in_specs = (P(axis),) + tuple(
            P(axis, *([None] * (a.ndim - 1))) for a in arrs)
        out_specs = tuple(P() for _ in fetch_names)
        fn = jax.jit(shard_map(
            _agg_shard_fn(fetch_names, dict(col_combiners), axis,
                          num_groups),
            mesh=mesh.mesh, in_specs=in_specs, out_specs=out_specs))
        with _stream_lock:
            fn = _stream_cache.setdefault(key, fn)
            _stream_cache.move_to_end(key)
            while len(_stream_cache) > _STREAM_CACHE_CAP:
                _stream_cache.popitem(last=False)
        counters.inc("dplan.fused_programs")
    with span("stream.mesh_fold"):
        tables = fn(ids_dev, *arrs)
    counters.inc("mesh.dispatches")
    counters.inc("stream.mesh_folds")
    return dict(zip(fetch_names, tables))
