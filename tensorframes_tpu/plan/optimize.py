"""Plan optimizer: fusion legality, column pruning, stage building.

The optimizer turns a linear chain of plan nodes into an
:class:`ExecPlan` — a list of :class:`Stage`\\ s, each one composed
:class:`~..computation.Computation` dispatched ONCE per block — or
returns ``None``, in which case the frame's unchanged per-op thunk runs
(the always-correct fallback; also the whole path under ``TFT_FUSE=0``).

Correctness is proof-driven, never assumed:

- a non-trim ``map_blocks`` fuses only when a symbolic abstract
  evaluation PROVES every fetch preserves the shared row symbol (the
  per-op path's runtime row-count check, discharged statically — a
  computation that violates it falls back and raises exactly as today);
- a trim ``map_blocks`` fuses only when all fetches provably share one
  lead expression; a filter predicate only when its mask provably has
  block length; ``map_rows`` is row-preserving by vmap construction;
- a filter ends its fusion stage: its mask is computed INSIDE the fused
  program (one extra output) but applied host-side, because a
  data-dependent row count is not expressible in one static-shape XLA
  program — the next stage then consumes the gathered, still
  device-resident columns;
- column pruning is a backward pass over the chain: only columns that
  feed a computation or survive to the final schema are read
  (``ParquetScanNode``), marshalled, or materialized as program outputs.

Composed computations are cached structurally (weakly anchored on their
first member computation), so repeated forcings — per-batch streaming
frames included — re-dispatch one compiled program instead of
re-tracing, and the serve layer's :class:`~..serve.cache
.SharedCompileCache` interns them across tenants like any other
computation.
"""

from __future__ import annotations

import threading
import weakref
from typing import Dict, List, Optional, Sequence, Set, Tuple

from ..computation import Computation, TensorSpec, _sym_avals
from ..resilience import env_bool
from ..utils.logging import get_logger
from . import nodes as _n

__all__ = ["enabled", "build_plan", "ExecPlan", "Stage", "MASK"]

_log = get_logger("plan.optimize")

# the fused filter mask's reserved output name; a user column with this
# name disables planning for the chain (checked in build_plan)
MASK = "_tft_mask"


def enabled() -> bool:
    """``TFT_FUSE`` gate (default on). ``TFT_FUSE=0`` disables every
    pass; forcing then runs the per-op thunks, bit-identical to the
    pre-plan engine by construction."""
    return env_bool("TFT_FUSE", True)


# ---------------------------------------------------------------------------
# symbolic legality proofs (cached per Computation)
# ---------------------------------------------------------------------------

def _abstract_outputs(comp: Computation):
    """``(eval_shape outputs, shared lead symbol)`` under symbolic
    avals, or ``None`` for symbolic-hostile / row-dim-free computations.
    Cached on the computation — one abstract eval per comp per process."""
    comp = comp.base  # a bound view proves what its computation does
    cached = getattr(comp, "_tft_plan_absout", False)
    if cached is not False:
        return cached
    res = None
    try:
        import jax
        avals, _ = _sym_avals(comp.inputs, share_lead_symbol=True)
        lead = None
        for spec, av in zip(comp.inputs, avals):
            if spec.shape.ndim > 0 and spec.shape.head == -1:
                lead = av.shape[0]
                break
        if lead is not None:
            extra = () if comp.bound is None else (comp.bound,)
            out = jax.eval_shape(
                comp.fn, {s.name: a for s, a in zip(comp.inputs, avals)},
                *extra)
            res = (out, lead)
    except Exception as e:
        # not an error: the computation simply stays unfused
        _log.debug("abstract eval for fusion proof failed (%s: %s); "
                   "computation stays unfused", type(e).__name__, e)
        res = None
    try:
        comp._tft_plan_absout = res
    except Exception as e:
        _log.debug("could not cache fusion proof on %r: %s", comp, e)
    return res


def _row_preserving(comp: Computation) -> bool:
    """Every fetch provably keeps the shared input row symbol — the
    static discharge of the per-op runtime row-count check."""
    r = _abstract_outputs(comp)
    if r is None:
        return False
    out, lead = r
    for name in comp.output_names:
        sh = out[name].shape
        if len(sh) == 0 or not bool(sh[0] == lead):
            return False
    return True


def _uniform_lead(comp: Computation) -> bool:
    """All fetches provably share ONE lead expression (the trim
    contract: fetches may change the row count, but must agree)."""
    r = _abstract_outputs(comp)
    if r is None:
        return False
    out, _ = r
    first = None
    for name in comp.output_names:
        sh = out[name].shape
        if len(sh) == 0:
            return False
        if first is None:
            first = sh[0]
        elif not bool(sh[0] == first):
            return False
    return True


def _mask_shaped(comp: Computation) -> bool:
    """The filter predicate provably yields one block-length vector."""
    r = _abstract_outputs(comp)
    if r is None:
        return False
    out, lead = r
    sh = out[comp.output_names[0]].shape
    return len(sh) == 1 and bool(sh[0] == lead)


# ---------------------------------------------------------------------------
# chain linearization
# ---------------------------------------------------------------------------

def linearize(frame):
    """``(leaf_node, [op nodes leaf->final])`` or ``None``.

    Walks ``input`` links from the frame's node; an upstream op whose
    own frame is already forced becomes the leaf (its cached blocks are
    free — exactly what the per-op thunk would reuse)."""
    node = getattr(frame, "_plan_node", None)
    if node is None:
        return None
    chain: List[_n.PlanNode] = []
    while node is not None:
        if node.kind not in _n.OP_KINDS:
            chain.reverse()
            return node, chain
        rf = node.result_ref() if node.result_ref is not None else None
        if rf is not None and rf is not frame \
                and getattr(rf, "_cache", None) is not None:
            chain.reverse()
            return _n.SourceNode(rf), chain
        chain.append(node)
        node = node.input
    return None


# ---------------------------------------------------------------------------
# composed computations (structurally cached)
# ---------------------------------------------------------------------------

# anchor comp (weak) -> {structural key: (composed, [strong member refs])}
# The strong refs keep the other members' id()s valid for as long as the
# entry lives; the anchor itself must NOT be held strongly by its own
# entry (a value->key reference in a WeakKeyDictionary would leak).
_composed_cache: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_composed_lock = threading.Lock()

# a stage member is ("mb", comp, trim) | ("mr", vcomp) | ("f", comp)
# | ("sel", names)
Member = Tuple


def _bound(m: Member) -> bool:
    return m[0] != "sel" and m[1].bound is not None


def _compose(members: Sequence[Member], in_specs: List[TensorSpec],
             out_specs: List[TensorSpec]) -> Computation:
    """The members' program as one computation. ``members`` hold
    unbound computations; the bound members' values ride in as the
    composed computation's own bound argument, a tuple in member
    order."""
    mem = tuple(members)
    data_names = tuple(s.name for s in out_specs if s.name != MASK)
    has_mask = any(s.name == MASK for s in out_specs)
    bound = tuple(m[1].bound for m in mem if _bound(m)) or None

    def fused_fn(d, *b):
        env = dict(d)
        mask = None
        values = iter(b[0] if b else ())
        for m in mem:
            if m[0] == "sel":
                keep = set(m[1])
                env = {k: v for k, v in env.items() if k in keep}
                continue
            comp = m[1]
            extra = (next(values),) if comp.bound is not None else ()
            out = comp.fn({n: env[n] for n in comp.input_names}, *extra)
            if m[0] == "f":
                mask = out[comp.output_names[0]]
            elif m[0] == "mb" and m[2]:
                env = dict(out)  # trim: only the fetches survive
            else:
                env.update(out)
        res = {n: env[n] for n in data_names}
        if has_mask:
            res[MASK] = mask
        return res

    return Computation(fused_fn, in_specs, out_specs, bound)


def _member_key(m: Member):
    if m[0] == "sel":
        return ("sel", m[1])
    if m[0] == "mb":
        return ("mb", id(m[1]), m[2])
    return (m[0], id(m[1]))


def _composed_cached(members: Sequence[Member], in_specs: List[TensorSpec],
                     out_specs: List[TensorSpec]) -> Computation:
    """The composed computation, cached on the members' unbound
    computations (so every value set of a bound member shares one
    program) and bound to this chain's values."""
    values = tuple(m[1].bound_values for m in members if _bound(m))
    members = [m if m[0] == "sel" else (m[0], m[1].base) + tuple(m[2:])
               for m in members]
    comp = _composed_unbound(members, in_specs, out_specs)
    return comp if comp.bound is None else comp.bind(values)


def _composed_unbound(members: Sequence[Member],
                      in_specs: List[TensorSpec],
                      out_specs: List[TensorSpec]) -> Computation:
    anchor = next((m[1] for m in members if m[0] != "sel"), None)
    if anchor is None:
        return _compose(members, in_specs, out_specs)
    key = (tuple(_member_key(m) for m in members),
           tuple(s.name for s in in_specs),
           tuple((s.name, s.dtype.name, tuple(s.shape.dims))
                 for s in out_specs))
    try:
        with _composed_lock:
            per = _composed_cache.setdefault(anchor, {})
            hit = per.get(key)
    except TypeError:  # unweakrefable anchor: compose fresh
        return _compose(members, in_specs, out_specs)
    if hit is not None:
        return hit[0]
    comp = _compose(members, in_specs, out_specs)
    strong = [m[1] for m in members
              if m[0] != "sel" and m[1] is not anchor]
    with _composed_lock:
        per = _composed_cache.setdefault(anchor, {})
        hit = per.setdefault(key, (comp, strong))
    return hit[0]


# ---------------------------------------------------------------------------
# stages and the executable plan
# ---------------------------------------------------------------------------

class Stage:
    """One fused dispatch: a composed program plus the host-side glue
    around it (passthrough columns, the filter mask, the boundary
    schema for mid-chain empty results)."""

    __slots__ = ("comp", "inputs", "outputs", "passthrough", "mask",
                 "label", "op_end", "boundary_schema", "row_local")

    def __init__(self, comp: Optional[Computation], inputs: Tuple[str, ...],
                 outputs: Tuple[str, ...], passthrough: Tuple[str, ...],
                 mask: bool, label: str, op_end: int, boundary_schema,
                 row_local: bool):
        self.comp = comp
        self.inputs = inputs
        self.outputs = outputs
        self.passthrough = passthrough
        self.mask = mask
        self.label = label
        self.op_end = op_end  # index into ExecPlan.ops of the last op
        self.boundary_schema = boundary_schema
        # every member is a vmapped map_rows: rows are independent BY
        # CONSTRUCTION, so the stage keeps the per-op map_rows executor
        # semantics — bucketed padding and the reactive OOM split. A
        # stage with a map_blocks/filter member may be cross-row
        # (z = x - mean(x)) and must run exact-shape, like its per-op
        # twin does through the default executor.
        self.row_local = row_local


class ExecPlan:
    """The optimizer's output: leaf + stages + pruning decisions."""

    __slots__ = ("leaf", "ops", "stages", "final_schema", "leaf_required",
                 "scan_names", "device_ops", "pruned", "scan_atoms",
                 "row_local_chain", "priced_sel", "reordered")

    def __init__(self, leaf, ops, stages, final_schema, leaf_required,
                 scan_names, device_ops, pruned, scan_atoms=(),
                 row_local_chain=False, priced_sel=None, reordered=False):
        self.leaf = leaf
        self.ops = ops
        self.stages = stages
        self.final_schema = final_schema
        self.leaf_required = leaf_required  # leaf columns actually needed
        self.scan_names = scan_names        # leaf columns feeding programs
        self.device_ops = device_ops
        self.pruned = pruned                # leaf columns NOT read
        self.scan_atoms = scan_atoms        # parquet pushdown predicates
        # every device op provably row-local (vmapped map_rows, selects,
        # atom-proven filter predicates): the adaptive block-sizing pass
        # may legally re-bucket the leaf stream (docs/adaptive.md)
        self.row_local_chain = row_local_chain
        # selectivity each filter op was PRICED at when this plan was
        # built (None = the keeps-everything upper bound); the executor
        # compares observations against these to trigger a re-plan
        self.priced_sel = priced_sel or {}
        self.reordered = reordered  # filter run re-ordered by feedback

    def describe(self) -> List[str]:
        """``explain()``'s plan section: fused groups, pruned columns,
        resident edges."""
        lines = [f"  plan     : {len(self.ops) + 1} node(s) -> "
                 f"{len(self.stages)} fused stage(s), "
                 f"{self.device_ops} device op(s) fused (TFT_FUSE=1)"]
        src = self.leaf.describe()
        if self.pruned:
            lines.append(
                f"    source : {src} · read {len(self.leaf_required)}/"
                f"{len(self.leaf.schema)} column(s) "
                f"{list(self.leaf_required)} (pruned {list(self.pruned)})")
        else:
            lines.append(f"    source : {src} · "
                         f"{len(self.leaf_required)} column(s)")
        if self.scan_atoms:
            preds = ", ".join(f"{a.column} {a.op} {a.value:g}"
                              for a in self.scan_atoms)
            lines.append(
                f"    pushdown: [{preds}] checked against row-group "
                f"footer statistics (refuted groups never read)")
        if self.reordered:
            lines.append(
                "    adaptive: conjunctive filters re-ordered by "
                "observed selectivity (TFT_ADAPTIVE=1, "
                "docs/adaptive.md)")
        for i, st in enumerate(self.stages):
            edge = ("host rows" if i == 0 else "device-resident")
            mask_s = " · mask applied host-side" if st.mask else ""
            lines.append(
                f"    stage {i}: {st.label} -> 1 dispatch/block "
                f"(in: {edge}){mask_s}")
        return lines


def _atom_filter(comp) -> bool:
    """True when the predicate's sole output is PROVEN a conjunction of
    column-vs-literal comparisons (:mod:`.predicates`) — i.e. the
    predicate is row-local: its mask row depends only on that row."""
    from .predicates import extract_atoms
    return bool(extract_atoms(comp))


def _reorder_filters(ops):
    """Adaptive re-planning (docs/adaptive.md): runs of ADJACENT
    filters whose predicates are all atom-proven (row-local, so they
    commute — same final row set, same order, same block boundaries)
    re-order most-selective-first by observed selectivity, so later
    filter dispatches see fewer rows. Unobserved predicates price at
    the keeps-everything upper bound and keep their position
    (stable sort). Returns ``(ops, reordered)``."""
    from .adaptive import enabled as _adaptive_on
    if not _adaptive_on():
        return ops, False
    out = list(ops)
    changed = False
    i = 0
    while i < len(out):
        if out[i].kind != "filter" or not _atom_filter(out[i].comp):
            i += 1
            continue
        j = i
        while j < len(out) and out[j].kind == "filter" \
                and _atom_filter(out[j].comp):
            j += 1
        if j - i > 1:
            run = out[i:j]
            sels = [_n.observed_selectivity(o.comp) for o in run]
            if any(s is not None for s in sels):
                order = sorted(range(len(run)),
                               key=lambda k: (sels[k] if sels[k]
                                              is not None else 1.0, k))
                if order != list(range(len(run))):
                    out[i:j] = [run[k] for k in order]
                    changed = True
                    from ..observability import flight as _flight
                    _flight.record(
                        "plan.filter_reorder", order=order,
                        selectivities=[round(s, 6) if s is not None
                                       else None for s in sels])
        i = j
    if changed:
        from ..utils.tracing import counters
        counters.inc("plan.filter_reorders")
    return out, changed


def build_plan(frame) -> Optional[ExecPlan]:
    """Optimize ``frame``'s recorded chain, or ``None`` for the per-op
    fallback. Never raises for an unsupported chain — unsupported means
    unplanned, not failed."""
    if not enabled():
        return None
    lin = linearize(frame)
    if lin is None:
        return None
    leaf, ops = lin
    if not ops:
        return None
    device_ops = sum(1 for o in ops
                     if o.kind in ("map_blocks", "map_rows", "filter"))
    # parquet scans prune their read; join leaves prune the columns
    # the join materializes (docs/joins.md)
    prunable_leaf = leaf.kind in ("parquet", "join")
    if device_ops < 2 and not prunable_leaf:
        return None  # nothing to win; per-op semantics stay canonical
    if MASK in leaf.schema or any(MASK in o.schema for o in ops):
        return None
    # adaptive re-plan (docs/adaptive.md): order observed-selective
    # conjunctive filters first — on every forcing AND between stream
    # batches, since each batch builds a fresh plan over the shared
    # canonical computations carrying the observations
    ops, reordered = _reorder_filters(ops)

    # legality: every device op must carry a proof, or the chain falls
    # back wholesale (all-or-nothing keeps error contracts identical)
    for o in ops:
        if o.kind == "map_blocks":
            if o.comp.opaque:
                return None  # foreign/static modules stay per-op
            if o.trim:
                if not _uniform_lead(o.comp):
                    return None
            elif not _row_preserving(o.comp):
                return None
        elif o.kind == "map_rows":
            if o.vcomp is None or o.comp.opaque:
                return None
        elif o.kind == "filter":
            if not _mask_shaped(o.comp):
                return None

    # backward pass: required columns after (and before) every op
    final_schema = ops[-1].schema
    need: Set[str] = set(final_schema.names)
    req_after: List[Set[str]] = [set()] * len(ops)
    for i in range(len(ops) - 1, -1, -1):
        req_after[i] = set(need)
        o = ops[i]
        if o.kind == "map_blocks":
            if o.trim:
                need = set(o.comp.input_names)
            else:
                need = (need - set(o.comp.output_names)) \
                    | set(o.comp.input_names)
        elif o.kind == "map_rows":
            need = (need - set(o.comp.output_names)) \
                | set(o.comp.input_names)
        elif o.kind == "filter":
            need = need | set(o.comp.input_names)
        # select: need is already a subset of the selected names
    if not len(final_schema):
        # select([]) chains: the per-op path owns the empty-schema
        # corner (a zero-output fused program cannot carry the row
        # count a mid-chain trim may have changed)
        return None
    leaf_required = tuple(f.name for f in leaf.schema if f.name in need)
    if not leaf_required and len(leaf.schema):
        return None  # empty projection: a corner the per-op path owns

    # forward simulation: group ops into stages, resolve program
    # inputs/outputs, keep per-name block-level specs
    spec_of: Dict[str, TensorSpec] = {}
    origin: Dict[str, str] = {}
    for f in leaf.schema:
        if f.name not in need:
            continue
        spec_of[f.name] = (TensorSpec(f.name, f.dtype, f.block_shape)
                           if f.dtype.tensor and f.block_shape is not None
                           else None)
        origin[f.name] = "leaf"
    live: Set[str] = set(leaf_required)
    stages: List[Stage] = []
    members: List[Member] = []
    ext: Dict[str, TensorSpec] = {}
    internal: Set[str] = set()
    labels: List[str] = []
    scan_names: Set[str] = set()

    def close(idx: int, mask_member: Optional[Member]) -> None:
        nonlocal live, members, ext, internal, labels
        req = req_after[idx]
        produced = tuple(n for n in sorted(live)
                         if n in internal and n in req)
        passthrough = tuple(n for n in sorted(live)
                            if n not in internal and n in req)
        out_specs = [spec_of[n] for n in produced]
        if mask_member is not None:
            mspec = mask_member[1].outputs[0]
            out_specs.append(TensorSpec(MASK, mspec.dtype, mspec.shape))
        comp = None
        if any(m[0] != "sel" for m in members) or mask_member is not None:
            mem = list(members) + ([mask_member] if mask_member else [])
            in_specs = [ext[n] for n in sorted(ext)]
            comp = _composed_cached(mem, in_specs, out_specs)
        if comp is not None:
            row_local = (mask_member is None
                         and all(m[0] in ("mr", "sel") for m in members)
                         and any(m[0] == "mr" for m in members))
            stages.append(Stage(
                comp, tuple(sorted(ext)), produced, passthrough,
                mask_member is not None, "+".join(labels) or "pass",
                idx, ops[idx].schema, row_local))
        live = set(produced) | set(passthrough)
        for n in produced:
            origin[n] = "computed"
        members, ext, internal, labels = [], {}, set(), []

    bailed = False
    for i, o in enumerate(ops):
        if o.kind == "select":
            keep = set(o.names)
            live &= keep
            internal &= keep
            members.append(("sel", tuple(o.names)))
            continue
        comp = o.vcomp if o.kind == "map_rows" else o.comp
        ok = True
        for n in comp.input_names:
            if n in internal:
                continue
            sp = spec_of.get(n)
            if n not in live or sp is None:
                ok = False
                break
            ext.setdefault(n, sp)
            if origin.get(n) == "leaf":
                scan_names.add(n)
        if not ok:
            bailed = True
            break
        if o.kind == "filter":
            labels.append("filter")
            close(i, ("f", comp))
            continue
        trim = o.kind == "map_blocks" and o.trim
        members.append(("mb", comp, trim) if o.kind == "map_blocks"
                       else ("mr", comp))
        labels.append(o.kind + ("[trim]" if trim else ""))
        if trim:
            live, internal = set(), set()
        for s in comp.outputs:
            live.add(s.name)
            internal.add(s.name)
            spec_of[s.name] = s
            origin[s.name] = "computed"
    if bailed:
        return None
    if members:
        close(len(ops) - 1, None)
    if not stages:
        # a pure-projection chain still plans when it prunes a parquet
        # read; otherwise the per-op path is already minimal
        if not (prunable_leaf and len(leaf_required) < len(leaf.schema)):
            return None
    pruned = tuple(f.name for f in leaf.schema if f.name not in need) \
        if prunable_leaf else ()
    # adaptive legality + priced selectivities (docs/adaptive.md): the
    # block re-bucketing pass may only touch chains whose every device
    # op is provably row-local — vmapped map_rows, selects, and
    # atom-proven filter predicates (cross-row map_blocks statistics
    # would change under coalescing); filters record the selectivity
    # this plan priced them at, the re-plan trigger's baseline
    row_local_chain = bool(stages) and all(
        o.kind in ("map_rows", "select")
        or (o.kind == "filter" and _atom_filter(o.comp))
        for o in ops)
    priced_sel = {i: _n.observed_selectivity(o.comp)
                  for i, o in enumerate(ops) if o.kind == "filter"}
    return ExecPlan(leaf, list(ops), stages, final_schema, leaf_required,
                    frozenset(scan_names), device_ops, pruned,
                    _scan_atoms(leaf, ops), row_local_chain=row_local_chain,
                    priced_sel=priced_sel, reordered=reordered)


def _scan_atoms(leaf, ops):
    """Pushdown atoms for a parquet leaf (ROADMAP 2c): conjunctive
    ``column <op> literal`` filter predicates over SCAN columns,
    extractable from any filter BEFORE the first trim (a trim replaces
    the schema, severing column identity; non-trim maps only append —
    fetch-name collisions are rejected — so a leaf-named column still
    carries the leaf's values at every later filter). Sound for
    whole-group skipping regardless of earlier filters: a group whose
    every row fails the predicate contributes nothing downstream.
    Explicitly re-partitioned scans (``num_partitions=``) push down
    too: the scan node remaps surviving group rows onto the partition
    spans the unpushed read would have produced (``docs/plan.md``)."""
    if leaf.kind != "parquet":
        return ()
    from .predicates import extract_atoms
    leaf_cols = set(leaf.schema.names)
    atoms = []
    for o in ops:
        if o.kind == "map_blocks" and o.trim:
            break
        if o.kind != "filter":
            continue
        for a in extract_atoms(o.comp):
            if a.column in leaf_cols:
                atoms.append(a)
    return tuple(atoms)
