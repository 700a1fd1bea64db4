"""Block executor: jit-compile-cached execution of computations on blocks.

Replaces the reference's per-partition C++ session path
(``DebugRowOps.scala:755-794``: convert -> readGraph -> new Session ->
``tfLock.synchronized { session.Run }`` -> convertBack). The XLA model has no
session and needs no lock: a computation is compiled once per distinct input
signature (shape/dtype tuple) and the compiled executable is re-dispatched
for every block with that signature. The compile cache is the engine's answer
to the reference's "unknown leading dimension" problem (SURVEY.md §7 hard
part #1): exact-shape compiles by default, with an optional bucketed-padding
mode that pads the row dim to the next power of two so streams of odd-sized
blocks share executables (safe only for row-local computations, hence opt-in;
reductions and trim never pad).
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Dict, Mapping, Optional, Tuple

import jax
import numpy as np

from .. import dtypes as _dt
from .. import memory as _memory
from .. import native as _native
from ..computation import Computation
from ..observability import flight as _flight
from ..observability.events import add_event as _obs_event
from ..observability.events import current_trace as _obs_current_trace
from ..resilience import (default_policy, env_bool, faults, is_oom,
                          is_permanent)
from ..utils.logging import get_logger
from ..utils.tracing import (counters, enabled as _tracing_enabled,
                             histograms, span)

__all__ = ["BlockExecutor", "PaddingExecutor", "PendingBlock",
           "default_executor", "default_padding_executor",
           "set_computation_interner", "to_storage_dtype"]

_log = get_logger("engine.executor")


# Shared cross-query compile cache hook (the serving layer's interner):
# when installed, every run/submit first maps its Computation to a
# process-canonical equivalent, so two tenants tracing the same `x + 3`
# land on ONE weak-keyed jit cache entry instead of recompiling per
# Computation object. One slot, installed by serve.QueryScheduler;
# None (the default) is zero-cost.
_comp_interner = None


def set_computation_interner(fn):
    """Install (or clear with ``None``) the computation interner; returns
    the previous hook so callers can restore it."""
    global _comp_interner
    prev = _comp_interner
    _comp_interner = fn
    return prev


def current_computation_interner():
    """The installed interner (None when off) — lets an uninstalling
    owner check it still holds the slot before restoring."""
    return _comp_interner


def _intern(comp: Computation) -> Computation:
    f = _comp_interner
    if f is None:
        return comp
    try:
        out = f(comp)
        return out if out is not None else comp
    except Exception as e:  # interning is an optimization, never a gate
        _log.debug("computation interner failed (%s); running the "
                   "un-interned computation", e)
        return comp


def _oom_split_enabled() -> bool:
    return env_bool("TFT_OOM_SPLIT", True)


_backend_cpu: Optional[bool] = None


def _backend_is_cpu() -> bool:
    global _backend_cpu
    if _backend_cpu is None:
        _backend_cpu = jax.default_backend() == "cpu"
    return _backend_cpu


def _split_rows(comp: Computation, arrays: Mapping, n_rows: int):
    """Halve the row dimension: two input mappings whose row-dimensioned
    inputs are the top / bottom halves (non-row inputs ride whole)."""
    half = n_rows // 2
    first, second = {}, {}
    for spec in comp.inputs:
        a = arrays[spec.name]
        if spec.shape.ndim > 0 and spec.shape.head == -1:
            first[spec.name] = a[:half]
            second[spec.name] = a[half:]
        else:
            first[spec.name] = a
            second[spec.name] = a
    return first, second


def _concat_outputs(comp: Computation, a: Mapping, b: Mapping):
    """Stitch two half-block results back together; every output must be
    row-dimensioned (the row-local contract the split path requires)."""
    out = {}
    for spec in comp.outputs:
        if not (spec.shape.ndim > 0 and spec.shape.head == -1):
            raise ValueError(
                f"output {spec.name!r} has no row dimension; the OOM "
                f"split path only serves row-local computations")
        out[spec.name] = np.concatenate([a[spec.name], b[spec.name]])
    return out


def _oom_split_run(executor, comp: Computation, arrays: Mapping,
                   n_rows: Optional[int], cause: BaseException):
    """Re-dispatch an OOM'd row-local block as two halves (recursively:
    a half that still OOMs halves again through the same path).

    The caller established row-locality before calling; each half runs
    at its EXACT shape (``pad_ok=False``) — re-padding a half back up to
    the minimum bucket would dispatch the identical program and OOM
    identically, making the recovery futile for small blocks.

    Returns the stitched outputs, or re-raises ``cause`` when splitting
    is impossible (no rows / single row / non-row outputs / disabled).
    """
    if (not _oom_split_enabled() or not n_rows or n_rows < 2
            or any(not (s.shape.ndim > 0 and s.shape.head == -1)
                   for s in comp.outputs)):
        raise cause
    counters.inc("oom_split.dispatches")
    # OOM forensics: tag the split with the HBM watermark observed at
    # the moment it fired (backends without memory_stats contribute
    # nothing; gated on an active trace so the untraced path never
    # calls memory_stats)
    hbm: Dict = {}
    if _obs_current_trace() is not None:
        try:
            from ..observability import device as _obs_device
            wm = _obs_device.watermark()
            if wm is not None:
                hbm = {"hbm_live_bytes": wm["live_bytes"],
                       "hbm_peak_bytes": wm["peak_bytes"]}
        except Exception as e:
            # best-effort forensics, but never silently: a regression in
            # the sampler must not make watermark tags vanish unnoticed
            _log.debug("OOM watermark sample failed: %s", e)
    _obs_event("oom_split", rows=n_rows, error=type(cause).__name__,
               **hbm)
    _flight.record("engine.oom_split", rows=n_rows,
                   error=type(cause).__name__, **hbm)
    _log.warning(
        "block dispatch hit an OOM-shaped failure (%s); re-dispatching "
        "as two %d/%d-row halves", cause, n_rows // 2,
        n_rows - n_rows // 2)
    first, second = _split_rows(comp, arrays, n_rows)
    with span("executor.oom_split"):
        out_a = _run_half(executor, comp, first, n_rows // 2)
        out_b = _run_half(executor, comp, second, n_rows - n_rows // 2)
    return _concat_outputs(comp, out_a, out_b)


def _run_half(executor, comp: Computation, arrays: Mapping, n_rows: int):
    """One half of a split: exact-shape dispatch, recursing into a
    further split when the half itself still OOMs."""
    try:
        return executor.run(comp, arrays, pad_ok=False)
    except Exception as e:
        if is_oom(e):
            return _oom_split_run(executor, comp, arrays, n_rows, e)
        raise


def _dispatch_estimate(dev_arrays: Mapping, pad_to, n_rows) -> int:
    """Admission estimate of one dispatch's device footprint: inputs
    (scaled to the padded row count when bucketing) plus outputs
    assumed input-sized — 2x the staged input bytes."""
    total = 0
    for a in dev_arrays.values():
        total += int(a.nbytes)
    if pad_to and n_rows:
        total = int(total * (pad_to / n_rows))
    return 2 * total


def _splittable(comp: Computation, row_local: bool, n_rows) -> bool:
    """Whether the proactive pre-dispatch split is legal: the same
    row-locality contract as the reactive OOM split (every output
    row-dimensioned, >= 2 rows to halve)."""
    return bool(
        row_local and n_rows and n_rows >= 2
        and all(s.shape.ndim > 0 and s.shape.head == -1
                for s in comp.outputs))


def _proactive_split_run(executor, comp: Computation, arrays: Mapping,
                         n_rows: int, est: int):
    """Split a block BEFORE dispatch when its admission estimate alone
    exceeds the whole device budget (ROADMAP item 5's "blind split"
    fix: the reactive ``oom_split`` waited for the allocator to fail
    first). Counted separately (``memory.proactive_splits``); each half
    re-enters :meth:`BlockExecutor.run` and splits again if still over.
    """
    counters.inc("memory.proactive_splits")
    _obs_event("proactive_split", rows=n_rows, est_bytes=est)
    mgr = _memory.active()
    _flight.record("memory.proactive_split", rows=n_rows, bytes=est,
                   limit=mgr.limit if mgr is not None else None)
    _log.info(
        "block of %d rows (~%d B estimated) exceeds the device budget; "
        "splitting before dispatch", n_rows, est)
    first, second = _split_rows(comp, arrays, n_rows)
    with span("executor.proactive_split"):
        out_a = executor.run(comp, first, pad_ok=True)
        out_b = executor.run(comp, second, pad_ok=True)
    return _concat_outputs(comp, out_a, out_b)


def _next_bucket(n: int, minimum: int = 8) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def _timed_first_dispatch(fn, dev_arrays):
    """First dispatch of a freshly-jitted signature: jax traces and
    XLA-compiles synchronously inside this call (only execution is
    async), so its duration IS the compile time. Feeds the always-on
    ``compile_seconds`` histogram and, when a query trace listens, a
    ``compile`` event."""
    t0 = time.perf_counter()
    out = fn(dev_arrays)
    dt = time.perf_counter() - t0
    histograms.observe("compile_seconds", dt, engine="jax")
    _obs_event("compile", name="jax", dur=dt, engine="jax")
    return out


def to_storage_dtype(a: np.ndarray, dtype) -> np.ndarray:
    """Cast one host output array to its column storage dtype (bfloat16
    keeps its device view) — the single rule ``_convert_back`` and the
    plan executor's final-column conversion share."""
    storage = dtype.np_storage
    if a.dtype != storage and dtype is not _dt.bfloat16:
        return _native.convert(a, storage)
    return a


def _row_count(comp: Computation, arrays: Mapping) -> Optional[int]:
    """Leading row count of the first row-dimensioned input, if any."""
    for spec in comp.inputs:
        if spec.shape.ndim > 0 and spec.shape.head == -1:
            return np.asarray(arrays[spec.name]).shape[0]
    return None


def _pad_inputs(comp: Computation, arrays: Mapping, pad_to: int,
                n_rows: int) -> Dict[str, np.ndarray]:
    """Pad row-dimensioned inputs to ``pad_to`` rows (edge fill; pooled
    staging buffers so bucketed sizes reuse allocations)."""
    padded = {}
    for spec in comp.inputs:
        a = np.asarray(arrays[spec.name])
        if spec.shape.ndim > 0 and spec.shape.head == -1:
            dst = _native.empty_aligned((pad_to,) + a.shape[1:], a.dtype)
            dst[:n_rows] = a
            dst[n_rows:] = a[n_rows - 1:n_rows]  # edge fill
            a = dst
        padded[spec.name] = a
    return padded


def _slice_outputs(comp: Computation, out: Mapping, pad_to: int,
                   n_rows: int) -> Dict[str, np.ndarray]:
    """Drop pad rows from row-dimensioned outputs."""
    result = {}
    for spec in comp.outputs:
        a = out[spec.name]
        if spec.shape.ndim > 0 and spec.shape.head == -1 \
                and a.shape[:1] == (pad_to,):
            a = a[:n_rows]
        result[spec.name] = a
    return result


class PendingBlock:
    """One in-flight block: dispatched asynchronously, barrier deferred.

    The drain half of the :meth:`BlockExecutor.submit` /
    :meth:`drain` split. ``drain()`` waits for readiness and converts
    outputs back to host storage dtypes. Resilience composition: the
    async fast path carries NO retry loop — any failure (recorded at
    submit, or surfacing here at the output barrier, where JAX's async
    dispatch materializes execution errors) re-runs the originating
    block **synchronously** through :meth:`BlockExecutor.run`, i.e.
    through the existing retry / OOM-split / pad-fallback machinery.
    Each such recovery increments ``pipeline.sync_fallbacks``.
    """

    __slots__ = ("_executor", "_comp", "_arrays", "_pad_ok", "_out",
                 "_pad_to", "_n_rows", "_error", "_host", "_mem_mgr",
                 "_mem_bytes", "_keep_device", "__weakref__")

    def __init__(self, executor, comp, arrays, pad_ok, out=None,
                 pad_to=None, n_rows=None, error=None,
                 keep_device=False):
        self._executor = executor
        self._comp = comp
        self._arrays = arrays
        self._pad_ok = pad_ok
        self._out = out
        self._pad_to = pad_to
        self._n_rows = n_rows
        self._error = error
        # keep_device drains return raw (sliced) device outputs — the
        # plan executor's pipelined resident edges (docs/plan.md); an
        # early ledger spill (mem_spill) still hands back host arrays,
        # which every consumer accepts
        self._keep_device = keep_device
        # memory-manager integration: while in the FIFO window this
        # block is a registered spill candidate — its device output can
        # be drained to pinned host early under pressure
        self._host: Optional[Dict[str, np.ndarray]] = None
        self._mem_mgr = None
        self._mem_bytes = 0

    # -- memory-ledger entry protocol (docs/memory.md) ---------------------
    def mem_name(self) -> str:
        return f"pending-block-{id(self):x}"

    def mem_is_spilled(self) -> bool:
        return self._out is None

    def mem_device_bytes(self) -> int:
        return self._mem_bytes if self._out is not None else 0

    def mem_host_bytes(self) -> int:
        return 0  # spilled pendings ARE their drain result; never fault

    def mem_fault(self) -> int:
        return 0

    def mem_spill(self) -> int:
        """Early-drain the device output to host (called under the
        ledger lock, so it cannot race :meth:`drain` — drain unregisters
        first). A conversion failure records the error for the normal
        drain-side recovery."""
        if self._out is None or self._error is not None:
            return 0
        try:
            self._host = self._executor._convert_back(
                self._comp, self._out, self._pad_to, self._n_rows)
        except Exception as e:
            self._error = e
        self._out = None
        freed = self._mem_bytes
        self._mem_bytes = 0
        return freed

    def drain(self) -> Dict[str, np.ndarray]:
        m = self._mem_mgr
        if m is not None:
            # unregister FIRST (under the ledger lock): after this no
            # concurrent spill can touch our device output
            self._mem_mgr = None
            m.drop(self)
        if self._host is not None:
            return self._host
        if self._error is None:
            try:
                faults.check("drain")
                if self._keep_device:
                    out = self._out
                    result = {}
                    for spec in self._comp.outputs:
                        a = out[spec.name]
                        if self._pad_to is not None \
                                and spec.shape.ndim > 0 \
                                and spec.shape.head == -1 \
                                and a.shape[:1] == (self._pad_to,):
                            a = a[:self._n_rows]
                        result[spec.name] = a
                    jax.block_until_ready(result)
                    return result
                return self._executor._convert_back(
                    self._comp, self._out, self._pad_to, self._n_rows)
            except Exception as e:
                self._error = e
        if self._pad_to is None and is_permanent(self._error):
            # a deterministic failure with no padded attempt to fall back
            # from re-runs identically: raise it here (serial semantics,
            # attributed to this block by the FIFO drain) instead of
            # paying a duplicate execution and a bogus "recovery" count.
            # Padded-path errors always re-run: the sync path's
            # exact-shape fallback can still recover them.
            raise self._error
        counters.inc("pipeline.sync_fallbacks")
        _obs_event("sync_fallback", error=type(self._error).__name__,
                   padded=self._pad_to is not None)
        _flight.record("pipeline.sync_fallback",
                       error=type(self._error).__name__,
                       padded=self._pad_to is not None)
        _log.warning(
            "async fast path failed for a block (%s); re-running it "
            "synchronously through the resilient path", self._error)
        self._out = None  # drop the failed device outputs before re-running
        return self._executor.run(self._comp, self._arrays,
                                  pad_ok=self._pad_ok,
                                  keep_device=self._keep_device)


class BlockExecutor:
    """Executes :class:`Computation`s on columnar blocks with a compile cache.

    ``pad_rows``: when True, blocks are padded along the leading (row)
    dimension to power-of-two buckets before execution and outputs sliced
    back — one compile serves many block sizes. Only valid for computations
    whose per-row outputs do not depend on other rows.

    ``donate``: padded dispatches donate their input buffers to XLA
    (``jax.jit(..., donate_argnums=0)``) so the staging buckets'
    device allocations are reused for outputs instead of doubling HBM
    peak. Safe because every row-dimensioned input on that path is a
    freshly-built staging buffer the engine owns (``_pad_inputs``), never
    a caller array. ``TFT_DONATE=0`` disables.
    """

    def __init__(self, pad_rows: bool = False, donate: bool = True):
        self.pad_rows = pad_rows
        self._donate = donate
        # Keyed by the live Computation object (weakly): entries die with the
        # computation, so neither unbounded growth nor stale reuse after
        # CPython id() recycling is possible.
        self._cache: "weakref.WeakKeyDictionary[Computation, Dict[Tuple, object]]" = \
            weakref.WeakKeyDictionary()
        self._lock = threading.Lock()
        self.compile_count = 0  # observability: distinct signatures compiled

    # -- compile cache -----------------------------------------------------
    @staticmethod
    def _sig(comp: Computation, dev_arrays: Mapping) -> Tuple:
        """Compile-cache signature of one input mapping.

        The sorted input-name order is computed once per Computation and
        cached on it — the per-block ``sorted()`` over every (name, shape,
        dtype) tuple was measurable on streams of small blocks."""
        names = getattr(comp, "_tft_sig_names", None)
        if names is None:
            names = comp._tft_sig_names = tuple(
                sorted(s.name for s in comp.inputs))
        return tuple((n, dev_arrays[n].shape, str(dev_arrays[n].dtype))
                     for n in names)

    def _compiled(self, comp: Computation, sig: Tuple,
                  donate: bool = False):
        """Returns ``(fn, fresh)`` — ``fresh`` is True when THIS call
        created the jitted wrapper (a compile-cache miss): the caller
        times the first dispatch and attributes it as compile time
        (jax compiles lazily at first call, so the wrapper's creation
        itself costs nothing)."""
        # Double-checked locking: the lock-free fast path is safe under
        # the GIL (a dict read racing a dict write sees either the old or
        # the new table, never a torn one); EVERY mutation of the
        # weak-keyed outer map and the per-computation signature dicts
        # happens under self._lock, so two threads racing the same new
        # signature compile once and both get that executable
        # (tests/test_resilience.py::TestConcurrentDispatch).
        if donate:
            sig = ("donate",) + sig
        fresh = False
        per_comp = self._cache.get(comp)
        fn = None if per_comp is None else per_comp.get(sig)
        if fn is None:
            with self._lock:
                per_comp = self._cache.setdefault(comp, {})
                fn = per_comp.get(sig)
                if fn is None:
                    fn = jax.jit(comp.fn, donate_argnums=0) if donate \
                        else jax.jit(comp.fn)
                    per_comp[sig] = fn
                    fresh = True
                    self.compile_count += 1
                    counters.inc("compile_cache.misses")
                    _obs_event("compile_cache", hit=False)
                    _log.debug("compile #%d for signature %s",
                               self.compile_count, sig)
                elif _tracing_enabled():  # raced another thread to it
                    counters.inc("compile_cache.hits")
                    _obs_event("compile_cache", hit=True)
        elif _tracing_enabled():
            # hit bookkeeping only under tracing: hits are a per-dispatch
            # perf stat, and the counter's global mutex must not serialize
            # the lock-free fast path above when observability is off
            # (misses are rare and already inside the compile lock, so
            # they stay always-on)
            counters.inc("compile_cache.hits")
            _obs_event("compile_cache", hit=True)
        return fn, fresh

    def _donate_padded(self) -> bool:
        # donation only ever applies to the padded staging path, whose
        # row-dimensioned inputs the engine freshly allocates per dispatch
        # (and whose non-row inputs are host numpy, copied at device_put —
        # a donated copy, never the caller's buffer). Default: on where
        # device memory is the scarce resource (TPU/GPU), off on CPU —
        # there it buys nothing and a donating executable is an extra
        # compile-cache entry next to the plain one. TFT_DONATE overrides
        # either way.
        return self._donate and env_bool("TFT_DONATE",
                                         not _backend_is_cpu())

    # -- execution ---------------------------------------------------------
    def _dispatch(self, comp: Computation, dev_arrays: Mapping,
                  donate: bool = False):
        """Compile (cached) + dispatch one signature, with transient
        failures retried under the process policy. Fault sites:
        ``compile``, ``dispatch``, ``oom``."""
        sig = self._sig(comp, dev_arrays)

        def attempt():
            faults.check("compile")
            fn, fresh = self._compiled(comp, sig, donate=donate)
            faults.check("dispatch")
            faults.check("oom")
            with span("executor.dispatch"):
                if fresh:
                    out = _timed_first_dispatch(fn, dev_arrays)
                else:
                    out = fn(dev_arrays)
                # JAX dispatch is async: an execution failure would
                # otherwise surface at convert_back's np.asarray, OUTSIDE
                # this retry and the OOM-split handlers (it also keeps
                # device time attributed to this span)
                jax.block_until_ready(out)
            return out

        return default_policy().call(attempt, op="executor.dispatch")

    def _convert_inputs(self, comp: Computation, arrays: Mapping):
        """Host marshalling half: inputs cast to device dtypes; returns
        ``(dev_arrays, n_rows)`` with ``n_rows`` the leading row count of
        the first row-dimensioned input (None when there is none).

        Already-device-resident inputs (jax arrays in the device dtype —
        the logical plan's stage chaining, ``docs/plan.md``) pass through
        untouched: no D2H pull, no host cast, no re-upload."""
        dev_arrays = {}
        n_rows = None
        with span("executor.convert"):
            for spec in comp.inputs:
                a = arrays[spec.name]
                dd = _dt.device_dtype(spec.dtype)
                if isinstance(a, jax.Array) and a.dtype == dd:
                    dev_arrays[spec.name] = a
                else:
                    a = np.asarray(a)
                    if a.dtype != dd:
                        a = _native.convert(a, dd)  # threaded when built
                    dev_arrays[spec.name] = a
                if spec.shape.ndim > 0 and spec.shape.head == -1:
                    n_rows = a.shape[0] if n_rows is None else n_rows
        return dev_arrays, n_rows

    def _plan_pad(self, n_rows, pad_ok: bool):
        """Bucketed-padding plan: ``(row_local, pad_to)``.

        pad_rows+pad_ok is the executor's row-locality contract — the
        same property that makes padding safe makes halving safe."""
        row_local = bool(self.pad_rows and pad_ok and n_rows)
        pad_to = None
        if row_local:  # 0-row blocks never pad
            pad_to = _next_bucket(n_rows)
            if pad_to == n_rows:
                pad_to = None
        return row_local, pad_to

    def _convert_back(self, comp: Computation, out, pad_to,
                      n_rows) -> Dict[str, np.ndarray]:
        """D2H half: readiness wait (``np.asarray`` blocks on the async
        dispatch), pad-row slicing, storage-dtype casts."""
        result: Dict[str, np.ndarray] = {}
        with span("executor.convert_back"):
            host_out = {s.name: np.asarray(out[s.name])
                        for s in comp.outputs}
            if pad_to is not None:
                host_out = _slice_outputs(comp, host_out, pad_to, n_rows)
            for spec in comp.outputs:
                result[spec.name] = to_storage_dtype(
                    host_out[spec.name], spec.dtype)
        return result

    def run(self, comp: Computation,
            arrays: Mapping[str, np.ndarray],
            pad_ok: bool = True,
            keep_device: bool = False) -> Dict[str, np.ndarray]:
        """Run a computation on host arrays; returns host arrays.

        ``keep_device=True`` returns the raw device outputs instead of
        converting back to host storage dtypes — the logical plan's
        stage chaining feeds them straight into the next stage's inputs
        (``docs/plan.md``). Recovery paths (OOM split, proactive split)
        still return host arrays; callers must accept either.

        Inputs are cast to their device dtypes (double -> f32 on TPU) and
        outputs cast back to the computation's declared storage dtypes.

        Failure handling (``docs/resilience.md``): transient dispatch
        errors retry with backoff; a failing bucketed (padded) compile
        falls back to the exact shape; an OOM-shaped error on a row-local
        dispatch re-runs the block as two halves.

        Memory admission (``docs/memory.md``): under an active device
        budget the dispatch's estimated footprint is reserved first —
        spilling cold resident buffers, then waiting (bounded) for
        in-flight work; a row-local block whose estimate alone exceeds
        the whole budget splits BEFORE dispatch
        (``memory.proactive_splits``). With no budget configured this is
        one global read.
        """
        comp = _intern(comp)
        dev_arrays, n_rows = self._convert_inputs(comp, arrays)
        row_local, pad_to = self._plan_pad(n_rows, pad_ok)
        mgr = _memory.active()
        mem_tok = 0
        if mgr is not None:
            est = _dispatch_estimate(dev_arrays, pad_to, n_rows)
            if mgr.would_overflow(est) and _splittable(comp, row_local,
                                                       n_rows):
                return _proactive_split_run(self, comp, arrays, n_rows,
                                            est)
            mem_tok = mgr.reserve(est, op="executor.run")
        try:
            out = None
            if pad_to is not None:
                try:
                    faults.check("pad_compile")
                    padded = _pad_inputs(comp, dev_arrays, pad_to, n_rows)
                    out = self._dispatch(comp, padded,
                                         donate=self._donate_padded())
                except Exception as e:
                    if is_oom(e):
                        return _oom_split_run(self, comp, arrays, n_rows,
                                              e)
                    counters.inc("pad_fallback.compiles")
                    _obs_event("pad_fallback", pad_to=pad_to, rows=n_rows,
                               error=type(e).__name__)
                    _log.warning(
                        "bucketed %d-row compile/dispatch failed (%s); "
                        "falling back to the exact %d-row shape",
                        pad_to, e, n_rows)
                    pad_to = None
            if out is None:
                try:
                    out = self._dispatch(comp, dev_arrays)
                except Exception as e:
                    if is_oom(e) and row_local:
                        return _oom_split_run(self, comp, arrays, n_rows,
                                              e)
                    raise

            if keep_device:
                result = {}
                for spec in comp.outputs:
                    a = out[spec.name]
                    if pad_to is not None and spec.shape.ndim > 0 \
                            and spec.shape.head == -1 \
                            and a.shape[:1] == (pad_to,):
                        a = a[:n_rows]  # slices stay device-resident
                    result[spec.name] = a
                return result
            return self._convert_back(comp, out, pad_to, n_rows)
        finally:
            if mem_tok:
                mgr.release(mem_tok)

    def submit(self, comp: Computation,
               arrays: Mapping[str, np.ndarray],
               pad_ok: bool = True,
               keep_device: bool = False) -> PendingBlock:
        """Async fast-path half of :meth:`run`: convert + pad + dispatch
        with NO readiness barrier and NO retry loop. Never raises — any
        failure (including injected compile/dispatch/oom/pad_compile
        faults) is recorded on the returned :class:`PendingBlock`, whose
        ``drain()`` re-runs the block synchronously through :meth:`run`
        and therefore through the full resilience machinery.
        """
        comp = _intern(comp)
        pad_to = None
        mem = None  # (manager, token, est) while a reservation is held
        try:
            dev_arrays, n_rows = self._convert_inputs(comp, arrays)
            _, pad_to = self._plan_pad(n_rows, pad_ok)
            mgr = _memory.active()
            if mgr is not None:
                est = _dispatch_estimate(dev_arrays, pad_to, n_rows)
                tok = mgr.try_reserve(est, op="executor.submit")
                if tok is None:
                    # pressure: the async fast path must NEVER block (a
                    # stream waiting here while holding its own window
                    # would deadlock the budget) — run synchronously
                    # through the admitted path, which may wait, spill,
                    # or proactively split
                    counters.inc("memory.sync_dispatches")
                    _obs_event("mem_sync_dispatch", rows=n_rows,
                               est_bytes=est)
                    from .pipeline import ReadyResult
                    return ReadyResult(self.run(comp, arrays,
                                                pad_ok=pad_ok,
                                                keep_device=keep_device))
                mem = (mgr, tok, est)
            donate = False
            if pad_to is not None:
                faults.check("pad_compile")
                dev_arrays = _pad_inputs(comp, dev_arrays, pad_to, n_rows)
                donate = self._donate_padded()
            faults.check("compile")
            fn, fresh = self._compiled(comp, self._sig(comp, dev_arrays),
                                       donate=donate)
            faults.check("dispatch")
            faults.check("oom")
            with span("executor.dispatch_async"):
                # a fresh signature compiles synchronously inside this
                # call even on the async path — worth attributing
                out = (_timed_first_dispatch(fn, dev_arrays) if fresh
                       else fn(dev_arrays))
            pending = PendingBlock(self, comp, arrays, pad_ok, out=out,
                                   pad_to=pad_to, n_rows=n_rows,
                                   keep_device=keep_device)
            if mem is not None:
                # the reservation becomes a resident ledger entry: while
                # this block sits in the FIFO window its device output is
                # a spill candidate (early host drain under pressure)
                mgr, tok, est = mem
                pending._mem_mgr = mgr
                pending._mem_bytes = est
                mgr.convert_reservation(tok, pending)
                mem = None
            return pending
        except Exception as e:
            if mem is not None:
                mem[0].release(mem[1])
            # pad_to rides along so drain() knows whether the sync
            # re-run's exact-shape fallback could still recover this
            return PendingBlock(self, comp, arrays, pad_ok, error=e,
                                pad_to=pad_to, keep_device=keep_device)

    def clear(self):
        with self._lock:
            self._cache.clear()


class PaddingExecutor:
    """Bucketed-padding wrapper around ANY exact-shape executor.

    Pads the leading (row) dimension of row-dimensioned inputs to
    power-of-two buckets before delegating, and slices outputs back — so
    streams of odd-sized blocks share the inner executor's compiled
    programs (the same compile-signature bound ``BlockExecutor(pad_rows=
    True)`` provides, but composable with e.g. the native PJRT executor).
    Only valid for row-local computations, like every padding path.
    """

    def __init__(self, inner):
        self.inner = inner
        self.pad_rows = True

    @property
    def compile_count(self) -> int:
        return self.inner.compile_count

    def run(self, comp: Computation, arrays: Mapping[str, np.ndarray],
            pad_ok: bool = True) -> Dict[str, np.ndarray]:
        n_rows = _row_count(comp, arrays)
        pad_to = _next_bucket(n_rows) if (pad_ok and n_rows) else None
        if pad_to is None or pad_to == n_rows:  # incl. 0-row blocks
            try:
                return self.inner.run(comp, arrays, pad_ok=False)
            except Exception as e:
                if is_oom(e) and pad_ok:  # pad_ok == row-local here
                    return _oom_split_run(self, comp, arrays, n_rows, e)
                raise
        try:
            faults.check("pad_compile")
            padded = _pad_inputs(comp, arrays, pad_to, n_rows)
            out = self.inner.run(comp, padded, pad_ok=False)
        except Exception as e:
            if is_oom(e):
                return _oom_split_run(self, comp, arrays, n_rows, e)
            # a failing bucketed compile must not take the job down when
            # the exact shape (the no-padding semantics) can still run
            counters.inc("pad_fallback.compiles")
            _obs_event("pad_fallback", pad_to=pad_to, rows=n_rows,
                       error=type(e).__name__)
            _log.warning(
                "bucketed %d-row compile failed (%s); falling back to "
                "the exact %d-row shape", pad_to, e, n_rows)
            try:
                return self.inner.run(comp, arrays, pad_ok=False)
            except Exception as e2:
                # the exact-shape fallback can OOM too; this path is as
                # row-local as the one above, so the split still applies
                if is_oom(e2):
                    return _oom_split_run(self, comp, arrays, n_rows, e2)
                raise
        return _slice_outputs(comp, out, pad_to, n_rows)

    def clear(self):
        self.inner.clear()


_default: Optional[BlockExecutor] = None
_default_padding: Optional[BlockExecutor] = None
_default_lock = threading.Lock()


def default_executor() -> BlockExecutor:
    """Exact-shape executor: block-level computations may be cross-row
    (e.g. ``z = x - mean(x)``), so padding would corrupt them.

    ``TFT_EXECUTOR=pjrt`` routes the process default through the native
    C++ PJRT core (``native_pjrt.PjrtBlockExecutor``) with the jax
    in-process path as fallback if the native library is unavailable.
    """
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                import os
                if os.environ.get("TFT_EXECUTOR") == "pjrt":
                    try:
                        from ..native_pjrt import PjrtBlockExecutor
                        _default = PjrtBlockExecutor()
                    except Exception as e:  # fall back to the jax path
                        _log.warning(
                            "TFT_EXECUTOR=pjrt requested but the native "
                            "core is unavailable (%s); using the jax "
                            "executor", e)
                        _default = BlockExecutor()
                else:
                    _default = BlockExecutor()
    return _default


def default_padding_executor() -> BlockExecutor:
    """Bucketed-padding executor for row-local computations (``map_rows``:
    rows are independent under vmap, so padding the row dim to power-of-two
    buckets is safe and bounds compile signatures to O(log max_rows) for
    streams of odd-sized blocks — SURVEY.md §7 hard part #1).

    Under ``TFT_EXECUTOR=pjrt`` the buckets wrap the native PJRT executor
    (:class:`PaddingExecutor` composition), so map_rows runs through the
    C++ core too."""
    global _default_padding
    if _default_padding is None:
        inner = default_executor()  # resolves TFT_EXECUTOR + fallback once
        with _default_lock:
            if _default_padding is None:
                if type(inner) is BlockExecutor:
                    _default_padding = BlockExecutor(pad_rows=True)
                else:
                    # native core default: share its ONE client (a second
                    # PJRT client per process can be refused on TPU hosts)
                    _default_padding = PaddingExecutor(inner)
    return _default_padding
