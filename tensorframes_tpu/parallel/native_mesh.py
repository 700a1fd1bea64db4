"""Mesh execution through the native C++ PJRT core (GSPMD-partitioned).

The reference's defining property is that *every* execution bottoms out in
C++ — each partition's work runs in a libtensorflow session
(``TensorFlowOps.scala:55-64``, ``DebugRowOps.scala:776-788``). The
single-host six ops already do (``native_pjrt.PjrtBlockExecutor``); this
module extends the property to the DISTRIBUTED half of the framework: the
same logical programs ``dmap_blocks`` / ``dreduce_blocks`` build are

- lowered once on the driver (jax used for tracing only, GSPMD flavor:
  ``mhlo.sharding``-annotated global shapes),
- compiled in the native core as ONE SPMD-partitioned executable
  (``tfr_pjrt_compile_spmd`` — XLA's SPMD partitioner derives the
  per-device program and inserts the ICI collectives), and
- executed across all mesh devices in ONE native call with per-device
  shard buffers (``tfr_pjrt_execute_replicated``).

Routing: ``TFT_EXECUTOR=pjrt`` (the same switch that routes the host
engine through the native core) enables this path for single-process
meshes, covering row-aligned ``dmap_blocks``, the collective
``dreduce_blocks``, the full ``dsort`` columnsort pipeline (local sorts
AND all_to_all/ppermute exchanges in one executable), ``dfilter``, and
both ``daggregate`` paths — the monoid segment-reduce (with the XLA
scatter-add ``segment_sum`` flavor: the Pallas flavor lowers to Mosaic
custom calls outside the native backends' vocabulary) and the generic
sorted-scan fold — so every mesh op now reaches the C++ core. Anything
the native route cannot express (trim/global outputs, bfloat16 columns,
multi-host frames) falls back to the in-process jax dispatch with
identical semantics. The device-resident benchmark loops
keep using the jax path — data staying in jax Arrays is the point there;
the native mesh path demonstrates (and tests, cpu:4 parity vs jax) that
the C ABI can host the sharded programs themselves.
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional

import numpy as np

from ..observability import events as _obs
from ..utils.logging import get_logger
from ..utils.tracing import histograms as _histograms
from ..utils.tracing import span

_log = get_logger("native_mesh")


def _record_compile(dt: float) -> None:
    """Compile-time attribution for a native SPMD compile: always feeds
    the ``compile_seconds`` histogram (compiles are rare), and attaches a
    ``compile`` event to the active query trace when one listens."""
    _histograms.observe("compile_seconds", dt, engine="native_mesh")
    _obs.add_event("compile", name="native_mesh", dur=dt,
                   engine="native_mesh")


def _trace_native_dispatch(trace, op: str, args_per_dev) -> float:
    """Per-device ``shard`` events (actual marshalled bytes per device)
    before a native replicated execute; returns the dispatch start
    timestamp. Caller records the matching ``mesh_dispatch`` after."""
    for p, dev_args in enumerate(args_per_dev):
        nb = sum(int(getattr(a, "nbytes", 0) or 0) for a in dev_args)
        trace.add("shard", name=f"{op} shard {p}", device=p, bytes=nb,
                  native=True, track=_obs.DEVICE_TRACK_BASE + p)
    return trace.clock()

__all__ = ["executor_for", "NativeMeshExecutor"]

_executors: Dict[str, "NativeMeshExecutor"] = {}
_executors_lock = threading.Lock()
_unavailable_logged = False


def executor_for(mesh) -> Optional["NativeMeshExecutor"]:
    """The process-wide native mesh executor able to span ``mesh``, or
    ``None`` when native mesh routing is off or unavailable.

    Enabled by ``TFT_EXECUTOR=pjrt`` (single-process only: a multi-host
    mesh's shards live in other processes, which the in-process native
    client cannot address — and cross-process native CPU collectives are
    not buildable from this environment's libtensorflow wheel, whose
    headers ship only ``in_process_collectives``; no Gloo/MPI backend.
    Multi-process meshes therefore execute via jax's distributed
    runtime, by construction, not omission). The native client needs at least as many
    devices as the mesh: ``TFT_PJRT_MESH_BACKEND`` overrides the spec;
    by default a ``cpu`` backend is widened to ``cpu:<n_devices>`` and a
    plugin backend is used as-is (its device count is the plugin's).
    """
    global _unavailable_logged
    if os.environ.get("TFT_EXECUTOR") != "pjrt":
        return None
    import jax

    if jax.process_count() > 1:
        return None
    n = mesh.num_devices
    spec = os.environ.get("TFT_PJRT_MESH_BACKEND")
    if spec is None:
        base = os.environ.get("TFT_PJRT_BACKEND", "cpu")
        spec = f"cpu:{n}" if base == "cpu" or base.startswith("cpu:") \
            else base
    with _executors_lock:
        if spec in _executors:  # including the failed-once None sentinel
            ex = _executors[spec]
        else:
            try:
                ex = NativeMeshExecutor(spec)
            except Exception as e:
                if not _unavailable_logged:
                    _log.warning(
                        "TFT_EXECUTOR=pjrt mesh routing unavailable (%s); "
                        "mesh ops use the in-process jax path", e)
                    _unavailable_logged = True
                ex = None
            _executors[spec] = ex
    if ex is None or ex.client.device_count < n:
        return None
    return ex


def _shardy_off():
    """Context: lower with GSPMD sharding annotations (``mhlo.sharding``)
    instead of the shardy dialect — the native core's StableHLO→HLO
    conversion + SPMD partitioner consume the GSPMD form."""
    import contextlib
    import jax

    @contextlib.contextmanager
    def ctx():
        old = jax.config.jax_use_shardy_partitioner
        jax.config.update("jax_use_shardy_partitioner", False)
        try:
            yield
        finally:
            jax.config.update("jax_use_shardy_partitioner", old)

    return ctx()


_NOT_ROUTABLE = object()  # cached verdict: this program can't go native


class NativeMeshExecutor:
    """GSPMD mesh programs compiled + executed by the C++ PJRT core."""

    CACHE_CAP = 32        # dreduce programs (executor-wide)
    COMP_CACHE_CAP = 8    # dmap signatures per live Computation

    def __init__(self, backend: str):
        from ..native_pjrt import PjrtCoreClient

        self.client = PjrtCoreClient(backend)
        self._cache: "OrderedDict[tuple, object]" = OrderedDict()
        self._lock = threading.Lock()
        self.compile_count = 0
        self.dispatch_count = 0

    def _cache_put(self, cache: OrderedDict, key, entry, cap: int):
        """Insert under self._lock with LRU eviction. Evicted executables
        are NOT closed here: another thread may have read the entry and be
        mid-execute outside the lock; dropping the cache reference lets
        the executable's own ``__del__`` free the native handle once the
        last reference (including that thread's) is gone."""
        cache[key] = entry
        cache.move_to_end(key)
        while len(cache) > cap:
            cache.popitem(last=False)

    # -- shard marshalling -------------------------------------------------
    @staticmethod
    def _supported(np_dtype) -> bool:
        from ..native_pjrt import _CODES

        return np.dtype(np_dtype) in _CODES

    @staticmethod
    def _split(host: np.ndarray, sharding, dev_order) -> List[np.ndarray]:
        imap = sharding.devices_indices_map(host.shape)
        return [np.ascontiguousarray(host[imap[d]]) for d in dev_order]

    @staticmethod
    def _assemble(shards: List[np.ndarray], sharding, shape, dtype,
                  dev_order) -> np.ndarray:
        if getattr(sharding, "is_fully_replicated", False):
            return shards[0]  # every device holds the whole array
        out = np.empty(shape, dtype)
        imap = sharding.devices_indices_map(shape)
        for piece, d in zip(shards, dev_order):
            out[imap[d]] = piece
        return out

    # -- dmap --------------------------------------------------------------
    def dmap(self, comp, dist) -> Optional[Dict[str, np.ndarray]]:
        """Run a row-aligned map natively; global padded outputs as numpy.

        Returns ``None`` when this program cannot take the native route
        (non-row-aligned outputs, unsupported dtypes) — the caller falls
        back to the jax dispatch.
        """
        import jax

        mesh = dist.mesh
        n_total = mesh.num_devices
        in_names = list(comp.input_names)
        out_names = [s.name for s in comp.outputs]
        host_in = {n: np.asarray(dist.columns[n]) for n in in_names}
        key = ("dmap", mesh.mesh, n_total,
               tuple((n, host_in[n].shape, str(host_in[n].dtype))
                     for n in in_names))
        # cached ON the computation (the _tft_jitted pattern): entries die
        # with it, so id() recycling can never alias two programs. The
        # entry stores the output specs with the executable, so cache hits
        # skip retracing (no per-call jax.eval_shape); a NOT_ROUTABLE
        # verdict is cached too, so un-routable programs fall back to jax
        # without re-tracing every dispatch.
        with self._lock:
            per_comp = getattr(comp, "_tft_native_mesh_cache", None)
            if per_comp is None:
                per_comp = comp._tft_native_mesh_cache = OrderedDict()
            entry = per_comp.get(key)
            if entry is not None:
                per_comp.move_to_end(key)
        if entry is _NOT_ROUTABLE:
            return None
        in_shardings = [mesh.row_sharding(host_in[n].ndim)
                        for n in in_names]
        if entry is None:
            def flat_fn(*args):
                out = comp.fn(dict(zip(in_names, args)))
                return tuple(out[n] for n in out_names)

            avals = [jax.ShapeDtypeStruct(
                host_in[n].shape, host_in[n].dtype, sharding=s)
                for n, s in zip(in_names, in_shardings)]
            routable = all(self._supported(a.dtype)
                           for a in host_in.values())
            out_avals = out_shardings = None
            if routable:
                out_avals = jax.eval_shape(flat_fn, *avals)
                padded = dist.padded_rows
                routable = all(
                    o.shape and o.shape[0] == padded
                    and self._supported(o.dtype) for o in out_avals)
            if not routable:
                with self._lock:
                    self._cache_put(per_comp, key, _NOT_ROUTABLE,
                                    self.COMP_CACHE_CAP)
                return None
            out_shardings = [mesh.row_sharding(len(o.shape))
                             for o in out_avals]
            with self._lock:
                entry = per_comp.get(key)
                if entry is None or entry is _NOT_ROUTABLE:
                    t_c = time.perf_counter()
                    with _shardy_off():
                        text = jax.jit(
                            flat_fn, in_shardings=in_shardings,
                            out_shardings=tuple(out_shardings),
                        ).lower(*avals).as_text().encode()
                    exe = self.client.compile_spmd(text, n_total)
                    _record_compile(time.perf_counter() - t_c)
                    entry = (exe, out_avals, out_shardings)
                    self._cache_put(per_comp, key, entry,
                                    self.COMP_CACHE_CAP)
                    self.compile_count += 1
        exe, out_avals, out_shardings = entry
        dev_order = list(mesh.mesh.devices.flat)
        per_arg = [self._split(host_in[n], s, dev_order)
                   for n, s in zip(in_names, in_shardings)]
        args_per_dev = [[shards[p] for shards in per_arg]
                        for p in range(n_total)]
        trace = _obs.current_trace()
        t0 = (_trace_native_dispatch(trace, "dmap_blocks", args_per_dev)
              if trace is not None else 0.0)
        with span("native_mesh.dmap_dispatch"):
            outs = exe.execute(args_per_dev)
        if trace is not None:
            trace.add("mesh_dispatch", name="dmap_blocks", ts=t0,
                      dur=max(trace.clock() - t0, 0.0), native=True)
        self.dispatch_count += 1
        result = {}
        for i, (nm, oav, osh) in enumerate(
                zip(out_names, out_avals, out_shardings)):
            result[nm] = self._assemble(
                [outs[p][i] for p in range(n_total)], osh, oav.shape,
                oav.dtype, dev_order)
        return result

    # -- generic sharded program -------------------------------------------
    def _entry_for(self, cache_key, build_fn, host_args, in_shardings,
                   out_shardings, mesh, owner=None, out_check=None):
        """Compile-or-reuse the GSPMD program (shared by the one-shot and
        resident-loop dispatch paths); ``None`` when not routable."""
        import jax

        n_total = mesh.num_devices
        with self._lock:
            if owner is not None:
                cache = getattr(owner, "_tft_native_mesh_cache", None)
                if cache is None:
                    cache = owner._tft_native_mesh_cache = OrderedDict()
                cap = self.COMP_CACHE_CAP
            else:
                cache = self._cache
                cap = self.CACHE_CAP
            entry = cache.get(cache_key)
            if entry is not None:
                cache.move_to_end(cache_key)
        if entry is _NOT_ROUTABLE:
            return None
        if entry is None:
            fn = build_fn()
            avals = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
                     for a, s in zip(host_args, in_shardings)]
            routable = all(self._supported(a.dtype) for a in host_args)
            out_avals = out_sh = None
            if routable:
                out_avals = jax.eval_shape(fn, *avals)
                if not isinstance(out_avals, (list, tuple)):
                    out_avals = (out_avals,)
                routable = all(self._supported(o.dtype)
                               for o in out_avals)
                if routable and out_check is not None:
                    routable = bool(out_check(out_avals))
                if routable:
                    out_sh = (out_shardings(out_avals)
                              if callable(out_shardings)
                              else out_shardings)
            if not routable:
                with self._lock:
                    self._cache_put(cache, cache_key, _NOT_ROUTABLE, cap)
                return None
            with self._lock:
                entry = cache.get(cache_key)
                if entry is None or entry is _NOT_ROUTABLE:
                    try:
                        t_c = time.perf_counter()
                        with _shardy_off():
                            # out_shardings FORCED: ops that post-process
                            # a shard_map result (e.g. dsort's global
                            # slice) would otherwise let GSPMD pick
                            # replicated outputs, and the per-device
                            # buffers would not be the shards the
                            # assembler expects
                            text = jax.jit(
                                fn, out_shardings=tuple(out_sh),
                            ).lower(*avals).as_text().encode()
                        exe = self.client.compile_spmd(text, n_total)
                        _record_compile(time.perf_counter() - t_c)
                    except Exception:
                        # latch: don't re-trace/re-lower on every call
                        # just to fail again
                        self._cache_put(cache, cache_key, _NOT_ROUTABLE,
                                        cap)
                        raise
                    entry = (exe, out_avals, out_sh)
                    self._cache_put(cache, cache_key, entry, cap)
                    self.compile_count += 1
        return entry

    def run_sharded(self, cache_key, build_fn, host_args, in_shardings,
                    out_shardings, mesh, owner=None, out_check=None):
        """Compile-or-reuse ONE GSPMD program and execute it natively.

        ``build_fn() -> traceable fn`` over positional args matching
        ``host_args``/``in_shardings``; ``out_shardings`` is a list (or a
        callable of the out avals returning one). ``out_check(out_avals)
        -> bool`` vetoes routing from the abstract output shapes (e.g.
        dmap's row-alignment requirement). Results come back as GLOBAL
        numpy arrays assembled from the per-device shards. Returns
        ``None`` when not routable — the verdict (including a FAILED
        compile: a backend without a lowering for some collective must
        not pay a full re-trace per call before the jax fallback) is
        cached. ``owner`` (e.g. a live Computation) keys the cache on the
        owning object instead of the executor-wide LRU, dying with it.
        """
        n_total = mesh.num_devices
        host_args = [np.asarray(a) for a in host_args]
        entry = self._entry_for(cache_key, build_fn, host_args,
                                in_shardings, out_shardings, mesh,
                                owner=owner, out_check=out_check)
        if entry is None:
            return None
        exe, out_avals, out_sh = entry
        dev_order = list(mesh.mesh.devices.flat)
        per_arg = [self._split(a, s, dev_order)
                   for a, s in zip(host_args, in_shardings)]
        args_per_dev = [[shards[p] for shards in per_arg]
                        for p in range(n_total)]
        trace = _obs.current_trace()
        op = str(cache_key[0]) if isinstance(cache_key, tuple) \
            and cache_key else "run_sharded"
        t0 = (_trace_native_dispatch(trace, op, args_per_dev)
              if trace is not None else 0.0)
        with span("native_mesh.sharded_dispatch"):
            outs = exe.execute(args_per_dev)
        result = [self._assemble([outs[p][i] for p in range(n_total)],
                                 sh, oav.shape, oav.dtype, dev_order)
                  for i, (oav, sh) in enumerate(zip(out_avals, out_sh))]
        if trace is not None:
            trace.add("mesh_dispatch", name=op, ts=t0,
                      dur=max(trace.clock() - t0, 0.0), native=True)
        self.dispatch_count += 1  # after assembly: failures don't count
        return result

    def run_sharded_loop(self, cache_key, build_fn, host_args,
                         in_shardings, out_shardings, mesh, iters: int,
                         owner=None):
        """Iterate ONE GSPMD program with DEVICE-RESIDENT loop state.

        The shards upload once, each dispatch's output buffers feed the
        next dispatch directly (``PjrtDeviceBuffer`` handles — HBM on a
        TPU host, no per-call host marshalling), and only the final
        iteration's results come back as global numpy arrays. Requires
        the program's outputs to match its inputs positionally
        (shape + dtype) — the fixed-point/loop-state shape every
        iterative workload (k-means, logreg) has. Returns ``None`` when
        the program is not natively routable.
        """
        if iters < 1:
            raise ValueError(f"iters must be >= 1, got {iters}")
        n_total = mesh.num_devices
        host_args = [np.asarray(a) for a in host_args]
        entry = self._entry_for(cache_key, build_fn, host_args,
                                in_shardings, out_shardings, mesh,
                                owner=owner)
        if entry is None:
            return None
        exe, out_avals, out_sh = entry
        # shardings must match too: each replica's output buffer feeds
        # the same input slot, so a rows-sharded input produced as a
        # columns-sharded output would silently permute the loop state
        mismatch = [
            i for i, (a, o, ish, osh)
            in enumerate(zip(host_args, out_avals, in_shardings, out_sh))
            if a.shape != o.shape or a.dtype != o.dtype or ish != osh]
        if len(host_args) != len(out_avals) or mismatch:
            raise ValueError(
                "run_sharded_loop needs outputs matching inputs "
                f"positionally (shape, dtype AND sharding); mismatched "
                f"positions: {mismatch}")
        dev_order = list(mesh.mesh.devices.flat)
        per_arg = [self._split(a, s, dev_order)
                   for a, s in zip(host_args, in_shardings)]
        args = [[shards[p] for shards in per_arg] for p in range(n_total)]
        with span("native_mesh.resident_loop"):
            for _ in range(iters - 1):
                args = exe.execute(args, keep_outputs=True)
                self.dispatch_count += 1
            outs = exe.execute(args, keep_outputs=False)
            self.dispatch_count += 1
        return [self._assemble([outs[p][i] for p in range(n_total)],
                               sh, oav.shape, oav.dtype, dev_order)
                for i, (oav, sh) in enumerate(zip(out_avals, out_sh))]

    # -- collective reduce -------------------------------------------------
    def dreduce_collective(self, shard_fn, in_specs, names, dist,
                           nv_host: np.ndarray, cache_key
                           ) -> Optional[List[np.ndarray]]:
        """Run the collective-reduce shard program natively.

        ``shard_fn``/``in_specs`` are the SAME per-shard function and
        specs the jax path wraps in ``shard_map`` — one source of truth
        for masking/combiner semantics. ``cache_key`` is the caller's
        stable program key (the ``_collective_cache`` key: mesh + columns
        + combiners + shapes). Outputs are replicated (one numpy array
        per reduced column).
        """
        from jax import shard_map
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = dist.mesh
        in_shardings = [NamedSharding(mesh.mesh, s) for s in in_specs]
        host_args = [nv_host.astype(np.int32)] + [dist.columns[n]
                                                 for n in names]

        def build():
            return shard_map(shard_fn, mesh=mesh.mesh,
                             in_specs=tuple(in_specs),
                             out_specs=tuple(P() for _ in names))

        out_shardings = [NamedSharding(mesh.mesh, P()) for _ in names]
        return self.run_sharded(("dreduce", cache_key), build, host_args,
                                in_shardings, out_shardings, mesh)
