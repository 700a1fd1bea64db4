"""ctypes binding to the C++ PJRT execution core (``native/libtfrpjrt.so``).

The reference bottoms out every graph execution in C++ — a libtensorflow
``Session.Run`` reached through JNI (``TensorFlowOps.scala:46-64``,
``DebugRowOps.scala:776-788``). This is the TPU-native equivalent: the
driver (Python) authors and lowers a computation to StableHLO, and the
native core compiles + executes it against XLA **in C++** — XLA:CPU linked
in-process for local runs, or any PJRT C API plugin (``libtpu.so``) on TPU
hosts. Results are written straight into caller-allocated numpy arrays
(the ``tensor_data().asBuffer()`` zero-copy read analogue,
``DataOps.scala:373``).

Routing: :class:`PjrtBlockExecutor` drops into the engine anywhere a
:class:`~tensorframes_tpu.engine.executor.BlockExecutor` is accepted, or
set ``TFT_EXECUTOR=pjrt`` to make it the process default. The jax
in-process path remains the default and the fallback.
"""

from __future__ import annotations

import ctypes
import os
import threading
import time
from typing import Dict, Mapping, Optional, Tuple

import numpy as np

from . import dtypes as _dt
from .computation import Computation
from .observability import events as _obs
from .utils.logging import get_logger
from .utils.tracing import counters as _counters
from .utils.tracing import enabled as _tracing_enabled
from .utils.tracing import histograms as _histograms

__all__ = ["available", "PjrtCoreClient", "PjrtBlockExecutor",
           "PjrtDeviceBuffer"]

_log = get_logger("native_pjrt")

# tfr_dtype codes from native/tfrpjrt.h
_CODES = {
    np.dtype(np.float32): 1,
    np.dtype(np.float64): 2,
    np.dtype(np.int32): 3,
    np.dtype(np.int64): 4,
    np.dtype(np.bool_): 6,
}
_NP_FROM_CODE = {1: np.dtype(np.float32), 2: np.dtype(np.float64),
                 3: np.dtype(np.int32), 4: np.dtype(np.int64),
                 6: np.dtype(np.bool_)}
_BF16_CODE = 5

_lib: Optional[ctypes.CDLL] = None
_load_attempted = False
_ERRLEN = 4096


def _find_library() -> Optional[str]:
    cand = os.environ.get("TFT_PJRT_LIB")
    if cand and os.path.exists(cand):
        return cand
    here = os.path.dirname(os.path.abspath(__file__))
    for rel in (os.path.join(here, "..", "native", "libtfrpjrt.so"),
                os.path.join(here, "libtfrpjrt.so")):
        p = os.path.abspath(rel)
        if os.path.exists(p):
            return p
    return None


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    if _load_attempted:
        return _lib
    _load_attempted = True
    if os.environ.get("TFT_DISABLE_NATIVE"):
        return None
    path = _find_library()
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(path)
    except OSError as e:
        _log.warning("libtfrpjrt.so failed to load: %s", e)
        return None
    vp = ctypes.c_void_p
    ci = ctypes.c_int
    cll = ctypes.c_longlong
    lib.tfr_pjrt_client_create.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                           ci]
    lib.tfr_pjrt_client_create.restype = vp
    lib.tfr_pjrt_client_destroy.argtypes = [vp]
    lib.tfr_pjrt_client_device_count.argtypes = [vp]
    lib.tfr_pjrt_client_device_count.restype = ci
    lib.tfr_pjrt_client_platform.argtypes = [vp, ctypes.c_char_p, ci]
    lib.tfr_pjrt_client_platform.restype = ci
    lib.tfr_pjrt_compile.argtypes = [vp, ctypes.c_char_p, ctypes.c_long,
                                     ctypes.c_char_p, ci]
    lib.tfr_pjrt_compile.restype = vp
    lib.tfr_pjrt_compile_dynamic.argtypes = [
        vp, ctypes.c_char_p, ctypes.c_long, ci, ctypes.c_char_p,
        ctypes.c_char_p, ci, ctypes.POINTER(ci), ctypes.POINTER(ci),
        ctypes.POINTER(cll), ctypes.c_char_p, ci]
    lib.tfr_pjrt_compile_dynamic.restype = vp
    lib.tfr_pjrt_compile_dynamic_n.argtypes = [
        vp, ctypes.c_char_p, ctypes.c_long, ci, ctypes.c_char_p,
        ctypes.c_char_p, ci, ctypes.POINTER(ci), ctypes.POINTER(ci),
        ctypes.POINTER(cll), ci, ctypes.c_char_p, ci]
    lib.tfr_pjrt_compile_dynamic_n.restype = vp
    lib.tfr_pjrt_compile_n.argtypes = [vp, ctypes.c_char_p, ctypes.c_long,
                                       ci, ctypes.c_char_p, ci]
    lib.tfr_pjrt_compile_n.restype = vp
    lib.tfr_pjrt_compile_spmd.argtypes = [vp, ctypes.c_char_p,
                                          ctypes.c_long, ci,
                                          ctypes.c_char_p, ci]
    lib.tfr_pjrt_compile_spmd.restype = vp
    lib.tfr_pjrt_execute_replicated.argtypes = [
        vp, vp, ci, ci, ctypes.POINTER(ci), ctypes.POINTER(ci),
        ctypes.POINTER(cll), ctypes.POINTER(vp), ctypes.c_char_p, ci]
    lib.tfr_pjrt_execute_replicated.restype = vp
    lib.tfr_pjrt_exe_destroy.argtypes = [vp]
    lib.tfr_pjrt_execute.argtypes = [vp, vp, ci, ctypes.POINTER(ci),
                                     ctypes.POINTER(ci),
                                     ctypes.POINTER(cll),
                                     ctypes.POINTER(vp), ctypes.c_char_p, ci]
    lib.tfr_pjrt_execute.restype = vp
    lib.tfr_pjrt_results_count.argtypes = [vp]
    lib.tfr_pjrt_results_count.restype = ci
    lib.tfr_pjrt_result_meta.argtypes = [vp, ci, ctypes.POINTER(ci),
                                         ctypes.POINTER(ci),
                                         ctypes.POINTER(cll)]
    lib.tfr_pjrt_result_meta.restype = ci
    lib.tfr_pjrt_result_read.argtypes = [vp, ci, vp, cll, ctypes.c_char_p,
                                         ci]
    lib.tfr_pjrt_result_read.restype = ci
    lib.tfr_pjrt_results_destroy.argtypes = [vp]
    try:
        lib.tfr_pjrt_result_release_buffer.argtypes = [vp, ci]
        lib.tfr_pjrt_result_release_buffer.restype = vp
        lib.tfr_pjrt_buffer_meta.argtypes = [vp, ctypes.POINTER(ci),
                                             ctypes.POINTER(ci),
                                             ctypes.POINTER(cll)]
        lib.tfr_pjrt_buffer_meta.restype = ci
        lib.tfr_pjrt_buffer_destroy.argtypes = [vp]
        lib.tfr_pjrt_execute_replicated_mixed.argtypes = [
            vp, vp, ci, ci, ctypes.POINTER(ci), ctypes.POINTER(ci),
            ctypes.POINTER(cll), ctypes.POINTER(vp), ctypes.POINTER(vp),
            ctypes.c_char_p, ci]
        lib.tfr_pjrt_execute_replicated_mixed.restype = vp
        lib._tfr_has_resident = True
    except AttributeError:
        # an older libtfrpjrt.so without the device-resident surface;
        # execute(keep_outputs=...) / device-buffer args will raise
        lib._tfr_has_resident = False
    _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


class PjrtCoreError(RuntimeError):
    pass


def _dtype_code(dt: np.dtype) -> int:
    code = _CODES.get(dt)
    if code is None:
        if dt == _dt.bfloat16.np_storage:
            return _BF16_CODE
        raise PjrtCoreError(f"unsupported input dtype {dt}")
    return code


def _read_results(lib, res) -> list:
    """Decode every result buffer of a tfr_pjrt_results into numpy
    (shared by the single and replicated execute paths)."""
    err = ctypes.create_string_buffer(_ERRLEN)
    outs = []
    for i in range(lib.tfr_pjrt_results_count(res)):
        dt = ctypes.c_int()
        nd = ctypes.c_int()
        odims = (ctypes.c_longlong * 8)()
        if lib.tfr_pjrt_result_meta(res, i, ctypes.byref(dt),
                                    ctypes.byref(nd), odims):
            raise PjrtCoreError(f"result {i}: meta query failed")
        shape = tuple(odims[k] for k in range(nd.value))
        np_dt = (_dt.bfloat16.np_storage if dt.value == _BF16_CODE
                 else _NP_FROM_CODE.get(dt.value))
        if np_dt is None:
            raise PjrtCoreError(
                f"result {i}: unsupported dtype code {dt.value}")
        out = np.empty(shape, np_dt)
        if lib.tfr_pjrt_result_read(
                res, i, out.ctypes.data_as(ctypes.c_void_p),
                out.nbytes, err, _ERRLEN):
            raise PjrtCoreError(
                f"result {i}: {err.value.decode(errors='replace')}")
        outs.append(out)
    return outs


def _device_views(comp: "Computation", arrays: Mapping) -> Dict:
    """Inputs as contiguous device-dtype arrays (shared input prep)."""
    dev = {}
    for spec in comp.inputs:
        a = np.ascontiguousarray(arrays[spec.name])
        dd = _dt.device_dtype(spec.dtype)
        if a.dtype != dd:
            from . import native as _native
            a = _native.convert(a, dd)
        dev[spec.name] = a
    return dev


def _to_storage(comp: "Computation", outs) -> Dict:
    """Zip outputs back to names + storage dtypes (shared output conv)."""
    rec = {}
    for spec, a in zip(comp.outputs, outs):
        storage = spec.dtype.np_storage
        if a.dtype != storage and spec.dtype is not _dt.bfloat16:
            from . import native as _native
            a = _native.convert(a, storage)
        rec[spec.name] = a
    return rec


class PjrtCoreClient:
    """A native PJRT client: the per-host analogue of the reference's
    per-executor TF C++ session factory (``TensorFlowOps.withSession``).

    ``backend``: ``"cpu"``/``"cpu:<n>"`` for in-process XLA:CPU, or
    ``"plugin:<path.so>"`` for a PJRT C API plugin (TPU: libtpu.so).
    """

    def __init__(self, backend: str = "cpu"):
        lib = _load()
        if lib is None:
            raise PjrtCoreError(
                "libtfrpjrt.so is not available; build it with "
                "`make -C native pjrt`")
        self._lib = lib
        err = ctypes.create_string_buffer(_ERRLEN)
        self._client = lib.tfr_pjrt_client_create(
            backend.encode(), err, _ERRLEN)
        if not self._client:
            raise PjrtCoreError(
                f"client create failed: {err.value.decode(errors='replace')}")
        self.backend = backend

    @property
    def device_count(self) -> int:
        return self._lib.tfr_pjrt_client_device_count(self._client)

    @property
    def platform(self) -> str:
        buf = ctypes.create_string_buffer(256)
        self._lib.tfr_pjrt_client_platform(self._client, buf, 256)
        return buf.value.decode()

    def compile(self, stablehlo: bytes) -> "PjrtExecutable":
        err = ctypes.create_string_buffer(_ERRLEN)
        h = self._lib.tfr_pjrt_compile(self._client, stablehlo,
                                       len(stablehlo), err, _ERRLEN)
        if not h:
            raise PjrtCoreError(
                f"compile failed: {err.value.decode(errors='replace')}")
        return PjrtExecutable(self, h)

    def compile_dynamic(self, module: bytes, cc_version: int, platforms,
                        arg_dtypes, arg_shapes, n_replicas: int = 1):
        """Compile a serialized dynamic-shape module (jax.export wire
        format) at concrete shapes: refinement happens in the native core,
        no jax involved. ``arg_dtypes``: numpy dtypes; ``arg_shapes``:
        tuples. ``n_replicas > 1`` compiles SPMD-replicated and returns a
        :class:`PjrtReplicatedExecutable`."""
        n = len(arg_dtypes)
        dtypes = (ctypes.c_int * n)()
        ndims = (ctypes.c_int * n)()
        flat = []
        for i, (dt, shp) in enumerate(zip(arg_dtypes, arg_shapes)):
            dtypes[i] = _dtype_code(np.dtype(dt))
            ndims[i] = len(shp)
            flat.extend(shp)
        dims = (ctypes.c_longlong * max(1, len(flat)))(*flat)
        select = self.platform
        if select not in platforms and platforms:
            raise PjrtCoreError(
                f"computation was lowered for {platforms}, not for this "
                f"client's platform {select!r}")
        err = ctypes.create_string_buffer(_ERRLEN)
        h = self._lib.tfr_pjrt_compile_dynamic_n(
            self._client, module, len(module), cc_version,
            ",".join(platforms).encode(), select.encode(), n, dtypes,
            ndims, dims, n_replicas, err, _ERRLEN)
        if not h:
            raise PjrtCoreError(
                f"dynamic compile failed: "
                f"{err.value.decode(errors='replace')}")
        if n_replicas > 1:
            return PjrtReplicatedExecutable(self, h, n_replicas)
        return PjrtExecutable(self, h)

    def compile_replicated(self, stablehlo: bytes,
                           n_replicas: int) -> "PjrtReplicatedExecutable":
        """Compile for ``n_replicas`` devices (SPMD replication); run all
        replicas in one native call via the returned executable."""
        err = ctypes.create_string_buffer(_ERRLEN)
        h = self._lib.tfr_pjrt_compile_n(self._client, stablehlo,
                                         len(stablehlo), n_replicas, err,
                                         _ERRLEN)
        if not h:
            raise PjrtCoreError(
                f"replicated compile failed: "
                f"{err.value.decode(errors='replace')}")
        return PjrtReplicatedExecutable(self, h, n_replicas)

    def compile_spmd(self, stablehlo: bytes,
                     n_partitions: int) -> "PjrtReplicatedExecutable":
        """GSPMD-partitioned compile: ONE logical program spanning
        ``n_partitions`` devices. ``stablehlo`` is a jax mesh lowering
        (GSPMD flavor, ``mhlo.sharding``-annotated global shapes); XLA's
        SPMD partitioner inside the native core derives the per-device
        program and its collectives. Execute with per-device SHARDS
        (device-major, equal shapes); sharded outputs come back as
        per-device shards, replicated outputs as one copy per device."""
        err = ctypes.create_string_buffer(_ERRLEN)
        h = self._lib.tfr_pjrt_compile_spmd(self._client, stablehlo,
                                            len(stablehlo), n_partitions,
                                            err, _ERRLEN)
        if not h:
            raise PjrtCoreError(
                f"spmd compile failed: "
                f"{err.value.decode(errors='replace')}")
        return PjrtReplicatedExecutable(self, h, n_partitions)

    def close(self):
        if self._client:
            self._lib.tfr_pjrt_client_destroy(self._client)
            self._client = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class PjrtExecutable:
    """A compiled program held by the native core."""

    def __init__(self, client: PjrtCoreClient, handle):
        self._client = client
        self._h = handle

    def execute(self, arrays) -> list:
        """Run on dense row-major host arrays; returns numpy arrays."""
        lib = self._client._lib
        n = len(arrays)
        arrays = [np.ascontiguousarray(a) for a in arrays]
        dtypes = (ctypes.c_int * n)()
        ndims = (ctypes.c_int * n)()
        flat_dims = []
        datas = (ctypes.c_void_p * n)()
        for i, a in enumerate(arrays):
            dtypes[i] = _dtype_code(a.dtype)
            ndims[i] = a.ndim
            flat_dims.extend(a.shape)
            datas[i] = a.ctypes.data_as(ctypes.c_void_p)
        dims = (ctypes.c_longlong * max(1, len(flat_dims)))(*flat_dims)
        err = ctypes.create_string_buffer(_ERRLEN)
        res = lib.tfr_pjrt_execute(self._client._client, self._h, n, dtypes,
                                   ndims, dims, datas, err, _ERRLEN)
        if not res:
            raise PjrtCoreError(
                f"execute failed: {err.value.decode(errors='replace')}")
        try:
            return _read_results(lib, res)
        finally:
            lib.tfr_pjrt_results_destroy(res)

    def close(self):
        if self._h:
            self._client._lib.tfr_pjrt_exe_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class PjrtDeviceBuffer:
    """A DEVICE-RESIDENT buffer detached from a replicated result set.

    Holds device (HBM) memory owned by the native core; pass it back as
    an input slot of :meth:`PjrtReplicatedExecutable.execute` to chain
    dispatches without the per-call host round-trip (the residency the
    jax path gets from ``jax.Array``). The buffer lives on the replica
    device that produced it — reuse it only in the same replica slot.
    """

    def __init__(self, client: PjrtCoreClient, handle, dtype: np.dtype,
                 shape: Tuple[int, ...]):
        self._client = client
        self._h = handle
        self.dtype = np.dtype(dtype)
        self.shape = tuple(shape)

    def close(self):
        if self._h:
            self._client._lib.tfr_pjrt_buffer_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class PjrtReplicatedExecutable:
    """A program compiled for N devices; one ``execute`` call runs every
    replica in parallel inside the native core — the in-process analogue
    of the reference's fleet of executor sessions each running the same
    shipped graph on its partition (``DebugRowOps.scala:372-386``)."""

    def __init__(self, client: PjrtCoreClient, handle, n_replicas: int):
        self._client = client
        self._h = handle
        self.n_replicas = n_replicas

    def execute(self, per_replica_args, keep_outputs: bool = False) -> list:
        """``per_replica_args``: list of ``n_replicas`` argument lists
        (equal shapes/dtypes across replicas — XLA's static world). An
        argument may be a :class:`PjrtDeviceBuffer` (device-resident, no
        host upload for that slot). Returns one output list per replica —
        numpy arrays, or :class:`PjrtDeviceBuffer` handles when
        ``keep_outputs`` (no host download; feed them back in)."""
        lib = self._client._lib
        if len(per_replica_args) != self.n_replicas:
            raise PjrtCoreError(
                f"expected {self.n_replicas} replica argument lists, got "
                f"{len(per_replica_args)}")
        nargs = len(per_replica_args[0])
        views = [[a if isinstance(a, PjrtDeviceBuffer)
                  else np.ascontiguousarray(a) for a in rep]
                 for rep in per_replica_args]
        first = views[0]
        has_dev = any(isinstance(a, PjrtDeviceBuffer)
                      for rep in views for a in rep)
        if (has_dev or keep_outputs) and \
                not getattr(lib, "_tfr_has_resident", False):
            raise PjrtCoreError(
                "this libtfrpjrt.so predates device-resident buffers; "
                "rebuild with make -C native pjrt")
        dtypes = (ctypes.c_int * nargs)()
        ndims = (ctypes.c_int * nargs)()
        flat_dims = []
        for i, a in enumerate(first):
            dtypes[i] = _dtype_code(a.dtype)
            ndims[i] = len(a.shape)
            flat_dims.extend(a.shape)
        for rep in views[1:]:
            if len(rep) != nargs or any(
                    b.shape != a.shape or b.dtype != a.dtype
                    for a, b in zip(first, rep)):
                raise PjrtCoreError(
                    "replica argument lists must share shapes and dtypes")
        dims = (ctypes.c_longlong * max(1, len(flat_dims)))(*flat_dims)
        n_total = self.n_replicas * nargs
        datas = (ctypes.c_void_p * n_total)()
        err = ctypes.create_string_buffer(_ERRLEN)
        if has_dev or keep_outputs:
            devs = (ctypes.c_void_p * n_total)()
            for r, rep in enumerate(views):
                for i, a in enumerate(rep):
                    if isinstance(a, PjrtDeviceBuffer):
                        if not a._h:
                            raise PjrtCoreError(
                                f"replica {r} arg {i}: device buffer "
                                f"already closed")
                        devs[r * nargs + i] = a._h
                    else:
                        datas[r * nargs + i] = a.ctypes.data_as(
                            ctypes.c_void_p)
            res = lib.tfr_pjrt_execute_replicated_mixed(
                self._client._client, self._h, self.n_replicas, nargs,
                dtypes, ndims, dims, datas, devs, err, _ERRLEN)
        else:
            for r, rep in enumerate(views):
                for i, a in enumerate(rep):
                    datas[r * nargs + i] = a.ctypes.data_as(ctypes.c_void_p)
            res = lib.tfr_pjrt_execute_replicated(
                self._client._client, self._h, self.n_replicas, nargs,
                dtypes, ndims, dims, datas, err, _ERRLEN)
        if not res:
            raise PjrtCoreError(
                f"replicated execute failed: "
                f"{err.value.decode(errors='replace')}")
        try:
            if keep_outputs:
                outs = self._release_all(lib, res)
            else:
                outs = _read_results(lib, res)
        finally:
            lib.tfr_pjrt_results_destroy(res)
        per_rep = len(outs) // self.n_replicas
        return [outs[r * per_rep:(r + 1) * per_rep]
                for r in range(self.n_replicas)]

    def _release_all(self, lib, res) -> list:
        """Detach every result as a device-resident buffer handle."""
        outs = []
        for i in range(lib.tfr_pjrt_results_count(res)):
            dt = ctypes.c_int()
            nd = ctypes.c_int()
            odims = (ctypes.c_longlong * 8)()
            if lib.tfr_pjrt_result_meta(res, i, ctypes.byref(dt),
                                        ctypes.byref(nd), odims):
                raise PjrtCoreError(f"result {i}: meta query failed")
            np_dt = (_dt.bfloat16.np_storage if dt.value == _BF16_CODE
                     else _NP_FROM_CODE.get(dt.value))
            if np_dt is None:
                raise PjrtCoreError(
                    f"result {i}: unsupported dtype code {dt.value}")
            h = lib.tfr_pjrt_result_release_buffer(res, i)
            if not h:
                raise PjrtCoreError(f"result {i}: buffer release failed")
            outs.append(PjrtDeviceBuffer(
                self._client, h, np_dt,
                tuple(odims[k] for k in range(nd.value))))
        return outs

    def close(self):
        if self._h:
            self._client._lib.tfr_pjrt_exe_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# Engine integration
# ---------------------------------------------------------------------------

class _PjrtPending:
    """In-flight native dispatch: ``drain()`` joins the worker future.

    The worker already executed through the executor's full resilient
    path, so a failure here re-raises (attributed to this block by the
    pipeline's FIFO drain) rather than re-running.
    """

    __slots__ = ("_future",)

    def __init__(self, future):
        self._future = future

    def drain(self) -> Dict[str, np.ndarray]:
        return self._future.result()


def _lower_stablehlo(comp: Computation, arrays: Mapping[str, np.ndarray],
                     in_names, out_names) -> bytes:
    """Lower a LIVE computation at these concrete shapes to StableHLO text.

    The driver-side authoring step (the reference built a GraphDef with real
    TF in Python, ``core.py:37-40``); jax is used for *tracing only* — the
    compile and every execution happen in the native core. Deserialized
    computations never come through here: their raw dynamic module is
    refined and compiled natively (``PjrtCoreClient.compile_dynamic``), so
    an executing host needs no jax at all.
    """
    import jax

    def flat_fn(*args):
        out = comp.fn(dict(zip(in_names, args)))
        return tuple(out[n] for n in out_names)

    avals = [jax.ShapeDtypeStruct(arrays[n].shape, arrays[n].dtype)
             for n in in_names]
    lowered = jax.jit(flat_fn).lower(*avals)
    text = str(lowered.compiler_ir("stablehlo")).encode()
    if b"?" not in text:
        return text
    # Legacy fallback only: blobs serialized before the raw-module section
    # existed deserialize with symbolic inner dims and no _native_dynamic;
    # refine them through jaxlib if it still exposes the pass. New blobs
    # never reach this (they compile via compile_dynamic, jax-free).
    try:
        from jax._src.lib import _jax as _jaxlib

        return _jaxlib.mlir.refine_polymorphic_shapes(
            text, enable_shape_assertions=True,
            validate_static_shapes=True)
    except (ImportError, AttributeError) as e:
        raise PjrtCoreError(
            "this computation carries symbolic dims but no raw dynamic "
            "module (a pre-native serialized blob) and this jax exposes "
            f"no refinement pass ({e}); re-serialize it with a current "
            "authoring host") from e


class PjrtBlockExecutor:
    """Block executor routing through the native PJRT core.

    Drop-in for :class:`~tensorframes_tpu.engine.executor.BlockExecutor`
    where an ``executor=`` argument is accepted: same ``run`` contract,
    same per-signature compile cache, but compilation and execution happen
    in C++ (per-executor sessions ↔ one native client per executor
    object). No ``pad_rows`` mode: the native path compiles exact shapes.
    """

    def __init__(self, backend: Optional[str] = None):
        import weakref

        backend = backend or os.environ.get("TFT_PJRT_BACKEND", "cpu")
        self.client = PjrtCoreClient(backend)
        self.pad_rows = False
        # weakly keyed by the live Computation (mirrors BlockExecutor):
        # entries die with it, so id() recycling cannot alias programs
        self._cache: "weakref.WeakKeyDictionary[Computation, Dict[Tuple, PjrtExecutable]]" = \
            weakref.WeakKeyDictionary()
        self._lock = threading.Lock()
        self._pool = None  # lazily-built single worker for submit()
        self.compile_count = 0

    def _compiled(self, comp: Computation, dev_arrays: Dict,
                  n_replicas: int = 1):
        """Per-(comp, signature[, replicas]) compile cache. Shipped
        computations (``_native_dynamic``) refine + compile natively;
        live ones lower through jax tracing."""
        in_names = [s.name for s in comp.inputs]
        sig = tuple((n, dev_arrays[n].shape, str(dev_arrays[n].dtype))
                    for n in in_names)
        if n_replicas > 1:
            sig = ("replicated", n_replicas) + sig
        per_comp = self._cache.get(comp)
        exe = None if per_comp is None else per_comp.get(sig)
        if exe is not None:
            if _tracing_enabled():  # hit stats must not lock the fast path
                _counters.inc("compile_cache.hits")
                _obs.add_event("compile_cache", hit=True, native=True)
            return exe
        with self._lock:
            per_comp = self._cache.setdefault(comp, {})
            exe = per_comp.get(sig)
            if exe is not None:
                if _tracing_enabled():
                    _counters.inc("compile_cache.hits")
                    _obs.add_event("compile_cache", hit=True, native=True)
                return exe
            t_c = time.perf_counter()  # native compiles are synchronous
            dyn = getattr(comp, "_native_dynamic", None)
            if dyn:
                exe = self.client.compile_dynamic(
                    dyn["module"], dyn["cc_version"], dyn["platforms"],
                    [dev_arrays[n].dtype for n in in_names],
                    [dev_arrays[n].shape for n in in_names],
                    n_replicas=n_replicas)
            else:
                hlo = _lower_stablehlo(comp, dev_arrays, in_names,
                                       [s.name for s in comp.outputs])
                exe = (self.client.compile_replicated(hlo, n_replicas)
                       if n_replicas > 1 else self.client.compile(hlo))
            dt = time.perf_counter() - t_c
            per_comp[sig] = exe
            self.compile_count += 1
            _counters.inc("compile_cache.misses")
            _histograms.observe("compile_seconds", dt, engine="native")
            _obs.add_event("compile_cache", hit=False, native=True)
            _obs.add_event("compile", name="native", dur=dt,
                           engine="native")
            _log.debug("native compile #%d for %s", self.compile_count,
                       sig)
            return exe

    def run(self, comp: Computation, arrays: Mapping[str, np.ndarray],
            pad_ok: bool = True) -> Dict[str, np.ndarray]:
        del pad_ok  # exact-shape compiles; padding never applies
        from .resilience import default_policy, faults

        in_names = [s.name for s in comp.inputs]
        dev_arrays = _device_views(comp, arrays)

        def attempt():
            faults.check("pjrt_execute")
            exe = self._compiled(comp, dev_arrays)
            outs = exe.execute([dev_arrays[n] for n in in_names])
            return _to_storage(comp, outs)

        # PjrtCoreError carries the PJRT status word (UNAVAILABLE /
        # ABORTED / ...) in its message, which is exactly what the
        # transient classifier keys on
        trace = _obs.current_trace()
        if trace is None:
            return default_policy().call(attempt, op="pjrt.execute")
        t0 = trace.clock()
        out = default_policy().call(attempt, op="pjrt.execute")
        trace.add("dispatch", name="pjrt.execute", ts=t0,
                  dur=trace.clock() - t0)
        return out

    def submit(self, comp: Computation, arrays: Mapping[str, np.ndarray],
               pad_ok: bool = True) -> "_PjrtPending":
        """Submit half for the pipelined engine (``engine/pipeline.py``):
        the native dispatch runs on a dedicated worker thread — the
        ctypes execute call releases the GIL, so the main thread marshals
        the next blocks while C++ computes this one. The worker runs the
        FULL resilient :meth:`run` (retry policy included), so ``drain``
        re-raises a failure instead of re-running it; one worker keeps
        device dispatches serialized like the serial path.
        """
        pool = self._pool
        if pool is None:
            from concurrent.futures import ThreadPoolExecutor
            with self._lock:
                if self._pool is None:
                    self._pool = ThreadPoolExecutor(
                        max_workers=1,
                        thread_name_prefix="tfr-pjrt-submit")
                pool = self._pool
        # wrap_context carries the submitting query's correlation id
        # (contextvars) onto the worker thread, so events the resilient
        # run records over there still attach to the right QueryTrace
        return _PjrtPending(pool.submit(_obs.wrap_context(self.run),
                                        comp, arrays, pad_ok))

    def run_blocks_parallel(self, comp: Computation, blocks,
                            ) -> "list[Dict[str, np.ndarray]]":
        """Run one map computation over MANY blocks in parallel — native
        replicated dispatches in device-count-sized waves when the blocks
        share shapes, else the sequential per-block path.

        The parallel case is the reference's executor fleet in-process:
        every device runs the same compiled program on its own partition,
        one C++ call per wave. Works for shipped (jax-free) computations
        too — the replicated compile goes through the native refinement.
        """
        blocks = list(blocks)
        if not blocks:
            return []
        in_names = [s.name for s in comp.inputs]
        prepared = [_device_views(comp, arrays) for arrays in blocks]
        sig0 = tuple((n, prepared[0][n].shape, str(prepared[0][n].dtype))
                     for n in in_names)
        uniform = all(
            tuple((n, p[n].shape, str(p[n].dtype)) for n in in_names)
            == sig0 for p in prepared[1:])
        wave = min(len(prepared), self.client.device_count)
        if not uniform or wave < 2:
            return [self.run(comp, p, pad_ok=False) for p in prepared]

        results: "list[Dict[str, np.ndarray]]" = []
        i = 0
        # full waves run replicated; the ragged tail (< wave blocks, a
        # different replica count) takes the sequential path rather than
        # paying a second replicated compile
        while len(prepared) - i >= wave:
            exe = self._compiled(comp, prepared[i], n_replicas=wave)
            rep_outs = exe.execute(
                [[p[nm] for nm in in_names]
                 for p in prepared[i:i + wave]])
            results.extend(_to_storage(comp, outs) for outs in rep_outs)
            i += wave
        for p in prepared[i:]:
            results.append(self.run(comp, p, pad_ok=False))
        return results

    def clear(self):
        with self._lock:
            for per_comp in self._cache.values():
                for exe in per_comp.values():
                    exe.close()
            self._cache.clear()
