"""Computation IR: capture, validate, and serialize tensor programs.

This is the TPU-native replacement for the reference's GraphDef pipeline
(``/root/reference/src/main/scala/org/tensorframes/impl/TensorFlowOps.scala``):
where the reference serializes a TF ``GraphDef`` protobuf on the driver,
broadcasts the bytes, and parses them into a C++ session per executor, here a
user computation is a **pure JAX function over named arrays**, captured once
with shape polymorphism (``jax.export.symbolic_shape`` stands in for TF's
``None`` placeholder dims) and serialized as **StableHLO** bytes
(:meth:`Computation.serialize`), which any host can deserialize and compile
with XLA — no graph-parsing session required.

``analyze_graph`` is the analogue of ``TensorFlowOps.analyzeGraph``
(``TensorFlowOps.scala:84-161``): it validates a computation against shape
hints and reports input/output summaries *without executing it*, via
``jax.eval_shape`` (abstract interpretation replaces loading the graph into a
throwaway C++ session).
"""

from __future__ import annotations

import copy
import inspect
import json
import struct
from dataclasses import dataclass
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import export as jax_export

from . import dtypes as _dt
from .shape import Shape, Unknown
from .utils.logging import get_logger

_log = get_logger("computation")

__all__ = [
    "TensorSpec",
    "GraphNodeSummary",
    "Computation",
    "analyze_graph",
]

_MAGIC = b"TFTPU1\x00"


@dataclass(frozen=True)
class TensorSpec:
    """Name + dtype + (possibly unknown) shape of a computation input/output."""

    name: str
    dtype: _dt.DType
    shape: Shape

    def __repr__(self):
        return f"{self.name}:{self.dtype.name}{self.shape!r}"

    def to_json(self) -> dict:
        return {"name": self.name, "dtype": self.dtype.name,
                "shape": list(self.shape.dims)}

    @staticmethod
    def from_json(d: dict) -> "TensorSpec":
        return TensorSpec(d["name"], _dt.by_name(d["dtype"]),
                          Shape(tuple(d["shape"])))


@dataclass(frozen=True)
class GraphNodeSummary:
    """Summary of one computation endpoint — the ``GraphNodeSummary``
    analogue (reference ``TensorFlowOps.scala:183-189``)."""

    name: str
    is_input: bool
    is_output: bool
    dtype: _dt.DType
    shape: Shape

    def __repr__(self):
        kind = "input" if self.is_input else "output"
        return f"[{kind}] {self.name} {self.dtype.name}{self.shape!r}"


def _sym_avals(inputs: Sequence[TensorSpec], share_lead_symbol: bool):
    """Build (possibly symbolic) ShapeDtypeStructs for the input specs.

    All inputs with an Unknown *leading* dim share one symbol when
    ``share_lead_symbol`` — the "rows in this block" dimension is one
    quantity across every column of a block. Other Unknown dims each get a
    fresh symbol.
    """
    scope = jax_export.SymbolicScope()
    lead = None
    fresh = 0
    avals = []
    any_symbolic = False
    for spec in inputs:
        dims = []
        for i, d in enumerate(spec.shape.dims):
            if d == Unknown:
                any_symbolic = True
                if i == 0 and share_lead_symbol:
                    if lead is None:
                        (lead,) = jax_export.symbolic_shape("_n", scope=scope)
                    dims.append(lead)
                else:
                    (s,) = jax_export.symbolic_shape(f"_d{fresh}", scope=scope)
                    fresh += 1
                    dims.append(s)
            else:
                dims.append(d)
        avals.append(jax.ShapeDtypeStruct(
            tuple(dims), _dt.device_dtype(spec.dtype)))
    return avals, any_symbolic


def _shape_from_aval(dims) -> Shape:
    return Shape(tuple(d if isinstance(d, int) else Unknown for d in dims))


def _dtype_from_np(np_dtype) -> _dt.DType:
    s = str(np.dtype(np_dtype)) if str(np_dtype) != "bfloat16" else "bfloat16"
    if s == "bfloat16":
        return _dt.bfloat16
    dt = _dt.from_numpy(np_dtype)
    if not dt.tensor:
        raise ValueError(
            f"Computation outputs must be numeric tensors, got {dt.name}")
    return dt


def _output_framework_dtype(np_dtype, input_specs: Sequence[TensorSpec]) -> _dt.DType:
    """Map an output's device dtype back to a framework dtype.

    On TPU, ``double`` columns compute in f32 (dtypes.device_dtype policy);
    an f32 output must then still be a ``double`` column, or the
    fetch/input same-dtype contract would break on TPU only. Rule: if some
    input's device dtype equals the output's device dtype, the output
    inherits the widest such input's framework dtype; otherwise the direct
    numpy mapping applies.
    """
    np_dtype = np.dtype(np_dtype) if str(np_dtype) != "bfloat16" else np_dtype
    cand = None
    for s in input_specs:
        if _dt.device_dtype(s.dtype) == np_dtype:
            if cand is None or s.dtype.priority > cand.priority:
                cand = s.dtype
    return cand if cand is not None else _dtype_from_np(np_dtype)


class Computation:
    """A captured tensor program: ordered named inputs -> named outputs.

    Outputs are canonically **sorted by name**, matching the reference
    engine's output-column ordering contract (``DebugRowOps.scala:344-355``).

    ``opaque`` marks a computation that wraps an exported or foreign
    module (:meth:`from_stablehlo`, :meth:`deserialize`): it runs through
    ``jax.export``'s ``call``, which has no batching rule, so the planners
    leave it per-op instead of fusing it into a chain.

    ``bound`` (a pytree of ``jax.ShapeDtypeStruct``) makes ``fn`` take a
    second argument of that structure, such as a model's weights:
    :meth:`bind` gives a view of the computation that carries the values,
    and the executor passes them to the compiled block program as an
    argument, so the program holds no copy of them and one executable
    serves every value set of those shapes (``base`` is the unbound
    computation the executor keys it on).
    """

    opaque = False
    bound_values = None
    _base = None

    def __init__(self, fn: Callable, inputs: Sequence[TensorSpec],
                 outputs: Sequence[TensorSpec], bound=None):
        self._fn = fn  # dict[str, Array] (, bound) -> dict[str, Array]
        self.bound = bound
        self.inputs: Tuple[TensorSpec, ...] = tuple(inputs)
        self.outputs: Tuple[TensorSpec, ...] = tuple(
            sorted(outputs, key=lambda s: s.name))
        self._input_index = {s.name: s for s in self.inputs}
        self._output_index = {s.name: s for s in self.outputs}

    # -- access ------------------------------------------------------------
    @property
    def input_names(self) -> List[str]:
        return [s.name for s in self.inputs]

    @property
    def output_names(self) -> List[str]:
        return [s.name for s in self.outputs]

    def input(self, name: str) -> TensorSpec:
        return self._input_index[name]

    def output(self, name: str) -> TensorSpec:
        return self._output_index[name]

    def __call__(self, arrays: Mapping[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        missing = [n for n in self.input_names if n not in arrays]
        if missing:
            raise ValueError(f"Missing computation inputs: {missing}")
        feeds = {n: arrays[n] for n in self.input_names}
        extra = () if self.bound is None else (self.bound_values,)
        return dict(self._fn(feeds, *extra))

    @property
    def fn(self) -> Callable:
        """The raw dict->dict JAX-traceable callable (for jit/shard_map);
        with ``bound`` it takes the bound values as a second argument."""
        return self._fn

    @property
    def base(self) -> "Computation":
        """The computation a :meth:`bind` view was made from (itself
        otherwise)."""
        return self if self._base is None else self._base

    def bind(self, values) -> "Computation":
        """A view of this computation that carries ``values``, a pytree
        of the structure, shapes and dtypes of ``bound``."""
        if self.bound is None:
            raise ValueError("this computation takes no bound argument")
        want = jax.tree_util.tree_structure(self.bound)
        got = jax.tree_util.tree_structure(values)
        if got != want:
            raise ValueError(f"bound values have the structure {got}; "
                             f"the computation was traced with {want}")
        for w, v in zip(jax.tree_util.tree_leaves(self.bound),
                        jax.tree_util.tree_leaves(values)):
            if tuple(v.shape) != tuple(w.shape) or v.dtype != w.dtype:
                raise ValueError(
                    f"a bound leaf is {v.dtype}{list(v.shape)}; the "
                    f"computation was traced with {w.dtype}{list(w.shape)}")
        view = copy.copy(self)
        view.bound_values = values
        view._base = self.base
        return view

    def __repr__(self):
        ins = ", ".join(map(repr, self.inputs))
        outs = ", ".join(map(repr, self.outputs))
        return f"Computation({ins} -> {outs})"

    # -- construction ------------------------------------------------------
    @staticmethod
    def trace(fn: Callable,
              input_specs: Mapping[str, Tuple[_dt.DType, Shape]] | Sequence[TensorSpec],
              output_shapes: Optional[Mapping[str, Shape]] = None,
              share_lead_symbol: bool = True,
              takes_dict: Optional[bool] = None,
              bound=None) -> "Computation":
        """Capture a Python function as a Computation.

        ``fn`` takes named arrays (one kw/positional arg per input, in
        signature order, or a single dict argument) and returns a dict of
        named outputs (a single array return is named after the function).
        Output shapes are inferred abstractly; ``output_shapes`` are optional
        driver-provided hints (the ``ShapeDescription`` analogue, reference
        ``ShapeDescription.scala:12-17``) used when symbolic inference cannot
        determine a shape. With ``bound`` (a pytree of
        ``jax.ShapeDtypeStruct``), ``fn`` takes the bound values as its
        first argument, before the named arrays.
        """
        if isinstance(input_specs, Mapping):
            specs = [TensorSpec(n, dt, sh) for n, (dt, sh) in input_specs.items()]
        else:
            specs = list(input_specs)

        if takes_dict is None:
            takes_dict = bound is None and _fn_takes_dict(fn, len(specs))
        kw_only = _keyword_only_names(fn)

        def dict_fn(d: Mapping[str, jnp.ndarray], *b) -> Dict[str, jnp.ndarray]:
            if takes_dict:
                out = fn(*b, dict(d))
            else:
                args = [d[s.name] for s in specs if s.name not in kw_only]
                kwargs = {s.name: d[s.name] for s in specs
                          if s.name in kw_only}
                out = fn(*b, *args, **kwargs)
            if not isinstance(out, Mapping):
                name = getattr(fn, "__name__", "output")
                if name == "<lambda>":
                    name = "output"
                out = {name: out}
            return {k: jnp.asarray(v) for k, v in out.items()}

        out_specs = _infer_outputs(dict_fn, specs, share_lead_symbol,
                                   output_shapes, bound)
        return Computation(dict_fn, specs, out_specs, bound)

    # -- serialization (StableHLO via jax.export) --------------------------
    def serialize(self) -> bytes:
        """Serialize to portable bytes: a JSON header (names/dtypes/shapes)
        + the full ``jax.export`` blob (symbolic dims for Unknowns). The
        analogue of ``GraphDef.SerializeToString`` + ``ShapeDescription``
        travelling together. Lowered for both cpu and tpu so one blob runs
        on either host kind.
        """
        avals, _ = _sym_avals(self.inputs, share_lead_symbol=True)
        names = self.input_names

        def flat_fn(*args):
            return self._fn(dict(zip(names, args)))

        jitted = jax.jit(flat_fn)
        try:
            exported = jax_export.export(
                jitted, platforms=("cpu", "tpu"))(*avals)
        except Exception as e:
            # a computation that cannot lower for one of the platforms
            # still serializes for the local one (jax-path only); leave a
            # breadcrumb — the executor-side error ("lowered for (...)")
            # is far from this root cause otherwise
            _log.warning(
                "dual-platform (cpu,tpu) export failed (%s: %s); "
                "serializing for the local platform only", type(e).__name__,
                e)
            exported = jax_export.export(jitted)(*avals)
        blob = exported.serialize()
        header = json.dumps({
            "inputs": [s.to_json() for s in self.inputs],
            "outputs": [s.to_json() for s in self.outputs],
        }).encode("utf-8")
        return _MAGIC + struct.pack("<I", len(header)) + header + blob

    @staticmethod
    def from_stablehlo(module, inputs: Sequence[TensorSpec],
                       outputs: Optional[Sequence[TensorSpec]] = None,
                       platforms: Optional[Sequence[str]] = None
                       ) -> "Computation":
        """Import a BARE StableHLO/MLIR module as a Computation.

        The foreign-graph entry: the reference accepted computations
        authored by an alien stack — real TF Python serialized a
        ``GraphDef`` and the engine ran it (reference ``core.py:37-40``,
        ``TensorFlowOps.scala:46-52``). Here any exporter that can produce
        StableHLO qualifies: ``module`` is MLIR text (``str``/``bytes``,
        e.g. ``jax.jit(fn).lower(...).as_text()`` from a DIFFERENT
        library/process) or a StableHLO portable-bytecode artifact. No
        ``TFTPU1`` header is involved; the signature comes from the
        explicit ``inputs`` specs (the ShapeDescription side-channel
        role). Shapes must be concrete — a bare module is a static graph;
        for symbolic row dims use this library's ``serialize`` format.

        ``outputs``: explicit specs, or ``None`` to infer shapes/dtypes
        abstractly (named ``out_0``, ``out_1``, ... in module result
        order). ``platforms`` defaults to the current backend; it must
        name the platform(s) the module was lowered for. The imported
        computation runs through ``jax.export``'s calling convention.
        """
        for s in inputs:
            if any(d is None or d < 0 for d in s.shape.dims):
                raise ValueError(
                    f"from_stablehlo input {s.name!r} has unknown dims "
                    f"({s.shape}); bare modules are static graphs")
        if isinstance(module, str):
            module = module.encode()
        if not module.startswith(b"ML\xefR"):  # MLIR text -> bytecode
            from jaxlib.mlir.dialects import stablehlo as _sh
            version = _sh.get_minimum_version()
            from .utils.compat import serialize_stablehlo_artifact
            module = serialize_stablehlo_artifact(module, version)
        if platforms is None:
            platforms = (jax.default_backend(),)
        import jax.tree_util as jtu

        names = [s.name for s in inputs]
        in_avals = tuple(
            jax.core.ShapedArray(tuple(s.shape.dims),
                                 _dt.device_dtype(s.dtype))
            for s in inputs)
        n = len(inputs)

        def build_exported(out_avals):
            import dataclasses as _dc

            kwargs = dict(
                fun_name="foreign_stablehlo",
                in_tree=jtu.tree_structure((tuple(in_avals), {})),
                in_avals=in_avals,
                out_tree=jtu.tree_structure(tuple(out_avals)),
                out_avals=tuple(out_avals),
                in_shardings_hlo=(None,) * n,
                out_shardings_hlo=(None,) * len(out_avals),
                _has_named_shardings=False,
                _in_named_shardings=None,
                _out_named_shardings=None,
                nr_devices=1,
                platforms=tuple(platforms),
                ordered_effects=(),
                unordered_effects=(),
                disabled_safety_checks=(),
                mlir_module_serialized=module,
                calling_convention_version=(
                    jax_export.maximum_supported_calling_convention_version),
                module_kept_var_idx=tuple(range(n)),
                uses_global_constants=False,
                _get_vjp=None,
            )
            # the named-shardings triple is newer than some supported jax
            # builds; construct with whatever fields this Exported declares
            fields = {f.name for f in _dc.fields(jax_export.Exported)}
            return jax_export.Exported(
                **{k: v for k, v in kwargs.items() if k in fields})

        if outputs is None:
            # the module knows its results; discover them abstractly by
            # declaring one output and reading the real structure from
            # the deserialized module's main signature via eval_shape on
            # a permissive Exported is not possible — instead parse the
            # result count/types from the portable artifact's text form
            out_specs_raw = _module_result_avals(module)
            outputs = [
                TensorSpec(f"out_{i}", _dt.from_numpy(np.dtype(dt)),
                           Shape(*shape))
                for i, (shape, dt) in enumerate(out_specs_raw)]
        out_names = [s.name for s in outputs]
        out_avals = tuple(
            jax.core.ShapedArray(tuple(s.shape.dims),
                                 _dt.device_dtype(s.dtype))
            for s in outputs)
        exported = build_exported(out_avals)

        def dict_fn(d: Mapping[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
            res = exported.call(*[d[nm] for nm in names])
            if isinstance(res, (list, tuple)):
                return dict(zip(out_names, res))
            return {out_names[0]: res}

        comp = Computation(dict_fn, list(inputs), list(outputs))
        comp.opaque = True
        return comp

    @staticmethod
    def deserialize(data: bytes) -> "Computation":
        if not data.startswith(_MAGIC):
            raise ValueError("Not a serialized tensorframes-tpu computation")
        off = len(_MAGIC)
        (hlen,) = struct.unpack_from("<I", data, off)
        off += 4
        header = json.loads(data[off:off + hlen].decode("utf-8"))
        blob = data[off + hlen:]
        native = header.get("native")
        if native:
            # older blobs carry a raw StableHLO module section ahead of
            # the jax.export payload: skip it
            blob = blob[native["module_len"]:]
        exported = jax_export.deserialize(blob)
        inputs = [TensorSpec.from_json(d) for d in header["inputs"]]
        outputs = [TensorSpec.from_json(d) for d in header["outputs"]]
        names = [s.name for s in inputs]
        out_names = [s.name for s in outputs]

        def dict_fn(d: Mapping[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
            res = exported.call(*[d[n] for n in names])
            # exported.call returns the original dict pytree when possible;
            # normalize both dict and flat-sequence forms.
            if isinstance(res, Mapping):
                return dict(res)
            if isinstance(res, (list, tuple)):
                return dict(zip(out_names, res))
            return {out_names[0]: res}

        comp = Computation(dict_fn, inputs, outputs)
        comp.opaque = True
        return comp


def _module_result_avals(bytecode: bytes):
    """(shape tuple, numpy dtype) per result of the module's @main, read
    from the portable artifact's text form — used when
    :meth:`Computation.from_stablehlo` is given no output specs."""
    import re

    from .utils.compat import deserialize_stablehlo_artifact

    text = deserialize_stablehlo_artifact(bytecode)
    if isinstance(text, bytes):
        text = text.decode("utf-8", errors="replace")
    m = re.search(
        r"@main\s*\((?:[^()]|\([^()]*\))*\)\s*->\s*"
        r"(\((?P<multi>.*?)\)|(?P<single>tensor<[^>]*>))\s*(\{|attributes)",
        text, re.S)
    if m is None:
        raise ValueError(
            "could not parse the module's @main result signature; pass "
            "explicit output specs to from_stablehlo")
    res = m.group("multi") if m.group("multi") is not None \
        else m.group("single")
    dt_map = {"f32": np.float32, "f64": np.float64, "i32": np.int32,
              "i64": np.int64, "i1": np.bool_, "ui32": np.uint32,
              "ui64": np.uint64, "bf16": "bfloat16"}
    declared = re.findall(r"tensor<[^>]*>", res)
    out = []
    for tm in re.finditer(r"tensor<([0-9x]*?)(" + "|".join(dt_map) + r")>",
                          res):
        dims_s, dt = tm.group(1), tm.group(2)
        dims = tuple(int(d) for d in dims_s.split("x") if d) \
            if dims_s else ()
        np_dt = dt_map[dt]
        if np_dt == "bfloat16":
            import ml_dtypes

            np_dt = ml_dtypes.bfloat16
        out.append((dims, np.dtype(np_dt)))
    if not out or len(out) != len(declared):
        # a result type this importer cannot map (i8/f16/complex/dynamic
        # dims...) must not silently drop outputs
        raise ValueError(
            f"module's @main declares {len(declared)} tensor result(s) "
            f"but only {len(out)} have element types this importer "
            f"understands; pass explicit output specs to from_stablehlo")
    return out


def _keyword_only_names(fn: Callable) -> frozenset:
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return frozenset()
    return frozenset(p.name for p in sig.parameters.values()
                     if p.kind == p.KEYWORD_ONLY)


def _fn_takes_dict(fn: Callable, n_inputs: int) -> bool:
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return False
    params = [p for p in sig.parameters.values()
              if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]
    has_varargs = any(p.kind == p.VAR_POSITIONAL
                      for p in sig.parameters.values())
    if has_varargs:
        return False
    return len(params) == 1 and n_inputs != 1


def _infer_outputs(dict_fn: Callable, specs: Sequence[TensorSpec],
                   share_lead_symbol: bool,
                   output_shapes: Optional[Mapping[str, Shape]],
                   bound=None) -> List[TensorSpec]:
    """Abstractly evaluate the computation to get output specs.

    Strategy 1: symbolic dims (exact propagation of the unknown row dim).
    Strategy 2 (fallback, when an op rejects symbolic dims): substitute a
    distinctive concrete size for each Unknown and mark output dims that
    equal it as Unknown — with driver hints taking precedence (the
    reference's hint mechanism existed for exactly this reason).
    """
    avals, any_symbolic = _sym_avals(specs, share_lead_symbol)
    extra = () if bound is None else (bound,)
    out = None
    try:
        out = jax.eval_shape(dict_fn, dict(zip([s.name for s in specs], avals)),
                             *extra)
    except Exception:
        # Only symbolic-dim-hostile computations may fall back; a failure on
        # fully-concrete avals is a real error in the user computation.
        if not any_symbolic:
            raise
    if out is None:
        # Fallback: probe with a sentinel size per unknown dim.
        SENTINEL = 61  # prime, unlikely to appear as a real static dim
        conc = []
        for spec, aval in zip(specs, avals):
            dims = tuple(SENTINEL if not isinstance(d, int) else d
                         for d in aval.shape)
            conc.append(jax.ShapeDtypeStruct(dims, aval.dtype))
        out = jax.eval_shape(dict_fn, {s.name: a for s, a in zip(specs, conc)},
                             *extra)
        inferred = {name: Shape(tuple(Unknown if d == SENTINEL else d
                                      for d in out[name].shape))
                    for name in out}
    else:
        inferred = {name: _shape_from_aval(out[name].shape) for name in out}
    out_specs = []
    for name in sorted(out):
        sh = inferred[name]
        if output_shapes and name in output_shapes:
            hinted = output_shapes[name]
            if not sh.is_more_precise_than(hinted) and \
                    not hinted.is_more_precise_than(sh):
                raise ValueError(
                    f"Output {name!r}: hint {hinted} incompatible with "
                    f"inferred shape {sh}")
            sh = hinted if hinted.is_more_precise_than(sh) else sh
        out_specs.append(TensorSpec(
            name, _output_framework_dtype(out[name].dtype, specs), sh))
    return out_specs


def analyze_graph(comp: Computation,
                  shape_hints: Optional[Mapping[str, Shape]] = None,
                  fetches: Optional[Sequence[str]] = None) -> List[GraphNodeSummary]:
    """Validate a computation and summarize its endpoints without running it.

    The ``analyzeGraph`` analogue (reference ``TensorFlowOps.scala:84-161``):
    inputs are the computation's placeholders; outputs are the requested
    fetches (default: all outputs). Shape hints must be consistent with the
    captured specs; fetches must exist.
    """
    shape_hints = dict(shape_hints or {})
    fetch_names = list(fetches) if fetches is not None else comp.output_names
    summaries: List[GraphNodeSummary] = []
    for spec in comp.inputs:
        sh = spec.shape
        hint = shape_hints.get(spec.name)
        if hint is not None:
            if not hint.is_more_precise_than(sh) and \
                    not sh.is_more_precise_than(hint):
                raise ValueError(
                    f"Input {spec.name!r}: hint {hint} incompatible with "
                    f"declared shape {sh}")
            sh = hint if hint.is_more_precise_than(sh) else sh
        summaries.append(GraphNodeSummary(spec.name, True, False,
                                          spec.dtype, sh))
    for name in fetch_names:
        if name not in comp.output_names:
            raise ValueError(
                f"Fetch {name!r} not produced by computation; outputs: "
                f"{comp.output_names}")
        spec = comp.output(name)
        sh = spec.shape
        hint = shape_hints.get(name)
        if hint is not None and hint.is_more_precise_than(sh):
            sh = hint
        summaries.append(GraphNodeSummary(name, False, True, spec.dtype, sh))
    return summaries
