"""Computation IR tests: tracing, shape inference, serialization,
analyze_graph validation (the TFInitializationSuite/analyzeGraph analogue)."""

import json
import struct

import jax.numpy as jnp
import numpy as np
import pytest
from jax import export as jax_export

from tensorframes_tpu import dtypes as dt
from tensorframes_tpu.computation import (
    _MAGIC, Computation, TensorSpec, analyze_graph)
from tensorframes_tpu.shape import Shape, Unknown


def specs(**kw):
    return [TensorSpec(n, d, s) for n, (d, s) in kw.items()]


def test_trace_simple_add():
    c = Computation.trace(
        lambda x: {"z": x + 3.0},
        specs(x=(dt.double, Shape(Unknown))))
    assert c.input_names == ["x"]
    assert c.output_names == ["z"]
    assert c.output("z").shape == Shape(Unknown)
    out = c({"x": jnp.asarray(np.arange(4.0))})
    np.testing.assert_allclose(np.asarray(out["z"]), np.arange(4.0) + 3)


def test_outputs_sorted_by_name():
    c = Computation.trace(
        lambda x: {"b": x, "a": x * 2},
        specs(x=(dt.double, Shape(Unknown))))
    assert c.output_names == ["a", "b"]


def test_shared_lead_dim_across_inputs():
    c = Computation.trace(
        lambda x, y: {"z": x + y},
        specs(x=(dt.double, Shape(Unknown)), y=(dt.double, Shape(Unknown))))
    assert c.output("z").shape == Shape(Unknown)


def test_block_reduce_shape():
    c = Computation.trace(
        lambda x_input: {"x": jnp.sum(x_input, axis=0)},
        specs(x_input=(dt.double, Shape(Unknown, 3))))
    assert c.output("x").shape == Shape(3)


def test_single_output_named_after_function():
    def doubled(x):
        return x * 2
    c = Computation.trace(doubled, specs(x=(dt.double, Shape(Unknown))))
    assert c.output_names == ["doubled"]


def test_trace_dict_style_fn():
    def f(cols):
        return {"z": cols["x"] + cols["y"]}
    c = Computation.trace(
        f, specs(x=(dt.double, Shape(Unknown)), y=(dt.double, Shape(Unknown))))
    assert c.output_names == ["z"]


def test_missing_input_raises():
    c = Computation.trace(
        lambda x: {"z": x}, specs(x=(dt.double, Shape(Unknown))))
    with pytest.raises(ValueError, match="Missing computation inputs"):
        c({})


def test_serialize_roundtrip():
    c = Computation.trace(
        lambda x: {"z": x * 2 + 1, "m": jnp.min(x, axis=0)},
        specs(x=(dt.double, Shape(Unknown, 2))))
    blob = c.serialize()
    c2 = Computation.deserialize(blob)
    assert c2.input_names == ["x"]
    assert c2.output_names == ["m", "z"]
    assert c2.output("z").shape == Shape(Unknown, 2)
    x = np.arange(8.0).reshape(4, 2)
    out = c2({"x": x})
    np.testing.assert_allclose(np.asarray(out["z"]), x * 2 + 1)
    np.testing.assert_allclose(np.asarray(out["m"]), x.min(axis=0))


def test_deserialize_skips_a_raw_module_section():
    # older blobs carry a "native" header entry and the raw StableHLO
    # module between the header and the jax.export payload
    c = Computation.trace(
        lambda x: {"z": x * 2 + 1, "m": jnp.min(x, axis=0)},
        specs(x=(dt.double, Shape(Unknown, 2))))
    blob = c.serialize()
    off = len(_MAGIC)
    (hlen,) = struct.unpack_from("<I", blob, off)
    header = json.loads(blob[off + 4:off + 4 + hlen].decode("utf-8"))
    payload = blob[off + 4 + hlen:]
    exported = jax_export.deserialize(payload)
    module = exported.mlir_module_serialized
    header["native"] = {
        "cc_version": exported.calling_convention_version,
        "platforms": list(exported.platforms),
        "module_len": len(module),
        "arg_dtypes": [str(np.dtype(a.dtype)) for a in exported.in_avals],
    }
    head = json.dumps(header).encode("utf-8")
    old = _MAGIC + struct.pack("<I", len(head)) + head + module + payload
    c2 = Computation.deserialize(old)
    assert c2.opaque
    assert c2.output_names == ["m", "z"]
    x = np.arange(8.0).reshape(4, 2)
    got, want = c2({"x": x}), c({"x": jnp.asarray(x)})
    for n in ("m", "z"):
        np.testing.assert_array_equal(np.asarray(got[n]), np.asarray(want[n]))


def test_deserialize_rejects_garbage():
    with pytest.raises(ValueError, match="Not a serialized"):
        Computation.deserialize(b"not-a-computation")


def test_analyze_graph_summaries():
    c = Computation.trace(
        lambda x: {"z": x + 1.0},
        specs(x=(dt.double, Shape(Unknown))))
    summ = analyze_graph(c)
    assert [s.name for s in summ] == ["x", "z"]
    assert summ[0].is_input and not summ[0].is_output
    assert summ[1].is_output


def test_analyze_graph_hint_refines():
    c = Computation.trace(
        lambda x: {"z": x}, specs(x=(dt.double, Shape(Unknown))))
    summ = analyze_graph(c, shape_hints={"x": Shape(10)})
    assert summ[0].shape == Shape(10)


def test_analyze_graph_bad_hint_and_fetch():
    c = Computation.trace(
        lambda x: {"z": x}, specs(x=(dt.double, Shape(Unknown, 3))))
    with pytest.raises(ValueError, match="incompatible"):
        analyze_graph(c, shape_hints={"x": Shape(Unknown)})
    with pytest.raises(ValueError, match="not produced"):
        analyze_graph(c, fetches=["nope"])


def test_fallback_inference_for_symbolic_hostile_ops():
    # jnp.reshape(x, (-1,)) handles symbolic fine, but argsort-based tricks
    # may not; exercise the sentinel fallback via an op that inspects shape.
    def f(x):
        n = x.shape[0]
        return {"z": jnp.broadcast_to(jnp.sum(x), (n,))}
    c = Computation.trace(f, specs(x=(dt.double, Shape(Unknown))))
    assert c.output("z").shape == Shape(Unknown)
