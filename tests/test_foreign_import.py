"""Foreign-graph import: bare StableHLO modules as computations.

The reference accepted computations authored by an alien stack — real TF
Python serialized a GraphDef and the engine ran it (``core.py:37-40``,
``TensorFlowOps.scala:46-52``). The analogue here: a module produced by
ANY exporter (plain ``jax.jit(...).lower()``, not this library's
``serialize``) enters through ``builder.map_blocks_builder`` with explicit
specs and runs through ``jax.export``'s calling convention. Such modules
are opaque to the planners: chains that hold one stay per-op.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import tensorframes_tpu as tft
from tensorframes_tpu import builder, dtypes as _dt
from tensorframes_tpu.computation import Computation, TensorSpec
from tensorframes_tpu.engine import ops as _ops
from tensorframes_tpu.parallel import distribute, dmap_blocks, local_mesh
from tensorframes_tpu.plan.optimize import build_plan
from tensorframes_tpu.shape import Shape, Unknown
from tensorframes_tpu.utils.tracing import counters


def _foreign_module_text(n=6, dtype=jnp.float64):
    """A module this library did NOT produce: plain jax.jit lowering."""
    fn = lambda x: x * 2.0 + 1.0  # noqa: E731
    return jax.jit(fn).lower(
        jax.ShapeDtypeStruct((n,), dtype)).as_text()


class TestFromStablehlo:
    def test_through_map_blocks_builder(self):
        text = _foreign_module_text()
        df = tft.frame({"x": np.arange(6.0)}, num_partitions=1)
        out = (builder.map_blocks_builder(df)
               .graph(text.encode())
               .signature([TensorSpec("x", _dt.double, Shape(6))],
                          [TensorSpec("z", _dt.double, Shape(6))])
               .build())
        rows = out.collect()
        assert [r["z"] for r in rows] == [v * 2.0 + 1.0
                                          for v in np.arange(6.0)]
        # inputs ride along untrimmed, like any map_blocks
        assert [r["x"] for r in rows] == list(np.arange(6.0))

    def test_outputs_inferred_from_module(self):
        text = _foreign_module_text()
        comp = Computation.from_stablehlo(
            text, [TensorSpec("x", _dt.double, Shape(6))])
        assert comp.output_names == ["out_0"]
        assert comp.outputs[0].shape.dims == (6,)
        df = tft.frame({"x": np.arange(6.0)}, num_partitions=1)
        rows = _ops.map_blocks(comp, df, trim=True).collect()
        assert [r["out_0"] for r in rows] == [v * 2.0 + 1.0
                                              for v in np.arange(6.0)]

    def test_composes_under_jit(self):
        # exported-call computations must stay traceable (the engine jits
        # comp.fn; the mesh layer may jit it inside larger programs)
        comp = Computation.from_stablehlo(
            _foreign_module_text(),
            [TensorSpec("x", _dt.double, Shape(6))],
            [TensorSpec("z", _dt.double, Shape(6))])
        f = jax.jit(lambda d: comp.fn(d)["z"] + 1.0)
        got = f({"x": jnp.arange(6.0)})
        np.testing.assert_allclose(np.asarray(got),
                                   np.arange(6.0) * 2.0 + 2.0)

    def test_unknown_dims_rejected(self):
        with pytest.raises(ValueError, match="unknown dims"):
            Computation.from_stablehlo(
                _foreign_module_text(),
                [TensorSpec("x", _dt.double, Shape(Unknown))])

    def test_bare_module_without_signature_errors(self):
        df = tft.frame({"x": np.arange(6.0)}, num_partitions=1)
        b = builder.map_blocks_builder(df).graph(
            _foreign_module_text().encode())
        with pytest.raises(ValueError, match="signature"):
            b.build()

    def test_garbage_bytes_still_canonical_error(self):
        df = tft.frame({"x": np.arange(6.0)}, num_partitions=1)
        with pytest.raises(ValueError, match="Not a serialized"):
            builder.map_blocks_builder(df).graph(b"\x00\x01garbage")


_ROWS = 8


def _opaque(source):
    if source == "from_stablehlo":
        return Computation.from_stablehlo(
            _foreign_module_text(_ROWS),
            [TensorSpec("x", _dt.double, Shape(_ROWS))],
            [TensorSpec("z", _dt.double, Shape(_ROWS))])
    traced = Computation.trace(
        lambda x: {"z": x * 2.0 + 1.0},
        [TensorSpec("x", _dt.double, Shape(Unknown))])
    return Computation.deserialize(traced.serialize())


@pytest.mark.parametrize("source", ["from_stablehlo", "deserialize"])
@pytest.mark.parametrize("chain", ["host", "mesh"])
def test_opaque_computation_stays_per_op(source, chain):
    comp = _opaque(source)
    assert comp.opaque
    df = tft.frame({"x": np.arange(float(_ROWS))}, num_partitions=1)
    want = np.arange(float(_ROWS)) * 2.0 + 1.0
    if chain == "host":
        lazy = df.map_blocks(comp).map_blocks(lambda z: {"w": z + 1.0})
        assert build_plan(lazy) is None
        eager = _ops.map_blocks(comp, df).collect()
        rows = lazy.collect()
        assert lazy._plan_info is None
        assert [r["z"] for r in rows] == [r["z"] for r in eager]
        assert [r["w"] for r in rows] == list(want + 1.0)
    else:
        dist = distribute(df, local_mesh(4))
        before = counters.get("dplan.recorded_ops")
        out = dmap_blocks(comp, dist.lazy())
        assert counters.get("dplan.recorded_ops") == before
        rows = out.collect_frame().collect()
        eager = dmap_blocks(comp, dist).collect_frame().collect()
        assert [r["z"] for r in rows] == [r["z"] for r in eager]
    assert [r["z"] for r in rows] == list(want)
