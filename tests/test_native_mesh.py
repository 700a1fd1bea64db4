"""Mesh ops through the native C++ PJRT core (GSPMD), parity vs jax.

The reference's property that every execution bottoms out in C++
(``TensorFlowOps.scala:55-64``) extended to the DISTRIBUTED layer: the
same mesh programs dmap_blocks/dreduce_blocks build, GSPMD-compiled and
executed by ``native/libtfrpjrt.so`` on a cpu:4 client, must match the
in-process jax dispatch bit-for-bit (same XLA, same partitioner).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import tensorframes_tpu as tft
from tensorframes_tpu import parallel as par
from tensorframes_tpu.parallel import native_mesh


def _native_available() -> bool:
    from tensorframes_tpu import native_pjrt

    return native_pjrt.available()


pytestmark = pytest.mark.skipif(
    not _native_available(),
    reason="libtfrpjrt.so not built (make -C native pjrt)")


@pytest.fixture
def mesh4():
    return par.local_mesh(4)


@pytest.fixture
def pjrt_routing(monkeypatch):
    monkeypatch.setenv("TFT_EXECUTOR", "pjrt")


def _executor(mesh4):
    ex = native_mesh.executor_for(mesh4)
    assert ex is not None, "native mesh executor should be available"
    return ex


class TestNativeDmap:
    def test_parity_with_jax_path(self, mesh4, pjrt_routing):
        x = np.arange(32, dtype=np.float64)
        df = tft.frame({"x": x})
        fetch = lambda x: {"z": x * 2.0 + 1.0}  # noqa: E731

        dist = par.distribute(df, mesh4)
        ex = _executor(mesh4)
        before = ex.dispatch_count
        out = par.dmap_blocks(fetch, dist)
        assert ex.dispatch_count == before + 1  # the native core ran it
        got = np.asarray(out.columns["z"])

        # identical program through the in-process jax dispatch
        import os

        os.environ.pop("TFT_EXECUTOR", None)
        ref = par.dmap_blocks(fetch, par.distribute(df, mesh4))
        np.testing.assert_array_equal(got, np.asarray(ref.columns["z"]))

    def test_vector_columns_and_collect(self, mesh4, pjrt_routing):
        v = np.arange(24, dtype=np.float64).reshape(12, 2)
        df = tft.analyze(tft.frame({"v": v}))
        dist = par.distribute(df, mesh4)
        ex = _executor(mesh4)
        before = ex.dispatch_count
        out = par.dmap_blocks(lambda v: {"s": v.sum(axis=1)}, dist)
        assert ex.dispatch_count == before + 1
        rows = out.collect_frame().collect()
        np.testing.assert_allclose([r["s"] for r in rows], v.sum(axis=1))

    def test_pad_rows_flow_through(self, mesh4, pjrt_routing):
        # 10 rows over 4 shards pads to 12; pad rows must be dropped at
        # collect exactly as on the jax path
        x = np.arange(10, dtype=np.float64)
        dist = par.distribute(tft.frame({"x": x}), mesh4)
        out = par.dmap_blocks(lambda x: {"z": x + 3.0}, dist)
        rows = out.collect_frame().collect()
        assert [r["z"] for r in rows] == [v + 3.0 for v in x]

    def test_trim_falls_back_to_jax(self, mesh4, pjrt_routing):
        # a global (row-count-changing) computation cannot take the
        # native route; it must still produce the right answer via jax —
        # including the ONE-summary-row case, whose row count does not
        # even tile the data axis
        x = np.arange(8, dtype=np.float64)
        dist = par.distribute(tft.frame({"x": x}), mesh4)
        ex = _executor(mesh4)
        before = ex.dispatch_count
        out = par.dmap_blocks(
            lambda x: {"s": x.sum(keepdims=True)}, dist, trim=True,
            row_aligned=False)
        assert ex.dispatch_count == before  # native path not used
        rows = out.collect_frame().collect()
        assert len(rows) == 1
        np.testing.assert_allclose(rows[0]["s"], x.sum())

    def test_compile_cache_reused(self, mesh4, pjrt_routing):
        # one live Computation, two dispatches -> one native compile
        # (the cache lives on the Computation, the _tft_jitted pattern)
        from tensorframes_tpu import dtypes as _dt
        from tensorframes_tpu.computation import Computation, TensorSpec
        from tensorframes_tpu.shape import Shape, Unknown

        comp = Computation.trace(
            lambda x: {"z": x - 1.0},
            [TensorSpec("x", _dt.double, Shape(Unknown))])
        x = np.arange(16, dtype=np.float64)
        dist = par.distribute(tft.frame({"x": x}), mesh4)
        ex = _executor(mesh4)
        before = ex.compile_count
        par.dmap_blocks(comp, dist)
        par.dmap_blocks(comp, dist)
        assert ex.compile_count == before + 1  # second call hit the cache


class TestNativeDreduce:
    def test_sum_min_parity(self, mesh4, pjrt_routing):
        rng = np.random.default_rng(7)
        x = rng.normal(size=100)
        df = tft.frame({"x": x})
        dist = par.distribute(df, mesh4)
        ex = _executor(mesh4)
        before = ex.dispatch_count
        out = par.dreduce_blocks({"x": "sum"}, dist)
        assert ex.dispatch_count == before + 1
        np.testing.assert_allclose(out["x"], x.sum(), rtol=1e-12)

        out2 = par.dreduce_blocks({"x": "min"}, dist)
        np.testing.assert_allclose(out2["x"], x.min())

    def test_vector_column_and_pad_masking(self, mesh4, pjrt_routing):
        # 10 rows pad to 12: the two pad rows must be masked to the
        # neutral element inside the native program too
        v = np.arange(20, dtype=np.float64).reshape(10, 2)
        df = tft.analyze(tft.frame({"v": v}))
        dist = par.distribute(df, mesh4)
        out = par.dreduce_blocks({"v": "sum"}, dist)
        np.testing.assert_allclose(out["v"], v.sum(axis=0))

    def test_generic_computation_runs_natively(self, mesh4, pjrt_routing):
        # the arbitrary-computation reduce (per-shard partials + ragged
        # tail + final stacked combine) compiles as one GSPMD executable
        import os

        rng = np.random.default_rng(13)
        x = rng.normal(size=42)  # 42 over 4 shards: tail shard exercised
        df = tft.frame({"x": x})
        dist = par.distribute(df, mesh4)
        ex = _executor(mesh4)
        before = ex.dispatch_count

        def fetch(x_input):
            return {"x": jnp.sqrt((x_input ** 2).sum(0))}

        out = par.dreduce_blocks(fetch, dist)
        assert ex.dispatch_count == before + 1
        os.environ.pop("TFT_EXECUTOR", None)
        ref = par.dreduce_blocks(fetch, par.distribute(df, mesh4))
        np.testing.assert_array_equal(out["x"], ref["x"])

    def test_matches_jax_path_exactly(self, mesh4, pjrt_routing):
        # same partitioner, same program -> same floats up to reduction
        # order. The native core may be built against a different XLA
        # (tensorflow's) than jaxlib's, so bit-exactness across the two
        # builds is not guaranteed — hold them to ~1 ULP instead.
        import os

        rng = np.random.default_rng(11)
        x = rng.normal(size=64)
        dist = par.distribute(tft.frame({"x": x}), mesh4)
        native = par.dreduce_blocks({"x": "sum"}, dist)
        os.environ.pop("TFT_EXECUTOR", None)
        ref = par.dreduce_blocks({"x": "sum"},
                                 par.distribute(tft.frame({"x": x}), mesh4))
        np.testing.assert_allclose(native["x"], ref["x"],
                                   rtol=1e-15, atol=0)


class TestNativeDsortDfilter:
    def test_dsort_parity_with_jax_path(self, mesh4, pjrt_routing):
        import os

        rng = np.random.default_rng(21)
        x = rng.normal(size=600)
        x[::71] = np.nan
        dist = par.distribute(tft.frame({"x": x}), mesh4)
        ex = _executor(mesh4)
        before = ex.dispatch_count
        out = par.dsort("x", dist, descending=True)
        assert ex.dispatch_count == before + 1  # columnsort ran natively
        got = np.asarray(out.columns["x"])
        os.environ.pop("TFT_EXECUTOR", None)
        ref = par.dsort("x", par.distribute(tft.frame({"x": x}), mesh4),
                        descending=True)
        np.testing.assert_array_equal(got, np.asarray(ref.columns["x"]))

    def test_dsort_collect_with_string_riders(self, mesh4, pjrt_routing):
        k = np.array([f"s{i}" for i in range(10)], object)
        x = np.arange(10, dtype=np.float64)[::-1].copy()
        dist = par.distribute(tft.frame({"k": k, "x": x}), mesh4)
        rows = par.dsort("x", dist).collect_frame().collect()
        assert [r["k"] for r in rows] == [f"s{i}" for i in range(9, -1, -1)]

    def test_dfilter_parity_and_chain(self, mesh4, pjrt_routing):
        x = np.arange(40, dtype=np.float64)
        dist = par.distribute(tft.frame({"x": x}), mesh4)
        ex = _executor(mesh4)
        before = ex.dispatch_count
        flt = par.dfilter(lambda x: x % 3.0 == 0.0, dist)
        assert ex.dispatch_count == before + 1
        assert flt.count() == 14
        # chain into a native reduce and a native sort
        red = par.dreduce_blocks({"x": "sum"}, flt.select("x"))
        np.testing.assert_allclose(red["x"], x[x % 3 == 0].sum())
        srt = par.dsort("x", flt, descending=True)
        rows = srt.collect_frame().collect()
        assert [r["x"] for r in rows] == sorted(
            x[x % 3 == 0].tolist(), reverse=True)


class TestNativeDaggregate:
    """daggregate through the C++ core — the last mesh op to gain the
    route (reference property: every UDAF compaction ran in the C++
    session, ``DebugRowOps.scala:617-662``)."""

    def test_monoid_parity_with_jax_path(self, mesh4, pjrt_routing):
        import os

        rng = np.random.default_rng(31)
        n, g = 200, 17
        keys = rng.integers(0, g, n).astype(np.int64)
        vals = rng.normal(size=n)
        df = tft.frame({"key": keys, "x": vals})
        dist = par.distribute(df, mesh4)
        ex = _executor(mesh4)
        before = ex.dispatch_count
        out = par.daggregate({"x": "sum"}, dist, "key")
        assert ex.dispatch_count == before + 1  # native core ran it
        got = {r["key"]: r["x"] for r in out.collect()}

        os.environ.pop("TFT_EXECUTOR", None)
        ref_out = par.daggregate({"x": "sum"},
                                 par.distribute(df, mesh4), "key")
        ref = {r["key"]: r["x"] for r in ref_out.collect()}
        assert set(got) == set(ref)
        for k in ref:  # same XLA, same partitioner -> identical floats
            np.testing.assert_array_equal(got[k], ref[k])

    def test_monoid_min_vector_column(self, mesh4, pjrt_routing):
        rng = np.random.default_rng(32)
        k = rng.integers(0, 5, 30).astype(np.int64)
        v = rng.normal(size=(30, 2))
        df = tft.analyze(tft.frame({"k": k, "v": v}))
        dist = par.distribute(df, mesh4)
        ex = _executor(mesh4)
        before = ex.dispatch_count
        out = par.daggregate({"v": "min"}, dist, "k")
        assert ex.dispatch_count == before + 1
        for r in out.collect():
            np.testing.assert_allclose(
                r["v"], v[k == r["k"]].min(axis=0), rtol=1e-12)

    def test_device_key_composite_parity(self, mesh4, pjrt_routing):
        # composite (mixed-radix) device-side keys: the key columns never
        # visit the host; the aggregation program still runs natively
        import os

        rng = np.random.default_rng(33)
        k1 = rng.integers(0, 4, 60).astype(np.int64)
        k2 = rng.integers(0, 3, 60).astype(np.int64)
        x = rng.normal(size=60)
        df = tft.frame({"k1": k1, "k2": k2, "x": x})
        dist = par.distribute(df, mesh4)
        ex = _executor(mesh4)
        before = ex.dispatch_count
        out = par.daggregate({"x": "sum"}, dist, ["k1", "k2"],
                             max_groups=16)
        assert ex.dispatch_count > before
        got = {(r["k1"], r["k2"]): r["x"] for r in out.collect()}

        os.environ.pop("TFT_EXECUTOR", None)
        ref_out = par.daggregate({"x": "sum"}, par.distribute(df, mesh4),
                                 ["k1", "k2"])
        ref = {(r["k1"], r["k2"]): r["x"] for r in ref_out.collect()}
        assert set(got) == set(ref)
        for k in ref:
            np.testing.assert_allclose(got[k], ref[k], rtol=1e-12)

    def test_integer_sum_exact(self, mesh4, pjrt_routing):
        # int64 sums must stay exact through the native route (the XLA
        # scatter-add flavor: the Pallas kernel's exact integer route
        # holds 32 bits)
        rng = np.random.default_rng(35)
        k = rng.integers(0, 6, 64).astype(np.int64)
        # values near 2^53: per-key sums leave f64's exact-integer range,
        # so a silent float detour (f32 OR f64 accumulation) fails loudly
        x = rng.integers(2**53 - 2**20, 2**53, 64).astype(np.int64)
        df = tft.frame({"k": k, "x": x})
        dist = par.distribute(df, mesh4)
        ex = _executor(mesh4)
        before = ex.dispatch_count
        out = par.daggregate({"x": "sum"}, dist, "k")
        assert ex.dispatch_count == before + 1
        got = {r["k"]: r["x"] for r in out.collect()}
        for kk in np.unique(k):
            assert got[kk] == x[k == kk].sum(), kk  # exact, not approx

    def test_generic_fold_runs_natively(self, mesh4, pjrt_routing):
        # the arbitrary-computation (sorted-scan) path compiles as one
        # GSPMD executable too
        import os

        import jax.numpy as jnp

        rng = np.random.default_rng(34)
        n = 120
        k = rng.integers(0, 7, n).astype(np.int64)
        v = rng.normal(size=n)

        def fetch(v_input):
            return {"v": jnp.sqrt((v_input ** 2).sum(0))}

        df = tft.frame({"k": k, "v": v})
        dist = par.distribute(df, mesh4)
        ex = _executor(mesh4)
        before = ex.dispatch_count
        out = par.daggregate(fetch, dist, "k")
        assert ex.dispatch_count == before + 1
        got = {r["k"]: r["v"] for r in out.collect()}

        os.environ.pop("TFT_EXECUTOR", None)
        ref_out = par.daggregate(fetch, par.distribute(df, mesh4), "k")
        ref = {r["k"]: r["v"] for r in ref_out.collect()}
        assert set(got) == set(ref)
        for key in ref:
            np.testing.assert_array_equal(got[key], ref[key])


class TestResidentLoop:
    """Device-resident iteration through the native core: shards upload
    once, outputs feed back as device buffers, one final download —
    the HBM-resident loop the jax path gets from ``jax.Array``."""

    def test_loop_matches_per_call_dispatch(self, mesh4, pjrt_routing):
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        ex = _executor(mesh4)
        axis = mesh4.data_axis
        n = 16
        x = np.arange(n, dtype=np.float64)

        def build():
            def step(x):
                # a collective every iteration proves the ICI path runs
                # inside the resident loop too
                total = jax.lax.psum(x.sum(), axis)
                return (x * 0.5 + total / n,)
            return shard_map(step, mesh=mesh4.mesh,
                             in_specs=(P(axis),), out_specs=(P(axis),))

        in_sh = [mesh4.row_sharding(1)]
        out_sh = [mesh4.row_sharding(1)]
        iters = 5
        before = ex.dispatch_count
        looped = ex.run_sharded_loop(("loop-test", n), build, [x], in_sh,
                                     out_sh, mesh4, iters=iters)
        assert looped is not None
        assert ex.dispatch_count == before + iters

        # reference: the same program applied per-call via jax
        fn = jax.jit(build())
        ref = jnp.asarray(x)
        for _ in range(iters):
            (ref,) = fn(ref)
        np.testing.assert_allclose(looped[0], np.asarray(ref), rtol=1e-12)

    def test_loop_multi_arg_mixed_dtypes(self, mesh4, pjrt_routing):
        # two-state loop (f64 vector + i32 counter), both resident
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        ex = _executor(mesh4)
        axis = mesh4.data_axis
        x = np.arange(8, dtype=np.float64)
        c = np.zeros(8, dtype=np.int32)

        def build():
            def step(x, c):
                return (x * 2.0, c + 1)
            return shard_map(step, mesh=mesh4.mesh,
                             in_specs=(P(axis), P(axis)),
                             out_specs=(P(axis), P(axis)))

        sh = [mesh4.row_sharding(1), mesh4.row_sharding(1)]
        outs = ex.run_sharded_loop(("loop-multi", 8), build, [x, c],
                                   sh, sh, mesh4, iters=3)
        assert outs is not None, "two-state program should be routable"
        np.testing.assert_array_equal(outs[0], x * 8.0)
        np.testing.assert_array_equal(outs[1], np.full(8, 3, np.int32))

    def test_loop_rejects_signature_mismatch(self, mesh4, pjrt_routing):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        ex = _executor(mesh4)
        axis = mesh4.data_axis
        x = np.arange(8, dtype=np.float64)

        def build():
            return shard_map(lambda x: (x[: x.shape[0] // 2],),
                             mesh=mesh4.mesh, in_specs=(P(axis),),
                             out_specs=(P(axis),))

        with pytest.raises(ValueError, match="positionally"):
            ex.run_sharded_loop(("loop-bad", 8), build, [x],
                                [mesh4.row_sharding(1)],
                                [mesh4.row_sharding(1)], mesh4, iters=2)


class TestRoutingGuards:
    def test_off_without_env(self, mesh4, monkeypatch):
        monkeypatch.delenv("TFT_EXECUTOR", raising=False)
        assert native_mesh.executor_for(mesh4) is None

    def test_string_columns_ride_along(self, mesh4, pjrt_routing):
        # string ride-along columns never enter the computation; the
        # native route must still work for the tensor outputs
        k = np.array([f"k{i}" for i in range(8)], object)
        x = np.arange(8, dtype=np.float64)
        dist = par.distribute(tft.frame({"k": k, "x": x}), mesh4)
        out = par.dmap_blocks(lambda x: {"z": x + 1.0}, dist)
        rows = out.collect_frame().collect()
        assert [(r["k"], r["z"]) for r in rows] == [
            (f"k{i}", float(i) + 1.0) for i in range(8)]
