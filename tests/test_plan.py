"""Logical-plan suite (tier-1; marker ``plan``; ``run-tests.sh --plan``).

The load-bearing contract: **every lazy-op chain is bit-identical fused
and unfused**. Each equivalence case builds the same chain twice — once
under the default (``TFT_FUSE`` unset: fusion, pruning, device-resident
stage chaining) and once under ``TFT_FUSE=0`` (the per-op dispatch
path) — and compares blocks value-for-value, dtype-for-dtype, block
boundaries included. On top of that:

- fusion actually reduces dispatches (pipeline counters);
- error contracts survive: chains the optimizer cannot prove
  row-preserving fall back and raise exactly like the per-op path;
- injected faults (transient dispatch failures, map_rows OOM splits)
  retry/recover THROUGH the fused computation, results still identical;
- plan-node estimates: UNFORCED frames price per column (serve
  admission input), not by the whole-schema row-byte ratio;
- ``explain()`` renders the optimized plan (fused groups, pruned
  columns, resident edges);
- parquet pruning: a chain referencing 2 of 6 columns decodes exactly
  those two (``io._column_to_numpy`` instrumented).
"""

import os

import numpy as np
import pytest

import tensorframes_tpu as tft
from tensorframes_tpu import io as tio
from tensorframes_tpu.memory.estimate import frame_estimate
from tensorframes_tpu.resilience import faults
from tensorframes_tpu.utils import tracing
from tensorframes_tpu.utils.tracing import counters

pytestmark = pytest.mark.plan


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.setenv("TFT_RETRY_BASE_DELAY", "0.001")
    monkeypatch.setenv("TFT_RETRY_MAX_DELAY", "0.01")
    monkeypatch.delenv("TFT_FUSE", raising=False)
    faults.reset()
    yield
    faults.reset()


def _snapshot(frame):
    out = []
    for b in frame.blocks():
        cols = {}
        for n, c in b.columns.items():
            cols[n] = list(c) if not isinstance(c, np.ndarray) else c
        out.append((b.num_rows, cols))
    return out


def _assert_identical(fused, unfused):
    assert len(fused) == len(unfused), "block count differs"
    for i, ((nf, cf), (nu, cu)) in enumerate(zip(fused, unfused)):
        assert nf == nu, f"block {i}: row count {nf} != {nu}"
        assert set(cf) == set(cu), f"block {i}: columns differ"
        for n in cu:
            a, b = cf[n], cu[n]
            if isinstance(b, np.ndarray):
                assert isinstance(a, np.ndarray), (i, n)
                assert a.dtype == b.dtype, (i, n, a.dtype, b.dtype)
                assert a.shape == b.shape, (i, n, a.shape, b.shape)
                assert np.array_equal(a, b), (i, n)
            else:
                assert len(a) == len(b), (i, n)
                for x, y in zip(a, b):
                    if isinstance(y, np.ndarray):
                        assert np.array_equal(np.asarray(x), y), (i, n)
                    else:
                        assert x == y, (i, n)


def _both_ways(monkeypatch, make_frame, build, expect_fused=True):
    """Force build(make_frame()) fused and unfused; assert bit-identity.
    Returns the fused chain frame (plan info inspection)."""
    chain = build(make_frame())
    fused = _snapshot(chain)
    if expect_fused:
        assert chain._plan_info, "expected the fused plan to execute"
    monkeypatch.setenv("TFT_FUSE", "0")
    chain0 = build(make_frame())
    unfused = _snapshot(chain0)
    assert chain0._plan_info is None
    monkeypatch.delenv("TFT_FUSE")
    _assert_identical(fused, unfused)
    return chain


def _frame(parts=4, rows=97):
    rng = np.random.default_rng(7)
    return tft.frame(
        {"x": np.arange(float(rows)),
         "y": rng.random(rows),
         "k": (np.arange(rows) % 5).astype(np.int64),
         "v": rng.random((rows, 3)),
         "s": np.array([f"r{i}" for i in range(rows)], dtype=object)},
        num_partitions=parts)


# ---------------------------------------------------------------------------
# equivalence: fused == TFT_FUSE=0, bit for bit
# ---------------------------------------------------------------------------

class TestEquivalence:
    def test_map_blocks_chain(self, monkeypatch):
        _both_ways(monkeypatch, _frame, lambda df: (
            df.map_blocks(lambda x: {"a": x + 1.0})
              .map_blocks(lambda a, y: {"b": a * y})
              .map_blocks(lambda b: {"c": b - 2.0})))

    def test_chain_with_filter_between_maps(self, monkeypatch):
        _both_ways(monkeypatch, _frame, lambda df: (
            df.map_blocks(lambda x: {"a": x * 2.0})
              .filter(lambda a: a % 4.0 == 0.0)
              .map_blocks(lambda a: {"b": a + 0.5})))

    def test_cross_row_map_blocks_fuses(self, monkeypatch):
        # z = x - mean(x) is cross-row but row-preserving: fusable, and
        # per-block semantics identical because block boundaries are
        # identical on both paths
        _both_ways(monkeypatch, _frame, lambda df: (
            df.map_blocks(lambda x: {"a": x - x.mean()})
              .map_blocks(lambda a: {"b": a * 3.0})))

    def test_filter_after_row_growing_trim(self, monkeypatch):
        # regression: the mask length is the TRIM output's row count,
        # not the stage input's — when they coincidentally relate the
        # gather must still run (review finding: fused path returned
        # all 2n rows when keep == pre-trim n)
        def make():
            return tft.frame({"x": np.arange(4.0)}, num_partitions=1)
        import jax.numpy as jnp
        _both_ways(monkeypatch, make, lambda df: (
            df.map_blocks(lambda x: {"y": jnp.concatenate([x, x])},
                          trim=True)
              .filter(lambda y: y < 2.0)))

    def test_trim_chain(self, monkeypatch):
        _both_ways(monkeypatch, _frame, lambda df: (
            df.select(["x"])
              .map_blocks(lambda x: {"z": x[: x.shape[0] // 2]}, trim=True)
              .map_blocks(lambda z: {"w": z + 1.0})))

    def test_map_rows_chain(self, monkeypatch):
        _both_ways(monkeypatch, _frame, lambda df: (
            df.map_rows(lambda v: {"n": (v * v).sum()})
              .map_rows(lambda n: {"m": n + 1.0})
              .select(["n", "m", "s"])))

    def test_mixed_ops_with_select_pruning(self, monkeypatch):
        _both_ways(monkeypatch, _frame, lambda df: (
            df.map_blocks(lambda x, y: {"a": x + y})
              .select(["a", "k", "s"])
              .filter(lambda a: a > 1.0)
              .map_rows(lambda a: {"b": a * 0.5})
              .select(["b", "s"])))

    def test_two_filters(self, monkeypatch):
        _both_ways(monkeypatch, _frame, lambda df: (
            df.filter(lambda x: x > 5.0)
              .filter(lambda x: x < 60.0)
              .map_blocks(lambda x: {"a": x + 1.0})))

    def test_filter_drops_everything(self, monkeypatch):
        _both_ways(monkeypatch, _frame, lambda df: (
            df.map_blocks(lambda x: {"a": x + 1.0})
              .filter(lambda a: a < -1.0)
              .map_blocks(lambda a: {"b": a * 2.0})))

    def test_empty_partitions(self, monkeypatch):
        def make():
            return tft.frame({"x": np.arange(3.0)}, num_partitions=1) \
                .repartition(5)
        _both_ways(monkeypatch, make, lambda df: (
            df.map_blocks(lambda x: {"a": x + 1.0})
              .map_blocks(lambda a: {"b": a * 2.0})))

    def test_single_partition(self, monkeypatch):
        _both_ways(monkeypatch, lambda: _frame(parts=1),
                   lambda df: (df.map_blocks(lambda x: {"a": x + 1.0})
                                 .map_blocks(lambda a: {"b": a * 2.0})))

    def test_vector_columns_through_chain(self, monkeypatch):
        _both_ways(monkeypatch, _frame, lambda df: (
            df.map_blocks(lambda v: {"v2": v * 2.0})
              .filter(lambda x: x % 2.0 == 0.0)
              .select(["v", "v2", "x"])))

    def test_collect_and_count_equal(self, monkeypatch):
        df = _frame()
        chain = df.map_blocks(lambda x: {"a": x + 1.0}) \
                  .filter(lambda a: a > 10.0)
        n1 = chain.count()
        rows1 = chain.collect()
        monkeypatch.setenv("TFT_FUSE", "0")
        chain0 = df.map_blocks(lambda x: {"a": x + 1.0}) \
                   .filter(lambda a: a > 10.0)
        assert chain0.count() == n1
        rows0 = chain0.collect()
        for r1, r0 in zip(rows1, rows0):
            for a, b in zip(r1, r0):
                if isinstance(b, np.ndarray):
                    assert np.array_equal(np.asarray(a), b)
                else:
                    assert a == b

    def test_reduction_over_fused_chain(self, monkeypatch):
        df = _frame()
        out1 = tft.reduce_blocks(
            lambda a_input: {"a": a_input.sum()},
            df.map_blocks(lambda x: {"a": x + 1.0})
              .map_blocks(lambda a: {"a_sq": a * a}).select(["a"]))
        monkeypatch.setenv("TFT_FUSE", "0")
        out0 = tft.reduce_blocks(
            lambda a_input: {"a": a_input.sum()},
            df.map_blocks(lambda x: {"a": x + 1.0})
              .map_blocks(lambda a: {"a_sq": a * a}).select(["a"]))
        assert out1 == out0


# ---------------------------------------------------------------------------
# fallback correctness: unplannable chains keep per-op semantics
# ---------------------------------------------------------------------------

class TestFallback:
    def test_row_count_violation_still_raises(self):
        # not provably row-preserving -> falls back -> the per-op
        # runtime check fires exactly as before
        from tensorframes_tpu.engine.ops import InvalidShapeError
        df = _frame()
        chain = df.select(["x"]) \
                  .map_blocks(lambda x: {"z": x[:2]}) \
                  .map_blocks(lambda z: {"w": z + 1.0})
        with pytest.raises(InvalidShapeError, match="trim"):
            chain.blocks()
        assert chain._plan_info is None

    def test_ragged_inputs_fall_back(self, monkeypatch):
        def make():
            return tft.frame(
                [(1.0, np.arange(2.0)), (2.0, np.arange(5.0))],
                columns=["x", "r"]).analyze()
        chain = _both_ways(
            monkeypatch, make,
            lambda df: (df.map_rows(lambda r: {"n": r.sum()})
                          .map_rows(lambda n: {"m": n * 2.0})),
            expect_fused=False)
        assert chain._plan_info is None  # ragged comp inputs stay per-op

    def test_explicit_executor_disables_planning(self):
        from tensorframes_tpu.engine.executor import BlockExecutor
        df = _frame()
        ex = BlockExecutor()
        chain = df.map_blocks(lambda x: {"a": x + 1.0}, executor=ex) \
                  .map_blocks(lambda a: {"b": a * 2.0}, executor=ex)
        chain.blocks()
        assert chain._plan_info is None

    def test_single_op_stays_per_op(self):
        df = _frame()
        one = df.map_blocks(lambda x: {"a": x + 1.0})
        one.blocks()
        assert one._plan_info is None

    def test_empty_final_schema_stays_per_op(self, monkeypatch):
        # select([]) after a row-changing trim: a zero-output fused
        # program cannot carry the trimmed row count, so the chain must
        # stay per-op — and count() must report the TRIMMED rows
        df = tft.frame({"x": np.arange(8.0)}, num_partitions=2)
        chain = df.map_blocks(lambda x: {"z": x[: x.shape[0] // 2]},
                              trim=True) \
                  .map_blocks(lambda z: {"w": z + 1.0}).select([])
        n1 = chain.count()
        assert chain._plan_info is None
        monkeypatch.setenv("TFT_FUSE", "0")
        chain0 = df.map_blocks(lambda x: {"z": x[: x.shape[0] // 2]},
                               trim=True) \
                   .map_blocks(lambda z: {"w": z + 1.0}).select([])
        assert chain0.count() == n1 == 4

    def test_fuse_env_kill_switch(self, monkeypatch):
        monkeypatch.setenv("TFT_FUSE", "0")
        df = _frame()
        chain = df.map_blocks(lambda x: {"a": x + 1.0}) \
                  .map_blocks(lambda a: {"b": a * 2.0})
        chain.blocks()
        assert chain._plan_info is None


# ---------------------------------------------------------------------------
# the point of it all: fewer dispatches
# ---------------------------------------------------------------------------

class TestDispatchReduction:
    def test_fused_chain_is_one_dispatch_per_block(self, monkeypatch):
        df = _frame(parts=4)
        df.cache()

        def pipeline_units(build):
            before = counters.get("pipeline.submitted") \
                + counters.get("pipeline.drained")
            build().blocks()
            return (counters.get("pipeline.submitted")
                    + counters.get("pipeline.drained")) - before

        fused_units = pipeline_units(lambda: (
            df.map_blocks(lambda x: {"a": x + 1.0})
              .map_blocks(lambda a: {"b": a * 2.0})
              .map_blocks(lambda b: {"c": b - 1.0})
              .map_blocks(lambda c: {"d": c * 0.5})))
        monkeypatch.setenv("TFT_FUSE", "0")
        unfused_units = pipeline_units(lambda: (
            df.map_blocks(lambda x: {"a": x + 1.0})
              .map_blocks(lambda a: {"b": a * 2.0})
              .map_blocks(lambda b: {"c": b - 1.0})
              .map_blocks(lambda c: {"d": c * 0.5})))
        # 4 ops over 4 blocks: per-op streams 4x the blocks the fused
        # single stage does
        assert unfused_units >= 4 * fused_units > 0

    def test_device_resident_stage_chaining(self, monkeypatch):
        # map -> filter -> map: two stages; the second stage's input is
        # the first's device output (no host round trip). Proven by
        # bit-identity plus the stage structure in the plan rendering.
        chain = _both_ways(monkeypatch, _frame, lambda df: (
            df.map_blocks(lambda x: {"a": x * 2.0})
              .filter(lambda a: a > 10.0)
              .map_blocks(lambda a: {"b": a + 1.0})))
        text = "\n".join(chain._plan_info)
        assert "device-resident" in text
        assert "mask applied host-side" in text


# ---------------------------------------------------------------------------
# resilience composition on the fused computation
# ---------------------------------------------------------------------------

class TestFusedResilience:
    def test_transient_dispatch_fault_retries_through_fused(
            self, monkeypatch):
        df = _frame()
        expected = _snapshot(df.map_blocks(lambda x: {"a": x + 1.0})
                               .map_blocks(lambda a: {"b": a * 2.0}))
        chain = df.map_blocks(lambda x: {"a": x + 1.0}) \
                  .map_blocks(lambda a: {"b": a * 2.0})
        with faults.inject("dispatch", fail_n=2):
            got = _snapshot(chain)
        assert chain._plan_info, "fused path expected"
        _assert_identical(got, expected)

    def test_oom_split_operates_on_fused_map_rows(self, monkeypatch):
        # a pure-map_rows stage keeps the padding executor, so the
        # reactive OOM split recovers the fused computation too
        df = tft.frame({"x": np.arange(64.0)}, num_partitions=1)
        expected = _snapshot(df.map_rows(lambda x: {"a": x + 1.0})
                               .map_rows(lambda a: {"b": a * 2.0}))
        before = counters.get("oom_split.dispatches")
        chain = df.map_rows(lambda x: {"a": x + 1.0}) \
                  .map_rows(lambda a: {"b": a * 2.0})
        with faults.inject("oom", fail_n=1):
            got = _snapshot(chain)
        assert chain._plan_info, "fused path expected"
        assert counters.get("oom_split.dispatches") > before
        _assert_identical(got, expected)

    def test_oom_on_unsplittable_stage_falls_back_to_per_op(self):
        # a stage with a filter member cannot legally split; an OOM
        # there must hand the forcing back to the per-op path (which
        # recovers with its op-granular machinery) instead of failing
        # a query TFT_FUSE=0 survives
        df = tft.frame({"v": np.arange(64.0)}, num_partitions=1)
        expected = (np.arange(64.0) + 1.0) * 2.0
        chain = df.map_rows(lambda v: {"a": v + 1.0}) \
                  .filter(lambda a: a > 0.0) \
                  .map_rows(lambda a: {"b": a * 2.0})
        before = counters.get("plan.oom_fallbacks")
        with faults.inject("oom", fail_n=1):
            out = chain.blocks()
        assert counters.get("plan.oom_fallbacks") > before
        got = np.concatenate([b.columns["b"] for b in out])
        assert np.array_equal(got, expected)

    def test_permanent_fault_still_raises(self):
        df = _frame()
        chain = df.map_blocks(lambda x: {"a": x + 1.0}) \
                  .map_blocks(lambda a: {"b": a * 2.0})
        with faults.inject("dispatch", fail_n=100):
            with pytest.raises(Exception):
                chain.blocks()


# ---------------------------------------------------------------------------
# plan-derived estimates (serve admission input)
# ---------------------------------------------------------------------------

class TestPlanEstimates:
    def test_select_prices_per_column_not_schema_ratio(self):
        rows = 1000
        df = tft.frame({"x": np.arange(float(rows)),
                        "v": np.ones((rows, 8))}, num_partitions=2)
        sel = df.select(["x"])
        est_rows, est_bytes = frame_estimate(sel)
        assert est_rows == rows
        # per-column accounting: exactly x's bytes, not total * ratio
        assert est_bytes == rows * 8

    def test_map_adds_fetch_bytes(self):
        rows = 500
        df = tft.frame({"x": np.arange(float(rows))}, num_partitions=2)
        chain = df.map_blocks(lambda x: {"a": x + 1.0})
        est_rows, est_bytes = frame_estimate(chain)
        assert est_rows == rows
        assert est_bytes == 2 * rows * 8  # x + the new fetch column

    def test_unforced_serve_estimate_comes_from_plan(self):
        # what serve.scheduler._estimate consumes for admission. A
        # long-string column makes the old ratio heuristic (strings
        # count an 8-byte pointer in schema_row_bytes) wildly wrong;
        # the per-column model subtracts the string's MEASURED bytes.
        rows = 256
        df = tft.frame({"x": np.arange(float(rows)),
                        "pad": np.ones((rows, 16))}, num_partitions=2)
        chain = df.select(["x"]).map_blocks(lambda x: {"a": x * 2.0})
        assert chain._cache is None
        # the plan node is the source of truth: zero out the scalar
        # hints the pre-plan heuristic lived on and the estimate is
        # still exact, per column
        chain._rows_hint = None
        chain._bytes_hint = None
        est_rows, est_bytes = frame_estimate(chain)
        assert est_rows == rows
        assert est_bytes == 2 * rows * 8  # x + a; pad pruned away

    def test_filter_estimate_is_upper_bound(self):
        df = tft.frame({"x": np.arange(100.0)}, num_partitions=2)
        chain = df.filter(lambda x: x > 1e9) \
                  .map_blocks(lambda x: {"a": x + 1.0})
        est_rows, _ = frame_estimate(chain)
        assert est_rows == 100  # upper bound, same contract as before


# ---------------------------------------------------------------------------
# explain() renders the plan
# ---------------------------------------------------------------------------

class TestExplain:
    def test_plan_section_in_explain(self):
        df = _frame()
        chain = df.map_blocks(lambda x: {"a": x + 1.0}) \
                  .filter(lambda a: a > 2.0) \
                  .map_blocks(lambda a: {"b": a * 2.0})
        tracing.enable()
        try:
            chain.blocks()
            report = chain.explain()
        finally:
            tracing.disable()
        assert "plan" in report
        assert "fused stage" in report
        assert "1 dispatch/block" in report

    def test_no_plan_section_when_fusion_off(self, monkeypatch):
        monkeypatch.setenv("TFT_FUSE", "0")
        df = _frame()
        chain = df.map_blocks(lambda x: {"a": x + 1.0}) \
                  .map_blocks(lambda a: {"b": a * 2.0})
        tracing.enable()
        try:
            chain.blocks()
            report = chain.explain()
        finally:
            tracing.disable()
        assert "fused stage" not in report


# ---------------------------------------------------------------------------
# parquet pruning end to end
# ---------------------------------------------------------------------------

class TestParquetPruning:
    @pytest.fixture
    def six_col_file(self, tmp_path):
        path = str(tmp_path / "six.parquet")
        cols = {f"c{i}": np.arange(40.0) + 10 * i for i in range(6)}
        tio.write_parquet(tft.frame(cols, num_partitions=4), path)
        return path, cols

    def test_chain_reads_only_referenced_columns(self, six_col_file,
                                                 monkeypatch):
        path, cols = six_col_file
        decoded = []
        import tensorframes_tpu.io as io_mod
        real = io_mod._column_to_numpy
        monkeypatch.setattr(io_mod, "_column_to_numpy",
                            lambda col, name: decoded.append(name)
                            or real(col, name))
        chain = tio.read_parquet(path) \
            .map_blocks(lambda c1, c4: {"s": c1 + c4}).select(["s"])
        out = chain.blocks()
        assert chain._plan_info
        assert "pruned" in "\n".join(chain._plan_info)
        assert set(decoded) == {"c1", "c4"}
        got = np.concatenate([b.columns["s"] for b in out])
        assert np.array_equal(got, cols["c1"] + cols["c4"])

    def test_pruned_chain_equals_unfused(self, six_col_file, monkeypatch):
        path, _ = six_col_file
        _both_ways(
            monkeypatch, lambda: tio.read_parquet(path),
            lambda df: (df.map_blocks(lambda c0, c2: {"s": c0 * c2})
                          .filter(lambda s: s > 100.0)
                          .select(["s", "c0"])))

    def test_select_only_chain_prunes_scan(self, six_col_file,
                                           monkeypatch):
        path, cols = six_col_file
        decoded = []
        import tensorframes_tpu.io as io_mod
        real = io_mod._column_to_numpy
        monkeypatch.setattr(io_mod, "_column_to_numpy",
                            lambda col, name: decoded.append(name)
                            or real(col, name))
        sel = tio.read_parquet(path).select(["c3"])
        out = sel.blocks()
        assert set(decoded) == {"c3"}
        assert np.array_equal(
            np.concatenate([b.columns["c3"] for b in out]), cols["c3"])

    def test_empty_row_group_with_pruned_mid_select(self, tmp_path,
                                                    monkeypatch):
        # regression: a 0-row row group's replay walks the per-op chain,
        # whose mid-chain select names a PRUNED column — the empty leaf
        # block must be widened back to the full leaf schema first
        path = str(tmp_path / "er.parquet")
        src = tft.frame({"a": np.arange(3.0), "b": np.ones(3)},
                        num_partitions=1).repartition(4)  # one 0-row blk
        tio.write_parquet(src, path)
        _both_ways(
            monkeypatch, lambda: tio.read_parquet(path),
            lambda df: (df.select(["a", "b"])
                          .map_rows(lambda a: {"x": a * 2.0})
                          .select(["x"])))

    def test_forcing_leaf_directly_reads_everything(self, six_col_file):
        path, cols = six_col_file
        df = tio.read_parquet(path)
        blocks = df.blocks()
        assert set(blocks[0].columns) == set(cols)
        assert df.num_partitions == 4


class TestPredicateExtraction:
    """Atom extraction must not fail silently: a jax API drift once
    turned every filter into "no atoms" and switched pushdown off."""

    def test_plain_filter_extracts_without_failure(self):
        from tensorframes_tpu.engine.ops import cached_map_computation
        from tensorframes_tpu.plan.predicates import extract_atoms

        df = tft.frame({"x": np.arange(8.0)})
        comp = cached_map_computation(lambda x: {"keep": x > 3.0},
                                      df.schema, block_level=True)
        before = counters.snapshot().get("plan.predicate_extract_failures",
                                         0)
        atoms = extract_atoms(comp)
        assert [(a.column, a.op, a.value) for a in atoms] == \
            [("x", "gt", 3.0)]
        assert counters.snapshot().get("plan.predicate_extract_failures",
                                       0) == before

    def test_extraction_failure_is_counted(self, monkeypatch):
        from tensorframes_tpu.engine.ops import cached_map_computation
        from tensorframes_tpu.plan import predicates

        def broken(comp):
            raise ImportError("symbol removed from jax")

        monkeypatch.setattr(predicates, "_extract", broken)
        df = tft.frame({"x": np.arange(8.0)})
        comp = cached_map_computation(lambda x: {"keep": x > 4.0},
                                      df.schema, block_level=True)
        before = counters.snapshot().get("plan.predicate_extract_failures",
                                         0)
        assert predicates.extract_atoms(comp) == []
        assert counters.snapshot()["plan.predicate_extract_failures"] == \
            before + 1
