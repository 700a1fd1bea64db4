"""Query-scoped observability suite (tier-1; marker ``observability``).

Proves the PR-3 contract end-to-end on CPU: query-id correlation across
the pipeline (including worker threads), chrome-trace export validity,
Prometheus text-format rendering + escaping, ring-buffer bounding, the
explain()/counters consistency, the gauge stat-family fix, the merged
stats report, profile()/span() exception safety — and that with tracing
disabled the event layer records nothing at all.

The mesh/device half (this PR): per-device shard events and Perfetto
tracks for the distributed ops, self-describing ``traced_query``
metadata, straggler-ratio mesh sections in ``explain()``, HBM watermark
sampling (graceful None on CPU; fake devices prove the recording),
OOM-split watermark tagging, Prometheus histogram families, and the
``TFT_SLOW_QUERY_MS`` slow-query log.
"""

import json
import re
import threading
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import tensorframes_tpu as tft
from tensorframes_tpu import dtypes as _dt
from tensorframes_tpu import observability as obs
from tensorframes_tpu.computation import Computation, TensorSpec
from tensorframes_tpu.engine.executor import BlockExecutor
from tensorframes_tpu.observability import device as obs_device
from tensorframes_tpu.observability import events as obs_events
from tensorframes_tpu.parallel.distributed import (daggregate, dfilter,
                                                   dmap_blocks,
                                                   dreduce_blocks, dsort,
                                                   distribute)
from tensorframes_tpu.parallel.mesh import local_mesh
from tensorframes_tpu.resilience import faults
from tensorframes_tpu.shape import Shape, Unknown
from tensorframes_tpu.utils import tracing

pytestmark = pytest.mark.observability


@pytest.fixture(autouse=True)
def _clean_observability():
    tracing.disable()
    tracing.timings.reset()
    tracing.counters.reset()
    tracing.histograms.reset()
    obs.clear_ring()
    obs_events._reset_last_query()
    obs_device._reset()
    yield
    tracing.disable()
    tracing.timings.reset()
    tracing.counters.reset()
    tracing.histograms.reset()
    obs.clear_ring()
    obs_events._reset_last_query()
    obs_device._reset()


def _depth(monkeypatch, d):
    monkeypatch.setenv("TFT_PIPELINE_DEPTH", str(d))


def _traced_map(monkeypatch, n=30, parts=6, depth=3):
    _depth(monkeypatch, depth)
    tracing.enable()
    df = tft.frame({"x": np.arange(float(n))}, num_partitions=parts)
    out = df.map_blocks(lambda x: {"y": x + 1.0})
    out.blocks()
    return df, out, out._trace


# ---------------------------------------------------------------------------
# correlation / context propagation
# ---------------------------------------------------------------------------

class TestCorrelation:
    def test_forcing_opens_query_trace(self, monkeypatch):
        _, out, t = _traced_map(monkeypatch)
        assert t is not None
        assert t.op == "map_blocks"
        assert re.fullmatch(r"q\d+", t.query_id)
        assert t.duration is not None and t.duration >= 0

    def test_query_ids_unique_per_query(self, monkeypatch):
        _, _, t1 = _traced_map(monkeypatch)
        _, _, t2 = _traced_map(monkeypatch)
        assert t1.query_id != t2.query_id

    def test_nested_forcings_join_outer_query(self, monkeypatch):
        # a chained lazy plan forces upstream frames inside one query:
        # exactly ONE trace, owned by the outermost forcing
        _depth(monkeypatch, 3)
        tracing.enable()
        df = tft.frame({"x": np.arange(20.0)}, num_partitions=4)
        mid = df.map_blocks(lambda x: {"y": x + 1.0})
        top = mid.map_blocks(lambda y: {"z": y * 2.0})
        top.blocks()
        assert top._trace is not None
        assert mid._trace is None  # joined the ambient query
        assert obs.last_query() is top._trace

    def test_query_id_survives_worker_threads(self):
        tracing.enable()
        seen = []
        with obs.query_trace("threaded") as t:
            with ThreadPoolExecutor(max_workers=2) as pool:
                fs = [pool.submit(obs.wrap_context(obs.current_trace))
                      for _ in range(4)]
                seen = [f.result() for f in fs]
            # an UNwrapped hop must not see the trace (that would mean
            # thread-inherited globals, not context propagation)
            with ThreadPoolExecutor(max_workers=1) as pool:
                bare = pool.submit(obs.current_trace).result()
        assert all(s is t for s in seen)
        assert bare is None

    def test_pipeline_worker_thread_events_attach_to_query(self,
                                                           monkeypatch):
        """An executor that dispatches on its own worker thread (via
        wrap_context) records events that land on the submitting query's
        trace."""
        _depth(monkeypatch, 3)
        tracing.enable()
        inner = BlockExecutor()
        worker_qids = []

        class ThreadedExecutor:
            pad_rows = False

            def __init__(self):
                self._pool = ThreadPoolExecutor(max_workers=1)

            @property
            def compile_count(self):
                return inner.compile_count

            def run(self, comp, arrays, pad_ok=True):
                return inner.run(comp, arrays, pad_ok=pad_ok)

            def submit(self, comp, arrays, pad_ok=True):
                def work():
                    t = obs.current_trace()
                    worker_qids.append(t.query_id if t else None)
                    obs.add_event("worker_dispatch")
                    return inner.run(comp, arrays, pad_ok=pad_ok)

                fut = self._pool.submit(obs.wrap_context(work))

                class P:
                    def drain(self):
                        return fut.result()

                return P()

            def clear(self):
                inner.clear()

        df = tft.frame({"x": np.arange(24.0)}, num_partitions=6)
        out = df.map_blocks(lambda x: {"y": x - 1.0},
                            executor=ThreadedExecutor())
        got = np.asarray([r["y"] for r in out.collect()], float).ravel()
        np.testing.assert_array_equal(got, np.arange(24.0) - 1.0)
        t = out._trace
        assert t is not None
        assert worker_qids == [t.query_id] * 6
        assert t.count("worker_dispatch") == 6

    def test_eager_reduce_records_last_query(self, monkeypatch):
        _depth(monkeypatch, 3)
        tracing.enable()
        df = tft.frame({"x": np.arange(12.0)}, num_partitions=3)
        val = tft.reduce_blocks(lambda x_input: {"x": x_input.sum(0)}, df)
        assert float(val) == float(np.arange(12.0).sum())
        t = obs.last_query()
        assert t is not None and t.op == "reduce_blocks"
        assert "reduce_blocks" in tft.last_query_report()


# ---------------------------------------------------------------------------
# chrome trace export
# ---------------------------------------------------------------------------

class TestChromeTrace:
    def test_chrome_trace_valid_and_sorted(self, monkeypatch, tmp_path):
        _, out, t = _traced_map(monkeypatch, n=30, parts=6, depth=3)
        path = tmp_path / "trace.json"
        text = t.to_chrome_trace(file=str(path))
        doc = json.loads(text)
        assert json.loads(path.read_text()) == doc
        evs = doc["traceEvents"]
        assert evs, "no events exported"
        for e in evs:
            for field in ("ph", "ts", "pid", "tid"):
                assert field in e, (field, e)
        ts = [e["ts"] for e in evs]
        assert ts == sorted(ts)

    def test_per_block_events_on_slot_tracks_share_query_id(
            self, monkeypatch):
        _, out, t = _traced_map(monkeypatch, n=30, parts=6, depth=3)
        doc = json.loads(t.to_chrome_trace())
        evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
        assert {e["args"]["query_id"] for e in evs} == {t.query_id}
        by_cat = {}
        for e in evs:
            by_cat.setdefault(e.get("cat"), []).append(e)
        assert len(by_cat["block_submit"]) == 6
        assert len(by_cat["block_compute"]) == 6
        assert len(by_cat["block_drain"]) == 6
        # per-slot tracks: depth 3 -> tids 1..3, plus the query track 0
        block_tids = {e["tid"] for cat in ("block_submit", "block_drain")
                      for e in by_cat[cat]}
        assert block_tids == {1, 2, 3}
        # slot thread names exported for perfetto
        names = {e["args"]["name"] for e in doc["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        assert {"query", "slot 0", "slot 1", "slot 2"} <= names

    def test_serial_depth_records_block_runs(self, monkeypatch):
        _, out, t = _traced_map(monkeypatch, n=12, parts=3, depth=1)
        assert t.count("block_run") == 3
        s = t.summary()
        assert s["blocks"] == 3 and s["rows_in"] == 12


# ---------------------------------------------------------------------------
# explain() / summary vs counters
# ---------------------------------------------------------------------------

class TestExplain:
    def test_explain_counts_match_counters(self, monkeypatch):
        base = tracing.counters.snapshot()
        _, out, t = _traced_map(monkeypatch, n=30, parts=6, depth=3)
        s = t.summary()
        now = tracing.counters.snapshot()

        def delta(name):
            return now.get(name, 0) - base.get(name, 0)

        assert s["blocks"] == delta("pipeline.submitted") == 6
        assert s["rows_in"] == 30 and s["rows_out"] == 30
        assert s["bytes_in"] == 30 * np.dtype(float).itemsize
        assert s["sync_fallbacks"] == delta("pipeline.sync_fallbacks") == 0
        assert s["compile_misses"] == delta("compile_cache.misses")
        assert s["compile_hits"] == delta("compile_cache.hits")
        report = out.explain()
        assert "30 in / 30 out" in report
        assert "6 block(s)" in report
        assert t.query_id in report
        assert "wall time by stage" in report

    def test_explain_reports_sync_fallbacks(self, monkeypatch):
        _depth(monkeypatch, 3)
        tracing.enable()
        base = tracing.counters.snapshot()
        df = tft.frame({"x": np.arange(20.0)}, num_partitions=4)
        out = df.map_blocks(lambda x: {"y": x + 1.0})
        # two async submit faults -> two blocks recover through the sync
        # fallback path; the trace must agree with the global counters
        with faults.inject("dispatch", fail_n=2):
            out.blocks()
        got = np.asarray([r["y"] for r in out.collect()], float).ravel()
        np.testing.assert_array_equal(got, np.arange(20.0) + 1.0)
        t = out._trace
        s = t.summary()
        now = tracing.counters.snapshot()

        def delta(name):
            return now.get(name, 0) - base.get(name, 0)

        assert s["sync_fallbacks"] == delta("pipeline.sync_fallbacks") == 2
        fb = [e for e in t.events if e.etype == "sync_fallback"]
        assert [e.args["error"] for e in fb] == ["InjectedFault"] * 2
        assert "2 sync fallback(s)" in out.explain()

    def test_explain_reports_retries_with_classified_error(
            self, monkeypatch):
        _depth(monkeypatch, 1)  # serial path: the fault hits the retry
        monkeypatch.setenv("TFT_RETRY_BASE_DELAY", "0.001")
        tracing.enable()
        base = tracing.counters.snapshot()
        df = tft.frame({"x": np.arange(12.0)}, num_partitions=3)
        out = df.map_blocks(lambda x: {"y": x + 1.0})
        with faults.inject("dispatch", fail_n=1):
            out.blocks()
        t = out._trace
        s = t.summary()
        now = tracing.counters.snapshot()
        delta = (now.get("retry.executor.dispatch.retries", 0)
                 - base.get("retry.executor.dispatch.retries", 0))
        assert s["retries"] == delta == 1
        retry = [e for e in t.events if e.etype == "retry"][0]
        assert retry.args["error"] == "InjectedFault"
        assert retry.args["kind"] == "transient"
        assert "1 retried" in out.explain()

    def test_explain_forces_untraced_frame(self, monkeypatch):
        _depth(monkeypatch, 3)
        df = tft.frame({"x": np.arange(10.0)}, num_partitions=2)
        out = df.map_blocks(lambda x: {"y": x * 3.0})
        out.blocks()  # forced with tracing OFF: no trace recorded
        assert out._trace is None
        report = out.explain()  # re-forces once, tracing temporarily on
        assert out._trace is not None
        assert "map_blocks" in report
        assert not tracing.enabled()  # restored

    def test_last_query_report_without_queries(self):
        assert "no query recorded" in tft.last_query_report()


# ---------------------------------------------------------------------------
# sinks: ring buffer + JSONL file
# ---------------------------------------------------------------------------

class TestSinks:
    def test_ring_buffer_bounded_under_10k_events(self, monkeypatch):
        monkeypatch.setenv("TFT_TRACE_RING", "1000")
        obs.clear_ring()
        tracing.enable()
        with obs.query_trace("flood") as t:
            for i in range(10_500):
                t.add("tick", i=i)
        ring = obs.recent_events()
        assert len(ring) == 1000  # bounded, newest kept
        assert ring[-1]["i"] == 10_499
        assert t.dropped == 0  # per-trace bound is separate

    def test_per_trace_event_bound_drops_and_counts(self):
        tracing.enable()
        with obs.query_trace("flood") as t:
            t._max_events = 10
            for i in range(25):
                t.add("tick", i=i)
        assert len(t.events) == 10
        assert t.dropped == 15
        assert tracing.counters.get("trace.events_dropped") == 15
        assert "+15 dropped" in t.report()

    def test_jsonl_file_sink(self, monkeypatch, tmp_path):
        path = tmp_path / "events.jsonl"
        monkeypatch.setenv("TFT_TRACE_FILE", str(path))
        _, out, t = _traced_map(monkeypatch, n=12, parts=3, depth=3)
        lines = [json.loads(line) for line in
                 path.read_text().splitlines()]
        heads = [r for r in lines if r["type"] == "query"]
        assert any(h["query_id"] == t.query_id for h in heads)
        evs = [r for r in lines if r.get("query_id") == t.query_id
               and r["type"] != "query"]
        assert len(evs) == len(t.events)
        assert all("ts" in e for e in evs)


# ---------------------------------------------------------------------------
# Prometheus metrics
# ---------------------------------------------------------------------------

_PROM_LINE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*='
    r'"(?:[^"\\\n]|\\["\\n])*"(,[a-zA-Z_][a-zA-Z0-9_]*='
    r'"(?:[^"\\\n]|\\["\\n])*")*\})? '
    r'(-?\d+(\.\d+)?([eE][+-]?\d+)?|[+-]Inf|NaN)$')


class TestMetrics:
    def test_metrics_text_parses_as_prometheus(self, monkeypatch):
        _traced_map(monkeypatch)
        text = obs.metrics_text()
        assert text.endswith("\n")
        for line in text.splitlines():
            if not line or line.startswith("#"):
                continue
            assert _PROM_LINE.match(line), line
        assert 'tft_counter_total{name="pipeline.submitted"} 6' \
            in text
        assert 'tft_span_seconds_count{span="pipeline.submit"} 6' in text
        assert 'tft_gauge{name="pipeline.occupancy",stat="mean"}' in text
        assert "tft_trace_ring_events" in text

    def test_label_escaping(self):
        tracing.counters.inc('weird"name\\with\nnasties')
        text = obs.metrics_text()
        line = next(ln for ln in text.splitlines()
                    if "weird" in ln)
        assert _PROM_LINE.match(line), line
        assert '\\"' in line and "\\\\" in line and "\\n" in line
        assert "\n" not in line  # the raw newline never leaks through

    def test_endpoint_serves_metrics_on_loopback(self):
        tracing.counters.inc("endpoint.smoke")
        port = obs.serve_metrics(0)
        try:
            assert obs.metrics_port() == port
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
                assert r.status == 200
                assert "text/plain" in r.headers["Content-Type"]
                body = r.read().decode()
            assert 'tft_counter_total{name="endpoint.smoke"} 1' in body
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/nope", timeout=10)
        finally:
            obs.stop_metrics()
        assert obs.metrics_port() is None

    def test_rebind_to_different_port_raises(self):
        port = obs.serve_metrics(0)
        try:
            assert obs.serve_metrics(0) == port  # idempotent
            assert obs.serve_metrics(port) == port
            with pytest.raises(RuntimeError, match="already running"):
                obs.serve_metrics(port + 1)  # silently dead scrape target
        finally:
            obs.stop_metrics()


# ---------------------------------------------------------------------------
# zero-cost-when-off
# ---------------------------------------------------------------------------

class TestZeroCostWhenOff:
    def test_no_events_recorded_with_tracing_disabled(self, monkeypatch):
        _depth(monkeypatch, 3)
        assert not tracing.enabled()
        df = tft.frame({"x": np.arange(20.0)}, num_partitions=4)
        out = df.map_blocks(lambda x: {"y": x + 1.0})
        out.blocks()
        tft.reduce_blocks(lambda x_input: {"x": x_input.sum(0)}, df)
        assert out._trace is None
        assert obs.last_query() is None
        assert obs.recent_events() == []
        assert obs.current_trace() is None
        assert tracing.timings.snapshot() == {}

    def test_add_event_without_trace_is_noop(self):
        obs.add_event("orphan", detail="nothing listens")
        assert obs.recent_events() == []

    def test_bypass_strips_layer_even_when_enabled(self):
        tracing.enable()
        with obs_events.bypass():
            with obs.query_trace("stripped") as t:
                assert t is None
        assert obs.last_query() is None


# ---------------------------------------------------------------------------
# satellite: gauge stat family + merged report + dump_stats
# ---------------------------------------------------------------------------

class TestStatsSatellites:
    def test_gauge_has_own_stat_family(self):
        tracing.enable()
        for v in (1.0, 3.0, 2.0):
            tracing.gauge("my.level", v)
        snap = tracing.timings.snapshot()
        g = snap["my.level"]
        assert g == {"count": 3, "mean": 2.0, "min": 1.0, "max": 3.0,
                     "last": 2.0}
        assert "mean_s" not in g  # no vestigial seconds suffix

    def test_occupancy_legacy_aliases_removed(self):
        # the pre-0.2 duration-suffixed aliases were kept for exactly
        # one release (PR 3); they are gone now, as promised
        tracing.enable()
        tracing.gauge("pipeline.occupancy", 2.0)
        tracing.gauge("pipeline.occupancy", 4.0)
        occ = tracing.timings.snapshot()["pipeline.occupancy"]
        assert occ["mean"] == 3.0 and occ["last"] == 4.0
        assert "mean_s" not in occ
        assert "min_s" not in occ
        assert "max_s" not in occ

    def test_report_merges_counters_and_gauges(self):
        tracing.enable()
        with tracing.span("stagey"):
            pass
        tracing.gauge("leveley", 5.0)
        tracing.counters.inc("county.things", 3)
        rep = tracing.timings.report()
        assert "stagey" in rep
        assert "leveley" in rep
        assert "county.things" in rep
        assert "gauge" in rep and "counter" in rep

    def test_dump_stats_prints_everything(self, capsys):
        tracing.enable()
        with tracing.span("dumped.span"):
            pass
        tracing.gauge("dumped.gauge", 1.0)
        tracing.counters.inc("dumped.counter")
        tft.dump_stats()
        out = capsys.readouterr().out
        for name in ("dumped.span", "dumped.gauge", "dumped.counter"):
            assert name in out


# ---------------------------------------------------------------------------
# mesh & device observability
# ---------------------------------------------------------------------------

def _mesh_comp(factor=2.0):
    return Computation.trace(
        lambda x: {"y": x * factor},
        [TensorSpec("x", _dt.double, Shape(Unknown))])


def _mesh_fixture(n=64):
    mesh = local_mesh()
    df = tft.frame({"x": np.arange(float(n))})
    dist = distribute(df, mesh)
    return mesh, dist


class TestMeshObservability:
    def test_dmap_records_shard_events_and_entry_meta(self):
        tracing.enable()
        mesh, dist = _mesh_fixture()
        S = mesh.num_data_shards
        dmap_blocks(_mesh_comp(), dist)
        t = obs.last_query()
        assert t is not None and t.op == "dmap_blocks"
        # traced_query entry metadata: self-describing, not a bare name
        assert t.meta["shards"] == S
        assert t.meta["mesh_shape"] == dict(mesh.mesh.shape)
        assert t.meta["fetches"] == ["y"]
        assert t.meta["rows"] == 64
        # one shard event and one readiness timing per data shard
        assert t.count("shard") == S
        assert t.count("shard_compute") == S
        assert t.count("mesh_dispatch") == 1
        s = t.summary()
        assert s["mesh"] is not None
        devs = s["mesh"]["devices"]
        assert set(devs) == set(range(S))
        assert all(d["rows"] == 64 // S for d in devs.values())
        assert all(d["bytes"] > 0 for d in devs.values())
        assert all(d["time_s"] >= 0.0 for d in devs.values())

    def test_chrome_trace_one_track_per_device(self):
        tracing.enable()
        mesh, dist = _mesh_fixture()
        dmap_blocks(_mesh_comp(), dist)
        t = obs.last_query()
        doc = json.loads(t.to_chrome_trace())
        names = {e["args"]["name"] for e in doc["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        want = {f"device {i}" for i in range(mesh.num_data_shards)}
        assert want <= names
        # device events land on device tracks (tid >= DEVICE_TRACK_BASE)
        dev_tids = {e["tid"] for e in doc["traceEvents"]
                    if e.get("cat") in ("shard", "shard_compute")}
        assert dev_tids == {obs.DEVICE_TRACK_BASE + i
                            for i in range(mesh.num_data_shards)}

    def test_explain_mesh_section_with_straggler_ratio(self):
        tracing.enable()
        mesh, dist = _mesh_fixture()
        dmap_blocks(_mesh_comp(), dist)
        report = tft.last_query_report()
        assert "mesh" in report
        assert "straggler ratio" in report
        for i in range(mesh.num_data_shards):
            assert f"device {i}:" in report
        ratio = obs.last_query().summary()["mesh"]["straggler_ratio"]
        assert ratio is None or ratio >= 1.0

    def test_skew_warning_above_threshold(self, monkeypatch):
        monkeypatch.setenv("TFT_SKEW_WARN", "1.5")
        tracing.enable()
        with obs.query_trace("skewed") as t:
            for i in range(4):
                t.add("shard", device=i, rows=10, bytes=80,
                      track=obs.DEVICE_TRACK_BASE + i)
                t.add("shard_compute", device=i, ts=0.0,
                      dur=1.0 if i == 3 else 0.1,
                      track=obs.DEVICE_TRACK_BASE + i)
        report = obs.render(t)
        assert "WARNING" in report and "imbalance" in report
        assert t.summary()["mesh"]["straggler_ratio"] == pytest.approx(10.0)

    def test_mesh_ops_record_collectives(self):
        tracing.enable()
        mesh, dist = _mesh_fixture()
        dreduce_blocks({"x": "sum"}, dist)
        t = obs.last_query()
        assert t.op == "dreduce_blocks"
        coll = [e for e in t.events if e.etype == "collective"]
        assert [e.name for e in coll] == ["psum"]
        dsort("x", dist)
        t = obs.last_query()
        names = {e.name for e in t.events if e.etype == "collective"}
        assert ({"all_to_all", "ppermute"} <= names
                or mesh.num_data_shards == 1)

    def test_dfilter_and_daggregate_record_mesh_events(self):
        tracing.enable()
        mesh, dist = _mesh_fixture()
        S = mesh.num_data_shards
        pred = Computation.trace(
            lambda x: {"keep": x < 32.0},
            [TensorSpec("x", _dt.double, Shape(Unknown))])
        dfilter(pred, dist)
        t = obs.last_query()
        assert t.op == "dfilter" and t.count("shard") == S
        assert t.count("mesh_dispatch") == 1
        df2 = tft.frame({"k": np.arange(16) % 4,
                         "v": np.arange(16.0)})
        dist2 = distribute(df2, mesh)
        daggregate({"v": "sum"}, dist2, "k")
        t = obs.last_query()
        assert t.op == "daggregate"
        assert t.meta["keys"] == ["k"] and t.meta["fetches"] == ["v"]
        assert t.count("collective") == 1
        assert t.count("mesh_dispatch") == 1

    def test_interleaved_queries_distinct_ids_no_track_collisions(self):
        tracing.enable()
        mesh, dist = _mesh_fixture(n=32)
        comp = _mesh_comp()
        dmap_blocks(comp, dist)  # warm the jit so both workers overlap
        barrier = threading.Barrier(2)

        def worker(i):
            barrier.wait()
            with obs.query_trace(f"interleaved") as t:
                dmap_blocks(comp, dist)
            return t

        with ThreadPoolExecutor(max_workers=2) as pool:
            ts = list(pool.map(worker, range(2)))
        assert all(t is not None for t in ts)
        assert ts[0].query_id != ts[1].query_id
        for t in ts:
            doc = json.loads(t.to_chrome_trace())
            evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
            # every event carries exactly this trace's correlation id
            assert {e["args"]["query_id"] for e in evs} == {t.query_id}
            tracks = [(e["tid"], e["args"]["name"])
                      for e in doc["traceEvents"]
                      if e["ph"] == "M" and e["name"] == "thread_name"]
            assert len(tracks) == len(set(tracks))  # no collisions
            assert t.count("shard") == mesh.num_data_shards

    def test_ring_allreduce_records_collective_event(self):
        import jax

        from tensorframes_tpu.parallel.ring import ring_allreduce
        tracing.enable()
        mesh = local_mesh()
        n = mesh.num_data_shards
        x = jax.device_put(np.arange(float(n * 4)).reshape(n, 4),
                           mesh.row_sharding(2))
        with obs.query_trace("ring") as t:
            out = ring_allreduce(x, mesh)
        np.testing.assert_allclose(
            np.asarray(out)[0], np.asarray(x).sum(axis=0))
        ev = [e for e in t.events if e.etype == "collective"]
        assert len(ev) == 1 and ev[0].name == "ring_allreduce"
        assert ev[0].args["hops"] == 2 * (n - 1)
        assert ev[0].dur is not None and ev[0].dur >= 0.0

    def test_mesh_ops_record_nothing_with_tracing_off(self):
        assert not tracing.enabled()
        mesh, dist = _mesh_fixture(n=16)
        dmap_blocks(_mesh_comp(), dist)
        dreduce_blocks({"x": "sum"}, dist)
        assert obs.last_query() is None
        assert obs.recent_events() == []


# ---------------------------------------------------------------------------
# device memory (HBM watermarks)
# ---------------------------------------------------------------------------

class _FakeDevice:
    def __init__(self, live, peak):
        self._stats = {"bytes_in_use": live, "peak_bytes_in_use": peak}

    def memory_stats(self):
        return self._stats


class TestDeviceMemory:
    def test_cpu_backend_is_a_graceful_none(self):
        # the real CPU backend reports nothing (or an empty dict):
        # sampling must return None and latch off, never raise
        tracing.enable()
        with obs.query_trace("probe") as t:
            got = obs_device.sample(t, "probe")
        if got is None:
            assert not obs_device.supported()
        else:  # a backend that DOES report stats records the event
            assert t.count("hbm_sample") >= 1

    def test_fake_devices_record_watermarks_in_explain(self, monkeypatch):
        monkeypatch.setattr(obs_device, "_local_devices",
                            lambda: [_FakeDevice(100, 300),
                                     _FakeDevice(50, 200)])
        obs_device._reset()
        tracing.enable()
        df = tft.frame({"x": np.arange(8.0)}, num_partitions=2)
        out = df.map_blocks(lambda x: {"y": x + 1.0})
        out.blocks()
        t = out._trace
        s = t.summary()
        assert s["hbm"] is not None
        assert s["hbm"]["peak"] == 500  # summed across devices
        assert s["hbm"]["live_start"] == 150
        report = out.explain()
        assert "peak HBM" in report
        # per-device samples land on the device tracks at query start/end
        per_dev = [e for e in t.events if e.etype == "hbm_sample"
                   and (e.args or {}).get("device") is not None]
        assert {e.args["device"] for e in per_dev} == {0, 1}

    def test_oom_split_tagged_with_watermark(self, monkeypatch):
        monkeypatch.setattr(obs_device, "_local_devices",
                            lambda: [_FakeDevice(111, 222)])
        obs_device._reset()
        tracing.enable()
        df = tft.frame({"x": np.arange(16.0)}, num_partitions=1)
        out = df.map_rows(lambda x: {"y": x * 3.0})
        with faults.inject("oom", fail_n=1):
            out.blocks()
        t = out._trace
        splits = [e for e in t.events if e.etype == "oom_split"]
        assert splits and splits[0].args["hbm_peak_bytes"] == 222
        assert splits[0].args["hbm_live_bytes"] == 111

    def test_no_memory_stats_calls_with_tracing_off(self, monkeypatch):
        calls = []

        def probed():
            calls.append(1)
            return []

        monkeypatch.setattr(obs_device, "_local_devices", probed)
        obs_device._reset()
        assert not tracing.enabled()
        df = tft.frame({"x": np.arange(8.0)}, num_partitions=2)
        df.map_blocks(lambda x: {"y": x + 1.0}).blocks()
        assert calls == []  # zero-cost-when-off: no device probing at all


# ---------------------------------------------------------------------------
# satellite: Prometheus histograms
# ---------------------------------------------------------------------------

class TestHistograms:
    def test_histogram_families_valid_and_cumulative(self, monkeypatch):
        _traced_map(monkeypatch)  # one compile miss + one finished query
        text = obs.metrics_text()
        for line in text.splitlines():
            if not line or line.startswith("#"):
                continue
            assert _PROM_LINE.match(line), line
        assert "# TYPE tft_query_latency_seconds histogram" in text
        assert "# TYPE tft_compile_seconds histogram" in text
        buckets, count = [], None
        for line in text.splitlines():
            if line.startswith(
                    'tft_query_latency_seconds_bucket{op="map_blocks"'):
                buckets.append(int(line.rsplit(" ", 1)[1]))
            elif line.startswith(
                    'tft_query_latency_seconds_count{op="map_blocks"'):
                count = int(line.rsplit(" ", 1)[1])
        assert buckets, "no latency buckets rendered"
        assert buckets == sorted(buckets)  # cumulative le semantics
        assert buckets[-1] == count == 1   # +Inf bucket equals _count
        assert 'le="+Inf"' in text
        assert 'tft_compile_seconds_bucket{engine="jax",le="+Inf"} 1' \
            in text
        assert "tft_compile_seconds_sum" in text

    def test_compile_seconds_observed_even_untraced(self, monkeypatch):
        # the histogram is always-on (like counters): a compile miss with
        # tracing off still observes
        _depth(monkeypatch, 1)
        assert not tracing.enabled()
        df = tft.frame({"x": np.arange(6.0)}, num_partitions=2)
        df.map_blocks(lambda x: {"y": x - 1.0}).blocks()
        snap = tracing.histograms.snapshot()
        key = ("compile_seconds", (("engine", "jax"),))
        assert key in snap and snap[key]["count"] >= 1

    def test_counter_and_gauge_output_unchanged(self, monkeypatch):
        # byte-compatibility: the pre-histogram families render the same
        _traced_map(monkeypatch)
        text = obs.metrics_text()
        assert 'tft_counter_total{name="pipeline.submitted"} 6' in text
        assert 'tft_gauge{name="pipeline.occupancy",stat="mean"}' in text
        assert "tft_trace_ring_events" in text


# ---------------------------------------------------------------------------
# satellite: slow-query log
# ---------------------------------------------------------------------------

class TestSlowQueryLog:
    def test_logs_jsonl_with_tracing_off(self, monkeypatch, tmp_path):
        path = tmp_path / "slow.jsonl"
        monkeypatch.setenv("TFT_SLOW_QUERY_MS", "0")
        monkeypatch.setenv("TFT_TRACE_FILE", str(path))
        assert not tracing.enabled()
        df = tft.frame({"x": np.arange(8.0)}, num_partitions=2)
        df.map_blocks(lambda x: {"y": x + 1.0}).blocks()
        recs = [json.loads(line) for line in
                path.read_text().splitlines()]
        slow = [r for r in recs if r["type"] == "slow_query"]
        assert len(slow) == 1  # one condensed line, no event stream
        assert slow[0]["op"] == "map_blocks"
        assert slow[0]["duration_ms"] >= 0.0
        assert "query_id" not in slow[0]  # no trace existed

    def test_includes_summary_fields_when_traced(self, monkeypatch,
                                                 tmp_path):
        path = tmp_path / "slow.jsonl"
        monkeypatch.setenv("TFT_SLOW_QUERY_MS", "0")
        monkeypatch.setenv("TFT_TRACE_FILE", str(path))
        tracing.enable()
        df = tft.frame({"x": np.arange(12.0)}, num_partitions=3)
        out = df.map_blocks(lambda x: {"y": x + 1.0})
        out.blocks()
        recs = [json.loads(line) for line in
                path.read_text().splitlines()]
        slow = [r for r in recs if r["type"] == "slow_query"]
        assert len(slow) == 1
        assert slow[0]["query_id"] == out._trace.query_id
        assert slow[0]["blocks"] == 3
        assert slow[0]["retries"] == 0

    def test_fast_queries_stay_silent(self, monkeypatch, tmp_path):
        path = tmp_path / "slow.jsonl"
        monkeypatch.setenv("TFT_SLOW_QUERY_MS", "60000")
        monkeypatch.setenv("TFT_TRACE_FILE", str(path))
        df = tft.frame({"x": np.arange(8.0)}, num_partitions=2)
        df.map_blocks(lambda x: {"y": x + 1.0}).blocks()
        if path.exists():
            recs = [json.loads(line) for line in
                    path.read_text().splitlines()]
            assert not [r for r in recs if r["type"] == "slow_query"]

    def test_failed_query_marked_in_log_and_histogram(self, monkeypatch,
                                                      tmp_path):
        path = tmp_path / "slow.jsonl"
        monkeypatch.setenv("TFT_SLOW_QUERY_MS", "0")
        monkeypatch.setenv("TFT_TRACE_FILE", str(path))
        tracing.enable()
        with pytest.raises(RuntimeError, match="boom"):
            with obs.query_trace("doomed"):
                raise RuntimeError("boom")
        recs = [json.loads(line) for line in
                path.read_text().splitlines()]
        slow = [r for r in recs if r["type"] == "slow_query"]
        assert slow and slow[0]["error"] == "RuntimeError"
        key = ("query_latency_seconds",
               (("op", "doomed"), ("outcome", "error")))
        assert tracing.histograms.snapshot()[key]["count"] == 1
        assert obs.last_query().meta["error"] == "RuntimeError"
        # the tracing-off timer branch carries the marker too
        tracing.disable()
        with pytest.raises(ValueError):
            with obs.query_trace("doomed2"):
                raise ValueError("x")
        recs = [json.loads(line) for line in
                path.read_text().splitlines()]
        assert any(r.get("op") == "doomed2"
                   and r.get("error") == "ValueError" for r in recs)

    def test_malformed_threshold_ignored(self, monkeypatch):
        monkeypatch.setenv("TFT_SLOW_QUERY_MS", "not-a-number")
        df = tft.frame({"x": np.arange(4.0)}, num_partitions=1)
        df.map_blocks(lambda x: {"y": x + 1.0}).blocks()  # must not raise


# ---------------------------------------------------------------------------
# satellite: profile()/span() exception safety
# ---------------------------------------------------------------------------

class TestTracingExceptionSafety:
    def test_profile_stop_failure_does_not_mask_body_error(
            self, monkeypatch, tmp_path):
        import jax

        # fake session: a real one left open by the raising stop_trace
        # would wedge every later jax.profiler user in the process
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda log_dir, **k: None)
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            _raise_runtime_error)
        with pytest.raises(ValueError, match="body failed"):
            with tracing.profile(str(tmp_path)):
                raise ValueError("body failed")
        assert not tracing.enabled()

    def test_profile_stop_failure_does_not_fail_successful_body(
            self, monkeypatch, tmp_path):
        import jax

        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda log_dir, **k: None)
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            _raise_runtime_error)
        with tracing.profile(str(tmp_path)):
            pass  # succeeded; the failing stop must be swallowed+logged
        assert not tracing.enabled()

    def test_span_survives_annotation_exit_failure(self, monkeypatch):
        class EvilAnnotation:
            def __enter__(self):
                return self

            def __exit__(self, *exc):
                raise RuntimeError("annotation teardown exploded")

        monkeypatch.setattr(tracing, "_device_annotation",
                            lambda name: EvilAnnotation())
        tracing.enable()
        with tracing.span("guarded"):
            pass  # must not raise
        snap = tracing.timings.snapshot()
        assert snap["guarded"]["count"] == 1  # timing still recorded

    def test_span_survives_annotation_enter_failure(self, monkeypatch):
        class Unenterable:
            def __enter__(self):
                raise RuntimeError("no profiler session")

            def __exit__(self, *exc):
                raise AssertionError("never entered, never exited")

        monkeypatch.setattr(tracing, "_device_annotation",
                            lambda name: Unenterable())
        tracing.enable()
        with tracing.span("guarded2"):
            pass
        assert tracing.timings.snapshot()["guarded2"]["count"] == 1


def _raise_runtime_error(*a, **k):
    raise RuntimeError("profiler session already gone")
