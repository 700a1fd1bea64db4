"""Span records (utils/tracing.py): start, end, parent, query id, thread
and attributes of every span, kept in memory while tracing is on; the
spans over the serve finish, the block upload and the daggregate
collect."""

import contextvars
import threading
import time
from collections import deque

import numpy as np
import pytest

import tensorframes_tpu as tft
from tensorframes_tpu.observability import events
from tensorframes_tpu.parallel import distribute, local_mesh
from tensorframes_tpu.parallel.distributed import daggregate
from tensorframes_tpu.serve import QueryScheduler
from tensorframes_tpu.utils import tracing

FINISH_HOOKS = ("quarantine", "baseline", "flight", "history", "slo")


@pytest.fixture
def traced():
    """Tracing on with an empty record buffer; the observer and the
    switch as they were afterwards."""
    was, obs = tracing.enabled(), tracing._span_observer
    tracing.enable()
    tracing.timings.reset()
    try:
        yield
    finally:
        tracing.join_waits()
        tracing.set_span_observer(obs)
        if not was:
            tracing.disable()


def by_name(name, recs=None):
    return [r for r in (recs or tracing.span_records()) if r.name == name]


def test_record_fields(traced):
    tok = tracing.set_query("q-fields")
    try:
        with tracing.span("outer") as outer:
            with tracing.span("inner", {"rows": 3}):
                time.sleep(0.001)
    finally:
        tracing.reset_query(tok)
    inner, = by_name("inner")
    assert outer.parent is None and inner.parent == outer.id
    for r in (inner, outer):
        assert r.start_ns <= r.end_ns
        assert r.query == "q-fields"
        assert r.thread == threading.get_ident()
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    assert inner.end_ns - inner.start_ns >= 1_000_000
    assert inner.attrs == {"rows": 3} and outer.attrs is None


def test_nesting_restores_the_current_span(traced):
    with tracing.span("a") as a:
        with tracing.span("b") as b:
            with tracing.span("c") as c:
                pass
        with tracing.span("d") as d:
            pass
    with tracing.span("e") as e:
        pass
    assert (b.parent, c.parent, d.parent) == (a.id, b.id, a.id)
    assert e.parent is None
    # records are kept in the order the spans ended
    assert [r.name for r in tracing.span_records()] == list("cbdae")


def test_cross_thread_parent_and_thread(traced):
    seen = {}

    def child(tag):
        with tracing.span("child." + tag) as sp:
            seen[tag] = sp

    with tracing.span("parent") as parent:
        ctx = contextvars.copy_context()
        carried = threading.Thread(target=ctx.run, args=(child, "carried"))
        fresh = threading.Thread(target=child, args=("fresh",))
        for t in (carried, fresh):
            t.start()
        for t in (carried, fresh):
            t.join(10)
            assert not t.is_alive()
    # a thread that runs in the caller's context nests under its span;
    # a thread of its own starts a new tree
    assert seen["carried"].parent == parent.id
    assert seen["fresh"].parent is None
    assert seen["carried"].thread != parent.thread
    assert seen["fresh"].thread != parent.thread


def test_span_ended_by_a_waiter(traced):
    calls = []
    tracing.set_span_observer(
        lambda name, dt: calls.append((name, dt, threading.get_ident())))
    with tracing.span("dispatch") as outer:
        sp = tracing.begin("upload", {"bytes": 8})
        tracing.end_after(sp, lambda: time.sleep(0.02))
        with tracing.span("after") as after:
            pass
    tracing.join_waits()
    # begin() never becomes the current span: "after" is not its child
    assert sp.parent == outer.id and after.parent == outer.id
    assert sp.thread == threading.get_ident()
    assert sp.end_ns - sp.start_ns >= 20_000_000
    assert outer.end_ns < sp.end_ns       # the caller did not wait
    name, dt, thread = calls[-1]
    assert name == "upload" and thread != threading.get_ident()
    assert dt == pytest.approx((sp.end_ns - sp.start_ns) * 1e-9)


def test_observer_contract_unchanged(traced):
    calls = []
    tracing.set_span_observer(
        lambda name, dt: calls.append((name, dt, threading.get_ident())))
    with tracing.span("one"):
        with tracing.span("two"):
            pass
    assert [c[0] for c in calls] == ["two", "one"]   # once each, at end
    for (name, dt, thread), rec in zip(calls, tracing.span_records()):
        assert type(dt) is float and thread == threading.get_ident()
        assert dt == pytest.approx((rec.end_ns - rec.start_ns) * 1e-9)
    assert tracing.timings.snapshot()["one"]["count"] == 1


def test_tracing_off_records_nothing():
    was, obs = tracing.enabled(), tracing._span_observer
    calls = []
    tracing.disable()
    tracing.timings.reset()
    tracing.set_span_observer(lambda name, dt: calls.append(name))
    try:
        with tracing.span("quiet") as sp:
            assert sp is None
        assert tracing.begin("quiet.async") is None
        df = tft.frame({"x": np.arange(8.0)}, num_partitions=2)
        tft.map_blocks(lambda x: {"y": x + 1.0}, df).collect()
    finally:
        tracing.set_span_observer(obs)
        if was:
            tracing.enable()
    assert tracing.span_records() == [] and calls == []
    # one shared object, nothing allocated per call
    assert tracing.span("a") is tracing.span("b")


def test_buffer_overflow_counts_dropped(traced, monkeypatch):
    monkeypatch.setattr(tracing, "_records", deque(maxlen=3))
    before = tracing.counters.get("trace.spans_dropped")
    for i in range(5):
        with tracing.span(f"s{i}"):
            pass
    assert [r.name for r in tracing.span_records()] == ["s2", "s3", "s4"]
    assert tracing.counters.get("trace.spans_dropped") - before == 2
    cut = tracing.span_records()[1].end_ns
    assert [r.name for r in tracing.span_records(since_ns=cut)] == \
        ["s3", "s4"]
    tracing.timings.reset()
    assert tracing.span_records() == []


def test_served_query_spans_carry_its_id(traced):
    df = tft.frame({"x": np.arange(16.0)}, num_partitions=2)
    with QueryScheduler(workers=1, name="spans") as s:
        fut = tft.submit(df, lambda x: {"y": x * 2.0}, tenant="t",
                         scheduler=s)
        fut.result(timeout=60)
        # the finish tail runs after the future resolves
        deadline = time.monotonic() + 30
        while not by_name("serve.finish") and time.monotonic() < deadline:
            time.sleep(0.01)
    tracing.join_waits()
    recs = tracing.span_records()
    names = {r.name for r in recs}
    assert {"serve.submit", "serve.admit", "serve.run", "serve.finish",
            "executor.h2d", "executor.convert_back"} <= names, names
    assert {r.query for r in recs} == {fut.query_id}
    submit, = by_name("serve.submit", recs)
    run, = by_name("serve.run", recs)
    assert submit.thread == threading.get_ident()
    assert run.thread != submit.thread            # the worker's thread
    finish, = by_name("serve.finish", recs)
    assert finish.attrs == {"outcome": "completed"}


@pytest.mark.parametrize("invariants", ["1", "0"])
def test_serve_finish_child_per_hook(traced, monkeypatch, invariants):
    monkeypatch.setenv("TFT_INVARIANTS", invariants)
    df = tft.frame({"x": np.arange(4.0)}, num_partitions=1)
    with QueryScheduler(workers=0, name="finish") as s:
        fut = s.submit(df, lambda x: {"y": x + 1.0})
        assert s.step()
    assert fut.result(timeout=5) is not None
    recs = tracing.span_records()
    finish, = by_name("serve.finish", recs)
    kids = sorted(r.name for r in recs if r.parent == finish.id)
    hooks = FINISH_HOOKS + (("audit",) if invariants == "1" else ())
    assert kids == sorted("serve.finish." + h for h in hooks)
    for r in recs:
        if r.parent == finish.id:
            assert finish.start_ns <= r.start_ns <= r.end_ns \
                <= finish.end_ns


@pytest.mark.parametrize("depth", ["1", "3"])
def test_one_upload_span_per_block(traced, monkeypatch, depth):
    # depth 1: the serial path (executor.run); 3: the pipelined stream
    monkeypatch.setenv("TFT_PIPELINE_DEPTH", depth)
    x = np.arange(40.0).reshape(20, 2)
    df = tft.frame({"x": x}, num_partitions=4)
    out = tft.map_blocks(lambda x: {"y": x * 3.0}, df)
    np.testing.assert_allclose(
        np.stack([r["y"] for r in out.collect()]), x * 3.0)
    tracing.join_waits()
    h2d = by_name("executor.h2d")
    assert len(h2d) == 4
    assert sum(r.attrs["bytes"] for r in h2d) == x.nbytes
    q = events.last_query()
    assert q is not None and {r.query for r in h2d} == {q.query_id}
    for r in h2d:
        assert r.start_ns <= r.end_ns


def test_compile_span_on_first_dispatch_only(traced):
    misses = tracing.counters.get("compile_cache.misses")
    fetch = tft.frame({"x": np.arange(6.0)}, num_partitions=3)
    out = tft.map_blocks(lambda x: {"y": x - 1.0}, fetch)
    out.collect()
    assert len(by_name("compile.executor")) == 1
    assert tracing.counters.get("compile_cache.misses") - misses == 1


def test_daggregate_collect_once_per_query(traced):
    n = 64
    df = tft.frame({"k": (np.arange(n) % 4).astype(np.int32),
                    "v": np.arange(n, dtype=np.float64)})
    dist = distribute(df, local_mesh())
    misses = tracing.counters.get("compile_cache.misses")
    for _ in range(3):
        out = daggregate({"v": "sum"}, dist, "k", max_groups=4)
    got = {r["k"]: r["v"] for r in out.collect()}
    assert got == {k: float(np.arange(n)[np.arange(n) % 4 == k].sum())
                   for k in range(4)}
    recs = tracing.span_records()
    collect = by_name("daggregate.collect", recs)
    assert len(collect) == 3
    assert len({r.query for r in collect}) == 3 and None not in \
        {r.query for r in collect}
    assert len(by_name("daggregate.group_ids", recs)) == 3
    # the program compiles once, under its dispatch; the cache counts it
    compile_, = by_name("compile.daggregate", recs)
    dispatch = {r.id: r for r in by_name("daggregate.dispatch", recs)}
    assert compile_.parent in dispatch
    assert tracing.counters.get("compile_cache.misses") - misses == 1
