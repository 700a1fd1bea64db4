"""The readers of the program's span records (h2d_share, engine_ms,
finish_ms) on synthetic records and windows."""

from types import SimpleNamespace

import pytest

from cellbench import harness
from tensorframes_tpu.utils import tracing

MS = 1_000_000


def reader(name):
    return harness.load_module(harness.metric_file(name),
                               "cellbench_metric_" + name.replace(".", "_"))


def rec(id_, name, start_ms, end_ms, parent=None, query=None, attrs=None):
    return SimpleNamespace(id=id_, name=name, start_ns=start_ms * MS,
                           end_ns=end_ms * MS, parent=parent, query=query,
                           attrs=attrs)


def run_over(t0_ms, t1_ms):
    win = SimpleNamespace(t0=t0_ms / 1e3, t_end=t1_ms / 1e3)
    return SimpleNamespace(window=win, window_s=(t1_ms - t0_ms) / 1e3)


@pytest.fixture
def records(monkeypatch):
    """Puts synthetic records (and a drop count) in the program's place."""
    held = {"recs": [], "dropped": 0}
    monkeypatch.setattr(tracing, "span_records",
                        lambda since_ns=None: list(held["recs"]))
    real_get = tracing.counters.get
    monkeypatch.setattr(
        tracing.counters, "get",
        lambda name: held["dropped"] if name == "trace.spans_dropped"
        else real_get(name))
    return held


def served(q, start, parts, outcome="completed", first_id=1):
    """One served query's records: serve.run enclosing a map_blocks.block
    that encloses an executor.dispatch, then serve.finish."""
    i = first_id
    out = [rec(i, "serve.run", start, start + parts["run"], query=q)]
    out.append(rec(i + 1, "map_blocks.block", start + 1,
                   start + 1 + parts["engine"], parent=i, query=q))
    out.append(rec(i + 2, "executor.dispatch", start + 2, start + 3,
                   parent=i + 1, query=q))
    fin = start + parts["run"]
    out.append(rec(i + 3, "serve.finish", fin, fin + parts["finish"],
                   query=q, attrs={"outcome": outcome}))
    return out


def test_h2d_share_unions_and_clips(records):
    records["recs"] = [
        rec(1, "executor.h2d", 90, 110),     # clipped to [100, 110]
        rec(2, "executor.h2d", 105, 130),    # overlaps: union to 130
        rec(3, "executor.dispatch_async", 130, 400),
        rec(4, "executor.h2d", 300, 350),
        rec(5, "executor.h2d", 1050, 1150),  # ends after the window
    ]
    got = reader("h2d_share.infer").read(run_over(100, 1100))
    assert got == pytest.approx(100.0 * (30 + 50) / 1000)


def test_engine_ms_outermost_per_completed_query(records):
    records["recs"] = (
        served("a", 100, {"run": 10, "engine": 6, "finish": 2})
        + served("b", 200, {"run": 20, "engine": 14, "finish": 4},
                 first_id=10)
        # a failed query counts in neither reader
        + served("c", 300, {"run": 30, "engine": 25, "finish": 9},
                 outcome="failed", first_id=20))
    # a second outermost engine span of query b adds to its sum
    records["recs"].append(rec(30, "executor.convert_back", 218, 219,
                               parent=10, query="b"))
    run = run_over(50, 1000)
    assert reader("engine_ms.serve").read(run) == pytest.approx(
        (6 + 15) / 2)
    assert reader("finish_ms.serve").read(run) == pytest.approx((2 + 4) / 2)


def test_serve_readers_keep_the_window(records):
    records["recs"] = (
        served("early", 10, {"run": 10, "engine": 5, "finish": 1})
        + served("in", 100, {"run": 10, "engine": 8, "finish": 3},
                 first_id=10))
    run = run_over(50, 1000)
    assert reader("engine_ms.serve").read(run) == pytest.approx(8)
    assert reader("finish_ms.serve").read(run) == pytest.approx(3)


@pytest.mark.parametrize("name", ["h2d_share.infer", "engine_ms.serve",
                                  "finish_ms.serve"])
def test_nothing_to_read(records, name):
    assert reader(name).read(run_over(0, 1000)) is None
    # records, but none of this reader's
    records["recs"] = [rec(1, "daggregate.collect", 10, 20, query="q1")]
    assert reader(name).read(run_over(0, 1000)) is None


@pytest.mark.parametrize("name", ["h2d_share.infer", "engine_ms.serve",
                                  "finish_ms.serve"])
def test_records_lost_from_the_window(records, name):
    recs = served("a", 100, {"run": 10, "engine": 6, "finish": 2}) + [
        rec(9, "executor.h2d", 120, 130)]
    records["recs"] = recs
    records["dropped"] = 5
    # the oldest record kept ended inside the window: evictions reached it
    assert reader(name).read(run_over(50, 1000)) is None
    # evictions stopped before the window: what it holds is whole
    records["recs"] = [rec(99, "setup.work", 0, 10)] + recs
    assert reader(name).read(run_over(50, 1000)) is not None


@pytest.mark.parametrize("name", ["h2d_share.infer", "engine_ms.serve",
                                  "finish_ms.serve"])
def test_program_without_span_records(monkeypatch, name):
    monkeypatch.delattr(tracing, "span_records")
    assert reader(name).read(run_over(0, 1000)) is None
