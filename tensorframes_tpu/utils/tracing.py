"""Tracing / profiling hooks.

The reference has no tracer — only ``logDebug`` narration along the
execution path and self-timed perf suites (``System.nanoTime``,
``perf/ConvertPerformanceSuite.scala:44-53``; SURVEY.md §5). The TPU-native
replacement is real instrumentation:

 - :func:`span` — a context manager timing a named stage on the host AND
   annotating it into the XLA device trace via
   ``jax.profiler.TraceAnnotation``, so host stages line up with device ops
   in the profiler UI; :func:`begin` opens a span that another thread
   may end (an upload whose completion a waiter observes);
 - :class:`Span` records — every span, once ended, is kept in a bounded
   in-memory buffer (:func:`span_records`) with its start and end on
   ``time.monotonic_ns()``, the enclosing span, the query it served
   (:func:`set_query`), its thread and optional attributes;
 - :class:`Timings` — a process-wide registry of per-stage statistics
   (count / total / min / max seconds) plus dimensionless gauges, the
   structured replacement for the reference's log-line narration; the
   engine's hot stages (validate, convert, execute, convertBack) report
   here;
 - :func:`profile` — wraps ``jax.profiler.start_trace/stop_trace`` for a
   whole-program device trace dump viewable in TensorBoard/XProf.

All hooks are zero-cost-when-off: ``span`` is one flag check — no
allocation, no clock read — unless tracing is enabled (it is during
:func:`profile`, under ``TFT_TRACE=1``, or after :func:`enable`).

Per-QUERY attribution (which query's block 17, which query's retry) lives
one layer up in :mod:`tensorframes_tpu.observability`, which registers a
span observer here (:func:`set_span_observer`) so every span is also
credited to the active query trace.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import os
import queue
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterator, List, Optional

from .logging import get_logger

__all__ = ["Timings", "timings", "Counters", "counters", "Histograms",
           "histograms", "Span", "span", "begin", "end_after", "join_waits",
           "span_records", "set_query", "reset_query", "current_query",
           "gauge", "enable", "disable", "enabled", "profile",
           "dump_stats", "set_span_observer"]

_log = get_logger("utils.tracing")


class _Stat:
    __slots__ = ("count", "total", "min", "max")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = 0.0

    def add(self, dt: float):
        self.count += 1
        self.total += dt
        if dt < self.min:
            self.min = dt
        if dt > self.max:
            self.max = dt

    def as_dict(self) -> Dict[str, float]:
        return {"count": self.count, "total_s": self.total,
                "mean_s": self.total / self.count if self.count else 0.0,
                "min_s": self.min if self.count else 0.0, "max_s": self.max}


class _GaugeStat:
    """Stats for a sampled LEVEL (window occupancy, queue depth): gauges
    are dimensionless, so their stat keys carry no ``_s`` unit suffix and
    they track ``last`` (the newest sample) instead of ``total``."""

    __slots__ = ("count", "total", "min", "max", "last")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = float("-inf")
        self.last = 0.0

    def add(self, value: float):
        self.count += 1
        self.total += value
        if value < self.min:
            self.min = value
        if value > self.max:
            self.max = value
        self.last = value

    def as_dict(self) -> Dict[str, float]:
        mean = self.total / self.count if self.count else 0.0
        return {"count": self.count, "mean": mean,
                "min": self.min if self.count else 0.0,
                "max": self.max if self.count else 0.0,
                "last": self.last}


class Timings:
    """Thread-safe per-stage timing registry (+ gauge samples)."""

    def __init__(self):
        self._stats: Dict[str, _Stat] = {}
        self._gauges: Dict[str, _GaugeStat] = {}
        self._lock = threading.Lock()

    def add(self, name: str, dt: float) -> None:
        with self._lock:
            stat = self._stats.get(name)
            if stat is None:
                stat = self._stats[name] = _Stat()
            stat.add(dt)

    def add_gauge(self, name: str, value: float) -> None:
        with self._lock:
            stat = self._gauges.get(name)
            if stat is None:
                stat = self._gauges[name] = _GaugeStat()
            stat.add(value)

    def spans_snapshot(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {k: v.as_dict() for k, v in self._stats.items()}

    def gauges_snapshot(self) -> Dict[str, Dict[str, float]]:
        # gauge entries carry ONLY the unit-less stat keys
        # (mean/min/max/last). The pre-0.2 duration-suffixed aliases
        # (`mean_s`/...) that `pipeline.occupancy` kept for one release
        # are gone as scheduled.
        with self._lock:
            return {k: v.as_dict() for k, v in self._gauges.items()}

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """Spans and gauges in one dict; span entries use ``*_s`` keys,
        gauge entries unit-less ``mean``/``min``/``max``/``last``."""
        out = self.spans_snapshot()
        out.update(self.gauges_snapshot())
        return out

    def reset(self) -> None:
        """Clear the stage statistics, the gauges and the span records."""
        with self._lock:
            self._stats.clear()
            self._gauges.clear()
        with _records_lock:
            _records.clear()

    def report(self, include_counters: bool = True) -> str:
        """One merged human-readable report: spans, gauges, and (by
        default) the always-on :data:`counters`."""
        spans = self.spans_snapshot()
        gauges = self.gauges_snapshot()
        counts = counters.snapshot() if include_counters else {}
        if not spans and not gauges and not counts:
            return "(no spans recorded; enable tracing first)"
        lines = []
        if spans:
            width = max(len(k) for k in spans)
            lines.append("%-*s %8s %12s %12s" % (width, "span", "count",
                                                 "total_s", "mean_s"))
            for name in sorted(spans, key=lambda k: -spans[k]["total_s"]):
                s = spans[name]
                lines.append("%-*s %8d %12.6f %12.6f"
                             % (width, name, s["count"], s["total_s"],
                                s["mean_s"]))
        else:
            lines.append("(no spans recorded; enable tracing first)")
        if gauges:
            width = max(len(k) for k in gauges)
            lines.append("")
            lines.append("%-*s %8s %12s %12s %12s" % (width, "gauge",
                                                      "count", "mean",
                                                      "max", "last"))
            for name in sorted(gauges):
                g = gauges[name]
                lines.append("%-*s %8d %12.4f %12.4f %12.4f"
                             % (width, name, g["count"], g["mean"],
                                g["max"], g["last"]))
        if counts:
            width = max(len(k) for k in counts)
            lines.append("")
            lines.append("%-*s %8s" % (width, "counter", "value"))
            for name in sorted(counts):
                lines.append("%-*s %8d" % (width, name, counts[name]))
        return "\n".join(lines)


timings = Timings()


class Counters:
    """Thread-safe named event counters (retries, giveups, fallbacks).

    Unlike :class:`Timings` spans these are ALWAYS on: the resilience
    layer's retry/giveup counts must be observable after the fact even
    when span timing was disabled during the failure (the moment you most
    want them). Incrementing an int under a lock is cheap enough.
    """

    def __init__(self):
        self._counts: Dict[str, int] = {}
        self._lock = threading.Lock()

    def inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + n

    def get(self, name: str) -> int:
        with self._lock:
            return self._counts.get(name, 0)

    def get_many(self, names) -> Dict[str, int]:
        """Read several counters under one lock acquisition without
        copying the whole registry (the per-query cost capture reads a
        fixed family set on every serve completion)."""
        with self._lock:
            g = self._counts.get
            return {n: g(n, 0) for n in names}

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()


counters = Counters()


# Default histogram buckets (seconds): spans compile times (sub-ms jit
# cache-assembly on reuse up to tens of seconds for a first TPU compile)
# and per-query latencies. Cumulative `le` semantics are applied at
# render time (observability.metrics); here each bucket holds its own
# non-cumulative count.
DEFAULT_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                   0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)


class _Hist:
    __slots__ = ("buckets", "counts", "sum", "count")

    def __init__(self, buckets):
        self.buckets = tuple(buckets)
        self.counts = [0] * (len(self.buckets) + 1)  # +1: the +Inf bucket
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        i = 0
        for i, le in enumerate(self.buckets):
            if value <= le:
                break
        else:
            i = len(self.buckets)  # +Inf
        self.counts[i] += 1
        self.sum += value
        self.count += 1

    def as_dict(self) -> Dict[str, object]:
        return {"les": self.buckets + (float("inf"),),
                "counts": list(self.counts),
                "sum": self.sum, "count": self.count}


class Histograms:
    """Thread-safe histogram registry (Prometheus-style bucketed counts).

    ALWAYS on, like :class:`Counters` — the observation sites are rare
    events (a compile-cache miss, a finished query), so one lock + one
    bucket increment per observation never shows up on a hot path.
    Keyed by ``(family, labels)``: one family (e.g. ``compile_seconds``)
    renders as one Prometheus histogram metric with one ``le`` series per
    label set.
    """

    def __init__(self):
        self._hists: Dict[tuple, _Hist] = {}
        self._lock = threading.Lock()

    def observe(self, family: str, value: float, buckets=None,
                **labels) -> None:
        key = (family, tuple(sorted(labels.items())))
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = _Hist(buckets or DEFAULT_BUCKETS)
            h.observe(float(value))

    def snapshot(self) -> Dict[tuple, Dict[str, object]]:
        with self._lock:
            return {k: v.as_dict() for k, v in self._hists.items()}

    def family_sum(self, family: str) -> float:
        """Summed observations across every label set of one family,
        without materializing bucket copies (the per-query cost capture
        reads the ``compile_seconds`` total on every serve completion)."""
        with self._lock:
            return sum(h.sum for (fam, _), h in self._hists.items()
                       if fam == family)

    def reset(self) -> None:
        with self._lock:
            self._hists.clear()


histograms = Histograms()


def dump_stats(file=None) -> None:
    """Print spans + gauges + counters in one report (the quick "what did
    that run do" convenience; ``tft.dump_stats()``)."""
    print(timings.report(include_counters=True), file=file)


_enabled = os.environ.get("TFT_TRACE", "") not in ("", "0", "false")


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


# the observability layer's per-query stage attribution: called as
# (name, dt_seconds) at the end of every recorded span. One slot, set
# once at import of tensorframes_tpu.observability.
_span_observer: Optional[Callable[[str, float], None]] = None


def set_span_observer(fn: Optional[Callable[[str, float], None]]) -> None:
    global _span_observer
    _span_observer = fn


def _device_annotation(name: str):
    try:
        import jax.profiler
        return jax.profiler.TraceAnnotation(name)
    except Exception:  # profiler unavailable on some backends
        return contextlib.nullcontext()


class Span:
    """One span: a timer while its work runs, then the record of it.

    Fields: ``id``; ``name``; ``start_ns`` and ``end_ns`` on
    ``time.monotonic_ns()`` (``end_ns`` is None while it runs);
    ``parent``, the ``id`` of the span that was current where this one
    opened (None at the top); ``query``, the query id current there
    (:func:`set_query`; a caller that learns the id later may set it
    before the end); ``thread``, the ident of the thread that opened it;
    ``attrs``, a dict (bytes, rows, outcome) or None.

    Used as a context manager (:func:`span`) it is the current span of
    its body, and annotates the device trace. Opened by :func:`begin`, it
    is neither, and any one thread may :meth:`end` it.
    """

    __slots__ = ("id", "name", "start_ns", "end_ns", "parent", "query",
                 "thread", "attrs", "_token", "_ann")

    def __init__(self, name: str, attrs: Optional[Dict[str, Any]] = None):
        parent = _current_span.get()
        self.id = next(_span_ids)
        self.name = name
        self.parent = None if parent is None else parent.id
        self.query = _current_query.get()
        self.thread = threading.get_ident()
        self.attrs = attrs
        self.end_ns: Optional[int] = None
        self._token = self._ann = None
        self.start_ns = time.monotonic_ns()

    def __enter__(self) -> "Span":
        # a trace-annotation context that fails on entry or exit (some
        # backends raise once the profiler has stopped) can neither lose
        # the host timing nor mask the body's exception
        self._token = _current_span.set(self)
        ann = _device_annotation(self.name)
        try:
            ann.__enter__()
        except Exception as e:  # annotation is best-effort decoration
            _log.debug("trace annotation enter failed for %s: %s",
                       self.name, e)
            ann = None
        self._ann = ann
        self.start_ns = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end()
        try:
            _current_span.reset(self._token)
        except ValueError:  # exited in another context than it entered
            pass
        ann, self._ann, self._token = self._ann, None, None
        if ann is not None:
            try:
                ann.__exit__(None, None, None)
            except Exception as e:
                _log.debug("trace annotation exit failed for %s: %s",
                           self.name, e)
        return False

    def end(self) -> None:
        """End the span on the calling thread (a second call does
        nothing): keep its record, add its duration to :data:`timings`
        and call the span observer with ``(name, seconds)``."""
        if self.end_ns is not None:
            return
        self.end_ns = end = time.monotonic_ns()
        dt = (end - self.start_ns) * 1e-9
        timings.add(self.name, dt)
        with _records_lock:
            full = len(_records) == _records.maxlen
            _records.append(self)
        if full:
            counters.inc("trace.spans_dropped")
        obs = _span_observer
        if obs is not None:
            obs(self.name, dt)

    def __repr__(self):
        return (f"Span({self.name!r}, id={self.id}, parent={self.parent}, "
                f"query={self.query!r}, start_ns={self.start_ns}, "
                f"end_ns={self.end_ns}, attrs={self.attrs!r})")


class _NullSpan:
    """What :func:`span` returns with tracing off: enters to None."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> bool:
        return False


_NULL_SPAN = _NullSpan()


# the newest ended spans; an eviction counts trace.spans_dropped. On one
# v5e a traced 10-s window of four tenants' served ResNet-50 queries
# ends about 60k spans.
_RECORDS_KEPT = 1 << 17
_records: "deque[Span]" = deque(maxlen=_RECORDS_KEPT)
_records_lock = threading.Lock()
_span_ids = itertools.count(1)
_current_span: "contextvars.ContextVar[Optional[Span]]" = \
    contextvars.ContextVar("tft_span", default=None)
_current_query: "contextvars.ContextVar[Optional[str]]" = \
    contextvars.ContextVar("tft_span_query", default=None)


def span(name: str, attrs: Optional[Dict[str, Any]] = None):
    """Time a named stage: ``with span("executor.convert"):``.

    Enters to the running :class:`Span` (to stamp ``query`` or ``attrs``
    before it ends), or to None with tracing off, when the call is one
    flag check: no allocation, no clock read.
    """
    if not _enabled:
        return _NULL_SPAN
    return Span(name, attrs)


def begin(name: str, attrs: Optional[Dict[str, Any]] = None
          ) -> Optional[Span]:
    """Open a span whose work ends on another thread (None with tracing
    off). It never becomes the current span; end it with
    :meth:`Span.end` or :func:`end_after`."""
    if not _enabled:
        return None
    return Span(name, attrs)


_waits: "Optional[queue.Queue]" = None
_waits_lock = threading.Lock()


def end_after(sp: Span, wait: Callable[[], Any]) -> None:
    """End ``sp`` when ``wait()`` returns, on a waiter thread, so the
    caller never blocks on it. One daemon thread, started at the first
    call, serves the calls in order. A ``wait`` that raises ends the span
    when it raises."""
    global _waits
    q = _waits
    if q is None:
        with _waits_lock:
            if _waits is None:
                _waits = queue.Queue()
                threading.Thread(target=_waiter, args=(_waits,),
                                 name="tft-span-waiter",
                                 daemon=True).start()
            q = _waits
    q.put((sp, wait))


def _waiter(q: "queue.Queue") -> None:
    while True:
        sp, wait = q.get()
        try:
            wait()
        except Exception as e:
            _log.debug("span %s: its wait raised (%s)", sp.name, e)
        try:
            sp.end()
        except Exception as e:  # the waiter must outlive an observer
            _log.warning("span %s: end failed: %s", sp.name, e)
        finally:
            q.task_done()


def join_waits() -> None:
    """Block until every span handed to :func:`end_after` has ended."""
    q = _waits
    if q is not None:
        q.join()


def span_records(since_ns: Optional[int] = None) -> List[Span]:
    """The kept span records, oldest first; with ``since_ns``, those that
    ended at or after it (``time.monotonic_ns()``). The buffer keeps the
    newest 131072 and counts each eviction in ``trace.spans_dropped``;
    :meth:`Timings.reset` clears it."""
    with _records_lock:
        recs = list(_records)
    if since_ns is None:
        return recs
    return [r for r in recs if r.end_ns >= since_ns]


def set_query(query_id: Optional[str]) -> contextvars.Token:
    """Make ``query_id`` the query that spans opened in the calling
    context serve; returns the token :func:`reset_query` takes."""
    return _current_query.set(query_id)


def reset_query(token: contextvars.Token) -> None:
    _current_query.reset(token)


def current_query() -> Optional[str]:
    return _current_query.get()


def gauge(name: str, value: float) -> None:
    """Sample a dimensionless value into the :data:`timings` registry.

    Same zero-cost-when-off contract as :func:`span`, but for quantities
    that are levels rather than durations — e.g. the pipelined engine
    samples its in-flight window size into ``pipeline.occupancy`` at every
    submit, so ``timings.snapshot()['pipeline.occupancy']['mean']`` reads
    as the mean window occupancy. Gauges keep their own stat family
    (``mean``/``min``/``max``/``last``, no seconds suffix). No-op unless
    tracing is enabled.
    """
    if _enabled:
        timings.add_gauge(name, float(value))


@contextlib.contextmanager
def profile(log_dir: str, host_spans: bool = True) -> Iterator[None]:
    """Capture a full XLA device trace to ``log_dir`` (TensorBoard format).

    Also enables host spans for the duration so the :data:`timings` registry
    covers the same window. A failing ``stop_trace`` (a torn-down or
    double-stopped profiler session) is logged, never raised — it must not
    mask an exception from the profiled body, nor fail a body that
    succeeded.
    """
    import jax

    was = _enabled
    jax.profiler.start_trace(log_dir)  # before enable(): a failure here
    if host_spans:                     # must not leave spans on forever
        enable()
    try:
        yield
    finally:
        if not was:
            disable()
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            _log.error("jax.profiler.stop_trace() failed (trace in %s "
                       "may be incomplete): %s", log_dir, e)
        else:
            _log.info("profile written to %s", log_dir)
