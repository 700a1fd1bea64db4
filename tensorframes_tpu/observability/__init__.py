"""Query-scoped observability: correlated events, timelines, metrics.

The reference's only observability was ``logDebug`` narration and
self-timed perf suites (SURVEY.md §5); the port's first pass was a flat,
process-global ``span``/``counters`` registry (:mod:`..utils.tracing`).
Once the engine pipelines blocks and queries overlap, flat registries
stop answering the questions that matter — *which query's* retry tripped
the OOM split, where did block 17 of *this* query spend its time. This
package adds the query dimension on top of the existing primitives
(every ``span``/``counters`` call site keeps working unchanged):

- :mod:`.events` — :class:`QueryTrace` + contextvar correlation: every
  public API forcing gets a unique query id; engine, pipeline and
  resilience layers attach typed events (block
  submit/compute/drain, retries with their classified error, OOM splits,
  pad/sync fallbacks, compile-cache hits/misses, occupancy samples).
  Finished traces land in a bounded ring buffer and an optional JSONL
  sink (``TFT_TRACE_FILE``); :meth:`QueryTrace.to_chrome_trace` exports
  a Perfetto/chrome://tracing timeline with one track per pipeline slot.
- :mod:`.device` — HBM watermark sampling (``Device.memory_stats()``
  where the backend supports it, graceful ``None`` fallback on CPU) at
  query start/end and around block drains; OOM splits carry the
  observed watermark.
- :mod:`.metrics` — Prometheus text-format export
  (:func:`metrics_text`), including proper histogram families
  (``tft_query_latency_seconds``, ``tft_compile_seconds``), and an
  opt-in loopback HTTP endpoint (:func:`serve_metrics`,
  ``TFT_METRICS_PORT``; binds 127.0.0.1 only).
- :mod:`.report` — ``frame.explain()`` / :func:`last_query_report`:
  the human-readable per-stage breakdown, plus a mesh section
  (per-device rows/bytes/time, straggler ratio, imbalance warning)
  for queries that touched the distributed layer.
- :mod:`.flight` — the ALWAYS-ON flight recorder: a bounded ring of
  decision-level records (admission verdicts, re-plans, shrinks,
  spills — each with the inputs it was decided from), correlated by
  query id with ``TFT_TRACE`` off; JSONL auto-dumps on slow query /
  giveup / device loss / exit (``TFT_FLIGHT_DUMP``).
- :mod:`.decisions` — ``tft.why(query_id)`` (one query's causal chain
  from the ring, the on-disk flight dumps, or the durable history)
  and ``tft.doctor()`` (process triage).
- :mod:`.history` — the ALWAYS-ON durable query log: every finished
  query folds into checksummed append-only segments on disk
  (``TFT_HISTORY_DIR``; free under a fabric's durable tier), queried
  by ``tft.history()`` across restarts; unclean shutdowns are
  detected at startup and ``tft.postmortem()`` merges the last
  flight dump, the history tail, and timeline rates into one triage
  report (``TFT_HISTORY=0`` bypasses the whole layer).
- :mod:`.slo` — per-tenant latency objectives + error-budget burn
  rates from the existing serve latency histograms
  (``tft_serve_slo_*``, ``serve_report()`` lines, burn callbacks).
- :mod:`.health` — ``tft.health()``: one machine-readable snapshot
  across ledger, mesh, serve, caches, streams, SLOs.
- :mod:`.timeline` — the ALWAYS-ON telemetry timeline: a bounded ring
  of periodic counter/gauge/histogram snapshots (``tft.timeline()``
  answers "what changed in the last N minutes" without an external
  Prometheus; ``TFT_TIMELINE=0`` bypasses the whole sentinel).
- :mod:`.baseline` — per-query cost attribution keyed by plan
  fingerprint, rolling EWMA+MAD baselines (persisted via the durable
  tier), and the ``perf.regression`` detector
  (``TFT_REGRESSION_SIGMA``; ``tft.regressions()``).

Everything is zero-cost-when-off: with tracing disabled
(``TFT_TRACE`` unset), :func:`query_trace` yields ``None`` and every
hook is a single ``None`` check. See ``docs/observability.md``.
"""

from __future__ import annotations

import os

from ..utils import tracing as _tracing
from ..utils.logging import get_logger
from .events import (DEVICE_TRACK_BASE, Event, QueryTrace, add_event,
                     block_meta, bypass, clear_ring, current_trace,
                     last_query, query_trace, recent_events, traced_query,
                     wrap_context)
from . import device
from . import flight
from . import slo
from . import timeline
from . import baseline
from . import history
from .baseline import perf_stats, regressions
from .decisions import doctor, why
from .health import health
from .metrics import metrics_port, metrics_text, serve_metrics, stop_metrics
from .report import frame_report, last_query_report, render
from .slo import SLO, on_burn, set_slo, slo_status

__all__ = [
    "Event", "QueryTrace", "query_trace", "current_trace", "add_event",
    "wrap_context", "traced_query", "last_query", "recent_events",
    "clear_ring", "block_meta", "bypass", "DEVICE_TRACK_BASE", "device",
    "metrics_text", "serve_metrics", "stop_metrics", "metrics_port",
    "frame_report", "last_query_report", "render",
    "flight", "slo", "why", "doctor", "health",
    "SLO", "set_slo", "slo_status", "on_burn",
    "timeline", "baseline", "regressions", "perf_stats",
    "history",
]

_log = get_logger("observability")

# credit every span to the active query's stage breakdown as well as to
# the flat registry (one slot; this package owns it)
from .events import _on_span as _span_observer  # noqa: E402

_tracing.set_span_observer(_span_observer)

# the flight recorder's, SLO layer's, and performance sentinel's
# metrics families register once the provider registry exists
# (deferred: flight/slo are imported by metrics' own import chain)
flight._register_metrics()
slo._register_metrics()
timeline._register_metrics()
baseline._register_metrics()
history._register_metrics()


def _maybe_autostart() -> None:
    """Opt-in metrics endpoint: ``TFT_METRICS_PORT=<port>`` starts the
    loopback server at import (``0`` picks a free port)."""
    raw = os.environ.get("TFT_METRICS_PORT")
    if not raw:
        return
    try:
        port = int(raw)
    except ValueError:
        _log.warning("ignoring malformed TFT_METRICS_PORT=%r", raw)
        return
    try:
        serve_metrics(port)
    except (OSError, OverflowError, ValueError) as e:
        # OverflowError: the socket layer's out-of-range-port error —
        # a bad env value must warn, never break `import tensorframes_tpu`
        _log.warning("metrics endpoint failed to start on port %s: %s",
                     raw, e)


_maybe_autostart()
