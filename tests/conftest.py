"""Test fixture: CPU backend with 8 virtual devices.

The analogue of the reference's shared `local[1]` Spark fixture with
`spark.sql.shuffle.partitions=4`
(`TensorFlossTestSparkContext.scala:10-43`): unit tests run on the CPU
backend of the same code path that targets TPU, and mesh/partition tests use
8 virtual devices via XLA_FLAGS, per SURVEY.md §4.

Tests force the CPU backend. x64 is enabled so `double`/`long` columns
stay exact in tests (on the TPU they compute as f32/i32 by policy — see
dtypes.py).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def timing_margin(seconds: float) -> float:
    """Scale a deadline-test assertion bound by ``TFT_TIMING_MARGIN``.

    The `timing`-marked tests assert that a deadline FIRED within a
    generous wall-clock bound; on badly oversubscribed boxes even those
    margins flake. ``TFT_TIMING_MARGIN=2`` doubles every bound (the
    ``run-tests.sh --timing`` lane runs them serially for the same
    reason). Malformed or missing values mean 1.0 — the written bound.
    """
    raw = os.environ.get("TFT_TIMING_MARGIN", "")
    try:
        margin = float(raw) if raw else 1.0
    except ValueError:
        margin = 1.0
    return seconds * max(margin, 1.0)


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavier smoke tests (model-sized benchmarks)")
    config.addinivalue_line(
        "markers", "resilience: retry/fallback/fault-injection suite "
                   "(run-tests.sh runs this lane standalone too)")
    config.addinivalue_line(
        "markers", "pipeline: pipelined block-execution suite "
                   "(run-tests.sh --pipeline runs this lane standalone)")
    config.addinivalue_line(
        "markers", "observability: query-trace/metrics/explain suite "
                   "(run-tests.sh --observability runs this lane "
                   "standalone)")
    config.addinivalue_line(
        "markers", "serve: multi-tenant scheduler/admission/quota suite "
                   "(run-tests.sh --serve runs this lane standalone)")
    config.addinivalue_line(
        "markers", "stream: streaming sources/windows/watermarks suite "
                   "(run-tests.sh --stream runs this lane standalone)")
    config.addinivalue_line(
        "markers", "elastic: device-loss recovery / skew-adaptive "
                   "repartitioning suite (run-tests.sh --elastic runs "
                   "this lane standalone)")
    config.addinivalue_line(
        "markers", "memory: device-memory manager suite — budget "
                   "ledger, spill/fault-back, external sort, "
                   "larger-than-budget queries (run-tests.sh --memory "
                   "runs this lane standalone)")
    config.addinivalue_line(
        "markers", "plan: logical-plan IR suite — operator fusion "
                   "bit-identity vs TFT_FUSE=0, column pruning, "
                   "device-resident stage chaining, plan-derived "
                   "estimates (run-tests.sh --plan runs this lane "
                   "standalone)")
    config.addinivalue_line(
        "markers", "dplan: distributed logical-plan suite — lazy d-op "
                   "chains fused into one GSPMD program per mesh stage, "
                   "bit-identity vs TFT_FUSE=0, folded reductions, "
                   "elastic recovery through fused programs, "
                   "resident-shard-edge spills (run-tests.sh --dplan "
                   "runs this lane standalone)")
    config.addinivalue_line(
        "markers", "join: relational join suite — broadcast hash join "
                   "and mesh sort-merge join vs the CPU host oracle, "
                   "ledger-chunked builds, stream enrichment, parquet "
                   "predicate pushdown, hot-key surfacing "
                   "(run-tests.sh --join runs this lane standalone)")
    config.addinivalue_line(
        "markers", "sketch: approximate-aggregate suite — HLL distinct "
                   "counts, relative-error quantiles, top-k heavy "
                   "hitters, error bounds + cross-path bit-identity "
                   "through aggregate/daggregate/windowed streams "
                   "(run-tests.sh --join runs this lane too)")
    config.addinivalue_line(
        "markers", "preempt: preemption/cancellation/elastic-growth "
                   "suite — checkpointed park/resume bit-identity, "
                   "scheduler cancel races, priority preemption, device "
                   "re-admission + shrink/grow churn (run-tests.sh "
                   "--preempt runs this lane standalone)")
    config.addinivalue_line(
        "markers", "adaptive: adaptive-execution suite — feedback-"
                   "driven block re-bucketing, observed-selectivity "
                   "filter re-ordering and mid-plan re-plans, the "
                   "plan-fingerprint result cache, adaptive stream "
                   "batch sizing, preempt-aware admission; every "
                   "decision bit-identical vs TFT_ADAPTIVE=0 / "
                   "TFT_RESULT_CACHE=0 (run-tests.sh --adaptive runs "
                   "this lane standalone)")
    config.addinivalue_line(
        "markers", "flight: flight-recorder/decision-audit/SLO/health "
                   "suite — always-on decision ring + tft.why() causal "
                   "chains with TFT_TRACE off, JSONL auto-dumps with "
                   "rotation, SLO burn math, tft.health(), metrics-"
                   "provider conformance (run-tests.sh --flight runs "
                   "this lane standalone)")
    config.addinivalue_line(
        "markers", "fabric: multi-host serving-fabric suite — tenant "
                   "sharding across workers, heartbeat/lease worker "
                   "loss with checkpointed cross-worker resume "
                   "(bit-identical), durable checkpoint/result tiers "
                   "surviving rolling restarts warm, SLO-burn-driven "
                   "re-placement, TFT_FABRIC=0 single-process parity "
                   "(run-tests.sh --fabric runs this lane standalone)")
    config.addinivalue_line(
        "markers", "shuffle: hash-repartition exchange suite — "
                   "placement/conservation properties, partitioned "
                   "hash join vs the broadcast oracle, shuffle "
                   "daggregate parity, TFT_SHUFFLE=0 bit-identity, "
                   "device-loss recovery mid-exchange (run-tests.sh "
                   "--shuffle runs this lane standalone)")
    config.addinivalue_line(
        "markers", "sentinel: performance-regression sentinel suite — "
                   "telemetry timeline ring + TFT_TIMELINE=0 bypass "
                   "bit-identity, per-query cost attribution, rolling "
                   "plan-fingerprint baselines with persistence, the "
                   "scripted regression drill (TFT_FAULTS=perf:1) "
                   "through every operator surface (run-tests.sh "
                   "--sentinel runs this lane standalone)")
    config.addinivalue_line(
        "markers", "chaos: seeded multi-site chaos-schedule suite — "
                   "reproducible fault composition over the existing "
                   "sites (TFT_CHAOS), the bounded mixed-workload "
                   "acceptance drill (bit-identity vs fault-free, zero "
                   "leaks, every failure classified), poison-query "
                   "quarantine, persist checksums (run-tests.sh --chaos "
                   "runs this lane standalone)")
    config.addinivalue_line(
        "markers", "invariants: cross-cutting invariant-auditor suite — "
                   "slot-lease balance, ledger reservation balance, "
                   "row conservation, checkpoint cursor consistency, "
                   "scheduler/fabric accounting; strict vs always-on "
                   "modes (run-tests.sh --chaos runs this lane too)")
    config.addinivalue_line(
        "markers", "history: durable query-history/post-mortem suite — "
                   "checksummed append-only segments with rotation and "
                   "retention, corrupt-segment cold behavior under "
                   "fault injection, tft.history() filters and "
                   "stitching, unclean-shutdown markers + "
                   "tft.postmortem(), cross-restart tft.why(), "
                   "TFT_HISTORY=0 bypass (run-tests.sh --history runs "
                   "this lane standalone)")
    config.addinivalue_line(
        "markers", "timing: wall-clock-sensitive deadline assertions — "
                   "margins are widened for loaded machines "
                   "(TFT_TIMING_MARGIN multiplies the bounds; "
                   "run-tests.sh --timing runs this lane serially); "
                   "deselect with -m 'not timing' when a box is badly "
                   "oversubscribed")
