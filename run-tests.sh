#!/usr/bin/env bash
# Test entry point (the reference's python/run-tests.sh analogue):
# builds the native runtime from source FIRST — a broken native build fails
# the run loudly instead of silently exercising only the numpy fallbacks —
# then runs the suite on the CPU backend with 8 virtual devices.
set -euo pipefail
cd "$(dirname "$0")"

echo "== static check: no bare 'except:' under tensorframes_tpu/ =="
python tools/check_no_bare_except.py

# --resilience: run only the retry/fallback/fault-injection lane
# (tests/test_resilience.py) — fast, CPU-only, no native build needed
if [ "${1:-}" = "--resilience" ]; then
  shift
  echo "== resilience lane (pytest -m resilience, CPU) =="
  exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m resilience "$@"
fi

# --pipeline: run only the pipelined block-execution lane
# (tests/test_pipeline.py) — fast, CPU-only, no native build needed
if [ "${1:-}" = "--pipeline" ]; then
  shift
  echo "== pipeline lane (pytest -m pipeline, CPU) =="
  exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m pipeline "$@"
fi

# --observability: run only the query-trace/metrics/explain lane
# (tests/test_observability.py, incl. the mesh/device half: per-device
# tracks, HBM watermarks, skew reports, histograms, slow-query log) —
# fast, CPU-only (8 virtual devices via conftest), no native build needed
if [ "${1:-}" = "--observability" ]; then
  shift
  echo "== observability lane (pytest -m observability, CPU) =="
  exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m observability "$@"
fi

# --serve: run only the multi-tenant serving lane (tests/test_serve.py:
# scheduler fairness, admission control, quotas, shared compile cache,
# slot leasing) — fast, CPU-only, no native build needed
if [ "${1:-}" = "--serve" ]; then
  shift
  echo "== serve lane (pytest -m serve, CPU) =="
  exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m serve "$@"
fi

# --stream: run only the streaming lane (tests/test_stream.py: block
# sources, finite equivalence, windows/watermarks, poisoned-batch
# isolation, bounded state) — fast, CPU-only, no native build needed
if [ "${1:-}" = "--stream" ]; then
  shift
  echo "== stream lane (pytest -m stream, CPU) =="
  exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m stream "$@"
fi

# --elastic: run only the elastic-mesh lane (tests/test_elastic.py:
# device-loss recovery, skew-adaptive repartitioning, hot-key salting)
# — fast, CPU-only, no native build needed
if [ "${1:-}" = "--elastic" ]; then
  shift
  echo "== elastic lane (pytest -m elastic, CPU) =="
  exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m elastic "$@"
fi

# --memory: run only the device-memory manager lane
# (tests/test_memory.py: budget ledger, spill/fault bit-identity,
# external dsort, larger-than-budget relational suite) — fast,
# CPU-only, no native build needed
if [ "${1:-}" = "--memory" ]; then
  shift
  echo "== memory lane (pytest -m memory, CPU) =="
  exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m memory "$@"
fi

# --plan: run only the logical-plan lane (tests/test_plan.py: fused vs
# TFT_FUSE=0 bit-identity across the relational chains, column pruning,
# device-resident stage chaining, plan-derived estimates, fault
# injection on fused computations) — fast, CPU-only, no native build
if [ "${1:-}" = "--plan" ]; then
  shift
  echo "== plan lane (pytest -m plan, CPU) =="
  exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m plan "$@"
fi

# --dplan: run only the distributed logical-plan lane
# (tests/test_dplan.py: lazy d-op chains fused vs TFT_FUSE=0
# bit-identity, folded dreduce/daggregate, device-loss recovery through
# fused programs, ledger spills of resident shard edges) — fast,
# CPU-only (8 virtual devices via conftest), no native build needed
if [ "${1:-}" = "--dplan" ]; then
  shift
  echo "== dplan lane (pytest -m dplan, CPU) =="
  exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m dplan "$@"
fi

# --join: run only the relational lane (tests/test_relational.py:
# broadcast/sort-merge joins vs the CPU host oracle, ledger-chunked
# builds, device-loss recovery, sketch error bounds through
# aggregate/daggregate/streams, parquet predicate pushdown, hot keys)
# — fast, CPU-only (8 virtual devices via conftest), no native build
if [ "${1:-}" = "--join" ]; then
  shift
  echo "== relational lane (pytest -m 'join or sketch', CPU) =="
  exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'join or sketch' "$@"
fi

# --preempt: run only the preemption/cancellation/elastic-growth lane
# (tests/test_preempt.py + growth tests: checkpointed park/resume
# bit-identity, scheduler cancel races, priority preemption, mesh
# admit/churn) — fast, CPU-only (8 virtual devices), no native build
if [ "${1:-}" = "--preempt" ]; then
  shift
  echo "== preempt lane (pytest -m preempt, CPU) =="
  exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m preempt "$@"
fi

# --adaptive: run only the adaptive-execution lane
# (tests/test_adaptive.py: feedback-driven block re-bucketing vs the
# static layout, filter re-ordering/re-plans, result-cache hits +
# invalidation, adaptive stream batches, preempt-aware admission) —
# fast, CPU-only, no native build needed
if [ "${1:-}" = "--adaptive" ]; then
  shift
  echo "== adaptive lane (pytest -m adaptive, CPU) =="
  exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m adaptive "$@"
fi

# --flight: run only the flight-recorder/decision-audit/SLO/health lane
# (tests/test_flight.py: decision ring + tft.why() reconstruction with
# tracing off, dump-on-slow-query/giveup with rotation, SLO burn math,
# tft.health(), metrics-provider conformance) — fast, CPU-only, no
# native build needed
if [ "${1:-}" = "--flight" ]; then
  shift
  echo "== flight lane (pytest -m flight, CPU) =="
  exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m flight "$@"
fi

# --fabric: run only the multi-host serving-fabric lane
# (tests/test_fabric.py: tenant sharding across workers, worker-loss
# leases with checkpointed cross-worker resume, durable
# checkpoint/result tiers surviving rolling restarts warm, SLO-burn
# re-placement, TFT_FABRIC=0 parity) — fast, CPU-only, no native
# build needed
if [ "${1:-}" = "--fabric" ]; then
  shift
  echo "== fabric lane (pytest -m fabric, CPU) =="
  exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m fabric "$@"
fi

# --shuffle: run only the hash-repartition exchange lane
# (tests/test_shuffle.py: placement/conservation properties, the
# partitioned hash join vs the broadcast oracle, shuffle daggregate
# parity, TFT_SHUFFLE=0 bit-identity, device-loss recovery
# mid-exchange) — fast, CPU-only (8 virtual devices), no native build
if [ "${1:-}" = "--shuffle" ]; then
  shift
  echo "== shuffle lane (pytest -m shuffle, CPU) =="
  exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m shuffle "$@"
fi

# --sentinel: run only the performance-regression sentinel lane
# (tests/test_sentinel.py: timeline ring + TFT_TIMELINE=0 bypass
# bit-identity, cost attribution, rolling baselines + persistence,
# the scripted TFT_FAULTS=perf:1 regression drill) — fast, CPU-only,
# no native build needed
if [ "${1:-}" = "--sentinel" ]; then
  shift
  echo "== sentinel lane (pytest -m sentinel, CPU) =="
  exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m sentinel "$@"
fi

# --chaos: run only the chaos/invariant lanes (tests/test_chaos.py:
# seeded multi-site schedules + replay, cross-cutting invariant
# auditors in strict and always-on modes, poison-query quarantine,
# persist checksums, the bounded mixed-workload acceptance drill) —
# fast, CPU-only (8 virtual devices), no native build needed
if [ "${1:-}" = "--chaos" ]; then
  shift
  echo "== chaos lane (pytest -m 'chaos or invariants', CPU) =="
  exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'chaos or invariants' "$@"
fi

# --history: run only the durable query-history/post-mortem lane
# (tests/test_history.py: checksummed segment framing + rotation +
# retention, corrupt/truncated segments going cold under fault
# injection, history filters + cross-worker stitching, unclean-
# shutdown markers + tft.postmortem(), cross-restart tft.why(),
# flight-dump pruning) — fast, CPU-only, no native build needed
if [ "${1:-}" = "--history" ]; then
  shift
  echo "== history lane (pytest -m history, CPU) =="
  exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m history "$@"
fi

# --timing: run only the wall-clock-sensitive deadline tests, serially
# (they flake under concurrent suite load; TFT_TIMING_MARGIN widens
# their assertion bounds further on badly oversubscribed boxes)
if [ "${1:-}" = "--timing" ]; then
  shift
  echo "== timing lane (pytest -m timing, CPU, serial) =="
  exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m timing "$@"
fi

echo "== building native runtime (libtfruntime.so) =="
make -C native

echo "== running test suite (timing-marked deadline tests deferred) =="
python -m pytest tests/ -q -m 'not timing' "$@"

# deadline tests run SERIALLY after the main suite: their wall-clock
# assertions flake when they share the box with the concurrent suite.
# Exit code 5 = nothing collected (passthrough args like -k can
# deselect every timing test) — that is not a failure of the run.
echo "== timing lane (deadline tests, serial) =="
timing_rc=0
python -m pytest tests/ -q -m timing "$@" || timing_rc=$?
if [ "$timing_rc" -ne 0 ] && [ "$timing_rc" -ne 5 ]; then
  exit "$timing_rc"
fi
