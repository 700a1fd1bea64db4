"""Headline benchmark: map_blocks rows/sec/chip (BASELINE.json config 3).

Workload: the Scala-DSL-equivalent ``mapBlocks`` add-constant over a
1M-row double column (reference ``README.md:154-172``), on the framework's
device-resident path: the frame is ``distribute``d to the chip mesh once
(the analogue of data living in Spark executors' memory), then each
``dmap_blocks`` iteration is one compiled XLA dispatch per step with NO
host↔device transfer — the TPU-native design BASELINE.json's north star
asks for ("streams ... directly into TPU HBM device buffers").

``vs_baseline``: the reference publishes no numbers (``BASELINE.json``), so the
denominator is a faithful host re-implementation of the reference's own data
path on this machine: materialize Row objects from the columns, map the
computation, rebuild columns from Rows — the row-at-a-time
convert/convertBack structure of ``DataOps.scala:158-283`` (its acknowledged
weakness, ``DataOps.scala:30-33``), with the arithmetic vectorized in its
favor. Ratio > 1 means the columnar TPU-resident path beats the
row-marshalling design at equal scale.

Runs in this one process, which holds the chip. Off a TPU
(``jax.devices()[0].platform != "tpu"``) it exits 1 and prints no result.
On the chip it prints one JSON line, and still exits non-zero when the
device kind has no published peak (``benchmarks/peaks.py``) or any
secondary measurement failed.
"""

import json
import os
import shutil
import sys
import time

N_ROWS = 1_000_000
WARMUP = 3
ITERS = 20


def _run() -> int:
    import numpy as np

    import tensorframes_tpu as tft
    from tensorframes_tpu import dtypes as _dt
    from tensorframes_tpu.computation import Computation, TensorSpec
    from tensorframes_tpu.marshal import columns_to_rows, rows_to_columns
    from tensorframes_tpu.parallel.distributed import distribute, dmap_blocks
    from tensorframes_tpu.parallel.mesh import local_mesh
    from tensorframes_tpu.shape import Shape, Unknown

    import jax

    x = np.arange(N_ROWS, dtype=np.float64)
    df = tft.frame({"x": x}, num_partitions=1)
    df.cache()

    # ours: device-resident columnar path, one dispatch per iteration
    mesh = local_mesh()
    dist = distribute(df, mesh)
    comp = Computation.trace(
        lambda x: {"z": x + 3.0},
        [TensorSpec("x", _dt.double, Shape(Unknown))])
    for _ in range(WARMUP):
        out = dmap_blocks(comp, dist, trim=True)
        jax.block_until_ready(out.columns["z"])
    t0 = time.perf_counter()
    for _ in range(ITERS):
        out = dmap_blocks(comp, dist, trim=True)
        jax.block_until_ready(out.columns["z"])
    ours = N_ROWS / ((time.perf_counter() - t0) / ITERS)

    # end-to-end including host<->device marshalling each iteration (the
    # reference's acknowledged weak spot, DataOps.scala:30-33): columnar
    # host frame -> device -> compute -> back to host

    def e2e_iter():
        d2 = distribute(df, mesh)
        o2 = dmap_blocks(comp, d2, trim=True)
        np.asarray(o2.columns["z"])

    e2e_iter()  # warm: allocator + any per-shape retrace out of the loop
    t0 = time.perf_counter()
    for _ in range(5):
        e2e_iter()
    e2e = N_ROWS / ((time.perf_counter() - t0) / 5)

    # which executor backs the engine path — recorded with the result,
    # not part of the measured loop above
    from tensorframes_tpu.engine.executor import default_executor
    executor = type(default_executor()).__name__

    # secondary metric: the host engine's pipelined block stream vs the
    # serial path on the SAME 1M-row map_blocks workload,
    # multi-partition so blocks actually stream. TFT_PIPELINE_DEPTH=1 is
    # the serial engine by construction. A wall-clock budget (checked
    # between full-frame forcings) bounds the run on a slow host.
    pipeline_secondary = None
    pipe_budget_s = 60.0
    pipe_t0 = time.perf_counter()
    try:
        pdf = tft.frame({"x": x}, num_partitions=8)
        pdf.cache()
        pcomp = Computation.trace(
            lambda x: {"z": x + 3.0},
            [TensorSpec("x", _dt.double, Shape(Unknown))])

        def _engine_rows_per_s(depth: int, reps: int = 3) -> float:
            os.environ["TFT_PIPELINE_DEPTH"] = str(depth)
            if time.perf_counter() - pipe_t0 > pipe_budget_s:
                raise RuntimeError(
                    f"pipeline secondary exceeded its {pipe_budget_s:.0f}s "
                    f"budget before the depth-{depth} warmup")
            pdf.map_blocks(pcomp, trim=True).blocks()  # warm the compile
            best = float("inf")
            for _ in range(reps):
                if time.perf_counter() - pipe_t0 > pipe_budget_s \
                        and best < float("inf"):
                    break
                t0 = time.perf_counter()
                pdf.map_blocks(pcomp, trim=True).blocks()
                best = min(best, time.perf_counter() - t0)
            return N_ROWS / best

        serial_rps = _engine_rows_per_s(1)
        pipelined_rps = _engine_rows_per_s(3)
        pipeline_secondary = {
            "serial_rows_per_s": round(serial_rps, 1),
            "pipelined_rows_per_s": round(pipelined_rps, 1),
            "speedup": round(pipelined_rps / serial_rps, 3),
            "depth": 3,
        }
    except Exception as e:  # noqa: BLE001 - headline must survive
        pipeline_secondary = {"error": str(e)[:300]}
    finally:
        os.environ.pop("TFT_PIPELINE_DEPTH", None)

    # secondary metric (never costs the headline): the observability
    # layer's cost on the SAME 1M-row host-engine map_blocks workload.
    # Three modes: "bypass" (query_trace/add_event short-circuited at
    # their first flag check — the closest runtime stand-in for the
    # pre-observability engine), "off" (the hooks run their normal
    # disabled checks — the default production path), "on" (TFT_TRACE=1
    # with query traces, block events, and stage attribution). The
    # acceptance bar is the off-vs-bypass delta: the disabled layer must
    # cost <2%. Wall-clock budgeted like the pipeline secondary.
    tracing_secondary = None
    trace_budget_s = 45.0
    trace_t0 = time.perf_counter()
    try:
        from tensorframes_tpu.observability import events as _obs_events
        from tensorframes_tpu.utils import tracing as _tracing

        tdf = tft.frame({"x": x}, num_partitions=8)
        tdf.cache()
        tcomp = Computation.trace(
            lambda x: {"z": x + 3.0},
            [TensorSpec("x", _dt.double, Shape(Unknown))])

        def _force_once() -> float:
            t0 = time.perf_counter()
            tdf.map_blocks(tcomp, trim=True).blocks()
            return time.perf_counter() - t0

        def _measure_bypass() -> float:
            with _obs_events.bypass():
                return _force_once()

        def _measure_off() -> float:
            return _force_once()

        def _measure_on() -> float:
            _tracing.enable()
            try:
                return _force_once()
            finally:
                _tracing.disable()

        from statistics import median as _median

        # The acceptance bar (off regresses <2% vs the layer stripped
        # out) is measured FIRST and alone, as alternating pairs with
        # the in-pair order flipped each round: sequential clumps
        # confound with machine drift, fixed ordering adds position
        # bias, min-of is unstable between near-identical distributions,
        # and tracing-ON iterations in the same loop leave allocation/GC
        # debt that lands asymmetrically — each effect alone dwarfs the
        # disabled layer's real (nanoseconds/block) cost on a ~10ms
        # workload. Medians over ~80 interleaved pairs are stable.
        _tracing.disable()
        _force_once()  # warm the compile cache once for every mode
        samples = {"bypass": [], "off": [], "on": []}
        rounds = 0
        pair_budget_s = trace_budget_s * 0.75
        while rounds < 250 and (time.perf_counter() - trace_t0
                                < pair_budget_s or rounds < 2):
            if rounds % 2:
                samples["off"].append(_measure_off())
                samples["bypass"].append(_measure_bypass())
            else:
                samples["bypass"].append(_measure_bypass())
                samples["off"].append(_measure_off())
            rounds += 1
        # tracing-ON cost is informational (the documented price of
        # TFT_TRACE=1), measured after the off/bypass pairs
        while len(samples["on"]) < 20 and (
                time.perf_counter() - trace_t0 < trace_budget_s
                or not samples["on"]):
            samples["on"].append(_measure_on())

        bypass_rps = N_ROWS / _median(samples["bypass"])
        off_rps = N_ROWS / _median(samples["off"])
        on_rps = N_ROWS / _median(samples["on"])
        off_overhead_pct = (bypass_rps - off_rps) / bypass_rps * 100.0
        tracing_secondary = {
            "bypass_rows_per_s": round(bypass_rps, 1),
            "off_rows_per_s": round(off_rps, 1),
            "on_rows_per_s": round(on_rps, 1),
            "off_overhead_pct": round(off_overhead_pct, 2),
            "on_overhead_pct": round(
                (bypass_rps - on_rps) / bypass_rps * 100.0, 2),
            "off_within_2pct": bool(off_overhead_pct < 2.0),
        }
    except Exception as e:  # noqa: BLE001 - headline must survive
        tracing_secondary = {"error": str(e)[:300]}

    # secondary metric (never costs the headline): the observability
    # layer's cost on the DISTRIBUTED path — the mesh-level shard/device
    # instrumentation in dmap_blocks (per-shard events, per-device
    # readiness, HBM samples) must stay free when tracing is off. Same
    # interleaved order-flipped off-vs-bypass pair discipline and
    # wall-clock budget as the host-engine tracing secondary; the
    # acceptance bar is off within 2% of bypass.
    mesh_tracing_secondary = None
    mesh_budget_s = 40.0
    mesh_t0 = time.perf_counter()
    try:
        from statistics import median as _mmedian

        from tensorframes_tpu.observability import events as _mobs_events
        from tensorframes_tpu.utils import tracing as _mtracing

        mdist = distribute(df, mesh)

        def _mesh_force() -> float:
            t0 = time.perf_counter()
            out = dmap_blocks(comp, mdist, trim=True)
            jax.block_until_ready(out.columns["z"])
            return time.perf_counter() - t0

        _mtracing.disable()
        _mesh_force()  # warm the compile cache for every mode
        msamples = {"bypass": [], "off": [], "on": []}
        rounds = 0
        mesh_pair_budget_s = mesh_budget_s * 0.75
        while rounds < 250 and (time.perf_counter() - mesh_t0
                                < mesh_pair_budget_s or rounds < 2):
            if rounds % 2:
                msamples["off"].append(_mesh_force())
                with _mobs_events.bypass():
                    msamples["bypass"].append(_mesh_force())
            else:
                with _mobs_events.bypass():
                    msamples["bypass"].append(_mesh_force())
                msamples["off"].append(_mesh_force())
            rounds += 1
        # tracing-ON cost is informational (per-device readiness waits
        # serialize the gather, the documented price of TFT_TRACE=1)
        while len(msamples["on"]) < 10 and (
                time.perf_counter() - mesh_t0 < mesh_budget_s
                or not msamples["on"]):
            _mtracing.enable()
            try:
                msamples["on"].append(_mesh_force())
            finally:
                _mtracing.disable()

        mbypass_rps = N_ROWS / _mmedian(msamples["bypass"])
        moff_rps = N_ROWS / _mmedian(msamples["off"])
        mon_rps = N_ROWS / _mmedian(msamples["on"])
        moff_pct = (mbypass_rps - moff_rps) / mbypass_rps * 100.0
        mesh_tracing_secondary = {
            "bypass_rows_per_s": round(mbypass_rps, 1),
            "off_rows_per_s": round(moff_rps, 1),
            "on_rows_per_s": round(mon_rps, 1),
            "off_overhead_pct": round(moff_pct, 2),
            "on_overhead_pct": round(
                (mbypass_rps - mon_rps) / mbypass_rps * 100.0, 2),
            "off_within_2pct": bool(moff_pct < 2.0),
        }
    except Exception as e:  # noqa: BLE001 - headline must survive
        mesh_tracing_secondary = {"error": str(e)[:300]}

    # secondary metric (never costs the headline): the serving layer
    # under a mixed 3-tenant workload — small/medium/large map_blocks
    # queries submitted concurrently through serve.QueryScheduler.
    # Reports sustained queries/sec, p99 end-to-end latency (from the
    # query_latency_seconds histogram the scheduler feeds with a tenant
    # label), and the shared compile cache's cross-tenant hits. Wall-
    # clock budgeted like the other secondaries.
    serving_secondary = None
    serve_budget_s = 40.0
    serve_t0 = time.perf_counter()
    try:
        from tensorframes_tpu.serve import (QueryScheduler, ServerStats,
                                            TenantQuota)

        sizes = {"small": 10_000, "medium": 100_000, "large": 400_000}
        frames = {t: [tft.frame({"x": np.arange(float(n)) + k},
                                num_partitions=4)
                      for k in range(8)]
                  for t, n in sizes.items()}
        quotas = {t: TenantQuota(weight=2.0 if t == "large" else 1.0,
                                 max_queue=1024)
                  for t in sizes}
        with QueryScheduler(quotas=quotas, workers=3,
                            name="bench") as sched:
            # warm the (shared) compile once so the measured window is
            # steady-state serving, not first-compile
            sched.submit(frames["small"][0],
                         lambda x: {"z": x + 3.0},
                         tenant="small").result(timeout=60)
            t0 = time.perf_counter()
            futs = []
            rounds = 0
            while time.perf_counter() - t0 < serve_budget_s * 0.5 \
                    and rounds < 8:
                for t in sizes:
                    for fr in frames[t]:
                        futs.append(sched.submit(
                            fr, lambda x: {"z": x + 3.0}, tenant=t))
                rounds += 1
            for f in futs:
                f.result(timeout=max(
                    5.0, serve_budget_s - (time.perf_counter() - t0)))
            elapsed = time.perf_counter() - t0
            stats = ServerStats(sched)
            p99 = stats.p99()
            cc = sched.compile_cache.stats()
            serving_secondary = {
                "queries": len(futs),
                "queries_per_s": round(len(futs) / elapsed, 1),
                "p99_latency_s": round(p99, 4) if p99 is not None
                else None,
                "tenants": len(sizes),
                "workers": 3,
                "compile_cache_hits": cc["hits"],
                "compile_cache_misses": cc["misses"],
            }
    except Exception as e:  # noqa: BLE001 - headline must survive
        serving_secondary = {"error": str(e)[:300]}

    # secondary metric (never costs the headline): the streaming
    # subsystem's sustained throughput — a generator source feeding
    # map_blocks + windowed keyed aggregation through StreamHandle.step,
    # reporting batches/sec and p99 per-batch latency at steady state
    # (post-warmup: every batch is a compile-cache hit; state stays
    # bounded by the watermark). Wall-clock budgeted like the others.
    streaming_secondary = None
    stream_budget_s = 30.0
    stream_t0 = time.perf_counter()
    try:
        from tensorframes_tpu import stream as tstream

        s_rows, s_keys = 50_000, 64

        def s_gen():
            i = 0
            base_k = (np.arange(s_rows) % s_keys).astype(np.int64)
            base_v = np.arange(s_rows, dtype=np.float64)
            while True:
                yield {"k": base_k, "v": base_v + i,
                       "ts": np.full(s_rows, float(i))}
                i += 1

        s_agg = (tstream.from_source(tstream.GeneratorSource(s_gen()))
                 .map_blocks(lambda v: {"v2": v * 2.0})
                 .select(["k", "v2", "ts"])
                 .group_by("k")
                 .aggregate({"v2": "sum"}, window=tstream.tumbling(8.0),
                            time_col="ts", watermark_delay=2.0))
        sh = s_agg.start(name="bench-stream")
        for _ in range(5):  # warm the compile + merge-program caches
            sh.step()
        lat = []
        t0 = time.perf_counter()
        while (time.perf_counter() - stream_t0 < stream_budget_s * 0.8
               and len(lat) < 400):
            b0 = time.perf_counter()
            sh.step()
            lat.append(time.perf_counter() - b0)
        elapsed = time.perf_counter() - t0
        sm = sh.metrics()
        if lat and elapsed > 0:
            lat.sort()
            p99 = lat[max(0, -(-len(lat) * 99 // 100) - 1)]
            streaming_secondary = {
                "batches": len(lat),
                "rows_per_batch": s_rows,
                "batches_per_s": round(len(lat) / elapsed, 2),
                "rows_per_s": round(len(lat) * s_rows / elapsed, 1),
                "p99_batch_latency_s": round(p99, 5),
                "state_rows": sm["state_rows"],
                "windows_emitted": sm["windows_emitted"],
                "skipped": sm["batches_skipped"],
            }
        else:
            # warmup ate the whole budget (slow box): report what ran
            # instead of erroring the secondary
            streaming_secondary = {
                "batches": 0,
                "error": "warmup consumed the wall-clock budget",
                "warmup_batches": sm["batches"],
            }
    except Exception as e:  # noqa: BLE001 - headline must survive
        streaming_secondary = {"error": str(e)[:300]}

    # secondary metric (never costs the headline): the elastic-mesh
    # layer — every mesh-op dispatch now passes through
    # parallel.elastic (device fault-site check + skew tracker + loss
    # recovery). Two numbers: (1) the healthy-mesh overhead of that
    # boundary, measured as interleaved order-flipped pairs against
    # elastic.bypass() like the tracing secondaries (acceptance bar:
    # <2%) — each sample amortizes a BATCH of forcings, because the
    # boundary's real cost (~2 us/op) sits below the per-forcing timer
    # noise of a loaded box; (2) dmap_blocks throughput after ONE
    # injected device loss — the op completes on the shrunken mesh
    # instead of raising, at proportionally lower throughput.
    # Wall-clock budgeted.
    elastic_secondary = None
    el_budget_s = 30.0
    el_t0 = time.perf_counter()
    try:
        from statistics import median as _emedian

        from tensorframes_tpu.parallel import elastic as _elastic
        from tensorframes_tpu.resilience import faults as _efaults
        from tensorframes_tpu.utils import tracing as _etracing

        edist = distribute(df, mesh)
        EL_BATCH = 20

        def _eforce(d) -> float:
            t0 = time.perf_counter()
            out = dmap_blocks(comp, d, trim=True)
            jax.block_until_ready(out.columns["z"])
            return time.perf_counter() - t0

        def _ebatch(d) -> float:
            t0 = time.perf_counter()
            for _ in range(EL_BATCH):
                out = dmap_blocks(comp, d, trim=True)
                jax.block_until_ready(out.columns["z"])
            return (time.perf_counter() - t0) / EL_BATCH

        _eforce(edist)  # warm
        esamples = {"bypass": [], "on": []}
        rounds = 0
        while rounds < 40 and (time.perf_counter() - el_t0
                               < el_budget_s * 0.5 or rounds < 2):
            if rounds % 2:
                esamples["on"].append(_ebatch(edist))
                with _elastic.bypass():
                    esamples["bypass"].append(_ebatch(edist))
            else:
                with _elastic.bypass():
                    esamples["bypass"].append(_ebatch(edist))
                esamples["on"].append(_ebatch(edist))
            rounds += 1
        eb_rps = N_ROWS / _emedian(esamples["bypass"])
        eo_rps = N_ROWS / _emedian(esamples["on"])
        e_pct = (eb_rps - eo_rps) / eb_rps * 100.0

        elastic_secondary = {
            "bypass_rows_per_s": round(eb_rps, 1),
            "on_rows_per_s": round(eo_rps, 1),
            "off_overhead_pct": round(e_pct, 2),
            "off_within_2pct": bool(e_pct < 2.0),
            "devices_full": mesh.num_devices,
        }
        if mesh.num_devices >= 2:
            # one injected device loss: the non-trim dmap recovers onto
            # the shrunken mesh and its output frame (input column
            # riding along) is the degraded-mesh workload
            lost_before = _etracing.counters.get("mesh.devices_lost")
            _efaults.arm("device", 1)
            try:
                shrunk = dmap_blocks(comp, edist).select(["x"])
            finally:
                _efaults.reset("device")
            _eforce(shrunk)  # warm the smaller-mesh compile
            deg = []
            while len(deg) < 10 and (time.perf_counter() - el_t0
                                     < el_budget_s or not deg):
                deg.append(_eforce(shrunk))
            elastic_secondary.update({
                "degraded_rows_per_s": round(N_ROWS / _emedian(deg), 1),
                "devices_degraded": shrunk.mesh.num_devices,
                "devices_lost":
                    _etracing.counters.get("mesh.devices_lost")
                    - lost_before,
            })
        else:
            # a 1-device mesh has no survivors to shrink to; the 8-way
            # recovery itself is proven by the tier-1 elastic lane on 8
            # virtual CPU devices — this secondary's loss half needs
            # real multi-chip (the TPU capture)
            elastic_secondary["degraded"] = (
                "skipped: single-device mesh (loss recovery needs >=2)")
    except Exception as e:  # noqa: BLE001 - headline must survive
        elastic_secondary = {"error": str(e)[:300]}

    # secondary metric (never costs the headline): the out-of-core
    # memory subsystem (docs/memory.md). Two numbers: (1) the admission
    # gate's overhead on the hot engine path — interleaved order-flipped
    # pairs of amortized forcing batches, no-limit configuration vs a
    # LIVE never-pressured ledger; the <2% bar on the ledger cost
    # bounds the unlimited gate's a fortiori; (2) out-of-core sort
    # throughput: external dsort of a frame ~4x a configured budget
    # (budget-sized device runs + host k-way merge), reported with its
    # spill count. Wall-clock budgeted like every secondary.
    memory_secondary = None
    mem_budget_s = 30.0
    mem_t0 = time.perf_counter()
    try:
        from statistics import median as _mmedian

        from tensorframes_tpu import memory as _memory
        from tensorframes_tpu.utils.tracing import counters as _mcounters

        mdf = tft.frame({"x": np.arange(200_000, dtype=np.float64)},
                    num_partitions=8)
        MEM_BATCH = 10
        HUGE = 1 << 60  # a LIVE ledger that is never under pressure

        def _mbatch() -> float:
            t0 = time.perf_counter()
            for _ in range(MEM_BATCH):
                out = tft.map_blocks(lambda x: {"z": x + 3.0}, mdf,
                                 trim=True)
                out.blocks()
            return (time.perf_counter() - t0) / MEM_BATCH

        # "off" = explicit no-limit (active() is None, the one-global-
        # read gate); "ledger" = full admission arithmetic on every
        # dispatch with a huge budget (zero spills). The measured
        # ledger cost bounds the unlimited gate's from above — with
        # limit_bytes=0 both halves would run identical code and the
        # bar would be vacuous.
        _memory.configure(limit_bytes=HUGE)
        _mbatch()  # warm the compiles
        msamples = {"off": [], "ledger": []}
        rounds = 0
        while rounds < 40 and (time.perf_counter() - mem_t0
                               < mem_budget_s * 0.5 or rounds < 2):
            if rounds % 2:
                _memory.configure(limit_bytes=HUGE)
                msamples["ledger"].append(_mbatch())
                _memory.configure(limit_bytes=0)
                msamples["off"].append(_mbatch())
            else:
                _memory.configure(limit_bytes=0)
                msamples["off"].append(_mbatch())
                _memory.configure(limit_bytes=HUGE)
                msamples["ledger"].append(_mbatch())
            rounds += 1
        mb = 200_000 / _mmedian(msamples["off"])
        mo = 200_000 / _mmedian(msamples["ledger"])
        m_pct = (mb - mo) / mb * 100.0
        memory_secondary = {
            "unlimited_rows_per_s": round(mb, 1),
            "ledger_rows_per_s": round(mo, 1),
            "ledger_overhead_pct": round(m_pct, 2),
            "off_within_2pct": bool(m_pct < 2.0),
        }

        # out-of-core half: external dsort of a frame ~4x the budget
        if time.perf_counter() - mem_t0 < mem_budget_s * 0.8:
            rng_m = np.random.default_rng(7)
            oc_rows = 100_000  # 2 f64 columns = 1.6 MB
            oc_df = tft.frame(
                {"k": rng_m.integers(0, 10_000, oc_rows)
                 .astype(np.int64),
                 "v": rng_m.random(oc_rows)}, num_partitions=8)
            _memory.configure(limit_bytes=400_000)  # ~4x over budget
            spills0 = _mcounters.get("memory.spills")
            oc_dist = distribute(oc_df, mesh)
            t0 = time.perf_counter()
            from tensorframes_tpu.parallel.distributed import dsort
            out = dsort("k", oc_dist)
            out.collect_frame()
            oc_dt = time.perf_counter() - t0
            memory_secondary.update({
                "out_of_core_sort_rows_per_s": round(oc_rows / oc_dt, 1),
                "out_of_core_sort_spills":
                    _mcounters.get("memory.spills") - spills0,
                "external_sorts":
                    _mcounters.get("memory.external_sorts"),
                "budget_bytes": 400_000,
                "frame_bytes": oc_rows * 16,
            })
        else:
            memory_secondary["out_of_core"] = (
                "skipped: overhead half consumed the wall-clock budget")
    except Exception as e:  # noqa: BLE001 - headline must survive
        memory_secondary = {"error": str(e)[:300]}
    finally:
        try:
            from tensorframes_tpu import memory as _memory
            _memory._reset()  # back to env-resolved (unlimited) state
        except Exception:  # noqa: BLE001 - cleanup is best-effort
            pass

    # secondary metric (never costs the headline): the logical-plan
    # layer (docs/plan.md). Two numbers: (1) a 4-op row-local
    # map_blocks chain forced fused (one composed dispatch per block,
    # the TFT_FUSE default) vs TFT_FUSE=0 (the per-op path: one
    # dispatch + host round trip per op per block) — the whole chain
    # uncached between forcings so the per-op side re-runs every op,
    # best-of timings; (2) a pruned parquet read's bytes-touched
    # figure: a chain referencing 2 of 6 columns reads only those
    # columns' chunks (footer-driven), reported against the whole
    # file. Wall-clock budgeted like every secondary.
    fused_secondary = None
    fuse_budget_s = 40.0
    fuse_t0 = time.perf_counter()
    try:
        fx = np.arange(N_ROWS, dtype=np.float64)
        fdf = tft.frame({"x": fx, "w": np.ones_like(fx)},
                        num_partitions=16)
        fdf.cache()
        f1 = fdf.map_blocks(lambda x: {"a": x + 1.0})
        f2 = f1.map_blocks(lambda a: {"b": a * 2.0})
        f3 = f2.map_blocks(lambda b, w: {"c": b + w})
        f4 = f3.map_blocks(lambda c: {"d": c * 0.5})
        fchain = f4.select(["d"])
        fframes = [f1, f2, f3, f4, fchain]

        def _force_chain_best(reps: int = 5) -> float:
            for f in fframes:
                f.uncache()
            fchain.blocks()  # warm the compile caches for this mode
            t = float("inf")
            for _ in range(reps):
                if time.perf_counter() - fuse_t0 > fuse_budget_s * 0.6 \
                        and t < float("inf"):
                    break
                for f in fframes:
                    f.uncache()
                t0 = time.perf_counter()
                fchain.blocks()
                t = min(t, time.perf_counter() - t0)
            return t

        os.environ.pop("TFT_FUSE", None)
        fused_s = _force_chain_best()
        fused_plan = bool(fchain._plan_info)
        os.environ["TFT_FUSE"] = "0"
        unfused_s = _force_chain_best()
        os.environ.pop("TFT_FUSE", None)
        fused_secondary = {
            "chain_ops": 4,
            "fused_rows_per_s": round(N_ROWS / fused_s, 1),
            "unfused_rows_per_s": round(N_ROWS / unfused_s, 1),
            "speedup": round(unfused_s / fused_s, 3),
            "plan_executed": fused_plan,
        }

        # pruned-read half: bytes touched for a 2-of-6-column chain
        if time.perf_counter() - fuse_t0 < fuse_budget_s * 0.85:
            import shutil
            import tempfile

            from tensorframes_tpu import io as tio

            pdir = tempfile.mkdtemp(prefix="tft_fused_bench_")
            try:
                ppth = os.path.join(pdir, "pruned.parquet")
                pcols = {f"c{i}": np.arange(200_000, dtype=np.float64) + i
                         for i in range(6)}
                tio.write_parquet(tft.frame(pcols, num_partitions=4), ppth)
                import pyarrow.parquet as pq
                md = pq.ParquetFile(ppth).metadata
                col_sz = {}
                for g in range(md.num_row_groups):
                    rg = md.row_group(g)
                    for j in range(rg.num_columns):
                        c = rg.column(j)
                        base = c.path_in_schema.split(".", 1)[0]
                        col_sz[base] = col_sz.get(base, 0) \
                            + int(c.total_compressed_size)
                pruned = (tio.read_parquet(ppth)
                          .map_blocks(lambda c0, c1: {"s": c0 + c1})
                          .select(["s"]))
                pruned.blocks()
                touched = col_sz["c0"] + col_sz["c1"]
                fused_secondary.update({
                    "pruned_read_cols": 2,
                    "total_cols": 6,
                    "pruned_bytes_touched": touched,
                    "file_bytes": sum(col_sz.values()),
                    "pruned_fraction": round(
                        touched / max(sum(col_sz.values()), 1), 3),
                    "pruned_plan_executed": bool(pruned._plan_info),
                })
            finally:
                shutil.rmtree(pdir, ignore_errors=True)
        else:
            fused_secondary["pruned_read"] = (
                "skipped: chain half consumed the wall-clock budget")
    except Exception as e:  # noqa: BLE001 - headline must survive
        fused_secondary = {"error": str(e)[:300]}
    finally:
        os.environ.pop("TFT_FUSE", None)

    # secondary metric (never costs the headline): the DISTRIBUTED
    # logical plan (docs/plan.md, distributed fusion). A 4-op d-op
    # chain (dmap -> dfilter -> dmap -> monoid dreduce_blocks) on the
    # local mesh, recorded lazily and forced as ONE fused GSPMD
    # program, vs TFT_FUSE=0 (the per-op dispatches: 4 compiled mesh
    # dispatches + the dfilter survivor-count host readback between
    # ops). Reports speedup, mesh dispatch counts, and inter-stage
    # host-transfer bytes (the acceptance bar: >= 2x fewer dispatches,
    # ZERO fused inter-stage bytes). Wall-clock budgeted.
    dfused_secondary = None
    dfuse_budget_s = 40.0
    dfuse_t0 = time.perf_counter()
    try:
        from tensorframes_tpu.utils.tracing import counters as _dfc

        dmesh = mesh
        dN = 200_000
        ddf = tft.frame({"x": np.arange(dN, dtype=np.float64)})
        ddist = distribute(ddf, dmesh)
        from tensorframes_tpu.parallel.distributed import (dfilter,
                                                           dreduce_blocks)

        _m1 = lambda x: {"z": x * 2.0}          # noqa: E731
        _f1 = lambda z: z % 3.0 == 0.0          # noqa: E731
        _m2 = lambda z: {"w": z + 1.0}          # noqa: E731

        def _dchain(d):
            d = dmap_blocks(_m1, d)
            d = dfilter(_f1, d)
            d = dmap_blocks(_m2, d)
            return dreduce_blocks({"w": "sum"}, d)

        def _dbest(lazy: bool, reps: int = 7) -> float:
            _dchain(ddist.lazy() if lazy else ddist)  # warm compiles
            t = float("inf")
            for _ in range(reps):
                if time.perf_counter() - dfuse_t0 > dfuse_budget_s * 0.6 \
                        and t < float("inf"):
                    break
                t0 = time.perf_counter()
                _dchain(ddist.lazy() if lazy else ddist)
                t = min(t, time.perf_counter() - t0)
            return t

        os.environ.pop("TFT_FUSE", None)
        d0 = _dfc.get("mesh.dispatches")
        h0 = _dfc.get("mesh.interstage_host_bytes")
        fused_r = _dchain(ddist.lazy())
        fused_disp = _dfc.get("mesh.dispatches") - d0
        fused_host = _dfc.get("mesh.interstage_host_bytes") - h0
        d1 = _dfc.get("mesh.dispatches")
        h1 = _dfc.get("mesh.interstage_host_bytes")
        os.environ["TFT_FUSE"] = "0"
        perop_r = _dchain(ddist.lazy())   # lazy() is the identity: per-op
        perop_disp = _dfc.get("mesh.dispatches") - d1
        perop_host = _dfc.get("mesh.interstage_host_bytes") - h1
        os.environ.pop("TFT_FUSE", None)
        bit_identical = bool(np.array_equal(fused_r["w"], perop_r["w"]))

        dfused_s = _dbest(lazy=True)
        os.environ["TFT_FUSE"] = "0"
        dperop_s = _dbest(lazy=False)
        os.environ.pop("TFT_FUSE", None)
        dfused_secondary = {
            "chain_ops": 4,
            "fused_rows_per_s": round(dN / dfused_s, 1),
            "perop_rows_per_s": round(dN / dperop_s, 1),
            "speedup": round(dperop_s / dfused_s, 3),
            "fused_mesh_dispatches": int(fused_disp),
            "perop_mesh_dispatches": int(perop_disp),
            "dispatch_reduction_x": round(perop_disp / max(fused_disp, 1),
                                          2),
            "fused_interstage_host_bytes": int(fused_host),
            "perop_interstage_host_bytes": int(perop_host),
            "bit_identical_vs_fuse0": bit_identical,
        }
    except Exception as e:  # noqa: BLE001 - headline must survive
        dfused_secondary = {"error": str(e)[:300]}
    finally:
        os.environ.pop("TFT_FUSE", None)

    # secondary metric (never costs the headline): broadcast hash join
    # probe throughput (docs/joins.md) — a 64k-row build side
    # factorized + device-broadcast once, a 512k-row probe side joined
    # block by block (one fused gather dispatch per block through the
    # resilient executor). Reports probe rows/s and the dispatch count.
    # Wall-clock budgeted like every secondary.
    join_secondary = None
    join_budget_s = 30.0
    join_t0 = time.perf_counter()
    try:
        from tensorframes_tpu import relational as _rel
        from tensorframes_tpu.utils.tracing import counters as _jc

        jbuild_n, jprobe_n, jparts = 64_000, 512_000, 8
        jrng = np.random.default_rng(0)
        jright = tft.frame({
            "k": np.arange(jbuild_n, dtype=np.int64),
            "w": jrng.normal(0, 1, jbuild_n),
            "w2": jrng.normal(0, 1, jbuild_n)})
        jleft = tft.frame({
            "k": jrng.integers(0, jbuild_n, jprobe_n).astype(np.int64),
            "v": jrng.normal(0, 1, jprobe_n)}, num_partitions=jparts)
        build = _rel.BuildTable(jright, "k")

        def _force_join():
            out = _rel.broadcast_join(jleft, build=build, on="k",
                                      how="inner")
            return out.count()

        _force_join()  # warm the probe program
        jt = float("inf")
        rounds = 0
        d0 = _jc.get("relational.probe_dispatches")
        while (time.perf_counter() - join_t0 < join_budget_s * 0.8
               or rounds < 2) and rounds < 5:
            t0 = time.perf_counter()
            jrows = _force_join()
            jt = min(jt, time.perf_counter() - t0)
            rounds += 1
        join_secondary = {
            "build_rows": jbuild_n,
            "probe_rows": jprobe_n,
            "output_rows": int(jrows),
            "probe_rows_per_s": round(jprobe_n / jt, 1),
            "probe_dispatches_per_forcing":
                (_jc.get("relational.probe_dispatches") - d0) // max(
                    rounds, 1),
            "chunked": bool(build.chunks),
        }
    except Exception as e:  # noqa: BLE001 - headline must survive
        join_secondary = {"error": str(e)[:300]}

    # secondary metric (never costs the headline): partitioned hash
    # join through the shuffle exchange (parallel/exchange.py) vs the
    # broadcast oracle — reports probe rows/s, the per-device build
    # residency (max shard vs global: the O(R/S) claim — the probe side
    # never collects onto one device), and bit-identity. Runs on
    # whatever mesh the chip mode provides (CPU: 1 device -> the
    # fallback path; TPU window: real shards). Wall-clock budgeted.
    pjoin_secondary = None
    pjoin_budget_s = 30.0
    pjoin_t0 = time.perf_counter()
    try:
        from tensorframes_tpu import relational as _rel

        pbuild_n, pprobe_n = 200_000, 400_000
        prng = np.random.default_rng(2)
        pright = tft.frame({
            "k": prng.integers(0, pbuild_n, pbuild_n).astype(np.int64),
            "w": prng.normal(0, 1, pbuild_n)})
        pleft = tft.frame({
            "k": prng.integers(0, pbuild_n, pprobe_n).astype(np.int64),
            "v": prng.normal(0, 1, pprobe_n)}, num_partitions=8)

        def _force_pjoin():
            out = _rel.partitioned_hash_join(pleft, pright, "k",
                                             how="inner", mesh=mesh)
            return out, out.count()

        pout, prows = _force_pjoin()  # warm the exchange programs
        pt = float("inf")
        rounds = 0
        while (time.perf_counter() - pjoin_t0 < pjoin_budget_s * 0.8
               or rounds < 1) and rounds < 3:
            t0 = time.perf_counter()
            pout, prows = _force_pjoin()
            pt = min(pt, time.perf_counter() - t0)
            rounds += 1
        pinfo = getattr(pout, "_partitioned_info", None) or {}
        oracle = _rel.broadcast_join(pleft, pright, "k", how="inner")
        pjoin_secondary = {
            "build_rows": pbuild_n,
            "probe_rows": pprobe_n,
            "output_rows": int(prows),
            "probe_rows_per_s": round(pprobe_n / pt, 1),
            "shards": pinfo.get("shards", 1),
            "max_shard_build_bytes": pinfo.get("max_build_bytes"),
            "global_build_bytes": pinfo.get("global_build_bytes"),
            "bit_identical_vs_broadcast":
                bool(int(prows) == int(oracle.count())),
        }
    except Exception as e:  # noqa: BLE001 - headline must survive
        pjoin_secondary = {"error": str(e)[:300]}

    # secondary metric (never costs the headline): shuffle-partitioned
    # daggregate (high-cardinality keys) vs the dense monoid path —
    # each device holds O(groups/shards) state instead of every group.
    # Reports rows/s for both paths and result parity. Wall-clock
    # budgeted, chip-mode ready.
    sagg_secondary = None
    sagg_budget_s = 30.0
    sagg_t0 = time.perf_counter()
    try:
        from tensorframes_tpu.parallel import (daggregate as _dagg,
                                               shuffle_daggregate
                                               as _sagg)

        aN, aG = 400_000, 50_000
        arng = np.random.default_rng(3)
        adf = tft.frame({
            "k": arng.integers(0, aG, aN).astype(np.int64),
            "v": arng.integers(0, 1000, aN).astype(np.int64)})

        def _run(fn):
            t0 = time.perf_counter()
            out = fn({"v": "sum"}, distribute(adf, mesh), ["k"])
            n = sum(b.num_rows for b in out.blocks())
            return n, time.perf_counter() - t0

        _run(_sagg)  # warm
        _run(_dagg)
        ns, ts = _run(_sagg)
        nd, td = _run(_dagg)
        sagg_secondary = {
            "rows": aN,
            "groups": int(nd),
            "shuffle_rows_per_s": round(aN / ts, 1),
            "dense_rows_per_s": round(aN / td, 1),
            "same_group_count": bool(ns == nd),
        }
    except Exception as e:  # noqa: BLE001 - headline must survive
        sagg_secondary = {"error": str(e)[:300]}

    # secondary metric (never costs the headline): approx_distinct
    # (HLL sketch, docs/joins.md) vs the EXACT distinct count computed
    # through two monoid aggregates (count per (g,item), then count per
    # g). Reports the speedup and the observed worst-group relative
    # error against the 1.04/sqrt(m) bound. Wall-clock budgeted.
    sketch_secondary = None
    sketch_budget_s = 30.0
    sketch_t0 = time.perf_counter()
    try:
        from tensorframes_tpu import relational as _rel

        sN, sG = 400_000, 8
        srng = np.random.default_rng(1)
        sdf = tft.frame({
            "g": srng.integers(0, sG, sN).astype(np.int64),
            "it": srng.integers(0, 50_000, sN).astype(np.int64),
            "one": np.ones(sN, np.int64)}, num_partitions=8)
        sk = _rel.approx_distinct(bits=12)

        def _exact():
            per_pair = tft.aggregate({"one": "sum"},
                                     sdf.group_by("g", "it"))
            ones2 = per_pair.map_blocks(
                lambda one: {"c": one * 0 + 1}).select(["g", "c"])
            return tft.aggregate({"c": "sum"}, ones2.group_by("g"))

        def _approx():
            return tft.aggregate({"it": sk},
                                 sdf.select(["g", "it"]).group_by("g"))

        exact_f = _exact()     # warm + truth
        approx_f = _approx()
        exact = {int(r[0]): int(r[1]) for r in exact_f.collect()}
        approx = {int(r[0]): int(r[1]) for r in approx_f.collect()}
        worst = max(abs(approx[g] - exact[g]) / exact[g]
                    for g in exact)
        te = ta = float("inf")
        rounds = 0
        while (time.perf_counter() - sketch_t0 < sketch_budget_s * 0.8
               or rounds < 1) and rounds < 3:
            t0 = time.perf_counter()
            _exact()
            te = min(te, time.perf_counter() - t0)
            t0 = time.perf_counter()
            _approx()
            ta = min(ta, time.perf_counter() - t0)
            rounds += 1
        sketch_secondary = {
            "rows": sN,
            "groups": sG,
            "exact_s": round(te, 4),
            "approx_s": round(ta, 4),
            "speedup": round(te / ta, 2),
            "worst_group_rel_error": round(worst, 4),
            "error_bound_1sigma": round(sk.relative_error, 4),
        }
    except Exception as e:  # noqa: BLE001 - headline must survive
        sketch_secondary = {"error": str(e)[:300]}

    # secondary metric (never costs the headline): preemptible serving
    # (docs/serving.md) — a whale query preempted by small
    # higher-priority queries. Reports (a) the small-query worst-case
    # latency behind a running whale WITH vs WITHOUT preemption (the
    # p99 a high-priority tenant actually feels), and (b) the cost of
    # being preempted: park-at-half + checkpointed resume vs one cold
    # uninterrupted run. Wall-clock budgeted like every secondary.
    preempt_secondary = None
    preempt_budget_s = 45.0
    preempt_t0 = time.perf_counter()
    try:
        import threading as _threading

        from tensorframes_tpu.engine import preempt as _pp
        from tensorframes_tpu.resilience import QueryPreempted
        from tensorframes_tpu.serve import QueryScheduler, TenantQuota

        wN, sN = 400_000, 20_000

        def whale_frame(seed=0.0):
            return tft.frame(
                {"x": np.arange(float(wN)) + seed},
                num_partitions=32).map_rows(
                lambda x: {"y": x * 2.0}).map_rows(
                lambda y: {"z": y + 1.0})

        # -- resume overhead vs a cold re-run (engine-level) ---------
        cold = whale_frame()
        t0 = time.perf_counter()
        cold.blocks()  # also warms the compile caches
        t_cold0 = time.perf_counter() - t0
        t0 = time.perf_counter()
        whale_frame(1.0).blocks()
        t_cold = time.perf_counter() - t0  # steady-state cold run
        from tensorframes_tpu.utils.tracing import counters as _pc
        parked = whale_frame(2.0)
        sc = _pp.PreemptionScope("bench-whale")
        timer = _threading.Timer(t_cold / 2.0,
                                 sc.request_preempt, args=("bench",))
        timer.start()
        t0 = time.perf_counter()
        preempted = False
        try:
            with _pp.activate(sc):
                parked.blocks()
        except QueryPreempted:
            preempted = True
        t_park = time.perf_counter() - t0
        timer.cancel()
        # a timer that fired between the park and the cancel leaves a
        # stale preempt request that would immediately re-park the
        # resume; clear it
        sc._take_preempt()
        # counter DELTA around this resume only: the scheduler
        # latency runs below preempt/resume on their own and must not
        # inflate the engine-level figure
        resumed0 = _pc.get("pipeline.resumed_blocks")
        t0 = time.perf_counter()
        with _pp.activate(sc):
            parked.blocks()
        t_resume = time.perf_counter() - t0
        resumed_blocks = _pc.get("pipeline.resumed_blocks") - resumed0
        resume_overhead_pct = ((t_park + t_resume) / t_cold - 1.0) * 100.0

        # -- small-query latency behind a whale, with/without --------
        def small_worst_latency(preemption: bool) -> float:
            quotas = {"whale": TenantQuota(weight=1.0),
                      "vip": TenantQuota(weight=8.0)}
            name = "bench-pre" if preemption else "bench-nopre"
            worst = 0.0
            with QueryScheduler(quotas=quotas, workers=1,
                                preemption=preemption,
                                name=name) as sched:
                wq = sched.submit(whale_frame(3.0), tenant="whale")
                for _ in range(2000):
                    if wq.state != "queued":
                        break
                    time.sleep(0.001)
                left = max(10.0, preempt_budget_s
                           - (time.perf_counter() - preempt_t0))
                for k in range(4):
                    fr = tft.frame({"x": np.arange(float(sN)) + k},
                                   num_partitions=2)
                    t0 = time.perf_counter()
                    sched.submit(fr, lambda x: {"z": x + 3.0},
                                 tenant="vip").result(timeout=left)
                    worst = max(worst, time.perf_counter() - t0)
                wq.result(timeout=left)
            return worst

        os.environ["TFT_PREEMPT_AFTER_MS"] = "0"
        try:
            small_off = small_worst_latency(False)
            small_on = small_worst_latency(True)
        finally:
            os.environ.pop("TFT_PREEMPT_AFTER_MS", None)
        preempt_secondary = {
            "whale_rows": wN,
            "small_rows": sN,
            "whale_cold_s": round(t_cold, 4),
            "preempted_mid_run": bool(preempted),
            "park_plus_resume_s": round(t_park + t_resume, 4),
            "resume_overhead_pct": round(resume_overhead_pct, 1),
            "resumed_blocks": int(resumed_blocks),
            "small_worst_latency_no_preempt_s": round(small_off, 4),
            "small_worst_latency_preempt_s": round(small_on, 4),
            "small_latency_speedup": round(
                small_off / small_on, 2) if small_on > 0 else None,
            "first_run_with_compile_s": round(t_cold0, 4),
        }
    except Exception as e:  # noqa: BLE001 - headline must survive
        preempt_secondary = {"error": str(e)[:300]}

    # secondary metric (never costs the headline): ADAPTIVE BLOCK
    # SIZING (docs/adaptive.md). A 4-op row-local chain (3 map_rows +
    # an atom filter) over a dispatch-bound 64-small-block layout;
    # adaptive sizing (feedback-gated coalesce to TFT_PIPELINE_DEPTH
    # full slots, original boundaries restored) vs TFT_ADAPTIVE=0 (one
    # dispatch chain per tiny block). Acceptance bar: >= 1.2x on the
    # CPU dev box. Wall-clock budgeted like every secondary.
    adaptive_secondary = None
    ad_budget_s = 40.0
    ad_t0 = time.perf_counter()
    try:
        from tensorframes_tpu.utils.tracing import counters as _adc

        aN = 400_000
        adf = tft.frame({"x": np.arange(aN, dtype=np.float64)},
                        num_partitions=64)
        adf.cache()
        _a1 = lambda x: {"a": x * 2.0}          # noqa: E731
        _a2 = lambda a: {"b": a + 1.0}          # noqa: E731
        _a3 = lambda b: {"c": b * 0.5}          # noqa: E731
        _ap = lambda c: c > 100.0               # noqa: E731
        ad1 = adf.map_rows(_a1)
        ad2 = ad1.map_rows(_a2)
        ad3 = ad2.map_rows(_a3)
        ad4 = ad3.filter(_ap)
        adchain = ad4.select(["c"])
        adframes = [ad1, ad2, ad3, ad4, adchain]
        os.environ["TFT_RESULT_CACHE"] = "0"  # measure layouts, not hits

        def _ad_force_best(reps: int = 5) -> float:
            for f in adframes:
                f.uncache()
            adchain.blocks()  # warm compiles + feedback for this mode
            t = float("inf")
            for _ in range(reps):
                if time.perf_counter() - ad_t0 > ad_budget_s * 0.45 \
                        and t < float("inf"):
                    break
                for f in adframes:
                    f.uncache()
                t0 = time.perf_counter()
                adchain.blocks()
                t = min(t, time.perf_counter() - t0)
            return t

        os.environ.pop("TFT_ADAPTIVE", None)
        layouts0 = _adc.get("plan.adaptive_layouts")
        adaptive_s = _ad_force_best()
        layouts_ran = _adc.get("plan.adaptive_layouts") - layouts0
        os.environ["TFT_ADAPTIVE"] = "0"
        static_s = _ad_force_best()
        os.environ.pop("TFT_ADAPTIVE", None)
        adaptive_secondary = {
            "chain_ops": 4,
            "leaf_blocks": 64,
            "adaptive_rows_per_s": round(aN / adaptive_s, 1),
            "static_rows_per_s": round(aN / static_s, 1),
            "speedup": round(static_s / adaptive_s, 3),
            "adaptive_layouts_ran": int(layouts_ran),
        }
    except Exception as e:  # noqa: BLE001 - headline must survive
        adaptive_secondary = {"error": str(e)[:300]}
    finally:
        os.environ.pop("TFT_ADAPTIVE", None)
        os.environ.pop("TFT_RESULT_CACHE", None)

    # secondary metric (never costs the headline): the PLAN-FINGERPRINT
    # RESULT CACHE (docs/adaptive.md). A repeated hot query (same
    # cached source, same canonical computations, rebuilt chain per
    # request — the dashboard shape) measured three ways: the hit
    # latency (zero block dispatches, asserted via pipeline counters),
    # the miss path with the cache ON (always-fresh fingerprints), and
    # TFT_RESULT_CACHE=0. Acceptance bar: ~0 dispatches on a hit and a
    # miss path within 2% of the off path. Wall-clock budgeted.
    rcache_secondary = None
    rc_budget_s = 30.0
    rc_t0 = time.perf_counter()
    try:
        from tensorframes_tpu.plan import adaptive as _rc_adaptive
        from tensorframes_tpu.utils.tracing import counters as _rcc

        rN = 200_000
        rdf = tft.frame({"x": np.arange(rN, dtype=np.float64)},
                        num_partitions=8)
        rdf.cache()
        _rf = lambda x: {"y": x * 2.0 + 1.0}    # noqa: E731

        def _rc_build(fn=None):
            return rdf.map_blocks(fn or _rf).select(["y"])

        _rc_adaptive.invalidate_results()
        os.environ.pop("TFT_RESULT_CACHE", None)
        _rc_build().blocks()   # seen
        _rc_build().blocks()   # interned
        d0 = _rcc.get("pipeline.submitted") + _rcc.get("pipeline.drained")
        t0 = time.perf_counter()
        hits = 0
        while time.perf_counter() - rc_t0 < rc_budget_s * 0.3 \
                or hits < 3:
            _rc_build().blocks()
            hits += 1
            if hits >= 50:
                break
        hit_s = (time.perf_counter() - t0) / hits
        hit_dispatches = (_rcc.get("pipeline.submitted")
                          + _rcc.get("pipeline.drained")) - d0

        def _force_fresh(reps: int) -> float:
            # a fresh lambda per forcing: always a new fingerprint, so
            # the cache-ON path runs its full lookup+offer overhead
            t = float("inf")
            for k in range(reps):
                fn = (lambda o: (lambda x: {"y": x * 2.0 + o}))(
                    float(k))
                t0 = time.perf_counter()
                _rc_build(fn).blocks()
                t = min(t, time.perf_counter() - t0)
            return t

        miss_on_s = _force_fresh(5)
        os.environ["TFT_RESULT_CACHE"] = "0"
        off_s = _force_fresh(5)
        os.environ.pop("TFT_RESULT_CACHE", None)
        rcache_secondary = {
            "rows": rN,
            "hit_s": round(hit_s, 6),
            "hit_block_dispatches": int(hit_dispatches),
            "hit_rows_per_s": round(rN / hit_s, 1),
            "miss_path_s": round(miss_on_s, 6),
            "off_path_s": round(off_s, 6),
            "miss_overhead_pct": round(
                (miss_on_s - off_s) / off_s * 100.0, 2)
            if off_s > 0 else None,
        }
    except Exception as e:  # noqa: BLE001 - headline must survive
        rcache_secondary = {"error": str(e)[:300]}
    finally:
        os.environ.pop("TFT_RESULT_CACHE", None)

    # secondary metric (never costs the headline): WARM RESTART of the
    # serving fabric (docs/serving.md). A parquet-backed hot query is
    # primed through a 2-worker ServeFabric until the result cache
    # persists to the durable tier; every worker is then rolling-
    # restarted (in-memory caches die with each epoch) and the same
    # query re-issued. Acceptance bar: the post-restart hit is served
    # WARM from disk with ZERO pipeline dispatches. Wall-clock
    # budgeted.
    restart_secondary = None
    rw_budget_s = 30.0
    rw_t0 = time.perf_counter()
    try:
        import tempfile as _rw_tempfile

        from tensorframes_tpu import io as _rw_io
        from tensorframes_tpu.plan import adaptive as _rw_adaptive
        from tensorframes_tpu.serve import ServeFabric as _RwFabric
        from tensorframes_tpu.utils.tracing import counters as _rwc

        rw_dir = _rw_tempfile.mkdtemp(prefix="tft-bench-restart-")
        rw_pq = os.path.join(rw_dir, "bench.parquet")
        rwN = 200_000
        _rw_io.write_parquet(
            tft.frame({"x": np.arange(rwN, dtype=np.float64)},
                      num_partitions=8), rw_pq)
        _rw_fn = lambda x: {"y": x * 2.0 + 1.0}    # noqa: E731
        _rw_adaptive.invalidate_results()
        with _RwFabric(workers=2, monitor=False, probe=False,
                       persist_dir=os.path.join(rw_dir, "persist"),
                       name="bench-rw") as rw_fab:
            rw_f = _rw_io.read_parquet(rw_pq)
            for _ in range(2):   # two-touch: second sighting persists
                rw_fab.submit(rw_f, _rw_fn,
                              tenant="bench").result(timeout=60)
            t0 = time.perf_counter()
            rw_fab.rolling_restart()
            restart_s = time.perf_counter() - t0
            d0 = (_rwc.get("pipeline.submitted")
                  + _rwc.get("pipeline.drained"))
            warm0 = _rwc.get("plan.result_cache_warm_hits")
            t0 = time.perf_counter()
            rw_fab.submit(rw_f, _rw_fn,
                          tenant="bench").result(timeout=60)
            warm_hit_s = time.perf_counter() - t0
            warm_dispatches = (_rwc.get("pipeline.submitted")
                               + _rwc.get("pipeline.drained")) - d0
            restart_secondary = {
                "rows": rwN,
                "rolling_restart_s": round(restart_s, 6),
                "warm_hit_s": round(warm_hit_s, 6),
                "warm_hit_rows_per_s": round(rwN / warm_hit_s, 1),
                "warm_hit_block_dispatches": int(warm_dispatches),
                "served_from_durable_tier": bool(
                    _rwc.get("plan.result_cache_warm_hits") == warm0
                    + 1),
                "budget_s": rw_budget_s,
                "elapsed_s": round(time.perf_counter() - rw_t0, 3),
            }
        shutil.rmtree(rw_dir, ignore_errors=True)
    except Exception as e:  # noqa: BLE001 - headline must survive
        restart_secondary = {"error": str(e)[:300]}

    # secondary metric (never costs the headline): the ALWAYS-ON flight
    # recorder + SLO accounting (docs/observability.md) on the serve
    # mixed workload. Unlike tracing (opt-in, measured off-vs-bypass),
    # the flight layer's default state IS on, so the acceptance bar is
    # the ON path within 2% of TFT_FLIGHT=0 (the bit-identical bypass)
    # — order-flipped interleaved pairs, medians, wall-clock budgeted
    # like every other secondary. The layer meets it by recording
    # DECISIONS (admit/start/finish per query), never blocks.
    flight_secondary = None
    flight_budget_s = 40.0
    flight_t0 = time.perf_counter()
    try:
        from statistics import median as _fl_median

        from tensorframes_tpu.observability import flight as _fl_mod
        from tensorframes_tpu.serve import (QueryScheduler as _FlSched,
                                            TenantQuota as _FlQuota)

        fl_sizes = {"small": 10_000, "medium": 50_000}
        fl_frames = {t: [tft.frame({"x": np.arange(float(n)) + k},
                                   num_partitions=4)
                         for k in range(4)]
                     for t, n in fl_sizes.items()}

        def _fl_round(sched) -> float:
            t0 = time.perf_counter()
            futs = [sched.submit(fr, lambda x: {"z": x + 3.0}, tenant=t)
                    for t in fl_sizes for fr in fl_frames[t]]
            for f in futs:
                f.result(timeout=60)
            return time.perf_counter() - t0

        def _fl_bypassed(sched) -> float:
            os.environ["TFT_FLIGHT"] = "0"
            try:
                return _fl_round(sched)
            finally:
                os.environ.pop("TFT_FLIGHT", None)

        rec0 = _fl_mod.stats()["recorded_total"]
        with _FlSched(quotas={t: _FlQuota(max_queue=1024)
                              for t in fl_sizes},
                      workers=2, name="flbench") as sched:
            # steady-state serving: warm the shared compile cache
            sched.submit(fl_frames["small"][0],
                         lambda x: {"z": x + 3.0},
                         tenant="small").result(timeout=60)
            fl_samples = {"on": [], "bypass": []}
            rounds = 0
            fl_pair_budget = flight_budget_s * 0.9
            while rounds < 60 and (
                    time.perf_counter() - flight_t0 < fl_pair_budget
                    or rounds < 2):
                if rounds % 2:
                    fl_samples["on"].append(_fl_round(sched))
                    fl_samples["bypass"].append(_fl_bypassed(sched))
                else:
                    fl_samples["bypass"].append(_fl_bypassed(sched))
                    fl_samples["on"].append(_fl_round(sched))
                rounds += 1
        fl_on = _fl_median(fl_samples["on"])
        fl_byp = _fl_median(fl_samples["bypass"])
        fl_pct = (fl_on - fl_byp) / fl_byp * 100.0
        flight_secondary = {
            "queries_per_round": sum(len(v) for v in fl_frames.values()),
            "rounds": rounds,
            "bypass_round_s": round(fl_byp, 6),
            "on_round_s": round(fl_on, 6),
            "always_on_overhead_pct": round(fl_pct, 2),
            "within_2pct": bool(fl_pct < 2.0),
            "decisions_recorded": _fl_mod.stats()["recorded_total"]
            - rec0,
        }
    except Exception as e:  # noqa: BLE001 - headline must survive
        flight_secondary = {"error": str(e)[:300]}
    finally:
        os.environ.pop("TFT_FLIGHT", None)

    # secondary metric (never costs the headline): the ALWAYS-ON
    # performance-regression sentinel (timeline sampling + per-query
    # cost capture + baseline folding; docs/observability.md) on the
    # same serve mixed workload, same protocol as the flight recorder
    # above: ON path within 2% of TFT_TIMELINE=0 (the bit-identical
    # bypass), order-flipped interleaved pairs, medians, wall-clock
    # budgeted. The layer meets it by doing per-QUERY work only (a
    # counter snapshot at capture, a vector + deque fold at finish),
    # never per-block.
    sentinel_secondary = None
    sent_budget_s = 40.0
    sent_t0 = time.perf_counter()
    try:
        from statistics import median as _sn_median

        from tensorframes_tpu.observability import baseline as _sn_bl
        from tensorframes_tpu.serve import (QueryScheduler as _SnSched,
                                            TenantQuota as _SnQuota)

        sn_sizes = {"small": 10_000, "medium": 50_000}
        sn_frames = {t: [tft.frame({"x": np.arange(float(n)) + k},
                                   num_partitions=4)
                         for k in range(4)]
                     for t, n in sn_sizes.items()}

        def _sn_round(sched) -> float:
            t0 = time.perf_counter()
            futs = [sched.submit(fr, lambda x: {"z": x + 3.0}, tenant=t)
                    for t in sn_sizes for fr in sn_frames[t]]
            for f in futs:
                f.result(timeout=60)
            return time.perf_counter() - t0

        def _sn_bypassed(sched) -> float:
            os.environ["TFT_TIMELINE"] = "0"
            try:
                return _sn_round(sched)
            finally:
                os.environ.pop("TFT_TIMELINE", None)

        comp0 = _sn_bl.perf_stats()["completions_total"]
        with _SnSched(quotas={t: _SnQuota(max_queue=1024)
                              for t in sn_sizes},
                      workers=2, name="snbench") as sched:
            sched.submit(sn_frames["small"][0],
                         lambda x: {"z": x + 3.0},
                         tenant="small").result(timeout=60)
            sn_samples = {"on": [], "bypass": []}
            rounds = 0
            sn_pair_budget = sent_budget_s * 0.9
            while rounds < 60 and (
                    time.perf_counter() - sent_t0 < sn_pair_budget
                    or rounds < 2):
                if rounds % 2:
                    sn_samples["on"].append(_sn_round(sched))
                    sn_samples["bypass"].append(_sn_bypassed(sched))
                else:
                    sn_samples["bypass"].append(_sn_bypassed(sched))
                    sn_samples["on"].append(_sn_round(sched))
                rounds += 1
        sn_on = _sn_median(sn_samples["on"])
        sn_byp = _sn_median(sn_samples["bypass"])
        sn_pct = (sn_on - sn_byp) / sn_byp * 100.0
        sn_stats = _sn_bl.perf_stats()
        sentinel_secondary = {
            "queries_per_round": sum(len(v) for v in sn_frames.values()),
            "rounds": rounds,
            "bypass_round_s": round(sn_byp, 6),
            "on_round_s": round(sn_on, 6),
            "always_on_overhead_pct": round(sn_pct, 2),
            "within_2pct": bool(sn_pct < 2.0),
            "completions_captured": sn_stats["completions_total"]
            - comp0,
            "baselines": sn_stats["baselines"],
            "timeline_samples": sn_stats["timeline"]["taken_total"],
        }
    except Exception as e:  # noqa: BLE001 - headline must survive
        sentinel_secondary = {"error": str(e)[:300]}
    finally:
        os.environ.pop("TFT_TIMELINE", None)

    # secondary metric (never costs the headline): the ALWAYS-ON
    # cross-cutting invariant auditors (docs/resilience.md) on the same
    # serve mixed workload, same protocol as the flight recorder above:
    # ON path within 2% of TFT_INVARIANTS=0 (the bit-identical bypass),
    # order-flipped interleaved pairs, medians, wall-clock budgeted.
    # The layer meets it by auditing only at quiesce points (query
    # finish, scheduler close) — a handful of lock-held count
    # comparisons per query, never per-block.
    invariant_secondary = None
    inv_budget_s = 40.0
    inv_t0 = time.perf_counter()
    try:
        from statistics import median as _iv_median

        from tensorframes_tpu.resilience import invariants as _iv_mod
        from tensorframes_tpu.serve import (QueryScheduler as _IvSched,
                                            TenantQuota as _IvQuota)
        from tensorframes_tpu.utils.tracing import counters as _iv_ctrs

        iv_sizes = {"small": 10_000, "medium": 50_000}
        iv_frames = {t: [tft.frame({"x": np.arange(float(n)) + k,
                                    "w": np.arange(float(n)) * 0.5},
                                   num_partitions=4)
                         for k in range(4)]
                     for t, n in iv_sizes.items()}

        def _iv_round(sched) -> float:
            t0 = time.perf_counter()
            futs = [sched.submit(fr, lambda x: {"z": x + 3.0}, tenant=t)
                    for t in iv_sizes for fr in iv_frames[t]]
            for f in futs:
                f.result(timeout=60)
            return time.perf_counter() - t0

        def _iv_bypassed(sched) -> float:
            os.environ["TFT_INVARIANTS"] = "0"
            try:
                return _iv_round(sched)
            finally:
                os.environ.pop("TFT_INVARIANTS", None)

        aud0 = _iv_ctrs.get("invariants.audits")
        vio0 = _iv_ctrs.get("invariants.violations")
        with _IvSched(quotas={t: _IvQuota(max_queue=1024)
                              for t in iv_sizes},
                      workers=2, name="invbench") as sched:
            sched.submit(iv_frames["small"][0],
                         lambda x: {"z": x + 3.0},
                         tenant="small").result(timeout=60)
            iv_samples = {"on": [], "bypass": []}
            rounds = 0
            iv_pair_budget = inv_budget_s * 0.9
            while rounds < 60 and (
                    time.perf_counter() - inv_t0 < iv_pair_budget
                    or rounds < 2):
                if rounds % 2:
                    iv_samples["on"].append(_iv_round(sched))
                    iv_samples["bypass"].append(_iv_bypassed(sched))
                else:
                    iv_samples["bypass"].append(_iv_bypassed(sched))
                    iv_samples["on"].append(_iv_round(sched))
                rounds += 1
        iv_on = _iv_median(iv_samples["on"])
        iv_byp = _iv_median(iv_samples["bypass"])
        iv_pct = (iv_on - iv_byp) / iv_byp * 100.0
        invariant_secondary = {
            "queries_per_round": sum(len(v) for v in iv_frames.values()),
            "rounds": rounds,
            "bypass_round_s": round(iv_byp, 6),
            "on_round_s": round(iv_on, 6),
            "always_on_overhead_pct": round(iv_pct, 2),
            "within_2pct": bool(iv_pct < 2.0),
            "audits": _iv_ctrs.get("invariants.audits") - aud0,
            "violations": _iv_ctrs.get("invariants.violations") - vio0,
            "auditors": len(_iv_mod._BUILTIN),
        }
    except Exception as e:  # noqa: BLE001 - headline must survive
        invariant_secondary = {"error": str(e)[:300]}
    finally:
        os.environ.pop("TFT_INVARIANTS", None)

    # secondary metric (never costs the headline): the ALWAYS-ON
    # durable query history (docs/observability.md) on the same serve
    # mixed workload, same protocol as the flight recorder above: the
    # ON path (archive armed via TFT_HISTORY_DIR) within 2% of
    # TFT_HISTORY=0 (the single-env-check bypass), order-flipped
    # interleaved pairs, medians, wall-clock budgeted. The layer meets
    # it with one json.dumps + one O_APPEND write() per QUERY at
    # finish, never per-block.
    history_secondary = None
    hist_budget_s = 40.0
    hist_t0 = time.perf_counter()
    import tempfile as _hi_tempfile
    hist_dir = _hi_tempfile.mkdtemp(prefix="tft-bench-history-")
    try:
        from statistics import median as _hi_median

        from tensorframes_tpu.observability import history as _hi_mod
        from tensorframes_tpu.serve import (QueryScheduler as _HiSched,
                                            TenantQuota as _HiQuota)

        os.environ["TFT_HISTORY_DIR"] = hist_dir
        hi_sizes = {"small": 10_000, "medium": 50_000}
        hi_frames = {t: [tft.frame({"x": np.arange(float(n)) + k},
                                   num_partitions=4)
                         for k in range(4)]
                     for t, n in hi_sizes.items()}

        def _hi_round(sched) -> float:
            t0 = time.perf_counter()
            futs = [sched.submit(fr, lambda x: {"z": x + 3.0}, tenant=t)
                    for t in hi_sizes for fr in hi_frames[t]]
            for f in futs:
                f.result(timeout=60)
            return time.perf_counter() - t0

        def _hi_bypassed(sched) -> float:
            os.environ["TFT_HISTORY"] = "0"
            try:
                return _hi_round(sched)
            finally:
                os.environ.pop("TFT_HISTORY", None)

        hrec0 = _hi_mod.stats()["records_written"]
        with _HiSched(quotas={t: _HiQuota(max_queue=1024)
                              for t in hi_sizes},
                      workers=2, name="histbench") as sched:
            sched.submit(hi_frames["small"][0],
                         lambda x: {"z": x + 3.0},
                         tenant="small").result(timeout=60)
            hi_samples = {"on": [], "bypass": []}
            rounds = 0
            hi_pair_budget = hist_budget_s * 0.9
            while rounds < 60 and (
                    time.perf_counter() - hist_t0 < hi_pair_budget
                    or rounds < 2):
                if rounds % 2:
                    hi_samples["on"].append(_hi_round(sched))
                    hi_samples["bypass"].append(_hi_bypassed(sched))
                else:
                    hi_samples["bypass"].append(_hi_bypassed(sched))
                    hi_samples["on"].append(_hi_round(sched))
                rounds += 1
        hi_on = _hi_median(hi_samples["on"])
        hi_byp = _hi_median(hi_samples["bypass"])
        hi_pct = (hi_on - hi_byp) / hi_byp * 100.0
        hi_stats = _hi_mod.stats()
        history_secondary = {
            "queries_per_round": sum(len(v) for v in hi_frames.values()),
            "rounds": rounds,
            "bypass_round_s": round(hi_byp, 6),
            "on_round_s": round(hi_on, 6),
            "always_on_overhead_pct": round(hi_pct, 2),
            "within_2pct": bool(hi_pct < 2.0),
            "records_archived": hi_stats["records_written"] - hrec0,
            "archive_bytes": hi_stats["bytes"],
        }
    except Exception as e:  # noqa: BLE001 - headline must survive
        history_secondary = {"error": str(e)[:300]}
    finally:
        os.environ.pop("TFT_HISTORY", None)
        os.environ.pop("TFT_HISTORY_DIR", None)
        shutil.rmtree(hist_dir, ignore_errors=True)

    # reference structure: Rows materialized in and out per block
    schema = df.schema
    t0 = time.perf_counter()
    for b in df.blocks():
        rows = columns_to_rows(b.columns, schema)          # convert
        mapped = [(r[0] + 3.0,) for r in rows]             # the computation
        rows_to_columns(mapped, schema)                    # convertBack
    ref = N_ROWS / (time.perf_counter() - t0)

    n_chips = max(1, len(jax.devices()))
    plat = jax.default_backend()
    rec = {
        "metric": "map_blocks_add_const_1M_rows",
        "value": round(ours / n_chips, 1),
        "unit": "rows/sec/chip",
        "vs_baseline": round(ours / ref, 2),
        "platform": plat,
        "n_chips": n_chips,
        "e2e_with_marshalling_rows_per_s": round(e2e, 1),
        "row_path_rows_per_s": round(ref, 1),
        "executor": executor,
        "pipelined_vs_serial": pipeline_secondary,
        "tracing_overhead": tracing_secondary,
        "mesh_tracing_overhead": mesh_tracing_secondary,
        "serving_mixed_workload": serving_secondary,
        "streaming_throughput": streaming_secondary,
        "elastic_degraded_mesh": elastic_secondary,
        "out_of_core_sort": memory_secondary,
        "fused_chain": fused_secondary,
        "dfused_chain": dfused_secondary,
        "broadcast_hash_join": join_secondary,
        "partitioned_hash_join": pjoin_secondary,
        "shuffle_daggregate": sagg_secondary,
        "approx_distinct": sketch_secondary,
        "preempt_resume": preempt_secondary,
        "adaptive_blocks": adaptive_secondary,
        "result_cache_hit": rcache_secondary,
        "restart_warm": restart_secondary,
        "flight_recorder_overhead": flight_secondary,
        "sentinel_overhead": sentinel_secondary,
        "invariant_overhead": invariant_secondary,
        "history_overhead": history_secondary,
    }

    def _steady_sec(fn, iters=30):
        """Pipelined steady state: async dispatches, one final block."""
        jax.block_until_ready(fn())
        t0 = time.perf_counter()
        for _ in range(iters):
            r = fn()
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / iters

    # HBM-saturation secondary metric: the 1M-row headline is
    # dispatch-overhead-limited; the SAME framework path (distribute +
    # dmap_blocks on a double column) at 16M rows amortizes the launch.
    # PER-CHIP numbers: on a mesh the rows shard, so the aggregate
    # divides by n_chips like the headline.
    big_df = tft.frame({"x": np.arange(16_000_000, dtype=np.float64)},
                       num_partitions=1)
    big_dist = distribute(big_df, mesh)
    big_sec = _steady_sec(lambda: dmap_blocks(
        comp, big_dist, trim=True).columns["z"])
    rec["map_blocks_16M_rows_per_s_chip"] = round(
        16_000_000 / big_sec / n_chips, 1)
    # double computes as f32 on TPU: 4 B read + 4 B written/row
    rec["hbm_gbps_16M_chip"] = round(
        16_000_000 * 8 / big_sec / 1e9 / n_chips, 1)

    # MXU secondary metric (the add-constant headline is HBM-bound; this
    # one exercises the matrix unit): bf16 2048^3 matmul,
    # device-resident, pipelined steady state.
    import jax.numpy as jnp

    from benchmarks.peaks import peak

    M = 2048
    a = jax.device_put(jnp.ones((M, M), jnp.bfloat16))
    b = jax.device_put(jnp.ones((M, M), jnp.bfloat16))
    mm = jax.jit(lambda a, b: a @ b)
    mm_sec = _steady_sec(lambda: mm(a, b))
    matmul_tflops = 2 * M ** 3 / mm_sec / 1e12
    rec["matmul_bf16_tflops"] = round(matmul_tflops, 2)
    kind = jax.devices()[0].device_kind
    rec["matmul_mfu"] = round(
        matmul_tflops * 1e12 / peak(kind)["bf16_flops"], 4)

    # every figure names the silicon it ran on: the headline AND each
    # dict-valued secondary carry platform / device_kind
    rec["device_kind"] = kind
    failed = []
    for name, sec in rec.items():
        if isinstance(sec, dict):
            sec.setdefault("platform", plat)
            sec.setdefault("device_kind", kind)
            if "error" in sec:
                failed.append(name)
    print(json.dumps(rec))
    if failed:
        print(f"bench.py: secondaries failed: {failed}", file=sys.stderr)
        return 1
    return 0


def main() -> int:
    from tensorframes_tpu.utils.platform import place_compile_cache

    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"bench.py: no TPU (jax found {platform!r} devices)",
              file=sys.stderr)
        return 1
    place_compile_cache()
    return _run()


if __name__ == "__main__":
    sys.exit(main())
