#!/usr/bin/env python3
"""Chip smoke: drive the frame engine's main path once on a TPU.

``python chip_smoke.py`` runs, in one process that holds one chip:

1. setup: a forced rebuild of ``native/libtfruntime.so``, the compile
   cache (``tensorframes_tpu.utils.platform``), and a TPU check;
2. the frame engine over a 2^26-row frame in 8 partitions (map_blocks in
   lambda and DSL form, filter, reduce_blocks, aggregate at 1,000 and
   100,000 groups), then the same map and aggregation on a 1-device mesh;
3. served queries: two tenants ``tft.submit`` through the default
   scheduler, whose HBM admission reads the allocator's limit;
4. ResNet-50 (``num_classes=1000``, 224x224x3 float32) through
   ``infer_via_frame`` over 256 images in 8 blocks, against
   ``jax.jit(model.apply)`` on the same chip;
5. the fallback audit: every counter that marks a retry, fallback, split,
   shrink or lost device must read zero.

``--chips 4`` runs only the mesh path on ``local_mesh(4)`` against
``local_mesh(1)`` and numpy, and the same audit. Every phase prints one
line with its compile and run seconds; the last line of stdout is the
JSON result. Any failed check raises, and nothing after it runs.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
EPS32 = float(np.finfo(np.float32).eps)

FRAME_ROWS = 1 << 26
PARTITIONS = 8
GROUPS = (1_000, 100_000)
SHUFFLE_GROUPS = 1 << 18     # above daggregate's 128k shuffle threshold
JOIN_ROWS = 1 << 22
RESNET_IMAGES = 256
RESNET_BLOCKS = 8

# counters that mark a retry, fallback, split, shrink, lost device or a
# broken invariant: any of them non-zero fails the audit
_DEGRADED = re.compile(
    r"fallback|retries|giveups|oom|split|shrink|lost|violations|overflow"
    r"|errors|corrupt|degraded|failures|crash|expired|injected|fired")


class CompileClock:
    """Wall seconds spent compiling, from jax's own compile events.

    Tracing, lowering, backend compiles and persistent-cache reads are
    time spans on the host clock; overlapping spans (a nested trace, two
    threads compiling at once) count once."""

    _EVENTS = ("/jax/core/compile/", "/jax/compilation_cache/")

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self._spans = []
        jax.monitoring.register_event_time_span_listener(self._on_span)

    def _on_span(self, event, start, end, **_):
        if event.startswith(self._EVENTS):
            with self._lock:
                self._spans.append((start, end))

    def seconds_since(self, t0: float) -> float:
        with self._lock:
            spans = sorted((max(s, t0), e) for s, e in self._spans if e > t0)
        total, cur_s, cur_e = 0.0, None, None
        for s, e in spans:
            if cur_e is None or s > cur_e:
                if cur_e is not None:
                    total += cur_e - cur_s
                cur_s, cur_e = s, e
            else:
                cur_e = max(cur_e, e)
        if cur_e is not None:
            total += cur_e - cur_s
        return total


class Phase:
    """Times one phase and prints its line; raises on a failed check."""

    def __init__(self, clock: CompileClock, name: str):
        self.clock, self.name, self.checks = clock, name, []

    def __enter__(self):
        self.t0 = time.time()
        return self

    def check(self, ok: bool, what: str) -> None:
        if not ok:
            raise AssertionError(f"[{self.name}] check failed: {what}")
        self.checks.append(what)

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        import jax

        wall = time.time() - self.t0
        compile_s = self.clock.seconds_since(self.t0)
        print(f"[{self.name}] compile_s={compile_s:.3f} "
              f"run_s={wall - compile_s:.3f} "
              f"device_kind={jax.devices()[0].device_kind!r} "
              f"checked: {'; '.join(self.checks)}", flush=True)
        return False


# -- reference checks --------------------------------------------------------

def sum_tol(n_terms, abs_sum):
    """Tolerance for an f32 sum of ``n_terms`` values (``double`` columns
    compute as f32 on TPU, ``dtypes.device_dtype``): a few ulps of the
    magnitude per sqrt(term), the random-walk bound of rounding errors."""
    return 4.0 * EPS32 * np.sqrt(np.maximum(n_terms, 1)) * abs_sum


def check_close(phase, got, want, tol, what):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    err = np.abs(got - want)
    phase.check(got.shape == want.shape and bool(np.all(err <= tol)),
                f"{what} (max err {float(err.max()) if err.size else 0:.3g})")


def make_data(n_rows: int, groups, seed: int = 0):
    """Seeded columns: ``x`` double values exactly representable in f32
    (so maps, min and max compare exactly), one int32 key per group
    count."""
    rng = np.random.default_rng(seed)
    cols = {"x": rng.random(n_rows, dtype=np.float32).astype(np.float64)}
    for g in groups:
        cols[f"k{g}"] = rng.integers(0, g, n_rows, dtype=np.int32)
    return cols


def keyed_reference(keys, x, groups):
    """numpy sum/min/max/count per key (float64)."""
    order = np.argsort(keys, kind="stable")
    ks, xs = keys[order], x[order]
    starts = np.flatnonzero(np.r_[True, ks[1:] != ks[:-1]])
    present = ks[starts]
    out = {"sum": np.bincount(keys, weights=x, minlength=groups),
           "abs": np.bincount(keys, weights=np.abs(x), minlength=groups),
           "count": np.bincount(keys, minlength=groups),
           "min": np.full(groups, np.nan), "max": np.full(groups, np.nan)}
    out["min"][present] = np.minimum.reduceat(xs, starts)
    out["max"][present] = np.maximum.reduceat(xs, starts)
    return present, out


def host_column(frame, name):
    return np.concatenate([np.asarray(b.dense(name)) for b in frame.blocks()])


def check_aggregate(phase, out, key, ref, combiner, what):
    present, r = ref
    k = host_column(out, key)
    v = host_column(out, "x")
    phase.check(np.array_equal(np.sort(k), present),
                f"{what} {combiner}: {len(present)} groups")
    want = r[combiner][k]
    tol = (sum_tol(r["count"][k], r["abs"][k]) if combiner == "sum"
           else 0.0)
    check_close(phase, v, want, tol, f"{what} {combiner}")


def shard_devices(arr) -> list:
    return sorted({s.device.id for s in arr.addressable_shards})


# -- phases ------------------------------------------------------------------

def phase_setup(clock: CompileClock) -> None:
    """Rebuild the native library from the tracked sources and place the
    compile cache; the TPU check is done by :func:`main` first."""
    with Phase(clock, "setup") as ph:
        subprocess.run(["make", "-B", "-s", "-C",
                        os.path.join(REPO, "native")],
                       check=True, stdout=sys.stderr)
        from tensorframes_tpu import native
        from tensorframes_tpu.utils.platform import place_compile_cache

        ph.check(native.available(), "native/libtfruntime.so built+loaded")
        cache = place_compile_cache()
        ph.check(bool(cache), f"compile cache at {cache}")


def phase_frame(clock, n_rows=FRAME_ROWS, partitions=PARTITIONS,
                groups=GROUPS, seed=0) -> None:
    """The frame engine's block path, then the same ops on a 1-device
    mesh, each against numpy on the same seeded data."""
    import jax.numpy as jnp

    import tensorframes_tpu as tft
    from tensorframes_tpu import dsl
    from tensorframes_tpu.parallel.distributed import (daggregate,
                                                       distribute,
                                                       dmap_blocks)
    from tensorframes_tpu.parallel.mesh import local_mesh

    cols = make_data(n_rows, groups, seed)
    x = cols["x"]
    refs = {g: keyed_reference(cols[f"k{g}"], x, g) for g in groups}
    with Phase(clock, "frame") as ph:
        df = tft.frame(cols, num_partitions=partitions)
        xs = df.select(["x"])
        z = host_column(tft.map_blocks(lambda x: {"z": x + 3.0}, xs), "z")
        check_close(ph, z, x + 3.0, EPS32 * (x + 3.0),
                    f"map_blocks lambda x+3 over {n_rows} rows "
                    f"in {partitions} blocks")
        with dsl.with_graph():
            node = (tft.block(xs, "x") * 2.0).named("w")
            w = host_column(tft.map_blocks(node, xs), "w")
        check_close(ph, w, 2.0 * x, 0.0, "map_blocks DSL x*2")

        kept = tft.filter_rows(lambda x: x > 0.5, xs)
        fx = host_column(kept, "x")
        ph.check(len(fx) == int((x > 0.5).sum()),
                 f"filter x>0.5 keeps {len(fx)} rows")
        check_close(ph, fx, x[x > 0.5], 0.0, "filter rows equal")

        total = tft.reduce_blocks(lambda x_input: {"x": jnp.sum(x_input)}, xs)
        check_close(ph, total, x.sum(), sum_tol(n_rows, np.abs(x).sum()),
                    "reduce_blocks sum")
        low = tft.reduce_blocks(lambda x_input: {"x": jnp.min(x_input)}, xs)
        check_close(ph, low, x.min(), 0.0, "reduce_blocks min")

        for g in groups:
            grouped = df.select([f"k{g}", "x"]).group_by(f"k{g}")
            for comb in ("sum", "min", "max"):
                check_aggregate(ph, tft.aggregate({"x": comb}, grouped),
                                f"k{g}", refs[g], comb, f"aggregate k{g}")

    with Phase(clock, "mesh1") as ph:
        mesh = local_mesh(1)
        dist = distribute(df, mesh)
        out = dmap_blocks(lambda x: {"z": x + 3.0}, dist.select(["x"]),
                          trim=True)
        check_close(ph, host_column(out.collect_frame(), "z"), x + 3.0,
                    EPS32 * (x + 3.0), "dmap_blocks x+3 on 1 device")
        for g in groups:
            agg = daggregate({"x": "sum"}, dist.select([f"k{g}", "x"]),
                             f"k{g}")
            check_aggregate(ph, agg, f"k{g}", refs[g], "sum",
                            f"daggregate k{g}")


def phase_serve(clock, n_rows=1 << 22, partitions=PARTITIONS, seed=1) -> None:
    """Two tenants submit queries through the default scheduler; HBM
    admission must read a real allocator limit."""
    import tensorframes_tpu as tft
    from tensorframes_tpu import memory
    from tensorframes_tpu.observability import device
    from tensorframes_tpu.serve import shutdown_default_scheduler

    cols = make_data(n_rows, (1_000,), seed)
    x = cols["x"]
    ref = keyed_reference(cols["k1000"], x, 1_000)
    with Phase(clock, "serve") as ph:
        wm = device.watermark()
        limit = (wm or {}).get("limit_bytes", 0)
        ph.check(limit > 0, f"allocator limit {limit} B")
        mgr = memory.manager()
        ph.check(mgr.limit is not None and mgr.limit > 0,
                 f"memory ledger budget {mgr.limit} B")
        df = tft.frame(cols, num_partitions=partitions)
        futures = []
        for tenant in ("alpha", "beta"):
            xs = df.select(["x"])
            futures.append((tenant, "map", tft.submit(
                xs, lambda x: {"z": x + 3.0}, tenant=tenant)))
            futures.append((tenant, "filter", tft.submit(
                tft.filter_rows(lambda x: x > 0.5, xs), tenant=tenant)))
            futures.append((tenant, "agg", tft.submit(
                tft.aggregate({"x": "sum"},
                              df.select(["k1000", "x"]).group_by("k1000")),
                tenant=tenant)))
        for tenant, kind, fut in futures:
            res = fut.result(timeout=600)
            if kind == "map":
                check_close(ph, host_column(res, "z"), x + 3.0,
                            EPS32 * (x + 3.0), f"{tenant} map")
            elif kind == "filter":
                check_close(ph, host_column(res, "x"), x[x > 0.5], 0.0,
                            f"{tenant} filter")
            else:
                check_aggregate(ph, res, "k1000", ref, "sum",
                                f"{tenant} aggregate")
        shutdown_default_scheduler()


def phase_resnet(clock, images=RESNET_IMAGES, blocks=RESNET_BLOCKS,
                 size=224, num_classes=1000, seed=2) -> float:
    """ResNet-50 through ``infer_via_frame`` against ``jax.jit(apply)`` on
    the same device; returns the max logit difference."""
    import jax

    import tensorframes_tpu as tft
    from tensorframes_tpu.models.resnet import ResNet50

    model = ResNet50(num_classes=num_classes)
    params = model.init(jax.random.PRNGKey(seed))
    imgs = np.random.default_rng(seed).normal(
        size=(images, size, size, 3)).astype(np.float32)
    with Phase(clock, "resnet50") as ph:
        df = tft.frame({"image": imgs}, num_partitions=blocks)
        logits = host_column(model.infer_via_frame(params, df), "logits")
        apply = jax.jit(model.apply)
        per = images // blocks
        want = np.concatenate([np.asarray(apply(params, imgs[i:i + per]))
                               for i in range(0, images, per)])
        ph.check(logits.shape == (images, num_classes)
                 and bool(np.isfinite(logits).all()),
                 f"logits {logits.shape} finite")
        diff = float(np.abs(logits - want).max())
        scale = float(np.abs(want).max())
        # both sides run the same ops at the chip's default matmul
        # precision; they differ only where XLA fuses or folds the
        # closed-over weights differently
        ph.check(diff <= 1e-2 * scale,
                 f"max |logits - jit(apply)| = {diff:.3g} "
                 f"(scale {scale:.3g})")
    return diff


def phase_mesh(clock, chips=4, n_rows=FRAME_ROWS, partitions=PARTITIONS,
               groups=GROUPS + (SHUFFLE_GROUPS,), join_rows=JOIN_ROWS,
               seed=0) -> None:
    """The mesh path on ``local_mesh(chips)`` against ``local_mesh(1)``
    and numpy; prints the devices holding each sharded output."""
    import tensorframes_tpu as tft
    from tensorframes_tpu.parallel.distributed import (daggregate,
                                                       distribute,
                                                       dmap_blocks,
                                                       dreduce_blocks)
    from tensorframes_tpu.parallel.exchange import \
        shuffle_agg_groups_threshold
    from tensorframes_tpu.parallel.mesh import local_mesh
    from tensorframes_tpu.relational.join import (broadcast_join,
                                                  partitioned_hash_join)
    from tensorframes_tpu.utils.tracing import counters

    cols = make_data(n_rows, groups, seed)
    x = cols["x"]
    refs = {g: keyed_reference(cols[f"k{g}"], x, g) for g in groups}
    df = tft.frame(cols, num_partitions=partitions)
    rng = np.random.default_rng(seed + 1)
    left = tft.frame({"k": rng.integers(0, join_rows, join_rows,
                                        dtype=np.int32),
                      "a": rng.random(join_rows, dtype=np.float32)
                      .astype(np.float64)}, num_partitions=partitions)
    right = tft.frame({"k": np.arange(join_rows, dtype=np.int32),
                       "b": np.arange(join_rows, dtype=np.float64)},
                      num_partitions=partitions)
    results = {}
    for n in (1, chips):
        with Phase(clock, f"mesh{n}") as ph:
            mesh = local_mesh(n)
            dist = distribute(df, mesh)
            placed = shard_devices(dist.columns["x"])
            mapped = dmap_blocks(lambda x: {"z": x + 3.0},
                                 dist.select(["x"]), trim=True)
            zdev = shard_devices(mapped.columns["z"])
            print(f"[mesh{n}] x shards on devices {placed}; "
                  f"z shards on devices {zdev}", flush=True)
            ph.check(len(placed) == n and len(zdev) == n,
                     f"x and z sharded over {n} distinct devices")
            z = host_column(mapped.collect_frame(), "z")
            check_close(ph, z, x + 3.0, EPS32 * (x + 3.0), "dmap_blocks x+3")
            total = dreduce_blocks({"x": "sum"}, dist.select(["x"]))
            total = total["x"] if isinstance(total, dict) else total
            check_close(ph, total, x.sum(),
                        sum_tol(n_rows, np.abs(x).sum()), "dreduce sum")
            aggs = {}
            for g in groups:
                shuffles = counters.snapshot().get("mesh.shuffle_agg_routes",
                                                   0)
                agg = daggregate({"x": "sum"}, dist.select([f"k{g}", "x"]),
                                 f"k{g}")
                check_aggregate(ph, agg, f"k{g}", refs[g], "sum",
                                f"daggregate k{g}")
                routed = counters.snapshot().get("mesh.shuffle_agg_routes",
                                                 0) > shuffles
                thr = shuffle_agg_groups_threshold()
                want = n > 1 and thr is not None and len(refs[g][0]) > thr
                ph.check(routed == want,
                         f"daggregate k{g} {'shuffle' if want else 'psum'}"
                         f" route")
                aggs[g] = np.zeros(g)
                aggs[g][host_column(agg, f"k{g}")] = host_column(agg, "x")
            # the 1-device reference joins by broadcast; the mesh by shuffle
            joined = (partitioned_hash_join(left, right, "k", mesh=mesh)
                      if n > 1 else broadcast_join(left, right, "k"))
            jk, ja, jb = (host_column(joined, c) for c in ("k", "a", "b"))
            lk = host_column(left, "k")
            ph.check(len(jk) == len(lk) and np.array_equal(jb, jk)
                     and np.array_equal(np.sort(ja),
                                        np.sort(host_column(left, "a"))),
                     f"{'partitioned' if n > 1 else 'broadcast'} join of "
                     f"{len(lk)} probe rows")
            results[n] = (z, total, aggs, ja)
    with Phase(clock, f"mesh{chips}_vs_mesh1") as ph:
        z1, t1, a1, j1 = results[1]
        zn, tn, an, jn = results[chips]
        check_close(ph, zn, z1, 0.0, "dmap equal to 1 device")
        check_close(ph, tn, t1, sum_tol(n_rows, np.abs(x).sum()),
                    "dreduce within f32 of 1 device")
        for g in groups:
            cnt, mag = refs[g][1]["count"], refs[g][1]["abs"]
            check_close(ph, an[g], a1[g], 2 * sum_tol(cnt, mag),
                        f"daggregate k{g} within f32 of 1 device")
        ph.check(np.array_equal(jn, j1), "join rows equal to 1 device")


def phase_audit(clock) -> dict:
    """Fail when any degraded-path counter moved."""
    from tensorframes_tpu.utils.tracing import counters

    with Phase(clock, "audit") as ph:
        snap = counters.snapshot()
        print(f"[audit] counters {json.dumps(snap, sort_keys=True)}",
              flush=True)
        bad = {k: v for k, v in snap.items() if _DEGRADED.search(k) and v}
        ph.check(not bad, f"no retry/fallback/split/shrink counters "
                          f"moved {bad or ''}".strip())
    return snap


# -- entry -------------------------------------------------------------------

def require_tpu(chips: int):
    """The first device must be a TPU, and ``chips`` of them visible."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (jax found "
                         f"{devs[0].platform!r} devices); nothing to run")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but jax sees "
                         f"{len(devs)} TPU devices")
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh path on local_mesh(4)")
    args = ap.parse_args(argv)
    devs = require_tpu(args.chips)
    clock = CompileClock()
    phase_setup(clock)
    if args.chips == 1:
        phase_frame(clock)
        phase_serve(clock)
        phase_resnet(clock)
    else:
        phase_mesh(clock, chips=args.chips)
    phase_audit(clock)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
