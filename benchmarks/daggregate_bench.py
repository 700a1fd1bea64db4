"""daggregate at scale: 1M rows x 100k groups (VERDICT round-2 weak #5/#8).

Measures the mesh keyed-aggregation path at a group count where the
reference's driver-side groupBy (and our host key-factorization path) is
dominated by key transfer + host sort, and compares the device-side key
path (``max_groups=``), where keys never leave the mesh.

Prints one JSON line per variant. Runs on whatever backend is live
(8-virtual-CPU mesh for relative numbers; the chip for device numbers).

Run:  [JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8]
      python benchmarks/daggregate_bench.py [n_rows] [n_groups]
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax

    from tensorframes_tpu.utils.platform import place_compile_cache

    place_compile_cache()

    n_rows = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    n_groups = int(sys.argv[2]) if len(sys.argv) > 2 else 100_000

    import tensorframes_tpu as tft
    from tensorframes_tpu import parallel as par

    rng = np.random.default_rng(7)
    # int (device-exact) keys: long would narrow to i32 with x64 off
    key = rng.integers(0, n_groups, n_rows).astype(np.int32)
    x = rng.standard_normal(n_rows)
    df = tft.frame({"k": key, "x": x})
    mesh = par.local_mesh()
    dist = par.distribute(df, mesh)
    platform = jax.devices()[0].platform

    def timed(fn, iters=3, cold=False, frame=None):
        """cold=True clears the frame's factorization memo per call, so
        the figure includes the key transfer/sort; warm measures the
        steady state an iterative workload sees (ids cached per frame)."""
        fn()  # compile/warm
        t0 = time.perf_counter()
        for _ in range(iters):
            if cold:
                (frame or dist)._group_ids_cache.clear()
            r = fn()
        return (time.perf_counter() - t0) / iters, r

    host = lambda: par.daggregate({"x": "sum"}, dist, "k")  # noqa: E731
    dev = lambda: par.daggregate(  # noqa: E731
        {"x": "sum"}, dist, "k", max_groups=n_groups + 8)
    sec_host_c, out_h = timed(host, cold=True)
    sec_host_w, _ = timed(host)
    sec_dev_c, out_d = timed(dev, cold=True)
    sec_dev_w, _ = timed(dev)

    # parity spot-check between the two paths
    h = {r["k"]: r["x"] for r in out_h.collect()}
    d = {r["k"]: r["x"] for r in out_d.collect()}
    assert set(h) == set(d)
    some = list(h)[:100]
    for k in some:
        assert np.isclose(h[k], d[k], rtol=1e-9), k

    results = [("host_keys", sec_host_c), ("host_keys_warm", sec_host_w),
               ("device_keys", sec_dev_c), ("device_keys_warm", sec_dev_w)]

    # composite device-side keys (mixed-radix combination): cap bound is
    # (cap+1)^2 < 2^31, so only measured at compatible group counts.
    # k2 is a function of k, so the PAIR count stays n_groups and the two
    # paths measure the same group structure
    if (n_groups + 9) ** 2 < 2 ** 31 - 1:  # radix = cap+1 must fit squared
        k2 = (key % 4).astype(np.int32)
        df2 = tft.frame({"k": key, "k2": k2, "x": x})
        dist2 = par.distribute(df2, mesh)
        sec_mk, out_mk = timed(
            lambda: par.daggregate({"x": "sum"}, dist2, ["k", "k2"],
                                   max_groups=n_groups + 8),
            iters=2, cold=True, frame=dist2)
        assert out_mk.count() == len(h)
        results.append(("multikey_device", sec_mk))

    for name, sec in results:
        print(json.dumps({
            "metric": f"daggregate_sum_{n_rows}x{n_groups}_{name}",
            "value": round(sec, 4), "unit": "s/call",
            "rows_per_s": round(n_rows / sec, 1),
            "platform": platform,
            "n_shards": mesh.num_data_shards,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
