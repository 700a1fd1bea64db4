"""1→N device scaling efficiency (BASELINE target metric).

Runs the headline device-resident workload (``dmap_blocks`` add-constant,
one compiled dispatch per iteration) and the collective reduce
(``dreduce_blocks`` sum) on meshes of 1, 2, 4 and 8 devices, each in its
own subprocess (``xla_force_host_platform_device_count`` must be set
before backend init), and reports per-mesh throughput + parallel
efficiency vs the 1-device run.

CPU-only: the parent never touches jax, and every child forces the CPU
backend with virtual devices. It validates the SHARDING path's scaling
behavior (the programs are the same ones a v5e-8 would run), not silicon
speed, and its numbers are stamped ``cpu-virtual``.

Run:  python benchmarks/scaling_bench.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

_CHILD = r"""
import json, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
sys.path.insert(0, {root!r})
import tensorframes_tpu as tft
from tensorframes_tpu import parallel as par

n_dev = int(sys.argv[1])
N = 1_000_000
df = tft.frame({{"x": np.arange(N, dtype=np.float64)}})
mesh = par.local_mesh(n_dev)
dist = par.distribute(df, mesh)

def bench(fn, iters=10):
    fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        r = fn()
    cols = getattr(r, "columns", None)
    if isinstance(cols, dict):          # DistributedFrame: async device work
        jax.block_until_ready(list(cols.values()))
    elif cols is None:                  # plain dict of arrays (dreduce)
        jax.block_until_ready(r)
    # host TensorFrame results (daggregate) are already materialized
    return (time.perf_counter() - t0) / iters

map_sec = bench(lambda: par.dmap_blocks(
    lambda x: {{"z": x + 3.0}}, dist, trim=True))
red_sec = bench(lambda: par.dreduce_blocks({{"x": "sum"}}, dist))
flt_sec = bench(lambda: par.dfilter(lambda x: x % 2.0 < 1.0, dist))
srt_sec = bench(lambda: par.dsort("x", dist, descending=True))

# keyed aggregation: 10k groups over the same rows (host-factorized ids
# are memoized per frame, so this measures the segment-reduce + psum)
keys = (np.arange(N) % 10_000).astype(np.int32)  # device-exact key dtype
kdist = par.distribute(tft.frame({{"k": keys,
                                   "x": np.arange(N, dtype=np.float64)}}),
                       mesh)
agg_sec = bench(lambda: par.daggregate({{"x": "sum"}}, kdist, "k"), iters=5)
print(json.dumps({{"n_dev": n_dev,
                   "map_rows_per_s": N / map_sec,
                   "reduce_rows_per_s": N / red_sec,
                   "filter_rows_per_s": N / flt_sec,
                   "sort_rows_per_s": N / srt_sec,
                   "aggregate_rows_per_s": N / agg_sec}}))
"""


def main() -> int:
    child = _CHILD.format(root=ROOT)
    results = []
    for n in (1, 2, 4, 8):
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" --xla_force_host_platform_device_count={n}"
                            ).strip()
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run([sys.executable, "-c", child, str(n)],
                              capture_output=True, text=True, env=env,
                              timeout=420)
        if proc.returncode != 0:
            print(json.dumps({"n_dev": n, "error":
                              proc.stderr.strip()[-300:]}), flush=True)
            return 1
        results.append(json.loads(proc.stdout.strip().splitlines()[-1]))

    base = results[0]
    for r in results:
        n = r["n_dev"]
        rec = {
            "metric": f"scaling_{n}dev",
            "map_rows_per_s": round(r["map_rows_per_s"], 1),
            "reduce_rows_per_s": round(r["reduce_rows_per_s"], 1),
            "filter_rows_per_s": round(r["filter_rows_per_s"], 1),
            "sort_rows_per_s": round(r["sort_rows_per_s"], 1),
            "aggregate_rows_per_s": round(r["aggregate_rows_per_s"], 1),
            "map_efficiency": round(
                r["map_rows_per_s"] / (n * base["map_rows_per_s"]), 3),
            "reduce_efficiency": round(
                r["reduce_rows_per_s"] / (n * base["reduce_rows_per_s"]),
                3),
            "platform": "cpu-virtual",
        }
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
