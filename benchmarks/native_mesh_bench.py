"""Native vs jax mesh dispatch overhead, and the resident-loop win.

Measures the SAME sharded program (per-shard elementwise step + a psum
collective, loop-state signature) three ways at 1/2/4/8 virtual devices:

- ``jax``: jitted ``shard_map`` with jax Arrays (device-resident — the
  framework's default dispatch);
- ``native_marshalled``: ``NativeMeshExecutor.run_sharded`` per call —
  the correctness-proof path that splits/uploads and downloads/assembles
  host numpy on EVERY dispatch (``native_mesh.py`` module docstring);
- ``native_resident``: ``NativeMeshExecutor.run_sharded_loop`` — shards
  upload once, outputs feed back as device buffers
  (``tfr_pjrt_buffer``), one final download.

The gap between the last two IS the per-dispatch host-marshalling cost;
the gap between ``native_resident`` and ``jax`` is the remaining C-ABI
dispatch overhead. Emits one JSON line per (devices, path).

CPU-only (virtual devices): the native core is opt-in and has no chip
path (ROADMAP Design 1).

Run:  python benchmarks/native_mesh_bench.py [rows] [iters]
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

if __name__ == "__main__":
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["TFT_EXECUTOR"] = "pjrt"

import jax  # noqa: E402


def main(n_rows: int = 1_000_000, iters: int = 20, dev_counts=(1, 2, 4, 8)):
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from tensorframes_tpu import parallel as par
    from tensorframes_tpu.parallel import native_mesh

    x_host = np.arange(n_rows, dtype=np.float32) / n_rows
    plat = jax.devices()[0].platform  # stamped on every line: chip-mode
    # output must be distinguishable from a 1-device CPU run

    for n_dev in dev_counts:
        mesh = par.local_mesh(n_dev)
        axis = mesh.data_axis

        def build(mesh=mesh, axis=axis):
            def step(x):
                total = jax.lax.psum(x.sum(), axis)
                return (x * 0.999 + total * 1e-9,)
            return shard_map(step, mesh=mesh.mesh, in_specs=(P(axis),),
                             out_specs=(P(axis),))

        in_sh = [mesh.row_sharding(1)]
        out_sh = [mesh.row_sharding(1)]

        # -- jax (device-resident by construction) ------------------------
        fn = jax.jit(build())
        xd = jax.device_put(jnp.asarray(x_host), in_sh[0])
        (r,) = fn(xd)
        jax.block_until_ready(r)
        t0 = time.perf_counter()
        r = xd
        for _ in range(iters):
            (r,) = fn(r)
        jax.block_until_ready(r)
        jax_s = (time.perf_counter() - t0) / iters
        print(json.dumps({"devices": n_dev, "path": "jax",
                          "s_per_dispatch": jax_s, "rows": n_rows,
                          "platform": plat}))

        ex = native_mesh.executor_for(mesh)
        if ex is None:
            print(json.dumps({"devices": n_dev, "path": "native",
                              "error": "executor unavailable",
                              "platform": plat}))
            continue

        # -- native, host-marshalled per call -----------------------------
        key = ("bench-marshalled", n_dev, n_rows)
        ex.run_sharded(key, build, [x_host], in_sh, out_sh, mesh)  # compile
        t0 = time.perf_counter()
        cur = x_host
        for _ in range(iters):
            (cur,) = ex.run_sharded(key, build, [cur], in_sh, out_sh, mesh)
        marsh_s = (time.perf_counter() - t0) / iters
        print(json.dumps({"devices": n_dev, "path": "native_marshalled",
                          "s_per_dispatch": marsh_s, "rows": n_rows,
                          "platform": plat}))

        # -- native, device-resident loop ---------------------------------
        ex.run_sharded_loop(key, build, [x_host], in_sh, out_sh, mesh,
                            iters=1)  # warm
        t0 = time.perf_counter()
        ex.run_sharded_loop(key, build, [x_host], in_sh, out_sh, mesh,
                            iters=iters)
        res_s = (time.perf_counter() - t0) / iters
        print(json.dumps({
            "devices": n_dev, "path": "native_resident",
            "s_per_dispatch": res_s, "rows": n_rows,
            "platform": plat,
            "marshalling_overhead_x": marsh_s / res_s if res_s else None,
            "vs_jax_x": res_s / jax_s if jax_s else None,
        }))


if __name__ == "__main__":
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    it = int(sys.argv[2]) if len(sys.argv) > 2 else 20
    main(n, it)
