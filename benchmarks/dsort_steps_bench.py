"""Columnsort per-step cost breakdown (the "4 rounds" model, measured).

The distributed sort is ONE compiled program (4 fused local sorts + 2
``all_to_all`` reshuffles + 2 ``ppermute`` half-block shifts —
``parallel/distributed.py::_dsort_columnsort``), so host spans cannot
time the rounds from outside. This bench measures each primitive at the
EXACT shapes the pipeline uses — a fused multi-key ``lax.sort`` of the
per-shard rows, one all_to_all round, one half-block ppermute — plus the
full ``dsort``, and checks the additive cost model

    full  ≈  4 × local_sort + 2 × all_to_all + 2 × ppermute

On the shared-core virtual mesh the sorts serialize onto one CPU, which
is exactly why 8-shard throughput sits near 1/4 of the 1-shard local
sort; on real chips the rounds run on S
chips in parallel. Emits one JSON line per step.

Run:  python benchmarks/dsort_steps_bench.py [rows] [devices]
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
    _argv_devices = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    if _argv_devices > 1:
        # a multi-device sweep runs on the virtual CPU mesh; a 1-device
        # run keeps the live platform, so on the chip it times the fused
        # local sort on silicon
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

from tensorframes_tpu.utils.platform import \
    place_compile_cache  # noqa: E402


def _block(r):
    # DistributedFrame is not a pytree: block on its column arrays
    cols = getattr(r, "columns", None)
    jax.block_until_ready(list(cols.values())
                          if isinstance(cols, dict) else r)


def bench(fn, iters=20):
    _block(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        r = fn()
    _block(r)
    return (time.perf_counter() - t0) / iters


def main(n_rows: int = 1_000_000, n_dev: int = 8):
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    import tensorframes_tpu as tft
    from tensorframes_tpu import parallel as par

    mesh = par.local_mesh(n_dev)
    axis = mesh.data_axis
    S = mesh.num_data_shards
    rng = np.random.default_rng(5)
    x = rng.normal(size=n_rows)
    dist = par.distribute(tft.frame({"x": x}), mesh)

    # the pipeline's internal per-shard row count (distributed.py:634-638)
    padded = dist.padded_rows
    r = padded // S
    need = max(r, 2 * (S - 1) * (S - 1))
    rp = ((need + 2 * S - 1) // (2 * S)) * (2 * S)
    h = rp // 2

    key = jnp.asarray(rng.normal(size=S * rp))
    flag = jnp.zeros(S * rp, jnp.int8)
    rowid = jnp.arange(S * rp, dtype=jnp.int32)
    sharded1 = mesh.row_sharding(1)
    key, flag, rowid = (jax.device_put(a, sharded1)
                        for a in (key, flag, rowid))

    spec = (P(axis), P(axis), P(axis))

    def local_sort(flag, key, rowid):
        # the colsort round: ONE fused lexicographic sort + payload gather
        m = flag.shape[0]
        ops = (flag, key, rowid, jnp.arange(m, dtype=rowid.dtype))
        s = jax.lax.sort(ops, num_keys=3)
        return s[0], s[1], s[2]

    def a2a_round(flag, key, rowid):
        def deal(a):
            a2 = a.reshape((rp // S, S) + a.shape[1:]).swapaxes(0, 1)
            a2 = jax.lax.all_to_all(a2, axis, 0, 0, tiled=False)
            return a2.reshape((rp,) + a.shape[1:])
        return deal(flag), deal(key), deal(rowid)

    def perm_round(flag, key, rowid):
        fwd = [(j, j + 1) for j in range(S - 1)]

        def shift(a):
            return jnp.concatenate(
                [jax.lax.ppermute(a[h:], axis, fwd), a[:h]])
        return shift(flag), shift(key), shift(rowid)

    def smap(f):
        return jax.jit(shard_map(f, mesh=mesh.mesh, in_specs=spec,
                                 out_specs=spec))

    steps = {
        "local_sort": smap(local_sort),
        "all_to_all": smap(a2a_round),
        "ppermute_shift": smap(perm_round),
    }
    out = {}
    for name, fn in steps.items():
        out[name] = bench(lambda fn=fn: fn(flag, key, rowid))
        print(json.dumps({"step": name, "s_per_call": out[name],
                          "per_shard_rows": rp, "devices": S}))

    full = bench(lambda: par.dsort("x", dist, descending=True), iters=5)
    model = 4 * out["local_sort"] + 2 * out["all_to_all"] \
        + 2 * out["ppermute_shift"]
    print(json.dumps({
        "step": "full_dsort", "s_per_call": full, "rows": n_rows,
        "devices": S, "model_s": model,
        "model_ratio": full / model if model else None,
        "rows_per_s": n_rows / full,
        "platform": jax.devices()[0].platform,
    }))
    return out, full, model


if __name__ == "__main__":
    place_compile_cache()
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    d = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    main(n, d)
