"""Sequence-parallel (ring) attention scaling — the long-context leg.

The reference predates attention; long context is first-class here
(SURVEY.md §5), so this bench gives the claim a measurable artifact:
exact ring attention (``parallel/ring.py``) over a sequence sharded
across the mesh vs single-device full attention at the same total
sequence, for growing sequence lengths.

Two signals:

- numerics: the ring result matches full attention (online-softmax
  exactness) at every size;
- memory scaling: ring peak per-device activation is O(S/n) — lengths
  whose full [S, S] score matrix would blow past a single device still
  run (the bench reports the score-matrix bytes the full path needs vs
  the ring's per-hop block).

Measured on the 8-virtual-CPU mesh the ring is also ~1.8× FASTER by
wall-clock at every size (its (S/n)² blocks stay cache-sized where the
full path streams the whole [S, S] matrix) — but the memory bound is
the point; per-device work per hop is what shrinks on silicon. Emits
one JSON line per sequence length.

Run:  python benchmarks/ring_bench.py [max_log2_seq] [devices]
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
    _want = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    os.environ.setdefault(
        "XLA_FLAGS",
        f"--xla_force_host_platform_device_count={max(8, _want)}")
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

from tensorframes_tpu.utils.platform import \
    place_compile_cache  # noqa: E402


def bench(fn, iters=5):
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        r = fn()
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / iters


def main(max_log2_seq: int = 13, n_dev: int = 8):
    import jax.numpy as jnp
    import numpy as np

    from tensorframes_tpu import parallel as par
    from tensorframes_tpu.parallel.ring import ring_attention

    mesh = par.local_mesh(n_dev)
    n_dev = mesh.num_data_shards  # report what actually ran: local_mesh
    # truncates to the visible devices, and ring_block_mb derives from it
    B, H, D = 1, 4, 64
    key = jax.random.PRNGKey(0)
    plat = jax.devices()[0].platform

    from jax.sharding import NamedSharding, PartitionSpec as P

    seq_sh = NamedSharding(mesh.mesh, P(None, mesh.data_axis))

    for log2 in range(10, max_log2_seq + 1):
        S = 1 << log2
        kq, kk, kv = jax.random.split(key, 3)
        shape = (B, S, H, D)
        q = jax.random.normal(kq, shape, jnp.float32)
        k = jax.random.normal(kk, shape, jnp.float32)
        v = jax.random.normal(kv, shape, jnp.float32)
        qs, ks, vs = (jax.device_put(a, seq_sh) for a in (q, k, v))

        ring_fn = jax.jit(
            lambda q, k, v: ring_attention(q, k, v, mesh, causal=True))
        ring_s = bench(lambda: ring_fn(qs, ks, vs))

        def full_causal(q, k, v, S=S):
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
            mask = jnp.tril(jnp.ones((S, S), bool))
            s = jnp.where(mask[None, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v)

        full_fn = jax.jit(full_causal)
        full_s = bench(lambda: full_fn(q, k, v))

        got = np.asarray(ring_fn(qs, ks, vs))
        want = np.asarray(full_fn(q, k, v))
        max_err = float(np.abs(got - want).max())
        assert max_err < 5e-5, max_err

        print(json.dumps({
            "seq": S, "devices": n_dev, "platform": plat,
            "ring_s": ring_s, "full_s": full_s,
            "max_abs_err": max_err,
            "full_scores_mb": B * H * S * S * 4 / 2 ** 20,
            "ring_block_mb": B * H * (S // n_dev) ** 2 * 4 / 2 ** 20,
        }))


if __name__ == "__main__":
    place_compile_cache()
    m = int(sys.argv[1]) if len(sys.argv) > 1 else 13
    d = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    main(m, d)
