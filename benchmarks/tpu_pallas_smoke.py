"""On-chip proof of the Pallas (Mosaic) kernels.

CPU tests run these kernels with ``interpret=True`` — that checks the
math, not the Mosaic compilation path. This script compiles and runs both
custom kernels on the real TPU and asserts parity with their XLA
fallbacks:

  * ``segment_sum(impl="pallas")`` — the one-hot-matmul map-side partial
    reduction kernel (MXU);
  * ``flash_attention(impl="pallas")`` — the blocked online-softmax
    attention kernel (MXU + VMEM accumulators).

Prints one JSON line.

Run:  python benchmarks/tpu_pallas_smoke.py
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from tensorframes_tpu.ops.flash_attention import flash_attention
    from tensorframes_tpu.ops.segment_reduce import segment_sum
    from tensorframes_tpu.utils.platform import place_compile_cache

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(json.dumps({"ok": False,
                          "reason": f"no TPU (platform={platform})"}))
        return 1
    place_compile_cache()

    rng = np.random.default_rng(0)

    v = rng.standard_normal((4096, 16)).astype(np.float32)
    ids = rng.integers(0, 64, 4096).astype(np.int32)
    seg_p = segment_sum(v, ids, 64, impl="pallas")
    seg_x = segment_sum(v, ids, 64, impl="xla")
    seg_diff = float(jnp.max(jnp.abs(seg_p - seg_x)))
    seg_ok = seg_diff < 1e-3

    q = rng.standard_normal((2, 4, 512, 64)).astype(np.float32)
    k = rng.standard_normal((2, 4, 512, 64)).astype(np.float32)
    vv = rng.standard_normal((2, 4, 512, 64)).astype(np.float32)
    fa_p = flash_attention(q, k, vv, impl="pallas")
    fa_x = flash_attention(q, k, vv, impl="xla")
    fa_diff = float(jnp.max(jnp.abs(fa_p - fa_x)))
    fa_ok = fa_diff < 5e-2  # MXU bf16 passes vs full-softmax reference

    # Mosaic kernel traced INSIDE shard_map(check_vma=True): the exact
    # combination daggregate runs per shard (regression: pallas_call's
    # out_shape must declare the varying mesh axes or tracing fails)
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("shards",))
    n_dev = len(jax.devices())
    v2 = rng.standard_normal((512 * n_dev, 8)).astype(np.float32)
    ids2 = rng.integers(0, 16, 512 * n_dev).astype(np.int32)
    shard_fn = jax.shard_map(
        lambda vv_, ii_: segment_sum(vv_, ii_, 16, impl="pallas"),
        mesh=mesh, in_specs=(P("shards"), P("shards")),
        out_specs=P("shards"), check_vma=True)
    sm_out = np.asarray(jax.jit(shard_fn)(v2, ids2))
    sm_sum = sm_out.reshape(n_dev, 16, 8).sum(axis=0)
    sm_ref = np.asarray(segment_sum(v2, ids2, 16, impl="xla"))
    sm_diff = float(np.max(np.abs(sm_sum - sm_ref)))
    sm_ok = sm_diff < 1e-3

    rec = {
        "ok": bool(seg_ok and fa_ok and sm_ok),
        "platform": platform,
        "segment_sum_pallas_max_diff": seg_diff,
        "flash_attention_pallas_max_diff": fa_diff,
        "segment_sum_in_shard_map_max_diff": sm_diff,
        "mosaic_compiled": True,  # impl="pallas" → interpret=False
    }
    print(json.dumps(rec))
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
