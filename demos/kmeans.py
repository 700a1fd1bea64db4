"""Distributed K-Means on TensorFrames-TPU — the reference's flagship demo.

Capability parity with ``tensorframes_snippets/kmeans.py:85-164`` and
``kmeans_demo.py:47-148`` (three coordination patterns over the same math),
re-designed TPU-first:

 - the distance computation is ONE batched matmul (``|x|^2 + |c|^2 - 2 x.c``)
   that XLA tiles onto the MXU — no expand/tile scaffolding like the
   reference's graph needed (its ``tf.tile``/``tf.pack`` dance exists only
   because TF1 graph building lacked broadcasting ergonomics);
 - variant A (``step_aggregate``): map_blocks computes per-point
   assignments, then a keyed ``aggregate`` regroups by centroid index —
   the reference's ``run_one_step`` (groupBy shuffle path);
 - variant B (``step_preaggregate``): the whole per-block centroid update is
   pre-aggregated IN-GRAPH via segment-sum (the
   ``tf.unsorted_segment_sum`` pattern of ``kmeans_demo.py:128-140``, here
   the framework's one-hot-matmul Pallas kernel on TPU) with ``trim=True``
   emitting one row per block, then a tiny ``reduce_blocks`` combine —
   communication drops from O(points) to O(blocks * k);
 - variant C (``step_device_resident``): variant B's math on a
   ``distribute``d frame — data stays in device HBM across iterations, the
   driver only moves k x m centroids per round (the TPU-native ideal: the
   reference re-marshals every row through the JVM every iteration);
 - variant D (``step_daggregate``): the groupBy shuffle itself at mesh
   scale — ``dmap_blocks`` appends assignments, ``daggregate`` with
   DEVICE-side keys folds the centroid table on the mesh (the reference's
   cross-executor shuffle became one segment-reduce + collective, and the
   key column never visits the driver).

The driver loop (``kmeans``) matches the reference's: centroids live on the
driver and are embedded as constants into the next round's computation
(``kmeans.py:148-163``).
"""

from __future__ import annotations

from typing import Callable, Tuple

import numpy as np

import tensorframes_tpu as tft
from tensorframes_tpu.ops.segment_reduce import segment_sum


def _distances(points, centers):
    """[n, k] squared distances; one MXU matmul plus broadcasting."""
    import jax.numpy as jnp

    sq = jnp.sum(points * points, axis=1, keepdims=True)        # [n, 1]
    csq = jnp.sum(centers * centers, axis=1)                    # [k]
    return sq + csq[None, :] - 2.0 * points @ centers.T         # [n, k]


# -- variant A: map_blocks + keyed aggregate (reference run_one_step) -------

def step_aggregate(df: tft.TensorFrame,
                   centers: np.ndarray) -> Tuple[np.ndarray, float]:
    import jax.numpy as jnp

    k = centers.shape[0]
    c = jnp.asarray(centers)

    def assign(features):
        d = _distances(features, c)
        return {
            "indexes": jnp.argmin(d, axis=1).astype(jnp.int32),
            "count": jnp.ones(features.shape[0], jnp.int64),
            "min_distances": jnp.min(d, axis=1),
        }

    df2 = tft.map_blocks(assign, df)
    gb = df2.group_by("indexes")

    def summarize(features_input, count_input, min_distances_input):
        return {
            "features": features_input.sum(0),
            "count": count_input.sum(0),
            "min_distances": min_distances_input.sum(0),
        }

    df3 = tft.aggregate(summarize, gb)
    new_centers = centers.copy()
    total = 0.0
    for row in df3.collect():
        idx = int(row["indexes"])
        new_centers[idx] = np.asarray(row["features"]) / row["count"]
        total += float(row["min_distances"])
    return new_centers, total


# -- variant B: in-graph segment-sum pre-aggregation (run_one_step2) --------

def _preagg_computation(centers: np.ndarray,
                        n_valid: int = None) -> Callable:
    """``n_valid`` masks pad rows on the device-resident path: their segment
    id becomes -1 (dropped by segment_sum) and their distance 0."""
    import jax.numpy as jnp

    k = centers.shape[0]
    c = jnp.asarray(centers)

    def preagg(features):
        d = _distances(features, c)
        idx = jnp.argmin(d, axis=1).astype(jnp.int32)
        mind = jnp.min(d, axis=1)
        if n_valid is not None:
            valid = jnp.arange(features.shape[0]) < n_valid
            idx = jnp.where(valid, idx, -1)
            mind = jnp.where(valid, mind, 0.0)
        ones = jnp.ones((features.shape[0], 1), features.dtype)
        # one row per BLOCK: [1, k, m] sums, [1, k] counts, [1] distance
        pts = segment_sum(features, idx, k)
        cnt = segment_sum(ones, idx, k)[:, 0]
        return {
            "agg_points": pts[None],
            "agg_counts": cnt[None],
            "agg_distances": mind.sum()[None],
        }

    return preagg


def _combine_partials(rows_pts, rows_cnt, rows_dst, centers):
    pts = rows_pts.sum(0)                      # [k, m]
    cnt = rows_cnt.sum(0)                      # [k]
    new = np.where(cnt[:, None] > 0, pts / np.maximum(cnt, 1.0)[:, None],
                   centers)                    # empty cluster keeps center
    return new.astype(centers.dtype), float(rows_dst.sum())


def step_preaggregate(df: tft.TensorFrame,
                      centers: np.ndarray) -> Tuple[np.ndarray, float]:
    from tensorframes_tpu.engine import ops as engine_ops

    df2 = tft.map_blocks(_preagg_computation(centers), df, trim=True)
    red = engine_ops.reduce_blocks(
        lambda agg_points_input, agg_counts_input, agg_distances_input: {
            "agg_points": agg_points_input.sum(0),
            "agg_counts": agg_counts_input.sum(0),
            "agg_distances": agg_distances_input.sum(0),
        }, df2)
    return _combine_partials(red["agg_points"][None],
                             red["agg_counts"][None],
                             np.asarray([red["agg_distances"]]), centers)


# -- variant C: device-resident frame, centroids-only traffic ---------------

def step_device_resident(dist, centers: np.ndarray) -> Tuple[np.ndarray, float]:
    """One step on a ``distribute``d frame (see ``parallel.distributed``).

    ``dist`` stays in HBM; per-step host traffic is just the k x m centroid
    matrix out and k x (m+2) partials back.
    """
    from tensorframes_tpu.computation import Computation, TensorSpec
    from tensorframes_tpu.parallel.distributed import dmap_blocks
    from tensorframes_tpu import dtypes as _dt
    from tensorframes_tpu.shape import Shape, Unknown

    m = centers.shape[1]
    comp = Computation.trace(
        _preagg_computation(centers, n_valid=dist.num_rows),
        [TensorSpec("features", _dt.double, Shape(Unknown, m))])
    out = dmap_blocks(comp, dist, trim=True, row_aligned=False)
    return _combine_partials(np.asarray(out.columns["agg_points"]),
                             np.asarray(out.columns["agg_counts"]),
                             np.asarray(out.columns["agg_distances"]),
                             centers)


def step_daggregate(dist, centers: np.ndarray) -> Tuple[np.ndarray, float]:
    """One step as a mesh-level keyed SHUFFLE (variant A at mesh scale).

    The reference's groupBy path moved every row between executors by
    centroid key; here ``dmap_blocks`` appends the assignment + per-point
    partials and ``daggregate(max_groups=k)`` folds them into the k-row
    table with DEVICE-side keys — per-step host traffic is the k x (m+2)
    table, and the key column never visits the driver.
    """
    import jax.numpy as jnp

    from tensorframes_tpu.parallel.distributed import daggregate, dmap_blocks

    k, m = centers.shape
    c = centers

    def assign_fn(features):
        d = _distances(features, c)
        a = jnp.argmin(d, axis=1).astype(jnp.int32)
        return {"assign": a,
                "mind": jnp.min(d, axis=1),
                "ones": jnp.ones((features.shape[0],), features.dtype)}

    scored = dmap_blocks(assign_fn, dist)
    # pad rows never reach the shuffle: daggregate marks them out via its
    # validity-aware group-id construction
    table = daggregate({"features": "sum", "mind": "sum", "ones": "sum"},
                       scored, "assign", max_groups=k)
    rows = table.collect()
    sums = np.zeros_like(centers)
    counts = np.zeros((k,))
    dist_total = 0.0
    for r in rows:
        i = int(r["assign"])
        sums[i] = np.asarray(r["features"])
        counts[i] = r["ones"]
        dist_total += float(r["mind"])
    safe = np.maximum(counts, 1.0)[:, None]
    new_centers = np.where(counts[:, None] > 0, sums / safe, centers)
    return new_centers, float(dist_total)


# -- driver loop (reference kmeans.py:148-163) ------------------------------

def kmeans(df: tft.TensorFrame, init_centers: np.ndarray,
           num_iters: int = 50, step=step_preaggregate,
           verbose: bool = False):
    """Iterate until the total distance stops improving."""
    c = np.asarray(init_centers, np.float64)
    d = np.inf
    history = []
    for i in range(num_iters):
        c1, d1 = step(df, c)
        if verbose:
            print(f"Step = {i} , overall distance = {d1}")
        c = c1
        if d == d1:
            break
        d = d1
        history.append(d1)
    return c, history


def make_data(n: int = 1000, num_features: int = 4, k: int = 2,
              num_partitions: int = 4, seed: int = 1):
    """Gaussian blobs around k corners (the RandomRDDs.normalVectorRDD
    analogue, but separable so convergence is checkable)."""
    rng = np.random.default_rng(seed)
    true_centers = rng.uniform(-5, 5, (k, num_features))
    assign = rng.integers(0, k, n)
    pts = true_centers[assign] + rng.normal(0, 0.3, (n, num_features))
    df = tft.frame({"features": pts}, num_partitions=num_partitions)
    df = tft.analyze(df)   # "For now, analysis is still required." — ditto
    init = pts[rng.choice(n, k, replace=False)]
    return df, init, true_centers


def main():
    df, init, true_centers = make_data()
    for name, step in [("aggregate", step_aggregate),
                       ("preaggregate", step_preaggregate)]:
        centers, history = kmeans(df, init, step=step, verbose=True)
        print(f"[{name}] converged after {len(history)} steps; "
              f"final distance {history[-1]:.3f}")
    print("centers:\n", centers)

    # mesh variants (C: device-resident frame, D: mesh keyed shuffle)
    from tensorframes_tpu.parallel.distributed import distribute
    from tensorframes_tpu.parallel.mesh import local_mesh

    dist = distribute(df, local_mesh())
    for name, step in [("device_resident", step_device_resident),
                       ("daggregate", step_daggregate)]:
        centers, history = kmeans(dist, init, step=step)
        print(f"[{name}] converged after {len(history)} steps; "
              f"final distance {history[-1]:.3f}")


if __name__ == "__main__":
    from tensorframes_tpu.utils.platform import place_compile_cache

    place_compile_cache()
    main()
