"""All-to-all hash-repartition exchange: rows routed to key-owning shards.

The missing shuffle primitive (ROADMAP item 3): both join strategies
funnel through a single-node bottleneck — broadcast materializes the
whole build side on every probe path, sort-merge pays a full columnsort
of both sides — because nothing could *repartition rows by key*. This
module is that primitive, built from the same pieces the existing mesh
ops already exercise:

- **device-side splitmix64 key hashing on uint32 pairs** — the exact
  splitmix64 the host sketches use (``relational/sketch.py``), but
  implemented as 64-bit arithmetic over two uint32 lanes so the program
  compiles and hashes identically with ``jax_enable_x64`` OFF (the
  chip-independent prep ROADMAP item 2 asks for: TPU int32/f32 worlds
  and x64 CPU tests place every row the same way for device-exact key
  dtypes);
- **per-shard bucket counts via the traced-survivor-count trick** from
  ``dfilter``: a first tiny program returns each shard's per-destination
  counts as an output read back on the host (``S*S`` int32s — counted in
  ``mesh.interstage_host_bytes``), which sizes the static exchange
  buffers;
- **static-shape ``all_to_all`` with validity masks**: each shard
  scatters its rows into ``[S, cap]`` destination buckets, one
  ``all_to_all`` swaps bucket ``d`` to shard ``d`` (the dsort
  contiguous-chunk idiom), received rows compact stably to the front and
  the per-source counts become the result's ``shard_valid``;
- **string ride-alongs re-laid out host-side exactly like reshard**:
  the program carries a global row id; host (non-tensor) columns replay
  the placement on the host from it.

Every dispatch rides the established contracts: ``elastic_call``
(device-loss shrink/reshard/re-run), ledger admission on the exchange
buffers (``memory.estimate.exchange_buffer_bytes`` + ``make_room``,
results registered spillable), compiled-program LRU caching, and the
skew observability surface (``mesh.exchange_*`` counters, an
``explain()`` imbalance line wired to ``TFT_SKEW_WARN``, and
``record_stream_feedback`` — groundwork for ROADMAP item 4).

``TFT_SHUFFLE=0`` is the kill switch: the CONSUMERS (``join()``
routing, :func:`shuffle_daggregate`, ``partitioned_hash_join``) fall
back to the broadcast/chunked/sort-merge paths bit-identically by
construction; the primitive itself stays callable either way.

Output order: received rows are ordered by (source shard, source row)
— i.e. the original global row order restricted to each shard's key
range — so consumers that need the pre-exchange order (the partitioned
join's probe side) restore it with one stable sort on a carried row id.
"""

from __future__ import annotations

import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map
from .. import memory as _memory
from ..engine import ops as _ops
from ..frame import TensorFrame
from ..observability import flight as _flight
from ..observability.events import current_trace, traced_query
from ..resilience import invariants as _invariants
from ..resilience.policy import env_bool, env_int
from ..utils.logging import get_logger
from ..utils.tracing import counters, span
from . import elastic as _elastic

__all__ = ["dexchange", "shuffle_daggregate", "shuffle_enabled",
           "shuffle_agg_groups_threshold", "exchange_hash_host"]

_log = get_logger("parallel.exchange")


def shuffle_enabled() -> bool:
    """The shuffle kill switch (``TFT_SHUFFLE``, default on). Off, the
    consumers — ``join()`` auto-routing, ``partitioned_hash_join``,
    :func:`shuffle_daggregate` and the ``daggregate`` high-cardinality
    auto-route — restore the broadcast/chunked/sort-merge paths
    bit-identically by construction."""
    return env_bool("TFT_SHUFFLE", True)


def shuffle_agg_groups_threshold() -> Optional[int]:
    """Group count above which ``daggregate``'s monoid host-key path
    auto-routes to the shuffle-partitioned aggregation
    (``TFT_SHUFFLE_AGG_GROUPS``, default 131072; <= 0 disables the
    auto-route)."""
    v = env_int("TFT_SHUFFLE_AGG_GROUPS", 1 << 17)
    return v if v and v > 0 else None


# ---------------------------------------------------------------------------
# splitmix64 on uint32 pairs (works with jax_enable_x64 off)
# ---------------------------------------------------------------------------
# The three 64-bit constants of the host _splitmix64
# (relational/sketch.py), split into (hi, lo) uint32 halves.

_SM_GAMMA = (0x9E3779B9, 0x7F4A7C15)
_SM_MUL1 = (0xBF58476D, 0x1CE4E5B9)
_SM_MUL2 = (0x94D049BB, 0x133111EB)


def _add64(ah, al, bh, bl):
    """(a + b) mod 2^64 over (hi, lo) uint32 pairs."""
    lo = al + bl
    carry = (lo < al).astype(jnp.uint32)
    return ah + bh + carry, lo


def _mul32_wide(a, b):
    """The full 64-bit product of two uint32 lanes as a (hi, lo) pair
    — 16-bit limb products, each exact in uint32."""
    a0 = a & jnp.uint32(0xFFFF)
    a1 = a >> 16
    b0 = b & jnp.uint32(0xFFFF)
    b1 = b >> 16
    ll = a0 * b0
    lh = a0 * b1
    mid = lh + a1 * b0
    carry_mid = (mid < lh).astype(jnp.uint32)
    lo = ll + (mid << 16)
    carry_lo = (lo < ll).astype(jnp.uint32)
    hi = a1 * b1 + (mid >> 16) + (carry_mid << 16) + carry_lo
    return hi, lo


def _mul64(ah, al, bh, bl):
    """(a * b) mod 2^64 over (hi, lo) uint32 pairs."""
    hi, lo = _mul32_wide(al, bl)
    return hi + al * bh + ah * bl, lo


def _xorshr64(h, l, n: int):
    """z ^ (z >> n) for 0 < n < 32, over a (hi, lo) uint32 pair."""
    return h ^ (h >> n), l ^ ((l >> n) | (h << (32 - n)))


def _splitmix64_pair(h, l):
    """The splitmix64 finalizer over (hi, lo) uint32 pairs — the same
    constants and shift schedule as the host ``_splitmix64``, so for
    device-exact key dtypes (ints, bools, f64 under x64) the device
    hash equals the host hash bit for bit."""
    h, l = _add64(h, l, jnp.uint32(_SM_GAMMA[0]), jnp.uint32(_SM_GAMMA[1]))
    h, l = _xorshr64(h, l, 30)
    h, l = _mul64(h, l, jnp.uint32(_SM_MUL1[0]), jnp.uint32(_SM_MUL1[1]))
    h, l = _xorshr64(h, l, 27)
    h, l = _mul64(h, l, jnp.uint32(_SM_MUL2[0]), jnp.uint32(_SM_MUL2[1]))
    return _xorshr64(h, l, 31)


def _key_pair(a):
    """A device key column as the (hi, lo) uint32 pair of the 64-bit
    value the host ``_hash64`` would hash: ints sign-extend to 64-bit
    two's complement, floats canonicalize -0.0 and NaN first. f32
    columns (x64 off) hash their own 32-bit pattern — deterministic and
    identical on both join sides (key dtypes must match), just not the
    host's f64 widening."""
    dt = a.dtype
    if jnp.issubdtype(dt, jnp.floating):
        if np.dtype(dt).itemsize < 4:
            a = a.astype(jnp.float32)
        a = jnp.where(a == 0, jnp.zeros((), a.dtype), a)
        a = jnp.where(jnp.isnan(a), jnp.full((), jnp.nan, a.dtype), a)
        if np.dtype(a.dtype).itemsize == 8:
            pair = jax.lax.bitcast_convert_type(a, jnp.uint32)
            return pair[..., 1], pair[..., 0]
        lo = jax.lax.bitcast_convert_type(a, jnp.uint32)
        return jnp.zeros_like(lo), lo
    if dt == jnp.bool_:
        lo = a.astype(jnp.uint32)
        return jnp.zeros_like(lo), lo
    if np.dtype(dt).itemsize == 8:  # int64 / uint64 (x64 on)
        pair = jax.lax.bitcast_convert_type(a, jnp.uint32)
        return pair[..., 1], pair[..., 0]
    if jnp.issubdtype(dt, jnp.unsignedinteger):
        lo = a.astype(jnp.uint32)
        return jnp.zeros_like(lo), lo
    i = a.astype(jnp.int32)
    lo = jax.lax.bitcast_convert_type(i, jnp.uint32)
    hi = jnp.where(i < 0, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
    return hi, lo


def _hash_pairs(key_cols):
    """Chain-combine per-key hashes exactly like the host sketches:
    ``h = hash(k0); h = splitmix64(h ^ hash(k))`` for each further key,
    where ``hash(k) = splitmix64(bits64(k))``."""
    h = l = None
    for a in key_cols:
        kh, kl = _splitmix64_pair(*_key_pair(a))
        if h is None:
            h, l = kh, kl
        else:
            h, l = _splitmix64_pair(h ^ kh, l ^ kl)
    return h, l


def _dest_from_hash(h, l, S: int):
    """``hash64 % S`` without 64-bit arithmetic:
    ``((hi % S) * (2^32 % S) + lo % S) % S`` — exact for S < 2^16."""
    m = jnp.uint32(S)
    r = jnp.uint32((1 << 32) % S)
    return (((h % m) * r + (l % m)) % m).astype(jnp.int32)


def exchange_hash_host(key_arrays: Sequence[np.ndarray]) -> np.ndarray:
    """The host twin of the device key hash (uint64 lanes): the sketch
    ``_hash64`` chain. Used for string / mixed key columns (which never
    enter the sharded program) and by the placement property tests —
    for device-exact key dtypes ``exchange_hash_host(keys) % S`` IS the
    destination shard the device program picks."""
    from ..relational.sketch import _hash64, _splitmix64
    h = _hash64(np.asarray(key_arrays[0]))
    for k in key_arrays[1:]:
        h = _splitmix64(h ^ _hash64(np.asarray(k)))
    return h


# ---------------------------------------------------------------------------
# the exchange programs (LRU-cached like _dsort_cache)
# ---------------------------------------------------------------------------

_exchange_cache: "OrderedDict[tuple, object]" = OrderedDict()
_EXCHANGE_CACHE_CAP = 32


def _cached_program(key, build):
    fn = _exchange_cache.get(key)
    if fn is not None:
        _exchange_cache.move_to_end(key)
        return fn
    fn = jax.jit(build())
    _exchange_cache[key] = fn
    while len(_exchange_cache) > _EXCHANGE_CACHE_CAP:
        _exchange_cache.popitem(last=False)
    return fn


def _counts_program(mesh, rows_per: int, S: int, key_specs, hash_on_device):
    """Per-shard per-destination bucket counts ([S] int32 out, sharded
    over the axis → global [S*S]) — the dfilter survivor-count trick,
    run first so the exchange buffers get a static size."""
    axis = mesh.data_axis
    key = ("counts", mesh.mesh, axis, rows_per, S, hash_on_device,
           key_specs)
    in_specs = (P(axis),) + tuple(P(axis) for _ in key_specs)
    out_specs = P(axis)

    def build():
        def shard_fn(cnt, *keys):
            if hash_on_device:
                dest = _dest_from_hash(*_hash_pairs(keys), S)
            else:
                dest = keys[0]
            valid = jnp.arange(rows_per) < cnt[0]
            d = jnp.where(valid, jnp.clip(dest, 0, S - 1), S)
            return jnp.zeros((S,), jnp.int32).at[d].add(
                jnp.where(valid, jnp.int32(1), jnp.int32(0)), mode="drop")

        return shard_map(shard_fn, mesh=mesh.mesh, in_specs=in_specs,
                         out_specs=out_specs)

    return _cached_program(key, build)


def _exchange_program(mesh, rows_per: int, S: int, cap: int, col_specs,
                      key_idx, hash_on_device, want_rowid: bool):
    """The exchange itself: stable bucket scatter into ``[S, cap]``,
    one ``all_to_all`` per column (+ the bucket counts), validity-mask
    compaction of the received slots, per-shard received total out."""
    axis = mesh.data_axis
    key = ("exchange", mesh.mesh, axis, rows_per, S, cap, col_specs,
           tuple(key_idx), hash_on_device, want_rowid)
    in_specs = (P(axis),) + tuple(
        P(axis, *([None] * (len(cell) )))
        for _, cell, _ in col_specs)
    n_cols = len(col_specs)
    out_col_specs = tuple(
        P(axis, *([None] * (len(cell))))
        for _, cell, _ in col_specs)
    out_specs = out_col_specs + ((P(axis),) if want_rowid else ()) \
        + (P(axis),)

    def build():
        def shard_fn(cnt, *cols):
            me = jax.lax.axis_index(axis)
            if hash_on_device:
                dest = _dest_from_hash(
                    *_hash_pairs([cols[i] for i in key_idx]), S)
            else:
                dest = cols[key_idx[0]]
            valid = jnp.arange(rows_per) < cnt[0]
            d = jnp.where(valid, jnp.clip(dest, 0, S - 1), S)
            # stable sort by destination: each bucket's rows keep their
            # source order, so receivers see original global row order
            order = jnp.argsort(d.astype(jnp.int32), stable=True)
            d_s = jnp.take(d, order)
            bcounts = jnp.zeros((S,), jnp.int32).at[d].add(
                jnp.where(valid, jnp.int32(1), jnp.int32(0)), mode="drop")
            starts = jnp.concatenate(
                [jnp.zeros((1,), jnp.int32), jnp.cumsum(bcounts)[:-1]])
            within = jnp.arange(rows_per, dtype=jnp.int32) - jnp.take(
                starts, jnp.clip(d_s, 0, S - 1))
            pos = jnp.where(d_s < S,
                            jnp.clip(d_s, 0, S - 1) * cap + within,
                            S * cap)  # pads scatter out of range: dropped

            def xchg(buf):
                b = buf.reshape((S, cap) + buf.shape[1:])
                b = jax.lax.all_to_all(b, axis, 0, 0, tiled=False)
                return b.reshape((S * cap,) + buf.shape[1:])

            rc = jax.lax.all_to_all(
                bcounts.reshape(S, 1), axis, 0, 0, tiled=False
            ).reshape(S)
            slot = jnp.arange(S * cap, dtype=jnp.int32)
            recv_valid = (slot % cap) < jnp.take(rc, slot // cap)
            corder = jnp.argsort(
                jnp.where(recv_valid, jnp.int8(0), jnp.int8(1)),
                stable=True)

            def route(c):
                cs = jnp.take(c, order, axis=0)
                buf = jnp.zeros((S * cap,) + c.shape[1:], c.dtype)
                buf = buf.at[pos].set(cs, mode="drop")
                return jnp.take(xchg(buf), corder, axis=0)

            outs = tuple(route(c) for c in cols)
            if want_rowid:
                rowid = (me * rows_per
                         + jnp.arange(rows_per)).astype(jnp.int32)
                outs = outs + (route(rowid),)
            return outs + (jnp.sum(rc, dtype=jnp.int32)[None],)

        return shard_map(shard_fn, mesh=mesh.mesh, in_specs=in_specs,
                         out_specs=out_specs)

    return _cached_program(key, build), n_cols


# ---------------------------------------------------------------------------
# the public exchange
# ---------------------------------------------------------------------------

def _meta_dexchange(keys=None, dist=None, *a, **k):
    dist = k.get("dist", dist)
    keys = k.get("keys", keys)
    if dist is None:
        return {}
    m = dist.mesh
    return {"mesh_shape": dict(m.mesh.shape),
            "shards": m.num_data_shards, "rows": dist.num_rows,
            "keys": [keys] if isinstance(keys, str) else list(keys or ())}


def dexchange(keys, dist):
    """Hash-repartition ``dist`` so every row lives on the shard owning
    its key's hash range (``splitmix64(key) % shards``).

    Placement is a pure function of the key VALUES and the shard count —
    two frames exchanged by equal-dtype keys on the same mesh colocate
    equal keys on the same shard (the partitioned-join invariant), and
    repeated exchanges of the same data place identically. Keys must be
    scalar columns; numeric keys hash on device (the uint32-pair
    splitmix64 — x64 not required), string / mixed key sets hash on the
    host and ship a destination column instead. Host (string) ride-along
    columns re-lay out host-side from the carried row ids, exactly like
    ``reshard``. Dispatch crosses ``elastic_call``: a device loss
    shrinks the mesh, re-shards, and re-runs — same rows, fewer (wider)
    hash ranges.

    Returns a frame with per-shard validity (``shard_valid``) whose
    received rows are ordered by original global row order within each
    shard. Single-shard meshes return ``dist`` unchanged.
    """
    lz = getattr(dist, "_tft_lazy_dist", False)
    if lz:
        from ..plan import dist as _dplan
        dist = _dplan.materialize(dist)
    keys = [keys] if isinstance(keys, str) else list(keys)
    if not keys:
        raise ValueError("dexchange needs at least one key column")
    for k in keys:
        f = dist.schema.get(k)
        if f is None:
            raise KeyError(
                f"No key column {k!r}; columns: {dist.schema.names}")
        if f.sql_rank != 0:
            raise _ops.InvalidTypeError(
                f"dexchange key {k!r} must be a scalar column")
    if dist.mesh.num_data_shards <= 1:
        return dist
    return _dexchange_eager(keys, dist)


@traced_query("dexchange", _meta_dexchange)
def _dexchange_eager(keys, dist):
    return _elastic.elastic_call("dexchange", dist,
                                 lambda d: _dexchange(keys, d))


def _dexchange(keys, dist):
    from .distributed import DistributedFrame, _read_global
    mesh = dist.mesh
    S = mesh.num_data_shards
    if S <= 1:
        return dist
    if dist.padded_rows % S != 0:
        # non-tiling (trim/global-result) frames first normalize to the
        # even prefix layout — the same host round-trip reshard uses
        dist = _elastic.reshard(dist, mesh)
    if dist.padded_rows >= 2 ** 31:
        raise ValueError(
            f"dexchange carries int32 row ids; {dist.padded_rows} padded "
            f"rows overflow them")
    t_start = time.perf_counter()
    axis = mesh.data_axis
    rows_per = dist.padded_rows // S
    schema = dist.schema
    tensor_names = [f.name for f in schema if f.dtype.tensor]
    host_names = [f.name for f in schema if not f.dtype.tensor]
    hash_on_device = all(schema[k].dtype.tensor for k in keys)

    counts_host = dist.per_shard_valid().astype(np.int32)
    cnt_dev = jax.make_array_from_callback(
        (S,), mesh.row_sharding(1), lambda idx: counts_host[idx])

    arrays = [dist.columns[n] for n in tensor_names]
    col_specs = tuple((n, tuple(a.shape[1:]), str(a.dtype))
                      for n, a in zip(tensor_names, arrays))

    if hash_on_device:
        key_arrays = [dist.columns[k] for k in keys]
        key_specs = tuple((k, str(dist.columns[k].dtype)) for k in keys)
    else:
        # string / mixed keys: destinations computed on the host with
        # the sketch hash chain, shipped in as one int32 column (both
        # join sides take this path — key dtypes must match — so
        # placement stays consistent)
        host_keys = [dist.host_read_padded(k) for k in keys]
        dest_host = (exchange_hash_host(host_keys)
                     % np.uint64(S)).astype(np.int32)
        key_arrays = [jax.make_array_from_callback(
            (dist.padded_rows,), mesh.row_sharding(1),
            lambda idx: dest_host[idx])]
        key_specs = (("_tft_dest", "int32"),)

    # -- phase 1: bucket counts (the traced-survivor-count trick) ---------
    cfn = _counts_program(mesh, rows_per, S, key_specs, hash_on_device)
    with span("dexchange.counts"):
        c_global = _read_global(cfn(cnt_dev, *key_arrays))
    counters.inc("mesh.interstage_host_bytes", 4 * S * S)
    cmat = np.asarray(c_global, np.int64).reshape(S, S)  # [src, dst]
    maxc = int(cmat.max()) if cmat.size else 0
    # round the static bucket capacity up so near-miss sizes reuse the
    # compiled program; never beyond rows_per (a bucket cannot exceed it)
    cap = min(max(((max(maxc, 1) + 15) // 16) * 16, 1), rows_per)

    # -- ledger admission on the receive buffers ---------------------------
    from ..memory.estimate import exchange_buffer_bytes
    est = exchange_buffer_bytes(
        [(cell, dt) for _, cell, dt in col_specs], S, cap,
        rowid_bytes=4 if host_names else 0)
    mgr = _memory.active()
    if mgr is not None and est:
        mgr.make_room(est)
    counters.inc("mesh.exchange_bytes", est)

    # -- phase 2: the exchange --------------------------------------------
    want_rowid = bool(host_names)
    prog_arrays = list(arrays)
    key_idx = []
    if hash_on_device:
        key_idx = [tensor_names.index(k) for k in keys]
    else:
        prog_arrays = prog_arrays + key_arrays
        col_specs = col_specs + (("_tft_dest", (), "int32"),)
        key_idx = [len(tensor_names)]
    fn, n_cols = _exchange_program(mesh, rows_per, S, cap, col_specs,
                                   key_idx, hash_on_device, want_rowid)
    trace = current_trace()
    t0 = 0.0
    if trace is not None:
        from .distributed import _trace_shards, _trace_mesh_done
        t0 = _trace_shards(trace, "dexchange", dist=dist)
        trace.add("collective", name="all_to_all", ts=t0, op="dexchange",
                  columns=len(col_specs))
    with span("dexchange.dispatch"):
        outs = fn(cnt_dev, *prog_arrays)
    if trace is not None:
        _trace_mesh_done(trace, list(outs), t0, "dexchange", mesh=mesh)
    counters.inc("mesh.dispatches")

    n_tensor = len(tensor_names)
    new_cols: Dict[str, jax.Array] = dict(zip(tensor_names, outs[:n_tensor]))
    recv = _read_global(outs[-1]).astype(np.int64)  # [S] per-shard totals
    counters.inc("mesh.interstage_host_bytes", 4 * S)
    total = int(recv.sum())
    if total != dist.num_rows:
        # raises in EVERY mode (resilience/invariants.py): rows lost
        # across an all-to-all are never a count-and-continue condition
        _invariants.conserve(
            dist.num_rows, total,
            f"dexchange (per-shard {recv.tolist()})")

    per_out = S * cap
    if want_rowid:
        rowid_g = _read_global(outs[n_cols])
        counters.inc("mesh.interstage_host_bytes", 4 * S * per_out)
        vmask = (np.arange(S * per_out) % per_out) < np.repeat(recv, per_out)
        for n in host_names:
            src = np.asarray(dist.columns[n], object)
            out_a = np.full(S * per_out, None, object)
            out_a[vmask] = src[rowid_g[vmask]]
            new_cols[n] = out_a

    if mgr is not None and mgr.spill_enabled:
        new_cols = _memory.spillable_columns(
            f"dexchange@{id(dist):x}", new_cols, mgr)
    result = DistributedFrame(mesh, schema, new_cols, dist.num_rows,
                              shard_valid=recv)
    _note_exchange_skew(result, recv, total, S,
                        time.perf_counter() - t_start)
    return result


def _note_exchange_skew(result, recv: np.ndarray, total: int, S: int,
                        wall_s: float) -> None:
    """The exchange's skew observability surface: ``mesh.exchange_*``
    counters, the ``explain()`` imbalance line (``result._exchange``),
    a flight-recorder anomaly past ``TFT_SKEW_WARN``, and the adaptive
    layer's stream feedback (ROADMAP item 4 groundwork)."""
    from ..observability.report import _skew_threshold
    counters.inc("mesh.exchange_dispatches")
    counters.inc("mesh.exchange_rows", total)
    med = float(np.median(recv))
    mx = float(recv.max()) if recv.size else 0.0
    ratio = (mx / med) if med > 0 else (float("inf") if mx else 0.0)
    thr = _skew_threshold()
    result._exchange = {"op": "dexchange",
                        "per_shard": [int(v) for v in recv],
                        "ratio": ratio, "threshold": thr}
    if ratio > thr:
        counters.inc("mesh.exchange_skew_events")
        _flight.record("mesh.exchange_skew", op="dexchange",
                       ratio=round(min(ratio, 1e9), 3), threshold=thr,
                       rows=total,
                       per_shard=[int(v) for v in recv[:16]])
        _log.info(
            "dexchange: partition imbalance %.2f over TFT_SKEW_WARN=%.2f "
            "(per-shard rows %s)", ratio, thr, [int(v) for v in recv])
    try:
        from ..plan.adaptive import record_stream_feedback
        occupancy = (total / S) / mx if mx else None
        record_stream_feedback("dexchange", blocks=S, rows=total,
                               wall_s=max(wall_s, 1e-9),
                               occupancy=occupancy)
    except Exception as e:  # noqa: BLE001 - feedback is advisory
        _log.debug("exchange stream feedback failed: %s", e)


# ---------------------------------------------------------------------------
# shuffle-partitioned aggregation (high-cardinality keys)
# ---------------------------------------------------------------------------

def shuffle_daggregate(fetches, dist, keys) -> TensorFrame:
    """Keyed aggregation by hash-repartition: rows exchange to their
    key-owning shards, each shard aggregates ONLY its own (disjoint)
    key ranges, and the per-shard results concatenate + reorder to
    ``daggregate``'s canonical ascending group order.

    For high-cardinality keys this replaces ``daggregate``'s dense
    ``[groups, ...]`` per-shard tables (every shard holds EVERY group)
    with O(groups / shards) state per device — beyond what hot-key
    salting addresses (salting spreads few huge groups; this spreads
    many). ``daggregate``'s monoid host-key path auto-routes here above
    ``TFT_SHUFFLE_AGG_GROUPS`` groups. Same result frame: same groups,
    same order, same dtypes — exact for discrete combiners (min/max,
    int sums); float sums may reassociate, like any resharding
    (``docs/joins.md``). ``TFT_SHUFFLE=0``, single-shard meshes,
    sketch combiners, and non-monoid fetches delegate to
    ``daggregate`` unchanged.
    """
    from .distributed import daggregate
    keys = [keys] if isinstance(keys, str) else list(keys)
    from ..engine.ops import _is_sketch, _monoid_mapping
    if (not shuffle_enabled() or dist.mesh.num_data_shards <= 1
            or not _monoid_mapping(fetches)
            or any(_is_sketch(v) for v in fetches.values())):
        return daggregate(fetches, dist, keys)
    if dist.num_rows == 0:
        raise ValueError("aggregate on an empty distributed frame")
    return _shuffle_daggregate_impl(fetches, dist, keys)


def _shuffle_daggregate_impl(fetches, dist, keys) -> TensorFrame:
    """The exchanged monoid aggregation (callers validated the route)."""
    from .. import api as _api
    from ..engine.ops import _factorize_keys
    from ..frame import Block
    from ..schema import Field, Schema
    from ..shape import Unknown

    fetch_names = sorted(fetches)
    needed = list(dict.fromkeys(list(keys) + fetch_names))
    sub = dist.select(needed) if set(needed) != set(dist.schema.names) \
        else dist
    with span("daggregate.shuffle"):
        ex = dexchange(keys, sub)
        S = ex.mesh.num_data_shards
        valid = ex.per_shard_valid()
        rows_per = ex.padded_rows // S
        host = {n: ex.host_read_padded(n) for n in needed}
        schema = ex.schema
        parts: List[Block] = []
        for s in range(S):
            k = int(valid[s])
            if k == 0:
                continue
            cols = {}
            for n in needed:
                a = host[n][s * rows_per: s * rows_per + k]
                f = schema[n]
                if isinstance(a, np.ndarray) and f.dtype.tensor \
                        and a.dtype != f.dtype.np_storage:
                    a = a.astype(f.dtype.np_storage)
                cols[n] = a
            shard_frame = TensorFrame.from_columns(
                cols, schema=schema.select(needed))
            part = _api.aggregate(dict(fetches),
                                  shard_frame.group_by(*keys))
            parts.append(Block.concat(part.blocks(), part.schema))
        out_fields = [schema[k] for k in keys] + [
            Field(f, schema[f].dtype,
                  block_shape=(schema[f].block_shape.with_lead(Unknown)
                               if schema[f].block_shape is not None
                               else None),
                  sql_rank=schema[f].sql_rank)
            for f in fetch_names]
        out_schema = Schema(out_fields)
        merged = Block.concat(parts, out_schema)
        # shards own disjoint hash ranges, not contiguous key ranges —
        # one stable lexsort restores daggregate's ascending group order
        fact = _factorize_keys([np.asarray(merged.columns[k])
                                for k in keys])
        order = fact.order
        cols = {n: (merged.columns[n][order]
                    if isinstance(merged.columns[n], np.ndarray)
                    else [merged.columns[n][i] for i in order])
                for n in out_schema.names}
        counters.inc("mesh.shuffle_daggregates")
        return TensorFrame.from_blocks(
            [Block(cols, merged.num_rows)], out_schema)
