"""Ring collectives: sequence-parallel (ring) attention and ring allreduce.

Long-context support is first-class in this framework even though the
reference predates attention entirely (SURVEY.md §5 notes only that the
mesh/collective layer must not preclude it). Both primitives run inside
``shard_map`` over a mesh axis and move data with ``jax.lax.ppermute`` —
neighbor hops that ride the ICI ring, never materializing the full sequence
(or the full gradient) on one chip.

``ring_attention`` shards the sequence dimension of q/k/v across the axis
and rotates k/v blocks around the ring, maintaining flash-attention-style
online softmax statistics (running max ``m``, normalizer ``l``, accumulator
``o``), so each chip holds only S/n of the sequence at any time. Supports
causal masking via global position indices.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from ..utils import compat as _compat
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .mesh import DeviceMesh
from ..observability.events import current_trace as _current_trace

__all__ = ["ring_attention", "ring_allreduce"]


def _traced_ring_dispatch(kind: str, fn, args, axis: str, devices: int,
                          hops: int):
    """Dispatch a ring program, recording a ``collective`` event on the
    active query trace (host-timed through readiness — tracing ON pays a
    barrier; the untraced path keeps jax's async dispatch untouched).
    Inputs that are tracers (the caller is itself under jit) skip the
    timing: there is no host-visible dispatch to measure there.
    """
    trace = _current_trace()
    if trace is None:
        return fn(*args)
    tracer_t = getattr(jax.core, "Tracer", ())
    if tracer_t and any(isinstance(a, tracer_t) for a in args):
        return fn(*args)
    t0 = trace.clock()
    out = fn(*args)
    jax.block_until_ready(out)
    trace.add("collective", name=kind, ts=t0,
              dur=max(trace.clock() - t0, 0.0), axis=axis,
              devices=devices, hops=hops)
    return out


def _varying(a, *axes: Optional[str]):
    """Type a fresh constant as device-varying over ``axes`` so it can seed
    a loop carry that becomes varying (shard_map's varying-manual-axes
    checker rejects unvarying→varying carries; the cast is free). ``None``
    axes and axes ``a`` already varies over are skipped (pcast rejects
    both). A carry must be cast over EVERY axis its updates vary on — e.g.
    ring attention's (m, l, o) vary over the batch/head axes too as soon
    as they combine with the sharded q block."""
    have = _compat.vma_of(a)
    need = tuple(ax for ax in axes if ax is not None and ax not in have)
    if not need:
        return a
    return jax.lax.pcast(a, need, to="varying")


def _local_attn_update(q, k, v, m, l, o, scale, mask):
    """One flash-attention block update with blockwise softmax rescaling.

    q: [B, Sq, H, D]; k/v: [B, Sk, H, D]; m/l: [B, H, Sq]; o like q.
    ``mask``: [Sq, Sk] boolean or None.
    """
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is not None:
        scores = jnp.where(mask[None, None, :, :], scores, -jnp.inf)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    # guard fully-masked rows: keep m finite so exp() stays 0, not NaN
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(scores - m_safe[..., None])
    if mask is not None:
        p = jnp.where(mask[None, None, :, :], p, 0.0)
    alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
    l_new = alpha * l + p.sum(axis=-1)
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + \
        jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return m_new, l_new, o_new


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mesh: DeviceMesh, seq_axis: Optional[str] = None,
                   causal: bool = False,
                   batch_axis: Optional[str] = None,
                   head_axis: Optional[str] = None) -> jax.Array:
    """Exact attention over a sequence sharded across a mesh axis.

    q/k/v: [batch, seq, heads, head_dim], seq row-sharded over ``seq_axis``
    (defaults to the mesh's data axis). Returns the attention output with
    the same sharding. Each ring step computes one local q-block/k-block
    interaction and ppermutes k/v one hop; softmax is exact via online
    (m, l, o) accumulation. Peak per-chip memory is O(S/n), enabling
    sequences n times longer than single-chip attention.

    ``batch_axis``/``head_axis`` additionally shard the batch and head dims
    (data/tensor parallelism composed with the sequence ring): attention is
    independent across batch and heads, so those axes never communicate —
    only k/v hop the ring over ``seq_axis``.
    """
    axis = seq_axis or mesh.data_axis
    n = mesh.mesh.shape[axis]
    # python float (weak type) so f32/bf16 inputs are not promoted
    scale = float(1.0 / np.sqrt(q.shape[-1]))
    perm = [(i, (i + 1) % n) for i in range(n)]

    def shard_fn(q_blk, k_blk, v_blk):
        B, S, H, D = q_blk.shape
        my = jax.lax.axis_index(axis)
        q_pos = my * S + jnp.arange(S)

        # carries combine with the sharded q block, so they vary over the
        # batch/head axes too when those are set — cast over all of them
        m0 = _varying(jnp.full((B, H, S), -jnp.inf, q_blk.dtype),
                      axis, batch_axis, head_axis)
        l0 = _varying(jnp.zeros((B, H, S), q_blk.dtype),
                      axis, batch_axis, head_axis)
        o0 = _varying(jnp.zeros_like(q_blk), axis, batch_axis, head_axis)

        def step(i, carry):
            k_cur, v_cur, m, l, o = carry
            # the k/v block now resident arrived from `i` hops upstream
            src = (my - i) % n
            k_pos = src * S + jnp.arange(S)
            mask = (q_pos[:, None] >= k_pos[None, :]) if causal else None
            m, l, o = _local_attn_update(q_blk, k_cur, v_cur, m, l, o,
                                         scale, mask)
            k_nxt = jax.lax.ppermute(k_cur, axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis, perm)
            return (k_nxt, v_nxt, m, l, o)

        k_f, v_f, m, l, o = jax.lax.fori_loop(
            0, n, step, (k_blk, v_blk, m0, l0, o0))
        l_safe = jnp.where(l == 0.0, 1.0, l)
        return o / l_safe.transpose(0, 2, 1)[..., None]

    spec = P(batch_axis, axis, head_axis, None)
    fn = shard_map(shard_fn, mesh=mesh.mesh,
                   in_specs=(spec, spec, spec), out_specs=spec)
    # n ring steps, each ppermuting k AND v one hop
    return _traced_ring_dispatch("ring_attention", fn, (q, k, v), axis,
                                 n, hops=2 * n)


def ring_allreduce(x: jax.Array, mesh: DeviceMesh,
                   axis: Optional[str] = None) -> jax.Array:
    """Bandwidth-optimal allreduce built from ppermute hops.

    ``x`` has shape [n, ...] with the leading dim sharded over the axis —
    one local value per device. Returns the same shape where every slice is
    the full sum. The classic schedule: reduce-scatter then all-gather,
    2(n-1) neighbor hops each moving 1/n of the payload. XLA's ``psum`` is
    normally what you want; this exists as the explicit-ICI-schedule
    primitive and benchmark baseline.
    """
    ax = axis or mesh.data_axis
    n = mesh.mesh.shape[ax]
    if n == 1:
        return x
    if x.shape[0] != n:
        raise ValueError(
            f"ring_allreduce expects leading dim == axis size {n}, got "
            f"{x.shape[0]}")
    fwd = [(i, (i + 1) % n) for i in range(n)]

    def shard_fn(blk):
        # blk: [1, ...] — this device's local value
        me = jax.lax.axis_index(ax)
        flat = blk.reshape(-1)
        pad = (-flat.shape[0]) % n
        if pad:
            flat = jnp.pad(flat, (0, pad))
        chunks = flat.reshape(n, -1)

        # reduce-scatter: at step s, send the partially-reduced chunk
        # (me - s) and fold the arriving chunk (me - s - 1) into our local
        # copy; after n-1 steps this device owns fully-reduced chunk me+1.
        buf = jnp.take(chunks, me % n, axis=0)
        for s in range(n - 1):
            buf = jax.lax.ppermute(buf, ax, fwd)
            buf = buf + jnp.take(chunks, (me - s - 1) % n, axis=0)
        owned = (me + 1) % n

        # all-gather: rotate each fully-reduced chunk around the ring
        out = _varying(jnp.zeros_like(chunks), ax)
        cur, idx = buf, owned
        out = out.at[idx].set(cur)
        for _ in range(n - 1):
            cur = jax.lax.ppermute(cur, ax, fwd)
            # node i-1 owned chunk i, so each arrival is one index lower
            idx = (idx - 1) % n
            out = out.at[idx].set(cur)
        full = out.reshape(-1)
        if pad:
            full = full[:-pad]
        return full.reshape(blk.shape)

    fn = shard_map(shard_fn, mesh=mesh.mesh,
                   in_specs=P(ax), out_specs=P(ax))
    # reduce-scatter + all-gather: 2(n-1) neighbor hops
    return _traced_ring_dispatch("ring_allreduce", fn, (x,), ax, n,
                                 hops=2 * (n - 1))
