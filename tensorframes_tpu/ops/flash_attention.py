"""Flash attention as a Pallas TPU kernel.

Exact softmax attention computed blockwise so the [Sq, Sk] score matrix is
never materialized in HBM: for each (batch*head, q-block) the kernel sweeps
k-blocks, maintaining the online-softmax statistics (running max ``m``,
normalizer ``l``, unnormalized accumulator ``acc``) in VMEM scratch, and
writes the normalized output once at the last k-step. Matmuls hit the MXU in
f32 accumulation regardless of the input dtype (bf16 in, f32 acc, input
dtype out); the softmax statistics are f32, and the probabilities meet ``v``
in its dtype. ``v`` may be narrower or wider than ``q``/``k`` (latent
attention scores over 192 dims and returns 128).

This is the single-device kernel; sequence parallelism composes *around* it:
:func:`~tensorframes_tpu.parallel.ring.ring_attention` rotates k/v shards
over the ICI ring and uses the same online-softmax update per local block
pair.

The ``impl="xla"`` path is the semantic reference (plain jnp softmax
attention); CPU tests run the Pallas kernel with ``interpret=True``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_mesh import interpret_blocked_by_vma, vma_union

__all__ = ["flash_attention"]

_LANES = 128  # VMEM lane width: m/l scratch keeps stats broadcast over lanes

_NEG_INF = -1e30  # large-negative, not -inf: keeps fully-masked rows NaN-free


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, block_q: int, block_k: int,
            sk_valid: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _update():
        q = q_ref[0]  # [block_q, d]
        k = k_ref[0]  # [block_k, d]
        v = v_ref[0]  # [block_k, dv]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < sk_valid  # pad k rows contribute nothing
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        scores = jnp.where(mask, scores, _NEG_INF)

        m_prev = m_ref[:, 0]  # [bq]
        l_prev = l_ref[:, 0]
        m_cur = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)          # rescale of old stats
        p = jnp.exp(scores - m_new[:, None])     # [bq, bk]
        p = jnp.where(mask, p, 0.0)              # exp(-1e30-…) underflows, but be exact
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc_ref[:] = acc_ref[:] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    if causal:
        # skip k-blocks fully above the diagonal
        @pl.when(kj * block_k <= qi * block_q + (block_q - 1))
        def _():
            _update()
    else:
        _update()

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_ref[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe[:, None]).astype(o_ref.dtype)


def _pad_to(x, axis: int, multiple: int):
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pallas_attention(q, k, v, *, causal: bool, scale: float,
                      block_q: int, block_k: int, interpret: bool):
    bh, sq, d = q.shape
    sk, dv = k.shape[1], v.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    qp = _pad_to(q, 1, block_q)
    kp = _pad_to(k, 1, block_k)
    vp = _pad_to(v, 1, block_k)
    nq = qp.shape[1] // block_q
    nk = kp.shape[1] // block_k

    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             block_q=block_q, block_k=block_k, sk_valid=sk)
    out = pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (bh, qp.shape[1], dv), q.dtype,
            # shard_map(check_vma=True) requires declaring the mesh axes the
            # output varies over — the attention output varies like q/k/v
            vma=vma_union(q, k, v)),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # m
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # l
            pltpu.VMEM((block_q, dv), jnp.float32),      # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="flash_attention",          # the kernel's name in a trace
    )(qp, kp, vp)
    return out[:, :sq]


def _xla_attention(q, k, v, *, causal: bool, scale: float):
    scores = jnp.einsum("bqd,bkd->bqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = scores.shape[-2:]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        scores = jnp.where(mask, scores, _NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bqk,bkd->bqd", p, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    impl: Optional[str] = None) -> jax.Array:
    """Exact attention, ``[B, S, H, D]`` layout (matching the model zoo);
    ``v`` is ``[B, S, H, Dv]`` and so is the result.

    ``impl``: ``"pallas"`` (TPU kernel), ``"xla"`` (plain jnp reference),
    ``"interpret"`` (Pallas interpreter — CPU tests), or None to pick
    automatically (Pallas on TPU backends, XLA elsewhere).
    """
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "interpret" and interpret_blocked_by_vma(q, k, v):
        impl = "xla"  # see ops/_pallas_mesh.py: interpreter can't do vma
    b, sq, h, d = q.shape
    sk, dv = k.shape[1], v.shape[3]
    if scale is None:
        scale = float(1.0 / (d ** 0.5))
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, dv)
    if impl == "xla":
        o = _xla_attention(qf, kf, vf, causal=causal, scale=scale)
    elif impl in ("pallas", "interpret"):
        o = _pallas_attention(qf, kf, vf, causal=causal, scale=scale,
                              block_q=block_q, block_k=block_k,
                              interpret=(impl == "interpret"))
    else:
        raise ValueError(f"Unknown flash_attention impl {impl!r}")
    return o.reshape(b, h, sq, dv).transpose(0, 2, 1, 3)
