"""Pipeline parallelism: GPipe-style stage execution over a mesh axis.

The reference has nothing like this (Spark partitions are embarrassingly
parallel); it exists because the multi-chip design makes pipeline a
first-class mesh axis. The implementation is the canonical TPU pattern
(the scaling-book recipe): stage parameters are stacked on a leading
``[P, ...]`` dim sharded over the ``pipe`` axis, and ``shard_map`` runs the
schedule — a ``lax.scan`` over ``M + P - 1`` ticks in which every device
applies its stage to the activation it holds and ``lax.ppermute`` rotates
activations one hop down the ICI ring. Microbatch ``m`` is picked up by
stage 0 at tick ``m`` and emitted by stage ``P-1`` at tick ``m + P - 1``;
in between, all stages work on different microbatches in flight (the
steady-state of the GPipe schedule — the ``P-1`` warmup/cooldown ticks are
the bubble). The whole schedule is one compiled program, differentiable
end-to-end (``ppermute`` transposes to the reverse rotation, so backprop
pipelines in the opposite direction automatically).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .mesh import DeviceMesh
from .ring import _varying

__all__ = ["pipeline_apply"]


def pipeline_apply(stage_fn: Callable, stacked_params, x: jax.Array,
                   mesh: DeviceMesh, pipe_axis: str = "pipe",
                   num_microbatches: int = None,
                   data_axis: str = None) -> jax.Array:
    """Run ``x`` through ``P`` pipeline stages over ``pipe_axis``.

    - ``stage_fn(params_for_one_stage, act) -> act`` — one stage's compute;
      activations must keep one shape throughout (the usual transformer
      block contract).
    - ``stacked_params``: pytree whose leaves have leading dim ``P``
      (stage-major). The caller shards them over ``pipe_axis``; inside the
      shard each device sees leading dim 1 — its own stage.
    - ``x``: [B, ...] batch; split into ``num_microbatches`` (default P)
      equal microbatches along dim 0.
    - ``data_axis``: when given, the per-microbatch row dim stays sharded
      over it through the pipeline (dp x pp composition); otherwise rows
      are replicated across the data axis inside the schedule.

    Returns the full batch output.
    """
    pipe_size = mesh.mesh.shape[pipe_axis]
    M = num_microbatches or pipe_size
    B = x.shape[0]
    if B % M:
        raise ValueError(f"Batch {B} not divisible into {M} microbatches")
    mb = B // M
    xs = x.reshape((M, mb) + x.shape[1:])

    row_spec = P(None, data_axis, *([None] * (x.ndim - 1)))
    in_specs = (
        jax.tree_util.tree_map(lambda _: P(pipe_axis), stacked_params,
                               is_leaf=lambda l: l is None),
        row_spec,  # stage 0 consumes microbatches; rows stay data-sharded
    )
    # Each device returns ITS outs buffer under a leading pipe-sharded dim;
    # only the last stage's slice holds real data and the caller reads just
    # that — no collective inside the schedule (a psum here would move the
    # full zero buffer of every non-final stage across the ring every call).
    out_specs = P(pipe_axis, None, data_axis, *([None] * (x.ndim - 1)))

    def shard_fn(params, xs_rep):
        p = jax.lax.axis_index(pipe_axis)
        params1 = jax.tree_util.tree_map(lambda a: a[0], params)
        ticks = M + pipe_size - 1
        perm = [(i, (i + 1) % pipe_size) for i in range(pipe_size)]

        def tick(carry, t):
            buf, outs = carry
            # stage 0 picks up microbatch t (clamped; masked when t >= M)
            fresh = xs_rep[jnp.minimum(t, M - 1)]
            inp = jnp.where(p == 0, fresh, buf)
            act = stage_fn(params1, inp)
            # last stage emits microbatch t - (P-1) when it is valid
            # (where, not lax.cond: branches must agree on shard_map's
            # varying-axis types, and an unconditional masked update does)
            m_idx = t - (pipe_size - 1)
            valid = jnp.logical_and(p == pipe_size - 1, m_idx >= 0)
            updated = jax.lax.dynamic_update_index_in_dim(
                outs, act, jnp.clip(m_idx, 0, M - 1), 0)
            outs = jnp.where(valid, updated, outs)
            nxt = jax.lax.ppermute(act, pipe_axis, perm)
            return (nxt, outs), None

        # the carries become device-varying inside the loop (they depend on
        # axis_index); their initial values must be typed varying too
        buf0 = _varying(jnp.zeros_like(xs_rep[0]), pipe_axis, data_axis)
        outs0 = _varying(jnp.zeros_like(xs_rep), pipe_axis, data_axis)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                    jnp.arange(ticks))
        # outs is populated only on the last stage (zeros elsewhere);
        # return it under a leading size-1 dim that the out_spec shards
        # over the pipe axis — the caller slices stage P-1's entry.
        return outs[None]

    fn = shard_map(shard_fn, mesh=mesh.mesh,
                   in_specs=in_specs, out_specs=out_specs)
    out = fn(stacked_params, xs)[pipe_size - 1]
    return out.reshape((B,) + out.shape[2:])
