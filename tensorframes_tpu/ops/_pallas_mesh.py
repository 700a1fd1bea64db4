"""Shared shard_map/vma plumbing for the Pallas kernels in this package.

Two facts every kernel here must honor when traced inside
``jax.shard_map(..., check_vma=True)``:

* ``pallas_call``'s ``out_shape`` must declare which mesh axes the output
  varies over (``jax.ShapeDtypeStruct(..., vma=...)``), or tracing fails
  with "`vma` ... must not be `None`" — for a per-shard kernel the output
  varies wherever any input does (:func:`vma_union`).
* jax's Pallas **HLO interpreter** cannot replay kernel bodies under vma
  tracking: block values carry varying mesh axes but jaxpr-internal iotas
  do not, so every mixed ``eq``/``add`` trips the checker. The Mosaic
  (real-TPU) path is unaffected — kernels trace with plain ref avals.
  Interpreted runs inside a mesh must therefore fall back to the kernel's
  XLA oracle (:func:`interpret_blocked_by_vma`), or run in Pallas's TPU
  interpreter (``interpret=pltpu.InterpretParams()``), which does replay
  them there: ``segment_reduce.py``'s window kernel, which has no oracle
  of its own, takes that one.

Any new Pallas kernel should route through both helpers; see
``segment_reduce.py`` / ``flash_attention.py`` for the pattern.
"""

from __future__ import annotations

from typing import FrozenSet

from ..utils.compat import vma_of

__all__ = ["vma_union", "interpret_blocked_by_vma"]


def vma_union(*arrays) -> FrozenSet[str]:
    """Union of the varying-mesh-axes of every input — the ``vma`` a
    per-shard kernel's ``out_shape`` must declare. Empty on jax builds
    without vma tracking (nothing to declare there)."""
    out: FrozenSet[str] = frozenset()
    for a in arrays:
        out = out | vma_of(a)
    return out


def interpret_blocked_by_vma(*arrays) -> bool:
    """True when an ``impl="interpret"`` run must use the XLA oracle
    instead: some input varies over a mesh axis, which the Pallas HLO
    interpreter cannot replay (see module docstring)."""
    return bool(vma_union(*arrays))
