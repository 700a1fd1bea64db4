"""The jax symbols the package reaches through one place.

Pinned to the installed jax (0.9.0): no version probes. The StableHLO
artifact serializers are private to jax, so their import path lives here
only; ``vma_of`` is a small idiom several modules share.
"""

from __future__ import annotations

from typing import FrozenSet

import jax

__all__ = ["vma_of", "serialize_stablehlo_artifact",
           "deserialize_stablehlo_artifact"]


def vma_of(a) -> FrozenSet[str]:
    """The mesh axes ``a`` varies over (empty outside ``shard_map``)."""
    return frozenset(getattr(jax.typeof(a), "vma", None) or ())


def serialize_stablehlo_artifact(module, version) -> bytes:
    """MLIR text/bytecode → portable StableHLO artifact."""
    from jax._src.lib import _jax as _jaxlib
    return _jaxlib.mlir.serialize_portable_artifact(module, version)


def deserialize_stablehlo_artifact(bytecode: bytes):
    """Portable StableHLO artifact → MLIR text."""
    from jax._src.lib import _jax as _jaxlib
    return _jaxlib.mlir.deserialize_portable_artifact(bytecode)
