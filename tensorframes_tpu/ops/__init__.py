"""Hand-written TPU kernels (Pallas) for the framework's hot ops.

The reference's compute path bottoms out in whatever libtensorflow's C++
kernels do (SURVEY.md §2.2); here XLA covers the general case and this
package holds the ops worth hand-scheduling on the TPU's memory hierarchy:

- :func:`flash_attention` — blockwise attention with online softmax; the
  quadratic-memory score matrix never leaves VMEM.
- :func:`segment_sum` — keyed segment reduction via one-hot matmul on the
  MXU; the device-side core of ``aggregate`` and the k-means
  ``unsorted_segment_sum`` pattern.
- :func:`moe_gmm` — a mixture-of-experts layer's grouped matmul over
  token-expert pairs sorted by expert (:func:`tile_layout`).

``flash_attention`` and ``segment_sum`` have a pure-XLA fallback
(`impl="xla"`) that is the semantic reference; CPU tests run the Pallas
path in interpret mode, and ``moe_gmm`` runs in interpret mode off the
chip.
"""

from .flash_attention import flash_attention
from .moe_gmm import moe_gmm, tile_layout
from .segment_reduce import segment_sum

__all__ = ["flash_attention", "moe_gmm", "segment_sum", "tile_layout"]
