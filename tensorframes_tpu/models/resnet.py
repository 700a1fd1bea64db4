"""ResNet-50 batch inference over an image-tensor column (BASELINE config 4).

The reference's north star ("ResNet-50 frozen-graph batch inference over
image-tensor DataFrame column", ``BASELINE.json``) maps a frozen network over
blocks of rows — exactly ``map_blocks(trim=True)``, with the network's
parameters bound to the block program as an argument where the reference
would broadcast a frozen ``GraphDef``.

Pure-JAX implementation, NHWC layout (TPU-native: channels-last feeds the
MXU's 128-lane minor dimension), inference-mode batch norm folded to a
scale/bias affine at parameter-preparation time so each residual branch is
conv → affine → relu — a chain XLA fuses into the convolution.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ResNet50"]

Params = Dict[str, Any]

# Stage specification for ResNet-50: (blocks, bottleneck width)
_STAGES: Tuple[Tuple[int, int], ...] = ((3, 64), (4, 128), (6, 256), (3, 512))
_EXPANSION = 4


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _affine(x, p):
    # inference-mode batch norm, pre-folded to y = x*scale + bias
    return x * p["scale"] + p["bias"]


class ResNet50:
    """Frozen ResNet-50 classifier, ``[N, H, W, 3] -> [N, num_classes]``.

    ``init`` builds a randomly-initialized frozen parameter pytree (He-normal
    convs, identity affines); real weights can be loaded into the same tree
    layout. ``apply`` is a pure jit-friendly function.
    """

    def __init__(self, num_classes: int = 1000,
                 dtype: jnp.dtype = jnp.float32):
        self.num_classes = int(num_classes)
        self.dtype = dtype

    # -- parameters ---------------------------------------------------------
    def init(self, rng: Optional[jax.Array] = None) -> Params:
        if rng is None:
            rng = jax.random.PRNGKey(0)
        keys = iter(jax.random.split(rng, 64))

        def conv_p(kh, kw, cin, cout):
            fan_in = kh * kw * cin
            w = jax.random.normal(next(keys), (kh, kw, cin, cout),
                                  self.dtype)
            return w * np.sqrt(2.0 / fan_in).astype(np.float32)

        def affine_p(c):
            return {"scale": jnp.ones((c,), self.dtype),
                    "bias": jnp.zeros((c,), self.dtype)}

        params: Params = {
            "stem": {"conv": conv_p(7, 7, 3, 64), "bn": affine_p(64)},
            "stages": [],
        }
        cin = 64
        for stage_i, (blocks, width) in enumerate(_STAGES):
            stage: List[Params] = []
            cout = width * _EXPANSION
            for block_i in range(blocks):
                stride = 2 if (block_i == 0 and stage_i > 0) else 1
                blk: Params = {
                    "conv1": conv_p(1, 1, cin, width), "bn1": affine_p(width),
                    "conv2": conv_p(3, 3, width, width),
                    "bn2": affine_p(width),
                    "conv3": conv_p(1, 1, width, cout), "bn3": affine_p(cout),
                }
                if block_i == 0:
                    blk["proj"] = conv_p(1, 1, cin, cout)
                    blk["proj_bn"] = affine_p(cout)
                stage.append(blk)
                cin = cout
            params["stages"].append(stage)
        params["head"] = {
            "w": jax.random.normal(next(keys),
                                   (cin, self.num_classes),
                                   self.dtype) * 0.01,
            "b": jnp.zeros((self.num_classes,), self.dtype),
        }
        return params

    # -- forward ------------------------------------------------------------
    def _bottleneck(self, x, blk, stride):
        y = jax.nn.relu(_affine(_conv(x, blk["conv1"]), blk["bn1"]))
        y = jax.nn.relu(_affine(_conv(y, blk["conv2"], stride), blk["bn2"]))
        y = _affine(_conv(y, blk["conv3"]), blk["bn3"])
        if "proj" in blk:
            x = _affine(_conv(x, blk["proj"], stride), blk["proj_bn"])
        return jax.nn.relu(x + y)

    def apply(self, params: Params, images: jax.Array) -> jax.Array:
        """images: [N, H, W, 3] (NHWC) -> logits [N, num_classes]."""
        x = images.astype(self.dtype)
        x = jax.nn.relu(_affine(_conv(x, params["stem"]["conv"], 2),
                                params["stem"]["bn"]))
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                  (1, 2, 2, 1), "SAME")
        for stage_i, blocks in enumerate(params["stages"]):
            for block_i, blk in enumerate(blocks):
                stride = 2 if (block_i == 0 and stage_i > 0) else 1
                x = self._bottleneck(x, blk, stride)
        x = jnp.mean(x, axis=(1, 2))
        return x @ params["head"]["w"] + params["head"]["b"]

    # -- DataFrame formulation (the BASELINE workload) ----------------------
    def infer_via_frame(self, params: Params, df, image_col: str = "image",
                        trim: bool = True):
        """Batch inference through ``map_blocks``: the frozen parameters
        are bound to the block program as an argument, so every call on
        parameters of the same shapes runs one executable. Returns a lazy
        frame with a ``logits`` column."""
        apply = self.apply

        def fn_impl(params, **cols):
            return {"logits": apply(params, cols[image_col])}

        from .logreg import _frame_fn
        fn = _frame_fn(self, ("infer", image_col), fn_impl,
                       ["params", image_col])
        return df.map_blocks(fn, trim=trim, bind=params)
