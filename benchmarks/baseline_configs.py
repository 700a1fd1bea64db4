"""The five BASELINE.json target configs, measured end to end.

1. README "x + 3" map_blocks on a 10-row double frame (latency config —
   measures per-call overhead, reference ``README.md:56-87``);
2. reduce_sum / reduce_min over a vector column after ``analyze``
   (``README.md:92-124``);
3. DSL mapBlocks add-constant on a 1M-row frame (``README.md:154-172``) —
   also the headline ``bench.py`` metric;
4. ResNet-50 batch inference over an image-tensor column via map_blocks;
5. logistic-regression gradient step: per-block grads via map_blocks +
   reduce_blocks allreduce, with the mesh path when >1 device is visible.

Each returns rows/sec (or steps/sec) plus wall seconds.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

import tensorframes_tpu as tft
from tensorframes_tpu import dsl
from tensorframes_tpu.engine import ops as engine_ops

ITERS = 10


def _timed(fn, iters=ITERS):
    fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        r = fn()
    return (time.perf_counter() - t0) / iters, r


def config1_readme_x_plus_3() -> Dict:
    df = tft.frame([(float(i),) for i in range(10)], columns=["x"])
    df.cache()

    def go():
        return tft.map_blocks(lambda x: {"z": x + 3.0}, df).collect()

    sec, rows = _timed(go)
    assert [r["z"] for r in rows] == [i + 3.0 for i in range(10)]
    return {"metric": "readme_x_plus_3", "value": sec, "unit": "s/call",
            "rows": 10}


def config2_reduce_vector(n: int = 100_000, width: int = 16) -> Dict:
    import jax.numpy as jnp

    data = np.random.default_rng(0).normal(size=(n, width))
    df = tft.analyze(tft.frame({"x": data}, num_partitions=4))
    df.cache()

    def go():
        s = engine_ops.reduce_blocks(
            lambda x_input: {"x": x_input.sum(0)}, df)
        m = engine_ops.reduce_rows(
            lambda x_1, x_2: {"x": jnp.minimum(x_1, x_2)}, df)
        return s, m

    sec, (s, m) = _timed(go)
    np.testing.assert_allclose(s["x"], data.sum(0), rtol=1e-3)
    np.testing.assert_allclose(m["x"], data.min(0), rtol=1e-5)
    return {"metric": "reduce_sum_min_vector", "value": sec,
            "unit": "s/call", "rows": n, "rows_per_s": n / sec}


def config3_dsl_map(n: int = 1_000_000) -> Dict:
    df = tft.frame({"x": np.arange(n, dtype=np.float64)})
    df.cache()

    def go():
        with dsl.with_graph():
            x = tft.block(df, "x")
            z = (x + 3.0).named("z")
            out = tft.map_blocks(z, df, trim=True)
            out.blocks()
        return out

    sec, _ = _timed(go)
    return {"metric": "dsl_map_blocks_1m", "value": sec, "unit": "s/call",
            "rows": n, "rows_per_s": n / sec}


def config4_resnet_inference(batch: int = 32, image: int = 224,
                             iters: int = 3) -> Dict:
    """Frozen-model batch inference over an image-tensor column."""
    from tensorframes_tpu.models.resnet import ResNet50

    model = ResNet50(num_classes=1000)
    params = model.init()
    imgs = np.random.default_rng(1).normal(
        size=(batch, image, image, 3)).astype(np.float32)
    df = tft.analyze(tft.frame({"image": imgs}))
    df.cache()

    def go():
        out = model.infer_via_frame(params, df, image_col="image")
        return out.blocks()

    sec, blocks = _timed(go, iters)
    assert blocks[0].dense("logits").shape == (batch, 1000)
    return {"metric": "resnet50_infer", "value": sec, "unit": "s/batch",
            "images": batch, "images_per_s": batch / sec}


def config5_logreg_step(n: int = 262_144, d: int = 64) -> Dict:
    """One SGD step: map_blocks per-block grads + reduce_blocks combine;
    the v5e-8 config of BASELINE.json runs the same step over the mesh."""
    from tensorframes_tpu.models.logreg import LogisticRegression

    rng = np.random.default_rng(2)
    w_true = rng.normal(size=d)
    x = rng.normal(size=(n, d))
    y = (x @ w_true + rng.normal(0, 0.1, n) > 0).astype(np.float64)
    df = tft.analyze(tft.frame({"features": x, "label": y},
                               num_partitions=8))
    df.cache()
    model = LogisticRegression(num_features=d)
    params = model.init()

    def go():
        return model.gradient_via_frame(params, df)

    sec, grads = _timed(go, 5)
    return {"metric": "logreg_grad_step", "value": sec, "unit": "s/step",
            "rows": n, "rows_per_s": n / sec}


def run(heavy: bool = True) -> List[Dict]:
    out = [config1_readme_x_plus_3(), config2_reduce_vector(),
           config3_dsl_map()]
    if heavy:
        out.append(config4_resnet_inference())
        out.append(config5_logreg_step())
    return out


if __name__ == "__main__":
    import json

    for rec in run():
        print(json.dumps(rec))
