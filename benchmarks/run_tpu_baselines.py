"""Per-config BASELINE runner for the chip: prints one JSON line per
config AS IT COMPLETES (a timeout loses only the configs after it, unlike
``run_all`` which buffers), and adds an MFU estimate for the MXU-heavy
configs using XLA's own cost model.

MFU convention: ``flops`` is XLA's ``cost_analysis()`` estimate for the
jitted program (analytic, pre-fusion), wall is the measured steady-state
iteration, peak is the chip's dense bf16 rate from ``benchmarks/peaks.py``
(an unknown device kind is an error). f32 matmuls execute on the MXU
through bf16-pass decomposition, so bf16 is the honest denominator.

Exits non-zero off a TPU. Usage:  python benchmarks/run_tpu_baselines.py
[1 2 3 4 5]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _emit(rec):
    print(json.dumps(rec), flush=True)


def _mfu(flops_per_iter: float, sec_per_iter: float) -> float:
    import jax

    from benchmarks.peaks import peak

    bf16 = peak(jax.devices()[0].device_kind)["bf16_flops"]
    return flops_per_iter / sec_per_iter / bf16


def _compile_with_flops(fn, *args):
    """Compile ``fn`` once; return (compiled executable, cost-model
    FLOPs). The compiled object serves both the cost analysis and the
    timed calls."""
    import jax

    comp = jax.jit(fn).lower(*args).compile()
    ca = comp.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return comp, float(ca.get("flops", 0.0)) if ca else 0.0


def _steady_state(compiled, *args, iters: int = 20):
    """Pipelined steady-state s/call of a pre-compiled executable on
    device-resident inputs: ``iters`` async dispatches, one
    ``block_until_ready`` at the end — sustained device throughput, the
    right wall for MFU, NOT single-call latency (configs report the
    end-to-end per-call figure separately). Inputs stay in HBM: no
    marshalling, re-trace, or re-compile in the loop.
    """
    import jax

    args = jax.device_put(args)
    jax.block_until_ready(compiled(*args))  # warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = compiled(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def config4_resnet_mfu(batch: int = 32, image: int = 224,
                       iters: int = 5):
    """ResNet-50 batch inference + MFU (BASELINE config 4).

    Two numbers: the via-frame end-to-end path (map_blocks + marshalling
    each call), and the device-resident steady-state apply — MFU uses the
    latter, which is what the chip itself sustains.
    """
    import jax
    import numpy as np

    import tensorframes_tpu as tft
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

    go()  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        blocks = go()
    sec = (time.perf_counter() - t0) / iters
    assert blocks[0].dense("logits").shape == (batch, 1000)

    params_dev = jax.device_put(params)
    compiled, flops = _compile_with_flops(model.apply, params_dev, imgs)
    dev_sec = _steady_state(compiled, params_dev, imgs)
    return {"metric": "resnet50_infer", "value": sec, "unit": "s/batch",
            "images": batch, "images_per_s": batch / sec,
            "platform": jax.default_backend(),
            "device_resident_s_per_batch": dev_sec,
            "device_resident_images_per_s": batch / dev_sec,
            "flops_per_batch": flops,
            "mfu": round(_mfu(flops, dev_sec), 4) if flops else None}


def config5_logreg_mfu(n: int = 262_144, d: int = 64, iters: int = 5):
    """Logreg gradient step + MFU (BASELINE config 5).

    Same two-number convention as config 4: via-frame end-to-end, plus
    device-resident steady-state grads (the MFU numerator's wall)."""
    import jax
    import numpy as np

    import tensorframes_tpu as tft
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

    go()
    t0 = time.perf_counter()
    for _ in range(iters):
        go()
    sec = (time.perf_counter() - t0) / iters

    xb = x.astype(np.float32)
    yb = y.astype(np.float32)
    rec = {"metric": "logreg_grad_step", "value": sec, "unit": "s/step",
           "rows": n, "rows_per_s": n / sec,
           "platform": jax.default_backend()}
    compiled, flops = _compile_with_flops(
        lambda p, xx, yy: model.grads(p, xx, yy), params, xb, yb)
    dev_sec = _steady_state(compiled, params, xb, yb)
    rec.update(
        device_resident_s_per_step=dev_sec,
        device_resident_rows_per_s=n / dev_sec,
        flops_per_step=flops,
        mfu=round(_mfu(flops, dev_sec), 6) if flops else None)
    return rec


def config2_with_device_resident(n: int = 100_000, width: int = 16):
    """Config 2 (reduce_sum/min) + the mesh collective-reduce rate.

    The base config times the full op path (build + marshal + reduce +
    collect) per call. The extra fields time the mesh reduce with the
    column already living in HBM — one compiled collective program per
    iteration, but each iteration still ends in the reduce contract's
    one-cell driver collect, so the figure includes one host round-trip
    (it is labelled ``collective_path_*``, not device-resident, for
    exactly that reason).
    """
    import jax
    import numpy as np

    import tensorframes_tpu as tft
    from benchmarks import baseline_configs as bc
    from tensorframes_tpu.parallel import distributed as par
    from tensorframes_tpu.parallel.mesh import local_mesh

    rec = bc.config2_reduce_vector(n, width)

    data = np.random.default_rng(0).normal(size=(n, width))
    df = tft.analyze(tft.frame({"x": data}, num_partitions=4))
    dist = par.distribute(df, local_mesh())

    def go():
        # the mapping form takes the monoid ICI-collective path (one
        # psum-tree shard_map program) — the BASELINE north-star path
        return par.dreduce_blocks({"x": "sum"}, dist)

    go()  # compile + warm
    iters = 10
    t0 = time.perf_counter()
    for _ in range(iters):
        out = go()
    dev_sec = (time.perf_counter() - t0) / iters
    np.testing.assert_allclose(out["x"], data.sum(0), rtol=1e-3)
    rec["collective_path_s_per_reduce"] = dev_sec
    rec["collective_path_rows_per_s"] = n / dev_sec
    return rec


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    which = [int(a) for a in argv] or [1, 2, 3, 4, 5]

    import jax

    from tensorframes_tpu.utils.platform import place_compile_cache

    plat = jax.default_backend()
    if plat != "tpu":
        print(f"run_tpu_baselines: no TPU (jax found {plat!r})",
              file=sys.stderr)
        return 1
    place_compile_cache()
    from benchmarks import baseline_configs as bc

    runners = {
        1: bc.config1_readme_x_plus_3,
        2: config2_with_device_resident,
        3: bc.config3_dsl_map,
        4: config4_resnet_mfu,
        5: config5_logreg_mfu,
    }
    rc = 0
    for i in which:
        try:
            rec = runners[i]()
            rec.setdefault("platform", plat)
            rec["config"] = i
            _emit(rec)
        except Exception as e:  # keep going; a failed config is a line too
            _emit({"config": i, "error": f"{type(e).__name__}: {e}"[:300],
                   "platform": plat})
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
