"""The perf harness must stay runnable (the reference's suites rotted to
``ignore``; ours are exercised at light scale in CI). Heavy runs are
opt-in: ``python -m benchmarks.run_all``."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import baseline_configs, e2e_bench, marshal_bench


def test_marshal_bench_light():
    recs = marshal_bench.run(n_scalar=20_000, n_vector=20_000, iters=1)
    metrics = {r["metric"] for r in recs}
    assert metrics == {"convert_scalar_rows", "convertBack_scalar_rows",
                       "convert_1row_vector", "convertBack_1row_vector"}
    assert all(r["value"] > 0 for r in recs)


def test_e2e_bench_light():
    recs = e2e_bench.run(n_rows=50_000, iters=1)
    assert {r["metric"] for r in recs} == {"e2e_map_agg_host",
                                           "e2e_map_agg_device"}


def test_baseline_light_configs():
    recs = baseline_configs.run(heavy=False)
    assert {r["metric"] for r in recs} == {
        "readme_x_plus_3", "reduce_sum_min_vector", "dsl_map_blocks_1m"}


@pytest.mark.slow
def test_heavy_configs_smoke():
    r4 = baseline_configs.config4_resnet_inference(batch=2, image=64,
                                                   iters=1)
    assert r4["images_per_s"] > 0
    r5 = baseline_configs.config5_logreg_step(n=4096, d=8)
    assert r5["rows_per_s"] > 0


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CPU_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def test_daggregate_bench_light():
    # keeps the keyed-aggregation bench runnable (host + device key paths)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "daggregate_bench.py"),
         "20000", "500"],
        capture_output=True, text=True, timeout=300, env=_CPU_ENV)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    metrics = {r["metric"].split("x", 1)[1].split("_", 1)[1]
               for r in lines}
    assert metrics == {"host_keys", "host_keys_warm", "device_keys",
                       "device_keys_warm", "multikey_device"}, metrics


def test_tpu_pallas_smoke_fails_gracefully_off_chip():
    # chip-only kernel smoke: off-TPU it must exit 1 with a JSON reason,
    # not crash
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "tpu_pallas_smoke.py")],
        capture_output=True, text=True, timeout=240, env=_CPU_ENV)
    out = proc.stdout.strip().splitlines()
    assert out and json.loads(out[-1]).get("ok") is False
    assert proc.returncode == 1
