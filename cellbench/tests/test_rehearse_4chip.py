"""The four-chip cell end to end on 4 virtual CPU devices at a small size
(the chip check skipped), and its per-layer readers on synthetic traces
and span records."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np

from cellbench import harness

CELL = "h2o1e9.q5_k10m_4chip"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# one process with 4 CPU devices: the test's own process may have made its
# JAX backend already, with another device count
SCRIPT = """
import json, time
import jax
from cellbench import harness
cell = harness.Cell(%r)
cfg, req = cell.config, cell.mix["request"]
cfg["rows"] = 100_000
for c in cfg["columns"]:
    if c["name"] == "id6":
        c["high"] = cfg["rows"] // cfg["K"]
req["max_groups"] = cfg["rows"] // cfg["K"]
harness.devices = lambda chips: jax.devices()[:chips]
res = harness.run_cell(cell, seed=2**31 + 12345, seconds=1.0, trace=False,
                       t_start=time.monotonic())
print(json.dumps(res))
""" % CELL


def test_cell_runs_and_is_correct_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=ROOT)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["compiles_in_window"] == 0
    assert res["device"] == {"platform": "cpu", "kind": "cpu", "count": 4,
                             "memory_peak_bytes": 0}
    assert set(res["metrics"]) == {"rows_per_s", "setup_s"}
    assert set(res["checks"]) == {"groups_mismatch", "int_sums_unequal",
                                  "float_sum_rel_err"}


def reader(name):
    return harness.load_module(harness.metric_file(name),
                               "cellbench_metric_" + name.replace(".", "_"))


def traced_run(ops, modules, queries=2):
    cell = harness.Cell(CELL)
    trace = {"chips": 4, "window_s": 10.0, "busy_s": 9.5, "ops": ops,
             "modules": modules}
    ok = [{"ok": True, "units": 1e9, "groups": 10_000_000}] * queries
    return SimpleNamespace(cell=cell, trace=trace, ok=ok,
                           device_kind="TPU v5 lite")


def test_shard_roofline_counts_one_chip():
    # each chip: 2.5e8 rows x 16 B + 1e7 groups x 12 B a query
    per_chip = 2.5e8 * 16 + 1e7 * 12
    run = traced_run({}, {"jit_daggregate_shard_fn": 4 * 5.0,
                          "jit_other": 7.0})
    want = 100 * 2 * per_chip / 819e9 / 5.0
    assert abs(reader("shard_roofline.agg4").read(run) - want) < 1e-9
    assert reader("shard_roofline.agg4").read(traced_run({}, {})) is None


def test_collective_share_sums_collectives_over_chips():
    run = traced_run({"all-reduce-start.1": 0.2, "all-reduce-done.1": 0.1,
                      "all-gather.3": 0.1, "fusion.2": 30.0}, {})
    assert abs(reader("collective_share.agg4").read(run)
               - 100 * 0.4 / 40.0) < 1e-12
    assert reader("collective_share.agg4").read(
        traced_run({"fusion": 1.0}, {})) is None


def test_collect_share_unions_collect_spans(monkeypatch):
    from tensorframes_tpu.utils import tracing

    ms = 1_000_000

    def rec(name, s, e):
        return SimpleNamespace(name=name, start_ns=s * ms, end_ns=e * ms)

    recs = [rec("daggregate.collect", 900, 1100),
            rec("daggregate.collect", 1050, 1300),
            rec("daggregate.dispatch", 1300, 1400),
            rec("daggregate.collect", 2500, 2600)]
    monkeypatch.setattr(tracing, "span_records",
                        lambda since_ns=None: list(recs))
    win = SimpleNamespace(t0=1.0, t_end=3.0)
    run = SimpleNamespace(window=win, window_s=2.0)
    # [1000, 1300] and [2500, 2600]: 400 ms of a 2-s window
    assert abs(reader("collect_share.agg4").read(run) - 20.0) < 1e-9
    recs[:] = [rec("daggregate.dispatch", 1300, 1400)]
    assert reader("collect_share.agg4").read(run) is None


def test_threaded_reference_equals_the_1e8_reference():
    ref9 = harness.load_module(os.path.join(
        ROOT, "cellbench", "reference", "h2o_groupby_g1e9_k100.py"), "r9")
    ref8 = harness.load_module(os.path.join(
        ROOT, "cellbench", "reference", "h2o_groupby_g1e8_k100.py"), "r8")
    rng = np.random.default_rng(4)
    n = 200_003
    cols = {"k": rng.integers(1, 5_000, n).astype("int32"),
            "v1": rng.integers(1, 6, n).astype("int32"),
            "v3": rng.random(n) * 100}
    agg = {"v1": "sum", "v3": "sum"}
    got, want = ref9.groupby(cols, ["k"], agg), ref8.groupby(cols, ["k"], agg)
    assert (got["k"] == want["k"]).all() and (got["v1"] == want["v1"]).all()
    assert got["v1"].dtype == want["v1"].dtype == "int64"
    assert abs(got["v3"] - want["v3"]).max() <= 1e-12 * abs(want["v3"]).max()
