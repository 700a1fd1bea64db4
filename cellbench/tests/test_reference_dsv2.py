"""The DeepSeek-V2-Lite cell on the CPU at a tiny size: its reference
against the program, its control, a rehearsal of the cell, and its
metric readers on a synthetic trace."""

import copy
import importlib.util
import os
import time
from types import SimpleNamespace

import numpy as np
import pytest

from cellbench import harness

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "dsv2lite.score_s4096"
# the published structure at widths a CPU test holds
TINY = dict(hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
            n_routed_experts=8, num_experts_per_tok=3, n_shared_experts=2,
            intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=3, vocab_size=256)


def tiny_cell() -> harness.Cell:
    cell = harness.Cell(CELL)
    cell.config = dict(copy.deepcopy(cell.config), **TINY)
    cell.mix = copy.deepcopy(cell.mix)
    cell.mix["request"].update(rows=8, seq_len=64, block=2)
    return cell


def reference():
    spec = importlib.util.spec_from_file_location(
        "ref_dsv2", os.path.join(HERE, "reference", "deepseek_v2_lite_9l.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_reference_matches_program_in_float32():
    import jax

    from tensorframes_tpu.models.deepseek_v2 import (DeepSeekV2,
                                                     DeepSeekV2Config)

    from cellbench.systems.llm_score import build_params, token_rows

    cfg = dict(tiny_cell().config, torch_dtype="float32")
    params = build_params(cfg, 9)
    tokens = token_rows(cfg, {"rows": 2, "seq_len": 64, "zipf_s": 1.0}, 9)
    want, stats = reference().forward(cfg, params, tokens)
    got, counts = jax.jit(DeepSeekV2(DeepSeekV2Config.from_dict(cfg)).apply)(
        params, tokens)
    assert want.shape == (2, 63)
    # float32 on both sides: summation order only
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-4)
    assert np.asarray(counts).sum() == 2 * 64 * 3 * 2
    assert set(stats) == {"logit_std", "router_top1",
                          "router_near_tie_share"}


def test_fp8_control_fails_the_limits():
    cell = tiny_cell()
    system = cell.system(5)
    from cellbench.systems.llm_score import build_params, token_rows

    system.params = build_params(cell.config, 5)
    system.tokens = token_rows(cell.config, cell.mix["request"], 5)
    checks = system.control(cell.limits, "float8_e4m3fn")
    assert not harness.judge(harness.compared(checks)), checks


@pytest.mark.usefixtures("no_chip_check")
def test_cell_runs_and_is_correct():
    cell = tiny_cell()
    res = harness.run_cell(cell, seed=2**31 + 12345, seconds=1.0, trace=False,
                           t_start=time.monotonic())
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["compiles_in_window"] == 0
    assert set(res["metrics"]) == {"rows_per_s", "setup_s"}


def test_token_flops_is_the_published_arithmetic():
    from cellbench.costs_llm import routed_flops, token_flops

    cfg = harness.Cell(CELL).config
    f = token_flops(cfg, 4096)
    # 2.10 GFLOP a token: routed experts 39.6% of it, the head 20.0%
    assert f == pytest.approx(2.0998e9, rel=1e-4)
    assert 8 * routed_flops(cfg) / f == pytest.approx(0.3955, abs=1e-3)
    assert 2 * 2048 * 102400 / f == pytest.approx(0.1997, abs=1e-3)


def traced_run(ops):
    cell = harness.Cell(CELL)
    trace = {"ops": ops, "chips": 1, "window_s": 10.0, "busy_s": 9.9}
    return SimpleNamespace(cell=cell, trace=trace, device_kind="TPU v5 lite",
                           ok=[{"units": 16}, {"units": 16}])


def metric(name):
    return harness.load_module(harness.metric_file(name),
                               "cellbench_metric_" + name.replace(".", "_"))


def test_llm_mfu_counts_every_token_of_the_window():
    run = traced_run({"moe_gmm.1": 1.0})
    # 32 rows x 4,096 tokens x 2.0998 GFLOP over 10 s at 197 TFLOP/s
    assert metric("llm_mfu.score").read(run) == pytest.approx(
        100 * 32 * 4096 * 2.0998e9 / (10 * 197e12), rel=1e-4)


def test_moe_roofline_reads_the_kernels_time():
    mod = metric("moe_roofline.score")
    assert mod.read(traced_run({"fusion.3": 1.0})) is None
    one = mod.read(traced_run({"moe_gmm": 0.5, "moe_gmm.7": 0.5}))
    two = mod.read(traced_run({"moe_gmm": 2.0}))
    assert one == pytest.approx(2 * two) and 0 < one < 100
