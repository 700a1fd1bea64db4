"""DeepSeek-V2 scoring (``models/deepseek_v2.py``) at a tiny size on the CPU.

The tiny preset keeps the published structure (latent attention with a
shared rope key, YaRN as published, a dense first layer, then expert
layers with shared experts) at widths a CPU test holds. The program runs
in float32 here, through ``score_via_frame`` with the weights bound, and is
compared with the plain reference of the benchmark cell
(``cellbench/reference/deepseek_v2_lite_9l.py``).
"""

import copy
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tensorframes_tpu as tft
from tensorframes_tpu.models.deepseek_v2 import (DeepSeekV2, DeepSeekV2Config,
                                                 _rope, moe_layer,
                                                 rope_frequencies,
                                                 softmax_scale, yarn_ramp)
from tensorframes_tpu.utils.tracing import counters

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "cellbench", "configs", "deepseek_v2_lite_9l.json")
REFERENCE = os.path.join(ROOT, "cellbench", "reference",
                         "deepseek_v2_lite_9l.py")
LIMITS = os.path.join(ROOT, "cellbench", "limits",
                      "dsv2lite.score_s4096.json")
TINY = dict(hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
            n_routed_experts=8, num_experts_per_tok=3, n_shared_experts=2,
            intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=3, vocab_size=256, torch_dtype="float32")
SEQ = 64


def published():
    with open(CONFIG) as f:
        return json.load(f)


def reference():
    """A fresh copy of the reference module (a test may patch it)."""
    spec = importlib.util.spec_from_file_location("dsv2_reference", REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny():
    """The tiny config, its seeded weights and token rows, the program's
    log-probs through the frame, and the reference's."""
    from cellbench.systems.llm_score import build_params, token_rows

    cfg = dict(published(), **TINY)
    params = build_params(cfg, 2**31 + 5)
    tokens = token_rows(cfg, {"rows": 4, "seq_len": SEQ, "zipf_s": 1.0}, 7)
    model = DeepSeekV2(DeepSeekV2Config.from_dict(cfg))
    out = model.score_via_frame(params, tft.frame({"tokens": tokens},
                                                  num_partitions=2))
    got = np.concatenate([b.dense("logprob") for b in out.blocks()])
    want, stats = reference().forward(cfg, params, tokens)
    return {"cfg": cfg, "params": params, "tokens": tokens, "got": got,
            "want": want, "stats": stats, "model": model}


def test_score_via_frame_matches_reference(tiny):
    got, want = tiny["got"], tiny["want"]
    assert got.shape == (4, SEQ - 1) and got.dtype == np.float32
    # both sides compute in float32 (the reference at "highest" matmul
    # precision); what is left is summation order over a 3-layer stack
    # and a 256-way log-softmax, of the order of 1e-6 nats
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4)
    # the seeded weights spread the logits as the configuration says
    assert 2.0 < tiny["stats"]["logit_std"] < 4.0


def test_score_counts_expert_layers_and_pairs():
    from cellbench.systems.llm_score import build_params

    cfg = dict(published(), **TINY)
    params = build_params(cfg, 3)
    model = DeepSeekV2(DeepSeekV2Config.from_dict(cfg))
    tokens = np.arange(3 * 16, dtype=np.int32).reshape(3, 16)
    d0 = counters.get("moe.dispatches")
    p0 = counters.get("moe.token_expert_pairs")
    b0 = counters.get("map_blocks.bound_dispatches")
    out = model.score_via_frame(params, tft.frame({"tokens": tokens},
                                                  num_partitions=3))
    assert out.schema.names == ["logprob"]
    assert sum(b.num_rows for b in out.blocks()) == 3
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    assert counters.get("moe.dispatches") - d0 == 3 * moe_layers
    assert counters.get("moe.token_expert_pairs") - p0 == \
        3 * 16 * cfg["num_experts_per_tok"] * moe_layers
    assert counters.get("map_blocks.bound_dispatches") - b0 == 3


def _expert_loop(cfg, p, x):
    """Per token, its top-k experts one at a time, in float64: the MoE
    layer as the published code's loop over experts computes it."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    x = np.asarray(x, np.float64)
    logits = x @ p["gate"]
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    k = cfg.num_experts_per_tok
    top = np.argsort(-probs, axis=-1, kind="stable")[:, :k]

    def swiglu(v, g, u, d):
        a = v @ g
        return (a / (1 + np.exp(-a)) * (v @ u)) @ d

    ex, sh = p["experts"], p["shared"]
    y = swiglu(x, sh["gate_proj"], sh["up_proj"], sh["down_proj"])
    for t in range(len(x)):
        for e in top[t]:
            y[t] += probs[t, e] * swiglu(x[t], ex["gate_proj"][e],
                                         ex["up_proj"][e], ex["down_proj"][e])
    return y, top


@pytest.mark.parametrize("routing", ["uneven", "one_expert_takes_all"])
def test_moe_layer_matches_expert_loop(routing):
    from cellbench.systems.llm_score import make_layer

    raw = dict(published(), **TINY)
    cfg = DeepSeekV2Config.from_dict(raw)
    p = make_layer(raw, jax.random.PRNGKey(11), False)["moe"]
    rng = np.random.default_rng(4)
    x = rng.standard_normal((40, cfg.hidden_size)).astype(np.float32)
    gate = np.array(p["gate"], np.float32)
    if routing == "uneven":
        # a feature most tokens have, read by expert 3: it takes most
        x[:, 0] = np.abs(x[:, 0]) + 1.0
        gate[0, 3] = 1.5
    else:
        # a feature every token has, read by experts 5, 2 and 0 only:
        # each token picks those three; expert 5 takes every token and
        # the other five experts none
        x[:, 0] = 8.0
        gate = np.zeros((cfg.hidden_size, cfg.n_routed_experts), np.float32)
        gate[0, [5, 2, 0]] = [3.0, 2.0, 1.0]
    p = dict(p, gate=jnp.asarray(gate))
    y, experts = moe_layer(cfg, p, jnp.asarray(x), tm=8)
    want, top = _expert_loop(cfg, p, x)
    counts = np.bincount(np.asarray(experts).ravel(),
                         minlength=cfg.n_routed_experts)
    # every token reached its k experts: nothing was dropped
    assert counts.sum() == len(x) * cfg.num_experts_per_tok
    np.testing.assert_array_equal(np.sort(np.asarray(experts), axis=1),
                                  np.sort(top, axis=1))
    if routing == "one_expert_takes_all":
        assert counts[5] == len(x) and (counts == 0).sum() == 5
    else:
        assert counts[3] == counts.max() > 2 * counts.min()
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())


def test_yarn_values_from_the_published_keys():
    cfg = DeepSeekV2Config.from_dict(published())
    assert yarn_ramp(cfg) == (10, 23)
    # q_head_dim 192 ** -0.5 times mscale(40, 0.707) ** 2
    assert softmax_scale(cfg) * math.sqrt(192) == pytest.approx(1.5896,
                                                                abs=1e-4)
    assert softmax_scale(cfg) == pytest.approx(0.11472, abs=1e-5)
    inv = rope_frequencies(cfg)
    orig = 1.0 / 10000 ** (np.arange(0, 64, 2) / 64)
    assert len(inv) == 32
    np.testing.assert_allclose(inv[:11], orig[:11], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], orig[23:] / 40, rtol=1e-12)
    between = inv[11:23] / orig[11:23]
    assert np.all((between < 1) & (between > 1 / 40))
    assert np.all(np.diff(between) < 0)


def test_rope_reads_interleaved_pairs():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, 5, 2, 8)).astype(np.float32)
    ang = rng.uniform(0, 3, (5, 4)).astype(np.float32)
    got = np.asarray(_rope(jnp.asarray(x), jnp.cos(ang), jnp.sin(ang)))
    # the published apply_rotary_pos_emb: view(d/2, 2), transpose, then
    # x * cat(cos, cos) + rotate_half(x) * cat(sin, sin)
    de = x.reshape(1, 5, 2, 4, 2).swapaxes(3, 4).reshape(1, 5, 2, 8)
    half = np.concatenate([-de[..., 4:], de[..., :4]], axis=-1)
    cos = np.concatenate([np.cos(ang)] * 2, -1)[None, :, None, :]
    sin = np.concatenate([np.sin(ang)] * 2, -1)[None, :, None, :]
    np.testing.assert_allclose(got, de * cos + half * sin, atol=1e-6)
    # a halves reading of the same numbers would be another rotation
    halves = x * cos + np.concatenate([-x[..., 4:], x[..., :4]], -1) * sin
    assert np.abs(got - halves).max() > 0.1


def _exceeds_limits(got, want):
    with open(LIMITS) as f:
        limits = json.load(f)
    from cellbench.systems.llm_score import compare

    return [k for k, (v, lim) in compare(want, got, limits).items()
            if lim is not None and v > lim]


def test_reference_without_shared_experts_fails_the_limits(tiny):
    params = copy.deepcopy(jax.tree_util.tree_map(np.asarray,
                                                  tiny["params"]))
    for lp in params["layers"][1:]:
        lp["moe"]["shared"] = jax.tree_util.tree_map(
            np.zeros_like, lp["moe"]["shared"])
    want, _ = reference().forward(tiny["cfg"], params, tiny["tokens"])
    assert _exceeds_limits(tiny["got"], want)


def test_reference_without_the_mscale_factor_fails_the_limits(tiny):
    ref = reference()
    ref.softmax_scale = lambda cfg: (cfg["qk_nope_head_dim"]
                                     + cfg["qk_rope_head_dim"]) ** -0.5
    want, _ = ref.forward(tiny["cfg"], tiny["params"], tiny["tokens"])
    assert _exceeds_limits(tiny["got"], want)


def test_config_refuses_q_compression():
    with pytest.raises(ValueError, match="q_lora_rank"):
        DeepSeekV2Config.from_dict(dict(published(), q_lora_rank=1536))
