"""Plain reference: DeepSeek-V2-Lite's forward pass as the log-probability
of each next token, in straightforward jax.numpy float32.

Written from the published ``modeling_deepseek.py`` (DeepSeek-V2,
arXiv:2405.04434) for ``q_lora_rank`` null; it imports nothing of the
program under test. Every matmul runs under
``jax.default_matmul_precision("highest")``. Per layer: RMSNorm; latent
attention (``q_proj``; ``kv_a_proj_with_mqa`` into the latent and one rope
key; ``kv_a_layernorm``; ``kv_b_proj`` into per-head ``k_nope`` and ``v``;
YaRN rope on interleaved pairs; softmax at ``q_head_dim ** -0.5 *
mscale(factor, mscale_all_dim) ** 2``; causal); RMSNorm; a SwiGLU on the
first ``first_k_dense_replace`` layers, else a float32 softmax router with
greedy top-k over every expert, each expert computed on every token and
weighted by its routing weight (zero where not chosen), plus the shared
experts' SwiGLU. Attention is computed for one row and one head at a
time, whole. Then RMSNorm, the head and a log-softmax over the vocabulary.

Departures: the rope's cos/sin stay float32 (the published code rounds
them to the model dtype).

Parameters are the program's pytree (``tensorframes_tpu.models.
deepseek_v2``'s layout, matrices ``[in, out]``), in any dtype: each matrix
is upcast to float32 where it is used (an expert's as its turn comes), so
no whole float32 copy of the weights exists.

``forward(cfg, params, tokens)`` -> ``(logprob [N, S-1], stats)``; the
control, ``precision="float8_e4m3fn"``, rounds every matmul's operands,
weights and activations, to float8 e4m3 (products still summed in
float32). ``stats``: ``logit_std``, the mean over tokens of the logits'
standard deviation over the vocabulary; ``router_top1``, the mean top-1
router probability; ``router_near_tie_share``, the share of (token,
expert layer) whose k-th and (k+1)-th router probabilities differ by less
than 2^-8 of the k-th, where bfloat16 rounding can swap them.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
NEAR_TIE = 2.0 ** -8
HEAD_CHUNK = 1024


def _mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def rope_tables(cfg: dict, s: int):
    """cos, sin [S, rope_dim] (halves layout), YaRN-scaled frequencies."""
    d, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    rs = cfg.get("rope_scaling")
    inv = 1.0 / base ** (np.arange(0, d, 2) / d)
    amp = 1.0
    if rs:
        f, orig = rs["factor"], rs["original_max_position_embeddings"]

        def corr(rot):
            return d * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

        lo = max(math.floor(corr(rs["beta_fast"])), 0)
        hi = min(math.ceil(corr(rs["beta_slow"])), d - 1)
        ramp = np.clip((np.arange(d // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
        inv = inv / f * ramp + inv * (1 - ramp)
        amp = _mscale(f, rs["mscale"]) / _mscale(f, rs["mscale_all_dim"])
    ang = np.outer(np.arange(s), inv)
    ang = np.concatenate([ang, ang], axis=-1)
    return (jnp.asarray(np.cos(ang) * amp, F32),
            jnp.asarray(np.sin(ang) * amp, F32))


def softmax_scale(cfg: dict) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs:
        scale *= _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def _rot(x, cos, sin):
    """Published apply_rotary_pos_emb on [S, h, d]: de-interleave the
    pairs, then x*cos + rotate_half(x)*sin."""
    s, h, d = x.shape
    x = x.reshape(s, h, d // 2, 2).transpose(0, 1, 3, 2).reshape(s, h, d)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos[:, None, :] + half * sin[:, None, :]


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


@functools.lru_cache(maxsize=8)
def _build(cfg_json: str, precision: str):
    cfg = json.loads(cfg_json)
    eps = cfg["rms_norm_eps"]
    nh, dn = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    dr, dv, r = cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"]
    k = cfg["num_experts_per_tok"]

    def q8(a):
        if precision == "float32":
            return a
        return a.astype(jnp.float8_e4m3fn).astype(F32)

    def mm(x, w):
        return q8(x) @ q8(w.astype(F32))

    def mlp(x, p):
        return mm(jax.nn.silu(mm(x, p["gate_proj"])) * mm(x, p["up_proj"]),
                  p["down_proj"])

    def attention(p, x, cos, sin):
        s = x.shape[0]
        q = mm(x, p["q_proj"]).reshape(s, nh, dn + dr)
        ckv = mm(x, p["kv_a_proj_with_mqa"])
        kv = mm(_norm(ckv[:, :r], vec(p["kv_a_layernorm"]), eps),
                p["kv_b_proj"]).reshape(s, nh, dn + dv)
        q = jnp.concatenate([q[..., :dn], _rot(q[..., dn:], cos, sin)], -1)
        k_pe = _rot(ckv[:, None, r:], cos, sin)
        key = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe, (s, nh, dr))], -1)
        causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

        def head(_, qkv):
            qh, kh, vh = qkv
            scores = q8(qh) @ q8(kh).T * softmax_scale(cfg)
            probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
            return None, q8(probs) @ q8(vh)

        _, out = jax.lax.scan(head, None, (q.transpose(1, 0, 2),
                                           key.transpose(1, 0, 2),
                                           kv[..., dn:].transpose(1, 0, 2)))
        return mm(out.transpose(1, 0, 2).reshape(s, nh * dv), p["o_proj"])

    def moe(p, x):
        probs = jax.nn.softmax(x @ p["gate"].astype(F32), axis=-1)
        w, idx = jax.lax.top_k(probs, k + 1)
        tie = (w[:, k - 1] - w[:, k]) < NEAR_TIE * w[:, k - 1]
        w, idx = w[:, :k], idx[:, :k]
        if cfg.get("norm_topk_prob"):
            w = w / w.sum(-1, keepdims=True)
        w = w * cfg["routed_scaling_factor"]
        dense = jnp.zeros((x.shape[0], cfg["n_routed_experts"]), F32)
        dense = dense.at[jnp.arange(x.shape[0])[:, None], idx].set(w)
        ex = p["experts"]

        def one(acc, e):
            g, u, d, we = e
            y = mm(jax.nn.silu(mm(x, g)) * mm(x, u), d)
            return acc + we[:, None] * y, None

        y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                            (ex["gate_proj"], ex["up_proj"],
                             ex["down_proj"], dense.T))
        return y + mlp(x, p["shared"]), probs.max(-1).mean(), tie.mean()

    def vec(a):
        return a.astype(F32)

    @functools.partial(jax.jit, static_argnums=(3,))
    def layer(p, x, tables, dense_layer):
        cos, sin = tables
        x = x + attention(p, _norm(x, vec(p["input_layernorm"]), eps),
                          cos, sin)
        h = _norm(x, vec(p["post_attention_layernorm"]), eps)
        if dense_layer:
            return x + mlp(h, p["mlp"]), jnp.float32(0), jnp.float32(0)
        y, top1, tie = moe(p["moe"], h)
        return x + y, top1, tie

    @jax.jit
    def head(norm, lm_head, x, tokens):
        h = _norm(x, norm.astype(F32), eps)
        nxt = jnp.roll(tokens, -1)

        def chunk(args):
            hc, tc = args
            logits = mm(hc, lm_head.astype(F32))
            lp = jax.nn.log_softmax(logits, axis=-1)
            return (jnp.take_along_axis(lp, tc[:, None], -1)[:, 0],
                    logits.std(-1))

        c = math.gcd(x.shape[0], HEAD_CHUNK)
        lp, std = jax.lax.map(chunk, (h.reshape(-1, c, h.shape[-1]),
                                      nxt.reshape(-1, c)))
        return lp.reshape(-1)[:-1], std.mean()

    return layer, head


def forward(cfg: dict, params, tokens, precision: str = "float32"):
    """Log-probabilities [N, S-1] (float64 numpy) of ``tokens`` [N, S],
    one row at a time, and the stats named in the module docstring."""
    if precision not in ("float32", "float8_e4m3fn"):
        raise ValueError(f"unknown precision {precision!r}")
    tokens = np.asarray(tokens, np.int32)
    layer, head = _build(json.dumps(cfg, sort_keys=True), precision)
    tables = rope_tables(cfg, tokens.shape[1])
    dense = cfg["first_k_dense_replace"]
    out, top1, ties, stds = [], [], [], []
    with jax.default_matmul_precision("highest"):
        for row in tokens:
            t = jnp.asarray(row)
            x = params["embed"][t].astype(F32)
            for i, lp in enumerate(params["layers"]):
                x, t1, tie = layer(lp, x, tables, i < dense)
                if i >= dense:
                    top1.append(float(t1))
                    ties.append(float(tie))
            lp, std = head(params["norm"], params["lm_head"], x, t)
            out.append(np.asarray(lp, np.float64))
            stds.append(float(std))
    stats = {"logit_std": float(np.mean(stds)),
             "router_top1": float(np.mean(top1)),
             "router_near_tie_share": float(np.mean(ties))}
    return np.stack(out), stats
