"""DeepSeek-V2 as a frozen scorer of token windows over a frame column.

The layer equations are those of the published ``modeling_deepseek.py``
(DeepSeek-V2, arXiv:2405.04434), for configurations without q compression
(``q_lora_rank`` null, as DeepSeek-V2-Lite):

- multi-head latent attention: ``q = x Wq`` split per head into a
  ``qk_nope_head_dim`` part and a ``qk_rope_head_dim`` part;
  ``[c_kv, k_pe] = x W_kv_a`` with ``c_kv`` the ``kv_lora_rank`` latent and
  ``k_pe`` one rope key shared by every head; ``[k_nope, v] =
  rms_norm(c_kv) W_kv_b`` per head; scores over ``[q_nope, rope(q_pe)] .
  [k_nope, rope(k_pe)]`` at the YaRN softmax scale, causal;
- YaRN rope on interleaved pairs: the published code reads ``q_pe``/``k_pe``
  as pairs ``(x0, x1), (x2, x3), ...``, de-interleaves them and rotates by
  halves, and so does :func:`_rope`;
- the first ``first_k_dense_replace`` layers have a SwiGLU; the rest a
  mixture of ``n_routed_experts`` SwiGLU experts, a float32 softmax router
  with greedy top-k, and ``n_shared_experts`` shared experts that make one
  SwiGLU of ``n_shared_experts`` times the expert width, added to every
  token. The layer is dropless: every token reaches its k experts, however
  uneven the load (``ops.moe_gmm``);
- RMSNorm before attention, before the MLP and before the untied head.

Precision: weights and activations in the parameters' dtype (bfloat16 on
the chip) with float32 accumulation; the router's logits, softmax and
top-k, the attention softmax and the final log-softmax in float32. The
rope angles and rotation are computed in float32 and rounded once (the
published code rounds its cos/sin tables to the model dtype first).

Parameters are a pytree, matrices stored ``[in, out]``: ``embed`` [V, H],
``layers`` (a list; each with ``input_layernorm``, ``q_proj``,
``kv_a_proj_with_mqa``, ``kv_a_layernorm``, ``kv_b_proj``, ``o_proj``,
``post_attention_layernorm``, and ``mlp`` (``gate_proj``, ``up_proj``,
``down_proj``) on a dense layer or ``moe`` (``gate`` [H, E], ``experts``
with [E, H, I] / [E, I, H] stacks, ``shared``) on an expert layer),
``norm`` [H] and ``lm_head`` [H, V].
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import flash_attention, moe_gmm, tile_layout
from ..ops.moe_gmm import max_tiles
from ..utils import tracing
from .transformer import _rms_norm

__all__ = ["DeepSeekV2Config", "DeepSeekV2", "rope_frequencies",
           "softmax_scale", "moe_layer"]

Params = Dict[str, Any]

# rows of a grouped-matmul tile; an expert's pairs are padded to a multiple
_TILE_ROWS = 256
# tokens whose logits over the vocabulary exist at once
_LOGIT_CHUNK = 2048
# query and key rows of an attention kernel block
_ATTN_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class DeepSeekV2Config:
    """The shape of a DeepSeek-V2 model, under the keys of its
    ``config.json``."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    num_attention_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[Dict[str, Any]] = None
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = False

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "DeepSeekV2Config":
        """The fields from a ``config.json``-style mapping; refuses the
        settings this implementation does not compute."""
        unsupported = {"q_lora_rank": None, "scoring_func": "softmax",
                       "topk_method": "greedy", "moe_layer_freq": 1,
                       "hidden_act": "silu", "n_group": 1, "topk_group": 1}
        for key, want in unsupported.items():
            if d.get(key, want) != want:
                raise ValueError(f"{key}={d[key]!r} is not supported "
                                 f"(only {want!r})")
        rs = d.get("rope_scaling")
        if rs is not None and rs.get("type") != "yarn":
            raise ValueError(f"rope_scaling {rs!r}: only yarn is supported")
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace


def _yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _yarn_correction_dim(rotations: float, dim: int, base: float,
                         max_pos: int) -> float:
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))
            / (2 * math.log(base)))


def rope_frequencies(cfg: DeepSeekV2Config) -> np.ndarray:
    """The rope's ``qk_rope_head_dim / 2`` inverse frequencies: YaRN's
    blend of the original ones (high frequencies) and the ones divided by
    ``factor`` (low frequencies), ramped linearly between the dims that
    ``beta_fast`` and ``beta_slow`` rotations reach over the original
    context."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    rs = cfg.rope_scaling
    if not rs:
        return extra
    low, high = yarn_ramp(cfg)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                         # 1: the original frequency
    return extra / rs["factor"] * (1.0 - keep) + extra * keep


def yarn_ramp(cfg: DeepSeekV2Config):
    """The rope dims (of ``qk_rope_head_dim / 2``) where YaRN's ramp from
    original to interpolated frequencies starts and ends."""
    rs, dim = cfg.rope_scaling, cfg.qk_rope_head_dim
    args = (dim, cfg.rope_theta, rs["original_max_position_embeddings"])
    low = math.floor(_yarn_correction_dim(rs["beta_fast"], *args))
    high = math.ceil(_yarn_correction_dim(rs["beta_slow"], *args))
    return max(low, 0), min(high, dim - 1)


def softmax_scale(cfg: DeepSeekV2Config) -> float:
    """``q_head_dim ** -0.5``, times YaRN's ``mscale(factor,
    mscale_all_dim) ** 2`` when the rope is scaled."""
    scale = cfg.q_head_dim ** -0.5
    rs = cfg.rope_scaling
    if rs and rs.get("mscale_all_dim"):
        scale *= _yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def _rope_tables(cfg: DeepSeekV2Config, seq_len: int):
    """cos and sin [S, rope_dim / 2] in float32, times YaRN's
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``."""
    rs = cfg.rope_scaling
    amp = 1.0
    if rs:
        amp = (_yarn_mscale(rs["factor"], rs.get("mscale", 1))
               / _yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0)))
    inv = jnp.asarray(rope_frequencies(cfg), jnp.float32)
    ang = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang) * amp, jnp.sin(ang) * amp


def _rope(x, cos, sin):
    """Rotate ``x`` [B, S, h, D], read as interleaved pairs, by the
    tables [S, D/2]; float32 out, halves layout (x0, x2, ..., x1, x3,
    ...), as the published code leaves it."""
    x = x.astype(jnp.float32)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _proj(x, w):
    return jnp.einsum("...i,io->...o", x, w,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _swiglu(x, p):
    g = _proj(x, p["gate_proj"]).astype(jnp.float32)
    u = _proj(x, p["up_proj"]).astype(jnp.float32)
    return _proj((jax.nn.silu(g) * u).astype(x.dtype), p["down_proj"])


def _attention(cfg: DeepSeekV2Config, lp: Params, h, cos, sin,
               impl: Optional[str]):
    b, s, _ = h.shape
    nh, dn, dv = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    q = _proj(h, lp["q_proj"]).reshape(b, s, nh, cfg.q_head_dim)
    ckv = _proj(h, lp["kv_a_proj_with_mqa"])
    c, k_pe = ckv[..., :cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank:]
    kv = _proj(_rms_norm(c, lp["kv_a_layernorm"], cfg.rms_norm_eps),
               lp["kv_b_proj"]).reshape(b, s, nh, dn + dv)
    q_pe = _rope(q[..., dn:], cos, sin).astype(h.dtype)
    k_pe = _rope(k_pe[:, :, None, :], cos, sin).astype(h.dtype)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, (b, s, nh, k_pe.shape[-1]))],
        axis=-1)
    block = min(_ATTN_BLOCK, s)
    o = flash_attention(q, k, kv[..., dn:], causal=True,
                        scale=softmax_scale(cfg), block_q=block,
                        block_k=block, impl=impl)
    return _proj(o.reshape(b, s, nh * dv), lp["o_proj"])


def moe_layer(cfg: DeepSeekV2Config, p: Params, x, *, tm: int = _TILE_ROWS,
              interpret: Optional[bool] = None):
    """The expert block on tokens ``x`` [T, H]: ``(y [T, H], experts
    [T, k])``, ``experts`` being the routed experts of each token.

    Routing in float32: softmax over every expert, greedy top-k, the
    weights taken as they are unless ``norm_topk_prob``, times
    ``routed_scaling_factor``. The token-expert pairs are sorted by expert
    into ``tm``-row tiles (``ops.tile_layout``), each expert's SwiGLU runs
    as three grouped matmuls (``ops.moe_gmm``), and each token sums its k
    results, weighted in float32, plus the shared experts' SwiGLU.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t, hdim = x.shape
    k, e = cfg.num_experts_per_tok, cfg.n_routed_experts
    logits = jnp.dot(x.astype(jnp.float32), p["gate"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    weights, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    if cfg.norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights * cfg.routed_scaling_factor
    slot, tile_expert, tiles_used = tile_layout(
        experts.reshape(-1).astype(jnp.int32), e, tm)
    rows = max_tiles(t * k, e, tm) * tm
    token = jnp.arange(t * k, dtype=jnp.int32) // k
    src = jnp.full((rows,), t, jnp.int32).at[slot].set(token)
    xp = jnp.concatenate([x, jnp.zeros((1, hdim), x.dtype)])[src]
    ex = p["experts"]

    def gmm(a, w):
        return moe_gmm(a, w, tile_expert, tiles_used, tm=tm,
                       interpret=interpret)

    g = gmm(xp, ex["gate_proj"]).astype(jnp.float32)
    u = gmm(xp, ex["up_proj"]).astype(jnp.float32)
    d = gmm((jax.nn.silu(g) * u).astype(x.dtype), ex["down_proj"])
    routed = jnp.sum(d[slot].reshape(t, k, hdim).astype(jnp.float32)
                     * weights[..., None], axis=1).astype(x.dtype)
    return routed + _swiglu(x, p["shared"]), experts


def _log_probs(cfg: DeepSeekV2Config, params: Params, x, tokens):
    """log p(tokens[:, t+1] | tokens[:, :t+1]) [B, S-1] in float32, the
    logits computed ``_LOGIT_CHUNK`` tokens at a time."""
    b, s, hdim = x.shape
    n = b * s
    chunk = math.gcd(n, _LOGIT_CHUNK)
    h = _rms_norm(x, params["norm"], cfg.rms_norm_eps).reshape(
        n // chunk, chunk, hdim)
    nxt = jnp.roll(tokens, -1, axis=1).reshape(n // chunk, chunk)

    def one(args):
        hc, tc = args
        logits = jnp.dot(hc, params["lm_head"],
                         preferred_element_type=jnp.float32)
        picked = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return picked - jax.nn.logsumexp(logits, axis=-1)

    return jax.lax.map(one, (h, nxt)).reshape(b, s)[:, :-1]


class DeepSeekV2:
    """A DeepSeek-V2 model of a given :class:`DeepSeekV2Config`."""

    def __init__(self, config: DeepSeekV2Config):
        self.config = config

    def apply(self, params: Params, tokens: jax.Array):
        """``tokens`` [B, S] int -> ``(logprob [B, S-1] float32,
        expert_counts [B, moe_layers, n_routed_experts] int32)``:
        ``logprob[:, t]`` is log p(tokens[:, t+1] | tokens[:, :t+1])
        under the full softmax over the vocabulary, and
        ``expert_counts`` the pairs of each row routed to each expert in
        each expert layer."""
        cfg = self.config
        b, s = tokens.shape
        tokens = tokens.astype(jnp.int32)
        impl = None if jax.default_backend() == "tpu" else "xla"
        cos, sin = _rope_tables(cfg, s)
        x = params["embed"][tokens]
        counts = []
        rows = jnp.repeat(jnp.arange(b, dtype=jnp.int32),
                          s * cfg.num_experts_per_tok)
        for i, lp in enumerate(params["layers"]):
            h = _rms_norm(x, lp["input_layernorm"], cfg.rms_norm_eps)
            x = x + _attention(cfg, lp, h, cos, sin, impl)
            h = _rms_norm(x, lp["post_attention_layernorm"],
                          cfg.rms_norm_eps)
            if i < cfg.first_k_dense_replace:
                x = x + _swiglu(h, lp["mlp"])
                continue
            y, experts = moe_layer(cfg, lp["moe"], h.reshape(b * s, -1))
            x = x + y.reshape(x.shape)
            counts.append(jnp.zeros((b, cfg.n_routed_experts), jnp.int32)
                          .at[rows, experts.reshape(-1)].add(1))
        return (_log_probs(cfg, params, x, tokens),
                jnp.stack(counts, axis=1))

    def score_via_frame(self, params: Params, df, tokens_col: str = "tokens"):
        """Per row of ``df``'s ``tokens_col`` ([S] int cells), the
        ``logprob`` column [S-1]: log p(x[t+1] | x[:t+1]). Lazy.

        One ``map_blocks`` dispatch a block, the weights bound to the
        block program as an argument. Counts ``moe.dispatches`` (expert
        layers run) and ``moe.token_expert_pairs``; with tracing on, also
        the gauge ``moe.expert_load_max_ratio``, the busiest expert's
        pairs over the mean, one sample per expert layer and block."""
        from ..frame import TensorFrame
        from .logreg import _frame_fn

        apply = self.apply

        def score(params, **cols):
            logprob, counts = apply(params, cols[tokens_col])
            return {"logprob": logprob, "expert_counts": counts}

        fn = _frame_fn(self, ("score", tokens_col), score,
                       ["params", tokens_col])
        scored = df.map_blocks(fn, trim=True, bind=params)
        moe_layers = self.config.moe_layers
        pairs_a_token = moe_layers * self.config.num_experts_per_tok

        def blocks():
            out = []
            for blk in scored.blocks():
                if blk.num_rows:
                    tokens = blk.num_rows * (blk.dense("logprob").shape[1] + 1)
                    tracing.counters.inc("moe.dispatches", moe_layers)
                    # dropless: every token meets k experts a layer
                    tracing.counters.inc("moe.token_expert_pairs",
                                         tokens * pairs_a_token)
                    if tracing.enabled():
                        counts = blk.dense("expert_counts")
                        for layer in counts.sum(axis=0):
                            tracing.gauge("moe.expert_load_max_ratio",
                                          layer.max() / layer.mean())
                out.append(blk.select(["logprob"]))
            return out

        return TensorFrame(scored.schema.select(["logprob"]), blocks,
                           scored.num_partitions,
                           plan=f"score_via_frame({df._plan})")
