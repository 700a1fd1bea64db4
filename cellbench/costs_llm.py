"""Operations and bytes of a DeepSeek-V2-style decoder's forward pass, from
the configuration's shapes alone.

These are the yardstick's functions for the LM cells: they do not look at
how the program implements the work (tiles, padding, the kernels it
chooses), so a later change to the program cannot move them. Operations
count 2 a multiply-add.
"""

from __future__ import annotations


def attention_flops(cfg: dict, seq_len: int) -> float:
    """One token's latent attention in one layer: its four projections, and
    the scores and weighted values over the mean causal context
    ``(seq_len + 1) / 2``."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    dq = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    proj = (h * nh * dq + h * (r + cfg["qk_rope_head_dim"])
            + r * nh * (cfg["qk_nope_head_dim"] + dv) + nh * dv * h)
    context = (seq_len + 1) / 2
    return 2.0 * (proj + nh * context * (dq + dv))


def routed_flops(cfg: dict) -> float:
    """One token's routed experts in one expert layer: k SwiGLUs."""
    return (2.0 * cfg["num_experts_per_tok"] * 3 * cfg["hidden_size"]
            * cfg["moe_intermediate_size"])


def token_flops(cfg: dict, seq_len: int) -> float:
    """The whole forward pass for one token of a ``seq_len`` window:
    attention in every layer; the dense SwiGLU in the first
    ``first_k_dense_replace``; router, routed and shared experts in the
    rest; the head over the vocabulary."""
    h = cfg["hidden_size"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    moe = (2.0 * h * cfg["n_routed_experts"] + routed_flops(cfg)
           + 2.0 * 3 * h * cfg["moe_intermediate_size"]
           * cfg["n_shared_experts"])
    return (layers * attention_flops(cfg, seq_len)
            + dense * 2.0 * 3 * h * cfg["intermediate_size"]
            + (layers - dense) * moe + 2.0 * h * cfg["vocab_size"])


def routed_bytes(cfg: dict, tokens: int, dtype_bytes: int = 2) -> float:
    """HBM bytes the routed experts of one expert layer must move at least
    for ``tokens`` tokens dispatched together: every expert's three
    matrices read once, and per token-expert pair its input row read
    twice (gate, up), its hidden row written and read, and its output row
    written."""
    h, i = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = cfg["n_routed_experts"] * 3 * h * i
    pairs = tokens * cfg["num_experts_per_tok"]
    return dtype_bytes * (weights + pairs * (2 * h + 2 * i + h))
