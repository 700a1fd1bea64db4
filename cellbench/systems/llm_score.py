"""Batch scoring of token windows with a frozen LM through the frame
engine (``models/deepseek_v2.py``).

Set-up makes the weights on the device one layer at a time, each from
``fold_in(seed, layer)`` (the embedding and head from ``fold_in(seed,
layers)``), so set-up never holds more than one layer's temporaries and
any layer can be rebuilt alone; then a seeded pool of token rows on the
host. The distributions are the configuration's ``assumed``.

Requests (the traffic's ``op``):

- ``score_pass``: a fresh host frame of the pool's ``rows`` rows of
  ``seq_len`` int32 tokens in blocks of ``block`` rows, through
  ``DeepSeekV2.score_via_frame`` (one ``map_blocks`` dispatch a block,
  ``trim=True``, the weights bound to the block program); every row's
  log-probabilities [seq_len - 1] reach the host.

The check runs the plain reference on ``check.rows`` rows drawn from the
seed, with the program's weights, and compares log-probabilities.
"""

from __future__ import annotations

import gc
import time

import numpy as np


def _key(seed: int):
    import jax

    # seeds reach 2**31 and more: fold the high bits in
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make_layer(cfg: dict, key, dense: bool):
    """One decoder layer's weights (``assumed.weights``), traceable.

    Matrices N(0, gain^2 / fan_in), so a unit-RMS input gives outputs of
    RMS about ``gain``; norm scales U(0.9, 1.1)."""
    import jax
    import jax.numpy as jnp

    keys = iter(jax.random.split(key, 16))
    dtype = jnp.dtype(cfg["torch_dtype"])
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    gains = cfg["assumed"]["gains"]

    def mat(*shape, gain=1.0):
        fan_in = shape[-2]
        w = jax.random.normal(next(keys), shape, jnp.float32)
        return (w * (gain / np.sqrt(fan_in))).astype(dtype)

    def norm(n):
        return jax.random.uniform(next(keys), (n,), jnp.float32, 0.9,
                                  1.1).astype(dtype)

    layer = {"input_layernorm": norm(h), "post_attention_layernorm": norm(h),
             "q_proj": mat(h, nh * (dn + dr)),
             "kv_a_proj_with_mqa": mat(h, r + dr),
             "kv_a_layernorm": norm(r),
             "kv_b_proj": mat(r, nh * (dn + dv)),
             "o_proj": mat(nh * dv, h, gain=gains["o_proj"])}
    if dense:
        i = cfg["intermediate_size"]
        layer["mlp"] = {"gate_proj": mat(h, i), "up_proj": mat(h, i),
                        "down_proj": mat(i, h, gain=gains["down_proj"])}
        return layer
    e, i = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    si = i * cfg["n_shared_experts"]
    layer["moe"] = {
        "gate": mat(h, e, gain=gains["router"]),
        "experts": {"gate_proj": mat(e, h, i), "up_proj": mat(e, h, i),
                    "down_proj": mat(e, i, h, gain=gains["expert_down_proj"])},
        "shared": {"gate_proj": mat(h, si), "up_proj": mat(h, si),
                   "down_proj": mat(si, h, gain=gains["down_proj"])}}
    return layer


def make_outer(cfg: dict, key):
    """Embedding N(0, 1), final norm, and the head (``gains.lm_head``)."""
    import jax
    import jax.numpy as jnp

    k1, k2, k3 = jax.random.split(key, 3)
    dtype = jnp.dtype(cfg["torch_dtype"])
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    gain = cfg["assumed"]["gains"]["lm_head"]
    return {"embed": jax.random.normal(k1, (v, h), jnp.float32).astype(dtype),
            "norm": jax.random.uniform(k2, (h,), jnp.float32, 0.9,
                                       1.1).astype(dtype),
            "lm_head": (jax.random.normal(k3, (h, v), jnp.float32)
                        * (gain / np.sqrt(h))).astype(dtype)}


def build_params(cfg: dict, seed: int):
    """The weights on the device, one jitted call a layer."""
    import functools

    import jax

    key = _key(seed)
    make = jax.jit(functools.partial(make_layer, cfg), static_argnums=(1,))
    layers = []
    for i in range(cfg["num_hidden_layers"]):
        layers.append(jax.block_until_ready(
            make(jax.random.fold_in(key, i),
                 i < cfg["first_k_dense_replace"])))
    outer = jax.jit(functools.partial(make_outer, cfg))(
        jax.random.fold_in(key, cfg["num_hidden_layers"]))
    return dict(jax.block_until_ready(outer), layers=layers)


def token_rows(cfg: dict, req: dict, seed: int) -> np.ndarray:
    """The pool: ``rows`` x ``seq_len`` int32 ids, Zipf(``zipf_s``) over
    the vocabulary's ranks, a seeded permutation mapping rank to id."""
    rng = np.random.default_rng([seed, 3])
    v = cfg["vocab_size"]
    p = 1.0 / np.arange(1, v + 1, dtype=np.float64) ** req["zipf_s"]
    ranks = rng.choice(v, size=(req["rows"], req["seq_len"]), p=p / p.sum())
    return rng.permutation(v).astype(np.int32)[ranks]


class System:
    CONTROLS = ("float8_e4m3fn",)

    def __init__(self, cfg: dict, mix: dict, seed: int, chips: int,
                 reference):
        self.cfg, self.req, self.seed, self.chips = cfg, mix["request"], seed, chips
        self.reference = reference
        self.check_rows = int(mix["check"]["rows"])
        if self.req["op"] != "score_pass":
            raise ValueError(f"llm_score serves score_pass, not "
                             f"{self.req['op']!r}")

    def setup(self) -> None:
        from tensorframes_tpu.models.deepseek_v2 import (DeepSeekV2,
                                                         DeepSeekV2Config)

        self.model = DeepSeekV2(DeepSeekV2Config.from_dict(self.cfg))
        self.params = build_params(self.cfg, self.seed)
        self.tokens = token_rows(self.cfg, self.req, self.seed)
        # the first request compiles the block program; the second runs warm
        for _ in range(2):
            self.request(0, -1)

    def request(self, client: int, i: int):
        import tensorframes_tpu as tft

        t0 = time.monotonic()
        frame = tft.frame({"tokens": self.tokens},
                          num_partitions=self.req["rows"] // self.req["block"])
        out = self.model.score_via_frame(self.params, frame, "tokens")
        got = np.concatenate([b.dense("logprob") for b in out.blocks()])
        return ({"start": t0, "end": time.monotonic(), "units": len(got)},
                {"logprob": got})

    def release(self) -> None:
        gc.collect()

    def _rows(self):
        rng = np.random.default_rng([self.seed, 1])
        return np.sort(rng.choice(self.req["rows"], self.check_rows,
                                  replace=False))

    def check(self, outputs: list, limits: dict) -> dict:
        rows = self._rows()
        got = outputs[0]["logprob"][rows]
        want, stats = self.reference.forward(self.cfg, self.params,
                                             self.tokens[rows])
        return dict(compare(want, got, limits),
                    **{k: (v, None) for k, v in stats.items()})

    def control(self, limits: dict, precision: str = "float8_e4m3fn") -> dict:
        """The reference in ``precision`` put in the program's place."""
        rows = self._rows()
        want, _ = self.reference.forward(self.cfg, self.params,
                                         self.tokens[rows])
        got, _ = self.reference.forward(self.cfg, self.params,
                                        self.tokens[rows], precision=precision)
        return compare(want, got, limits)


def compare(want: np.ndarray, got: np.ndarray, limits: dict) -> dict:
    """Over the compared tokens, in nats: the root-mean-square error of the
    log-probabilities, the 99th percentile of their absolute error, and the
    worst row's error of its mean log-probability. The last has no limit:
    a row's errors average out over its tokens, in the fp8 control as in
    the program, so no limit lies between the two."""
    d = got.astype(np.float64) - want.astype(np.float64)
    found = {"logprob_rms_err": float(np.sqrt(np.mean(d ** 2))),
             "logprob_err_p99": float(np.percentile(np.abs(d), 99)),
             "row_mean_logprob_err": float(np.abs(d.mean(axis=1)).max())}
    return {k: (v, limits.get(k)) for k, v in found.items()}
