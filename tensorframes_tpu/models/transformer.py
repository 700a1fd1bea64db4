"""Decoder-only transformer LM — the framework's flagship long-context model.

The reference predates attention entirely (SURVEY.md §5: "no attention, no
sequences"), but long-context and distributed execution are first-class in
this framework, so the flagship model exercises every mesh axis the parallel
layer provides in ONE compiled training step:

- **data parallelism**: batch row-sharded over the ``data`` axis (the
  reference's partition parallelism);
- **tensor parallelism**: attention heads and MLP hidden dim sharded over
  the ``model`` axis, Megatron-style — XLA inserts the two allreduces per
  layer from the ``NamedSharding`` annotations alone;
- **sequence parallelism**: activations sequence-sharded over the ``seq``
  axis with :func:`~tensorframes_tpu.parallel.ring.ring_attention` rotating
  k/v blocks around the ICI ring (peak per-chip memory O(S/n)).

Pure JAX: params are nested-dict pytrees, rotary positions (no position
table — computed from global indices, so sequence sharding needs no
parameter surgery), pre-LN blocks, bf16-friendly.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel.mesh import DeviceMesh
from ..parallel.ring import ring_attention

__all__ = ["TransformerConfig", "TransformerLM"]

Params = Dict[str, Any]


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 6
    d_ff: int = 2048
    rope_base: float = 10000.0
    dtype: Any = jnp.float32
    # MoE: >0 replaces every layer's dense FFN with a Switch top-1 MoE of
    # this many experts (expert-parallel over a mesh axis when given)
    num_experts: int = 0
    expert_capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads


def _rope(x: jax.Array, positions: jax.Array, base: float) -> jax.Array:
    """Rotary position embedding. x: [..., S, H, D], positions: [S]."""
    d = x.shape[-1]
    half = d // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[:, None].astype(jnp.float32) * freqs  # [S, half]
    cos = jnp.cos(angles)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1)


def _rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
            ).astype(x.dtype) * scale


class TransformerLM:
    """Causal LM: tokens [B, S] (int32) -> logits [B, S, vocab]."""

    def __init__(self, config: TransformerConfig):
        self.config = config

    # -- parameters ---------------------------------------------------------
    def init(self, rng: Optional[jax.Array] = None) -> Params:
        c = self.config
        if rng is None:
            rng = jax.random.PRNGKey(0)
        n_keys = 2 + 6 * c.n_layers
        keys = iter(jax.random.split(rng, n_keys))

        def dense(shape, fan_in):
            return (jax.random.normal(next(keys), shape, c.dtype)
                    * np.sqrt(1.0 / fan_in).astype(np.float32))

        H, D, Dh, F = c.n_heads, c.d_model, c.head_dim, c.d_ff
        layers = []
        for _ in range(c.n_layers):
            lp = {
                "ln1": jnp.ones((D,), c.dtype),
                "wq": dense((D, H, Dh), D),
                "wk": dense((D, H, Dh), D),
                "wv": dense((D, H, Dh), D),
                "wo": dense((H, Dh, D), D),
                "ln2": jnp.ones((D,), c.dtype),
            }
            if c.num_experts > 0:
                from ..parallel.moe import init_switch_ffn
                lp["moe"] = init_switch_ffn(next(keys), D, F,
                                            c.num_experts, c.dtype)
            else:
                lp["w1"] = dense((D, F), D)
                lp["w2"] = dense((F, D), F)
            layers.append(lp)
        return {
            "embed": dense((c.vocab_size, D), D) * np.float32(np.sqrt(D)),
            "layers": layers,
            "ln_f": jnp.ones((D,), c.dtype),
            "head": dense((D, c.vocab_size), D),
        }

    # -- forward ------------------------------------------------------------
    def _attention(self, q, k, v, *, mesh: Optional[DeviceMesh],
                   seq_axis: Optional[str], data_axis: Optional[str],
                   model_axis: Optional[str]):
        if mesh is not None and seq_axis is not None:
            return ring_attention(q, k, v, mesh, seq_axis=seq_axis,
                                  causal=True, batch_axis=data_axis,
                                  head_axis=model_axis)
        # single-device path: the Pallas flash kernel on TPU (blockwise,
        # scores never leave VMEM), plain-XLA softmax attention elsewhere
        from ..ops import flash_attention
        return flash_attention(q, k, v, causal=True)

    def _block(self, lp, x, positions, *, mesh, seq_axis, data_axis,
               model_axis, expert_axis):
        """One transformer block: attention + (dense | MoE) FFN.
        Returns (x, aux) — aux is the MoE load-balance term (0 for dense)."""
        c = self.config
        h = _rms_norm(x, lp["ln1"])
        q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
        q = _rope(q, positions, c.rope_base)
        k = _rope(k, positions, c.rope_base)
        attn = self._attention(q, k, v, mesh=mesh, seq_axis=seq_axis,
                               data_axis=data_axis, model_axis=model_axis)
        x = x + jnp.einsum("bshk,hkd->bsd", attn, lp["wo"])
        h = _rms_norm(x, lp["ln2"])
        if "moe" in lp:
            from ..parallel.moe import switch_ffn
            B, S, D = h.shape
            y, aux = switch_ffn(h.reshape(B * S, D), lp["moe"],
                                capacity_factor=c.expert_capacity_factor,
                                mesh=mesh, expert_axis=expert_axis)
            return x + y.reshape(B, S, D), aux
        return x + jax.nn.gelu(h @ lp["w1"]) @ lp["w2"], jnp.float32(0.0)

    def apply_with_aux(self, params: Params, tokens: jax.Array,
                       mesh: Optional[DeviceMesh] = None,
                       seq_axis: Optional[str] = None,
                       data_axis: Optional[str] = None,
                       model_axis: Optional[str] = None,
                       expert_axis: Optional[str] = None,
                       ) -> Tuple[jax.Array, jax.Array]:
        """Forward pass -> (logits, moe_aux_loss). With ``mesh`` +
        ``seq_axis``, attention runs as a sequence-parallel ring; positions
        are global, so rotary phases are correct on every shard."""
        S = tokens.shape[1]
        x = params["embed"][tokens]  # [B, S, D]
        positions = jnp.arange(S)
        aux_total = jnp.float32(0.0)
        for lp in params["layers"]:
            x, aux = self._block(lp, x, positions, mesh=mesh,
                                 seq_axis=seq_axis, data_axis=data_axis,
                                 model_axis=model_axis,
                                 expert_axis=expert_axis)
            aux_total = aux_total + aux
        x = _rms_norm(x, params["ln_f"])
        return x @ params["head"], aux_total

    def apply(self, params: Params, tokens: jax.Array, **kw) -> jax.Array:
        return self.apply_with_aux(params, tokens, **kw)[0]

    # -- autoregressive decoding (KV cache) ---------------------------------
    def init_cache(self, batch: int, max_len: int) -> Params:
        """Static-shape KV cache: per layer ``k``/``v`` of
        ``[B, max_len, H, Dh]`` — XLA-friendly decoding writes into fixed
        buffers with ``dynamic_update_slice`` instead of growing arrays."""
        c = self.config
        zeros = lambda: jnp.zeros(  # noqa: E731
            (batch, max_len, c.n_heads, c.head_dim), c.dtype)
        return {"layers": [{"k": zeros(), "v": zeros()}
                           for _ in range(c.n_layers)]}

    def _block_cached(self, lp, ck, x, start, positions, key_positions):
        """One block over ``x`` (``[B, S, D]`` at global ``positions``),
        reading/writing the KV cache at offset ``start``. Attention sees
        every cached key with ``key_positions <= position`` (causal within
        the new tokens, everything before them unconditionally). Returns
        ``(x, new_cache_entry)``."""
        c = self.config
        h = _rms_norm(x, lp["ln1"])
        q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
        q = _rope(q, positions, c.rope_base)
        k = _rope(k, positions, c.rope_base)
        kc = jax.lax.dynamic_update_slice(ck["k"], k, (0, start, 0, 0))
        vc = jax.lax.dynamic_update_slice(ck["v"], v, (0, start, 0, 0))
        scores = jnp.einsum("bqhk,bthk->bhqt", q, kc,
                            preferred_element_type=jnp.float32)
        scores = scores * (1.0 / np.sqrt(c.head_dim))
        mask = key_positions[None, :] <= positions[:, None]  # [S, T]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bhqt,bthk->bqhk", p, vc.astype(p.dtype))
        x = x + jnp.einsum("bshk,hkd->bsd", attn.astype(x.dtype), lp["wo"])
        h = _rms_norm(x, lp["ln2"])
        if "moe" in lp:
            from ..parallel.moe import switch_ffn
            B, S, D = h.shape
            y, _ = switch_ffn(h.reshape(B * S, D), lp["moe"],
                              capacity_factor=c.expert_capacity_factor)
            ff = y.reshape(B, S, D)
        else:
            ff = jax.nn.gelu(h @ lp["w1"]) @ lp["w2"]
        return x + ff, {"k": kc, "v": vc}

    def _forward_cached(self, params, cache, tokens, start, max_len):
        """Cached forward over ``tokens`` (``[B, S]``) written at cache
        offset ``start``; serves both prefill (S = prompt) and decode
        (S = 1). Returns ``(logits [B, S, V], new_cache)``."""
        S = tokens.shape[1]
        x = params["embed"][tokens]
        positions = start + jnp.arange(S)
        key_positions = jnp.arange(max_len)
        new_layers = []
        for lp, ck in zip(params["layers"], cache["layers"]):
            x, nck = self._block_cached(lp, ck, x, start, positions,
                                        key_positions)
            new_layers.append(nck)
        x = _rms_norm(x, params["ln_f"])
        return x @ params["head"], {"layers": new_layers}

    def generate(self, params: Params, prompt: jax.Array,
                 max_new_tokens: int, temperature: float = 0.0,
                 rng: Optional[jax.Array] = None) -> jax.Array:
        """Autoregressive decode: ``prompt`` ``[B, S0]`` int32 ->
        ``[B, S0 + max_new_tokens]``.

        One prefill pass fills the KV cache for the whole prompt, then a
        ``lax.scan`` emits one token per step against the static-shape
        cache — the whole loop is one compiled XLA program (no Python in
        the decode path, the TPU-idiomatic replacement for a host loop).
        ``temperature=0`` is greedy; otherwise softmax sampling with
        ``rng``.
        """
        if temperature > 0 and rng is None:
            raise ValueError("temperature > 0 sampling needs rng")
        if rng is None:
            rng = jax.random.PRNGKey(0)
        prompt = jnp.asarray(prompt, jnp.int32)
        # one compiled program for prefill + decode scan + glue (cached per
        # static (max_new_tokens, temperature); prompt shape changes
        # retrace as usual) — an un-jitted prefill would dispatch op by op
        if not hasattr(self, "_generate_jit"):
            self._generate_jit = jax.jit(self._generate_impl,
                                         static_argnums=(3, 4))
        return self._generate_jit(params, prompt, rng, max_new_tokens,
                                  temperature)

    def _generate_impl(self, params, prompt, rng, max_new_tokens,
                       temperature):
        B, S0 = prompt.shape
        T = S0 + max_new_tokens
        cache = self.init_cache(B, T)
        logits, cache = self._forward_cached(params, cache, prompt, 0, T)

        def pick(lg, key):
            if temperature > 0:
                return jax.random.categorical(key, lg / temperature, axis=-1)
            return jnp.argmax(lg, axis=-1)

        first_key, scan_key = jax.random.split(rng)
        first = pick(logits[:, -1].astype(jnp.float32), first_key)

        def step(carry, key):
            cache, tok, pos = carry
            lg, cache = self._forward_cached(
                params, cache, tok[:, None], pos, T)
            nxt = pick(lg[:, -1].astype(jnp.float32), key)
            return (cache, nxt.astype(jnp.int32), pos + 1), tok

        # each step emits the token it was CARRIED (first, then each
        # sampled successor), so max_new_tokens steps yield exactly
        # max_new_tokens tokens; the last step's sampled successor is
        # discarded (one spare decode forward keeps the loop uniform)
        keys = jax.random.split(scan_key, max_new_tokens)
        _, toks = jax.lax.scan(
            step, (cache, first.astype(jnp.int32), S0), keys)
        return jnp.concatenate([prompt, toks.transpose(1, 0)], axis=1)

    def generate_via_frame(self, params: Params, df,
                           max_new_tokens: int,
                           prompt_col: str = "prompt",
                           temperature: float = 0.0,
                           rng: Optional[jax.Array] = None,
                           trim: bool = True):
        """Batch decoding through ``map_blocks``: prompts live in a frame
        column (``[S0]`` int cells), completions come back as a
        ``completion`` column (``[S0 + max_new_tokens]``) — the
        broadcast-the-frozen-graph pattern the other zoo models use for
        inference, here driving the KV-cache decode loop per block.

        Sampling (``temperature > 0``) folds the block's token content
        into ``rng`` so different blocks draw independent streams; blocks
        with byte-identical prompts reproduce the same completion
        (deterministic by content — re-running the frame gives the same
        result, the laziness contract's requirement)."""
        def fn_impl(**cols):
            toks = cols[prompt_col].astype(jnp.int32)
            key = rng
            if key is not None:
                mix = jnp.sum(
                    toks.astype(jnp.uint32)
                    * (jnp.arange(toks.size, dtype=jnp.uint32)
                       .reshape(toks.shape)
                       * np.uint32(2654435761) + np.uint32(1)))
                key = jax.random.fold_in(key, mix.astype(jnp.uint32))
            out = self.generate(params, toks, max_new_tokens,
                                temperature=temperature, rng=key)
            return {"completion": out}

        from .logreg import _named_args_fn
        return df.map_blocks(_named_args_fn(fn_impl, [prompt_col]),
                             trim=trim)

    @staticmethod
    def _xent(logits: jax.Array, targets: jax.Array) -> jax.Array:
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return -jnp.mean(ll)

    def loss(self, params: Params, tokens: jax.Array, targets: jax.Array,
             **apply_kw) -> jax.Array:
        """Mean next-token cross-entropy (+ weighted MoE aux when
        experts are on); ``targets[b, s]`` is the label for position ``s``
        (caller pre-shifts)."""
        logits, aux = self.apply_with_aux(params, tokens, **apply_kw)
        return self._xent(logits, targets) \
            + self.config.aux_loss_weight * aux

    # -- sharding -----------------------------------------------------------
    def param_shardings(self, mesh: DeviceMesh, model_axis: str = "model",
                        expert_axis: Optional[str] = None) -> Params:
        """Megatron-style tensor-parallel placement over ``model_axis``;
        expert weights sharded over ``expert_axis`` when MoE is on."""
        m = mesh.mesh

        def s(*spec):
            return NamedSharding(m, P(*spec))

        layer = {
            "ln1": s(), "ln2": s(),
            "wq": s(None, model_axis, None),
            "wk": s(None, model_axis, None),
            "wv": s(None, model_axis, None),
            "wo": s(model_axis, None, None),
        }
        if self.config.num_experts > 0:
            layer["moe"] = {
                "router": s(),
                "w1": s(expert_axis, None, model_axis),
                "w2": s(expert_axis, model_axis, None),
            }
        else:
            layer["w1"] = s(None, model_axis)
            layer["w2"] = s(model_axis, None)
        return {
            "embed": s(None, None),
            "layers": [jax.tree_util.tree_map(
                lambda x: x, layer,
                is_leaf=lambda l: isinstance(l, NamedSharding))
                for _ in range(self.config.n_layers)],
            "ln_f": s(),
            "head": s(None, model_axis),
        }

    def make_sharded_train_step(self, mesh: DeviceMesh,
                                data_axis: str = "data",
                                model_axis: Optional[str] = "model",
                                seq_axis: Optional[str] = None,
                                expert_axis: Optional[str] = None,
                                learning_rate: float = 1e-3):
        """One compiled SPMD training step (adam) over the mesh.

        Returns ``(step, init_state)`` factories: ``state = init_state(rng)``
        then ``state, loss = step(state, tokens, targets)``. Shardings:
        params tensor-parallel over ``model_axis`` (replicated if the axis is
        absent/None), batch over ``data_axis``, activations sequence-sharded
        with ring attention when ``seq_axis`` is given, and — with MoE on —
        expert weights and the dispatched token buffer over ``expert_axis``
        (the all_to_all pair is XLA-inserted).
        """
        import optax

        axes = mesh.axis_names
        ma = model_axis if model_axis in axes else None
        sa = seq_axis if seq_axis in axes else None
        ea = expert_axis if expert_axis in axes else None
        p_shard = (self.param_shardings(mesh, ma, ea) if (ma or ea)
                   else jax.tree_util.tree_map(
                       lambda _: NamedSharding(mesh.mesh, P()),
                       jax.eval_shape(self.init)))
        tok_shard = NamedSharding(mesh.mesh, P(data_axis, sa))
        opt = optax.adam(learning_rate)

        def init_state(rng=None):
            params = jax.device_put(self.init(rng), p_shard)
            # adam moments inherit each param's sharding (jit propagates
            # input shardings to the zeros_like outputs), but scalar leaves
            # (adam's step count) come back with an uncommitted
            # single-device placement. That mixes fine with mesh-committed
            # params only because jax relocates uncommitted arrays — a
            # checkpoint restore commits every leaf, so resume would fail
            # with "incompatible devices". Commit every non-mesh leaf to a
            # replicated mesh sharding up front.
            opt_state = jax.jit(opt.init)(params)
            opt_state = jax.tree_util.tree_map(
                lambda l: l if isinstance(l.sharding, NamedSharding)
                else jax.device_put(l, NamedSharding(mesh.mesh, P())),
                opt_state)
            return {"params": params, "opt": opt_state}

        def step(state, tokens, targets):
            def loss_fn(p):
                return self.loss(p, tokens, targets, mesh=mesh,
                                 seq_axis=sa, data_axis=data_axis,
                                 model_axis=ma, expert_axis=ea)

            loss, grads = jax.value_and_grad(loss_fn)(state["params"])
            updates, new_opt = opt.update(grads, state["opt"],
                                          state["params"])
            new_params = optax.apply_updates(state["params"], updates)
            return {"params": new_params, "opt": new_opt}, loss

        jstep = jax.jit(step,
                        in_shardings=(None, tok_shard, tok_shard),
                        donate_argnums=(0,))
        return jstep, init_state

    # -- pipeline parallelism ------------------------------------------------
    def stacked_layer_params(self, params: Params):
        """Stack the per-layer pytrees into leading-dim-``L`` leaves (the
        layout :func:`~tensorframes_tpu.parallel.pipeline.pipeline_apply`
        wants, with L = stages when one layer per stage)."""
        layers = params["layers"]
        return jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls), *layers)

    def make_pipelined_train_step(self, mesh: DeviceMesh,
                                  pipe_axis: str = "pipe",
                                  data_axis: str = "data",
                                  num_microbatches: Optional[int] = None,
                                  learning_rate: float = 1e-3):
        """Training step with the layer stack run as a GPipe pipeline over
        ``pipe_axis`` (one or more layers per stage; ``n_layers`` must be a
        multiple of the axis size). Embed/head/final-norm are replicated and
        run outside the pipeline; batch rows are sharded over ``data_axis``
        and split into microbatches inside the pipeline schedule.

        The train state keeps the layer stack in stage-major layout
        ``[P, per_stage, ...]`` sharded over ``pipe_axis`` — each device
        holds (and adam tracks) only its own stage's parameters, the O(L/P)
        memory scaling pipelining exists for. Dense models only: the MoE
        aux loss cannot cross the pipeline boundary (use
        ``make_sharded_train_step`` with ``expert_axis`` for MoE).
        """
        import optax
        from ..parallel.pipeline import pipeline_apply

        c = self.config
        if c.num_experts > 0:
            raise ValueError(
                "make_pipelined_train_step supports dense FFN models only: "
                "the MoE load-balance aux loss would be silently dropped "
                "across the pipeline; use make_sharded_train_step with "
                "expert_axis for MoE")
        pipe_size = mesh.mesh.shape[pipe_axis]
        if c.n_layers % pipe_size:
            raise ValueError(
                f"n_layers={c.n_layers} not divisible by pipe={pipe_size}")
        per_stage = c.n_layers // pipe_size

        def stage_fn(stage_params, act):
            # act: [mb, S, D]; rope positions are just arange(S) — S is
            # static, so each stage recomputes them (nothing to smuggle)
            positions = jnp.arange(act.shape[1])
            x = act
            for i in range(per_stage):
                lp = jax.tree_util.tree_map(lambda a: a[i], stage_params)
                x, _ = self._block(lp, x, positions, mesh=None,
                                   seq_axis=None, data_axis=None,
                                   model_axis=None, expert_axis=None)
            return x

        def forward(params, tokens):
            x = params["outer"]["embed"][tokens]
            out = pipeline_apply(stage_fn, params["stages"], x, mesh,
                                 pipe_axis=pipe_axis,
                                 num_microbatches=num_microbatches,
                                 data_axis=data_axis)
            x = _rms_norm(out, params["outer"]["ln_f"])
            return x @ params["outer"]["head"]

        stage_shard = NamedSharding(mesh.mesh, P(pipe_axis))
        repl = NamedSharding(mesh.mesh, P())
        tok_shard = NamedSharding(mesh.mesh, P(data_axis, None))
        opt = optax.adam(learning_rate)

        def init_state(rng=None):
            flat = self.init(rng)
            # stage-major [P, per, ...] leaves, each sharded over the pipe
            # axis: device p holds exactly its own stage's slice
            stages = jax.tree_util.tree_map(
                lambda a: a.reshape((pipe_size, per_stage) + a.shape[1:]),
                self.stacked_layer_params(flat))
            params = {
                "outer": jax.device_put(
                    {"embed": flat["embed"], "ln_f": flat["ln_f"],
                     "head": flat["head"]}, repl),
                "stages": jax.tree_util.tree_map(
                    lambda a: jax.device_put(a, stage_shard), stages),
            }
            # adam moments inherit each leaf's sharding through jit;
            # commit scalar leaves (adam count) to the mesh so a
            # checkpoint-restored state matches (see make_sharded_train_step)
            opt_state = jax.jit(opt.init)(params)
            opt_state = jax.tree_util.tree_map(
                lambda l: l if isinstance(l.sharding, NamedSharding)
                else jax.device_put(l, repl),
                opt_state)
            return {"params": params, "opt": opt_state}

        def step(state, tokens, targets):
            def loss_fn(p):
                return self._xent(forward(p, tokens), targets)

            loss, grads = jax.value_and_grad(loss_fn)(state["params"])
            updates, new_opt = opt.update(grads, state["opt"],
                                          state["params"])
            new_params = optax.apply_updates(state["params"], updates)
            return {"params": new_params, "opt": new_opt}, loss

        jstep = jax.jit(step,
                        in_shardings=(None, tok_shard, tok_shard),
                        donate_argnums=(0,))
        return jstep, init_state
