"""Model zoo tests: logreg via the six-op API, ResNet-50 forward,
transformer LM (single-device and mesh-sharded train step)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tensorframes_tpu as tft
from tensorframes_tpu.models import (LogisticRegression, ResNet50,
                                     TransformerConfig, TransformerLM)
from tensorframes_tpu.parallel.mesh import DeviceMesh, local_mesh
from jax.sharding import Mesh


def _logreg_frame(rng, n=200, d=4, parts=3):
    w_true = np.array([1.5, -2.0, 0.5, 3.0])
    x = rng.normal(size=(n, d))
    p = 1.0 / (1.0 + np.exp(-(x @ w_true)))
    y = (rng.uniform(size=n) < p).astype(np.float64)
    return tft.frame({"features": x, "label": y}, num_partitions=parts)


class TestLogReg:
    def test_gradient_via_frame_matches_direct(self, rng):
        df = _logreg_frame(rng)
        model = LogisticRegression(4)
        params = {k: np.asarray(v) for k, v in model.init().items()}

        grad, loss = model.gradient_via_frame(params, df)

        merged = np.concatenate([b.dense("features") for b in df.blocks()])
        labels = np.concatenate([b.dense("label") for b in df.blocks()])
        direct = jax.grad(model.loss)(
            {"w": jnp.asarray(params["w"], jnp.float32),
             "b": jnp.asarray(params["b"], jnp.float32)},
            jnp.asarray(merged, jnp.float32),
            jnp.asarray(labels, jnp.float32))
        np.testing.assert_allclose(grad["w"], np.asarray(direct["w"]),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(grad["b"], np.asarray(direct["b"]),
                                   rtol=1e-4, atol=1e-5)
        direct_loss = float(model.loss(
            {"w": jnp.asarray(params["w"], jnp.float32),
             "b": jnp.asarray(params["b"], jnp.float32)},
            jnp.asarray(merged, jnp.float32),
            jnp.asarray(labels, jnp.float32)))
        assert abs(loss - direct_loss) < 1e-4

    def test_fit_via_frame_learns(self, rng):
        df = _logreg_frame(rng, n=400)
        model = LogisticRegression(4)
        params, losses = model.fit_via_frame(df, steps=15, lr=1.0)
        assert losses[-1] < losses[0] * 0.7
        # learned weights correlate with the generating weights
        w = params["w"]
        assert w[3] > w[0] > 0 > w[1]

    def test_sharded_train_step(self, rng):
        mesh = local_mesh(8)
        model = LogisticRegression(4)
        step = model.make_sharded_train_step(mesh, lr=0.5)
        params = model.init()
        x = jnp.asarray(rng.normal(size=(64, 4)), jnp.float32)
        w_true = jnp.array([1.5, -2.0, 0.5, 3.0])
        y = (jax.nn.sigmoid(x @ w_true) > 0.5).astype(jnp.float32)
        losses = []
        for _ in range(20):
            params, loss = step(params, x, y)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.5


class TestResNet50:
    def test_forward_shape_and_determinism(self):
        model = ResNet50(num_classes=10)
        params = model.init()
        x = jnp.ones((2, 32, 32, 3), jnp.float32)
        logits = jax.jit(model.apply)(params, x)
        assert logits.shape == (2, 10)
        logits2 = jax.jit(model.apply)(params, jnp.ones((2, 32, 32, 3)))
        np.testing.assert_allclose(np.asarray(logits), np.asarray(logits2))

    def test_infer_via_frame(self, rng):
        model = ResNet50(num_classes=5)
        params = model.init()
        imgs = rng.normal(size=(6, 32, 32, 3)).astype(np.float64)
        df = tft.frame({"image": imgs}, num_partitions=2)
        out = model.infer_via_frame(params, df, trim=True)
        rows = out.collect()
        assert len(rows) == 6
        assert np.asarray(rows[0]["logits"]).shape == (5,)
        # frame path agrees with direct application
        direct = np.asarray(model.apply(params,
                                        jnp.asarray(imgs, jnp.float32)))
        got = np.stack([np.asarray(r["logits"]) for r in rows])
        np.testing.assert_allclose(got, direct, rtol=2e-4, atol=2e-4)


CFG = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                        d_ff=64)


class TestTransformer:
    def test_forward_and_causality(self):
        model = TransformerLM(CFG)
        params = model.init()
        tok = jnp.zeros((1, 8), jnp.int32).at[0, 4].set(7)
        logits = model.apply(params, tok)
        assert logits.shape == (1, 8, 64)
        # causality: changing token at position 4 must not affect logits < 4
        tok2 = tok.at[0, 4].set(9)
        logits2 = model.apply(params, tok2)
        np.testing.assert_allclose(np.asarray(logits[0, :4]),
                                   np.asarray(logits2[0, :4]),
                                   rtol=1e-5, atol=1e-6)
        assert not np.allclose(np.asarray(logits[0, 4:]),
                               np.asarray(logits2[0, 4:]))

    def test_ring_attention_matches_local(self):
        model = TransformerLM(CFG)
        params = model.init()
        tok = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
        local = model.apply(params, tok)

        devices = np.array(jax.devices()[:4]).reshape(1, 4)
        mesh = DeviceMesh(Mesh(devices, ("data", "seq")), data_axis="data")
        ringed = model.apply(params, tok, mesh=mesh, seq_axis="seq",
                             data_axis="data")
        np.testing.assert_allclose(np.asarray(local), np.asarray(ringed),
                                   rtol=2e-3, atol=2e-3)

    def test_ring_attention_composed_axes_matches_local(self):
        """dp+sp+tp composed: batch over data, seq ring, heads over model."""
        model = TransformerLM(CFG)
        params = model.init()
        tok = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0, 64)
        local = model.apply(params, tok)

        devices = np.array(jax.devices()[:8]).reshape(2, 2, 2)
        mesh = DeviceMesh(Mesh(devices, ("data", "seq", "model")),
                          data_axis="data")
        ringed = model.apply(params, tok, mesh=mesh, seq_axis="seq",
                             data_axis="data", model_axis="model")
        np.testing.assert_allclose(np.asarray(local), np.asarray(ringed),
                                   rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("axes,shape,seq", [
        (("data",), (8,), None),                 # pure dp
        (("data", "model"), (2, 4), None),       # dp + tp
        (("data", "model", "seq"), (2, 2, 2), "seq"),  # dp + tp + sp
    ])
    def test_sharded_train_step(self, axes, shape, seq):
        devices = np.array(jax.devices()[:int(np.prod(shape))]
                           ).reshape(shape)
        mesh = DeviceMesh(Mesh(devices, axes), data_axis="data")
        model = TransformerLM(CFG)
        step, init_state = model.make_sharded_train_step(
            mesh, seq_axis=seq, learning_rate=1e-2)
        state = init_state(jax.random.PRNGKey(0))
        tok = jax.random.randint(jax.random.PRNGKey(2), (8, 8), 0, 64)
        tgt = jnp.roll(tok, -1, axis=1)
        losses = []
        for _ in range(5):
            state, loss = step(state, tok, tgt)
            losses.append(float(loss))
        assert losses[-1] < losses[0]  # it learns (memorizes the batch)
        assert np.isfinite(losses).all()


class TestGenerate:
    """KV-cache autoregressive decoding."""

    def _model(self, vocab=32, layers=2):
        cfg = TransformerConfig(vocab_size=vocab, d_model=32, n_heads=4,
                                n_layers=layers, d_ff=64)
        m = TransformerLM(cfg)
        return m, m.init(jax.random.PRNGKey(1))

    def test_cached_forward_matches_apply(self):
        # teacher forcing through the cache (prefill + per-token decode)
        # must reproduce the plain causal forward exactly
        model, params = self._model()
        toks = jnp.asarray(
            np.random.default_rng(0).integers(0, 32, (2, 12)), jnp.int32)
        ref = model.apply(params, toks)                      # [2, 12, V]

        T = 12
        cache = model.init_cache(2, T)
        lg_pre, cache = model._forward_cached(params, cache, toks[:, :5],
                                              0, T)
        np.testing.assert_allclose(np.asarray(lg_pre),
                                   np.asarray(ref[:, :5]),
                                   rtol=2e-4, atol=2e-4)
        for pos in range(5, 12):
            lg, cache = model._forward_cached(
                params, cache, toks[:, pos:pos + 1], pos, T)
            np.testing.assert_allclose(np.asarray(lg[:, 0]),
                                       np.asarray(ref[:, pos]),
                                       rtol=2e-4, atol=2e-4)

    def test_generate_shapes_and_determinism(self):
        model, params = self._model()
        prompt = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
        out = model.generate(params, prompt, max_new_tokens=5)
        assert out.shape == (2, 8)
        np.testing.assert_array_equal(np.asarray(out[:, :3]),
                                      np.asarray(prompt))
        again = model.generate(params, prompt, max_new_tokens=5)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(again))

    def test_greedy_equals_stepwise_argmax(self):
        # greedy generate must match manually feeding argmax tokens back
        # through the full (uncached) forward — the cache cannot change
        # the distribution
        model, params = self._model()
        prompt = jnp.asarray([[7, 3, 11, 2]], jnp.int32)
        out = np.asarray(model.generate(params, prompt, max_new_tokens=4))
        toks = np.asarray(prompt)
        for _ in range(4):
            lg = model.apply(params, jnp.asarray(toks))
            nxt = np.argmax(np.asarray(lg[:, -1]), axis=-1)
            toks = np.concatenate([toks, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(out, toks)

    def test_sampling_needs_rng_and_runs(self):
        model, params = self._model()
        prompt = jnp.asarray([[1, 2]], jnp.int32)
        with pytest.raises(ValueError, match="needs rng"):
            model.generate(params, prompt, 3, temperature=0.8)
        out = model.generate(params, prompt, 3, temperature=0.8,
                             rng=jax.random.PRNGKey(7))
        assert out.shape == (1, 5)
        assert (np.asarray(out) >= 0).all() and (np.asarray(out) < 32).all()

    def test_trained_model_continues_sequence(self):
        # train on +1/+2 modular sequences (the train_lm task), then ask
        # the model to continue a +1 prompt greedily
        from demos.train_lm import train

        mesh = local_mesh()
        vocab = 32
        cfg = TransformerConfig(vocab_size=vocab, d_model=64, n_heads=8,
                                n_layers=2, d_ff=128)
        model = TransformerLM(cfg)
        state, losses = train(mesh, n_steps=60, batch=16, seq_len=16,
                              vocab=vocab, config=cfg, learning_rate=3e-3)
        assert losses[-1] < 0.3, losses[-1]
        params = jax.device_put(state["params"])
        start = 5
        prompt = jnp.asarray(
            [[(start + i) % vocab for i in range(8)]], jnp.int32)
        out = np.asarray(model.generate(params, prompt, max_new_tokens=6))
        expect = [(start + i) % vocab for i in range(14)]
        assert out[0].tolist() == expect, (out[0].tolist(), expect)

    def test_generate_via_frame(self):
        model, params = self._model()
        prompts = np.asarray([[1, 2, 3, 4], [9, 8, 7, 6]], np.int64)
        df = tft.analyze(tft.frame({"prompt": prompts}))
        out = model.generate_via_frame(params, df, max_new_tokens=3)
        comp = out.blocks()[0].dense("completion")
        assert comp.shape == (2, 7)
        ref = np.asarray(model.generate(
            params, jnp.asarray(prompts, jnp.int32), 3))
        np.testing.assert_array_equal(np.asarray(comp), ref)

    def test_generate_via_frame_sampling_independent_blocks(self):
        # temperature>0 across partitions: different blocks must draw
        # different streams; identical frames must reproduce exactly
        model, params = self._model()
        prompts = np.asarray([[1, 2, 3, 4], [1, 2, 3, 4],
                              [1, 2, 3, 4], [1, 2, 3, 4]], np.int64)
        df = tft.analyze(tft.frame({"prompt": prompts}, num_partitions=2))
        key = jax.random.PRNGKey(3)
        out = model.generate_via_frame(params, df, max_new_tokens=6,
                                       temperature=1.5, rng=key)
        blocks = [b.dense("completion") for b in out.blocks()]
        assert len(blocks) == 2
        # same prompts, different block content is identical here — both
        # blocks hold the same rows, so streams coincide by the
        # deterministic-by-content contract...
        np.testing.assert_array_equal(blocks[0], blocks[1])
        # ...but a block with different content draws a different stream
        prompts2 = np.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], np.int64)
        df2 = tft.analyze(tft.frame({"prompt": prompts2},
                                    num_partitions=2))
        out2 = model.generate_via_frame(params, df2, max_new_tokens=6,
                                        temperature=1.5, rng=key)
        b2 = [b.dense("completion") for b in out2.blocks()]
        # the SAME prompt row [1,2,3,4] sits in both frames, but df2's
        # first block has different sibling rows than df's — the content
        # fold must give it a different sample stream (near-uniform model,
        # 6 tokens, vocab 32: collision odds ~1e-9). Deleting the fold_in
        # mix would make these byte-identical.
        assert not np.array_equal(blocks[0][0], b2[0][0]), (
            blocks[0][0], b2[0][0])
        # reproducibility: rerunning the same frame gives the same bytes
        again = model.generate_via_frame(params, df2, max_new_tokens=6,
                                         temperature=1.5, rng=key)
        a2 = [b.dense("completion") for b in again.blocks()]
        np.testing.assert_array_equal(b2[0], a2[0])
        np.testing.assert_array_equal(b2[1], a2[1])
