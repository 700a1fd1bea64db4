"""Pallas kernel tests, run in interpreter mode on the CPU backend.

The XLA implementations are the semantic oracles (the ExtractNodes pattern
from SURVEY.md §4 applied to kernels: same computation, two lowerings, equal
outputs).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorframes_tpu.ops import flash_attention, segment_sum


def _qkv(rng, b=2, s=64, h=2, d=16, dtype=jnp.float32):
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    return q, k, v


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_xla(self, rng, causal):
        q, k, v = _qkv(rng)
        ref = flash_attention(q, k, v, causal=causal, impl="xla")
        out = flash_attention(q, k, v, causal=causal, impl="interpret",
                              block_q=16, block_k=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_non_multiple_seq_len(self, rng):
        # seq length not a multiple of the block: pad rows must not leak
        q, k, v = _qkv(rng, s=37)
        ref = flash_attention(q, k, v, impl="xla")
        out = flash_attention(q, k, v, impl="interpret",
                              block_q=16, block_k=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_causal_non_multiple(self, rng):
        q, k, v = _qkv(rng, s=21)
        ref = flash_attention(q, k, v, causal=True, impl="xla")
        out = flash_attention(q, k, v, causal=True, impl="interpret",
                              block_q=8, block_k=8)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_cross_attention_lengths(self, rng):
        # Sq != Sk (decoder attending over a different-length memory)
        q = jnp.asarray(rng.standard_normal((1, 24, 2, 8)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 40, 2, 8)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 40, 2, 8)), jnp.float32)
        ref = flash_attention(q, k, v, impl="xla")
        out = flash_attention(q, k, v, impl="interpret",
                              block_q=16, block_k=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_single_block(self, rng):
        q, k, v = _qkv(rng, s=8)
        ref = flash_attention(q, k, v, impl="xla")
        out = flash_attention(q, k, v, impl="interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_matches_ring_attention(self, rng):
        """Kernel and the mesh-level ring implementation agree — the two
        halves of the long-context story compute the same function."""
        from tensorframes_tpu.parallel.mesh import local_mesh
        from tensorframes_tpu.parallel.ring import ring_attention

        mesh = local_mesh(4)
        q, k, v = _qkv(rng, b=1, s=32, h=2, d=8)
        ref = np.asarray(flash_attention(q, k, v, causal=True, impl="xla"))
        ring = np.asarray(ring_attention(q, k, v, mesh, causal=True))
        flash = np.asarray(flash_attention(q, k, v, causal=True,
                                           impl="interpret",
                                           block_q=8, block_k=8))
        np.testing.assert_allclose(ring, ref, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(flash, ref, rtol=2e-5, atol=2e-5)


class TestSegmentSum:
    def test_matches_xla(self, rng):
        vals = jnp.asarray(rng.standard_normal((100, 5)), jnp.float32)
        ids = jnp.asarray(rng.integers(0, 7, 100), jnp.int32)
        ref = segment_sum(vals, ids, 7, impl="xla")
        out = segment_sum(vals, ids, 7, impl="interpret", block_rows=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_out_of_range_ids_dropped(self, rng):
        vals = jnp.ones((10, 2), jnp.float32)
        ids = jnp.asarray([0, 1, -1, 2, 5, 1, 0, -1, 2, 1], jnp.int32)
        out = segment_sum(vals, ids, 3, impl="interpret", block_rows=4)
        ref = segment_sum(vals, ids, 3, impl="xla")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref))
        # id 5 and -1 dropped: total mass = rows with id in [0, 3)
        assert float(np.asarray(out).sum()) == pytest.approx(2 * 7)

    def test_1d_values(self, rng):
        vals = jnp.asarray(rng.standard_normal(50), jnp.float32)
        ids = jnp.asarray(rng.integers(0, 4, 50), jnp.int32)
        ref = segment_sum(vals, ids, 4, impl="xla")
        out = segment_sum(vals, ids, 4, impl="interpret", block_rows=8)
        assert out.shape == (4,)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_nd_values(self, rng):
        vals = jnp.asarray(rng.standard_normal((30, 2, 3)), jnp.float32)
        ids = jnp.asarray(rng.integers(0, 5, 30), jnp.int32)
        ref = segment_sum(vals, ids, 5, impl="xla")
        out = segment_sum(vals, ids, 5, impl="interpret", block_rows=8)
        assert out.shape == (5, 2, 3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_empty(self):
        vals = jnp.zeros((0, 3), jnp.float32)
        ids = jnp.zeros((0,), jnp.int32)
        out = segment_sum(vals, ids, 4, impl="interpret")
        np.testing.assert_array_equal(np.asarray(out), np.zeros((4, 3)))

    def test_int_values_routed_to_exact_path(self, rng):
        vals = jnp.asarray(rng.integers(-5, 5, (40, 2)), jnp.int32)
        ids = jnp.asarray(rng.integers(0, 3, 40), jnp.int32)
        out = segment_sum(vals, ids, 3)  # default impl: ints -> scatter-add
        ref = np.zeros((3, 2), np.int64)
        np.add.at(ref, np.asarray(ids), np.asarray(vals, np.int64))
        np.testing.assert_array_equal(np.asarray(out, np.int64), ref)
        # an explicit f32-accumulating impl on ints is an error, not silent
        with pytest.raises(ValueError, match="inexact for integer"):
            segment_sum(vals, ids, 3, impl="interpret", block_rows=16)

    def test_unknown_impl_rejected(self, rng):
        vals = jnp.asarray(rng.integers(-5, 5, (4, 2)), jnp.int32)
        ids = jnp.zeros(4, jnp.int32)
        with pytest.raises(ValueError, match="Unknown segment_sum impl"):
            segment_sum(vals, ids, 1, impl="bogus")


class TestShardMapVma:
    """Pallas kernels inside shard_map(check_vma=True).

    Regression (hit on TPU by daggregate, where segment_sum auto-picks
    Pallas): pallas_call's out_shape must declare the mesh axes it varies
    over, or *tracing* fails with "vma ... must not be None". Tracing the
    real impl="pallas" path via eval_shape exercises exactly that check
    without needing Mosaic, so these run on CPU. Execution-side CPU
    coverage goes through the documented interpret→xla redirect (the
    Pallas HLO interpreter cannot replay kernel bodies under vma
    tracking); the non-interpreted on-chip run lives in
    benchmarks/tpu_pallas_smoke.py.
    """

    def _mesh(self, n):
        from jax.sharding import Mesh

        return Mesh(np.array(jax.devices()[:n]), ("shards",))

    def test_segment_sum_pallas_traces_under_shard_map(self, rng):
        from jax.sharding import PartitionSpec as P

        mesh = self._mesh(4)
        vals = jnp.ones((32, 3), jnp.float32)
        ids = jnp.zeros((32,), jnp.int32)

        def fn(v, i):
            return segment_sum(v, i, 5, impl="pallas", block_rows=8)

        sharded = jax.shard_map(
            fn, mesh=mesh, in_specs=(P("shards"), P("shards")),
            out_specs=P("shards"), check_vma=True)
        out = jax.eval_shape(sharded, vals, ids)  # raises pre-fix
        assert out.shape == (5 * 4, 3)

    def test_flash_attention_pallas_traces_under_shard_map(self, rng):
        from jax.sharding import PartitionSpec as P

        mesh = self._mesh(2)
        q, k, v = _qkv(rng, b=4, s=32, h=1, d=8)

        def fn(q, k, v):
            return flash_attention(q, k, v, impl="pallas",
                                   block_q=16, block_k=16)

        sharded = jax.shard_map(
            fn, mesh=mesh, in_specs=(P("shards"), P("shards"), P("shards")),
            out_specs=P("shards"), check_vma=True)
        out = jax.eval_shape(sharded, q, k, v)  # raises pre-fix
        assert out.shape == q.shape

    def test_segment_sum_interpret_redirects_and_matches(self, rng):
        from jax.sharding import PartitionSpec as P

        mesh = self._mesh(4)
        n = 8 * 4
        vals = jnp.asarray(rng.standard_normal((n, 3)), jnp.float32)
        ids = jnp.asarray(rng.integers(0, 5, n), jnp.int32)

        def fn(v, i):
            return segment_sum(v, i, 5, impl="interpret", block_rows=8)

        sharded = jax.shard_map(
            fn, mesh=mesh, in_specs=(P("shards"), P("shards")),
            out_specs=P("shards"), check_vma=True)
        out = jax.jit(sharded)(vals, ids)  # [5 * ndev, 3] stacked partials
        per_shard = np.asarray(out).reshape(4, 5, 3).sum(axis=0)
        ref = np.zeros((5, 3), np.float32)
        np.add.at(ref, np.asarray(ids), np.asarray(vals))
        np.testing.assert_allclose(per_shard, ref, rtol=1e-5, atol=1e-5)

    def test_interpret_redirect_covers_partial_vma(self, rng):
        # replicated q but sharded k/v: the redirect must consider every
        # input's vma, not just the first one's
        from jax.sharding import PartitionSpec as P

        mesh = self._mesh(2)
        q, k, v = _qkv(rng, b=2, s=32, h=1, d=8)

        def fn(q, k, v):
            o = flash_attention(q, k, v, impl="interpret",
                                block_q=16, block_k=16)
            return jax.lax.psum(o, "shards")

        sharded = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(None), P(None, "shards"), P(None, "shards")),
            out_specs=P(None), check_vma=True)
        out = jax.jit(sharded)(q, k, v)  # pre-fix: interpreter vma crash
        assert out.shape == q.shape
