"""Pallas kernel tests, run in interpreter mode on the CPU backend.

The XLA implementations are the semantic oracles (the ExtractNodes pattern
from SURVEY.md §4 applied to kernels: same computation, two lowerings, equal
outputs).
"""

import zlib

import jax
import jax.extend.core
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

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                           (jnp.bfloat16, 2e-2)])
    def test_value_narrower_than_query_and_key(self, rng, dtype, tol):
        # latent attention: scores over 24 dims, values of 16
        q, k, _ = _qkv(rng, d=24, dtype=dtype)
        v = jnp.asarray(rng.standard_normal((2, 64, 2, 16)), dtype)
        ref = flash_attention(q, k, v, causal=True, scale=0.3, impl="xla")
        out = flash_attention(q, k, v, causal=True, scale=0.3,
                              impl="interpret", block_q=16, block_k=16)
        assert out.shape == (2, 64, 2, 16) and out.dtype == dtype
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol)

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

    @pytest.mark.parametrize("dtype", [np.int8, np.int16, np.int32,
                                       np.uint8, np.uint16, np.uint32])
    def test_int_matches_xla_bit_for_bit(self, rng, dtype):
        # values over the dtype's whole range; ids -1 and >= num_segments
        # are dropped; 1000 rows are not a multiple of the 64-row block
        info = np.iinfo(dtype)
        vals = jnp.asarray(rng.integers(info.min, info.max, (1000, 2),
                                        dtype=dtype, endpoint=True))
        ids = jnp.asarray(rng.integers(-1, 9, 1000), jnp.int32)
        ref = segment_sum(vals, ids, 7, impl="xla")
        out = segment_sum(vals, ids, 7, impl="interpret", block_rows=64)
        assert out.dtype == ref.dtype == dtype
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    @pytest.mark.parametrize("edge", ["max", "min"])
    def test_int32_sums_wrap_as_xla_does(self, edge):
        info = np.iinfo(np.int32)
        w = info.max - 3 if edge == "max" else info.min + 3
        vals = jnp.full((300,), w, jnp.int32)
        ids = jnp.asarray(np.arange(300) % 3, jnp.int32)
        ref = segment_sum(vals, ids, 3, impl="xla")
        out = segment_sum(vals, ids, 3, impl="interpret", block_rows=32)
        # 100 rows a group: the true sum lies far past int32's range
        assert int(np.asarray(out)[0]) != 100 * w
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_64bit_ints_stay_on_xla(self, rng):
        vals = jnp.asarray(rng.integers(-5, 5, (40, 2)), jnp.int64)
        ids = jnp.asarray(rng.integers(0, 3, 40), jnp.int32)
        out = segment_sum(vals, ids, 3)  # default impl: int64 -> scatter-add
        ref = np.zeros((3, 2), np.int64)
        np.add.at(ref, np.asarray(ids), np.asarray(vals, np.int64))
        np.testing.assert_array_equal(np.asarray(out, np.int64), ref)
        # the limb route holds 32 bits: a kernel impl on int64 is an error
        for impl in ("interpret", "pallas"):
            with pytest.raises(ValueError, match="32 bits or fewer"):
                segment_sum(vals, ids, 3, impl=impl, block_rows=16)

    def test_int_block_rows_past_exact_f32_rejected(self):
        vals = jnp.ones((8,), jnp.int32)
        ids = jnp.zeros((8,), jnp.int32)
        with pytest.raises(ValueError, match="block_rows <= 65536"):
            segment_sum(vals, ids, 2, impl="interpret", block_rows=65537)
        out = segment_sum(vals, ids, 2, impl="interpret", block_rows=65536)
        np.testing.assert_array_equal(np.asarray(out), [8, 0])

    @pytest.mark.parametrize("dtype,counted", [(jnp.int32, True),
                                               (jnp.float32, False),
                                               (jnp.int64, False)])
    def test_int_kernel_route_counted_per_program(self, dtype, counted):
        from tensorframes_tpu.utils.tracing import counters

        vals = jnp.arange(20, dtype=dtype)
        ids = jnp.asarray(np.arange(20) % 4, jnp.int32)
        impl = "interpret" if dtype != jnp.int64 else None
        fn = jax.jit(lambda v, i: segment_sum(v, i, 4, impl=impl,
                                              block_rows=8))
        before = counters.get("segment_sum.onehot_int")
        fn(vals, ids).block_until_ready()
        fn(vals, ids).block_until_ready()  # cached program: no new count
        assert counters.get("segment_sum.onehot_int") - before == counted

    @pytest.mark.parametrize("where", ["dropped_rows", "valid_rows"])
    def test_nonfinite_values_stay_in_their_group(self, rng, where):
        # NaN, inf and -inf on rows whose ids are dropped vanish; on valid
        # rows they reach their own group alone, as scatter-add's do (a
        # group with inf and -inf is NaN), not every group of the block
        vals = rng.standard_normal((200, 2)).astype(np.float32)
        ids = rng.integers(0, 9, 200).astype(np.int32)
        rows = [3, 40, 41, 77, 150]
        vals[rows, 0] = [np.nan, np.inf, -np.inf, np.inf, -np.inf]
        vals[rows, 1] = [np.inf, np.nan, 1.0, -np.inf, np.nan]
        if where == "dropped_rows":
            ids[rows] = [-1, 9, -1, 12, -1]
        else:
            ids[rows] = [0, 1, 1, 2, 4]
        out = segment_sum(jnp.asarray(vals), jnp.asarray(ids), 9,
                          impl="interpret", block_rows=64)
        ref = segment_sum(jnp.asarray(vals), jnp.asarray(ids), 9, impl="xla")
        got, want = np.asarray(out), np.asarray(ref)
        assert bool(np.isfinite(want).all()) == (where == "dropped_rows")
        np.testing.assert_array_equal(np.isnan(got), np.isnan(want))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_unknown_impl_rejected(self, rng):
        vals = jnp.asarray(rng.integers(-5, 5, (4, 2)), jnp.int32)
        ids = jnp.zeros(4, jnp.int32)
        with pytest.raises(ValueError, match="Unknown segment_sum impl"):
            segment_sum(vals, ids, 1, impl="bogus")


def _window_case(case: str):
    """``(columns, ids, num_segments, block_rows, fits)`` of a
    sorted-window case; ``fits`` is whether the route takes it."""
    from tensorframes_tpu.ops.segment_reduce import WINDOW_MAX

    rng = np.random.default_rng(zlib.crc32(case.encode()))

    def ints(dtype, n, d=None):
        info = np.iinfo(dtype)
        shape = (n,) if d is None else (n, d)
        return rng.integers(info.min, info.max, shape, dtype=dtype,
                            endpoint=True)

    if case.startswith("span_"):
        # block 0 holds ids 0..62 and one more, the block's 64th smallest
        last = {"span_128": 127, "span_129": 128, "span_max": WINDOW_MAX - 1,
                "span_max_plus_1": WINDOW_MAX}[case]
        ids = np.concatenate([np.arange(63), [last],
                              np.full(64, WINDOW_MAX + 50)])
        ids = rng.permutation(ids).astype(np.int32)
        n = len(ids)
        cols = [ints(np.int32, n), rng.random(n).astype(np.float32)]
        return cols, ids, WINDOW_MAX + 60, 64, case != "span_max_plus_1"
    if case == "int8_int16_int32_wrap":
        # full-range values, 300 rows a group: every sum wraps
        n, groups = 3000, 10
        ids = rng.integers(0, groups, n).astype(np.int32)
        return ([ints(np.int8, n), ints(np.int16, n), ints(np.int32, n)],
                ids, groups, 512, True)
    if case == "unsigned":
        n, groups = 2000, 300
        ids = rng.integers(0, groups, n).astype(np.int32)
        return ([ints(np.uint8, n), ints(np.uint16, n), ints(np.uint32, n)],
                ids, groups, 128, True)
    if case == "f32":
        n, groups = 4000, 500
        ids = rng.integers(0, groups, n).astype(np.int32)
        return ([(rng.random(n) * 100).astype(np.float32)], ids, groups,
                128, True)
    if case == "d1":
        n, groups = 1500, 400
        ids = rng.integers(0, groups, n).astype(np.int32)
        return [ints(np.int32, n)], ids, groups, 128, True
    if case == "d3_mixed":
        # an int32 column, a vector f32 column of width 2, an int16 column
        n, groups = 2500, 700
        ids = rng.integers(0, groups, n).astype(np.int32)
        return ([ints(np.int32, n), rng.random((n, 2)).astype(np.float32),
                 ints(np.int16, n)], ids, groups, 256, True)
    if case == "pad_and_out_of_range":
        n, groups = 2000, 300
        ids = rng.integers(-1, groups + 40, n).astype(np.int32)
        ids[:100] = -1
        ids[100:150] = -7
        return ([ints(np.int32, n), rng.random(n).astype(np.float32)], ids,
                groups, 128, True)
    if case.startswith("nonfinite_"):
        # NaN, inf and -inf on pad rows (id -1, which sort to the front
        # and share the first blocks with valid rows) or on valid rows,
        # one group holding both inf and -inf
        n, groups = 3000, 700
        ids = rng.integers(0, groups, n).astype(np.int32)
        vals = rng.random((n, 2)).astype(np.float32)
        rows = rng.choice(n, 8, replace=False)
        vals[rows, 0] = [np.nan, np.inf, -np.inf, np.inf, np.nan, -np.inf,
                         np.inf, 1.0]
        vals[rows, 1] = [np.inf, -np.inf, np.nan, 2.0, np.nan, -np.inf,
                         np.inf, np.nan]
        if case == "nonfinite_pad_rows":
            ids[rows] = -1
            ids[rng.choice(n, 300, replace=False)] = -1
        else:
            ids[rows[1]] = ids[rows[2]]
        return ([ints(np.int32, n), vals], ids, groups, 128, True)
    rows = {"one_row_a_group": 1, "two_rows_a_group": 2,
            "many_rows_a_group": 600}[case]
    groups = 3000 // rows
    ids = rng.permutation(np.repeat(np.arange(groups), rows)).astype(
        np.int32)
    n = len(ids)
    return ([ints(np.int32, n), rng.random(n).astype(np.float32)], ids,
            groups, 128, True)


WINDOW_CASES = ["int8_int16_int32_wrap", "unsigned", "f32", "d1", "d3_mixed",
                "pad_and_out_of_range", "span_128", "span_129", "span_max",
                "span_max_plus_1", "one_row_a_group", "two_rows_a_group",
                "many_rows_a_group", "nonfinite_pad_rows",
                "nonfinite_valid_rows"]


@pytest.mark.parametrize("case", WINDOW_CASES)
def test_segment_sums_sorted_window_matches_scatter_add(case):
    """``segment_sums`` on the sorted-window route, the kernel in the
    Pallas interpreter, against ``jax.ops.segment_sum``: integer sums bit
    for bit (wraparound included), f32 sums within 1e-6 relative; rows
    with ids out of range dropped, whatever their values; a NaN or inf
    in its own group alone; a block span past the widest window takes
    scatter-add."""
    from tensorframes_tpu.ops.segment_reduce import (
        segment_sums, sorted_block_span, window_fits)

    cols, ids, groups, block_rows, fits = _window_case(case)
    span = int(sorted_block_span(jnp.asarray(ids), groups, block_rows))
    if case.startswith("span_"):
        assert span == {"span_128": 128, "span_129": 129}.get(
            case, 512 + (case == "span_max_plus_1"))
    assert window_fits(span, block_rows, cols) is fits
    out = segment_sums(cols, ids, groups,
                       window=(block_rows, span) if fits else None,
                       impl="interpret")
    valid = (ids >= 0) & (ids < groups)
    for c, got in zip(cols, out):
        mask = valid.reshape((-1,) + (1,) * (c.ndim - 1))
        assert got.dtype == c.dtype
        assert got.shape == (groups,) + c.shape[1:]
        if np.issubdtype(c.dtype, np.integer):
            want = jax.ops.segment_sum(jnp.where(mask, c, 0),
                                       np.where(valid, ids, 0), groups)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        else:
            # f32 sums against the exact sums (f64 on the CPU backend)
            want = jax.ops.segment_sum(
                jnp.where(mask, c.astype(np.float64), 0),
                np.where(valid, ids, 0), groups)
            got, want = np.asarray(got), np.asarray(want)
            assert bool(np.isfinite(want).all()) == (
                case != "nonfinite_valid_rows")
            np.testing.assert_array_equal(np.isnan(got), np.isnan(want))
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def _dots(jaxpr):
    """Every ``dot_general`` of a jaxpr, sub-jaxprs (a Pallas kernel's
    body among them) included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    found += _dots(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    found += _dots(sub)
    return found


def _dot_mix(mix: str):
    """``(fn, args, rows)`` of a mix of columns through a kernel route:
    ``rows`` the stacked operand's height, before padding."""
    from tensorframes_tpu.ops.segment_reduce import segment_sums, sort_key_plan

    n, groups = 4096, 3000
    rng = np.random.default_rng(zlib.crc32(mix.encode()))
    ids = jnp.asarray(rng.integers(0, groups, n), jnp.int32)
    window = (1024, 128)
    if mix == "onehot_int":
        return (lambda v, i: segment_sum(v, i, 9, impl="interpret"),
                (jnp.arange(n, dtype=jnp.int32), ids % 9), 4)
    if mix == "onehot_float":
        return (lambda v, i: segment_sum(v, i, 9, impl="interpret"),
                (jnp.ones((n, 2), jnp.float32), ids % 9), 12)
    if mix == "window_ints":
        cols = [jnp.ones(n, dt) for dt in (jnp.int8, jnp.int16, jnp.int32,
                                           jnp.uint32)]
        return (lambda i, *c: segment_sums(c, i, groups, window=window,
                                           impl="interpret"),
                (ids, *cols), 16)
    if mix == "window_float":
        return (lambda i, c: segment_sums([c], i, groups, window=window,
                                          impl="interpret"),
                (ids, jnp.ones(n, jnp.float32)), 6)
    # q5: v1 and v2 packed into the sort key, v3 a payload
    window += (sort_key_plan(groups, [(1, 5), (1, 15), None]),)
    return (lambda i, a, b, c, m: segment_sums(
                [a, b, c], i, groups, window=window, impl="interpret",
                mins=m),
            (ids, jnp.ones(n, jnp.int32), jnp.ones(n, jnp.int32),
             jnp.ones(n, jnp.float32), jnp.asarray([1, 1], jnp.uint32)), 14)


@pytest.mark.parametrize("mix", ["onehot_int", "onehot_float", "window_ints",
                                 "window_float", "window_q5_packed"])
def test_block_sums_make_one_bf16_dot(mix):
    # each kernel's block step is one MXU dot of bf16 operands with f32
    # accumulation, whatever the columns: int limbs, float pieces and the
    # non-finite counts stacked as rows of one operand; each program built
    # counts that operand's rows
    from tensorframes_tpu.utils.tracing import counters

    fn, args, rows = _dot_mix(mix)
    before = counters.get("segment_sum.dot_rows")
    dots = _dots(jax.make_jaxpr(fn)(*args).jaxpr)
    assert counters.get("segment_sum.dot_rows") - before == rows
    assert len(dots) == 1
    assert [v.aval.dtype for v in dots[0].invars] == [jnp.bfloat16] * 2
    assert dots[0].params["preferred_element_type"] == jnp.float32
    assert dots[0].params["precision"] is None


def _split_values():
    """Finite f32 values whose pieces never fall below the smallest
    normal (magnitudes 0 or at least 2^-102), and values just above the
    smallest normal, whose can."""
    rng = np.random.default_rng(7)
    big = np.float32(3.4028235e38)
    pow2 = np.ldexp(np.float32(1), np.arange(-102, 128)).astype(np.float32)
    bits = rng.integers(0, 1 << 32, 20000, dtype=np.uint64).astype(
        np.uint32).view(np.float32)
    bits = bits[np.isfinite(bits) & (np.abs(bits) >= 2.0 ** -102)]
    wide = np.concatenate([
        [big, -big, 0.0, -0.0, 1.0, -1.0], pow2, -pow2, bits,
        rng.standard_normal(1000) * 1e6]).astype(np.float32)
    tiny = np.finfo(np.float32).tiny
    near = (tiny * (1 + np.arange(0, 1 << 18, 97) / (1 << 23)))
    near = np.concatenate([near, -near]).astype(np.float32)
    return wide, near


def test_split3_pieces_are_exact_in_bf16_and_sum_to_the_value():
    from tensorframes_tpu.ops.segment_reduce import _split3

    wide, near = _split_values()
    for x in (wide, near):
        pieces = [np.asarray(p) for p in jax.jit(_split3)(jnp.asarray(x))]
        for p in pieces:
            assert np.isfinite(p).all()
            normal = (p == 0) | (np.abs(p) >= np.finfo(np.float32).tiny)
            as_bf16 = np.asarray(jnp.asarray(p[normal]).astype(jnp.bfloat16)
                                 .astype(jnp.float32))
            np.testing.assert_array_equal(as_bf16, p[normal])
        b0, b1, b2 = pieces
        if x is wide:
            np.testing.assert_array_equal((b0 + b1) + b2, x)
        else:
            # where denormals flush, a piece below 2^-126 is lost: the
            # value is kept within 2^-126
            flushed = [np.where(np.abs(p) < np.finfo(np.float32).tiny, 0, p)
                       for p in pieces]
            err = np.abs(((flushed[0] + flushed[1]) + flushed[2])
                         .astype(np.float64) - x.astype(np.float64))
            assert (err < 2.0 ** -126).all()


@pytest.mark.parametrize("route", ["onehot", "window"])
def test_float_block_sums_within_f32_accumulation(route):
    # one 4,096-row block of values over six decades: each group's sum
    # against float64 is within f32 summation's error bound, (n - 1) u
    # sum |x| of its n rows, and far inside the h2o cells' 1e-4 limit
    from tensorframes_tpu.ops.segment_reduce import segment_sums

    n, groups = 4096, 64
    rng = np.random.default_rng(11)
    x = (10.0 ** rng.uniform(-3, 3, n) * rng.choice([-1, 1], n)).astype(
        np.float32)
    ids = rng.integers(0, groups, n).astype(np.int32)
    if route == "onehot":
        got = segment_sum(jnp.asarray(x), jnp.asarray(ids), groups,
                          impl="interpret", block_rows=n)
    else:
        got, = segment_sums([x], ids, groups, window=(n, 128),
                            impl="interpret")
    got = np.asarray(got, np.float64)
    want = np.zeros(groups)
    np.add.at(want, ids, x.astype(np.float64))
    mass = np.zeros(groups)
    np.add.at(mass, ids, np.abs(x.astype(np.float64)))
    rows = np.bincount(ids, minlength=groups)
    u = 2.0 ** -24
    assert (np.abs(got - want) <= (rows - 1) * u * mass).all()
    assert (np.abs(got - want) <= 1e-5 * mass).all()


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

    def test_int_segment_sum_pallas_traces_under_shard_map(self):
        from jax.sharding import PartitionSpec as P

        mesh = self._mesh(4)
        vals = jnp.ones((32, 3), jnp.int32)
        ids = jnp.zeros((32,), jnp.int32)

        def fn(v, i):
            return segment_sum(v, i, 5, impl="pallas", block_rows=8)

        sharded = jax.shard_map(
            fn, mesh=mesh, in_specs=(P("shards"), P("shards")),
            out_specs=P("shards"), check_vma=True)
        out = jax.eval_shape(sharded, vals, ids)
        assert out.shape == (5 * 4, 3)
        assert out.dtype == jnp.int32

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
