"""The sorted-window route of ``daggregate`` on 4 CPU devices.

Past the group count the one-hot kernel holds, ``daggregate`` on a TPU
sums its ``sum`` columns through one sort keyed on the group ids and the
windowed one-hot kernel (``ops/segment_reduce.py``), given the largest
block span of the sorted ids, which is read once and cached beside the
ids. Integer columns whose exact range, read once and cached on the
frame, is narrow enough ride in the sort key beside the ids. Off the TPU
it keeps scatter-add; the ``tpu_route`` fixture takes the route here all
the same, the kernel running in Pallas's TPU interpreter inside the
mesh. The kernel alone is tested in ``test_kernels.py``.
"""

import functools
import time
from collections import OrderedDict

import numpy as np
import pytest

import jax.numpy as jnp

import tensorframes_tpu as tft
from tensorframes_tpu.ops import segment_reduce as SR
from tensorframes_tpu.ops.segment_reduce import pallas_fits
from tensorframes_tpu.parallel import dfilter, distribute, local_mesh
from tensorframes_tpu.parallel import distributed as D
from tensorframes_tpu.parallel.distributed import daggregate
from tensorframes_tpu.utils import tracing
from tensorframes_tpu.utils.tracing import counters


@pytest.fixture
def tpu_route(monkeypatch):
    """``daggregate`` wants the window as it does on a TPU."""
    monkeypatch.setattr(SR, "window_wanted", functools.partial(
        SR.window_wanted, backend="tpu"))


AGG = {"v1": "sum", "v2": "sum", "v3": "sum"}


def _frame(keys: np.ndarray, seed: int = 0, narrow: bool = False):
    """``v1`` in 1..5 and an f32 ``v3``; ``v2`` uniform over int32, or
    with ``narrow`` an int8 in -100..27 beside an int16 ``v4`` in
    -1100..-1000 (the frame keeps both as int32): with 2e4 groups the
    ids and the three int columns then take all 32 bits of the sort
    key."""
    rng = np.random.default_rng(seed)
    n = len(keys)
    cols = {"k": keys.astype(np.int32),
            "v1": rng.integers(1, 6, n).astype(np.int32)}
    if narrow:
        cols["v2"] = rng.integers(-100, 28, n).astype(np.int8)
        cols["v4"] = rng.integers(-1100, -999, n).astype(np.int16)
    else:
        cols["v2"] = rng.integers(-2**31, 2**31 - 1, n,
                                  dtype=np.int64).astype(np.int32)
    cols["v3"] = (rng.random(n) * 100).astype(np.float32)
    return tft.frame(cols)


def _sums(df) -> dict:
    return {n: "sum" for n in df.schema.names if n != "k"}


def _tables(out) -> dict:
    cols = {n: np.concatenate([np.asarray(b.dense(n)) for b in out.blocks()])
            for n in out.schema.names}
    order = np.argsort(cols["k"])
    return {n: c[order] for n, c in cols.items()}


def _reference(df) -> dict:
    """Plain numpy group sums: integers, int32 in a frame, wrapping as
    the device's do."""
    b = df.blocks()[0]
    keys = np.asarray(b.dense("k"))
    uniq, inv = np.unique(keys, return_inverse=True)
    out = {"k": uniq}
    for c in _sums(df):
        col = np.asarray(b.dense(c))
        if np.issubdtype(col.dtype, np.integer):
            acc = np.zeros(len(uniq), np.int64)
            np.add.at(acc, inv, col.astype(np.int64))
            out[c] = acc.astype(np.uint64).astype(np.uint32).view(np.int32)
        else:
            acc = np.zeros(len(uniq), np.float64)
            with np.errstate(invalid="ignore"):    # inf + -inf is NaN
                np.add.at(acc, inv, col.astype(np.float64))
            out[c] = acc
    return out


def _assert_equal_sums(got: dict, want: dict):
    assert got.keys() == want.keys()
    for c in want:
        if c == "v3":
            err = np.abs(got[c] - want[c]) / np.maximum(np.abs(want[c]),
                                                        1e-30)
            assert err.max() < 1e-5
        else:
            assert got[c].dtype == want[c].dtype
            assert np.array_equal(got[c], want[c])


COUNTED = ("segment_sum.sorted_window", "segment_sum.window_fallback",
           "segment_sum.packed_key", "segment_sum.packed_columns")


def _counted(fn):
    """``fn()`` and what it added to each of :data:`COUNTED`."""
    before = [counters.get(n) for n in COUNTED]
    out = fn()
    return out, [counters.get(n) - b for n, b in zip(COUNTED, before)]


def _keys(case: str) -> np.ndarray:
    rng = np.random.default_rng(11)
    if case == "uniform_2e4":
        return rng.integers(0, 20_000, 200_000)
    if case == "uniform_8e3":
        # room in every block's window for a filter that drops 30%
        return rng.integers(0, 8_000, 200_000)
    if case == "shard_missing_groups":
        # the second quarter of the rows (the second shard) holds only
        # every seventh group: its sorted ids have gaps
        n, groups = 120_000, 8_000
        keys = rng.integers(0, groups, n)
        q = slice(n // 4, n // 2)
        keys[q] = rng.integers(0, groups // 7, n // 4) * 7
        return keys
    raise ValueError(case)


def _route_case(case: str):
    """A route case's frame, the int columns it packs, and whether it is
    filtered (``v1 < 100``) once distributed."""
    if case == "all_narrow":
        return _frame(_keys("uniform_2e4"), seed=7, narrow=True), 3, False
    if case == "filtered_out_of_range":
        # the dropped rows hold values outside the range the kept rows
        # span: they stay at each shard's tail with id -1, and must add
        # nothing to any field of the packed key
        df = _frame(_keys("uniform_8e3"), seed=8, narrow=True)
        cols = {n: np.asarray(df.blocks()[0].dense(n)).copy()
                for n in df.schema.names}
        drop = np.random.default_rng(9).random(len(cols["k"])) < 0.3
        cols["v1"][drop] = 1000
        cols["v2"][drop] = 127
        cols["v4"][drop] = -2**15
        return tft.frame(cols), 3, True
    keys = _keys(case)
    return _frame(keys, seed=len(keys)), 1, False   # v1 packs, v2 not


@pytest.mark.parametrize("case", ["uniform_2e4", "shard_missing_groups",
                                  "all_narrow", "filtered_out_of_range"])
def test_window_route_equals_scatter_route(case, tpu_route, monkeypatch):
    df, packed, filtered = _route_case(case)
    agg = _sums(df)
    keys = np.asarray(df.blocks()[0].dense("k"))
    groups = len(np.unique(keys))

    def query():
        dist = distribute(df, local_mesh(4))
        if filtered:
            dist = dfilter(lambda v1: v1 < 100, dist)
        return _tables(daggregate(agg, dist, "k", max_groups=groups))

    got, moved = _counted(query)
    assert moved == [1, 0, 1, packed]
    # the same query with the window route taken away: today's scatter-add
    monkeypatch.setattr(D, "_sum_window", lambda *a, **k: (None, False))
    scatter = query()
    if filtered:
        cols = {n: np.asarray(df.blocks()[0].dense(n))
                for n in df.schema.names}
        df = tft.frame({n: c[cols["v1"] < 100] for n, c in cols.items()})
    want = _reference(df)
    _assert_equal_sums(got, want)
    _assert_equal_sums(scatter, want)
    np.testing.assert_allclose(got["v3"], scatter["v3"], rtol=1e-6)


@pytest.mark.parametrize("groups,ranges,widths,key_bits", [
    (1_000_000, [(1, 5), (1, 15)], (3, 4), 27),            # q5, 1e8 rows
    (10_000_000, [(1, 5), (1, 15)], (3, 4), 31),           # q5, 1e9 rows
    (10_000_000, [(0, 2**10), (7, 2**10 + 7)], (None, None), 24),
    (1_000, [(-100, -3), (-2**15, -2**15 + 9)], (7, 4), 21),
    (1_000, [(7, 7), None, (-3, -3)], (0, None, 0), 10),
    (1, [(-2**31, 2**31 - 1), (0, 1)], (None, 1), 2),
    (1_000_000, [(0, 4095)], (12,), 32),
], ids=["1e6_q5", "1e7_q5", "1e7_wide", "negative_mins", "constant",
        "full_int32", "uint32_key"])
def test_sort_key_plan(groups, ranges, widths, key_bits):
    plan = SR.sort_key_plan(groups, ranges)
    assert plan.widths == widths
    assert plan.key_bits == key_bits
    assert plan.packed == sum(w is not None for w in widths)
    assert plan.dtype == (jnp.uint32 if key_bits == 32 else jnp.int32)


@pytest.mark.parametrize("dtype,low,high", [
    (np.int8, -100, 27), (np.int16, -1100, -1000), (np.uint8, 3, 130),
    (np.int32, -5, 5)])
def test_packed_key_sums_narrow_dtypes_exactly(dtype, low, high):
    # segment_sums itself, on columns of their own width: each packed
    # value unpacks to itself, and a row with an invalid id may hold any
    # value, out of range or not, in any field
    rng = np.random.default_rng(21)
    n, groups, block_rows = 30_000, 2_000, 1024
    ids = rng.integers(-1, groups + 5, n).astype(np.int32)
    valid = (ids >= 0) & (ids < groups)
    a = rng.integers(low, high + 1, n).astype(dtype)
    b = rng.integers(1, 6, n).astype(np.int32)
    c = (rng.random(n) * 100).astype(np.float32)
    info = np.iinfo(dtype)
    a[~valid] = rng.integers(info.min, info.max, (~valid).sum(),
                             dtype=np.int64).astype(dtype)
    b[~valid] = -77
    ranges = [(int(v[valid].min()), int(v[valid].max())) for v in (a, b)]
    plan = SR.sort_key_plan(groups, ranges + [None])
    assert plan.packed == 2
    mins = np.array([lo & 0xFFFFFFFF for lo, _ in ranges], np.uint32)
    span = int(SR.sorted_block_span(jnp.asarray(ids), groups, block_rows))
    got = SR.segment_sums([a, b, c], ids, groups,
                          window=(block_rows, span, plan), mins=mins)
    for col, out in zip((a, b), got):
        acc = np.zeros(groups, np.int64)
        np.add.at(acc, ids[valid], col[valid].astype(np.int64))
        want = acc.astype(np.uint64).astype(
            np.dtype(f"uint{8 * col.dtype.itemsize}")).view(col.dtype)
        assert out.dtype == col.dtype
        assert np.array_equal(np.asarray(out), want)
    want = np.zeros(groups, np.float64)
    np.add.at(want, ids[valid], c[valid].astype(np.float64))
    np.testing.assert_allclose(np.asarray(got[2]), want, rtol=1e-5)


def test_span_read_once_and_cached_beside_the_ids(tpu_route, monkeypatch):
    keys = _keys("uniform_2e4")
    dist = distribute(_frame(keys), local_mesh(4))
    reads = []
    prog = D._window_span_program

    def counting(*args):
        reads.append(args)
        return prog(*args)

    monkeypatch.setattr(D, "_window_span_program", counting)
    for _ in range(3):
        _, moved = _counted(
            lambda: daggregate(AGG, dist, "k", max_groups=20_000))
        assert moved == [1, 0, 1, 1]
    assert len(reads) == 1


def test_column_ranges_read_once_and_cached_beside_the_ids(tpu_route,
                                                          monkeypatch):
    # a column's range does not depend on the key: a second key reads no
    # range again, and the ranges evict no group-id entry
    keys = _keys("uniform_2e4")
    df = _frame(keys)
    cols = {n: np.asarray(df.blocks()[0].dense(n)) for n in df.schema.names}
    cols["k2"] = (keys * 7 + 3) % 15_000
    dist = distribute(tft.frame(cols), local_mesh(4))
    reads = []
    prog = D._column_ranges_program

    def counting(*args):
        reads.append(args)
        return prog(*args)

    monkeypatch.setattr(D, "_column_ranges_program", counting)
    for key, groups in (("k", 20_000), ("k", 20_000), ("k2", 15_000)):
        _, moved = _counted(
            lambda: daggregate(AGG, dist, key, max_groups=groups))
        assert moved == [1, 0, 1, 1]
    assert len(reads) == 1
    assert dist._column_ranges == {
        "v1": (int(cols["v1"].min()), int(cols["v1"].max())),
        "v2": (int(cols["v2"].min()), int(cols["v2"].max()))}
    assert {("device", ("k",), 20_000), ("device", ("k2",), 15_000)} \
        <= set(dist._group_ids_cache)


def test_frames_of_equal_widths_share_one_program(tpu_route, monkeypatch):
    # the minimums are operands of the program, not constants in it: a
    # frame whose int columns start elsewhere, with the same bit widths,
    # compiles nothing new
    monkeypatch.setattr(D, "_collective_cache", OrderedDict())
    keys = _keys("uniform_2e4")
    base = {n: np.asarray(_frame(keys, narrow=True).blocks()[0].dense(n))
            for n in ("k", "v1", "v2", "v3", "v4")}
    shifted = dict(base, v1=base["v1"] + 1_000,
                   v2=(base["v2"] - 20).astype(np.int8),
                   v4=(base["v4"] + 3_000).astype(np.int16))
    was = tracing.enabled()
    tracing.enable()
    try:
        compiles = []
        for cols in (base, shifted):
            df = tft.frame(cols)
            since = time.monotonic_ns()
            out, moved = _counted(lambda: daggregate(
                _sums(df), distribute(df, local_mesh(4)), "k",
                max_groups=20_000))
            assert moved == [1, 0, 1, 3]
            compiles.append(sum(r.name == "compile.daggregate"
                                for r in tracing.span_records(since)))
            _assert_equal_sums(_tables(out), _reference(df))
    finally:
        if not was:
            tracing.disable()
    assert compiles == [1, 0]


def test_zipf_key_past_the_widest_window_falls_back(tpu_route):
    # a Zipf key: the rare keys hold a row each and most sit on one shard
    # only, so a block of a shard's sorted ids spans past the widest window
    rng = np.random.default_rng(5)
    keys = rng.zipf(1.3, 60_000) % 1_000_003
    groups = len(np.unique(keys))
    assert not pallas_fits(groups + 1, 1)
    df = _frame(keys, seed=2)
    dist = distribute(df, local_mesh(4))
    out, moved = _counted(
        lambda: daggregate(AGG, dist, "k", max_groups=groups))
    assert moved == [0, 1, 0, 0]
    _assert_equal_sums(_tables(out), _reference(df))


def test_few_groups_keep_the_one_hot_kernel(tpu_route):
    keys = np.random.default_rng(1).integers(0, 100, 10_000)
    dist = distribute(_frame(keys), local_mesh(4))
    _, moved = _counted(lambda: daggregate(AGG, dist, "k", max_groups=100))
    assert moved == [0, 0, 0, 0]


def test_folded_daggregate_is_unchanged(tpu_route):
    # plan/dist.py's folded daggregate (host keys, folded into a lazy
    # chain's program) builds _monoid_agg_shard_fn without a window:
    # scatter-add, no span read; the eager chain takes the window
    from tensorframes_tpu.parallel import dmap_blocks

    keys = _keys("uniform_2e4")
    dist = distribute(_frame(keys), local_mesh(4))
    agg = {"v1": "sum", "v2": "sum", "w": "sum"}

    def chain(d):
        return dmap_blocks(lambda v3: {"w": v3 * 1.0}, d).select(
            ["k", "v1", "v2", "w"])

    folds = counters.get("dplan.fused_programs")
    out, moved = _counted(lambda: daggregate(agg, chain(dist.lazy()), "k"))
    assert moved == [0, 0, 0, 0]
    assert counters.get("dplan.fused_programs") > folds
    eager, moved = _counted(lambda: daggregate(agg, chain(dist), "k"))
    assert moved == [1, 0, 1, 1]
    got, want = _tables(out), _tables(eager)
    for c in ("k", "v1", "v2"):
        assert np.array_equal(got[c], want[c])
    np.testing.assert_allclose(got["w"], want["w"], rtol=1e-6)


def test_off_the_tpu_scatter_add_is_kept():
    keys = _keys("uniform_2e4")
    df = _frame(keys)
    out, moved = _counted(lambda: daggregate(
        AGG, distribute(df, local_mesh(4)), "k", max_groups=20_000))
    assert moved == [0, 0, 0, 0]
    _assert_equal_sums(_tables(out), _reference(df))


@pytest.mark.parametrize("nonfinite", ["filtered_out", "kept"])
def test_filtered_frame_with_nonfinite_values(nonfinite, tpu_route):
    # dfilter leaves the dropped rows' values at each shard's tail with id
    # -1: they sort to the front, beside valid rows, and must add nothing.
    # A NaN or inf the filter keeps stays in its own group
    keys = np.random.default_rng(12).integers(0, 8_000, 200_000)
    df = _frame(keys, seed=3)
    b = df.blocks()[0]
    v3 = np.asarray(b.dense("v3")).copy()
    rng = np.random.default_rng(4)
    v3[rng.choice(len(v3), 2_000, replace=False)] = np.nan
    v3[rng.choice(len(v3), 500, replace=False)] = np.inf
    v3[rng.choice(len(v3), 500, replace=False)] = -np.inf
    cols = {n: np.asarray(b.dense(n)) for n in ("k", "v1", "v2")}
    df = tft.frame(dict(cols, v3=v3))
    if nonfinite == "filtered_out":
        keep = lambda v3: jnp.abs(v3) < jnp.inf  # noqa: E731
        mask = np.abs(v3) < np.inf
    else:
        keep = lambda v1: v1 > 1  # noqa: E731
        mask = cols["v1"] > 1
    dist = dfilter(keep, distribute(df, local_mesh(4)))
    out, moved = _counted(lambda: daggregate(AGG, dist, "k",
                                             max_groups=8_000))
    assert moved == [1, 0, 1, 1]
    got = _tables(out)
    want = _reference(tft.frame({n: c[mask] for n, c in
                                 dict(cols, v3=v3).items()}))
    assert np.isfinite(want["v3"]).all() == (nonfinite == "filtered_out")
    np.testing.assert_array_equal(np.isnan(got["v3"]), np.isnan(want["v3"]))
    finite = np.isfinite(want["v3"])
    np.testing.assert_array_equal(got["v3"][~finite], want["v3"][~finite])
    got["v3"], want["v3"] = got["v3"][finite], want["v3"][finite]
    _assert_equal_sums(got, want)
