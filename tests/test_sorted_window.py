"""The sorted-window route of ``daggregate`` on 4 CPU devices.

Past the group count the one-hot kernel holds, ``daggregate`` on a TPU
sums its ``sum`` columns through one sort keyed on the group ids and the
windowed one-hot kernel (``ops/segment_reduce.py``), given the largest
block span of the sorted ids, which is read once and cached beside the
ids. Off the TPU it keeps scatter-add; the ``tpu_route`` fixture takes
the route here all the same, the kernel running in Pallas's TPU
interpreter inside the mesh. The kernel alone is tested in
``test_kernels.py``.
"""

import functools

import numpy as np
import pytest

import jax.numpy as jnp

import tensorframes_tpu as tft
from tensorframes_tpu.ops import segment_reduce as SR
from tensorframes_tpu.ops.segment_reduce import pallas_fits
from tensorframes_tpu.parallel import dfilter, distribute, local_mesh
from tensorframes_tpu.parallel import distributed as D
from tensorframes_tpu.parallel.distributed import daggregate
from tensorframes_tpu.utils.tracing import counters


@pytest.fixture
def tpu_route(monkeypatch):
    """``daggregate`` wants the window as it does on a TPU."""
    monkeypatch.setattr(SR, "window_wanted", functools.partial(
        SR.window_wanted, backend="tpu"))


AGG = {"v1": "sum", "v2": "sum", "v3": "sum"}


def _frame(keys: np.ndarray, seed: int = 0):
    rng = np.random.default_rng(seed)
    n = len(keys)
    return tft.frame({"k": keys.astype(np.int32),
                      "v1": rng.integers(1, 6, n).astype(np.int32),
                      "v2": rng.integers(-2**31, 2**31 - 1, n,
                                         dtype=np.int64).astype(np.int32),
                      "v3": (rng.random(n) * 100).astype(np.float32)})


def _tables(out) -> dict:
    cols = {n: np.concatenate([np.asarray(b.dense(n)) for b in out.blocks()])
            for n in out.schema.names}
    order = np.argsort(cols["k"])
    return {n: c[order] for n, c in cols.items()}


def _reference(df) -> dict:
    """Plain numpy group sums: int32 wrapping as the device's do."""
    b = df.blocks()[0]
    keys = np.asarray(b.dense("k"))
    uniq, inv = np.unique(keys, return_inverse=True)
    out = {"k": uniq}
    for c in ("v1", "v2"):
        acc = np.zeros(len(uniq), np.int64)
        np.add.at(acc, inv, np.asarray(b.dense(c)).astype(np.int64))
        out[c] = acc.astype(np.uint64).astype(np.uint32).view(np.int32)
    acc = np.zeros(len(uniq), np.float64)
    with np.errstate(invalid="ignore"):            # inf + -inf is NaN
        np.add.at(acc, inv, np.asarray(b.dense("v3")).astype(np.float64))
    out["v3"] = acc
    return out


def _assert_equal_sums(got: dict, want: dict):
    assert np.array_equal(got["k"], want["k"])
    for c in ("v1", "v2"):
        assert np.array_equal(got[c], want[c])
    err = np.abs(got["v3"] - want["v3"]) / np.maximum(np.abs(want["v3"]),
                                                      1e-30)
    assert err.max() < 1e-5


def _counted(fn):
    names = ("segment_sum.sorted_window", "segment_sum.window_fallback")
    before = [counters.get(n) for n in names]
    out = fn()
    return out, [counters.get(n) - b for n, b in zip(names, before)]


def _keys(case: str) -> np.ndarray:
    rng = np.random.default_rng(11)
    if case == "uniform_2e4":
        return rng.integers(0, 20_000, 200_000)
    if case == "shard_missing_groups":
        # the second quarter of the rows (the second shard) holds only
        # every seventh group: its sorted ids have gaps
        n, groups = 120_000, 8_000
        keys = rng.integers(0, groups, n)
        q = slice(n // 4, n // 2)
        keys[q] = rng.integers(0, groups // 7, n // 4) * 7
        return keys
    raise ValueError(case)


@pytest.mark.parametrize("case", ["uniform_2e4", "shard_missing_groups"])
def test_window_route_equals_scatter_route(case, tpu_route, monkeypatch):
    keys = _keys(case)
    df = _frame(keys, seed=len(keys))
    groups = len(np.unique(keys))
    dist = distribute(df, local_mesh(4))
    out, (taken, fell) = _counted(
        lambda: daggregate(AGG, dist, "k", max_groups=groups))
    assert (taken, fell) == (1, 0)
    got = _tables(out)
    # the same query with the window route taken away: today's scatter-add
    monkeypatch.setattr(D, "_sum_window", lambda *a, **k: (None, False))
    scatter = _tables(daggregate(AGG, distribute(df, local_mesh(4)), "k",
                                 max_groups=groups))
    for c in ("k", "v1", "v2"):
        assert np.array_equal(got[c], scatter[c])
    np.testing.assert_allclose(got["v3"], scatter["v3"], rtol=1e-6)
    _assert_equal_sums(got, _reference(df))


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
        _, (taken, fell) = _counted(
            lambda: daggregate(AGG, dist, "k", max_groups=20_000))
        assert (taken, fell) == (1, 0)
    assert len(reads) == 1


def test_zipf_key_past_the_widest_window_falls_back(tpu_route):
    # a Zipf key: the rare keys hold a row each and most sit on one shard
    # only, so a block of a shard's sorted ids spans past the widest window
    rng = np.random.default_rng(5)
    keys = rng.zipf(1.3, 60_000) % 1_000_003
    groups = len(np.unique(keys))
    assert not pallas_fits(groups + 1, 1)
    df = _frame(keys, seed=2)
    dist = distribute(df, local_mesh(4))
    out, (taken, fell) = _counted(
        lambda: daggregate(AGG, dist, "k", max_groups=groups))
    assert (taken, fell) == (0, 1)
    _assert_equal_sums(_tables(out), _reference(df))


def test_few_groups_keep_the_one_hot_kernel(tpu_route):
    keys = np.random.default_rng(1).integers(0, 100, 10_000)
    dist = distribute(_frame(keys), local_mesh(4))
    _, moved = _counted(lambda: daggregate(AGG, dist, "k", max_groups=100))
    assert moved == [0, 0]


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
    assert moved == [0, 0]
    assert counters.get("dplan.fused_programs") > folds
    eager, moved = _counted(lambda: daggregate(agg, chain(dist), "k"))
    assert moved == [1, 0]
    got, want = _tables(out), _tables(eager)
    for c in ("k", "v1", "v2"):
        assert np.array_equal(got[c], want[c])
    np.testing.assert_allclose(got["w"], want["w"], rtol=1e-6)


def test_off_the_tpu_scatter_add_is_kept():
    keys = _keys("uniform_2e4")
    df = _frame(keys)
    out, moved = _counted(lambda: daggregate(
        AGG, distribute(df, local_mesh(4)), "k", max_groups=20_000))
    assert moved == [0, 0]
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
    assert moved == [1, 0]
    got = _tables(out)
    want = _reference(tft.frame({n: c[mask] for n, c in
                                 dict(cols, v3=v3).items()}))
    assert np.isfinite(want["v3"]).all() == (nonfinite == "filtered_out")
    np.testing.assert_array_equal(np.isnan(got["v3"]), np.isnan(want["v3"]))
    finite = np.isfinite(want["v3"])
    np.testing.assert_array_equal(got["v3"][~finite], want["v3"][~finite])
    got["v3"], want["v3"] = got["v3"][finite], want["v3"][finite]
    _assert_equal_sums(got, want)
