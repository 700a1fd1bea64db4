"""Device group ids built per chip (``parallel/distributed.py``): on a mesh
of several data shards each chip sort-uniques its own rows and the
per-chip tables are merged; the result equals the one-program build bit
for bit, and no row of the table crosses to the host."""

import importlib.util
import json
import os

import jax
import numpy as np
import pytest

import tensorframes_tpu as tft
from tensorframes_tpu.parallel import distribute, local_mesh
from tensorframes_tpu.parallel import distributed as D
from tensorframes_tpu.parallel.distributed import daggregate
from tensorframes_tpu.utils import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
I32_MAX = np.iinfo(np.int32).max
CAP = 40


def _keys(case: str, n: int, chips: int) -> np.ndarray:
    """int32 keys for ``case`` over ``n`` rows (``chips`` row shards)."""
    rng = np.random.default_rng([n, chips])
    if case == "duplicated_across_chips":
        return rng.integers(0, CAP // 2, n).astype(np.int32)
    if case == "uneven_pad":
        return rng.integers(-5, CAP - 5, n).astype(np.int32)
    if case == "sentinel_key":
        k = rng.integers(0, 10, n).astype(np.int32)
        k[n // 2] = I32_MAX
        return k
    if case == "one_chip_overflows":
        # the first shard alone holds more than CAP distinct keys
        k = rng.integers(0, 4, n).astype(np.int32)
        k[:CAP + 3] = np.arange(1000, 1000 + CAP + 3)
        return k
    if case == "merge_overflows":
        # every chip holds at most CAP distinct keys; together they hold more
        per = -(-n // chips)
        span = CAP * 3 // 4
        return (np.arange(n) // per * span
                + rng.integers(0, span, n)).astype(np.int32)
    raise ValueError(case)


CASES = {"duplicated_across_chips": 96, "uneven_pad": 101,
         "sentinel_key": 64, "one_chip_overflows": 200,
         "merge_overflows": 160}


def _assert_same(a, b):
    for x, y in zip(a, b):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert np.array_equal(x, y)


@pytest.mark.parametrize("chips", [2, 4, 8])
@pytest.mark.parametrize("case", sorted(CASES) + ["composite_keys"])
def test_per_chip_build_equals_one_program(case, chips, monkeypatch):
    mesh = local_mesh(chips)
    if case == "composite_keys":
        n = 99
        rng = np.random.default_rng(chips)
        df = tft.frame({"a": rng.integers(0, 5, n).astype(np.int32),
                        "b": rng.integers(-3, 4, n).astype(np.int32)})
        dist = distribute(df, mesh)
        got = D._device_key_ids(dist, ["a", "b"], CAP)
        monkeypatch.setattr(D, "_device_ids", lambda m, kc, vm, cap:
                            D._build_device_ids(kc, vm, cap))
        want = D._device_key_ids(dist, ["a", "b"], CAP)
        assert got[0].sharding.is_equivalent_to(dist.mesh.row_sharding(1), 1)
        _assert_same([got[0], got[1][1], got[2]], [want[0], want[1][1],
                                                   want[2]])
        for u, w in zip(got[1][2], want[1][2]):
            _assert_same([u], [w])
        return
    n = CASES[case]
    keys = _keys(case, n, chips)
    dist = distribute(tft.frame({"k": keys}), mesh)
    kc, vm = dist.columns["k"], D._valid_dev(dist)
    got = D._build_device_ids_per_chip(mesh, kc, vm, CAP)
    want = D._build_device_ids(kc, vm, CAP)
    _assert_same(got, want)
    count, hit = int(got[2]), bool(got[3])
    assert hit is (case == "sentinel_key")
    assert (count > CAP) is case.endswith("overflows")
    if case == "uneven_pad":
        assert dist.padded_rows > n
        assert (np.asarray(got[0])[n:] == -1).all()


@pytest.mark.parametrize("case", ["one_chip_overflows", "merge_overflows"])
def test_overflow_raises(case):
    keys = _keys(case, CASES[case], 4)
    dist = distribute(tft.frame({"k": keys, "v": np.ones(len(keys))}),
                      local_mesh(4))
    with pytest.raises(ValueError, match="max_groups"):
        daggregate({"v": "sum"}, dist, "k", max_groups=CAP)


def test_sentinel_key_raises():
    keys = _keys("sentinel_key", 64, 4)
    dist = distribute(tft.frame({"k": keys, "v": np.ones(64)}),
                      local_mesh(4))
    with pytest.raises(tft.engine.ops.InvalidTypeError, match="sentinel"):
        daggregate({"v": "sum"}, dist, "k", max_groups=CAP)


def test_one_shard_runs_the_one_program():
    mesh = local_mesh(1)
    dist = distribute(tft.frame({"k": _keys("uneven_pad", 50, 1)}), mesh)
    kc, vm = dist.columns["k"], D._valid_dev(dist)
    before = tracing.counters.get("mesh.group_ids_two_level")
    _assert_same(D._device_ids(mesh, kc, vm, CAP),
                 D._build_device_ids(kc, vm, CAP))
    assert tracing.counters.get("mesh.group_ids_two_level") == before


@pytest.fixture
def traced():
    was, obs = tracing.enabled(), tracing._span_observer
    tracing.enable()
    tracing.timings.reset()
    try:
        yield
    finally:
        tracing.set_span_observer(obs)
        if not was:
            tracing.disable()


def test_spans_and_counters_on_four_chips(traced):
    n, cap = 1000, 64
    rng = np.random.default_rng(3)
    df = tft.frame({"k": rng.integers(0, 50, n).astype(np.int32),
                    "v1": rng.integers(0, 9, n).astype(np.int32),
                    "v3": rng.random(n).astype(np.float32)})
    dist = distribute(df, local_mesh(4))
    two = tracing.counters.get("mesh.group_ids_two_level")
    moved = tracing.counters.get("mesh.allreduce_bytes")
    for _ in range(2):
        daggregate({"v1": "sum", "v3": "sum"}, dist, "k", max_groups=cap)
    assert tracing.counters.get("mesh.group_ids_two_level") - two == 1
    # per query, one [cap + 1] table of each 4-byte column
    assert tracing.counters.get("mesh.allreduce_bytes") - moved \
        == 2 * 2 * (cap + 1) * 4
    recs = tracing.span_records()
    outer = {r.id for r in recs if r.name == "daggregate.group_ids"}
    local, = [r for r in recs if r.name == "daggregate.group_ids.local"]
    merge, = [r for r in recs if r.name == "daggregate.group_ids.merge"]
    for r in (local, merge):
        assert r.parent in outer
        assert r.attrs == {"chips": 4, "rows": n, "local_groups_max": 50,
                           "gathered_bytes": 4 * (cap + 1) * 4}
    assert local.end_ns <= merge.start_ns
    dispatch = [r for r in recs if r.name == "daggregate.dispatch"]
    assert len(dispatch) == 2
    assert all(r.attrs == {"chips": 4, "table_groups": cap + 1}
               for r in dispatch)


def test_no_row_reaches_the_host(monkeypatch):
    """Only the scalar readbacks and the result tables leave the device
    during the group-id build and the query: every read of a device array
    into host memory is of at most ``cap + 1`` entries.

    The transfer guard refuses implicit reads on a chip; on the CPU numpy
    reads a device array through the buffer protocol, which the guard does
    not see, so the test also wraps numpy's converters and the array's
    own host value."""
    from jax._src.array import ArrayImpl

    n, cap = 4000, 500
    rng = np.random.default_rng(5)
    df = tft.frame({"k": rng.integers(0, 400, n).astype(np.int32),
                    "v": rng.integers(0, 9, n).astype(np.int32)})
    dist = distribute(df, local_mesh(4))
    read = []

    def seen(x):
        if isinstance(x, jax.Array):
            read.append(x.size)
            assert x.size <= cap + 1, f"{x.size} rows reached the host"

    def wrapped(fn):
        def f(a, *args, **kwargs):
            seen(a)
            return fn(a, *args, **kwargs)
        return f

    for name in ("asarray", "array", "ascontiguousarray"):
        monkeypatch.setattr(np, name, wrapped(getattr(np, name)))
    value = ArrayImpl._value
    monkeypatch.setattr(ArrayImpl, "_value",
                        property(lambda a: (seen(a), value.fget(a))[1]))
    with jax.transfer_guard_device_to_host("disallow"):
        out = daggregate({"v": "sum"}, dist, "k", max_groups=cap)
    monkeypatch.undo()
    assert read and max(read) == cap + 1
    got = {r["k"]: r["v"] for r in out.collect()}
    keys = np.asarray(df.blocks()[0].dense("k"))
    vals = np.asarray(df.blocks()[0].dense("v"))
    assert got == {int(k): int(vals[keys == k].sum()) for k in np.unique(keys)}


def _cellbench(*parts):
    """A module of ``cellbench/`` loaded by its path."""
    path = os.path.join(ROOT, "cellbench", *parts)
    spec = importlib.util.spec_from_file_location(
        "test_" + "_".join(parts).replace(".py", ""), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_q5_on_four_chips_equals_the_reference():
    """db-benchmark q5 (sum v1:v3 by id6) on a 4-device mesh against the
    cell's plain reference, at 1e6 rows and 1e4 groups."""
    with open(os.path.join(ROOT, "cellbench", "configs",
                           "h2o_groupby_g1e9_k100.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "cellbench", "limits",
                           "h2o1e9.q5_k10m_4chip.json")) as f:
        limits = json.load(f)
    groups = 10_000
    cfg["rows"] = 1_000_000
    for c in cfg["columns"]:
        if c["name"] == "id6":
            c["high"] = groups
    ref = _cellbench("reference", "h2o_groupby_g1e9_k100.py")
    gen = _cellbench("systems", "frame_groupby_sharded.py")
    cols = gen.generate(cfg, 2**31 + 99)
    # the chip's dtypes: x64 is off there
    dev = {k: (v.astype(np.float32) if v.dtype == np.float64 else v)
           for k, v in cols.items()}
    agg = {"v1": "sum", "v2": "sum", "v3": "sum"}
    dist = distribute(tft.frame(dev), local_mesh(4))
    out = daggregate(agg, dist.select(["id6", "v1", "v2", "v3"]), "id6",
                     max_groups=groups)
    got = {n: np.concatenate([np.asarray(b.dense(n)) for b in out.blocks()])
           for n in out.schema.names}
    want = ref.groupby(cols, ["id6"], agg)
    order = np.argsort(got["id6"])
    assert np.array_equal(got["id6"][order], want["id6"])
    for c in ("v1", "v2"):
        assert np.array_equal(got[c][order].astype(np.int64), want[c])
    err = np.abs(got["v3"][order] - want["v3"]) / np.abs(want["v3"])
    assert err.max() <= limits["float_sum_rel_err"]
