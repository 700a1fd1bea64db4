"""The demos are acceptance workloads (SURVEY.md §2.5): each must run at
small scale and produce verifiably correct numbers against plain numpy."""

import numpy as np
import pytest

import tensorframes_tpu as tft

from demos import geom_mean as gm
from demos import groupby_scratch as gs
from demos import kmeans as km


# -- kmeans -----------------------------------------------------------------

@pytest.fixture(scope="module")
def km_data():
    return km.make_data(n=200, num_features=3, k=2, num_partitions=3)


def _numpy_step(pts, centers):
    d = ((pts[:, None, :] - centers[None, :, :]) ** 2).sum(-1)
    idx = d.argmin(1)
    new = np.stack([
        pts[idx == j].mean(0) if (idx == j).any() else centers[j]
        for j in range(centers.shape[0])])
    return new, float(d.min(1).sum())


@pytest.mark.parametrize("step", [km.step_aggregate, km.step_preaggregate],
                         ids=["aggregate", "preaggregate"])
def test_kmeans_step_matches_numpy(km_data, step):
    df, init, _ = km_data
    pts = np.concatenate([b.dense("features") for b in df.blocks()])
    got_c, got_d = step(df, init)
    want_c, want_d = _numpy_step(pts, init)
    np.testing.assert_allclose(got_c, want_c, rtol=1e-5)
    assert got_d == pytest.approx(want_d, rel=1e-5)


def test_kmeans_converges_to_true_centers(km_data):
    df, init, true_centers = km_data
    centers, history = km.kmeans(df, init, num_iters=30)
    assert history == sorted(history, reverse=True)  # monotone improvement
    # each true center has a learned center within the blob radius
    for t in true_centers:
        assert np.linalg.norm(centers - t, axis=1).min() < 0.5


def test_kmeans_device_resident_step_matches(km_data):
    from tensorframes_tpu.parallel.distributed import distribute
    from tensorframes_tpu.parallel.mesh import local_mesh

    df, init, _ = km_data
    pts = np.concatenate([b.dense("features") for b in df.blocks()])
    dist = distribute(df, local_mesh())
    got_c, got_d = km.step_device_resident(dist, init)
    want_c, want_d = _numpy_step(pts, init)
    np.testing.assert_allclose(got_c, want_c, rtol=1e-5)
    assert got_d == pytest.approx(want_d, rel=1e-5)


# -- harmonic / geometric mean ----------------------------------------------

def test_harmonic_mean_per_key():
    df = gm.make_data(n=30)
    rows = gm.harmonic_mean_per_key(df).collect()
    x = np.concatenate([b.dense("x") for b in df.blocks()])
    keys = np.concatenate(
        [np.asarray([c for c in b.columns["key"]]) for b in df.blocks()])
    got = {r["key"]: r["harmonic_mean"] for r in rows}
    assert set(got) == {"g0", "g1", "g2"}
    for g in got:
        grp = x[keys == g]
        want = len(grp) / (1.0 / grp).sum()
        assert got[g] == pytest.approx(want, rel=1e-6)


def test_geometric_mean_per_key():
    df = gm.make_data(n=30)
    rows = gm.geometric_mean_per_key(df).collect()
    x = np.concatenate([b.dense("x") for b in df.blocks()])
    keys = np.concatenate(
        [np.asarray([c for c in b.columns["key"]]) for b in df.blocks()])
    for r in rows:
        grp = x[keys == r["key"]]
        want = np.exp(np.log(grp).mean())
        assert r["geometric_mean"] == pytest.approx(want, rel=1e-6)


def test_string_key_and_unused_column_ride_along():
    # the two reference-found bugs: a string column in the frame, and a
    # numeric column unused by the computation — both must pass through
    df = gm.make_data(n=12)
    out = tft.map_blocks(lambda x: {"y": x * 2.0}, df)
    rows = out.collect()
    assert rows[0].fields == ("key", "x", "y")
    assert isinstance(rows[0]["key"], str)


# -- groupby scratch + README examples --------------------------------------

def test_groupby_sum():
    rows = gs.groupby_sum()
    # keys: 1,2 -> '0'; 3,4,5 -> '1'
    assert [(r["key"], r["x"]) for r in rows] == [("0", 3.0), ("1", 12.0)]


def test_readme_map_blocks():
    rows = gs.readme_map_blocks()
    assert [r["z"] for r in rows] == [3.0, 4.0, 5.0, 6.0, 7.0]


def test_readme_reduce_vector():
    s, m = gs.readme_reduce_vector()
    np.testing.assert_allclose(s, [3.0, 3.0])
    np.testing.assert_allclose(m, [1.0, 1.0])


def test_readme_dsl_map():
    rows = gs.readme_dsl_map()
    np.testing.assert_allclose([r["z"] for r in rows],
                               np.arange(5.0) * 0.1 + 3.0)


def test_kmeans_daggregate_step_matches(km_data):
    # variant D: the groupBy shuffle at mesh scale (device-side keys)
    from tensorframes_tpu.parallel.distributed import distribute
    from tensorframes_tpu.parallel.mesh import local_mesh

    df, init, _ = km_data
    pts = np.concatenate([b.dense("features") for b in df.blocks()])
    dist = distribute(df, local_mesh())
    got_c, got_d = km.step_daggregate(dist, init)
    want_c, want_d = _numpy_step(pts, init)
    np.testing.assert_allclose(got_c, want_c, rtol=1e-5)
    assert got_d == pytest.approx(want_d, rel=1e-5)


# -- LM training loop (frames as data path + mesh step + checkpoint) --------

def test_train_lm_learns_and_resumes(tmp_path):
    from demos import train_lm as tl
    from tensorframes_tpu.parallel.mesh import local_mesh

    mesh = local_mesh()
    root = str(tmp_path / "ckpt")
    kw = dict(batch=8, seq_len=16, vocab=32,
              checkpoint_root=root, checkpoint_every=4)

    _, losses = tl.train(mesh, n_steps=8, **kw)
    assert len(losses) == 8
    assert losses[-1] < losses[0]          # it learns

    # resume from the step-8 checkpoint; only steps 8..12 run
    _, more = tl.train(mesh, n_steps=12, resume=True, **kw)
    assert len(more) == 4

    # uninterrupted reference run over the same schedule, fresh root
    _, full = tl.train(mesh, n_steps=12, batch=8, seq_len=16, vocab=32,
                       checkpoint_root=str(tmp_path / "ckpt2"),
                       checkpoint_every=100)
    np.testing.assert_allclose(more, full[8:], rtol=1e-4, atol=1e-5)


def test_train_lm_corpus_is_frame_partitioned():
    from demos import train_lm as tl

    df = tl.corpus_frame(n_batches=3, batch=4, seq_len=8, vocab=16)
    blocks = df.blocks()
    assert len(blocks) == 3
    toks = blocks[0].dense("tokens")
    assert toks.shape == (4, 9)
    # modular-increment property: constant per-row step of 1 or 2
    diffs = np.diff(toks, axis=1) % 16
    assert set(np.unique(diffs)) <= {1, 2}
    assert (diffs == diffs[:, :1]).all()


# -- analytics pipeline (csv -> filter -> mesh aggregate -> rank) -----------

def test_analytics_pipeline_matches_numpy(tmp_path):
    from demos import analytics as an

    csv_path = str(tmp_path / "readings.csv")
    an.make_csv(csv_path, n=3000, sites=3, sensors=4, seed=5)
    ranked = an.pipeline(csv_path)
    rows = ranked.collect()

    # numpy recomputation from the raw file
    raw = np.genfromtxt(csv_path, delimiter=",", names=True)
    keep = raw["value"] >= 0
    ref = {}
    for s, d, v in zip(raw["site"][keep].astype(int),
                       raw["sensor"][keep].astype(int),
                       raw["value"][keep]):
        ref[(s, d)] = ref.get((s, d), 0.0) + v
    got = {(r["site"], r["sensor"]): r["value"] for r in rows}
    assert set(got) == set(ref)
    for k in ref:
        assert got[k] == pytest.approx(ref[k], rel=1e-5)
    totals = [r["value"] for r in rows]
    assert totals == sorted(totals, reverse=True)
