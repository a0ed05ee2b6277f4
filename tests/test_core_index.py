"""Dynamic index: build/search/update correctness and APS behaviour."""
import numpy as np
import pytest

from repro.core import QuakeConfig, QuakeIndex
from repro.data import datasets


@pytest.fixture(scope="module")
def clustered():
    return datasets.clustered(6000, 24, n_clusters=32, seed=0)


def _recall_of(index, ds, k=10, n=40, target=0.9, seed=1, **kw):
    rng = np.random.default_rng(seed)
    gt_all, got = [], []
    q = datasets.queries_near(ds, n, seed=seed)
    gt = ds.ground_truth(q, k)
    rs = []
    for i in range(n):
        r = index.search(q[i], k, recall_target=target, **kw)
        rs.append(len(set(r.ids.tolist()) & set(gt[i].tolist())) / k)
    return float(np.mean(rs))


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_build_search_recall(clustered, metric):
    ds = datasets.clustered(6000, 24, n_clusters=32, seed=0, metric=metric)
    idx = QuakeIndex.build(ds.vectors, config=QuakeConfig(metric=metric),
                           kmeans_iters=4)
    idx.check_invariants()
    assert _recall_of(idx, ds) >= 0.85


def test_multilevel_matches_flat(clustered):
    ds = clustered
    flat = QuakeIndex.build(ds.vectors, num_partitions=96, kmeans_iters=4)
    two = QuakeIndex.build(ds.vectors, level_sizes=(96, 12), kmeans_iters=4)
    two.check_invariants()
    r_flat = _recall_of(flat, ds)
    r_two = _recall_of(two, ds)
    assert r_two >= r_flat - 0.1   # hierarchy must not wreck recall


def test_insert_then_search(clustered):
    ds = clustered
    idx = QuakeIndex.build(ds.vectors[:4000], ids=np.arange(4000),
                           kmeans_iters=4)
    idx.insert(ds.vectors[4000:], np.arange(4000, 6000))
    idx.check_invariants()
    assert idx.num_vectors == 6000
    # new vectors must be findable
    q = ds.vectors[5000]
    r = idx.search(q, 5, recall_target=0.95)
    assert 5000 in r.ids.tolist()


def test_delete_removes(clustered):
    ds = clustered
    idx = QuakeIndex.build(ds.vectors, ids=np.arange(ds.n), kmeans_iters=4)
    victims = np.arange(0, 3000)
    removed = idx.delete(victims)
    idx.check_invariants()
    assert removed == 3000
    assert idx.num_vectors == ds.n - 3000
    r = idx.search(ds.vectors[100], 10)
    assert not np.isin(r.ids, victims).any()


def test_aps_adapts_nprobe_to_target(clustered):
    """Higher recall targets must scan at least as many partitions."""
    ds = clustered
    idx = QuakeIndex.build(ds.vectors, kmeans_iters=4)
    q = datasets.queries_near(ds, 20, seed=3)
    n_low = [idx.search(qi, 10, recall_target=0.5).nprobe[0] for qi in q]
    n_high = [idx.search(qi, 10, recall_target=0.99).nprobe[0] for qi in q]
    assert np.mean(n_high) >= np.mean(n_low)


def test_fixed_nprobe_baseline(clustered):
    ds = clustered
    idx = QuakeIndex.build(ds.vectors, kmeans_iters=4)
    r1 = idx.search(ds.vectors[0], 10, nprobe=1)
    r8 = idx.search(ds.vectors[0], 10, nprobe=8)
    assert r8.nprobe[0] == 8 and r1.nprobe[0] == 1
    assert r8.dists[-1] <= r1.dists[-1] + 1e-6  # more probes only improve


def test_recall_estimate_tracks_true_recall(clustered):
    """APS estimate should be well-calibrated on average (paper Table 5:
    estimate-driven termination lands near the target)."""
    ds = clustered
    idx = QuakeIndex.build(ds.vectors, kmeans_iters=4)
    q = datasets.queries_near(ds, 50, seed=5)
    gt = ds.ground_truth(q, 10)
    true_r, est_r = [], []
    for i in range(len(q)):
        r = idx.search(q[i], 10, recall_target=0.9)
        true_r.append(len(set(r.ids.tolist()) & set(gt[i].tolist())) / 10)
        est_r.append(r.recall_estimate)
    assert np.mean(true_r) >= 0.85
    assert abs(np.mean(est_r) - np.mean(true_r)) < 0.12


def test_calibrate_aps_keeps_model_that_meets_target(clustered):
    idx = QuakeIndex.build(clustered.vectors, num_partitions=32,
                           config=QuakeConfig(recall_target=0.9))
    cal = idx.aps_calibration
    assert cal["met"] and len(cal["tried"]) == 1
    assert idx.geometry_dim == idx.model_dim == clustered.dim
    assert idx.aps_f_m == idx.config.f_m


def test_calibrate_aps_fits_model_where_it_stops_early():
    # topics of isotropic noise at d=768: a query's neighbours spread over
    # many partitions and the unfitted cap model stops early
    from repro.data import wikipedia
    from repro.data.workload import IncrementalGroundTruth
    wl = wikipedia.wikipedia_workload(n_total=20_000, dim=768, months=1,
                                      queries_per_month=64, seed=0)
    q = [op.queries for op in wl.operations if op.kind == "query"][0]
    truth = IncrementalGroundTruth(wl.dataset, wl.initial_ids).topk(q, 10)
    idx = QuakeIndex.build(wl.initial_vectors, wl.initial_ids,
                           config=QuakeConfig(metric="ip"))
    cal = idx.aps_calibration
    assert cal["tried"][0][2] < cal["target"]      # the unfitted model
    assert cal["met"] and cal["recall"] >= cal["target"]
    assert idx.geometry_dim < idx.model_dim

    def recall():
        res = [idx.search(x, 10, record_stats=False) for x in q]
        return np.mean([len(set(r.ids.tolist()) & set(t.tolist())) / 10
                        for r, t in zip(res, truth)])

    fitted = recall()
    idx.set_aps_model(idx.config.f_m, idx.model_dim)
    assert fitted >= 0.9 > recall()


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_build_groups_rows_in_their_order(metric):
    """The build's one-sort grouping: every row lands in exactly one
    partition, each partition holds its rows in row order with their ids
    and norms, and the largest squared norm is the whole corpus's."""
    ds = datasets.clustered(3000, 16, n_clusters=12, seed=3, metric=metric)
    x = ds.vectors
    ids = np.arange(len(x)) * 5 + 11
    idx = QuakeIndex.build(x, ids, config=QuakeConfig(metric=metric),
                           kmeans_iters=3)
    lvl0 = idx.levels[0]
    seen = np.concatenate(lvl0.ids)
    assert np.array_equal(np.sort(seen), ids)
    for j in range(lvl0.num_partitions):
        rows = (lvl0.ids[j] - 11) // 5
        assert np.all(np.diff(rows) > 0)
        assert np.array_equal(lvl0.vectors[j], x[rows])
        assert np.array_equal(lvl0.sqnorms[j], np.sum(
            x[rows].astype(np.float64) ** 2, axis=1).astype(np.float32))
        assert all(idx.id_map[int(e)] == j for e in lvl0.ids[j])
    assert idx._max_norm_sq == float(np.max(np.sum(
        x.astype(np.float64) ** 2, axis=1)))


def test_calibrate_aps_runs_match_one_partition_at_a_time(clustered,
                                                          monkeypatch):
    """Sorting the exact candidates in runs of partitions picks the same
    neighbours, and so fits the same model, as sorting after each
    partition."""
    idx = QuakeIndex.build(clustered.vectors, kmeans_iters=3)
    runs = idx.calibrate_aps()
    monkeypatch.setattr(QuakeIndex, "_CALIB_RUN", 1)
    assert idx.calibrate_aps() == runs
