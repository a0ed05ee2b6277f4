"""Snapshot coherence under streaming mutations (paper §8.2 COW serving).

The batched executor serves searches from a cached device snapshot while
the index mutates; the mutation journal tells it *which partitions*
changed so it patches only those rows (``IndexSnapshot.apply_delta``)
instead of rebuilding the full ``(P, S_cap, d)`` tensor.  These tests pin
the coherence contract: delta-refreshed results must be exactly the
results a fresh full rebuild would produce, under any interleaving of
``insert`` / ``delete`` / ``Maintainer.run`` with ``search_batch``, for
both metrics — and every fallback edge (structural change, capacity
overflow, trimmed journal, lossy truncation, empty batch) must stay safe.
"""
import numpy as np
import pytest

from repro.core import (IndexSnapshot, Maintainer, MutationJournal,
                        QuakeConfig, QuakeIndex)
from repro.core.multiquery import (BatchedSearchExecutor, batch_search,
                                   get_executor, plan_batch)
from repro.data import datasets


# ---------------------------------------------------------------------------
# journal unit semantics
# ---------------------------------------------------------------------------

def test_journal_records_and_folds():
    j = MutationJournal()
    assert j.delta_since(0).empty
    j.record(dirty=[3, 5], reason="insert")
    j.record(dirty=[5, 7], reason="delete")
    d = j.delta_since(0)
    assert d.dirty == {3, 5, 7} and not d.structural
    assert j.delta_since(1).dirty == {5, 7}
    j.record(structural=True, reason="split")
    assert j.delta_since(0).structural
    assert j.delta_since(j.version).empty


def test_journal_trim_floor_forces_rebuild():
    j = MutationJournal(max_entries=2)
    for i in range(5):
        j.record(dirty=[i])
    assert j.delta_since(0) is None          # history lost -> full rebuild
    assert j.delta_since(j.version - 2).dirty == {3, 4}


def test_index_mutations_feed_journal():
    ds = datasets.clustered(1000, 8, n_clusters=8, seed=0)
    idx = QuakeIndex.build(ds.vectors, num_partitions=8, kmeans_iters=2)
    v0 = idx.version
    idx.insert(ds.vectors[:3] + 0.01, np.arange(10_000, 10_003))
    d = idx.journal.delta_since(v0)
    assert d.dirty and not d.structural
    idx.delete(np.arange(10_000, 10_003))
    d2 = idx.journal.delta_since(v0)
    assert d2.dirty >= d.dirty
    # deleting unknown ids is a no-op: no journal entry, no invalidation
    v = idx.version
    assert idx.delete(np.asarray([999_999])) == 0
    assert idx.version == v


# ---------------------------------------------------------------------------
# delta refresh == full rebuild (the coherence contract)
# ---------------------------------------------------------------------------

def _assert_matches_fresh_rebuild(idx, q, k, nprobe):
    """Cached (possibly delta-patched) executor vs a brand-new executor
    that full-rebuilds from the live index: identical results."""
    r_delta = batch_search(idx, q, k, nprobe=nprobe, impl="jnp")
    fresh = BatchedSearchExecutor(idx, impl="jnp")
    r_full = fresh.search(q, k, nprobe=nprobe)
    assert fresh.full_rebuilds == 1 and fresh.delta_refreshes == 0
    np.testing.assert_array_equal(np.sort(r_delta.ids, 1),
                                  np.sort(r_full.ids, 1))
    np.testing.assert_array_equal(np.sort(r_delta.dists, 1),
                                  np.sort(r_full.dists, 1))
    return r_delta


def _brute_force(idx, q, k):
    """Exact top-k over the live index contents (minimization dists)."""
    lvl0 = idx.levels[0]
    x = np.concatenate(lvl0.vectors)
    ids = np.concatenate(lvl0.ids)
    if idx.config.metric == "l2":
        d = (np.sum(x * x, 1)[None, :] + np.sum(q * q, 1)[:, None]
             - 2.0 * (q @ x.T))
    else:
        d = -(q @ x.T)
    order = np.argsort(d, axis=1, kind="stable")[:, :k]
    return ids[order], np.take_along_axis(d, order, axis=1)


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_insert_delta_matches_full_rebuild(metric):
    ds = datasets.clustered(3000, 16, n_clusters=12, seed=1)
    idx = QuakeIndex.build(ds.vectors, num_partitions=24, kmeans_iters=3,
                           config=QuakeConfig(metric=metric))
    q = datasets.queries_near(ds, 16, seed=2)
    batch_search(idx, q, 10, nprobe=6, impl="jnp")      # build snapshot
    ex = get_executor(idx)
    assert ex.full_rebuilds == 1
    idx.insert(q * 0.999, np.arange(50_000, 50_000 + len(q)))
    r = _assert_matches_fresh_rebuild(idx, q, 10, nprobe=6)
    assert ex.delta_refreshes == 1 and ex.full_rebuilds == 1
    # fresh inserts are visible through the patched rows
    assert set(r.ids.ravel().tolist()) & set(range(50_000, 50_016))


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_interleaved_stream_coherence(metric):
    """insert / delete / maintenance interleaved with search_batch: the
    cached executor (delta path) must track a fresh rebuild exactly, and an
    all-partition scan must equal brute force over the live contents."""
    rng = np.random.default_rng(7)
    ds = datasets.clustered(3000, 16, n_clusters=12, seed=3)
    idx = QuakeIndex.build(ds.vectors, num_partitions=24, kmeans_iters=3,
                           config=QuakeConfig(metric=metric))
    maint = Maintainer(idx)
    q = datasets.queries_near(ds, 8, seed=4)
    next_id = 100_000
    live = []
    batch_search(idx, q, 10, nprobe=idx.num_partitions, impl="jnp")
    ex = get_executor(idx)
    for step in range(6):
        op = step % 3
        if op == 0:                       # insert a small batch
            xb = (datasets.queries_near(ds, 12, seed=10 + step)
                  + rng.normal(scale=0.01, size=(12, 16))).astype(np.float32)
            new = np.arange(next_id, next_id + 12)
            idx.insert(xb, new)
            live.extend(new.tolist())
            next_id += 12
        elif op == 1:                     # delete some of them
            drop = live[: len(live) // 2]
            idx.delete(np.asarray(drop, dtype=np.int64))
            live = live[len(live) // 2:]
        else:                             # maintenance (may split/merge)
            for row in q:
                idx.search(row, 10)
            maint.run()
            idx.check_invariants()
        nprobe = idx.num_partitions       # exact scan -> brute-force oracle
        r = _assert_matches_fresh_rebuild(idx, q, 10, nprobe=nprobe)
        gt_ids, gt_d = _brute_force(idx, q, 10)
        np.testing.assert_allclose(np.sort(r.dists, 1), np.sort(gt_d, 1),
                                   rtol=1e-3, atol=1e-3)
        rec = np.mean([len(set(r.ids[i]) & set(gt_ids[i])) / 10
                       for i in range(len(q))])
        assert rec >= 0.99, (step, rec)
    # the stream must have run mostly on the cheap path: every insert /
    # delete step refreshes by patching, never by rebuilding
    assert ex.delta_refreshes >= 2, ex.delta_refreshes
    assert ex.full_rebuilds >= 1, ex.full_rebuilds


def test_structural_change_falls_back_to_rebuild():
    ds = datasets.clustered(2000, 8, n_clusters=8, seed=5)
    idx = QuakeIndex.build(ds.vectors, num_partitions=8, kmeans_iters=2)
    q = datasets.queries_near(ds, 4, seed=6)
    batch_search(idx, q, 5, nprobe=4)
    ex = get_executor(idx)
    idx.journal.record(structural=True, reason="test")
    batch_search(idx, q, 5, nprobe=4)
    assert ex.full_rebuilds == 2 and ex.delta_refreshes == 0


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_split_to_fit_keeps_capacity_and_patches(metric):
    ds = datasets.clustered(2000, 8, n_clusters=8, seed=8)
    idx = QuakeIndex.build(ds.vectors, num_partitions=8, kmeans_iters=2,
                           config=QuakeConfig(metric=metric,
                                              snapshot_capacity=512))
    q = np.asarray(datasets.queries_near(ds, 4, seed=9), np.float32)
    ex = BatchedSearchExecutor(idx, impl="jnp", part_bucket=32)
    ex.snapshot()
    cap, p0 = ex._snap.capacity, idx.num_partitions
    assert cap == 512
    # a burst of a new topic, away from every partition: it lands in the
    # nearest few and grows them past the snapshot's slots
    rng = np.random.default_rng(0)
    far = np.abs(ds.vectors).max(0) * 3.0
    xb = (far + rng.normal(scale=0.1, size=(2 * cap, idx.dim))
          ).astype(np.float32)
    idx.insert(xb, np.arange(200_000, 200_000 + len(xb)))
    r = ex.search(q, 5, nprobe=idx.num_partitions)
    assert ex.full_rebuilds == 1 and ex.delta_refreshes == 1
    assert ex._snap.capacity == cap and idx.num_partitions > p0
    assert idx.levels[0].sizes().max() <= cap / idx.config.snapshot_headroom
    idx.check_invariants()
    gt_ids, gt_d = _brute_force(idx, q, 5)
    np.testing.assert_allclose(np.sort(r.dists, 1), np.sort(gt_d, 1),
                               rtol=1e-3, atol=1e-3)


def test_capacity_overflow_falls_back_to_rebuild():
    ds = datasets.clustered(2000, 8, n_clusters=8, seed=8)
    idx = QuakeIndex.build(ds.vectors, num_partitions=8, kmeans_iters=2)
    q = datasets.queries_near(ds, 4, seed=9)
    batch_search(idx, q, 5, nprobe=4)
    ex = get_executor(idx)
    cap = ex._snap.capacity
    # overflow one partition past the slack capacity
    j = int(np.argmax([len(v) for v in idx.levels[0].vectors]))
    c = idx.levels[0].centroids[j]
    n_extra = cap  # certainly exceeds remaining slack
    xb = (c[None, :] + np.zeros((n_extra, idx.dim), np.float32))
    idx.insert(xb, np.arange(200_000, 200_000 + n_extra))
    r = batch_search(idx, q, 5, nprobe=idx.num_partitions, impl="jnp")
    assert ex.full_rebuilds == 2 and ex.delta_refreshes == 0
    assert ex._snap.capacity > cap
    gt_ids, gt_d = _brute_force(idx, np.asarray(q, np.float32), 5)
    np.testing.assert_allclose(np.sort(r.dists, 1), np.sort(gt_d, 1),
                               rtol=1e-3, atol=1e-3)


def test_dirty_fraction_threshold_forces_rebuild():
    ds = datasets.clustered(2000, 8, n_clusters=8, seed=10)
    idx = QuakeIndex.build(ds.vectors, num_partitions=16, kmeans_iters=2)
    q = datasets.queries_near(ds, 4, seed=11)
    ex = BatchedSearchExecutor(idx, impl="jnp", max_dirty_frac=0.1)
    ex.search(q, 5, nprobe=4)
    # touch every partition: way past the 10% delta threshold
    idx.insert(ds.vectors[:500] + 0.01, np.arange(300_000, 300_500))
    ex.search(q, 5, nprobe=4)
    assert ex.full_rebuilds == 2 and ex.delta_refreshes == 0


def test_journal_trim_forces_executor_rebuild():
    ds = datasets.clustered(1500, 8, n_clusters=8, seed=12)
    idx = QuakeIndex.build(ds.vectors, num_partitions=8, kmeans_iters=2)
    idx.journal.max_entries = 2
    q = datasets.queries_near(ds, 4, seed=13)
    batch_search(idx, q, 5, nprobe=4)
    ex = get_executor(idx)
    for i in range(5):                 # > max_entries mutations
        idx.insert(ds.vectors[i:i + 1] + 0.01, np.asarray([400_000 + i]))
    batch_search(idx, q, 5, nprobe=4)
    assert ex.full_rebuilds == 2 and ex.delta_refreshes == 0


# ---------------------------------------------------------------------------
# from_index truncation bugfix
# ---------------------------------------------------------------------------

def test_from_index_lossy_truncation_raises():
    ds = datasets.clustered(1500, 8, n_clusters=8, seed=14)
    idx = QuakeIndex.build(ds.vectors, num_partitions=8, kmeans_iters=2)
    with pytest.raises(ValueError, match="truncate"):
        IndexSnapshot.from_index(idx, capacity=8)


def test_from_index_truncation_clamps_sizes():
    ds = datasets.clustered(1500, 8, n_clusters=8, seed=15)
    idx = QuakeIndex.build(ds.vectors, num_partitions=8, kmeans_iters=2)
    snap = IndexSnapshot.from_index(idx, capacity=8, allow_truncation=True)
    sizes = np.asarray(snap.sizes)
    stored = np.asarray(snap.ids >= 0).sum(axis=1)
    np.testing.assert_array_equal(sizes, stored)   # sizes == valid mask
    assert sizes.max() <= snap.capacity


def test_from_index_headroom_pads_capacity():
    ds = datasets.clustered(1500, 8, n_clusters=8, seed=16)
    idx = QuakeIndex.build(ds.vectors, num_partitions=8, kmeans_iters=2)
    base = IndexSnapshot.from_index(idx)
    padded = IndexSnapshot.from_index(idx, headroom=2.0)
    assert padded.capacity >= base.capacity
    max_size = int(max(len(v) for v in idx.levels[0].vectors))
    assert padded.capacity >= 2 * max_size * 0.99


# ---------------------------------------------------------------------------
# empty batch (plan_batch IndexError bugfix)
# ---------------------------------------------------------------------------

def test_empty_batch_returns_empty_result():
    ds = datasets.clustered(1000, 8, n_clusters=8, seed=17)
    idx = QuakeIndex.build(ds.vectors, num_partitions=8, kmeans_iters=2)
    q0 = np.zeros((0, 8), dtype=np.float32)
    r = batch_search(idx, q0, 5, nprobe=4)
    assert r.ids.shape == (0, 5) and r.dists.shape == (0, 5)
    assert r.partitions_scanned == 0 and r.vectors_scanned == 0
    plan = plan_batch(idx, q0, 5, nprobe=4)
    assert plan.n_real == 0 and plan.qmask.shape[0] == 0
    assert len(plan.nprobe) == 0
