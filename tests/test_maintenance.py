"""Maintenance engine: estimate/verify/commit semantics (paper §4)."""
import numpy as np
import pytest

from repro.core import (LatencyModel, Maintainer, MaintenancePolicy,
                        QuakeConfig, QuakeIndex)
from repro.core import cost_model as cm
from repro.data import datasets


def _skewed_index(seed=1, hot=2, cold=20, hot_size=5000, cold_size=300,
                  dim=24, **cfg_kw):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(hot + cold, dim)) * 6
    parts = [centers[i] + rng.normal(size=(hot_size, dim))
             for i in range(hot)]
    parts += [centers[hot + i] + rng.normal(size=(cold_size, dim))
              for i in range(cold)]
    x = np.concatenate(parts).astype(np.float32)
    idx = QuakeIndex.build(x, num_partitions=hot + cold,
                           config=QuakeConfig(**cfg_kw), kmeans_iters=4)
    queries = np.concatenate(
        [centers[i] + rng.normal(size=(100, dim)) for i in range(hot)]
    ).astype(np.float32)
    for q in queries:
        idx.search(q, 10)
    return idx, x, centers


def test_cost_example_from_paper():
    """Paper §4.2.4 worked example: balanced split committed, imbalanced
    split rejected, with their exact lambda values."""
    lam = cm.fit_latency_model(np.array([50, 250, 450, 500]),
                               np.array([250e3, 550e3, 1050e3, 1200e3]))
    # reproduce the decision arithmetic with the paper's numbers directly
    d_over, tau, alpha, a = 60e3, 4e3, 0.5, 0.10
    lam_500, lam_250 = 1200e3, 550e3
    lam_450, lam_50 = 1050e3, 250e3
    est = d_over - a * lam_500 + 2 * alpha * a * lam_250
    assert est < -tau                       # tentative split accepted
    bal = d_over - a * lam_500 + alpha * a * (lam_250 + lam_250)
    imb = d_over - a * lam_500 + alpha * a * (lam_450 + lam_50)
    assert bal < -tau                       # P1 commit
    assert imb > -tau                       # P2 reject


def test_split_reduces_cost_monotonically():
    idx, x, _ = _skewed_index()
    m = Maintainer(idx)
    costs = [m.total_cost()]
    for _ in range(3):
        rng = np.random.default_rng(0)
        for q in x[rng.integers(0, len(x), 100)]:
            idx.search(q, 10)
        rep = m.run()
        assert rep.cost_after <= rep.cost_before + 1e-6
        costs.append(rep.cost_after)
        idx.check_invariants()
    assert costs[-1] < costs[0]


def test_split_triggers_on_hot_partitions():
    idx, _, _ = _skewed_index()
    rep = Maintainer(idx).run()
    assert rep.splits >= 1
    idx.check_invariants()


def test_merge_triggers_when_overpartitioned():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(4000, 16)).astype(np.float32)
    idx = QuakeIndex.build(x, num_partitions=200,
                           config=QuakeConfig(min_partition_size=64,
                                              tau_ns=1.0), kmeans_iters=3)
    for q in x[rng.integers(0, 4000, 200)]:
        idx.search(q, 10)
    rep = Maintainer(idx).run()
    assert rep.merges >= 1
    assert rep.cost_after <= rep.cost_before + 1e-6
    idx.check_invariants()


def test_rejection_blocks_bad_actions():
    """With a huge tau nothing should ever commit."""
    idx, x, _ = _skewed_index(tau_ns=1e12)
    rep = Maintainer(idx).run()
    assert rep.splits == 0 and rep.merges == 0


def test_noop_maintenance_does_not_invalidate_snapshots():
    """Regression: a maintenance pass where zero actions commit must not
    bump the mutation clock — the batched executor's cached snapshot stays
    valid and no refresh (full or delta) happens on the next search."""
    from repro.core.multiquery import batch_search, get_executor

    idx, x, _ = _skewed_index(tau_ns=1e12)     # tau blocks every commit
    q = x[:4]
    batch_search(idx, q, 5, nprobe=4)
    ex = get_executor(idx)
    v0, key0, rebuilds0 = idx.version, ex._key, ex.full_rebuilds
    rep = Maintainer(idx).run()
    assert rep.splits == 0 and rep.merges == 0
    assert not rep.level_added and not rep.level_removed
    assert idx.version == v0                   # clock untouched
    batch_search(idx, q, 5, nprobe=4)
    assert ex._key == key0
    assert ex.full_rebuilds == rebuilds0 and ex.delta_refreshes == 0
    # the maintenance log still records the pass, with an empty journal
    assert idx.maintenance_log[-1]["journal"] == []


def test_no_rejection_policy_commits_tentatives():
    idx, _, _ = _skewed_index()
    pol = MaintenancePolicy(use_rejection=False)
    rep = Maintainer(idx, policy=pol).run()
    assert rep.rejected_splits == 0 and rep.rejected_merges == 0
    idx.check_invariants()


def test_norefine_policy_skips_refinement():
    idx, _, _ = _skewed_index()
    pol = MaintenancePolicy(use_refinement=False)
    rep = Maintainer(idx, policy=pol).run()
    idx.check_invariants()   # structure stays coherent without refinement


def test_search_correct_after_maintenance():
    idx, x, _ = _skewed_index()
    Maintainer(idx).run()
    rng = np.random.default_rng(3)
    k = 10
    recs = []
    for _ in range(20):
        q = x[rng.integers(len(x))]
        d = np.sum((x - q) ** 2, axis=1)
        gt = set(np.argsort(d)[:k].tolist())
        r = idx.search(q, k, recall_target=0.9)
        recs.append(len(gt & set(r.ids.tolist())) / k)
    assert np.mean(recs) >= 0.85


def test_level_add_and_remove():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(3000, 8)).astype(np.float32)
    idx = QuakeIndex.build(x, num_partitions=64,
                           config=QuakeConfig(level_add_threshold=32))
    rep = Maintainer(idx).run()
    assert rep.level_added and len(idx.levels) == 2
    idx.check_invariants()
    # force removal
    idx.config.level_add_threshold = 10**9
    idx.config.level_remove_threshold = 10**6
    rep2 = Maintainer(idx).run()
    assert rep2.level_removed and len(idx.levels) == 1
    idx.check_invariants()


def test_latency_model_fit_and_profile():
    sizes = np.array([64, 256, 1024, 4096])
    lam0 = LatencyModel(c_fixed=100, c_lin=2.0, c_sel=0.3)
    fit = cm.fit_latency_model(sizes, lam0(sizes))
    np.testing.assert_allclose(fit(sizes), lam0(sizes), rtol=1e-6)
    prof = cm.profile(dim=16, sizes=(64, 256, 1024), repeats=2)
    assert (prof(np.array([10, 100, 1000])) > 0).all()


@pytest.mark.parametrize("k", [2, 7, 16, 23])
def test_refine_padded_centroids_match_unpadded(k):
    """Refinement pads its group's centroids to a multiple of 16 (masked)
    so one compiled Lloyd step serves every group size; the padding rows
    never take a point, so the result equals the unpadded step."""
    import jax.numpy as jnp
    from repro.core import kmeans
    rng = np.random.default_rng(k)
    cents = rng.normal(size=(k, 12)).astype(np.float32) * 4
    parts = []
    for j in range(k):
        n = int(rng.integers(0, 40))      # empty groups included
        parts.append((cents[j] + rng.normal(size=(n, 12)).astype(np.float32),
                      np.arange(n, dtype=np.int64) + 1000 * j))
    got_c, got_parts = kmeans.refine(parts, cents, iters=2)
    xs = np.concatenate([p[0] for p in parts])
    npad = kmeans._next_pow2(max(len(xs), 8))
    xp = np.zeros((npad, 12), np.float32)
    xp[:len(xs)] = xs
    c, a, _ = kmeans._lloyd(jnp.asarray(xp),
                            jnp.asarray(np.arange(npad) < len(xs)),
                            jnp.asarray(cents), jnp.ones(k, bool), k, 2)
    a = np.asarray(a)[:len(xs)]
    assert len(got_c) == len(got_parts) == k
    for j in range(k):
        np.testing.assert_array_equal(got_parts[j][0], xs[a == j])
        if (a == j).any():
            np.testing.assert_allclose(got_c[j], np.asarray(c)[j],
                                       rtol=1e-5, atol=1e-5)
