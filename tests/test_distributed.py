"""Mesh-sharded engine + distributed model steps.

The main pytest process keeps the single real device; multi-device checks
run in a subprocess with 8 virtual host devices (the dry-run pattern), per
the instruction that tests must not set the device-count flag globally.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import (EngineConfig, IndexSnapshot, QuakeConfig,
                        QuakeIndex, ShardedQuakeEngine)
from repro.data import datasets


@pytest.fixture(scope="module")
def snap_and_data():
    ds = datasets.clustered(4000, 16, n_clusters=16, seed=0)
    idx = QuakeIndex.build(ds.vectors, num_partitions=32, kmeans_iters=4)
    snap = IndexSnapshot.from_index(idx)
    return snap, ds


def _mesh111():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("pod", "data", "model"))


def test_engine_bruteforce_exact(snap_and_data):
    snap, ds = snap_and_data
    eng = ShardedQuakeEngine(_mesh111(), EngineConfig(
        k=10, part_axes=("pod", "data")))
    q = jnp.asarray(ds.vectors[:8])
    d, i = eng.search_bruteforce(q, eng.shard_snapshot(snap))
    gt = ds.ground_truth(np.asarray(q), 10)
    rec = np.mean([len(set(np.asarray(i[r]).tolist())
                       & set(gt[r].tolist())) / 10 for r in range(8)])
    assert rec == 1.0


def test_engine_fixed_and_adaptive(snap_and_data):
    snap, ds = snap_and_data
    eng = ShardedQuakeEngine(_mesh111(), EngineConfig(
        k=10, nprobe=8, recall_target=0.9, part_axes=("pod", "data")))
    ss = eng.shard_snapshot(snap)
    q = jnp.asarray(datasets.queries_near(ds, 8, seed=2))
    gt = ds.ground_truth(np.asarray(q), 10)
    d_f, i_f = eng.search_fixed(q, ss)
    d_a, i_a, r_est, nprobe = eng.search_adaptive(q, ss)
    rec_f = np.mean([len(set(np.asarray(i_f[r]).tolist())
                         & set(gt[r].tolist())) / 10 for r in range(8)])
    rec_a = np.mean([len(set(np.asarray(i_a[r]).tolist())
                         & set(gt[r].tolist())) / 10 for r in range(8)])
    assert rec_f >= 0.85 and rec_a >= 0.85
    assert (np.asarray(nprobe) >= 1).all()
    assert (np.asarray(nprobe) <= snap.num_partitions).all()


def test_engine_matches_dynamic_index(snap_and_data):
    """Compiled engine and dynamic index must agree on fixed-nprobe scans."""
    snap, ds = snap_and_data
    idx = QuakeIndex.build(ds.vectors, num_partitions=32, kmeans_iters=4)
    eng = ShardedQuakeEngine(_mesh111(), EngineConfig(
        k=10, nprobe=6, part_axes=("pod", "data")))
    ss = eng.shard_snapshot(IndexSnapshot.from_index(idx))
    q = datasets.queries_near(ds, 6, seed=3)
    d_e, i_e = eng.search_fixed(jnp.asarray(q), ss)
    for r in range(6):
        host = idx.search(q[r], 10, nprobe=6, record_stats=False)
        overlap = len(set(np.asarray(i_e[r]).tolist())
                      & set(host.ids.tolist())) / 10
        assert overlap >= 0.9, (r, overlap)


def test_engine_search_batch_shares_planner(snap_and_data):
    """The sharded multi-query entry runs through core.multiquery's
    plan_batch: identical results to the host batched executor on a fixed
    plan, and APS-driven per-query probe counts."""
    snap, ds = snap_and_data
    idx = QuakeIndex.build(ds.vectors, num_partitions=32, kmeans_iters=4)
    eng = ShardedQuakeEngine(_mesh111(), EngineConfig(
        k=10, part_axes=("pod", "data")))
    q = datasets.queries_near(ds, 12, seed=4)
    from repro.core.multiquery import batch_search
    r_host = batch_search(idx, q, 10, nprobe=6)
    r_eng = eng.search_batch(idx, q, 10, nprobe=6)
    assert (np.sort(r_host.ids, 1) == np.sort(r_eng.ids, 1)).all()
    assert r_eng.partitions_scanned == r_host.partitions_scanned
    # APS mode: adaptive per-query probe counts through the same planner
    r_aps = eng.search_batch(idx, q, 10, recall_target=0.9)
    assert len(np.unique(r_aps.nprobe)) > 1
    gt = ds.ground_truth(q, 10)
    rec = np.mean([len(set(r_aps.ids[i].tolist()) & set(gt[i].tolist()))
                   / 10 for i in range(12)])
    assert rec >= 0.8, rec


def test_engine_search_batch_union_cap_stats_consistent(snap_and_data):
    """EngineConfig.union_cap caps the plan itself, so the reported stats
    (partitions_scanned, effective nprobe) reflect what was scanned."""
    snap, ds = snap_and_data
    idx = QuakeIndex.build(ds.vectors, num_partitions=32, kmeans_iters=4)
    eng_full = ShardedQuakeEngine(_mesh111(), EngineConfig(
        k=10, part_axes=("pod", "data")))
    r_full = eng_full.search_batch(idx, datasets.queries_near(ds, 16,
                                                              seed=6),
                                   10, nprobe=8)
    cap = max(r_full.partitions_scanned // 2, 1)
    eng = ShardedQuakeEngine(_mesh111(), EngineConfig(
        k=10, part_axes=("pod", "data"), union_cap=cap))
    q = datasets.queries_near(ds, 16, seed=6)
    r = eng.search_batch(idx, q, 10, nprobe=8)
    from repro.core.multiquery import plan_batch
    plan = plan_batch(idx, np.asarray(q, np.float32), 10, nprobe=8,
                      union_cap=cap)
    assert r.partitions_scanned == plan.n_real
    assert r.partitions_scanned <= max(cap, len(np.unique(plan.anchor)))
    assert (r.nprobe == plan.nprobe).all()
    assert (r.nprobe >= 1).all() and (r.ids[:, 0] >= 0).all()


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_engine_search_batch_storage_dtypes(snap_and_data, dtype):
    snap, ds = snap_and_data
    idx = QuakeIndex.build(ds.vectors, num_partitions=32, kmeans_iters=4)
    eng = ShardedQuakeEngine(_mesh111(), EngineConfig(
        k=10, part_axes=("pod", "data"), storage_dtype=dtype))
    q = datasets.queries_near(ds, 8, seed=5)
    gt = ds.ground_truth(q, 10)
    r = eng.search_batch(idx, q, 10, nprobe=8)
    rec = np.mean([len(set(r.ids[i].tolist()) & set(gt[i].tolist())) / 10
                   for i in range(8)])
    assert rec >= 0.8, rec


def test_engine_fixed_capacity_splits_to_fit(snap_and_data):
    """A fixed slot capacity holds for the sharded snapshot too: the
    partitions larger than it are split, and every vector stays
    findable by a scan of every partition."""
    _, ds = snap_and_data
    idx = QuakeIndex.build(ds.vectors, num_partitions=8, kmeans_iters=4,
                           config=QuakeConfig(snapshot_capacity=128))
    p0 = idx.num_partitions
    eng = ShardedQuakeEngine(_mesh111(), EngineConfig(
        k=1, nprobe=4 * p0, part_axes=("pod", "data")))
    ss = eng.refresh_snapshot(idx)
    assert ss.capacity == 128 and idx.num_partitions > p0
    assert idx.levels[0].sizes().max() <= 128
    idx.check_invariants()
    q = jnp.asarray(ds.vectors[:16])
    d, i = eng.search_fixed(q, ss)
    assert np.array_equal(np.asarray(i)[:, 0], np.arange(16))


def test_engine_journal_refresh_patches_sharded_snapshot(snap_and_data):
    """The engine's cached snapshot consumes the mutation journal: an
    insert patches only the dirty rows (no re-shard), and the patched
    snapshot serves the fresh vectors."""
    _, ds = snap_and_data
    idx = QuakeIndex.build(ds.vectors, num_partitions=32, kmeans_iters=4)
    eng = ShardedQuakeEngine(_mesh111(), EngineConfig(
        k=10, nprobe=32, part_axes=("pod", "data")))
    ss = eng.refresh_snapshot(idx)
    assert eng.full_rebuilds == 1
    q = datasets.queries_near(ds, 4, seed=9)
    new_ids = np.arange(60_000, 60_004)
    idx.insert(q * 0.999, new_ids)
    ss2 = eng.refresh_snapshot(idx)
    assert eng.delta_refreshes == 1 and eng.full_rebuilds == 1
    assert ss2.capacity == ss.capacity
    _, i = eng.search_fixed(jnp.asarray(q), ss2)
    assert set(np.asarray(i).ravel().tolist()) & set(new_ids.tolist())
    # structural mutation -> full re-shard
    idx.journal.record(structural=True, reason="test")
    eng.refresh_snapshot(idx)
    assert eng.full_rebuilds == 2


MULTIDEV_SCRIPT = textwrap.dedent("""
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import (EngineConfig, IndexSnapshot, QuakeIndex,
                            ShardedQuakeEngine)
    from repro.data import datasets
    from repro.train import checkpoint as ck, optimizer as opt, steps
    import tempfile

    assert len(jax.devices()) == 8
    ds = datasets.clustered(3000, 16, n_clusters=16, seed=0)
    idx = QuakeIndex.build(ds.vectors, num_partitions=30, kmeans_iters=3)
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                ("pod", "data", "model"))
    eng = ShardedQuakeEngine(mesh, EngineConfig(
        k=10, nprobe=8, part_axes=("pod", "data")))
    snap = IndexSnapshot.from_index(idx, pad_partitions_to=eng.n_part_shards,
                                    layout=eng.layout)
    ss = eng.shard_snapshot(snap)
    q = jnp.asarray(datasets.queries_near(ds, 8, seed=1))
    gt = ds.ground_truth(np.asarray(q), 10)
    d_b, i_b = eng.search_bruteforce(q, ss)
    rec = np.mean([len(set(np.asarray(i_b[r]).tolist())
                       & set(gt[r].tolist())) / 10 for r in range(8)])
    assert rec == 1.0, rec
    d_a, i_a, r_est, nprobe = eng.search_adaptive(q, ss)
    rec_a = np.mean([len(set(np.asarray(i_a[r]).tolist())
                         & set(gt[r].tolist())) / 10 for r in range(8)])
    assert rec_a >= 0.8, rec_a

    # planner-driven multi-query entry on a real 2x2x2 mesh: each round
    # shards its query rows over "model" and its partitions over
    # ("pod", "data")
    r_b = eng.search_batch(idx, np.asarray(q), 10, nprobe=8)
    rec_b = np.mean([len(set(r_b.ids[r].tolist())
                         & set(gt[r].tolist())) / 10 for r in range(8)])
    assert rec_b >= 0.8, rec_b
    ex = eng.executor(idx)
    kept = np.arange(ex.snapshot().num_partitions // 2)
    take = np.ones((8, len(kept)), bool)
    seq = jnp.asarray(np.broadcast_to(kept.astype(np.int32), take.shape))
    d_r, f_r, _ = ex.scan_probe_round(q, seq, take, kept, 10)
    assert d_r.sharding.spec == P("model"), d_r.sharding
    d_7, f_7, _ = ex.scan_probe_round(q[:7], seq[:7], take[:7], kept, 10)
    np.testing.assert_array_equal(np.asarray(f_7), np.asarray(f_r)[:7])
    # a mesh of query replicas alone: one partition shard, eight batch
    eng_q = ShardedQuakeEngine(
        Mesh(np.array(jax.devices()).reshape(1, 1, 8),
             ("pod", "data", "model")),
        EngineConfig(k=10, nprobe=8, part_axes=("pod", "data")))
    r_q = eng_q.search_batch(idx, np.asarray(q), 10, nprobe=8)
    np.testing.assert_array_equal(r_q.ids, r_b.ids)
    ex_q = eng_q.executor(idx)
    d_q, _, _ = ex_q.scan_probe_round(q, seq, take, kept, 10)
    assert d_q.sharding.spec == P("model"), d_q.sharding

    # elastic checkpoint: save replicated, restore sharded on a new mesh
    params = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    with tempfile.TemporaryDirectory() as d:
        mgr = ck.CheckpointManager(d, async_write=False)
        mgr.save(1, params, block=True)
        mesh2 = Mesh(np.array(jax.devices()).reshape(4, 2),
                     ("data", "model"))
        sh = {"w": NamedSharding(mesh2, P("data", "model"))}
        restored, man = mgr.restore(params, shardings=sh)
        assert man["step"] == 1
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(params["w"]))
        assert restored["w"].sharding.spec == P("data", "model")

    # compressed-DP step on a real 2x2x2 mesh
    def loss(p, b):
        return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)
    p0 = {"w": jnp.zeros((8, 1))}
    st = opt.init_state(p0)
    res = opt.init_residual(p0)
    step = steps.make_compressed_dp_step(
        loss, opt.AdamWConfig(lr=5e-2, warmup_steps=1, total_steps=100),
        mesh, dp_axes=("pod", "data"))
    rng = np.random.default_rng(0)
    w_true = rng.normal(size=(8, 1))
    losses = []
    for s in range(60):
        x = rng.normal(size=(16, 8)).astype(np.float32)
        y = (x @ w_true).astype(np.float32)
        p0, st, res, m = step(p0, st, res, {"x": jnp.asarray(x),
                                            "y": jnp.asarray(y)})
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.1, losses[::10]
    print("MULTIDEV_OK")
""")


def test_multidevice_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MULTIDEV_OK" in out.stdout


def test_engine_search_batch_earlyexit_rounds(snap_and_data):
    """APS-driven engine search_batch runs the same multi-round
    early-exit loop as the host executor: footprint never above the
    rounds=1 fixed plan, per-query recall estimates populated, live
    counts non-increasing, and recall equivalent to the host round
    path."""
    snap, ds = snap_and_data
    idx = QuakeIndex.build(ds.vectors, num_partitions=32, kmeans_iters=4)
    eng = ShardedQuakeEngine(_mesh111(), EngineConfig(
        k=10, part_axes=("pod", "data")))
    q = datasets.queries_near(ds, 16, seed=8)
    r_fix = eng.search_batch(idx, q, 10, recall_target=0.9, rounds=1)
    assert r_fix.rounds == 1 and r_fix.recall_estimate is not None
    r_ee = eng.search_batch(idx, q, 10, recall_target=0.9)
    assert r_ee.vectors_scanned <= r_fix.vectors_scanned
    assert r_ee.comparisons <= r_fix.comparisons
    assert r_ee.recall_estimate is not None
    tr = r_ee.round_trace
    assert tr is not None and len(tr["round_live"]) == r_ee.rounds
    assert all(a >= b for a, b in zip(tr["round_live"],
                                      tr["round_live"][1:]))
    gt = ds.ground_truth(q, 10)
    def rec(r):
        return np.mean([len(set(r.ids[i].tolist()) & set(gt[i].tolist()))
                        / 10 for i in range(16)])
    assert rec(r_ee) >= 0.8
    from repro.core.multiquery import batch_search
    r_host = batch_search(idx, q, 10, recall_target=0.9)
    assert abs(rec(r_ee) - rec(r_host)) <= 0.1
    # a union cap (plan-level truncation) falls back to the one-shot path
    r_cap = eng.search_batch(idx, q, 10, recall_target=0.9, union_cap=8)
    assert r_cap.rounds == 1
