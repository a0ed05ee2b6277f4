"""Crash-consistent durability (docs/durability.md):

  * WAL framing — CRC-checked, length-prefixed, strictly-increasing
    LSNs; torn / bit-flipped / regressive tails stop the reader at the
    last valid prefix, never raise;
  * atomic checkpoints — temp + fsync + rename, incremental via the
    journal dirty set with hard-link reuse, damaged generations
    rejected in favour of older ones;
  * recovery — newest valid checkpoint + WAL-suffix replay, verified
    against the stored ``index_state_fingerprint``;
  * the randomized kill-point harness — ≥50 seeded crash samples across
    all four durability fault sites; every recovery must land on a
    *prefix* of the admitted write stream and match a fault-free twin
    replay of that prefix byte-for-byte.
"""
import copy
import json
import os

import numpy as np
import pytest

from repro.core import (QuakeConfig, QuakeIndex, ServingConfig,
                        ServingRuntime)
from repro.core import multiquery as mq
from repro.core.durability import (DurabilityManager, REC_FP, REC_INSERT,
                                   REC_MAINT, RecoveryError, WAL_NAME,
                                   WriteAheadLog, list_checkpoints,
                                   read_wal, recover_index, save_index,
                                   select_checkpoint, validate_checkpoint,
                                   write_checkpoint)
from repro.core.maintenance import checkpoint_index, restore_index
from repro.data import datasets
from repro.faults import FaultInjector, InjectedFault, index_state_fingerprint


@pytest.fixture(scope="module")
def ds():
    return datasets.clustered(3000, 16, n_clusters=12, seed=0)


@pytest.fixture(scope="module")
def base(ds):
    return QuakeIndex.build(ds.vectors[:2000], num_partitions=16,
                            kmeans_iters=3,
                            config=QuakeConfig(recall_target=0.9))


def fresh(base):
    return copy.deepcopy(base)


# ---------------------------------------------------------------------------
# the shared write stream: inserts with fresh ids + deletes of disjoint
# base-id slices, so *every prefix* of the stream is a valid replay
# ---------------------------------------------------------------------------

def make_ops(ds, n_ops=24, seed=123):
    rng = np.random.default_rng(seed)
    ops, nxt, del_base = [], 50_000, 1900
    for i in range(n_ops):
        if i % 6 == 5 and del_base + 5 <= 2000:
            ops.append(("delete", np.arange(del_base, del_base + 5)))
            del_base += 5
        else:
            x = (ds.vectors[rng.integers(2000, size=8)]
                 + rng.normal(0, 0.01, (8, ds.vectors.shape[1]))
                 ).astype(np.float32)
            ops.append(("insert", x, np.arange(nxt, nxt + 8)))
            nxt += 8
    return ops


def apply_op(idx, op):
    if op[0] == "insert":
        idx.insert(op[1], op[2])
    else:
        idx.delete(op[1])


@pytest.fixture(scope="module")
def ops(ds):
    return make_ops(ds)


# ---------------------------------------------------------------------------
# WAL unit tests
# ---------------------------------------------------------------------------

def test_wal_round_trip(tmp_path):
    path = str(tmp_path / WAL_NAME)
    wal = WriteAheadLog(path, fsync="always")
    payloads = [(REC_INSERT, b"ins-payload"), (REC_MAINT, b"splits=1"),
                (REC_FP, b"\x00" * 32)]
    lsns = [wal.append(rt, p) for rt, p in payloads]
    wal.close()
    records, valid, reason = read_wal(path)
    assert reason == "clean" and valid == os.path.getsize(path)
    assert [r.lsn for r in records] == lsns == [1, 2, 3]
    assert [(r.rtype, r.payload) for r in records] == payloads


def test_wal_torn_tail_truncated_on_open(tmp_path):
    path = str(tmp_path / WAL_NAME)
    wal = WriteAheadLog(path, fsync="always")
    wal.append(REC_MAINT, b"a")
    wal.append(REC_MAINT, b"b")
    wal.close()
    good = os.path.getsize(path)
    with open(path, "ab") as f:           # torn frame: header cut short
        f.write(b"\x01\x02\x03")
    records, valid, reason = read_wal(path)
    assert reason == "torn_header" and valid == good and len(records) == 2
    # reopening truncates the damage and continues LSNs past the prefix
    wal2 = WriteAheadLog(path, fsync="always")
    assert wal2.truncated_on_open == 3
    assert os.path.getsize(path) == good
    assert wal2.append(REC_MAINT, b"c") == 3
    wal2.close()
    assert read_wal(path)[2] == "clean"


def test_wal_corrupt_mid_record_recovers_prefix(tmp_path):
    path = str(tmp_path / WAL_NAME)
    wal = WriteAheadLog(path, fsync="always")
    offs = []
    for i in range(3):
        wal.append(REC_MAINT, b"x%d" % i)
        offs.append(os.path.getsize(path))
    wal.close()
    with open(path, "r+b") as f:          # flip a payload byte of record 2
        pos = offs[0] + 4 + 13            # past frame crc + body header
        f.seek(pos)
        b = f.read(1)
        f.seek(pos)
        f.write(bytes([b[0] ^ 0x01]))
    records, valid, reason = read_wal(path)
    # stops at the damaged record: the prefix before it survives
    assert reason == "crc_mismatch"
    assert [r.lsn for r in records] == [1] and valid == offs[0]


def test_wal_lsn_regression_detected(tmp_path):
    path = str(tmp_path / WAL_NAME)
    wal = WriteAheadLog(path, fsync="always")
    wal.append(REC_MAINT, b"a")
    first_end = os.path.getsize(path)
    wal.append(REC_MAINT, b"b")
    wal.close()
    with open(path, "rb") as f:           # replay frame 1 after frame 2
        data = f.read()
    frame1 = data[8:first_end]            # magic is 8 bytes
    with open(path, "ab") as f:
        f.write(frame1)
    records, _valid, reason = read_wal(path)
    assert reason == "lsn_regression" and [r.lsn for r in records] == [1, 2]


def test_wal_fsync_policies(tmp_path):
    always = WriteAheadLog(str(tmp_path / "a.log"), fsync="always")
    batch = WriteAheadLog(str(tmp_path / "b.log"), fsync="batch",
                          batch_ops=4)
    off = WriteAheadLog(str(tmp_path / "c.log"), fsync="off")
    for i in range(8):
        for w in (always, batch, off):
            w.append(REC_MAINT, b"p%d" % i)
    assert always.unsynced_bytes == 0
    assert always.fsyncs >= 8 + 1          # one per append (+ open)
    assert 1 <= batch.fsyncs - 1 <= 2      # every 4th append
    assert off.fsyncs == 1 and off.unsynced_bytes > 0   # open only
    assert off.sync() and off.unsynced_bytes == 0
    for w in (always, batch, off):
        w.close()


def test_wal_poisoned_after_injected_crash(tmp_path):
    fi = FaultInjector(seed=1, rates={"wal_torn_write": 1.0})
    wal = WriteAheadLog(str(tmp_path / WAL_NAME), fsync="always", faults=fi)
    with pytest.raises(InjectedFault):
        wal.append(REC_MAINT, b"doomed")
    # the process is dead: further appends refuse instead of writing
    # unreachable frames past the damaged tail
    with pytest.raises(RuntimeError, match="recover"):
        wal.append(REC_MAINT, b"after")
    # keep the whole flushed-but-unsynced tail: the torn frame survives
    size = wal.simulate_crash(keep_unsynced=10 ** 9)
    records, valid, reason = read_wal(wal.path)
    assert valid < size and records == [] and reason != "clean"


# ---------------------------------------------------------------------------
# checkpoint tests
# ---------------------------------------------------------------------------

def test_checkpoint_atomic_write_and_validate(tmp_path, base):
    idx = fresh(base)
    root = str(tmp_path)
    # tmp debris from a previous aborted attempt is swept, not fatal
    os.makedirs(os.path.join(root, ".tmp-ckpt-00000001/x"))
    manifest, stats = write_checkpoint(idx, root, 1, wal_lsn=0,
                                       write_op_count=0)
    assert not os.path.exists(os.path.join(root, ".tmp-ckpt-00000001"))
    assert stats["partitions_written"] == idx.levels[0].num_partitions
    gendir = os.path.join(root, "ckpt-00000001")
    assert validate_checkpoint(gendir) == manifest
    with pytest.raises(ValueError, match="already exists"):
        write_checkpoint(idx, root, 1, wal_lsn=0, write_op_count=0)


def test_damaged_generation_rejected_falls_back(tmp_path, base):
    idx = fresh(base)
    root = str(tmp_path)
    save_index(idx, root)
    apply_op(idx, ("insert", np.ones((1, idx.dim), np.float32),
                   np.array([77_000])))
    m2 = save_index(idx, root)
    gendir2 = os.path.join(root, "ckpt-00000002")
    # bit-flip one partition blob of the newest generation
    blob = os.path.join(gendir2, m2["partitions"][0])
    with open(blob, "r+b") as f:
        f.seek(10)
        c = f.read(1)
        f.seek(10)
        f.write(bytes([c[0] ^ 0xFF]))
    assert validate_checkpoint(gendir2) is None
    path, manifest = select_checkpoint(root)
    assert manifest["generation"] == 1      # falls back, does not raise
    rec, rep = recover_index(root)
    assert rep.generation == 1


def test_incremental_checkpoint_hardlinks_clean_partitions(tmp_path, base):
    idx = fresh(base)
    dm = DurabilityManager(idx, str(tmp_path), fsync="always",
                           ckpt_every_ops=None, keep_checkpoints=4)
    x = np.asarray(idx.levels[0].vectors[0][:2]) + 0.01
    dm.log_insert(x, np.array([60_000, 60_001]))
    idx.insert(x, np.array([60_000, 60_001]))
    assert dm.checkpoint(force=True)
    st = dm.stats()
    assert st["partitions_linked"] > 0
    assert st["partitions_written"] >= idx.levels[0].num_partitions + 1
    # linked blobs share the inode with the previous generation
    m1 = validate_checkpoint(os.path.join(str(tmp_path), "ckpt-00000001"))
    m2 = dm._prev_manifest
    shared = [n for n in m2["partitions"] if n in m1["files"]]
    assert shared
    a = os.stat(os.path.join(str(tmp_path), "ckpt-00000001", shared[0]))
    b = os.stat(os.path.join(str(tmp_path), "ckpt-00000002", shared[0]))
    assert a.st_ino == b.st_ino
    dm.close()


def test_pruning_keeps_newest_and_linked_blobs_survive(tmp_path, base):
    idx = fresh(base)
    dm = DurabilityManager(idx, str(tmp_path), fsync="always",
                           ckpt_every_ops=None, keep_checkpoints=2)
    for g in range(4):
        x = np.asarray(idx.levels[0].vectors[0][:1]) + 0.01 * (g + 1)
        dm.log_insert(x, np.array([61_000 + g]))
        idx.insert(x, np.array([61_000 + g]))
        dm.checkpoint(force=True)
    gens = [g for g, _p in list_checkpoints(str(tmp_path))]
    assert gens == [4, 5]                   # attach=1, then 2..5, keep 2
    rec, rep = recover_index(str(tmp_path))
    assert rep.generation == 5
    assert index_state_fingerprint(rec) == index_state_fingerprint(idx)
    dm.close()


def test_ckpt_crash_before_rename_loses_nothing_logged(tmp_path, base):
    idx = fresh(base)
    fi = FaultInjector(seed=2, rates={"ckpt_crash_before_rename": 1.0})
    dm = DurabilityManager(idx, str(tmp_path), fsync="always",
                           ckpt_every_ops=None, faults=fi)
    x = np.asarray(idx.levels[0].vectors[0][:2]) + 0.01
    dm.log_insert(x, np.array([62_000, 62_001]))
    idx.insert(x, np.array([62_000, 62_001]))
    with pytest.raises(InjectedFault):
        dm.checkpoint(force=True)
    assert dm.checkpoint_failures == 1
    dm.simulate_crash()
    # the aborted generation never appeared; the WAL suffix replays the
    # logged op on top of the attach baseline
    rec, rep = recover_index(str(tmp_path))
    assert rep.generation == 1 and rep.inserts_replayed == 1
    assert index_state_fingerprint(rec) == index_state_fingerprint(idx)


# ---------------------------------------------------------------------------
# recovery tests
# ---------------------------------------------------------------------------

def test_recover_requires_a_checkpoint(tmp_path):
    with pytest.raises(RecoveryError, match="no valid checkpoint"):
        recover_index(str(tmp_path))


def test_recover_rejects_fingerprint_mismatch(tmp_path, base):
    idx = fresh(base)
    root = str(tmp_path)
    save_index(idx, root)
    mpath = os.path.join(root, "ckpt-00000001", "MANIFEST.json")
    with open(mpath) as f:
        manifest = json.load(f)
    manifest["fingerprint"] = "00" * 32     # blobs still CRC-valid
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(RecoveryError, match="fingerprint"):
        recover_index(root)
    rec, _rep = recover_index(root, verify=False)
    assert rec.num_vectors == idx.num_vectors


def test_recover_truncates_torn_tail_persistently(tmp_path, base, ops):
    idx = fresh(base)
    dm = DurabilityManager(idx, str(tmp_path), fsync="always",
                           ckpt_every_ops=None)
    for op in ops[:3]:
        (dm.log_insert(op[1], op[2]) if op[0] == "insert"
         else dm.log_delete(op[1]))
        apply_op(idx, op)
    dm.simulate_crash()
    wal_path = os.path.join(str(tmp_path), WAL_NAME)
    good = os.path.getsize(wal_path)
    with open(wal_path, "ab") as f:
        f.write(b"\xde\xad\xbe\xef")
    rec, rep = recover_index(str(tmp_path))
    assert rep.wal_reason == "torn_header"
    assert rep.wal_truncated_bytes == 4
    assert os.path.getsize(wal_path) == good     # truncation is durable
    assert read_wal(wal_path)[2] == "clean"
    assert index_state_fingerprint(rec) == index_state_fingerprint(idx)


def test_save_load_round_trip(tmp_path, base, ops):
    idx = fresh(base)
    for op in ops[:6]:
        apply_op(idx, op)
    root = str(tmp_path)
    idx.save(root)
    loaded = QuakeIndex.load(root)
    assert index_state_fingerprint(loaded) == index_state_fingerprint(idx)
    loaded.check_invariants()
    # APS's fitted model is index state too
    idx.set_aps_model(0.2, 16)
    idx.save(root)
    loaded = QuakeIndex.load(root)
    assert (loaded.aps_f_m, loaded.geometry_dim) == (0.2, 16)
    np.testing.assert_array_equal(loaded._beta_table, idx._beta_table)
    # saving again bumps the generation; load picks the newest
    apply_op(idx, ops[6])
    idx.save(root)
    assert index_state_fingerprint(QuakeIndex.load(root)) == \
        index_state_fingerprint(idx)


# ---------------------------------------------------------------------------
# runtime integration
# ---------------------------------------------------------------------------

def _runtime_cfg(**kw):
    cfg = dict(k=5, cache_entries=0, ticker=False, flush_size=4,
               maint_min_ops=10 ** 9, fsync="always", ckpt_every_ops=6)
    cfg.update(kw)
    return ServingConfig(**cfg)


def test_runtime_recover_matches_live(tmp_path, base, ds, ops):
    idx = fresh(base)
    rt = ServingRuntime(idx, _runtime_cfg(wal_dir=str(tmp_path)))
    q = datasets.queries_near(ds, 8, seed=5).astype(np.float32)
    for op in ops[:10]:
        if op[0] == "insert":
            rt.submit_insert(op[1], op[2])
        else:
            rt.submit_delete(op[1])
    rt.submit_batch(q)
    rt.drain()
    st = rt.stats()
    assert st["durability"] is not None
    assert st["durability"]["wal_appends"] >= 10
    assert st["durability"]["checkpoints_written"] >= 2   # attach + cadence
    live_fp = index_state_fingerprint(idx)
    rt.close()

    rt2 = ServingRuntime.recover(str(tmp_path), _runtime_cfg())
    assert rt2.recovery_report is not None
    assert rt2.recovery_report.fingerprint == live_fp.hex()
    assert index_state_fingerprint(rt2.index) == live_fp
    qid = rt2.submit_query(q[0])
    rt2.drain()
    r = rt2.result(qid)
    assert r.status == "OK" and len(r.ids) == 5
    rt2.close()


def test_runtime_maintenance_checkpoint_protocol(tmp_path, base, ds):
    """A committed maintenance pass is made durable by the forced
    checkpoint that follows it (its effects are not WAL-replayable), so
    recovery after maintenance must still match the live index."""
    idx = fresh(base)
    rt = ServingRuntime(idx, _runtime_cfg(
        wal_dir=str(tmp_path), maint_min_ops=2, ckpt_every_ops=None))
    rng = np.random.default_rng(9)
    hot = np.asarray(idx.levels[0].vectors[0][:1])
    for i in range(12):                      # pile into one partition
        x = (hot + rng.normal(0, 0.005, (24, idx.dim))).astype(np.float32)
        rt.submit_insert(x, np.arange(70_000 + i * 24, 70_000 + (i+1) * 24))
        rt.maybe_maintain()
    rt.drain()
    st = rt.stats()
    ver_changed = st["maintenance_runs"] > 0
    records, _v, _r = read_wal(os.path.join(str(tmp_path), WAL_NAME))
    if ver_changed and st["durability"]["checkpoints_written"] > 1:
        assert any(r.rtype == REC_MAINT for r in records)
    live_fp = index_state_fingerprint(idx)
    rt.close()
    rec, rep = recover_index(str(tmp_path))
    assert index_state_fingerprint(rec) == live_fp
    rec.check_invariants()


# ---------------------------------------------------------------------------
# satellite: journal overflow is loud, and consumers fall back
# ---------------------------------------------------------------------------

def test_journal_overflow_flag_and_stats(base, ds):
    idx = fresh(base)
    assert idx.journal.overflowed is False
    rt = ServingRuntime(idx, _runtime_cfg())
    idx.journal.max_entries = 4
    for i in range(8):
        rt.submit_insert(np.ones((1, idx.dim), np.float32) * 0.01 * i,
                         np.array([80_000 + i]))
    st = rt.stats()
    assert st["journal_overflowed"] is True
    assert st["journal_overflow_count"] >= 4
    rt.close()


def test_journal_overflow_forces_executor_full_rebuild(base):
    idx = fresh(base)
    ex = mq.BatchedSearchExecutor(idx, storage_dtype="bf16")
    q = np.asarray(idx.levels[0].vectors[0][:2], dtype=np.float32)
    ex.search(q, 5, nprobe=4)
    assert ex.full_rebuilds == 1
    idx.insert(q + 0.01, np.array([81_000, 81_001]))
    ex.search(q, 5, nprobe=4)
    assert ex.delta_refreshes == 1 and ex.full_rebuilds == 1
    idx.journal.max_entries = 1              # force the loss window
    for i in range(4):
        idx.insert(q + 0.02 * (i + 1), np.array([81_010 + 2 * i,
                                                 81_011 + 2 * i]))
    assert idx.journal.overflowed is True
    ex.search(q, 5, nprobe=4)
    # the delta window is gone: the snapshot must full-rebuild, not
    # silently serve a stale view
    assert ex.full_rebuilds == 2


# ---------------------------------------------------------------------------
# satellite: checkpoint/restore round trip across storage dtypes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["f32", "bf16", "int8"])
def test_checkpoint_restore_round_trip_dtypes(base, ds, dtype, tmp_path):
    idx = fresh(base)
    q = datasets.queries_near(ds, 8, seed=7).astype(np.float32)
    ex = mq.BatchedSearchExecutor(idx, storage_dtype=dtype)
    before = ex.search(q, 10, nprobe=6)
    scales_before = (np.asarray(ex._snap.scales).copy()
                     if dtype == "int8" else None)
    ckpt = checkpoint_index(idx)
    ver = idx.version
    idx.insert(q[:2] + 0.01, np.array([90_000, 90_001]))
    idx.delete(np.arange(1800, 1805))
    restore_index(idx, ckpt)
    assert idx.version == ver               # snapshot consumers coherent
    after = mq.BatchedSearchExecutor(idx, storage_dtype=dtype)\
        .search(q, 10, nprobe=6)
    np.testing.assert_array_equal(before.ids, after.ids)
    np.testing.assert_array_equal(before.dists, after.dists)
    # durable round trip preserves it too (int8 scales exactly: the
    # quantization is deterministic in the stored f32 vectors)
    idx.save(str(tmp_path))
    loaded = QuakeIndex.load(str(tmp_path))
    ex3 = mq.BatchedSearchExecutor(loaded, storage_dtype=dtype)
    r3 = ex3.search(q, 10, nprobe=6)
    np.testing.assert_array_equal(before.ids, r3.ids)
    if dtype == "int8":
        np.testing.assert_array_equal(scales_before,
                                      np.asarray(ex3._snap.scales))


# ---------------------------------------------------------------------------
# satellite: fingerprint stability (canonical-ordering contract)
# ---------------------------------------------------------------------------

def test_fingerprint_invariant_under_commuting_interleavings(base):
    a = fresh(base)
    b = fresh(base)
    x1 = np.asarray(a.levels[0].vectors[0][:3]) + 0.01
    x2 = np.asarray(a.levels[0].vectors[1][:3]) + 0.01
    dele = np.arange(1850, 1855)
    # disjoint write batches commute: arrival order is not logical state
    a.insert(x1, np.array([95_000, 95_001, 95_002]))
    a.insert(x2, np.array([95_010, 95_011, 95_012]))
    a.delete(dele)
    b.delete(dele)
    b.insert(x2, np.array([95_010, 95_011, 95_012]))
    b.insert(x1, np.array([95_000, 95_001, 95_002]))
    assert index_state_fingerprint(a) == index_state_fingerprint(b)


def test_fingerprint_stable_across_save_load(base, ops, tmp_path):
    idx = fresh(base)
    for op in ops[:8]:
        apply_op(idx, op)
    fp = index_state_fingerprint(idx)
    idx.save(str(tmp_path))
    assert index_state_fingerprint(QuakeIndex.load(str(tmp_path))) == fp
    # serving-session state (journal, stats) is excluded by contract
    idx.journal.record(dirty=np.array([0]), reason="noise")
    assert index_state_fingerprint(idx) == fp


# ---------------------------------------------------------------------------
# the randomized kill-point harness (acceptance criterion)
# ---------------------------------------------------------------------------

SITES = ("wal_torn_write", "wal_corrupt_record",
         "ckpt_crash_before_rename", "fsync_dropped")
KILL_SAMPLES = 56                            # 14 per fault site


@pytest.mark.parametrize("sample", range(KILL_SAMPLES))
def test_kill_point_recovery_is_prefix_consistent(tmp_path, base, ops,
                                                 sample):
    """Crash at a seeded random point under one of the four durability
    fault sites; recovery must land on a *prefix* of the admitted write
    stream whose fingerprint is byte-identical to a fault-free twin
    replay of that prefix."""
    site = SITES[sample % len(SITES)]
    rng = np.random.default_rng([202608, sample])
    rate = float(rng.uniform(0.05, 0.5))
    policy = ("always", "batch", "off")[sample % 3]
    ckpt_every = int(rng.choice([4, 7, 10]))
    fi = FaultInjector(seed=1000 + sample, rates={site: rate})

    idx = fresh(base)
    dm = DurabilityManager(idx, str(tmp_path), fsync=policy,
                           wal_batch_ops=3, ckpt_every_ops=ckpt_every,
                           faults=fi)
    admitted = 0
    for op in ops:
        try:
            if op[0] == "insert":
                dm.log_insert(op[1], op[2])
            else:
                dm.log_delete(op[1])
        except InjectedFault:
            break                            # crashed mid-append: the op
        apply_op(idx, op)                    # was never applied
        admitted += 1
        if dm.checkpoint_due():
            try:
                dm.checkpoint()
            except InjectedFault:
                break                        # crashed before the rename
    dm.simulate_crash(keep_unsynced=int(rng.integers(0, 4096)))

    rec, rep = recover_index(str(tmp_path))
    m = rep.write_ops_recovered
    assert 0 <= m <= admitted, (site, policy, m, admitted)
    twin = fresh(base)
    for op in ops[:m]:
        apply_op(twin, op)
    assert index_state_fingerprint(rec) == index_state_fingerprint(twin), \
        (site, policy, rate, m, admitted, rep)
    rec.check_invariants()
