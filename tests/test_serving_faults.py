"""Failure semantics of the serving runtime (docs/serving.md):

  * per-query latency budgets — budget-expired queries retire at the
    end of the current round with their running top-k, status PARTIAL,
    carrying a *finite* recall estimate (the round loop's refined APS
    number over what was actually scanned);
  * admission control — bounded queue with block / shed-oldest /
    shed-newest policies; shed queries complete immediately with SHED;
  * degradation governor — sustained queue pressure steps the effective
    recall target down and caps probe budgets; calm restores them;
  * fault injection + self-healing (src/repro/faults.py) — scan faults
    retry with backoff then fail only the affected batch (FAILED);
    maintenance crashes roll back (index version unchanged, retried on
    the next trigger); cache failures degrade to cache-off; a dead
    ticker restarts on the next admission; a wedged ticker is counted.

Every admitted query reaches exactly one terminal status:
``sum(status_counts.values()) == queries_submitted`` is asserted
throughout.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import (QuakeConfig, QuakeIndex, ServingConfig,
                        ServingRuntime)
from repro.core import multiquery as mq
from repro.core.maintenance import (Maintainer, checkpoint_index,
                                    restore_index)
from repro.core.serving import (STATUS_FAILED, STATUS_OK, STATUS_PARTIAL,
                                STATUS_SHED, TERMINAL_STATUSES)
from repro.data import datasets
from repro.faults import FaultInjector, InjectedFault, index_state_fingerprint


@pytest.fixture(scope="module")
def ds():
    return datasets.clustered(3000, 16, n_clusters=12, seed=0)


def build(ds):
    return QuakeIndex.build(ds.vectors, num_partitions=16, kmeans_iters=3,
                            config=QuakeConfig(recall_target=0.9))


def _terminal_invariant(rt):
    st = rt.stats()
    assert sum(st["status_counts"].values()) == st["queries_submitted"], st
    return st


# ---------------------------------------------------------------------------
# config validation (satellite: reject zero/negative deadlines, _ms wins)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    {"flush_deadline": 0.0}, {"flush_deadline": -1.0},
    {"flush_deadline_ms": 0.0}, {"flush_deadline_ms": -5.0},
    {"deadline_s": 0.0}, {"deadline_s": -0.1},
    {"queue_cap": 0}, {"queue_policy": "drop-all"},
    {"govern_low": 0.8, "govern_high": 0.2}, {"govern_low": 0.0},
    {"govern_patience": 0}, {"govern_max_steps": 0},
    {"govern_probe_frac": 0.0}, {"govern_probe_frac": 1.5},
    {"scan_retries": -1}, {"scan_backoff_s": -0.1},
])
def test_config_validation_rejects(kw):
    with pytest.raises(ValueError):
        ServingConfig(**kw)


def test_config_ms_wins_over_seconds():
    cfg = ServingConfig(flush_deadline=9.0, flush_deadline_ms=5.0)
    assert cfg.flush_deadline == pytest.approx(0.005)
    # seconds-only form still folds through untouched
    assert ServingConfig(flush_deadline=0.25).flush_deadline == 0.25


def test_submit_rejects_nonpositive_deadline(ds):
    with ServingRuntime(build(ds), ServingConfig(k=5)) as rt:
        with pytest.raises(ValueError):
            rt.submit_query(np.zeros(16, np.float32), deadline_s=0.0)


# ---------------------------------------------------------------------------
# fault injector determinism
# ---------------------------------------------------------------------------

def test_injector_deterministic_per_site():
    a = FaultInjector(seed=7, rates={"scan": 0.3, "cache": 0.3})
    b = FaultInjector(seed=7, rates={"scan": 0.3, "cache": 0.3})
    # interleave differently: site streams must not influence each other
    seq_a = [a.fire("scan") for _ in range(50)]
    [a.fire("cache") for _ in range(17)]
    seq_a += [a.fire("scan") for _ in range(50)]
    [b.fire("cache") for _ in range(3)]
    seq_b = [b.fire("scan") for _ in range(100)]
    assert seq_a == seq_b
    assert a.counters()["draws"]["scan"] == 100
    with pytest.raises(ValueError):
        FaultInjector(rates={"not-a-site": 1.0})
    with pytest.raises(InjectedFault):
        FaultInjector(rates={"ticker": 1.0}).check("ticker")


# ---------------------------------------------------------------------------
# per-query latency budgets -> PARTIAL
# ---------------------------------------------------------------------------

def test_round_loop_deadline_budget(ds):
    """The Algorithm-2 primitive: the loop stops at the end of the
    current round once the budget is spent — at least one round always
    runs — and reports it in the trace."""
    import jax.numpy as jnp
    idx = build(ds)
    ex = mq.BatchedSearchExecutor(idx, storage_dtype="f32")
    q = datasets.queries_near(ds, 6, seed=3).astype(np.float32)
    snap = ex.snapshot()
    rplan = mq.plan_rounds(idx, q, 10, 0.99, planner=ex.planner,
                           cache=ex.planner_cache,
                           cent_norms=ex._cent_norms)
    q_dev = jnp.asarray(q)
    seq_dev = (rplan.seq_dev if rplan.seq_dev is not None
               else jnp.asarray(rplan.seq.astype(np.int32)))

    def scan_round(take, kept):
        return ex.scan_probe_round(q_dev, seq_dev, take, kept, 10,
                                   snap=snap, seq_host=rplan.seq)

    def run(deadline_s, clock):
        return mq.run_round_loop(
            rplan, 10, 0.99, idx._beta_table, mq._batch_rho_fn(idx, q),
            scan_round, rounds=4, k_keep=10,
            deadline_s=deadline_s, clock=clock)

    t = {"now": 0.0}

    def fast_clock():              # every read advances a full second
        t["now"] += 1.0
        return t["now"]

    *_, n_full, trace_full, _ = run(None, None)
    *_, n_cut, trace_cut, _ = run(0.5, fast_clock)
    assert not trace_full["budget_expired"]
    assert trace_cut["budget_expired"]
    assert n_cut == 1              # budget spent after the first round
    assert n_cut <= n_full


def test_partial_results_on_expired_budget(ds):
    """A fake clock that leaps past every per-query deadline: queries
    retire PARTIAL at the end of the first round, with running top-k
    and a finite recall estimate."""
    t = {"now": 0.0}

    def clock():
        t["now"] += 1.0
        return t["now"]

    idx = build(ds)
    cfg = ServingConfig(k=10, flush_size=4, scan_backend="host",
                        recall_target=0.99, rounds=4, ticker=False,
                        interleave_rounds=1, maint_min_ops=10 ** 9)
    qs = datasets.queries_near(ds, 4, seed=5).astype(np.float32)
    with ServingRuntime(idx, cfg, clock=clock) as rt:
        qids = [rt.submit_query(q, deadline_s=0.5) for q in qs]
        rt.drain()
        st = _terminal_invariant(rt)
        assert st["partials"] >= 1
        saw_partial = False
        for qid in qids:
            res = rt.result(qid)
            assert res is not None and res.status in TERMINAL_STATUSES
            if res.status == STATUS_PARTIAL:
                saw_partial = True
                assert np.isfinite(res.recall_estimate)
                assert 0.0 <= res.recall_estimate <= 1.0
                assert res.rounds >= 1           # ran at least one round
        assert saw_partial

    # same queries, no budget: everything completes OK
    t["now"] = 0.0
    with ServingRuntime(build(ds), cfg, clock=clock) as rt2:
        for q in qs:
            rt2.submit_query(q)
        rt2.drain()
        st2 = _terminal_invariant(rt2)
        assert st2["partials"] == 0
        assert st2["status_counts"][STATUS_OK] == len(qs)


# ---------------------------------------------------------------------------
# admission control / load shedding
# ---------------------------------------------------------------------------

def test_shed_newest_policy(ds):
    cfg = ServingConfig(k=5, flush_size=10 ** 6, queue_cap=2,
                        queue_policy="shed-newest", ticker=False)
    qs = datasets.queries_near(ds, 5, seed=1).astype(np.float32)
    with ServingRuntime(build(ds), cfg) as rt:
        qids = [rt.submit_query(q) for q in qs]
        # first two queued, the rest shed immediately
        for qid in qids[2:]:
            res = rt.result(qid)
            assert res is not None and res.status == STATUS_SHED
            assert res.recall_estimate == 0.0 and np.all(res.ids == -1)
        rt.drain()
        st = _terminal_invariant(rt)
        assert st["queries_shed"] == 3
        assert st["status_counts"][STATUS_SHED] == 3
        assert st["status_counts"][STATUS_OK] == 2


def test_shed_oldest_policy(ds):
    cfg = ServingConfig(k=5, flush_size=10 ** 6, queue_cap=2,
                        queue_policy="shed-oldest", ticker=False)
    qs = datasets.queries_near(ds, 5, seed=2).astype(np.float32)
    with ServingRuntime(build(ds), cfg) as rt:
        qids = [rt.submit_query(q) for q in qs]
        # the three oldest were evicted; the two newest survive
        for qid in qids[:3]:
            assert rt.result(qid).status == STATUS_SHED
        rt.drain()
        st = _terminal_invariant(rt)
        assert st["queries_shed"] == 3
        for qid in qids[3:]:
            assert rt.result(qid).status == STATUS_OK


def test_block_policy_applies_backpressure(ds):
    """block: the submitter pays for a flush and retries — nothing is
    shed, every query completes, and the queue never exceeds the cap."""
    cfg = ServingConfig(k=5, flush_size=10 ** 6, queue_cap=2,
                        queue_policy="block", ticker=False)
    qs = datasets.queries_near(ds, 7, seed=3).astype(np.float32)
    with ServingRuntime(build(ds), cfg) as rt:
        qids = [rt.submit_query(q) for q in qs]
        rt.drain()
        st = _terminal_invariant(rt)
        assert st["queries_shed"] == 0
        assert st["status_counts"][STATUS_OK] == len(qs)
        assert all(rt.result(q).status == STATUS_OK for q in qids)


def test_governor_degrades_and_restores(ds):
    cfg = ServingConfig(k=5, flush_size=4, queue_cap=4, govern=True,
                        govern_high=0.75, govern_low=0.25,
                        govern_patience=1, govern_step=0.05,
                        govern_max_steps=2, govern_probe_frac=0.5,
                        recall_target=0.9, ticker=False,
                        maint_min_ops=10 ** 9)
    qs = datasets.queries_near(ds, 32, seed=4).astype(np.float32)
    with ServingRuntime(build(ds), cfg) as rt:
        base = rt.target
        # full-cap flushes: sustained pressure -> degrade
        for q in qs[:8]:
            rt.submit_query(q)        # flush_size=4 == queue_cap fill
        st = rt.stats()
        assert st["governor"]["degrades"] >= 1
        assert st["effective_target"] < base
        assert st["probe_frac"] is not None and st["probe_frac"] < 1.0
        steps_after_pressure = st["governor"]["steps"]
        # empty flushes: sustained calm -> restore to baseline
        for _ in range(2 * steps_after_pressure):
            rt.flush()
        rt.drain()
        st = _terminal_invariant(rt)
        assert st["governor"]["restores"] >= steps_after_pressure
        assert st["governor"]["steps"] == 0
        assert st["effective_target"] == pytest.approx(base)
        assert st["probe_frac"] is None


# ---------------------------------------------------------------------------
# scan faults: retry with backoff, then fail only the affected batch
# ---------------------------------------------------------------------------

def test_scan_fault_recovers_with_retry(ds):
    """Rate-1.0 scan faults with enough retries: every round scan fails
    then succeeds on retry — results identical to the fault-free run."""
    sleeps = []
    fi = FaultInjector(seed=3, rates={"scan": 0.5},
                       sleep_fn=sleeps.append)
    cfg = ServingConfig(k=10, flush_size=4, scan_backend="host",
                        ticker=False, scan_retries=8,
                        scan_backoff_s=0.001, scan_backoff_max_s=0.004,
                        maint_min_ops=10 ** 9)
    qs = datasets.queries_near(ds, 8, seed=6).astype(np.float32)
    with ServingRuntime(build(ds), cfg, faults=fi) as rt:
        qids = [rt.submit_query(q) for q in qs]
        rt.drain()
        st = _terminal_invariant(rt)
        assert st["status_counts"][STATUS_OK] == len(qs)
        assert st["scan_faults"] >= 1
        assert st["scan_retries_used"] >= 1
        assert st["failed_batches"] == 0
    with ServingRuntime(build(ds), cfg) as clean:
        ref = [clean.submit_query(q) for q in qs]
        clean.drain()
        for qid, rid in zip(qids, ref):
            np.testing.assert_array_equal(rt.result(qid).ids,
                                          clean.result(rid).ids)
    # backoff doubled then capped
    if len(sleeps) >= 3:
        assert sleeps[0] <= sleeps[1] <= max(sleeps) <= 0.004 + 1e-12


def test_scan_fault_exhausts_retries_fails_batch_only(ds):
    fi = FaultInjector(seed=1, rates={"scan": 1.0}, sleep_fn=lambda s: None)
    cfg = ServingConfig(k=10, flush_size=4, scan_backend="host",
                        ticker=False, scan_retries=2,
                        maint_min_ops=10 ** 9)
    qs = datasets.queries_near(ds, 8, seed=7).astype(np.float32)
    with ServingRuntime(build(ds), cfg, faults=fi) as rt:
        first = [rt.submit_query(q) for q in qs[:4]]
        rt.drain()
        for qid in first:
            res = rt.result(qid)
            assert res.status == STATUS_FAILED
            assert "InjectedFault" in res.error
            assert np.all(res.ids == -1) and np.all(np.isinf(res.dists))
        # the runtime survives: stop injecting, later batches succeed
        fi.rates["scan"] = 0.0
        second = [rt.submit_query(q) for q in qs[4:]]
        rt.drain()
        assert all(rt.result(q).status == STATUS_OK for q in second)
        st = _terminal_invariant(rt)
        assert st["failed_batches"] == 1
        assert st["status_counts"][STATUS_FAILED] == 4
        assert st["status_counts"][STATUS_OK] == 4


def test_slow_round_stall_is_absorbed(ds):
    """A straggler round (stall injection) delays but never corrupts:
    queries complete OK, and the injected sleeps actually happened."""
    sleeps = []
    fi = FaultInjector(seed=4, rates={"slow_round": 1.0}, delay_s=0.001,
                       sleep_fn=sleeps.append)
    cfg = ServingConfig(k=10, flush_size=4, scan_backend="host",
                        ticker=False, maint_min_ops=10 ** 9)
    qs = datasets.queries_near(ds, 4, seed=10).astype(np.float32)
    with ServingRuntime(build(ds), cfg, faults=fi) as rt:
        qids = [rt.submit_query(q) for q in qs]
        rt.drain()
        st = _terminal_invariant(rt)
        assert st["status_counts"][STATUS_OK] == len(qs)
        assert all(rt.result(q).status == STATUS_OK for q in qids)
    assert len(sleeps) >= 1 and all(s == 0.001 for s in sleeps)


# ---------------------------------------------------------------------------
# cache faults degrade to cache-off
# ---------------------------------------------------------------------------

def test_cache_fault_degrades_to_cache_off(ds):
    fi = FaultInjector(seed=2, rates={"cache": 1.0})
    cfg = ServingConfig(k=10, flush_size=2, scan_backend="host",
                        cache_entries=64, ticker=False,
                        maint_min_ops=10 ** 9)
    qs = datasets.queries_near(ds, 6, seed=8).astype(np.float32)
    with ServingRuntime(build(ds), cfg, faults=fi) as rt:
        qids = [rt.submit_query(q) for q in qs]
        rt.drain()
        st = _terminal_invariant(rt)
        # every query still answered, none errored
        assert all(rt.result(q).status == STATUS_OK for q in qids)
        assert st["cache_errors"] >= 1
        assert st["cache_disabled"] is True
        # degraded mode: no further probes, identical repeat is re-run
        rpt = rt.submit_query(qs[0])
        rt.drain()
        assert rt.result(rpt).from_cache is False
        _terminal_invariant(rt)


# ---------------------------------------------------------------------------
# maintenance crash mid-recluster: rollback, version unchanged, retried
# ---------------------------------------------------------------------------

def _skewed_index(seed=1, hot=2, cold=10, hot_size=2500, cold_size=250,
                  dim=16):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(hot + cold, dim)) * 6
    parts = [centers[i] + rng.normal(size=(hot_size, dim))
             for i in range(hot)]
    parts += [centers[hot + i] + rng.normal(size=(cold_size, dim))
              for i in range(cold)]
    x = np.concatenate(parts).astype(np.float32)
    idx = QuakeIndex.build(x, num_partitions=hot + cold, kmeans_iters=4)
    for q in np.concatenate(
            [centers[i] + rng.normal(size=(60, dim)) for i in range(hot)]
    ).astype(np.float32):
        idx.search(q, 10)
    return idx


def test_checkpoint_restore_roundtrip():
    idx = _skewed_index()
    before_fp = index_state_fingerprint(idx)
    before_v = idx.version
    ckpt = checkpoint_index(idx)
    rep = Maintainer(idx).run()
    assert rep.splits + rep.merges >= 1       # something actually moved
    assert index_state_fingerprint(idx) != before_fp
    restore_index(idx, ckpt)
    assert index_state_fingerprint(idx) == before_fp
    assert idx.version == before_v
    idx.check_invariants()


def test_maintenance_crash_rolls_back_and_retries():
    idx = _skewed_index()
    fi = FaultInjector(seed=0, rates={"maintenance": 1.0})
    cfg = ServingConfig(k=10, flush_size=4, scan_backend="host",
                        ticker=False, maint_min_ops=10 ** 9)
    with ServingRuntime(idx, cfg, faults=fi) as rt:
        before_fp = index_state_fingerprint(idx)
        before_v = idx.version
        rep = rt.maybe_maintain(force=True)
        assert rep is None                    # the pass crashed
        st = rt.stats()
        assert st["maintenance_failures"] == 1
        assert st["maintenance_runs"] == 0    # nothing was committed
        # rollback: index state and version byte-identical
        assert index_state_fingerprint(idx) == before_fp
        assert idx.version == before_v
        idx.check_invariants()
        # self-healing: stop injecting, the retry commits
        fi.rates["maintenance"] = 0.0
        rep = rt.maybe_maintain(force=True)
        assert rep is not None and rep.splits + rep.merges >= 1
        assert rt.stats()["maintenance_runs"] == 1
        idx.check_invariants()


# ---------------------------------------------------------------------------
# ticker: death -> restart on next admission; wedge -> counted in close()
# ---------------------------------------------------------------------------

def test_ticker_death_restarts_on_admission(ds):
    fi = FaultInjector(seed=0, rates={"ticker": 1.0})
    cfg = ServingConfig(k=5, flush_size=10 ** 6, flush_deadline_ms=4.0,
                        ticker=True, maint_min_ops=10 ** 9)
    with ServingRuntime(build(ds), cfg, faults=fi) as rt:
        deadline = time.perf_counter() + 5.0
        while (rt.stats()["ticker_errors"] == 0
               and time.perf_counter() < deadline):
            time.sleep(0.005)
        st = rt.stats()
        assert st["ticker_errors"] >= 1       # the injected tick killed it
        # next admission revives the ticker (which dies again at rate
        # 1.0 — restarts keep pace with deaths, flushes keep happening)
        rt.submit_query(datasets.queries_near(ds, 1, seed=9)
                        .astype(np.float32)[0])
        assert rt.stats()["ticker_restarts"] >= 1
        rt.drain()
        _terminal_invariant(rt)


def test_close_detects_wedged_ticker(ds):
    class WedgedThread:
        name = "serving-ticker"

        def join(self, timeout=None):
            pass                              # never actually joins

        def is_alive(self):
            return True

    cfg = ServingConfig(k=5, flush_deadline_ms=50.0, ticker=True)
    rt = ServingRuntime(build(ds), cfg)
    real = rt._ticker_thread
    rt._ticker_thread = WedgedThread()
    rt.close()
    st = rt.stats()
    assert st["ticker_wedged"] is True
    assert rt._ticker_thread is not None      # kept observable
    # the real thread exits via _closed; tidy up
    if real is not None:
        real.join(timeout=5.0)
        assert not real.is_alive()


def test_close_clean_ticker_not_wedged(ds):
    cfg = ServingConfig(k=5, flush_deadline_ms=50.0, ticker=True)
    rt = ServingRuntime(build(ds), cfg)
    rt.close()
    assert rt.stats()["ticker_wedged"] is False
    assert rt._ticker_thread is None


@pytest.mark.parametrize("real", [True, False], ids=["real", "injected"])
def test_first_real_scan_error_logged_with_traceback(ds, caplog, real):
    """A real scan error (a compile or device fault) is logged once with
    its traceback; injected faults are counted but not logged."""
    fi = FaultInjector(seed=1, rates={"scan": 0.0 if real else 1.0},
                       sleep_fn=lambda s: None)
    cfg = ServingConfig(k=10, flush_size=4, scan_backend="host",
                        ticker=False, scan_retries=1,
                        maint_min_ops=10 ** 9)
    qs = datasets.queries_near(ds, 8, seed=7).astype(np.float32)
    with ServingRuntime(build(ds), cfg, faults=fi) as rt:
        if real:
            def broken(*a, **kw):
                raise ValueError("Mosaic refused the kernel")
            rt.scheduler._scan_once = broken
        with caplog.at_level("ERROR", logger="repro.serving"):
            for q in qs:
                rt.submit_query(q)
            rt.drain()
        st = _terminal_invariant(rt)
        assert st["status_counts"][STATUS_FAILED] == len(qs)
        assert st["scan_faults"] == 4          # 2 batches x (1 + 1 retry)
    logged = [r for r in caplog.records if r.name == "repro.serving"
              and r.levelname == "ERROR"]
    if real:
        assert len(logged) == 1
        assert logged[0].exc_info is not None
        assert "Mosaic refused the kernel" in caplog.text
        assert "Traceback" in caplog.text
    else:
        assert logged == []
