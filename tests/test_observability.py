"""Observability stack (repro.obs + the serving hooks).

Pins the subsystem's contracts:

  * registry semantics — counters/gauges/histograms under one innermost
    lock, log-bucketed percentiles within the documented ~4.4% relative
    error, the lazy-fold pending buffer invisible to readers, and the
    batched ``update`` path equivalent to per-sample recording;
  * ``summarize`` — the repo's one shared percentile path matches
    ``numpy.percentile`` (linear interpolation) exactly;
  * the pinned ``round_trace`` schema (docs/observability.md) that the
    serving trace emitter and benchmarks/common rely on;
  * ``metrics_snapshot()`` golden dotted names, and the zero-observer
    guarantee: ``ServingConfig(metrics=False)`` yields byte-identical
    results, including under admission-log replay;
  * trace spans — compact terminal records expand to full
    admit -> flush -> round* -> done event lists; cache hits and shed
    queries get single-instant spans; ring eviction is accounted;
  * profiler spans — the served path's steps on the profiler's
    timeline, nested flush > round > scan/fold, counted as the
    registry's histograms count them, and none with metrics off.
"""
import json
import threading

import numpy as np
import pytest

from repro.core import (QuakeConfig, QuakeIndex, ServingConfig,
                        ServingRuntime)
from repro.core.serving import STATUS_OK, STATUS_SHED
from repro.data import datasets
from repro.obs import (CalibrationTracker, Histogram, MetricsRegistry,
                       QueryTracer, summarize, to_prometheus)
from repro.obs.tracing import DONE_FIELDS


@pytest.fixture(scope="module")
def ds():
    return datasets.clustered(4000, 16, n_clusters=16, seed=0)


def build(ds, **cfg):
    return QuakeIndex.build(ds.vectors, num_partitions=32, kmeans_iters=4,
                            config=QuakeConfig(**cfg))


def serve_cfg(**kw):
    kw.setdefault("k", 10)
    kw.setdefault("flush_size", 8)
    kw.setdefault("scan_backend", "host")
    kw.setdefault("maint_min_ops", 10 ** 9)
    return ServingConfig(**kw)


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------

def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.inc("a.count")
    reg.inc("a.count", 4)
    reg.set_gauge("a.gauge", 2.5)
    reg.set_gauge("a.gauge", 1.5)          # last write wins
    for v in (0.001, 0.002, 0.003):
        reg.observe("a.lat", v)
    assert reg.counter("a.count") == 5
    assert reg.counter("missing") == 0
    assert reg.gauge("a.gauge") == 1.5
    snap = reg.histogram("a.lat")
    assert snap["count"] == 3
    assert snap["min"] == 0.001 and snap["max"] == 0.003
    assert snap["sum"] == pytest.approx(0.006)
    # unknown histogram reads as the empty snapshot, not an error
    assert reg.histogram("missing")["count"] == 0
    flat = reg.snapshot()
    assert flat["a.count"] == 5
    assert flat["a.gauge"] == 1.5
    assert flat["a.lat.count"] == 3


def test_registry_update_batch_equivalent():
    """The batched hot-path entry point records exactly what the
    per-sample calls would."""
    a, b = MetricsRegistry(), MetricsRegistry()
    vals = [0.01, 0.02, 0.05, 0.1]
    a.update(counters={"c": 3}, gauges={"g": 7.0},
             observations={"h": vals})
    b.inc("c", 3)
    b.set_gauge("g", 7.0)
    for v in vals:
        b.observe("h", v)
    assert a.snapshot() == b.snapshot()


def test_histogram_percentile_accuracy():
    """Log buckets at 8/octave: every reported percentile within the
    documented ~4.4% relative error of the exact order statistic, and
    clamped to the exact observed [min, max]."""
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=-7.0, sigma=1.0, size=5000)
    h = Histogram()
    h.observe_many(xs)
    for q in (0.5, 0.9, 0.95, 0.99):
        exact = float(np.percentile(xs, q * 100))
        got = h.percentile(q)
        assert abs(got - exact) / exact <= 0.045, (q, got, exact)
    snap = h.snapshot()
    assert snap["min"] == float(xs.min())
    assert snap["max"] == float(xs.max())
    # single observation: envelope clamping makes the snapshot exact
    h1 = Histogram()
    h1.observe(0.0123)
    s1 = h1.snapshot()
    assert s1["p50"] == s1["p99"] == s1["min"] == s1["max"] == 0.0123


def test_histogram_lazy_fold():
    """Recording only appends to the pending buffer; folds happen at the
    _FOLD_AT threshold and on any read — never visible to readers."""
    h = Histogram()
    h.observe(0.5)
    assert h.count == 0 and len(h._pending) == 1     # not folded yet
    assert h.snapshot()["count"] == 1                # read folds
    assert not h._pending
    h.observe_many([0.1] * (Histogram._FOLD_AT - 1))
    assert h._pending                                 # below threshold
    h.observe(0.1)                                    # hits _FOLD_AT
    assert not h._pending and h.count == 1 + Histogram._FOLD_AT
    # non-finite samples are discarded at fold time
    h2 = Histogram()
    h2.observe_many([1.0, float("nan"), float("inf"), 2.0])
    assert h2.snapshot()["count"] == 2


def test_registry_thread_safety_smoke():
    reg = MetricsRegistry()
    n_threads, per = 8, 500

    def worker(t):
        for i in range(per):
            reg.update(counters={"hits": 1},
                       observations={"lat": (float(i + 1) * 1e-6,)})

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("hits") == n_threads * per
    assert reg.histogram("lat")["count"] == n_threads * per


# ---------------------------------------------------------------------------
# summarize — the shared percentile path
# ---------------------------------------------------------------------------

def test_summarize_matches_numpy_percentile():
    rng = np.random.default_rng(1)
    xs = rng.random(257)
    s = summarize(xs)
    assert s["count"] == 257
    assert s["min"] == float(xs.min()) and s["max"] == float(xs.max())
    assert s["mean"] == pytest.approx(float(xs.mean()))
    for key, q in (("p50", 50), ("p95", 95), ("p99", 99)):
        assert s[key] == pytest.approx(float(np.percentile(xs, q)))


def test_summarize_edge_cases():
    empty = summarize([])
    assert empty["count"] == 0 and empty["p99"] == 0.0
    one = summarize([0.25])
    assert one["p50"] == one["p99"] == one["min"] == one["max"] == 0.25


def test_to_prometheus_exposition():
    text = to_prometheus({"a.b": 1, "lat.p50": 0.5, "flag": True,
                          "skip_nan": float("nan"), "skip_str": "x"})
    lines = text.strip().split("\n")
    assert "quake_a_b 1" in lines
    assert "quake_lat_p50 0.5" in lines
    assert "quake_flag 1" in lines                  # bool -> 0/1
    assert not any("skip" in ln for ln in lines)    # nan/str dropped
    assert text.endswith("\n")


# ---------------------------------------------------------------------------
# round_trace — the pinned per-round schema
# ---------------------------------------------------------------------------

ROUND_TRACE_KEYS = {"round_live", "round_partitions", "round_vectors",
                    "round_comparisons", "round_kth", "round_wall_s",
                    "budget_expired", "timed_out_rows"}


def test_round_trace_pinned_schema(ds):
    """docs/observability.md pins exactly these keys; the serving trace
    emitter and benchmarks/common.round_trajectory both rely on them."""
    idx = build(ds)
    q = datasets.queries_near(ds, 24, seed=30)
    r = idx.search_batch(q, 10, recall_target=0.9)
    tr = r.round_trace
    assert tr is not None
    assert set(tr.keys()) == ROUND_TRACE_KEYS
    assert r.rounds >= 1
    for key in ("round_live", "round_partitions", "round_vectors",
                "round_comparisons", "round_kth", "round_wall_s"):
        assert len(tr[key]) == r.rounds, key
    assert isinstance(tr["budget_expired"], bool)
    assert isinstance(tr["timed_out_rows"], int)
    assert tr["round_live"][0] == len(q)
    assert all(w >= 0.0 for w in tr["round_wall_s"])
    assert sum(tr["round_vectors"]) == r.vectors_scanned


# ---------------------------------------------------------------------------
# metrics_snapshot — golden dotted names
# ---------------------------------------------------------------------------

GOLDEN_KEYS = (
    # serving front-end
    "serving.queries_submitted", "serving.queries_completed",
    "serving.flushes", "serving.in_flight", "serving.queue_depth",
    "serving.write_ops", "serving.cache_hits", "serving.queries_shed",
    "serving.status.OK", "serving.status.PARTIAL",
    "serving.status.SHED", "serving.status.FAILED",
    "serving.governor.steps",
    # latency histograms (registry-backed)
    "serving.latency_s.count", "serving.latency_s.p50",
    "serving.latency_s.p95", "serving.latency_s.p99",
    "serving.queue_wait_s.count", "serving.queue_wait_s.p50",
    # scheduler
    "scheduler.rounds", "scheduler.partitions_streamed",
    "scheduler.vectors_streamed", "scheduler.round_wall_s.count",
    "scheduler.round_wall_s.p50",
    "scheduler.one_window_rows", "scheduler.windowed_rows",
    # the snapshot's partition shards (one on one device)
    "scan.shards", "scan.shard_vectors_max",
    # the served path's steps, on the runtime's clock
    "serving.engine_wait_s.count", "serving.flush_s.count",
    "planner.plan_s.count", "scan.wait_s.count",
    # calibration (LatencyModel predicted vs observed)
    "calibration.latency.samples", "calibration.latency.rel_err",
    "calibration.latency.predicted_s.p50",
    "calibration.latency.observed_s.p50",
    # tracer
    "trace.emitted", "trace.dropped", "trace.completed",
    "trace.flushes_tracked", "trace.rounds_tracked",
    # maintenance + sanitizer bridge
    "maintenance.runs", "sanitize.acquisitions",
    "sanitize.order_violations", "sanitize.guarded_violations",
)


def test_metrics_snapshot_golden_keys(ds):
    rt = ServingRuntime(build(ds), serve_cfg())
    q = datasets.queries_near(ds, 40, seed=31)
    rt.submit_batch(q)
    rt.submit_insert(ds.vectors[:5] + 0.01, np.arange(90_000, 90_005))
    rt.drain()
    ms = rt.metrics_snapshot()
    missing = [k for k in GOLDEN_KEYS if k not in ms]
    assert not missing, missing
    assert ms["serving.queries_submitted"] == 40
    assert ms["serving.latency_s.count"] == 40
    assert ms["trace.completed"] == 40
    assert ms["scheduler.rounds"] >= 1
    assert ms["calibration.latency.samples"] >= 1
    # numbers only: renderable straight to Prometheus text
    assert all(isinstance(v, (int, float)) for v in ms.values())
    text = to_prometheus(ms)
    assert "quake_serving_latency_s_p50" in text
    # snapshots never lag in-flight rounds: a second drain-free read
    # still balances submitted == completed
    assert ms["serving.queries_completed"] >= ms["serving.queries_submitted"]


def test_metrics_off_byte_identical(ds):
    """metrics=False leaves rt.obs None; every result is byte-identical
    to the metrics-on run of the same operation stream."""
    q = datasets.queries_near(ds, 32, seed=32).astype(np.float32)
    ins = ds.vectors[:8] + 0.01

    def run(metrics):
        rt = ServingRuntime(build(ds), serve_cfg(metrics=metrics))
        qa = rt.submit_batch(q[:20])
        rt.submit_insert(ins, np.arange(91_000, 91_008))
        qb = rt.submit_batch(q[20:])
        rt.drain()
        return rt, [rt.result(i) for i in qa + qb]

    rt_on, res_on = run(True)
    rt_off, res_off = run(False)
    assert rt_on.obs is not None and rt_off.obs is None
    for a, b in zip(res_on, res_off):
        assert a.ids.tobytes() == b.ids.tobytes()
        assert a.dists.tobytes() == b.dists.tobytes()
        assert a.status == b.status and a.nprobe == b.nprobe
    # the snapshot still works without the registry: stats-only keys
    ms_off = rt_off.metrics_snapshot()
    assert "serving.queries_submitted" in ms_off
    assert "trace.emitted" not in ms_off


def test_metrics_off_admission_replay_identical(ds):
    """A metrics-on run's admission log, replayed on a metrics-off twin,
    reproduces every per-query result byte-for-byte — the observability
    layer is a pure observer even of admission ordering."""
    q = datasets.queries_near(ds, 30, seed=33).astype(np.float32)
    rt = ServingRuntime(build(ds), serve_cfg(flush_size=4,
                                             record_admissions=True))
    qvec = {}
    for i, row in enumerate(q):
        qid = rt.submit_query(row)
        qvec[qid] = row
        if i == 10:
            rt.submit_insert(ds.vectors[:3] + 0.02,
                             np.arange(92_000, 92_003))
    rt.drain()
    log = rt.admission_log()
    ref = {qid: rt.result(qid) for qid in qvec}

    rt2 = ServingRuntime(build(ds), serve_cfg(flush_size=10 ** 9,
                                              metrics=False))
    pairs = []
    for entry in log:
        if entry[0] == "q":
            for qid in entry[1]:
                pairs.append((qid, rt2.submit_query(qvec[qid])))
            rt2.flush()
        elif entry[0] == "insert":
            rt2.submit_insert(entry[1], entry[2])
        else:
            rt2.submit_delete(entry[1])
    rt2.drain()
    assert pairs
    for orig, rep in pairs:
        got = rt2.result(rep)
        assert ref[orig].ids.tobytes() == got.ids.tobytes()
        assert ref[orig].dists.tobytes() == got.dists.tobytes()


# ---------------------------------------------------------------------------
# trace spans
# ---------------------------------------------------------------------------

def test_trace_span_synthesis(ds, tmp_path):
    """Compact terminal records expand to ordered
    admit -> flush -> round* -> done event lists with non-decreasing
    timestamps, and dump_jsonl round-trips them as JSON-lines."""
    rt = ServingRuntime(build(ds), serve_cfg(flush_size=8))
    q = datasets.queries_near(ds, 16, seed=34)
    qids = rt.submit_batch(q)
    rt.drain()
    spans = rt.obs.tracer.spans()
    by_qid = {s["qid"]: s for s in spans if "qid" in s}
    assert set(qids) <= set(by_qid)
    saw_round = False
    for qid in qids:
        s = by_qid[qid]
        assert s["status"] == STATUS_OK
        names = [e["e"] for e in s["events"]]
        assert names[0] == "admit" and names[-1] == "done"
        assert "flush" in names
        assert names.index("flush") == 1            # right after admit
        saw_round |= "round" in names
        ts = [e["t"] for e in s["events"]]
        assert ts == sorted(ts)                     # non-decreasing
        done = s["events"][-1]
        assert done["status"] == STATUS_OK
        assert done["latency_s"] >= 0.0
        assert done["rounds"] >= 1
        for e in s["events"]:
            if e["e"] == "round":
                assert e["partitions"] >= 1 and e["wall_s"] >= 0.0
    assert saw_round                               # rounds joined back in
    out = tmp_path / "trace.jsonl"
    n = rt.obs.tracer.dump_jsonl(str(out))
    lines = out.read_text().strip().split("\n")
    assert n == len(lines) == len(spans)
    parsed = [json.loads(ln) for ln in lines]
    assert {p["qid"] for p in parsed if "qid" in p} >= set(qids)


def test_trace_cache_hit_span(ds):
    rt = ServingRuntime(build(ds), serve_cfg(flush_size=1,
                                             cache_entries=64))
    q = datasets.queries_near(ds, 1, seed=35)[0]
    rt.submit_query(q)
    rt.drain()
    hit = rt.submit_query(q)                       # identical repeat
    rt.drain()
    assert rt.stats()["cache_hits"] == 1
    span = {s["qid"]: s for s in rt.obs.tracer.spans()
            if "qid" in s}[hit]
    names = [e["e"] for e in span["events"]]
    assert names == ["admit", "cache_hit", "done"]
    assert span["events"][-1]["cache"] is True
    assert span["status"] == STATUS_OK


def test_trace_shed_span(ds):
    rt = ServingRuntime(build(ds), serve_cfg(
        flush_size=10 ** 9, queue_cap=2, queue_policy="shed-newest"))
    q = datasets.queries_near(ds, 4, seed=36)
    qids = [rt.submit_query(row) for row in q]
    shed = [i for i in qids
            if rt.result(i) is not None
            and rt.result(i).status == STATUS_SHED]
    assert shed                                     # cap 2 -> rows 3,4 shed
    spans = {s["qid"]: s for s in rt.obs.tracer.spans() if "qid" in s}
    for qid in shed:
        names = [e["e"] for e in spans[qid]["events"]]
        assert names == ["admit", "done"]
        assert spans[qid]["status"] == STATUS_SHED
    rt.drain()


def test_tracer_ring_eviction_accounting():
    assert DONE_FIELDS == ("qid", "t", "status", "rounds", "nprobe",
                           "recall_estimate", "latency_s", "t_submit",
                           "batch")
    tr = QueryTracer(capacity=4)
    recs = [(qid, 1.0, STATUS_OK, 1, 4, 0.95, 0.001, 0.0, 0)
            for qid in range(10)]
    tr.close_many(recs)
    c = tr.counters()
    assert c["emitted"] == 10 and c["dropped"] == 6 and c["completed"] == 4
    # survivors are the newest four, expanded on read
    assert [s["qid"] for s in tr.spans()] == [6, 7, 8, 9]
    tr.audit("maintenance", {"action": "split", "partition": 3})
    audits = [s for s in tr.spans() if s.get("audit")]
    assert audits and audits[0]["action"] == "split"


# ---------------------------------------------------------------------------
# calibration tracker
# ---------------------------------------------------------------------------

class _FakeLam:
    def predict_scan_ns(self, sizes):
        return float(sum(sizes)) * 100.0


def test_calibration_latency_and_recall():
    reg = MetricsRegistry()
    cal = CalibrationTracker(reg, lam=_FakeLam(), window=4)
    assert cal.latency_error() is None and cal.recall_error() is None
    # predicted = 3000 * 100 ns = 0.3 ms vs observed 0.6 ms -> rel 0.5
    cal.record_scan([1000, 2000], 0.0006)
    assert cal.latency_error() == pytest.approx(0.5)
    cal.record_scan([1000, 2000], 0.0003)          # exact -> rel 0.0
    assert cal.latency_error() == pytest.approx(0.25)
    cal.record_recall(0.95, 0.90)
    cal.record_recall(0.85, 0.90)
    assert cal.recall_error() == pytest.approx(0.05)
    flat = reg.snapshot()
    assert flat["calibration.latency.samples"] == 2
    assert flat["calibration.latency.rel_err"] == pytest.approx(0.25)
    assert flat["calibration.recall.samples"] == 2
    assert flat["calibration.recall.abs_err"] == pytest.approx(0.05)
    # non-finite and non-positive samples are discarded, not recorded
    cal.record_scan([10], 0.0)
    cal.record_recall(float("nan"), 0.9)
    assert reg.counter("calibration.latency.samples") == 2
    assert reg.counter("calibration.recall.samples") == 2


def test_calibration_without_model_is_inert():
    reg = MetricsRegistry()
    cal = CalibrationTracker(reg, lam=None)
    cal.record_scan([100], 0.001)
    assert cal.latency_error() is None
    assert reg.counter("calibration.latency.samples") == 0


# ---------------------------------------------------------------------------
# profiler spans (docs/observability.md, "Profiler spans")
# ---------------------------------------------------------------------------

PROGRAM_SPANS = ("serving.engine_wait", "serving.flush", "planner.plan",
                 "scheduler.round", "scan.dispatch", "scan.wait",
                 "scheduler.fold", "serving.collect")


def _profiled(log_dir, fn, names=PROGRAM_SPANS):
    """Run ``fn`` under the JAX profiler; returns its result and the
    spans of ``names`` recorded, per host thread:
    ``[[(name, start_ns, end_ns, stats), ...], ...]``."""
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = next(log_dir.rglob("*.xplane.pb"))
    threads = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    {k: v for k, v in e.stats})
                   for e in line.events if e.name in names]
            if evs:
                threads.append(evs)
    return out, threads


def _serve_mixed(ds, metrics):
    """Flushes by size, a drain, a write barrier and a drain with
    nothing queued, on the device scan backend."""
    rt = ServingRuntime(build(ds), serve_cfg(
        scan_backend="device", impl="jnp", ticker=False, metrics=metrics))
    q = datasets.queries_near(ds, 20, seed=36).astype(np.float32)
    rt.submit_batch(q[:4])
    rt.drain()                     # warm: the scan compiles here

    def serve():
        rt.submit_batch(q[4:16])   # one flush of 8 by size
        rt.drain()                 # the other 4
        rt.submit_insert(ds.vectors[:3] + 0.01, np.arange(93_000, 93_003))
        rt.submit_batch(q[16:])
        rt.drain()
        rt.drain()                 # nothing queued, nothing in flight
    return rt, serve


def test_snapshot_stage_span(ds, tmp_path):
    """A full rebuild of the device snapshot (here at ``stage()``) is one
    ``snapshot.stage`` span over the host padding and the device puts,
    carrying the shards and the partitions staged."""
    idx = build(ds)
    rt = ServingRuntime(idx, serve_cfg(scan_backend="device", impl="jnp",
                                       ticker=False))
    _, threads = _profiled(tmp_path, rt.stage, names=("snapshot.stage",))
    rt.close()
    evs = [ev for t in threads for ev in t]
    assert len(evs) == 1
    assert evs[0][3] == {"shards": 1, "partitions": idx.num_partitions}


def _inside(inner, outers):
    return any(o[1] <= inner[1] and inner[2] <= o[2] for o in outers)


def test_profiler_spans_nest_and_match_registry(ds, tmp_path):
    """Every span of the served path lands on the profiler's timeline:
    scan and fold inside their round, rounds and plans inside a served
    flush; the registry's histograms count what the spans count."""
    rt, serve = _serve_mixed(ds, True)
    before = rt.metrics_snapshot()
    _, threads = _profiled(tmp_path, serve)
    after = rt.metrics_snapshot()
    rt.close()

    def delta(key):
        return after.get(key, 0) - before.get(key, 0)

    names = {ev[0] for evs in threads for ev in evs}
    assert names == set(PROGRAM_SPANS)
    count = {n: sum(ev[0] == n for evs in threads for ev in evs)
             for n in PROGRAM_SPANS}
    for evs in threads:
        by = {n: [ev for ev in evs if ev[0] == n] for n in PROGRAM_SPANS}
        for inner in ("scan.dispatch", "scan.wait", "scheduler.fold"):
            assert all(_inside(ev, by["scheduler.round"])
                       for ev in by[inner]), inner
        for inner in ("scheduler.round", "planner.plan", "serving.collect"):
            assert all(_inside(ev, by["serving.flush"])
                       for ev in by[inner]), inner
        for ev in by["scheduler.round"]:
            st = ev[3]
            assert 1 <= st["rows"] <= st["b_pad"]
            assert 1 <= st["union"] <= st["u_pad"]
        for ev in by["scan.dispatch"]:
            assert set(ev[3]) == {"b_pad", "u_pad", "shards", "shard_union"}
            assert ev[3]["shards"] == 1
    # every query of the window left the queue in one served flush
    assert sum(ev[3]["n"] for evs in threads for ev in evs
               if ev[0] == "serving.flush") == 16
    # five served calls ran, three of them with queries to plan
    assert count["serving.flush"] == count["serving.engine_wait"] == 5
    assert delta("serving.flush_s.count") == 5
    assert delta("serving.engine_wait_s.count") == 5
    assert delta("serving.flushes") == count["planner.plan"] == 3
    assert delta("planner.plan_s.count") == 3
    rounds = delta("scheduler.rounds")
    assert rounds >= 3
    assert count["scheduler.round"] == count["scan.wait"] == rounds
    assert delta("scheduler.round_wall_s.count") == rounds
    assert delta("scan.wait_s.count") == rounds
    assert 0.0 < delta("scan.wait_s.sum") \
        < delta("scheduler.round_wall_s.sum")


def test_metrics_off_records_no_span(ds, tmp_path):
    """metrics=False: the served path opens no span at all."""
    rt, serve = _serve_mixed(ds, False)
    _, threads = _profiled(tmp_path, serve)
    rt.close()
    assert threads == []


def test_queue_wait_ends_before_planning(ds):
    """A query's queue wait ends as it leaves the queue; planning is
    counted once, in ``planner.plan_s``."""
    now = [0.0]
    rt = ServingRuntime(build(ds), serve_cfg(ticker=False),
                        clock=lambda: now[0])
    ensure = rt._ensure_radius

    def slow_plan():
        now[0] += 5.0
        ensure()
    rt._ensure_radius = slow_plan
    q = datasets.queries_near(ds, 8, seed=37).astype(np.float32)
    rt.submit_batch(q)             # one flush by size
    ms = rt.metrics_snapshot()
    rt.close()
    assert ms["serving.queue_wait_s.count"] == 8
    assert ms["serving.queue_wait_s.max"] == 0.0
    assert ms["planner.plan_s.count"] == 1
    assert ms["planner.plan_s.sum"] == 5.0


def test_round_intervals_split_scan_from_fold(ds):
    """The round's wall time is the whole round; the calibration sample
    is the scan alone, dispatch through the pull; the round record
    carries the host's wait on the device."""
    now = [0.0]
    rt = ServingRuntime(build(ds), serve_cfg(
        scan_backend="device", impl="jnp", ticker=False),
        clock=lambda: now[0])
    sched = rt.scheduler
    scan, retire = sched.ex.scan_probe_round, sched._retire

    def slow_scan(*a, **kw):
        now[0] += 1.0
        return scan(*a, **kw)

    def slow_retire(*a, **kw):
        now[0] += 10.0
        return retire(*a, **kw)
    sched.ex.scan_probe_round = slow_scan
    sched._retire = slow_retire
    q = datasets.queries_near(ds, 8, seed=38).astype(np.float32)
    rt.submit_batch(q)
    rt.drain()
    ms = rt.metrics_snapshot()
    rounds = [rr for rr in rt.obs.tracer._rounds]
    rt.close()
    assert ms["scheduler.rounds"] == len(rounds) >= 1
    assert all(rr["wall_s"] == 11.0 and rr["wait_s"] == 0.0
               for rr in rounds)
    assert ms["scheduler.round_wall_s.mean"] == 11.0
    assert ms["scan.wait_s.max"] == 0.0
    assert ms["calibration.latency.samples"] >= 1
    assert ms["calibration.latency.observed_s.min"] == 1.0
    assert ms["calibration.latency.observed_s.max"] == 1.0
