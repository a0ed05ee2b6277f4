"""The harness's logic on tiny cells on the CPU: schedule, latency from
the due time, the result line's keys, the metric readers, and cells,
mixes and metrics found by name.  The measurement on the chip is not
exercised here (``require_tpu=False``)."""
import hashlib
import json
import os
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import harness, spec
from chipbench.gen.traffic import Schedule
from chipbench.tests.tiny import make_root

REPO = spec.ROOT


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", ["tiny-read", "tiny-churn"])
def test_tiny_cell_runs_and_is_correct(root, cell):
    out = harness.run(cell, 2 ** 31 + 99, 1.5, False, root=root,
                      require_tpu=False)
    json.dumps(out)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 30
    m = out["metrics"]
    assert {"setup_s", "query_p50_ms", "query_p95_ms"} <= set(m)
    assert ("write_p50_ms" in m) == (cell == "tiny-churn")
    assert all(v["value"] > 0 for v in m.values())
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    if cell == "tiny-churn":
        assert "insert_miss" in out["checks"]


class _SlowRuntime:
    """Answers each query a fixed time after it is submitted, and blocks
    the submitter of every fourth query for a while (a flush).  The
    latency it reports of itself is far off: the client's clock is the
    one that counts."""

    def __init__(self, service: float, block: float):
        self.service, self.block, self.n = service, block, 0
        self.results = {}

    def submit_query(self, q):
        qid, self.n = self.n, self.n + 1
        t = time.perf_counter()
        self.results[qid] = SimpleNamespace(t_submit=t, latency_s=100.0)
        if qid % 4 == 3:
            time.sleep(self.block)
        return qid

    def result(self, qid):
        r = self.results.get(qid)
        if r is None or time.perf_counter() < r.t_submit + self.service:
            return None
        return r

    def tick(self):
        return False

    def drain(self):
        pass


def test_latency_runs_from_the_due_time():
    n = 12
    due = np.arange(n) * 0.01
    sched = Schedule(q_due=due, q=np.zeros((n, 4), np.float32),
                     q_base=np.zeros(n, np.int64), q_fresh=np.zeros(n, bool),
                     w_due=np.zeros(0), w_kind=[], w_lo=np.zeros(0, np.int64),
                     w_hi=np.zeros(0, np.int64))
    rt = _SlowRuntime(service=0.005, block=0.05)
    dr = harness.drive(rt, sched, None, {"started": 0, "acked": 0}, 0.01)
    lag = dr.q_send - (dr.t0 + due)
    lat = dr.q_done - (dr.t0 + due)
    assert np.all(lag >= 0)
    assert lag[4] >= 0.035          # sent late behind the blocked submit
    # seen at the first look after the answer is there (the flusher
    # looks every 10 ms), and never before the submit that returns the
    # ticket has returned
    blocked = np.arange(n) % 4 == 3
    assert np.all(lat >= lag + 0.005 - 1e-4)
    assert np.all(lat[~blocked] <= lag[~blocked] + 0.005 + 0.01 + 5e-3)
    assert np.all(lat[blocked] >= lag[blocked] + 0.05)
    assert np.all(lat[blocked] <= lag[blocked] + 0.05 + 5e-3)
    assert threading.active_count() == 1 or all(
        t.name not in ("reader", "writer", "flusher")
        for t in threading.enumerate())


def test_metric_readers_read_the_window():
    readers = {m["name"]: spec.metric_reader(m["name"])
               for m in json.loads((REPO / "BENCHMARK.json").read_text())
               ["per_layer"]}
    res = [SimpleNamespace(status="OK", nprobe=p) for p in (4, 6)]
    trace = SimpleNamespace(busy_s=0.5, window_s=2.0, idle_share=0.75)
    bucket = 160          # ~1 ms in the registry's buckets
    ctx = SimpleNamespace(
        before=SimpleNamespace(
            counters={"serving.flushes": 10, "scheduler.rounds": 20,
                      "scheduler.vectors_streamed": 0,
                      "serving.comparisons": 0,
                      "maintenance.splits": 1},
            fp={"full_rebuilds": 1}, waits={bucket: 5}),
        after=SimpleNamespace(
            counters={"serving.flushes": 14, "scheduler.rounds": 32,
                      "scheduler.vectors_streamed": 819_000,
                      "serving.comparisons": 819_000 * 8,
                      "maintenance.splits": 3, "maintenance.merges": 1},
            fp={"full_rebuilds": 1}, waits={bucket: 8}),
        results=res, checks=SimpleNamespace(recall=np.array([0.8, 1.0])),
        compiles=2, lag_s=np.linspace(0, 0.1, 101), trace=trace, dim=100,
        storage_bytes=4, peaks={"hbm_bw": 819e9, "peak_flops": 197e12})
    got = {n: r(ctx) for n, r in readers.items()}
    assert got["scheduler.rounds_per_flush"] == 3.0
    assert got["planner.partitions_per_query"] == 5.0
    assert got["planner.recall_mean"] == pytest.approx(0.9)
    assert got["device.idle_share"] == 75.0
    assert got["compile.in_window"] == 2
    assert got["client.lag_ms"] == pytest.approx(95.0)
    assert 0.9 < got["frontend.queue_wait_ms"] < 1.1
    # 819,000 vectors x 100 x 4 B at 819 GB/s = 0.4 ms of 0.5 s busy
    assert got["scan.hbm_roofline"] == pytest.approx(0.08)
    # nothing to read: no flush, no trace
    ctx.after.counters["serving.flushes"] = 10
    ctx.trace = None
    assert readers["scheduler.rounds_per_flush"](ctx) is None
    assert readers["scan.hbm_roofline"](ctx) is None
    assert readers["device.idle_share"](ctx) is None


def _digest(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()}


def test_new_cell_mix_and_metric_are_found_by_name(tmp_path):
    root = make_root(tmp_path)
    before = _digest(root)
    cb = root / "chipbench"
    (cb / "configs" / "extra.json").write_text(
        (cb / "configs" / "tiny-wiki.json").read_text())
    mix = json.loads((cb / "traffic" / "tiny-read.json").read_text())
    mix["arrivals"]["rate_per_s"] = 3.0
    (cb / "traffic" / "extra-mix.json").write_text(json.dumps(mix))
    (cb / "metrics" / "extra.metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "extra", "source": "test",
                             "file": "chipbench/configs/extra.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "extra-cell", "config": "extra",
                               "traffic": "extra-mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "extra.metric", "unit": "count",
                               "better": "lower", "source": "host_clock",
                               "layer": "test", "moves": "query_p50_ms",
                               "workloads": ["extra-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("extra-cell", root)
    assert cell.traffic["arrivals"]["rate_per_s"] == 3.0
    assert cell.config["dim"] == 32
    readers = spec.metric_readers(cell)
    assert readers["extra.metric"](None) == 42.0
    assert "extra.metric" not in spec.metric_readers(
        spec.load_cell("tiny-read", root))
    after = _digest(root)
    changed = [p for p in before if after[p] != before[p]]
    assert changed == [root / "BENCHMARK.json"]


def test_run_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(REPO / "chipbench" / "run.py"), "--workload",
         "wiki768-read", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_fails_outside_a_checkout(tmp_path):
    for rel in ["BENCHMARK.json", "chipbench"]:
        src = REPO / rel
        dst = tmp_path / rel
        if src.is_dir():
            import shutil
            shutil.copytree(src, dst, ignore=shutil.ignore_patterns(
                "__pycache__", "trace-*"))
        else:
            dst.write_bytes(src.read_bytes())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, str(tmp_path / "chipbench" / "run.py"),
         "--workload", "wiki768-read", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
