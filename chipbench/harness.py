"""One run of one cell: generate, build, stage, warm up, drive the window
open loop, check the answers, read the metrics.

The system under test is ``repro.core.ServingRuntime`` over a
``QuakeIndex`` built by ``QuakeIndex.build`` at the configuration's fixed
snapshot capacity, with the device scan backend and the kernels the
configuration names.  The window is driven from the client side: a reader
thread sends each query at its due time (``submit_query``), a writer
thread each write (``submit_insert`` / ``submit_delete``), and a flusher
thread calls ``tick`` at the period of the runtime's own deadline ticker.
A query's latency runs from its due time to the moment the client sees
its result, on the client's clock: the results the runtime hands out
appear at the return of the call that collected them (``submit_query``,
``tick``, a write, ``drain``), and the client looks for them after every
such call.  A write's latency runs from its due time to its
acknowledgement.  Nothing is retried and nothing is dropped: every query
of the window is waited for.
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import threading
import time
from dataclasses import dataclass
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

import numpy as np

from . import check as check_mod
from . import spec as spec_mod
from . import trace as trace_mod
from .gen import corpus as corpus_mod
from .gen import traffic as traffic_mod
from .peaks import peaks


STORAGE_BYTES = {"f32": 4, "bf16": 2, "int8": 1}


class NoAccelerator(RuntimeError):
    pass


@dataclass
class Drive:
    """What one stretch of traffic did, on the client's clock."""
    t0: float
    qids: np.ndarray             # (nq,) runtime tickets
    q_send: np.ndarray           # (nq,) when each query was sent
    q_state: np.ndarray          # (nq,) writes applied when it was sent
    q_clear: np.ndarray          # (nq,) bool: no write in flight meanwhile
    w_send: np.ndarray           # (nw,) when each write was sent
    w_ack: np.ndarray            # (nw,) when it was acknowledged
    q_done: np.ndarray = None    # (nq,) when the client saw each result
    end: float = 0.0             # when the drain after the last op ended


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def drive(rt, sched, corpus, state: dict, period: Optional[float]) -> Drive:
    """Send ``sched`` open loop from now; returns once every op is sent,
    every write acknowledged and the runtime drained.  ``state`` counts
    the writes started and acknowledged over the runbook (it carries on
    across stretches)."""
    nq, nw = len(sched.q_due), len(sched.w_due)
    out = Drive(t0=0.0, qids=np.full(nq, -1, np.int64),
                q_send=np.zeros(nq), q_state=np.zeros(nq, np.int64),
                q_clear=np.zeros(nq, bool), w_send=np.zeros(nw),
                w_ack=np.zeros(nw), q_done=np.full(nq, np.nan))
    stop = threading.Event()
    errors: List[BaseException] = []
    waiting: Dict[int, int] = {}         # runtime ticket -> query row
    seen = threading.Lock()

    def collect() -> None:
        """Stamp every result that has appeared since the last look."""
        now = time.perf_counter()
        with seen:
            for qid, i in list(waiting.items()):
                if rt.result(qid) is not None:
                    out.q_done[i] = now
                    del waiting[qid]

    def wait_until(t: float) -> None:
        dt = t - time.perf_counter()
        if dt > 0:
            with annotate(trace_mod.SLEEP):
                time.sleep(dt)

    def reader() -> None:
        for i in range(nq):
            if stop.is_set():
                return
            wait_until(out.t0 + sched.q_due[i])
            s0, a0 = state["started"], state["acked"]
            out.q_send[i] = time.perf_counter()
            with annotate("submit_query"):
                qid = rt.submit_query(sched.q[i])
            out.qids[i] = qid
            with seen:
                waiting[int(qid)] = i
            collect()
            out.q_state[i] = a0
            out.q_clear[i] = s0 == a0 == state["started"]

    def writer() -> None:
        for j in range(nw):
            if stop.is_set():
                return
            wait_until(out.t0 + sched.w_due[j])
            lo, hi = int(sched.w_lo[j]), int(sched.w_hi[j])
            ids = np.arange(lo, hi, dtype=np.int64)
            state["started"] += 1
            out.w_send[j] = time.perf_counter()
            kind = sched.w_kind[j]
            with annotate(f"submit_{kind}"):
                if kind == "insert":
                    rt.submit_insert(corpus.x[lo:hi], ids)
                else:
                    rt.submit_delete(ids)
            out.w_ack[j] = time.perf_counter()
            state["acked"] += 1
            collect()

    def flusher() -> None:
        while not stop.wait(period):
            with annotate("flush"):
                rt.tick()
            collect()

    def guarded(fn: Callable) -> Callable:
        def run() -> None:
            try:
                fn()
            except BaseException as e:   # re-raised after the join
                errors.append(e)
                stop.set()
        return run

    threads = [threading.Thread(target=guarded(reader), name="reader")]
    if nw:
        threads.append(threading.Thread(target=guarded(writer),
                                        name="writer"))
    ticker = (threading.Thread(target=guarded(flusher), name="flusher")
              if period else None)
    out.t0 = time.perf_counter() + 0.01
    for t in threads + ([ticker] if ticker else []):
        t.start()
    for t in threads:
        t.join()
    stop.set()
    if ticker:
        ticker.join()
    if errors:
        raise errors[0]
    with annotate("drain"):
        rt.drain()
    collect()
    out.end = time.perf_counter()
    return out


def hist_counts(registry, name: str) -> Dict[int, int]:
    """Bucket counts of one of the registry's histograms, read under its
    lock (the registry keeps only cumulative buckets: a window's share is
    the difference of two readings)."""
    with registry._lock:
        h = registry._histograms.get(name)
        if h is None:
            return {}
        h._fold()
        return dict(h.counts)


def _percentile_ms(values: np.ndarray, q: float) -> float:
    return float(np.percentile(values, q) * 1e3)


@dataclass
class Session:
    """A cell set up for measuring: the runtime staged and warmed up."""
    cell: spec_mod.Cell
    devs: list
    corpus: corpus_mod.Corpus
    rt: object
    book: traffic_mod.Runbook
    state: dict
    period: Optional[float]
    k: int


def setup(cell_name: str, seed: int, root=spec_mod.ROOT,
          require_tpu: bool = True, t_start: Optional[float] = None,
          log: Callable[[str], None] = lambda m: None) -> Session:
    """Generate the corpus, build the index, stage the snapshot and warm
    up on the cell's own traffic until passes stop compiling."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax
    cell = spec_mod.load_cell(cell_name, root)
    cfg, mix = cell.config, cell.traffic
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < cell.chips:
        raise NoAccelerator(f"the cell needs {cell.chips} chips, JAX sees "
                            f"{len(devs)}")

    from repro import sanitize
    from repro.core import QuakeConfig, QuakeIndex, ServingConfig, \
        ServingRuntime

    corpus = corpus_mod.make_corpus(cfg, seed)
    log(f"corpus: {corpus.n} x {corpus.dim} ({corpus.metric}), "
        f"{corpus.n_resident} built; {time.perf_counter() - t_start:.1f}s")
    g = cfg["guarantees"]
    k = int(g["k"])
    icfg = QuakeConfig(metric=cfg["metric"],
                       recall_target=float(g["recall_target"]),
                       snapshot_capacity=int(cfg["snapshot_capacity"]))
    ids0 = np.arange(corpus.n_resident, dtype=np.int64)
    index = QuakeIndex.build(corpus.x[:corpus.n_resident], ids0,
                             config=icfg)
    log(f"built: {index.num_partitions} partitions; "
        f"{time.perf_counter() - t_start:.1f}s")
    serving = dict(cfg["serving"])
    serving.update(mix.get("serving", {}))
    scfg = ServingConfig(k=k, recall_target=float(g["recall_target"]),
                         ticker=False, **serving)
    rt = ServingRuntime(index, scfg)
    rt.stage()
    log(f"staged: {rt.executor.footprint()}; APS {index.aps_calibration}; "
        f"{time.perf_counter() - t_start:.1f}s")
    period = (max(scfg.flush_deadline / 4.0, 1e-3)
              if scfg.flush_deadline else None)
    s = Session(cell=cell, devs=devs, corpus=corpus, rt=rt,
                book=traffic_mod.start_runbook(corpus),
                state={"started": 0, "acked": 0}, period=period, k=k)
    # warm-up, first the shapes: for each burst size, that many of the
    # cell's own queries sent at once and drained.  A burst no larger
    # than the flush size is one flush of that size (the planner
    # compiles per flush size), a larger one rides in as flushes of the
    # flush size, so the round scan meets every active-row bucket up to
    # the largest burst, each with unions from one query's first window
    # to the whole burst's.  Then passes of the cell's own traffic until
    # ``quiet_passes`` in a row compile nothing (at least ``min_passes``)
    warm = mix["warmup"]
    rng_warm = np.random.default_rng([seed, 2])
    qrng_warm = traffic_mod.query_rng(mix, 2)
    ev = sanitize.CompileEvents()
    reads = {key: v for key, v in mix.items() if key != "writes"}
    rate = float(mix["arrivals"]["rate_per_s"])
    for b in warm.get("bursts", []):
        burst = traffic_mod.make_schedule(reads, corpus, int(b) / rate + 1,
                                          rng_warm, s.book, qrng_warm)
        for q in burst.q[:int(b)]:
            rt.submit_query(q)
        rt.drain()
    log(f"warm-up bursts {warm.get('bursts', [])}: {ev.new()} compiles; "
        f"{time.perf_counter() - t_start:.1f}s")
    quiet = 0
    for p in range(int(warm["max_passes"])):
        ev = sanitize.CompileEvents()
        sched = traffic_mod.make_schedule(mix, corpus,
                                          float(warm["seconds"]),
                                          rng_warm, s.book, qrng_warm)
        drive(rt, sched, corpus, s.state, period)
        new = ev.new()
        quiet = quiet + 1 if new == 0 else 0
        log(f"warm-up pass {p}: {new} compiles; "
            f"{time.perf_counter() - t_start:.1f}s")
        if quiet >= int(warm["quiet_passes"]) \
                and p + 1 >= int(warm["min_passes"]):
            break
    return s


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        root=spec_mod.ROOT, require_tpu: bool = True,
        t_start: Optional[float] = None,
        log: Callable[[str], None] = lambda m: None) -> dict:
    """One run; returns the result line's dict (``checks`` last)."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax
    from repro import sanitize
    s = setup(cell_name, seed, root, require_tpu, t_start, log)
    cell, corpus, rt, book, state = s.cell, s.corpus, s.rt, s.book, s.state
    cfg, mix, k, period = cell.config, cell.traffic, s.k, s.period
    devs, dev = s.devs, s.devs[0]
    readers = spec_mod.metric_readers(cell) if trace else {}

    # ---- the window ----
    sched = traffic_mod.make_schedule(mix, corpus, seconds,
                                      np.random.default_rng([seed, 3]), book,
                                      traffic_mod.query_rng(mix, 3))
    reg = rt.obs.metrics
    before = SimpleNamespace(
        counters=rt.metrics_snapshot(), fp=rt.executor.footprint(),
        waits=hist_counts(reg, "serving.queue_wait_s"))
    compiles = sanitize.CompileEvents()
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="trace-",
                                     dir=str(cell.root / "chipbench"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1    # the harness's spans; no runtime detail
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    try:
        with annotate(trace_mod.WINDOW):
            dr = drive(rt, sched, corpus, state, period)
    finally:
        if trace:
            jax.profiler.stop_trace()
    n_compiles = compiles.new()
    after = SimpleNamespace(
        counters=rt.metrics_snapshot(), fp=rt.executor.footprint(),
        waits=hist_counts(reg, "serving.queue_wait_s"))
    results = [rt.result(int(q)) for q in dr.qids]
    mem = dev.memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))

    # the witness: every partition of the same snapshot, a fixed plan
    rng_chk = np.random.default_rng([seed, 4])
    n_wit = min(int(cfg["checks"]["witness_queries"]), len(sched.q))
    wit_rows = np.sort(rng_chk.choice(len(sched.q), n_wit, replace=False))
    wit = rt.executor.search(sched.q[wit_rows], k,
                             nprobe=rt.index.num_partitions)
    log(f"window done: {len(results)} queries, {len(sched.w_due)} writes; "
        f"witness over {rt.index.num_partitions} partitions")
    rt.close()
    s.rt = rt = None      # frees the snapshot before the reference
    gc.collect()

    # ---- correctness, against the plain reference ----
    served = check_mod.Served.from_results(results, k)
    chk = check_mod.check(cfg, corpus, sched, dr.q_state, dr.q_clear,
                          served, witness_ids=wit.ids,
                          witness_rows=wit_rows, history=book.history)

    log(f"checked {chk.judged} of {len(results)} queries; distance "
        f"error spread {chk.spread}")

    # ---- end-to-end metrics ----
    ok = np.asarray([r is not None and r.status == "OK" for r in results])
    done = np.where(np.isnan(dr.q_done), dr.end, dr.q_done)
    q_lat = done - (dr.t0 + sched.q_due)
    w_lat = dr.w_ack - (dr.t0 + sched.w_due)
    lag = np.concatenate([dr.q_send - (dr.t0 + sched.q_due),
                          dr.w_send - (dr.t0 + sched.w_due)])
    e2e = {"setup_s": setup_s,
           "query_p50_ms": _percentile_ms(q_lat, 50),
           "query_p95_ms": _percentile_ms(q_lat, 95),
           "write_p50_ms": (_percentile_ms(w_lat, 50) if len(w_lat)
                            else None)}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    out = {"correct": chk.correct,
           "attempted": int(len(results) + len(w_lat)),
           "failed": int((~ok).sum()),
           "metrics": {}, "device": device}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if not trace:
        for m in cell.end_to_end:
            v = e2e.get(m["name"])
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": units[
                    m["name"]]}
    else:
        tr = trace_mod.reduce(trace_mod.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        ctx = SimpleNamespace(
            before=before, after=after, results=results, checks=chk,
            compiles=n_compiles, lag_s=lag, trace=tr, dim=corpus.dim,
            storage_bytes=STORAGE_BYTES[cfg["storage"]],
            peaks=peaks(dev.device_kind))
        for name, read in readers.items():
            v = read(ctx)
            if v is not None:
                out["metrics"][name] = {"value": float(v),
                                        "unit": units[name]}
        out["breakdown"] = {"device_ops": tr.device_ops,
                            "idle_gaps": tr.idle_gaps}
    out["checks"] = chk.line()
    return out
