"""The program's spans in a traced window: where the device's idle time
goes inside the served path.

``ServingRuntime`` opens named spans around the steps of each served call
on the profiler's timeline (``repro.obs.tracing.span``; the list is in
docs/observability.md), and JAX opens ``backend_compile_and_load``
around each compile.  :func:`load` keeps them beside the device's op
lines and the harness's own spans; :func:`reduce` splits the window's
idle time by them:

* ``idle_in_flush_share``: the share of the union of the
  ``serving.flush`` spans inside the window in which no device op runs,
  averaged over the chips;
* ``idle_by_span``: the window's idle seconds by the innermost program
  span over them (``none`` where no program span is), the ten largest;
* ``gaps``: the ten longest idle gaps as ``trace.reduce`` names them by
  the harness's spans, each with its seconds split the same way, and the
  part under no program span split by the harness's innermost span
  (``none:client.sleep``: the load generator sleeping until a query is
  due; ``none:flush``: a deadline tick; ``none`` where no span is);
* ``spans``: how many of each program span the window holds and their
  seconds (the union of their intervals inside the window).

Innermost: of the spans over an instant, the one that started last, a
span that only waits for the engine lock (``serving.engine_wait``)
counting only where no other is: the lock's holder is the one at work;
a harness span counts only where no program span is.

``trace.py`` reads the harness's spans only, so the benchmark's
``--trace 1`` line carries none of this.  One window is run and read
here instead:

    python3 chipbench/spans.py --workload <cell> --seed <n> --seconds <s>

runs the cell's set-up and one traced window as ``run.py --trace 1`` does,
without the correctness check, and prints one JSON line: the above, the
window's counter metrics (``metrics/<name>.py`` of every per-layer metric
with ``program_counter`` as its source) and ``query_p50_ms`` of the traced
window.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from typing import Dict, List, Tuple  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from chipbench import trace  # noqa: E402

PROGRAM_SPANS = ("serving.engine_wait", "serving.flush", "planner.plan",
                 "scheduler.round", "scan.dispatch", "scan.wait",
                 "scheduler.fold", "serving.collect")
COMPILE = "backend_compile_and_load"
FLUSH = "serving.flush"
WAITING = ("serving.engine_wait",)
NAMED = PROGRAM_SPANS + (COMPILE,)


def load(log_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``log_dir`` as ``trace.load``'s
    plain data, keeping the program's spans and compiles on the host
    too."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    keep = set(trace.HOST_SPANS) | {trace.WINDOW} | set(NAMED)
    planes = []
    for plane in pd.planes:
        dev = trace.is_device(plane.name)
        lines = []
        for line in plane.lines:
            if dev and line.name != trace.OP_LINE:
                continue
            evs = [[e.name, e.start_ns, e.duration_ns] for e in line.events
                   if dev or e.name in keep]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def harness_only(tr: dict) -> dict:
    """``tr`` without the program's spans: what ``trace.reduce`` reads
    from ``trace.load``'s data."""
    planes = []
    for plane in tr["planes"]:
        lines = [{"name": ln["name"],
                  "events": [ev for ev in ln["events"]
                             if trace.is_device(plane["name"])
                             or ev[0] not in NAMED]}
                 for ln in plane["lines"]]
        lines = [ln for ln in lines if ln["events"]]
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


def innermost(spans: List[Tuple[float, float, str]]) -> List[list]:
    """The timeline cut into ``[start, end, name]`` pieces, in order, each
    under one innermost span (module docstring); instants under no span
    are left out."""
    def rank(sp):
        return (sp[2] in NAMED, sp[2] not in WAITING, sp[0], -sp[1])
    by_start = sorted(spans)
    pts = sorted({t for s, e, _ in spans for t in (s, e)})
    active: list = []
    out: List[list] = []
    j = 0
    for a, b in zip(pts, pts[1:]):
        while j < len(by_start) and by_start[j][0] <= a:
            active.append(by_start[j])
            j += 1
        active = [sp for sp in active if sp[1] > a]
        if active:
            name = max(active, key=rank)[2]
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1][1] = b
            else:
                out.append([a, b, name])
    return out


def split(idle: List[List[float]], pieces: List[list]) -> Dict[str, float]:
    """Nanoseconds of ``idle`` (sorted, disjoint) under each name of
    ``pieces`` (sorted, disjoint), ``none`` for the rest."""
    out: Dict[str, float] = defaultdict(float)
    i = j = 0
    while i < len(idle) and j < len(pieces):
        lo = max(idle[i][0], pieces[j][0])
        hi = min(idle[i][1], pieces[j][1])
        if hi > lo:
            out[pieces[j][2]] += hi - lo
        if idle[i][1] < pieces[j][1]:
            i += 1
        else:
            j += 1
    rest = sum(e - s for s, e in idle) - sum(out.values())
    if rest > 0:
        out["none"] += rest
    return out


def _top(ns: Dict[str, float], scale: float, n: int = trace.TOP) -> list:
    return [[k, v / scale] for k, v in
            sorted(ns.items(), key=lambda kv: -kv[1])[:n]]


def reduce(tr: dict) -> dict:
    """The program spans' reading of one traced window (module
    docstring); ``idle_in_flush_share`` is None where the window holds
    no ``serving.flush`` span."""
    host: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    devices = []
    for plane in tr["planes"]:
        if trace.is_device(plane["name"]):
            devices.append([ev for line in plane["lines"]
                            for ev in line["events"]])
            continue
        for line in plane["lines"]:
            for name, s, d in line["events"]:
                host[name].append((s, s + d))
    if not host.get(trace.WINDOW):
        raise ValueError(f"the trace holds no {trace.WINDOW!r} span")
    if not devices:
        raise ValueError("the trace holds no device op line")
    lo = min(s for s, _ in host[trace.WINDOW])
    hi = max(e for _, e in host[trace.WINDOW])
    def inside(names, label=""):
        return [(max(s, lo), min(e, hi), label + name) for name in names
                for s, e in host.get(name, ()) if e > lo and s < hi]
    named = inside(NAMED)
    pieces = innermost(named)
    outer = innermost(named + inside(trace.HOST_SPANS, "none:"))
    flush = trace.clip(trace.union(host.get(FLUSH, [])), lo, hi)
    flush_ns = sum(e - s for s, e in flush)
    harness = {name: trace.union(iv) for name, iv in host.items()
               if name in trace.HOST_SPANS}

    by_span: Dict[str, float] = defaultdict(float)
    gaps, idle_flush_ns = [], 0.0
    for evs in devices:
        busy = trace.clip(trace.union([(s, s + d) for _, s, d in evs]),
                          lo, hi)
        edges = [lo] + [t for pair in busy for t in pair] + [hi]
        idle = [[gs, ge] for gs, ge in zip(edges[0::2], edges[1::2])
                if ge > gs]
        for k, v in split(idle, pieces).items():
            by_span[k] += v
        idle_flush_ns += split(idle, [[s, e, FLUSH] for s, e in flush]).get(
            FLUSH, 0.0)
        gaps += idle
    n = len(devices)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:trace.TOP]
    return {
        "idle_in_flush_share": (idle_flush_ns / n / flush_ns
                                if flush_ns > 0 else None),
        "idle_by_span": _top(by_span, 1e9 * n),
        "gaps": [[trace._host_activity(harness, gs, ge), (ge - gs) / 1e9,
                  _top(split([[gs, ge]], outer), 1e9, 4)]
                 for gs, ge in gaps],
        "spans": {name: [len(trace.clip(host[name], lo, hi)),
                         sum(e - s for s, e in trace.clip(
                             trace.union(host[name]), lo, hi)) / 1e9]
                  for name in NAMED if host.get(name)},
    }


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T_START:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import shutil
    import tempfile
    from types import SimpleNamespace

    import jax
    import numpy as np
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from chipbench import harness, spec
    from chipbench.gen import traffic as traffic_mod
    from repro import sanitize

    s = harness.setup(args.workload, args.seed, t_start=T_START, log=log)
    rt, mix = s.rt, s.cell.traffic
    sched = traffic_mod.make_schedule(
        mix, s.corpus, args.seconds, np.random.default_rng([args.seed, 3]),
        s.book, traffic_mod.query_rng(mix, 3))
    reg = rt.obs.metrics

    def reading():
        return SimpleNamespace(
            counters=rt.metrics_snapshot(),
            waits=harness.hist_counts(reg, "serving.queue_wait_s"))
    before = reading()
    compiles = sanitize.CompileEvents()
    log_dir = tempfile.mkdtemp(prefix="trace-", dir=os.path.join(
        ROOT, "chipbench"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with harness.annotate(trace.WINDOW):
            dr = harness.drive(rt, sched, s.corpus, s.state, s.period)
    finally:
        jax.profiler.stop_trace()
    after = reading()
    n_compiles = compiles.new()
    results = [rt.result(int(q)) for q in dr.qids]
    rt.close()
    raw = load(log_dir)
    shutil.rmtree(log_dir, ignore_errors=True)
    base = trace.reduce(harness_only(raw))
    done = np.where(np.isnan(dr.q_done), dr.end, dr.q_done)
    lat = done - (dr.t0 + sched.q_due)
    ctx = SimpleNamespace(before=before, after=after, results=results,
                          compiles=n_compiles)
    metrics = {m["name"]: spec.metric_reader(m["name"])(ctx)
               for m in s.cell.per_layer
               if m["source"] == "program_counter"}
    out = {"workload": args.workload, "seed": args.seed,
           "query_p50_ms": float(np.percentile(lat, 50) * 1e3),
           "busy_s": base.busy_s, "window_s": base.window_s,
           "idle_share": base.idle_share, "idle_gaps": base.idle_gaps,
           "metrics": metrics, **reduce(raw)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
