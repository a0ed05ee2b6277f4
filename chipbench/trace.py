"""From a profiler trace to the device's busy time, idle gaps and top ops.

The JAX profiler writes an ``.xplane.pb``; :func:`load` turns it into plain
data (planes of lines of ``[name, start_ns, duration_ns]`` events) that
:func:`reduce` reads, so the reduction can be checked on a small recorded
trace without a chip.

* busy: the union of the intervals of the device's op events (the
  ``XLA Ops`` line of each ``/device:TPU:<n>`` plane) inside the window,
  averaged over the chips used;
* window: the host span the harness opens around the measured window
  (``bench.window``);
* idle gaps: the complement of busy inside the window, each named by what
  the host was doing meanwhile -- the harness's own spans around its calls
  into the program, the one that covers most of the gap, with the
  generator's sleep only where nothing else runs (``none`` where no span
  covers it).
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

WINDOW = "bench.window"
SLEEP = "client.sleep"
HOST_SPANS = ("submit_query", "flush", "drain", "submit_insert",
              "submit_delete", SLEEP)
OP_LINE = "XLA Ops"
TOP = 10


def load(log_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``log_dir`` as plain data: device
    planes' op lines and the host's spans of the harness."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    keep = set(HOST_SPANS) | {WINDOW}
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            if is_device(plane.name):
                if line.name != OP_LINE:
                    continue
                evs = [[e.name, e.start_ns, e.duration_ns]
                       for e in line.events]
            else:
                evs = [[e.name, e.start_ns, e.duration_ns]
                       for e in line.events if e.name in keep]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:TPU:") and \
        plane_name[len("/device:TPU:"):].isdigit()


def op_name(event_name: str) -> str:
    """A device op's name without the HLO text the event carries:
    ``%quake_scan_topk_indexed.1 = (f32[...]) custom-call(...)`` ->
    ``quake_scan_topk_indexed.1``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def union(intervals: List[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> List[List[float]]:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


@dataclass
class Summary:
    busy_s: float
    window_s: float
    device_ops: List[list] = field(default_factory=list)
    idle_gaps: List[list] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(tr: dict) -> Summary:
    host: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    devices = []
    for plane in tr["planes"]:
        if is_device(plane["name"]):
            devices.append([ev for line in plane["lines"]
                            for ev in line["events"]])
        else:
            for line in plane["lines"]:
                for name, s, d in line["events"]:
                    host[name].append((s, s + d))
    if not host.get(WINDOW):
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    if not devices:
        raise ValueError("the trace holds no device op line")
    lo, hi = min(s for s, _ in host[WINDOW]), max(e for _, e in host[WINDOW])
    window_ns = hi - lo

    busy_ns, op_ns, gaps = 0.0, defaultdict(float), []
    spans = {name: union(iv) for name, iv in host.items() if name != WINDOW}
    for evs in devices:
        iv = clip(union([(s, s + d) for _, s, d in evs]), lo, hi)
        busy_ns += sum(e - s for s, e in iv)
        for name, s, d in evs:
            for cs, ce in clip([(s, s + d)], lo, hi):
                op_ns[op_name(name)] += ce - cs
        edges = [lo] + [t for pair in iv for t in pair] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps.append((gs, ge))
    n = len(devices)
    ops = sorted(([k, v / 1e9 / n] for k, v in op_ns.items()),
                 key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return Summary(busy_s=busy_ns / 1e9 / n, window_s=window_ns / 1e9,
                   device_ops=ops,
                   idle_gaps=[[_host_activity(spans, gs, ge),
                               (ge - gs) / 1e9] for gs, ge in gaps])


def _host_activity(spans: Dict[str, List[List[float]]], gs: float,
                   ge: float) -> str:
    best, best_ns = "none", 0.0
    for name, iv in spans.items():
        if name == SLEEP:
            continue
        ns = sum(e - s for s, e in clip(iv, gs, ge))
        if ns > best_ns:
            best, best_ns = name, ns
    if best_ns == 0.0 and sum(e - s for s, e in
                              clip(spans.get(SLEEP, []), gs, ge)) > 0:
        return SLEEP
    return best
