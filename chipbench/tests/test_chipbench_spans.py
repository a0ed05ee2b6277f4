"""The program spans' reduction on a small trace: idle time inside the
served flushes, idle time by the innermost program span, the longest
gaps split by it, and the harness's own reading left as it was."""
import json

import pytest

from chipbench import spans, trace
from chipbench.tests.tiny import DATA

MS = 1_000_000


def _ev(name, lo, hi):
    return [name, lo * MS, (hi - lo) * MS]


def _harness_trace():
    dev = {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        _ev("scan", 10, 30), _ev("scan", 61, 70)]}]}
    host = {"name": "/host:CPU", "lines": [
        {"name": "flusher", "events": [_ev(trace.WINDOW, 0, 100),
                                       _ev("flush", 0, 50)]},
        {"name": "reader", "events": [_ev("submit_query", 50, 90),
                                      _ev(trace.SLEEP, 90, 100)]}]}
    return {"planes": [host, dev]}


def _trace():
    """Two served flushes: one on the flusher's thread (plan, a round
    that compiles, fold, collect) while the ticker waits for the engine
    lock, one on the reader's thread after it waited for the lock."""
    tr = _harness_trace()
    flusher, reader = tr["planes"][0]["lines"]
    flusher["events"] += [
        _ev("serving.flush", 1, 49), _ev("planner.plan", 2, 8),
        _ev("scheduler.round", 8, 45), _ev("scan.dispatch", 8, 12),
        _ev(spans.COMPILE, 9, 11), _ev("scan.wait", 12, 30),
        _ev("scheduler.fold", 30, 45), _ev("serving.collect", 45, 48)]
    reader["events"] += [
        _ev("serving.engine_wait", 52, 58), _ev("serving.flush", 58, 90),
        _ev("scheduler.round", 60, 85), _ev("scan.dispatch", 60, 61),
        _ev("scan.wait", 61, 70), _ev("scheduler.fold", 70, 85)]
    tr["planes"][0]["lines"].append(
        {"name": "ticker", "events": [_ev("serving.engine_wait", 20, 40)]})
    return tr


def test_idle_inside_the_flushes():
    # flushes 1-49 and 58-90 (80 ms), idle 1-10, 30-49, 58-61, 70-90
    r = spans.reduce(_trace())
    assert r["idle_in_flush_share"] == pytest.approx(51 / 80)


def test_idle_by_innermost_span():
    by = dict(spans.reduce(_trace())["idle_by_span"])
    # the ticker's wait (20-40) yields to the fold that holds the lock
    assert by == pytest.approx({
        "scheduler.fold": 0.030, "none": 0.014, "serving.flush": 0.009,
        "planner.plan": 0.006, "serving.engine_wait": 0.006,
        "serving.collect": 0.003, "scan.dispatch": 0.002,
        spans.COMPILE: 0.001})
    assert sum(by.values()) == pytest.approx(
        trace.reduce(_trace()).window_s - trace.reduce(_trace()).busy_s)


def test_longest_gaps_split_by_span():
    gaps = spans.reduce(_trace())["gaps"]
    assert [g[0] for g in gaps] == ["flush", "submit_query", "flush"]
    assert [g[1] for g in gaps] == pytest.approx([0.031, 0.030, 0.010])
    assert gaps[0][2][:2] == [["scheduler.fold", pytest.approx(0.015)],
                              ["serving.engine_wait", pytest.approx(0.006)]]
    # what no program span covers is named by the harness's innermost
    # span: the generator sleeping until its next query is due, a tick
    assert gaps[1][2] == [["scheduler.fold", pytest.approx(0.015)],
                          ["none:client.sleep", pytest.approx(0.010)],
                          ["serving.flush", pytest.approx(0.005)]]
    assert gaps[2][2][:2] == [["planner.plan", pytest.approx(0.006)],
                              ["none:flush", pytest.approx(0.001)]]


def test_span_counts_and_seconds():
    sp = spans.reduce(_trace())["spans"]
    assert sp["serving.flush"] == [2, pytest.approx(0.080)]
    assert sp["scheduler.round"] == [2, pytest.approx(0.062)]
    assert sp["serving.engine_wait"] == [2, pytest.approx(0.026)]
    assert sp[spans.COMPILE] == [1, pytest.approx(0.002)]


def test_harness_reading_is_unchanged_by_program_spans():
    """What ``trace.reduce`` reads (busy time, the idle gaps named by the
    harness's spans, the top ops) is the same with the program's spans
    in the trace as without them."""
    a = trace.reduce(spans.harness_only(_trace()))
    b = trace.reduce(_harness_trace())
    assert (a.busy_s, a.window_s, a.device_ops, a.idle_gaps) == \
        (b.busy_s, b.window_s, b.device_ops, b.idle_gaps)
    assert spans.harness_only(_harness_trace()) == _harness_trace()


def test_recorded_chip_trace_without_program_spans():
    tr = json.loads((DATA / "trace_v5e.json").read_text())
    tr.pop("expected")
    assert spans.harness_only(tr) == tr
    r = spans.reduce(tr)
    s = trace.reduce(tr)
    assert r["idle_in_flush_share"] is None
    assert r["idle_by_span"] == [["none", pytest.approx(s.window_s
                                                        - s.busy_s)]]
    assert [g[1] for g in r["gaps"]] == [g for _, g in s.idle_gaps]
    assert [g[0] for g in r["gaps"]] == [n for n, _ in s.idle_gaps]
