"""The trace-to-metric reduction on small traces: busy union, idle share,
idle gaps by host activity, top device ops, and the peaks table."""
import json

import pytest

from chipbench import peaks, trace
from chipbench.tests.tiny import DATA

MS = 1_000_000


def _trace():
    dev = {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        ["scan", 10 * MS, 20 * MS],          # 10-30
        ["fold", 25 * MS, 10 * MS],          # 25-35, overlaps scan
        ["scan", 60 * MS, 10 * MS],          # 60-70
        ["pack", 95 * MS, 10 * MS],          # 95-105, half outside
    ]}]}
    host = {"name": "/host:CPU", "lines": [
        {"name": "reader", "events": [
            [trace.WINDOW, 0, 100 * MS],
            [trace.SLEEP, 35 * MS, 25 * MS],       # 35-60
            ["submit_query", 70 * MS, 25 * MS]]},  # 70-95
        {"name": "flusher", "events": [["flush", 36 * MS, 4 * MS]]}]}
    return {"planes": [host, dev]}


def test_busy_union_and_idle_share():
    s = trace.reduce(_trace())
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.040)      # 10-35, 60-70, 95-100
    assert s.idle_share == pytest.approx(0.6)


def test_top_ops_are_clipped_to_the_window():
    ops = dict(trace.reduce(_trace()).device_ops)
    assert ops == pytest.approx({"scan": 0.030, "fold": 0.010,
                                 "pack": 0.005})


def test_idle_gaps_named_by_host_activity():
    gaps = trace.reduce(_trace()).idle_gaps
    # 35-60: the flusher's flush overlaps it, so not the sleep; 70-95:
    # submit_query; 0-10: nothing
    assert gaps == [["flush", pytest.approx(0.025)],
                    ["submit_query", pytest.approx(0.025)],
                    ["none", pytest.approx(0.010)]]


def test_sleep_names_a_gap_only_where_nothing_else_runs():
    tr = _trace()
    tr["planes"][0]["lines"].pop()          # no flush
    gaps = dict((round(g, 3), n) for n, g in trace.reduce(tr).idle_gaps)
    assert gaps[0.025] in ("client.sleep", "submit_query")
    assert trace.reduce(tr).idle_gaps[0][0] == "client.sleep"


def test_a_trace_without_window_or_device_is_refused():
    tr = _trace()
    tr["planes"][0]["lines"][0]["events"].pop(0)
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(tr)
    tr = _trace()
    tr["planes"].pop()
    with pytest.raises(ValueError, match="device"):
        trace.reduce(tr)


def test_only_numbered_tpu_planes_are_devices():
    assert trace.is_device("/device:TPU:0")
    assert not trace.is_device("/device:TPU:0 SparseCore 0")
    assert not trace.is_device("/host:CPU")


def test_recorded_chip_trace():
    tr = json.loads((DATA / "trace_v5e.json").read_text())
    s = trace.reduce(tr)
    rec = tr["expected"]
    assert s.window_s == pytest.approx(rec["window_s"])
    assert s.busy_s == pytest.approx(rec["busy_s"])
    assert 0.0 < s.busy_s < s.window_s
    assert [op for op, _ in s.device_ops] == rec["top_ops"]
    assert s.device_ops[0][0].startswith("quake_scan_topk_indexed")
    assert s.idle_gaps[0][0] == "flush"


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("TPU v99")
