"""The readers of the served path's step metrics on a made-up window:
means over the window's differences of the registry's sums and counts,
and nothing where the program records none (a runtime without these
histograms reads None, not an error)."""
from types import SimpleNamespace

import pytest

from chipbench import spec


def _ctx(before, after):
    return SimpleNamespace(before=SimpleNamespace(counters=before),
                           after=SimpleNamespace(counters=after))


# the counters a runtime without the step histograms records
OLD = ({"serving.flushes": 10, "scheduler.rounds": 70,
        "scheduler.round_wall_s.sum": 1.0},
       {"serving.flushes": 14, "scheduler.rounds": 100,
        "scheduler.round_wall_s.sum": 1.6})


def _read(name, before, after):
    return spec.metric_reader(name)(_ctx(before, after))


def test_frontend_engine_wait_ms():
    b = {"serving.engine_wait_s.count": 5, "serving.engine_wait_s.sum": 0.1}
    a = {"serving.engine_wait_s.count": 9, "serving.engine_wait_s.sum": 0.3}
    assert _read("frontend.engine_wait_ms", b, a) == pytest.approx(50.0)
    assert _read("frontend.engine_wait_ms", b, b) is None
    assert _read("frontend.engine_wait_ms", *OLD) is None


def test_planner_plan_ms():
    b = {"serving.flushes": 10, "planner.plan_s.sum": 0.5}
    a = {"serving.flushes": 14, "planner.plan_s.sum": 0.58}
    assert _read("planner.plan_ms", b, a) == pytest.approx(20.0)
    assert _read("planner.plan_ms", a, a) is None
    assert _read("planner.plan_ms", *OLD) is None


def test_scheduler_host_ms_per_round():
    b = dict(OLD[0], **{"scan.wait_s.sum": 0.2})
    a = dict(OLD[1], **{"scan.wait_s.sum": 0.26})
    # (0.6 s of rounds - 0.06 s waiting) over 30 rounds
    assert _read("scheduler.host_ms_per_round", b, a) == pytest.approx(18.0)
    assert _read("scheduler.host_ms_per_round", a, a) is None
    assert _read("scheduler.host_ms_per_round", *OLD) is None


def test_scan_wait_ms_per_round():
    b = dict(OLD[0], **{"scan.wait_s.sum": 0.2})
    a = dict(OLD[1], **{"scan.wait_s.sum": 0.26})
    assert _read("scan.wait_ms_per_round", b, a) == pytest.approx(2.0)
    assert _read("scan.wait_ms_per_round", a, a) is None
    assert _read("scan.wait_ms_per_round", *OLD) is None
