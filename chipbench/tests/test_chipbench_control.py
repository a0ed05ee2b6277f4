"""The comparison that decides ``correct`` fails what it must: the
control (the reference at the next lower precision in the program's
place), and runs whose timed path is broken underneath."""
import numpy as np
import pytest

from chipbench import harness
from chipbench.control import control
from chipbench.tests.tiny import make_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", ["tiny-read", "tiny-churn"])
def test_control_is_not_correct(root, cell):
    chk = control(cell, 2 ** 31 + 5, 1.5, root=root)
    assert not chk.correct
    assert not chk.passed["dist_err_p99"]
    # it fails on its precision alone: the answers themselves are exact
    assert chk.numbers["recall_mean"] == 1.0
    assert chk.numbers["bad_ids"] == 0


@pytest.mark.parametrize("cell", ["tiny-read", "tiny-churn"])
def test_reference_in_the_programs_place_is_correct(root, cell):
    from chipbench.reference import HIGHEST
    assert control(cell, 11, 1.5, root=root, precision=HIGHEST).correct


def _scan_unchanged(orig):
    def scan(self, q_mat, seq_mat, take, kept, rows):
        d, flat, st = orig(self, q_mat, seq_mat, take, kept, rows)
        return np.full_like(d, 3.0e38), np.full_like(flat, -1), st
    return scan


def _half_batch(orig):
    def scan(self, q_mat, seq_mat, take, kept, rows):
        d, flat, st = orig(self, q_mat, seq_mat, take, kept, rows)
        h = len(d) // 2
        d, flat = d.copy(), flat.copy()
        d[h:], flat[h:] = 3.0e38, -1
        return d, flat, st
    return scan


def _altered(orig):
    def scan(self, q_mat, seq_mat, take, kept, rows):
        d, flat, st = orig(self, q_mat, seq_mat, take, kept, rows)
        return d + 1e-3 * np.abs(d), flat, st
    return scan


def _altered_few(orig):
    seen = [0]

    def scan(self, q_mat, seq_mat, take, kept, rows):
        # the best distance of one row in 40 that the round scans
        # return, made better so that it stays in the answer
        d, flat, st = orig(self, q_mat, seq_mat, take, kept, rows)
        d = d.copy()
        pick = (seen[0] + np.arange(len(d))) % 40 == 0
        d[pick, 0] -= 1e-3 * np.abs(d[pick, 0])
        seen[0] += len(d)
        return d, flat, st
    return scan


@pytest.mark.parametrize("cell,fault", [
    ("tiny-read", "scan_unchanged"), ("tiny-read", "half_batch"),
    ("tiny-read", "altered_answer"), ("tiny-read", "altered_few"),
    ("tiny-churn", "write_unchanged")])
def test_broken_timed_path_is_not_correct(root, monkeypatch, cell, fault):
    from repro.core import QuakeIndex
    from repro.core.serving import RoundScheduler
    if fault == "write_unchanged":
        monkeypatch.setattr(QuakeIndex, "insert", lambda self, x, ids: None)
        monkeypatch.setattr(QuakeIndex, "delete", lambda self, ids: 0)
    else:
        wrap = {"scan_unchanged": _scan_unchanged,
                "half_batch": _half_batch,
                "altered_answer": _altered,
                "altered_few": _altered_few}[fault]
        monkeypatch.setattr(RoundScheduler, "_scan_once",
                            wrap(RoundScheduler._scan_once))
    out = harness.run(cell, 77, 1.5, False, root=root, require_tpu=False)
    assert out["correct"] is False
    failed = [n for n, c in out["checks"].items()
              if not (c["value"] <= c["limit"] if c["op"] == "<="
                      else c["value"] >= c["limit"])]
    expect = {"scan_unchanged": "bad_ids", "half_batch": "bad_ids",
              "altered_answer": "dist_err_p99",
              "altered_few": "dist_err_max",
              "write_unchanged": "bad_ids"}[fault]
    assert expect in failed, out["checks"]
    if fault == "altered_few":
        # confined to a few answers, it passes the percentile
        assert "dist_err_p99" not in failed, out["checks"]
    if fault == "write_unchanged":
        assert "insert_miss" in failed
