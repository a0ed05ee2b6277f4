"""A benchmark root of tiny cells for the CPU tests: the repository's own
``BENCHMARK.json`` metrics and readers, with tiny configurations and mixes
from ``data/`` as cells ``tiny-read`` and ``tiny-churn`` (the streaming
runbook's writes, with a ``write_p50_ms`` of its own), and a
``query_p95_ms`` for both."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from chipbench import spec

DATA = Path(__file__).resolve().parent / "data"


def make_root(tmp: Path) -> Path:
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    cb = tmp / "chipbench"
    shutil.copytree(spec.HERE / "metrics", cb / "metrics")
    (cb / "configs").mkdir()
    (cb / "traffic").mkdir()
    for name in ("tiny-wiki", "tiny-clustered"):
        shutil.copy(DATA / f"{name}.json", cb / "configs" / f"{name}.json")
    for name in ("tiny-read", "tiny-churn"):
        shutil.copy(DATA / f"{name}.json", cb / "traffic" / f"{name}.json")
    bench["configs"] = [
        {"name": n, "source": "test data", "file":
         f"chipbench/configs/{n}.json", "reduced": [], "why": "test"}
        for n in ("tiny-wiki", "tiny-clustered")]
    bench["workloads"] = [
        {"name": "tiny-read", "config": "tiny-wiki", "traffic": "tiny-read",
         "chips": 1, "why": "test"},
        {"name": "tiny-churn", "config": "tiny-clustered",
         "traffic": "tiny-churn", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    # the tail, which the harness reports for a cell that lists it, and
    # the write path's metric, for the churn cell
    bench["end_to_end"].append({"name": "query_p95_ms", "unit": "ms",
                                "better": "lower", "bound": 0.25,
                                "source": "host_clock"})
    bench["end_to_end"].append({"name": "write_p50_ms", "unit": "ms",
                                "better": "lower", "bound": 0.25,
                                "source": "host_clock",
                                "workloads": ["tiny-churn"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return tmp
