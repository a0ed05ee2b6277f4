"""A tiny partition-sharded cell on four virtual CPU devices: the
harness runs it through ``ServingConfig.part_shards`` from the
configuration's ``serving`` block, the run is correct, and the two
readers of the sharded snapshot read the runtime's counters.  The chip's
busy time comes only from a device trace, so the roofline reader is given
one second of it here.  Four devices need their own process: the
device-count flag must be set before JAX starts."""
import os
import subprocess
import sys
import textwrap

from chipbench import spec

SCRIPT = textwrap.dedent("""
    import json, shutil, tempfile
    from pathlib import Path
    from types import SimpleNamespace
    import jax
    import numpy as np
    from chipbench import harness, spec
    from chipbench.peaks import peaks
    from chipbench.tests.tiny import DATA, make_root

    assert len(jax.devices()) == 4
    root = make_root(Path(tempfile.mkdtemp()))
    shutil.copy(DATA / "tiny-wiki-sharded.json",
                root / "chipbench" / "configs" / "tiny-wiki-sharded.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-wiki-sharded", "source": "test data", "file":
        "chipbench/configs/tiny-wiki-sharded.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "tiny-sharded4", "config": "tiny-wiki-sharded",
        "traffic": "tiny-read", "chips": 4, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    out = harness.run("tiny-sharded4", 2 ** 31 + 7, 1.5, False, root=root,
                      require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["device"]["count"] == 4

    s = harness.setup("tiny-sharded4", 2 ** 31 + 8, root, require_tpu=False)
    rt = s.rt
    assert rt.executor.shards == 4
    fp = rt.executor.footprint()
    assert fp["shards"] == 4 and len(set(fp["device_bytes_per_device"])) == 1
    before = rt.metrics_snapshot()
    sched = harness.traffic_mod.make_schedule(
        s.cell.traffic, s.corpus, 1.5, np.random.default_rng(1), s.book)
    harness.drive(rt, sched, s.corpus, s.state, s.period)
    after = rt.metrics_snapshot()
    ctx = SimpleNamespace(before=SimpleNamespace(counters=before),
                          after=SimpleNamespace(counters=after),
                          trace=SimpleNamespace(busy_s=1.0), dim=s.corpus.dim,
                          storage_bytes=4, peaks=peaks("TPU v5 lite"))
    readers = spec.metric_readers(spec.load_cell("wiki768-sharded4"))
    skew = readers["shard.stream_skew"](ctx)
    roof = readers["shard.scan_hbm_roofline"](ctx)
    assert 1.0 <= skew <= 4.0, skew
    assert 0.0 < roof < 100.0, roof
    print("SHARDED_CELL_OK", skew, roof)
""")


def test_tiny_sharded_cell_runs_and_reads():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([str(spec.ROOT / "src"),
                                         str(spec.ROOT)])
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=str(spec.ROOT))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_CELL_OK" in out.stdout
