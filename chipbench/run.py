"""The benchmark's one command: one run of one cell on the chips here.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device`` and, last,
``checks`` (each number compared with its limit); with ``--trace 1`` also
``breakdown``.  The same checks are the last lines of standard error.
Exits non-zero, with no result, when JAX finds no TPU or fewer chips than
the cell asks for, and when the ``repro`` package is not in ``src/`` of
the checkout.

JAX's persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR`` when
that is set, else ``.jax_cache`` at the root of the checkout: a fixed
path, so that only a cell's first run in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T_START:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"FAIL: the repro package is not in {ROOT}/src ({e})",
              file=sys.stderr)
        return 2
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from chipbench import harness
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START, log=log)
    except harness.NoAccelerator as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    for name, c in out["checks"].items():
        ok = (c["value"] <= c["limit"] if c["op"] == "<="
              else c["value"] >= c["limit"])
        print(f"check {name}: {c['value']!r} {c['op']} {c['limit']!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
