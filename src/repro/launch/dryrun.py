# The 512-device virtual platform MUST be configured before jax (or
# anything importing jax) is imported — jax locks the device count on
# first backend initialization.
import os  # noqa: E402
# a CPU study on 512 virtual devices: it must never take the chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run driver (deliverable e).

For every (architecture x input shape) cell and each production mesh
(single-pod 16x16, multi-pod 2x16x16):

    lowered  = jit(step, in_shardings=...).lower(*abstract_args)
    compiled = lowered.compile()
    -> memory_analysis()  (proves the cell fits per-device HBM)
    -> cost_analysis()    (FLOPs/bytes for the roofline, §Roofline)
    -> collective bytes parsed from the optimized HLO

Results stream to JSON for EXPERIMENTS.md.  Any failure here (sharding
mismatch, OOM at compile, unsupported collective) is a bug in the system.

Usage:
    python -m repro.launch.dryrun --all
    python -m repro.launch.dryrun --arch qwen2.5-14b --shape train_4k
    python -m repro.launch.dryrun --arch quake-ann --multi-pod-only
"""
import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax

from ..configs import REGISTRY, get_arch
from ..roofline.analysis import DRYRUN_TARGET_KIND, analyze_compiled
from .mesh import describe, make_production_mesh

def run_cell(arch: str, shape: str, mesh, *, verbose: bool = True) -> Dict:
    spec = get_arch(arch)
    t0 = time.time()
    lowering = spec.build(shape, mesh, smoke=False)
    lowered = lowering.lower()
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    result = analyze_compiled(compiled, mesh, device_kind=DRYRUN_TARGET_KIND,
                              arch=arch, shape=shape)
    result.update({
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "description": lowering.description,
    })
    if verbose:
        print(f"  [OK] {arch} x {shape}: "
              f"{result['bytes_per_device_gb']:.2f} GB/dev, "
              f"{result['flops_per_device_tf']:.2f} TF/dev, "
              f"coll {result['collective_gb']:.3f} GB "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
        print(f"       dominant: {result['dominant']} | "
              f"t_comp {result['t_compute_ms']:.3f}ms "
              f"t_mem {result['t_memory_ms']:.3f}ms "
              f"t_coll {result['t_collective_ms']:.3f}ms")
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    assert len(jax.devices()) >= 512, \
        "dry-run needs the 512 virtual devices (import order bug?)"

    meshes = []
    if not args.multi_pod_only:
        meshes.append(("single_pod", make_production_mesh(multi_pod=False)))
    if not args.single_pod_only:
        meshes.append(("multi_pod", make_production_mesh(multi_pod=True)))

    cells = []
    for name, spec in REGISTRY.items():
        if args.arch and name != args.arch:
            continue
        for shape in spec.shapes:
            if args.shape and shape != args.shape:
                continue
            cells.append((name, shape))
    if not cells:
        raise SystemExit("no cells selected")

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = {}
    if args.skip_existing and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    print(f"roofline terms below are compile-time estimates from a CPU "
          f"compile against {DRYRUN_TARGET_KIND} peaks; not measured")
    failures = []
    for mesh_name, mesh in meshes:
        print(f"=== mesh {mesh_name}: {describe(mesh)} ===")
        for arch, shape in cells:
            key = f"{mesh_name}/{arch}/{shape}"
            if (args.skip_existing and key in results
                    and "error" not in results[key]):
                print(f"  [skip] {key}")
                continue
            try:
                results[key] = run_cell(arch, shape, mesh)
            except Exception as e:  # noqa: BLE001 — report all failures
                traceback.print_exc()
                failures.append((key, repr(e)))
                results[key] = {"error": repr(e)}
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)

    print(f"\n{len(results) - len(failures)} cells OK, "
          f"{len(failures)} failed -> {args.out}")
    if failures:
        for k, e in failures:
            print(f"  FAIL {k}: {e}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
