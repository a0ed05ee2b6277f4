"""§Perf driver for LM cells: lower one (arch x shape) on the single-pod
mesh, print the three roofline terms + op-level attribution, optionally
with build overrides (the hillclimb knobs).

    PYTHONPATH=src python -m benchmarks.perf_lm --arch mistral-large-123b \
        --shape train_4k [--microbatches 4] [--profile]
"""
import os

# a CPU study on 512 virtual devices: it must never take the chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import json
import time


def run(arch: str, shape: str, label: str = "baseline", profile: bool = False,
        out_path: str = "results/perf_lm.json", **overrides):
    from repro.configs import get_arch
    from repro.launch.mesh import make_production_mesh
    from repro.roofline.analysis import DRYRUN_TARGET_KIND, analyze_compiled
    from repro.roofline import hlo_profile

    print(f"roofline terms below are compile-time estimates from a CPU "
          f"compile against {DRYRUN_TARGET_KIND} peaks; not measured")
    mesh = make_production_mesh()
    spec = get_arch(arch)
    t0 = time.perf_counter()
    lw = spec.build(shape, mesh, **overrides) if overrides \
        else spec.build(shape, mesh)
    lowered = lw.lower()
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    txt = compiled.as_text()
    r = analyze_compiled(compiled, mesh, device_kind=DRYRUN_TARGET_KIND,
                         arch=arch, shape=shape)
    r["label"] = label
    r["lower_s"] = round(t1 - t0, 1)
    r["compile_s"] = round(t2 - t1, 1)
    print(f"[{label}] {arch}/{shape}: "
          f"t_comp {r['t_compute_ms']:.0f}ms  t_mem {r['t_memory_ms']:.0f}ms"
          f"  t_coll {r['t_collective_ms']:.0f}ms  dom={r['dominant']}"
          f"  useful={r['useful_flops_ratio']:.3f}")
    print("  by kind:", r["collective_by_kind"])
    if profile:
        print("  -- top collectives (trip-weighted) --")
        for row in hlo_profile.top_collectives(txt, 10):
            print(f"    {row['kind']:<20} {row['shape']:<36} "
                  f"x{row['trips']:<5.0f} {row['wire_gb_total']:9.1f} GB"
                  f"   [{row['comp'][:40]}]")
        print("  -- top memory opcode classes --")
        for op, gb, ex in hlo_profile.top_memory_ops(txt, 10):
            print(f"    {op:<24} {gb:10.1f} GB   e.g. {ex}")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    existing = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            existing = json.load(f)
    existing[f"{arch}/{shape}/{label}"] = r
    with open(out_path, "w") as f:
        json.dump(existing, f, indent=1)
    return r


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--label", default="baseline")
    ap.add_argument("--profile", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--loss-chunk", type=int, default=None)
    args = ap.parse_args()
    kw = {}
    if args.microbatches is not None:
        kw["microbatches"] = args.microbatches
    if args.loss_chunk is not None:
        kw["loss_chunk"] = args.loss_chunk
    run(args.arch, args.shape, label=args.label, profile=args.profile, **kw)
