"""Three-term roofline analysis from compiled artifacts (deliverable g).

    compute term    = HLO_FLOPs_per_device   / peak_FLOP/s
    memory term     = HLO_bytes_per_device   / HBM_bw
    collective term = wire_bytes_per_device  / link_bw

``cost_analysis()`` on the SPMD module gives *per-device* flops/bytes
(verified empirically).  Collective bytes are NOT in cost_analysis — we
parse the optimized HLO: every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute, with ring-algorithm wire-byte formulas and
group sizes from ``replica_groups``.

Peaks come from :data:`PEAKS`, keyed by ``device_kind``.  A run on the
CPU compiles for a named target kind and its terms are compile-time
estimates, never measurements.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, Optional

import jax
import numpy as np

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, and
# 1,600 Gbit/s of chip-to-chip interconnect over 4 links, i.e. 50 GB/s per
# link (the collective term charges one link: a conservative basis).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"peak_flops": 197e12, "peak_int8_ops": 393e12,
                    "hbm_bw": 819e9, "hbm_bytes": 16e9, "ici_bw": 50e9},
}


# The chip a CPU dry run compiles for (its terms are estimates from that
# compile, never measurements).
DRYRUN_TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to "
                         f"repro.roofline.analysis.PEAKS with their "
                         f"source") from None

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_DEF_RE = re.compile(
    r"%([\w.\-]+)\s*=\s*\(?([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def _type_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo_text: str) -> Dict:
    """Sum per-device wire bytes over every collective in the module."""
    sizes: Dict[str, int] = {}
    for m in _DEF_RE.finditer(hlo_text):
        sizes[m.group(1)] = _type_bytes(m.group(2), m.group(3))

    per_kind: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    count = 0
    for line in hlo_text.splitlines():
        kind = None
        for k in _COLLECTIVES:
            if re.search(rf"\s=\s.*\b{k}(-start)?\(", line):
                kind = k
                break
        if kind is None:
            continue
        dm = _DEF_RE.search(line)
        if dm is None:
            continue
        result_bytes = _type_bytes(dm.group(2), dm.group(3))
        # group size
        gs = 1
        gm = _GROUPS_IOTA_RE.search(line)
        if gm:
            gs = int(gm.group(2))
        else:
            gl = _GROUPS_LIST_RE.search(line)
            if gl:
                gs = len(gl.group(1).split(","))
        if gs <= 1:
            continue
        # operand bytes (for reduce-scatter the operand is the big side)
        ops = re.findall(rf"{kind}(?:-start)?\(([^)]*)\)", line)
        operand_bytes = 0
        if ops:
            for name in re.findall(r"%([\w.\-]+)", ops[0]):
                operand_bytes += sizes.get(name, 0)
        frac = (gs - 1) / gs
        if kind == "all-reduce":
            wire = 2.0 * result_bytes * frac
        elif kind == "all-gather":
            wire = result_bytes * frac
        elif kind == "reduce-scatter":
            wire = (operand_bytes or result_bytes * gs) * frac
        elif kind == "all-to-all":
            wire = result_bytes * frac
        else:  # collective-permute
            wire = result_bytes
        per_kind[kind] += wire
        count += 1
    total = sum(per_kind.values())
    return {"wire_bytes_per_device": total, "ops": count,
            "by_kind": {k: v for k, v in per_kind.items() if v}}


def analyze_compiled(compiled, mesh, *, device_kind: str, arch: str = "",
                     shape: str = "") -> Dict:
    """Trip-count-aware roofline terms for one compiled cell, against the
    peaks of ``device_kind`` (the chip the cell targets; name it even when
    the compile ran on the CPU).

    flops / bytes / wire-bytes come from ``hlo_cost.analyze`` (XLA's
    ``cost_analysis()`` counts while bodies once — worthless for
    scan-over-layers programs); per-device residency from
    ``memory_analysis()``."""
    from . import hlo_cost
    hw = peaks(device_kind)
    c = hlo_cost.analyze(compiled.as_text())
    flops = c.flops
    bytes_acc = c.bytes_accessed
    mem = compiled.memory_analysis()
    per_dev_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     + mem.temp_size_in_bytes - mem.alias_size_in_bytes)

    t_comp = flops / hw["peak_flops"]
    t_mem = bytes_acc / hw["hbm_bw"]
    t_coll = c.wire_bytes / hw["ici_bw"]
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    dominant = max(terms, key=terms.get)

    n_dev = mesh.devices.size
    mf = model_flops(arch, shape)
    useful = (mf / n_dev / max(flops, 1.0)) if mf else None
    return {
        "arch": arch, "shape": shape, "devices": n_dev,
        "target_device_kind": device_kind,
        "source": (f"compile-time estimate from a "
                   f"{jax.default_backend()} compile against "
                   f"{device_kind} peaks; not measured"),
        "flops_per_device_tf": flops / 1e12,
        "hlo_bytes_per_device_gb": bytes_acc / 1e9,
        "bytes_per_device_gb": per_dev_bytes / 1e9,
        "collective_gb": c.wire_bytes / 1e9,
        "collective_ops": c.collective_ops,
        "collective_by_kind": {k: round(v / 1e9, 4)
                               for k, v in c.wire_by_kind.items()},
        "dynamic_whiles": c.dynamic_whiles,
        "t_compute_ms": t_comp * 1e3,
        "t_memory_ms": t_mem * 1e3,
        "t_collective_ms": t_coll * 1e3,
        "dominant": dominant,
        "model_flops_total": mf,
        "useful_flops_ratio": useful,
        "roofline_fraction": (t_comp / max(t_comp, t_mem, t_coll)
                              if max(terms.values()) > 0 else None),
    }


# ---------------------------------------------------------------------------
# MODEL_FLOPS: analytic "useful work" per cell (6ND convention for LM)
# ---------------------------------------------------------------------------

def model_flops(arch: str, shape: str) -> Optional[float]:
    try:
        from ..configs import get_arch
        spec = get_arch(arch)
    except Exception:
        return None
    cfg = spec.model_config()
    if spec.family == "lm":
        return _lm_model_flops(cfg, shape)
    if spec.family == "gnn":
        return _gnn_model_flops(cfg, shape)
    if spec.family == "recsys":
        return _recsys_model_flops(arch, cfg, shape)
    if spec.family == "ann":
        return _ann_model_flops(cfg, shape)
    return None


def _lm_model_flops(cfg, shape: str) -> float:
    from ..configs.families import LM_SHAPES
    from ..models.transformer import active_param_count
    sh = LM_SHAPES[shape]
    n = active_param_count(cfg)
    b, s = sh["batch"], sh["seq"]
    hdh = cfg.n_heads * cfg.head_dim
    if sh["kind"] == "train":
        # 6ND + causal attention 6 * L * S^2/2 * Hdh * 2(QK+PV) per batch row
        return 6.0 * n * b * s + 6.0 * cfg.n_layers * b * s * s * hdh
    if sh["kind"] == "prefill":
        return 2.0 * n * b * s + 2.0 * cfg.n_layers * b * s * s * hdh
    # decode: one token, full-cache attention
    return 2.0 * n * b + 4.0 * cfg.n_layers * b * s * hdh


def _gnn_model_flops(cfg, shape: str) -> float:
    from ..configs.families import GNN_SHAPES
    sh = GNN_SHAPES[shape]
    e = sh["n_edges"] * (2 * sh.get("n_graphs", 1) if "n_graphs" in sh
                         else 1)
    n = sh.get("n_graphs", 1) * sh["n_nodes"] if "n_graphs" in sh \
        else sh["n_nodes"]
    d_in = sh["d_feat"]
    f = 0.0
    for layer in range(cfg.n_layers):
        last = layer == cfg.n_layers - 1
        heads = 1 if last else cfg.n_heads
        d_out = cfg.n_classes if last else cfg.d_hidden
        f += 2.0 * n * d_in * heads * d_out      # projection
        f += 6.0 * e * heads * d_out             # scores+softmax+aggregate
        d_in = d_out * (1 if last else heads)
    return 3.0 * f                                # fwd + bwd


def _recsys_model_flops(arch: str, cfg, shape: str) -> float:
    from ..configs.families import RECSYS_SHAPES
    sh = RECSYS_SHAPES[shape]
    b = sh.get("n_cand", sh.get("batch", 1))

    def mlp_flops(dims):
        return sum(2.0 * dims[i] * dims[i + 1] for i in range(len(dims) - 1))

    if arch == "din":
        per = (cfg.seq_len * mlp_flops((4 * cfg.embed_dim,) + cfg.attn_mlp
                                       + (1,))
               + mlp_flops((2 * cfg.embed_dim + cfg.n_dense,) + cfg.mlp
                           + (1,)))
    elif arch == "sasrec":
        d = cfg.embed_dim
        per = cfg.n_blocks * (4 * cfg.seq_len * d * d * 2
                              + 2 * cfg.seq_len * cfg.seq_len * d * 2)
    elif arch == "two-tower-retrieval":
        per = 2 * mlp_flops((cfg.embed_dim,) + cfg.tower_mlp) \
            + 2 * cfg.tower_mlp[-1]
    else:  # dlrm
        f = cfg.n_sparse + 1
        per = (mlp_flops((cfg.n_dense,) + cfg.bot_mlp)
               + 2.0 * f * f * cfg.embed_dim
               + mlp_flops((cfg.n_interactions + cfg.embed_dim,)
                           + cfg.top_mlp))
    mult = 3.0 if sh["kind"] == "train" else 1.0
    return mult * b * per


def _ann_model_flops(dims: Dict, shape: str) -> float:
    from ..configs.quake_arch import QUAKE_SHAPES
    sh = QUAKE_SHAPES[shape]
    p, s_cap, d = dims["p"], dims["s_cap"], dims["d"]
    if sh["kind"] == "assign":
        return 2.0 * sh["n"] * p * d
    b = sh["batch"]
    route = 2.0 * b * p * d
    if sh["kind"] == "fixed":
        return route + 2.0 * b * sh["nprobe"] * s_cap * d
    if sh["kind"] == "brute":
        return 2.0 * b * p * s_cap * d
    # adaptive: nominal 2 rounds x chunk partitions per shard
    return route + 2.0 * b * 2 * 2 * s_cap * d
