"""The least time the chips could take for the vectors the plan selected,
over their mean device busy time in the traced window, in percent: the
bytes and operations of ``scan.hbm_roofline`` against the peaks of all
the chips that hold a shard (``scan.shards``, the registry gauge), over
the busy time ``trace.reduce`` averages over the chips.

Bytes: the live vectors the scheduler streamed
(``scheduler.vectors_streamed``) times the width and the storage's
bytes, each streamed once by the one shard that holds it.  Operations:
two per element of each query-vector comparison
(``serving.comparisons``), at the bf16 peak.  The per-shard kernel is
``scan_topk_indexed``; the all-gather that merges the shards' top-k
lists moves ``shards x rows x k`` entries a round, over the chips'
interconnect, not their HBM, and is left out.  None where the program
records no shards or the window streamed nothing."""


def scan_bytes(vectors, dim, storage_bytes):
    """HBM bytes of a scan that streams ``vectors`` live vectors."""
    return vectors * dim * storage_bytes


def scan_flops(comparisons, dim):
    """Operations of ``comparisons`` query-vector dot products."""
    return 2.0 * comparisons * dim


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or ctx.trace.busy_s <= 0:
        return None
    b, a = ctx.before.counters, ctx.after.counters
    shards = a.get("scan.shards")
    if not shards:
        return None
    vecs = a.get("scheduler.vectors_streamed", 0) \
        - b.get("scheduler.vectors_streamed", 0)
    comps = a.get("serving.comparisons", 0) - b.get("serving.comparisons", 0)
    if vecs <= 0:
        return None
    t_bytes = scan_bytes(vecs, ctx.dim, ctx.storage_bytes) \
        / (ctx.peaks["hbm_bw"] * shards)
    t_flops = scan_flops(comps, ctx.dim) / (ctx.peaks["peak_flops"] * shards)
    return 100.0 * max(t_bytes, t_flops) / ctx.trace.busy_s
