"""The least time the chip could take for the vectors the plan selected,
over the device's busy time in the traced window, in percent.

Bytes: the live vectors the scheduler streamed (registry counter
``scheduler.vectors_streamed``) times the width and the storage's bytes,
at the HBM bandwidth.  Operations: two per element of each query-vector
comparison (``serving.comparisons``), at the bf16 peak, an upper bound on
the float32 rate.  The larger of the two times is the roofline; the busy
time is every device op's, so a faster kernel or less work outside the
scan raises the share alike."""


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or ctx.trace.busy_s <= 0:
        return None
    b, a = ctx.before.counters, ctx.after.counters
    vecs = a.get("scheduler.vectors_streamed", 0) \
        - b.get("scheduler.vectors_streamed", 0)
    comps = a.get("serving.comparisons", 0) - b.get("serving.comparisons", 0)
    if vecs <= 0:
        return None
    t_bytes = vecs * ctx.dim * ctx.storage_bytes / ctx.peaks["hbm_bw"]
    t_flops = 2.0 * comps * ctx.dim / ctx.peaks["peak_flops"]
    return 100.0 * max(t_bytes, t_flops) / ctx.trace.busy_s
