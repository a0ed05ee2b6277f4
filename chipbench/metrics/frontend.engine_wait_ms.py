"""Mean wait of a served call (a deadline tick, a flush, a drain, a write
barrier) for ServingRuntime's engine lock, in ms (registry histogram
``serving.engine_wait_s``: the window's sum over its count)."""


def read(ctx):
    b, a = ctx.before.counters, ctx.after.counters
    key = "serving.engine_wait_s"
    n = a.get(f"{key}.count", 0) - b.get(f"{key}.count", 0)
    if n <= 0:
        return None
    return 1e3 * (a[f"{key}.sum"] - b.get(f"{key}.sum", 0.0)) / n
