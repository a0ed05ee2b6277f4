"""Mean recall@k of the window's queries against the exact top-k over the
rows resident when each was sent (the correctness check's reading)."""


def read(ctx):
    r = ctx.checks.recall
    return float(r.mean()) if len(r) else None
