"""Mean partitions a query of the window consumed (``QueryResult.nprobe``),
as APS planned them."""


def read(ctx):
    n = [r.nprobe for r in ctx.results if r is not None and r.status == "OK"]
    return sum(n) / len(n) if n else None
