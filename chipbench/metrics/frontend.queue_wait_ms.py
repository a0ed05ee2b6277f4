"""Median wait of a query in ServingRuntime's queue, submit to admission
(registry histogram ``serving.queue_wait_s``, the window's samples)."""
from repro.obs.registry import _HIST_GROWTH, _HIST_MIN


def read(ctx):
    before, after = ctx.before.waits, ctx.after.waits
    diff = {i: after[i] - before.get(i, 0) for i in after
            if after[i] > before.get(i, 0)}
    n = sum(diff.values())
    if n == 0:
        return None
    rank, cum = 0.5 * (n - 1), 0
    for i in sorted(diff):
        cum += diff[i]
        if cum > rank:
            sec = _HIST_MIN if i == 0 else _HIST_MIN * _HIST_GROWTH ** (
                i - 0.5)
            return sec * 1e3
