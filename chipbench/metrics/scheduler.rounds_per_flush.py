"""Probe rounds the RoundScheduler ran per flush over the window
(registry counters ``scheduler.rounds`` / ``serving.flushes``)."""


def read(ctx):
    b, a = ctx.before.counters, ctx.after.counters
    flushes = a.get("serving.flushes", 0) - b.get("serving.flushes", 0)
    if flushes <= 0:
        return None
    return (a.get("scheduler.rounds", 0) - b.get("scheduler.rounds", 0)) \
        / flushes
