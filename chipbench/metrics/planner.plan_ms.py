"""Mean time a flush spent planning its queries, in ms: the APS radius
check, the snapshot check and ``plan_rounds`` (registry histogram
``planner.plan_s``'s window sum over the window's ``serving.flushes``)."""


def read(ctx):
    b, a = ctx.before.counters, ctx.after.counters
    flushes = a.get("serving.flushes", 0) - b.get("serving.flushes", 0)
    if flushes <= 0 or "planner.plan_s.sum" not in a:
        return None
    return 1e3 * (a["planner.plan_s.sum"]
                  - b.get("planner.plan_s.sum", 0.0)) / flushes
