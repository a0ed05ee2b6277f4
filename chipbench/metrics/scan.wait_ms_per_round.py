"""Mean time a probe round's host blocked on the device for the scan's
result, in ms (registry histogram ``scan.wait_s``'s window sum over the
window's ``scheduler.rounds``)."""


def read(ctx):
    b, a = ctx.before.counters, ctx.after.counters
    rounds = a.get("scheduler.rounds", 0) - b.get("scheduler.rounds", 0)
    if rounds <= 0 or "scan.wait_s.sum" not in a:
        return None
    return 1e3 * (a["scan.wait_s.sum"] - b.get("scan.wait_s.sum", 0.0)) \
        / rounds
