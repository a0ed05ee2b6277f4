"""Mean host time of a probe round outside its wait on the device, in ms:
stacking, the union, padding, the scan's dispatch and copies, the top-k
fold and retirement (registry histograms: the window's
``scheduler.round_wall_s`` sum less its ``scan.wait_s`` sum, over the
window's ``scheduler.rounds``)."""


def read(ctx):
    b, a = ctx.before.counters, ctx.after.counters
    rounds = a.get("scheduler.rounds", 0) - b.get("scheduler.rounds", 0)
    if rounds <= 0 or "scan.wait_s.sum" not in a:
        return None
    wall = a["scheduler.round_wall_s.sum"] \
        - b.get("scheduler.round_wall_s.sum", 0.0)
    wait = a["scan.wait_s.sum"] - b.get("scan.wait_s.sum", 0.0)
    return 1e3 * (wall - wait) / rounds
