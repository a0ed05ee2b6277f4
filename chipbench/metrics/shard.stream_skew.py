"""How evenly the rounds' streamed vectors spread over the partition
shards: the shard count times the sum, over the window's rounds, of the
largest shard's live vectors streamed (registry counter
``scan.shard_vectors_max``), over all the vectors streamed
(``scheduler.vectors_streamed``).  1.0 is perfect balance; a round waits
for its busiest chip, so a round of skew ``s`` streams like ``s`` times
its even share.  The shard count is the registry gauge ``scan.shards``.
None where the program records no shards (a runtime without a sharded
snapshot) or the window streamed nothing."""


def read(ctx):
    b, a = ctx.before.counters, ctx.after.counters
    shards = a.get("scan.shards")
    if shards is None or "scan.shard_vectors_max" not in a:
        return None
    vecs = a.get("scheduler.vectors_streamed", 0) \
        - b.get("scheduler.vectors_streamed", 0)
    if vecs <= 0:
        return None
    vmax = a["scan.shard_vectors_max"] - b.get("scan.shard_vectors_max", 0)
    return shards * vmax / vecs
