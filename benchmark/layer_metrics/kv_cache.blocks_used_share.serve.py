"""Most of the paged pool's blocks that live sequences held at once over
the window, as a share of the pool: the driver reads the server's own
count (``server.stats()``, ``kv_cache.blocks_used``) at every answer. The
pool is allocated whole, so this says how much of it the traffic fills.
In per cent."""


def read(seen):
    counts = seen["counters"]
    if not counts.get("kv_blocks_pool"):
        return None
    return 100.0 * counts["kv_blocks_used_peak"] / counts["kv_blocks_pool"]
