"""Shuffle dispatch: the program's ``EngineStats.shuffle_s`` per query
(the fused rebucket, from key upload to the device_get of its result)."""
from harness.readers import done


def read(run):
    q = done(run, "query.")
    if not q:
        return None
    return sum(u.stats.shuffle_s for u in q) / len(q)
