"""Planner: the program's ``EngineStats.planning_s`` per query, in
milliseconds (a plan-cache hit costs tens of microseconds)."""
from harness.readers import done


def read(run):
    q = done(run, "query.")
    if not q:
        return None
    return 1e3 * sum(u.stats.planning_s for u in q) / len(q)
