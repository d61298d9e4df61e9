"""Requests the plan cache answered or warm-started, over all requests."""


def read(run):
    n = run.counters.get("requests", 0)
    if not n:
        return None
    c = run.counters
    return 100.0 * (c.get("cache_hits", 0) + c.get("cache_warm_starts", 0)) / n
