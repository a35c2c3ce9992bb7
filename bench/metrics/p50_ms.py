"""Median latency, due time to results in hand, over every request due
in the window; a request that failed ranks slowest (host clock, open
loops)."""


def read(run):
    return run.record.values.get("p50_ms")
