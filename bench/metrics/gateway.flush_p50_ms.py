"""Median time from a flush taking a request to the request's answer
(``latency_s - queued_s``: the flush's dispatch, the program's own
clock), over every answered request of the window, in ms."""
from bench.traffic import percentile


def read(ctx):
    s = ctx["window"].service_s
    if s is None or not len(s):
        return None
    return percentile(s, 50) * 1e3
