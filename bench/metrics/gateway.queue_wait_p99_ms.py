"""99th percentile of the time a request waited in the gateway's queue
before its flush took it (``RequestResult.queued_s``, the program's own
clock), over every answered request of the window, in ms."""
from bench.traffic import percentile


def read(ctx):
    q = ctx["window"].queued_s
    if q is None or not len(q):
        return None
    return percentile(q, 99) * 1e3
