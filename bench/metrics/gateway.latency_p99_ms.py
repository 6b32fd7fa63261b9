"""99th percentile of every request's latency from its due instant to its
answer (the benchmark's host clock; a failed request counts as
infinite), in ms: the serve tail, read here until its spread admits a
bound as an end-to-end metric."""
from bench.traffic import percentile


def read(ctx):
    lat = ctx["window"].latency_s
    if lat is None or not len(lat):
        return None
    return percentile(lat, 99) * 1e3
