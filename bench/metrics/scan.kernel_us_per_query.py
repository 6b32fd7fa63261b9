"""Summed device durations of the scan kernel's events (the Mosaic
custom calls) per query answered in the traced window, in us."""


def read(ctx):
    t = ctx["trace"]
    n = len(ctx["window"].qidx)
    if not t["kernel_events"] or not n:
        return None
    return t["kernel_s"] / n * 1e6
