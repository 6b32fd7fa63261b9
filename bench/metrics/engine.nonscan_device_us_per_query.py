"""Device busy time outside the scan kernel's events (list selection,
plan and lookup tables, padding copies, the exact refine), per query
answered in the traced window, in us."""


def read(ctx):
    t = ctx["trace"]
    n = len(ctx["window"].qidx)
    if not t["devices"] or not t["kernel_events"] or not n:
        return None
    return (t["busy_s"] - t["kernel_busy_s"]) / n * 1e6
