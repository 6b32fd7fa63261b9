"""Summed device durations of the scan kernel's events per grid step:
over queries answered in the traced window times the configuration's
``search.max_scan`` (the paged kernel's steps per query), in us."""


def read(ctx):
    t = ctx["trace"]
    n = len(ctx["window"].qidx)
    steps = ctx["config"].get("search", {}).get("max_scan")
    if not steps or not t["kernel_events"] or not n:
        return None
    return t["kernel_s"] / (n * steps) * 1e6
