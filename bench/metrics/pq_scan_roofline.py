"""The scan kernel's share of its roofline: the least time the chip
could take for the window's scan work (``bench/roofline.py``: the larger
of ops over peak ops/s and bytes over HBM bandwidth) over the kernel's
summed device time, in %.  Which of the two bounds it goes to stderr."""
import sys

from bench.roofline import min_time_s


def read(ctx):
    t = ctx["trace"]
    work = ctx["scan_work"]()
    if work is None or not t["kernel_events"] or t["kernel_s"] <= 0:
        return None
    least, bound = min_time_s(work, ctx["peaks"])
    print(f"bench: pq_scan_roofline bound by {bound}: ops "
          f"{work['ops']:.6g}, bytes {work['bytes']:.6g}, least "
          f"{least:.6g}s, kernel {t['kernel_s']:.6g}s", file=sys.stderr)
    return 100.0 * least / t["kernel_s"]
