"""mfu: the whole solve's share of the chip's roofline, in %.

The least time the chip allows for one solve's required work (the app's
``work``: the larger of bytes over peak HBM bandwidth and operations over
peak FLOP/s, from ``bench/peaks.py``) over the device time of one solve:
the seconds the solve's programs ran on the busiest chip in the traced
window, divided by the solves traced. It cannot pass 100% unless the work
is counted too high.
"""
from __future__ import annotations


def read(ctx):
    busiest = max(ctx.trace.module_s(d) for d in ctx.trace.devices)
    if busiest <= 0:
        return None
    return 100.0 * ctx.least_seconds / (busiest / ctx.solves)
