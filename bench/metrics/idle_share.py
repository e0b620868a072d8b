"""idle_share: the share of the traced window in which no op ran on a
chip, in %, averaged over the cell's chips: 1 - (union of the chip's op
intervals) / window."""
from __future__ import annotations


def read(ctx):
    if ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)
