"""comm_ms: device time of the collective ops (collective-permute,
all-reduce, ... by HLO opcode) per solve, in ms, averaged over the chips.
A trace without a collective op has nothing to read."""
from __future__ import annotations


def read(ctx):
    secs = [ctx.trace.collective_s(d) for d in ctx.trace.devices]
    if not any(secs):
        return None
    return 1e3 * sum(secs) / len(secs) / ctx.solves
