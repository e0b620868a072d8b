"""exposed_comm_ms: the part of comm_ms in which no other op runs on that
chip, per solve, in ms, averaged over the chips. A trace without a
collective op has nothing to read."""
from __future__ import annotations


def read(ctx):
    if not any(ctx.trace.collective_s(d) for d in ctx.trace.devices):
        return None
    secs = [ctx.trace.exposed_collective_s(d) for d in ctx.trace.devices]
    return 1e3 * sum(secs) / len(secs) / ctx.solves
