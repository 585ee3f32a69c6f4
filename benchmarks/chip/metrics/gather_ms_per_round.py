"""Slot gather: device milliseconds per round of the ops traced under the
``slot_gather`` scope (the row slices that compact the scheduled clients'
data onto the slot axis, ``sim.fleet.gather_active``)."""
from chipbench import tracing


def read(ctx):
    ns = tracing.scope_ns(ctx.trace, "slot_gather", *ctx.window)
    return ns / 1e6 / ctx.rounds if ns > 0 else None
