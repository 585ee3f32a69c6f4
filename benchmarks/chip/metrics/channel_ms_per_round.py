"""Channel: device milliseconds per round of the ops traced under the
``channel_draw`` scope (the round's key split, the rate draw of
``sim.channel`` and, with faults on, the outage and fade draws)."""
from chipbench import tracing


def read(ctx):
    ns = tracing.scope_ns(ctx.trace, "channel_draw", *ctx.window)
    return ns / 1e6 / ctx.rounds if ns > 0 else None
