"""Decision layer: device milliseconds per round of the ops traced under
the ``kkt_solve`` scope (the closed-form KKT of ``sim.policy``, run once
per round by greedy and once per chromosome by the genetic search)."""
from chipbench import tracing


def read(ctx):
    ns = tracing.scope_ns(ctx.trace, "kkt_solve", *ctx.window)
    return ns / 1e6 / ctx.rounds if ns > 0 else None
