"""Decision layer: device milliseconds per round of the ops traced under
the ``decision`` scope: the whole policy (greedy + KKT, or the genetic
search with its KKT fitness solves), so ``kkt_solve`` is part of it and
this less ``kkt_ms_per_round`` is the search and scheduling outside
the KKT."""
from chipbench import tracing


def read(ctx):
    ns = tracing.scope_ns(ctx.trace, "decision", *ctx.window)
    return ns / 1e6 / ctx.rounds if ns > 0 else None
