"""Server update: device milliseconds per round of the ops traced under
the ``server_update`` scope (the new global model, the O(U) scatters and
EMA updates of the estimators, the Lyapunov queues, the round's
outputs)."""
from chipbench import tracing


def read(ctx):
    ns = tracing.scope_ns(ctx.trace, "server_update", *ctx.window)
    return ns / 1e6 / ctx.rounds if ns > 0 else None
