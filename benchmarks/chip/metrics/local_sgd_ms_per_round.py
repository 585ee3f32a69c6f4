"""Local SGD: device milliseconds per round of the ops traced under the
``fleet_local_sgd`` scope (tau SGD steps of every active slot)."""
from chipbench import tracing


def read(ctx):
    ns = tracing.scope_ns(ctx.trace, "fleet_local_sgd", *ctx.window)
    return ns / 1e6 / ctx.rounds if ns > 0 else None
