"""Eval: device milliseconds per round of the ops traced under the
``eval`` scope (the test-set forward of the new global model)."""
from chipbench import tracing


def read(ctx):
    ns = tracing.scope_ns(ctx.trace, "eval", *ctx.window)
    return ns / 1e6 / ctx.rounds if ns > 0 else None
