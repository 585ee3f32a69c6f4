"""Wire: device milliseconds per round of the ops traced under the
``quantize_wire`` scope (flattening the slots' models, the stochastic
quantization of the uplink planes and the eq.-2 weights; the aggregate
kernel is ``aggregate_us_per_call``)."""
from chipbench import tracing


def read(ctx):
    ns = tracing.scope_ns(ctx.trace, "quantize_wire", *ctx.window)
    return ns / 1e6 / ctx.rounds if ns > 0 else None
