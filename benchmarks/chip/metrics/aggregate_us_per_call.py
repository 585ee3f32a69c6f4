"""Wire: device microseconds per call of the Pallas aggregate kernel
(fused dequantize and eq.-2 weighted sum, ``pallas_aggregate`` scope).

No roofline share: the compiled scan keeps the kernel's operands and
output in on-chip memory (``S(1)`` in the HLO layouts), so its HBM bytes
are not what bounds it, and the peaks table has no published on-chip
bandwidth."""
from chipbench import tracing


def read(ctx):
    calls = [o for o in ctx.trace.ops_in(*ctx.window)
             if o.kernel and tracing.in_scope(o, "pallas_aggregate")]
    if not calls:
        return None
    return sum(o.dur_ns for o in calls) / len(calls) / 1e3
