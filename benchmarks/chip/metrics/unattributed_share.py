"""Whole round: share of the traced window's device busy time spent in
leaf ops traced under none of the round's layers
(``repro.obs.profile.ROUND_SCOPES``): the device time that no per-layer
metric of the round holds. Nothing where the program names no round
layers."""
from chipbench import tracing


def read(ctx):
    try:
        from repro.obs.profile import ROUND_SCOPES
    except ImportError:
        return None
    lo, hi = ctx.window
    named: dict = {}  # scope path -> under some round layer
    ns = 0.0
    for o in ctx.trace.ops_in(lo, hi):
        if o.scope not in named:
            named[o.scope] = any(tracing.in_scope(o, s) for s in ROUND_SCOPES)
        if not named[o.scope]:
            ns += min(o.end_ns, hi) - max(o.start_ns, lo)
    ns /= max(ctx.trace.n_devices, 1)
    return 100.0 * ns / 1e9 / ctx.busy_s
