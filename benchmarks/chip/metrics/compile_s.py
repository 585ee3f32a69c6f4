"""Set-up: seconds of ``FleetSim.lower(...).compile()``, a compile or a
load from the persistent compilation cache."""


def read(ctx):
    return ctx.spans.get("compile")
