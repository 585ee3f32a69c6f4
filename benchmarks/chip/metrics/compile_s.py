"""Set-up: seconds of the program's compile (for the fleet scan
``FleetSim.lower(...).compile()``), a compile or a load from the
persistent compilation cache."""


def read(ctx):
    return ctx.spans.get("compile")
