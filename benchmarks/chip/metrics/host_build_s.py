"""Set-up: seconds of ``repro.sim.build_sim`` until the fleet and the test
set are on the device (host data generation and placement)."""


def read(ctx):
    return ctx.spans.get("host_build")
