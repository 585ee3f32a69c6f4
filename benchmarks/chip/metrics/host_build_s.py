"""Set-up: seconds of the runner's ``build`` until the program's inputs
are on the device (for the fleet scan ``repro.sim.build_sim``: host data
generation and placement of the fleet and the test set)."""


def read(ctx):
    return ctx.spans.get("host_build")
