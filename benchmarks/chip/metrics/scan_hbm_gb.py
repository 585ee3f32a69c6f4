"""Compiled program: device memory the compiler assigns to one
experiment's program, arguments (for the fleet scan the fleet and the
carry) plus outputs plus temp, less what outputs alias, in 1e9 B.
``peak_hbm_gb`` reads the runtime's ``peak_bytes_in_use``, which leaves
out the compiled temp, so a lowering that copies the whole fleet shows
here first."""


def read(ctx):
    m = ctx.memory
    if not m:
        return None
    return (m["argument"] + m["output"] + m["temp"] - m["alias"]) / 1e9
