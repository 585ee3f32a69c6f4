"""The chip benchmark of the compiled fleet scan (see ../README.md)."""
