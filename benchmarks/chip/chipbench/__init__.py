"""The chip benchmark's harness and yardstick (see ../README.md)."""
