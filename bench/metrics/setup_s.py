"""Process start to the window: JAX start, weights made on the device,
the trainer built, compilation or the persistent compile cache, and the
checked first steps (the host copy of the first gradient for the check
left out)."""

UNIT = "s"


def read(ctx):
    return ctx["setup_s"]
