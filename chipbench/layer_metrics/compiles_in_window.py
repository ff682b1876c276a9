"""XLA compiles of the process that holds the chip between the start
and the end of the measured window (``CompileLog``): must be 0."""


def read(ctx):
    return ctx["facts"]["compiles_in_window"]
