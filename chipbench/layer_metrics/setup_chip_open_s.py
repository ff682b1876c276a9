"""``rt.start.chip_open`` of the worker that holds the cell's chips: the
initialisation of its jax backend (one span for all four chips of the
four-chip cell)."""
from chipbench import startup_reduce


def read(ctx):
    return startup_reduce.value(ctx, "setup_chip_open_s")
