"""Union of the ``xla.trace`` / ``xla.lower`` / ``xla.compile`` spans of the
chip's holder before the measured window: up to their first silence of
0.9 x ``run_seconds`` (nothing compiles in a window)."""
from chipbench import startup_reduce


def read(ctx):
    return startup_reduce.value(ctx, "setup_xla_build_s")
