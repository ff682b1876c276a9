"""The part of ``setup_xla_build_s`` in ``xla.compile`` spans the persistent
cache did not answer (``cache_hit`` false): 0 only when every program was
loaded."""
from chipbench import startup_reduce


def read(ctx):
    return startup_reduce.value(ctx, "setup_xla_cache_miss_s")
