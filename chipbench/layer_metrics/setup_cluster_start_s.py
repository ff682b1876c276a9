"""``rt.start.cluster``: ``ray_tpu.init`` in the driver, from its call to the
driver attached (the GCS and the raylet spawned and registered inside it)."""
from chipbench import startup_reduce


def read(ctx):
    return startup_reduce.value(ctx, "setup_cluster_start_s")
