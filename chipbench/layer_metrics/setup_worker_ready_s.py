"""Until the worker that ends up holding the cell's chips can run what it was
leased for: from the driver's ask (``serve.start.app`` / ``train.start.workers``
begins) to the start of that worker's ``rt.start.actor_init``; every process
started on the way (the serve controller's) and the class's unpickling inside."""
from chipbench import startup_reduce


def read(ctx):
    return startup_reduce.value(ctx, "setup_worker_ready_s")
