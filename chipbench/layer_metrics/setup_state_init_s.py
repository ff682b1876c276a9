"""Self time of the replica's ``llm.start.weights`` + ``llm.start.engine`` or,
in a training cell, of ``train.start.state``: their durations less the
``xla.*`` builds and the chip opening inside them."""
from chipbench import startup_reduce


def read(ctx):
    return startup_reduce.value(ctx, "setup_state_init_s")
