"""The part of ``host_stall_share`` that no change to the program made:
the stops the program's own join of the cluster's stops marks ``outside``
(``ray_tpu.core.stall.join``, the one place that decides it: the process
did not run, or the machine stood still around it).  ``device_idle_share``
less this is what the program's own pace leaves the device idle.  What
is left of ``host_stall_share`` (``gc``, ``loop_held``, ``loop_waited``,
``interpreter_held`` of the process alone) is the program's own."""
from chipbench import stall_reduce


def read(ctx):
    return stall_reduce.value(ctx, "host_stall_outside_share")
