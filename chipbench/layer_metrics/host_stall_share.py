"""Share of the traced window in which the io loop of the process that
holds the cell's chips stood still: the sum of ``late_ms`` over its
``rt.stall`` spans that lie inside the profiler's session (``profiling``
true), over ``window_s``.  It holds the program's own waits too (an
engine whose admission waits for a prefill's result on the loop reads 35
here beside a device 0.4% idle), so it is not idle time: the companion
of ``device_idle_share`` is ``host_stall_outside_share``, and what is
left of this one is what a change to the program can take out."""
from chipbench import stall_reduce


def read(ctx):
    return stall_reduce.value(ctx, "host_stall_share")
