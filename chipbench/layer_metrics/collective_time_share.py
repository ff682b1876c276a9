"""Device time in collective operations on the op line (all-reduce,
all-gather, reduce-scatter, collective-permute and the waits on their
asynchronous forms) as a share of the traced window, mean over the
chips.  Collectives that XLA wraps in a generic ``async-start`` /
``async-done`` pair are not told apart from other asynchronous work
and are not counted (0.6% of the window in PR 23's trace); time a
transfer spends in flight beside compute is not device time."""
from chipbench import trace_reduce


def read(ctx):
    seconds = trace_reduce.op_seconds(ctx["planes"], trace_reduce.is_collective)
    return 100.0 * seconds / ctx["window_s"]
