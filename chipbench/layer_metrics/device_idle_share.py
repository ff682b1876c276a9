"""Share of the traced window in which no operation ran on the device
(mean over the chips): 1 - busy_s / window_s."""


def read(ctx):
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
