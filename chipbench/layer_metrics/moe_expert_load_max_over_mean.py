"""Rows of the busiest (layer, expert) over the mean of all (layer,
expert) pairs, over the measured window: 1 is perfectly even routing.
From the same counters as ``moe_experts_touched_mean``."""


def read(ctx):
    return ctx["facts"].get("moe_expert_load_max_over_mean")
