"""Experts with at least one row, mean over every layer-step of the
measured window (decode steps and prefills), from the counters the
engine's cache carries (``LlamaDeployment.stats``).  Of 64; a decode
step of 32 rows x 8 choices under near-uniform routing touches 63."""


def read(ctx):
    return ctx["facts"].get("moe_experts_touched_mean")
