"""Routed (token, expert) assignments of the measured window that fell
on an expert this chip holds, over all that were routed (token rows x
expert layers x experts per token), from the counters the engine's
cache carries.  16 of 256 experts held: 6.25% under an even router."""


def read(ctx):
    return ctx["facts"].get("moe_held_assignment_share")
