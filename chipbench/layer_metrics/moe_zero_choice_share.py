"""(token, choice) pairs of the measured window that fell on an IDENTITY
("zero-compute") expert, over all the pairs the router made (token rows x
expert layers x experts per token), from the counter the engine's cache
carries beside ``moe_expert_tokens`` (``moe_zero_choices``).  256 of the
router's 768 outputs are identity experts: 33.3% under an even router; what
a token costs hangs on it (12 choices, of which this share costs nothing).
None where the program has no such counter."""


def read(ctx):
    return ctx["facts"].get("moe_zero_choice_share")
