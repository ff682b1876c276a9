"""Of the tokens unmasked in the measured window, the share a pass unmasked
because their confidence was over the threshold (``diffusion_threshold_
transfers_total`` / ``diffusion_tokens_unmasked_total``, a ratio), the rest
being the most confident of a pass that had too few.  0 with random weights
over 18,991 ids.  None for a program that does not generate by diffusion."""


def read(ctx):
    return ctx["facts"].get("diff_threshold_transfer_share")
