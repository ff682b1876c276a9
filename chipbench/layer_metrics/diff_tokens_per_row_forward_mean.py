"""Tokens a forward of a live row gave its client, on average over the
measured window: ``diffusion_tokens_emitted_total`` over
``diffusion_forwards_total`` (refining passes and commits alike).  A block of
4 that takes 4 refining forwards and 1 commit reads 0.8, the worst a
deployment sees; a threshold that fires raises it.  None for a program that
does not generate by diffusion."""


def read(ctx):
    return ctx["facts"].get("diff_tokens_per_row_forward_mean")
