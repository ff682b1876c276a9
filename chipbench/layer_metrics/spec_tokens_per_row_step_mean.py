"""Tokens a live row of a speculative step gave its client, on average
over the measured window: ``spec_tokens_emitted_total`` over
``spec_drafted_total`` (1 + the acceptance rate, less what fell past a
request's budget).  None for a program that does not draft."""


def read(ctx):
    return ctx["facts"].get("spec_tokens_per_row_step_mean")
