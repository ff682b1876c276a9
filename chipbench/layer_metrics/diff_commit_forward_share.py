"""The commit forwards' share of the live rows' forwards over the measured
window (``diffusion_commit_forwards_total`` / ``diffusion_forwards_total``,
a ratio): forwards that found a block without MASK and so only kept its K/V
and emitted it.  1 in 5 where every block takes 4 refining passes: what
folding the commit into the next block's first pass could save.  None for a
program that does not generate by diffusion."""


def read(ctx):
    return ctx["facts"].get("diff_commit_forward_share")
