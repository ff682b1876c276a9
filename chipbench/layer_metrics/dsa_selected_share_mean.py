"""Keys attention was given over keys the indexer saw, over every
(layer, row, query) of the measured window, decode steps and prefills
together, from the totals the engine's cache carries
(``LlamaDeployment.stats``): how sparse the window's traffic was.  A
decode query at position 8,000 reads 2,048 of 8,001: 25.6%."""


def read(ctx):
    return ctx["facts"].get("dsa_selected_share_mean")
