"""Drafted tokens the main model accepted over tokens drafted, over the
live rows of the measured window's speculative steps, from the engine's
counters (``LlamaDeployment.stats``: ``spec_accepted_total`` /
``spec_drafted_total``).  With seeded N(0, 0.02) weights the two heads are
near-independent softmaxes and about half the drafts are accepted; a
trained module accepts 80-90%.  None for a program that does not draft."""


def read(ctx):
    return ctx["facts"].get("spec_acceptance_rate")
