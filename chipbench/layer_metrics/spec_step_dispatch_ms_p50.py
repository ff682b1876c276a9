"""Median host time of building and dispatching one speculative step
(``llm.step.build`` + ``llm.step.dispatch``: the list of rows, the thread
hop, the jitted call; no array is built, the rows' state is on the device),
over the steps of the run that admitted nothing, from the engine's own
spans.  It runs while the device computes the step launched before.
``chipbench/mtp_trace.py:host_step_ms`` says why this cell does not read it
through ``span_reduce``'s alignment.  None where the program records no
such spans."""
from chipbench import mtp_trace


def read(ctx):
    return mtp_trace.host_step_ms(ctx).get("dispatch")
