"""Device time of the multi-token-prediction module — the operations
traced under ``mtp_draft`` (``ray_tpu/models/mtp.py``: embedding, norms,
projection, the module's block, the head; decode steps and prefills alike)
— as a share of the device's busy time in the traced window: what drafting
costs.  The job reads the operations' scopes from the compiled programs'
text while the trace is on disk (``chipbench/mtp_trace.py``); None where it
found none (a program without the module)."""


def read(ctx):
    seconds = ctx["facts"].get("mtp_draft_device_s")
    if not seconds:
        return None
    return 100.0 * seconds / ctx["busy_s"]
