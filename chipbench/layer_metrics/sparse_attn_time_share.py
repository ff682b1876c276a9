"""Device time of the sparse-attention path — the operations traced
under the scopes ``dsa_index`` (indexer scores over every visible key),
``dsa_select`` (exact top-k, gather of the chosen latent rows) and
``mla_attn`` (attention over them) of ``ray_tpu/models/llama.py``,
decode steps and prefills alike — as a share of the device's busy time
in the traced window.  The job reads the operations' scopes from the
trace's own metadata while the trace is on disk
(``chipbench/dsa_trace.py``); None where it found none (a program
without the path, or a trace without operation metadata)."""


def read(ctx):
    seconds = ctx["facts"].get("sparse_attn_device_s")
    if not seconds:
        return None
    return 100.0 * seconds / ctx["busy_s"]
