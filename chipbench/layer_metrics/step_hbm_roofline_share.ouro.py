"""The WHOLE decode step of a looped decoder as a share of its memory
roofline: the bytes a step HAD to move (``chipbench/loop_cost.py:step_bytes``:
the blocks' weights once a PASS — a pass needs the pass before it whole, and
48 blocks do not stay in fast memory — the output head once, the K and V of
every key the step's rows could see in every (pass, layer)'s cache layer, the
engine's count over the window's steps, per step, and the new tokens' keys)
over the median device time of the decode program's executions in the trace,
over the chip's peak memory bandwidth (``peaks.json``).  A share of bandwidth
and not of FLOP/s: 16 token rows do 16 FLOP a weight byte against the chip's
240.  None where the program counted no keys or the model is not looped."""
from chipbench import loop_cost, trace_reduce
from chipbench.loadgen import percentile


def read(ctx):
    f = ctx["facts"]
    steps = f.get("decode_steps_in_window")
    if (not steps or f.get("kv_keys_visible_step") is None
            or "total_ut_steps" not in f.get("model", {})):
        return None
    ms = trace_reduce.module_durations_ms(ctx["planes"], "decode_step_rowwise")
    if not ms:
        return None
    per_step = loop_cost.step_bytes(
        f["model"], f["kv_keys_visible_step"] / steps, f["max_slots"])
    return 100.0 * per_step / ctx["peak"]["hbm_bytes_per_s"] / (percentile(ms, 50) / 1e3)
