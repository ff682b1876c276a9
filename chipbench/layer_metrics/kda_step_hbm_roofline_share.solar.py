"""Kimi delta attention's one-token update as a share of its memory
roofline: the bytes it HAD to move — every row's recurrent state of every KDA
layer read once and written once, as the program counted them over the
window's decode steps (``gdn_state_bytes_step``; a slot's state is
``chipbench/kda_cost.py:state_bytes``), per step, times the decode executions
in the trace — over the device time of the operations under ``kda_step``
inside those executions, over the chip's peak memory bandwidth
(``peaks.json``).  The counters are the measured window's, the executions the
traced seconds': the same traffic in both."""


def read(ctx):
    f = ctx["facts"]
    seconds, steps = f.get("kda_step_decode_device_s"), f.get("decode_steps_in_window")
    if not seconds or not steps or not f.get("gdn_state_bytes_step"):
        return None
    per_step = f["gdn_state_bytes_step"] / steps
    return (100.0 * per_step * f["decode_executions_traced"]
            / ctx["peak"]["hbm_bytes_per_s"] / seconds)
