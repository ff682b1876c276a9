"""Device time of the two attention kinds of a window-and-full decoder: which
operations of a trace ran under the ``jax.named_scope``s
``ray_tpu/models/llama.py:_kind_attention`` gives them — ``full_attn`` and
``swa_attn``: the new keys' write, the kernel (``kv_decode`` in a step,
``kv_prefill`` in a prefill) or XLA's body, decode steps and prefills alike —
and, inside the expert layer, the router and the sort (``moe_route``), the
grouped matmuls (``moe_experts``) and the landing (``moe_combine``).

``chipbench/mtp_trace.py``'s reduction, which reads its scopes' names from
its module when it is called, run with this list in their place, as
``chipbench/scmoe_trace.py`` does: ``<scope>_device_s`` in every program,
``<scope>_decode_device_s`` inside the decode program's executions, and
``decode_executions_traced``.  What the compiler fuses across two scopes goes
to the scope of the fusion's root.

``layer_shares`` reduces those seconds, the program's counters of the
measured window and the chip's peaks to the cell's SIX per-layer quantities
(``SHARES``); each has a reader of one line under ``chipbench/layer_metrics/``
that asks ``share`` for its own.
"""

from __future__ import annotations

from typing import Dict
from unittest import mock

from chipbench import mtp_trace, swa_cost, trace_reduce
from chipbench.loadgen import percentile

SCOPES = ("full_attn", "swa_attn", "moe_route", "moe_experts", "moe_combine")
SCOPE_FILE = mtp_trace.SCOPE_FILE


def _mine():
    return mock.patch.object(mtp_trace, "SCOPES", SCOPES)


def version(hlo_text: str) -> dict:
    """One compiled version of a program, as ``mtp_trace.version``."""
    with _mine():
        return mtp_trace.version(hlo_text)


def reduce(planes, versions) -> Dict[str, float]:
    with _mine():
        return mtp_trace.reduce(planes, versions)


def facts(trace_dir: str) -> Dict[str, float]:
    """The job's facts for the scope readers; {} where the replica wrote no
    programs' versions beside the trace, or the trace has no device plane."""
    with _mine():
        return mtp_trace.facts(trace_dir)


#: the six per-layer quantities of the cell (all move ``serve_tokens_per_s``)
SHARES = (
    "full_attn_time_share", "full_attn_hbm_roofline_share", "swa_attn_time_share",
    "swa_attn_hbm_roofline_share", "swa_step_hbm_roofline_share",
    "swa_prefill_pairs_over_band",
)


def layer_shares(planes, busy_s: float, facts: dict, peak: dict) -> Dict[str, float]:
    """The six, each left out where there is nothing to read it from (no
    seconds under its scope, no counter by kind: a program without attention
    kinds), none ever raised:

    ``<kind>_attn_time_share`` (%): device time under the scope, decode steps
    and prefills alike, over the device's busy time in the traced window.

    ``<kind>_attn_hbm_roofline_share`` (%): the bytes the decode steps'
    layers of the kind HAD to move (``swa_cost.attention_bytes``: the K and V
    of every key a row could see — the program's count over the measured
    window's steps, per step, times the decode executions in the trace — and
    the new key a slot and layer) over the scope's device time inside those
    executions, over the chip's peak memory bandwidth.  Under 100% is what
    the kernel reads beyond that (the rest of a row's last block of 128 keys)
    and the time it does not stream (the write, a call's first copies).

    ``swa_step_hbm_roofline_share`` (%): the WHOLE decode step
    (``swa_cost.step_bytes``: every matrix outside the experts and the output
    head once, the held experts touched as counted, both kinds' visible keys,
    the keys written) over the decode program's median device time, over peak
    bandwidth.  The program's count of experts touched is over decode steps
    and prefills together: the prefills' layer-steps (one a chunk of 2,048
    tokens) are taken out at their most, every held expert touched, so the
    step's share is counted from below.

    ``swa_prefill_pairs_over_band`` (x): (query, key) pairs the window
    layers' prefills computed scores for over the pairs inside the band, as
    the program counted both over the measured window: 2.0 under the flash
    kernel's tiles of 128."""
    out = {}
    f, model, steps = facts, facts.get("model"), facts.get("decode_steps_in_window")
    for scope in ("full_attn", "swa_attn"):
        if f.get(scope + "_device_s"):
            out[scope + "_time_share"] = 100.0 * f[scope + "_device_s"] / busy_s
    if f.get("swa_pairs_visible_run"):
        out["swa_prefill_pairs_over_band"] = f["swa_pairs_read_run"] / f["swa_pairs_visible_run"]
    if not (model and steps and f.get("full_keys_visible_step") is not None):
        return out
    bandwidth, slots, itemsize = peak["hbm_bytes_per_s"], f["max_slots"], f["moe_itemsize"]
    visible = {swa_cost.FULL: f["full_keys_visible_step"] / steps,
               swa_cost.WINDOW: f["swa_keys_visible_step"] / steps}
    for kind, scope in ((swa_cost.FULL, "full_attn"), (swa_cost.WINDOW, "swa_attn")):
        seconds = f.get(scope + "_decode_device_s")
        if seconds:
            moved = swa_cost.attention_bytes(model, kind, visible[kind], slots, itemsize)
            out[scope + "_hbm_roofline_share"] = (
                100.0 * moved * f["decode_executions_traced"] / bandwidth / seconds)
    ms = trace_reduce.module_durations_ms(planes, mtp_trace.DECODE_PROGRAM)
    if ms:
        prefill_layer_steps = max(0, f["moe_layer_steps"] - steps * swa_cost.expert_layers(model))
        touched = max(0.0, f["moe_experts_touched_mean"] * f["moe_layer_steps"]
                      - prefill_layer_steps * model["n_routed_experts"])
        moved = swa_cost.step_bytes(model, touched / steps, visible[swa_cost.FULL],
                                    visible[swa_cost.WINDOW], slots, itemsize)
        out["swa_step_hbm_roofline_share"] = (
            100.0 * moved / bandwidth / (percentile(ms, 50) / 1e3))
    return out


def share(ctx: dict, name: str):
    """Per-layer quantity ``name`` (one of ``SHARES``) of a traced run, for
    its reader; None where there is nothing to read it from.  Reduced once a
    run and kept in ``ctx``."""
    if "_swa_layer_shares" not in ctx:
        ctx["_swa_layer_shares"] = layer_shares(
            ctx["planes"], ctx["busy_s"], ctx["facts"], ctx["peak"])
    return ctx["_swa_layer_shares"].get(name)


def traced_program_ms(trace_dir: str) -> Dict[str, float]:
    """``program_ms`` of the trace under ``trace_dir``; {} where it holds no
    device plane (a CPU rehearsal)."""
    planes = trace_reduce.device_planes(
        trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir)))
    return program_ms(planes) if planes else {}


def program_ms(planes) -> Dict[str, float]:
    """Median device time of the two served programs' executions in the
    trace, in ms: the decode step, and the prefills of the mix's SHORTER and
    LONGER prompts apart (one program name, two versions: the executions are
    split at the midpoint of their range)."""
    out = {}
    step = trace_reduce.module_durations_ms(planes, mtp_trace.DECODE_PROGRAM)
    if step:
        out["decode_step_device_ms_p50"] = percentile(step, 50)
    prefill = trace_reduce.module_durations_ms(planes, "prefill_into_slot")
    if prefill:
        middle = (min(prefill) + max(prefill)) / 2
        short = [ms for ms in prefill if ms <= middle]
        long = [ms for ms in prefill if ms > middle]
        out["prefill_short_device_ms_p50"] = percentile(short, 50)
        if long:
            out["prefill_long_device_ms_p50"] = percentile(long, 50)
    return out
