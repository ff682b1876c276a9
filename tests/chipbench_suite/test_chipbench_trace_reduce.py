"""trace_reduce on a small recorded trace (trimmed from this PR's
first chip call: one device plane of a GPT-2 training step with its
nested Steps / XLA Modules / XLA Ops lines) and on hand-made planes."""

import copy
import json
import os

import pytest

from chipbench import contract, trace_reduce as tr

DATA = os.path.join(contract.ROOT, "chipbench", "testdata")


def plane(i, ops, modules=()):
    return {"name": f"/device:TPU:{i}", "lines": [
        {"name": "Steps", "events": [["0", 0.0, 1000.0, {}]]},
        {"name": tr.MODULES_LINE, "events": [list(m) + [{}] for m in modules]},
        {"name": tr.OPS_LINE, "events": [list(o) + [{}] for o in ops]},
    ]}


def test_union_counts_overlaps_once():
    assert tr.union_ns([(0, 10), (5, 15), (20, 30), (22, 25), (30, 30)]) == 25
    assert tr.union_ns([]) == 0


def test_busy_is_one_line_of_the_plane_not_the_sum_of_nested_lines():
    # ops cover 600 of 1000 ns; the module and step lines cover the same
    # time again and must not be added
    p = plane(0, [("fusion.1", 0.0, 400.0), ("fusion.2", 800.0, 200.0)],
              [("jit_step(1)", 0.0, 1000.0)])
    busy_s, window_s = tr.busy([p])
    assert busy_s == pytest.approx(600e-9)
    assert window_s == pytest.approx(1000e-9)
    assert busy_s <= window_s


def test_four_device_planes_give_the_mean_not_the_sum():
    planes = [plane(i, [("fusion.1", 0.0, 250.0 * (i + 1))]) for i in range(4)]
    busy_s, window_s = tr.busy(planes)
    assert window_s == pytest.approx(1000e-9)
    assert busy_s == pytest.approx((250 + 500 + 750 + 1000) / 4 * 1e-9)
    trace = {"planes": [{"name": "/host:CPU", "lines": []}] + planes[::-1]}
    assert [p["name"] for p in tr.device_planes(trace)] == [
        f"/device:TPU:{i}" for i in range(4)]


def test_the_host_interval_widens_the_window_and_never_narrows_it():
    p = plane(0, [("fusion.1", 100.0, 400.0)])
    assert tr.busy([p], host_s=2e-6)[1] == pytest.approx(2e-6)
    assert tr.busy([p], host_s=1e-9)[1] == pytest.approx(400e-9)


def test_no_device_plane_or_no_events_is_an_error_not_a_zero():
    with pytest.raises(tr.TraceError):
        tr.busy([])
    with pytest.raises(tr.TraceError):
        tr.busy([plane(0, [])])
    assert tr.device_planes({"planes": [{"name": "/host:CPU", "lines": []}]}) == []


def test_programs_kernels_collectives_ops_and_gaps_by_name():
    p = plane(0, [
        ("fusion.7 = fusion", 0.0, 100.0), ("all-gather.3 = all-gather", 100.0, 50.0),
        ("cc.2 = custom-call:tpu_custom_call", 200.0, 100.0), ("fusion.7 = fusion", 400.0, 100.0),
        ("reduce-scatter.1 = reduce-scatter", 500.0, 25.0),
    ], [("jit_decode_step_rowwise(123)", 0.0, 300.0),
        ("jit_prefill_into_slot(9)", 400.0, 125.0),
        ("jit_decode_step_rowwise(123)", 600.0, 100.0)])
    assert tr.module_durations_ms([p], "decode_step_rowwise") == pytest.approx([300e-6, 100e-6])
    assert tr.module_durations_ms([p], "prefill_into_slot") == pytest.approx([125e-6])
    assert tr.module_durations_ms([p], "decode_step") == []
    assert tr.op_seconds([p], tr.is_collective) == pytest.approx(75e-9)
    assert tr.op_seconds([p], tr.is_pallas) == pytest.approx(100e-9)
    assert tr.top_ops([p])[0] == ["fusion.7 = fusion", pytest.approx(200e-9)]
    gaps = tr.idle_gaps([p])
    assert [g[1] for g in gaps] == pytest.approx([100e-9, 50e-9])
    assert gaps[0][0].startswith("unattributed: after cc.2 = custom-call:tpu_custom_call before fusion.7")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "train_gpt2m_1chip_trace.json")) as f:
        return json.load(f)


def test_recorded_trace_nested_lines(recorded):
    planes = tr.device_planes(recorded)
    assert len(planes) == 1
    names = [ln["name"] for ln in planes[0]["lines"]]
    assert tr.OPS_LINE in names and tr.MODULES_LINE in names
    busy_s, window_s = tr.busy(planes)
    assert 0 < busy_s <= window_s
    # the module line covers the op line: adding it would pass the window
    mods = sum(e[2] for e in tr.line(planes[0], tr.MODULES_LINE)["events"]) / 1e9
    assert busy_s + mods > window_s
    assert tr.op_seconds(planes, tr.is_pallas) > 0
    assert tr.module_durations_ms(planes, "step")
    assert len(tr.top_ops(planes)) == 10


def test_recorded_trace_as_four_devices(recorded):
    one = tr.device_planes(recorded)[0]
    planes = []
    for i in range(4):
        p = copy.deepcopy(one)
        p["name"] = f"/device:TPU:{i}"
        if i:  # the other chips did half the work
            ops = tr.line(p, tr.OPS_LINE)
            ops["events"] = ops["events"][::2]
        planes.append(p)
    busy4, window4 = tr.busy(planes)
    busy1, window1 = tr.busy([one])
    assert window4 == pytest.approx(window1)
    assert busy1 * 0.3 < busy4 < busy1


def test_compact_name_of_real_instruction_texts():
    kernel = ('%closed_call.11 = (bf16[8,16,1024,64]{3,2,1,0:T(8,128)(2,1)S(1)}, '
              'f32[8,16,2,1024]{3,2,1,0:T(2,128)}) custom-call(bf16[8,16,1024,64]'
              '{3,2,1,0:T(8,128)(2,1)} %get-tuple-element.1158), '
              'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert tr.compact_name(kernel) == "closed_call.11 = custom-call:tpu_custom_call"
    assert tr.is_pallas(tr.compact_name(kernel))
    alloc = ('%custom-call.6 = bf16[24,8,1024,1024]{2,3,1,0:T(8,128)(2,1)} '
             'custom-call(), custom_call_target="AllocateBuffer"')
    assert not tr.is_pallas(tr.compact_name(alloc))
    fusion = ('%fusion.1 = f32[8192]{0:T(1024)S(1)} fusion(f32[8,1024,50304]{2,1,0:T(8,128)} '
              '%get-tuple-element.907), kind=kCustom, calls=%fused_computation.1')
    assert tr.compact_name(fusion) == "fusion.1 = fusion"
    gather = "%all-gather-start.3 = (f32[400]{0}, f32[1600]{0}) all-gather-start(f32[400]{0} %p), dimensions={0}"
    assert tr.is_collective(tr.compact_name(gather))
    assert not tr.is_collective("fusion.1 = fusion")
    assert tr.compact_name("jit_step(2521565712768925532)") == "jit_step(2521565712768925532)"


def test_self_time_takes_the_body_out_of_the_loop():
    events = [["while.7 = while", 0.0, 100.0, {}], ["fusion.1 = fusion", 10.0, 30.0, {}],
              ["fusion.2 = fusion", 50.0, 40.0, {}], ["copy.1 = copy", 120.0, 5.0, {}]]
    assert tr.self_times_ns(events) == {
        "while.7 = while": 30.0, "fusion.1 = fusion": 30.0,
        "fusion.2 = fusion": 40.0, "copy.1 = copy": 5.0}
