"""The two quantities that stand beside ``device_idle_share`` (PR 53):
``chipbench/stall_reduce.py`` on a hand-made span file with hand-computed
answers, the eight declarations in ``per_layer`` (found by name; every cell
that reports ``device_idle_share.<suffix>`` reports both beside it, under the
same suffix: all twelve since PR 58 joined the six batch cells that waited),
and one cell walked on the CPU."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import contract, stall_reduce
from ray_tpu.core import stall

with open(os.path.join(contract.ROOT, "chipbench", "testdata",
                       "stall_spans.json")) as f:
    DATA = json.load(f)
SPANS, WINDOW_S = DATA["spans"], DATA["window_s"]
OPENS = [s for s in SPANS if s["name"] == "rt.start.chip_open"]
QUANTITIES = ("host_stall_share", "host_stall_outside_share")
SUFFIXES = ("train", "batch", "chat", "mixed")


def test_the_sum_the_holder_the_session_and_the_window():
    """By hand, of chipbench/testdata/stall_spans.json: the holder is pid
    303 (pid 304 opened no chip); of its nine stops five have
    ``profiling`` true, 300 + 80 + 516 + 72 + 120 = 1,088 ms of a 3 s
    window.  Outside the program: the two of ``not_scheduled`` (516 + 72)
    and the 120 ms that its own evidence reads ``interpreter_held`` while
    the raylet and the driver did not run beside it (reading ``host``):
    708 ms.  start_trace's 400 ms and stop_trace's 6 s are the holder's
    but not the window's, the folded span lies after it, and pid 304's
    900 ms are not the holder's."""
    stops = stall.join(SPANS)
    assert len(stops) == 15
    got = stall_reduce.reduce_stalls(stops, OPENS, WINDOW_S)
    assert got["holder"] == 303 and got["stops"] == 5
    assert got["host_stall_share"] == pytest.approx(100 * 1.088 / 3.0)
    assert got["host_stall_outside_share"] == pytest.approx(100 * 0.708 / 3.0)
    # half the window, twice the share
    twice = stall_reduce.reduce_stalls(stops, OPENS, WINDOW_S / 2)
    assert twice["host_stall_share"] == pytest.approx(2 * got["host_stall_share"])
    # the cluster's readings are the program's: the 516 ms beside the
    # raylet's and the driver's is the host's, the 72 ms the process's own
    own = {s["late_ms"]: (s["cause"], s["reading"]) for s in stops
           if s["pid"] == 303 and s["profiling"]}
    assert own == {
        300.0: ("loop_waited", "process"), 80.0: ("gc", "process"),
        516.0: ("not_scheduled", "host"), 72.0: ("not_scheduled", "process"),
        120.0: ("interpreter_held", "host"),
    }
    frozen = [s for s in stops if s["late_ms"] == 19000.0]
    assert [s["reading"] for s in frozen] == ["chip_open", "chip_open"]


def test_no_holder_no_reading():
    assert stall_reduce.reduce_stalls(stall.join(SPANS), [], WINDOW_S) is None
    alone = [s for s in OPENS if s["pid"] == 304]  # where no other opened any
    got = stall_reduce.reduce_stalls(stall.join(SPANS), alone, WINDOW_S)
    assert (got["holder"], got["stops"]) == (304, 1)
    assert got["host_stall_share"] == pytest.approx(30.0)
    assert got["host_stall_outside_share"] == 0.0


def test_the_table_names_every_process_and_the_longest_stops():
    stops = stall.join(SPANS)
    text = stall_reduce.table(stops, 303, DATA["t0_ns"])
    rows = text.splitlines()
    assert rows[0] == "process | cause | stops | s in all | longest s"
    assert rows[1] == "raylet 302 | not_scheduled | 3 | 19.610 | 19.000"
    assert rows[2] == "driver 301 | not_scheduled | 3 | 19.585 | 19.000"
    assert "holder 303 | interpreter_held | 3 | 6.520 | 6.000" in rows
    assert "holder 303 | gc | 1 | 0.080 | 0.080" in rows
    assert ("100.200 | holder 303 | 0.300 | loop_waited | process | yes | "
            "loop: _deliver (llm.py:1) < step (llm.py:2)") in rows
    assert "101.300 | holder 303 | 0.516 | not_scheduled | host | yes | -" in rows
    assert "9.200 | raylet 302 | 19.000 | not_scheduled | chip_open | no | -" in rows
    starts = [float(r.split(" | ")[0]) for r in rows[rows.index(
        "start s | process | late s | cause | reading | in the trace | where") + 1:]]
    assert starts == sorted(starts) and len(starts) == 15


def test_value_asks_once_prints_the_table_once_and_keeps_the_result(monkeypatch, capsys):
    calls = []
    monkeypatch.setattr(stall_reduce, "fetch", lambda: calls.append(1) or {
        "stalls": stall.join(SPANS), "opens": OPENS})
    ctx = {"window_s": WINDOW_S}
    got = [stall_reduce.value(ctx, q) for q in QUANTITIES + QUANTITIES]
    assert got == pytest.approx([100 * 1.088 / 3, 100 * 0.708 / 3] * 2)
    err = capsys.readouterr().err
    assert len(calls) == 1 and err.count("[chipbench] stops: the run's") == 1
    assert ("holder 303: 5 stop(s) inside the traced window of 3.000 s; "
            "host_stall_share 36.267, host_stall_outside_share 23.600") in err


def test_a_program_without_the_record_reads_zero_and_says_so(monkeypatch, capsys):
    """The parent commit of PR 53 has no ``state.stalls``: both readers
    return 0 and nothing raises (``startup_reduce.value``'s rule)."""
    from ray_tpu.util import state

    monkeypatch.delattr(state, "stalls")
    assert stall_reduce.fetch() is None
    ctx = {"window_s": WINDOW_S}
    assert [stall_reduce.value(ctx, q) for q in QUANTITIES] == [0.0, 0.0]
    assert capsys.readouterr().err.count("keeps no record of its stops") == 2


def test_the_eight_entries_stand_beside_device_idle_share_in_its_cells():
    """By name — never by their place in the list or by how many cells the
    benchmark has: a later PR appends entries behind them, and a cell it
    adds joins ``device_idle_share.<suffix>`` and these two in one edit (the
    readers ask the GCS, no job feeds them)."""
    bench = contract.load_benchmark()
    assert contract.check_benchmark(bench) == []
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for quantity in QUANTITIES:
        for suffix in SUFFIXES:
            m, idle = by_name[f"{quantity}.{suffix}"], by_name[f"device_idle_share.{suffix}"]
            assert (m["unit"], m["better"], m["source"], m["layer"]) == (
                "%", "lower", "program_span", "device")
            # beside device_idle_share under every suffix, in its cells and order
            assert m["moves"] == idle["moves"] and m["workloads"] == idle["workloads"]
            assert contract.reader_path(m["name"]).endswith(
                f"layer_metrics/{quantity}.py")
            for w in m["workloads"]:
                assert m["name"] in contract.declared_metrics(bench, w, 1)
                assert m["name"] not in contract.declared_metrics(bench, w, 0)
    # both quantities under ONE suffix in a cell that has either
    for w in bench["workloads"]:
        got = [n for n in contract.declared_metrics(bench, w["name"], 1)
               if n.startswith("host_stall_")]
        assert not got or (len(got) == 2
                           and got[0].rsplit(".", 1)[1] == got[1].rsplit(".", 1)[1])


@pytest.mark.limit(170)
def test_a_rehearsed_cell_prints_both_quantities_and_the_table():
    cell = "serve_ilm2_chat"
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", cell, "--seed",
         "5300000017", "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=contract.ROOT, capture_output=True, text=True, timeout=160,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = contract.validate(contract.last_line(out.stdout), cell, 1)
    share = line["metrics"]["host_stall_share.chat"]
    outside = line["metrics"]["host_stall_outside_share.chat"]
    assert share["unit"] == outside["unit"] == "%"
    assert 0 <= outside["value"] <= share["value"] < 100
    assert "[chipbench] stops: the run's" in out.stderr
    assert "process | cause | stops | s in all | longest s" in out.stderr
    assert "inside the traced window of" in out.stderr
    assert "keeps no record of its stops" not in out.stderr
