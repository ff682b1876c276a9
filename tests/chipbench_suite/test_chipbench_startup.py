"""The per-layer metrics under ``setup_s`` (PR 34): ``chipbench/startup_reduce.py``
on hand-made spans with hand-computed answers, the six declarations, and
two cells walked on the CPU."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import contract, startup_reduce

METRICS = startup_reduce.METRICS
with open(os.path.join(contract.ROOT, "chipbench", "testdata",
                       "startup_spans.json")) as f:
    DATA = json.load(f)

# times of chipbench/testdata/startup_spans.json, in ms after the cluster's start
BY_HAND = {
    "serving": {
        "setup_cluster_start_s": 1.0,      # rt.start.cluster 0 -> 1,000
        # the holder is pid 103 (the controller's worker, pid 102, opens no
        # chip): serve.start.app asks at 3,000, and the holder's actor_init
        # begins at 9,000; the controller's worker (3,100 -> 5,100), its
        # deploy, the holder's spawn at 6,000, its lease bind and its class
        # unpickled (8,020 -> 8,900) lie between
        "setup_worker_ready_s": 6.0,
        "setup_chip_open_s": 20.0,         # 9,100 -> 29,100
        # llm.start.weights 29,150 -> 33,000 less the three xla spans
        # 29,200 -> 31,000 (1,800); there is no llm.start.engine
        "setup_state_init_s": 2.05,
        # 1,800 + the trace 33,500 -> 34,500 overlapping a compile in another
        # thread 34,000 -> 36,000 (2,500 together) + 500; the compile at 90,000
        # comes after a silence of 53 s
        "setup_xla_build_s": 4.8,
        "setup_xla_cache_miss_s": 1.5,     # jit(init) 1,000 + 500; the 2,000 was a hit
        "elapsed_s": 39.0,                 # the replica's actor_init ends last
        # cluster 1,000 + ready 6,000 + chip 20,000 + weights 3,850 + the
        # builds after it 2,500 + 500
        "covered": 33.85 / 39.0,
    },
    "training": {
        "setup_cluster_start_s": 0.8,
        # train.start.workers asks at 2,000, the worker's actor_init begins
        # at 4,600; the chips open in its loop thread, 7,000 -> 30,000
        "setup_worker_ready_s": 2.6,
        "setup_chip_open_s": 23.0,
        "setup_state_init_s": 1.1,         # train.start.state 6,000 less 4,900 of xla
        "setup_xla_build_s": 7.4,          # 4,900 + the step's 2,500; not jit(forward)
        "setup_xla_cache_miss_s": 4.0,
        "elapsed_s": 36.0,                 # train.start.state ends last
        # the step's 2,500 lie behind the last start-up span and are not of
        # these 36 s: cluster 800 + ready 2,600 + chips 23,000 + state 6,000
        "covered": 32.4 / 36.0,
    },
}


@pytest.mark.parametrize("run,key", [
    (run, key) for run, want in BY_HAND.items() for key in want
])
def test_a_start_up_reduces_to_the_numbers_computed_by_hand(run, key):
    got = startup_reduce.reduce_spans(DATA[run], DATA["run_seconds"])
    assert got[key] == pytest.approx(BY_HAND[run][key], abs=1e-9)


def test_the_holder_is_the_worker_that_opened_the_chips():
    assert startup_reduce.holder_open(DATA["serving"])["pid"] == 103
    assert startup_reduce.roles(DATA["serving"]) == {
        100: "driver", 101: "raylet", 102: "worker", 103: "holder"}
    assert startup_reduce.holder_open(DATA["before_the_spans"]) is None


def test_the_set_up_ends_at_the_first_long_silence():
    built = startup_reduce.set_up_xla(DATA["serving"], 103, DATA["run_seconds"])
    assert [startup_reduce.program(s) for s in built] == [
        "init", "init", "init", "prefill_into_slot", "decode_step_rowwise",
        "convert_element_type"]
    assert startup_reduce.longest_silence_s(built) == 2.5  # 31,000 -> 33,500
    # a shorter window moves the cut: with 2 s of it, 2.5 s is a silence
    assert len(startup_reduce.set_up_xla(DATA["serving"], 103, 2.0)) == 3


def test_union_and_self_time():
    assert startup_reduce.union_ns([(0, 10), (5, 20), (30, 40), (32, 35)]) == 30
    assert startup_reduce.union_ns([]) == 0
    spans = DATA["training"]
    state = next(s for s in spans if s["name"] == "train.start.state")
    assert startup_reduce.self_ns(state, spans) == 1_100_000_000
    # the replica's __init__ less the chip, the weights and the builds after
    init = [s for s in DATA["serving"] if s["name"] == "rt.start.actor_init"][1]
    assert startup_reduce.self_ns(init, DATA["serving"]) == 3_150_000_000


def test_the_time_line_names_every_start_up_span_and_the_largest_programs():
    text = startup_reduce.table(DATA["serving"], DATA["run_seconds"])
    rows = text.split("\n")
    assert rows[0] == "span | process | start s | duration s | self s | parent"
    assert rows[1] == "rt.start.cluster | driver 100 | 0.000 | 1.000 | 0.060 | -"
    assert ("llm.start.weights | holder 103 | 29.150 | 3.850 | 2.050 | "
            "rt.start.actor_init") in rows
    assert ("rt.start.chip_open | holder 103 | 9.100 | 20.000 | 20.000 | "
            "rt.start.actor_init") in rows
    assert sum(r.startswith(startup_reduce.START_PREFIXES) for r in rows) == 16
    assert "6 spans from 29.200 s to 37.000 s; longest silence 2.500 s" in text
    programs = rows[rows.index(
        "program | trace s | lower s | compile s | compiles the cache missed") + 1:]
    assert programs == [
        "decode_step_rowwise | 0.000 | 0.000 | 2.000 | 0",
        "init | 0.500 | 0.300 | 1.000 | 1",
        "prefill_into_slot | 1.000 | 0.000 | 0.000 | 0",
        "convert_element_type | 0.000 | 0.000 | 0.500 | 1",
    ]


def test_a_program_without_the_spans_reads_zero_and_says_so(monkeypatch, capsys):
    """The parent commit of PR 34 has a span table and no start-up span in
    it: every reader returns 0 and nothing raises (``span_reduce.value``'s
    rule for a commit from before the spans)."""
    assert startup_reduce.reduce_spans(DATA["before_the_spans"], 40) is None
    for got in ({"spans": DATA["before_the_spans"], "metrics": []}, None):
        monkeypatch.setattr(startup_reduce.span_reduce, "fetch", lambda ctx: got)
        ctx = {}
        assert [startup_reduce.value(ctx, m) for m in METRICS] == [0.0] * 6
        assert "records no start-up spans; 0 stands in" in capsys.readouterr().err


def test_value_reduces_once_and_prints_the_time_line(monkeypatch, capsys):
    calls = []
    monkeypatch.setattr(
        startup_reduce.span_reduce, "fetch",
        lambda ctx: calls.append(1) or {"spans": DATA["training"], "metrics": []})
    ctx = {}
    assert [startup_reduce.value(ctx, m) for m in METRICS] == pytest.approx(
        [BY_HAND["training"][m] for m in METRICS])
    err = capsys.readouterr().err
    assert len(calls) == 1 and err.count("time line") == 1
    assert "train.start.state | holder 203 | 30.000 | 6.000 | 1.100" in err
    assert "the first five cover 90.0% of the 36.000 s" in err


@pytest.mark.parametrize("name", METRICS)
def test_declared_with_a_reader_in_every_cell(name):
    """My entries are there, with every cell the benchmark has (however
    many) and this reader."""
    bench = contract.load_benchmark()
    assert contract.check_benchmark(bench) == []
    m = next(m for m in bench["per_layer"] if m["name"] == name)
    assert (m["unit"], m["better"], m["source"], m["moves"]) == (
        "s", "lower", "program_span", "setup_s")
    assert m["workloads"] == [w["name"] for w in bench["workloads"]]
    assert len(m["workloads"]) >= 8
    path = contract.reader_path(name)
    assert path is not None and path.endswith(f"layer_metrics/{name}.py")
    for w in m["workloads"]:
        assert name in contract.declared_metrics(bench, w, 1)
        assert name not in contract.declared_metrics(bench, w, 0)


@pytest.mark.limit(170)
@pytest.mark.parametrize("cell,state_span", [
    ("train_gpt2m_1chip", "train.start.state"),
    ("serve_ilm2_batch", "llm.start.weights"),
])
def test_a_rehearsed_cell_prints_the_six_metrics_and_the_time_line(cell, state_span):
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", cell, "--seed",
         "3400000019", "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=contract.ROOT, capture_output=True, text=True, timeout=160,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = contract.validate(contract.last_line(out.stdout), cell, 1)
    got = {m: line["metrics"][m]["value"] for m in METRICS}
    missed = got.pop("setup_xla_cache_miss_s")  # 0 where every program was kept
    assert all(v > 0 for v in got.values()), got
    assert 0 <= missed <= got["setup_xla_build_s"]
    assert "span | process | start s | duration s | self s | parent" in out.stderr
    for name in ("rt.start.cluster | driver", "rt.start.worker | raylet",
                 "rt.start.boot | holder", "rt.start.lease_bind | holder",
                 "rt.start.actor_load | holder", "rt.start.actor_init | holder",
                 "rt.start.chip_open | holder",
                 state_span + " | holder",
                 "program | trace s | lower s | compile s"):
        assert name in out.stderr, name
    # PR 44: the run waited for no chips (the CPU has none), said how long
    # its end took, and a traced serving run says how long the profiler's
    # stop held the replica and how many first tokens it left out
    facts = json.loads(out.stderr.split("[chipbench] facts: ")[1].splitlines()[0])
    assert facts["chips_waited_s"] == 0.0
    assert "[chipbench] end: the cluster stopped and the chips free" in out.stderr
    if cell.startswith("serve"):
        assert facts["trace_stop_s"] > 0 and facts["ttft_left_out"] >= 0
