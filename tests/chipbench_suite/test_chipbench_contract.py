"""The last line's contract and BENCHMARK.json's own limits."""

import copy
import json
import os

import pytest

from chipbench import contract, run

BENCH = contract.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def good_line(workload, trace):
    metrics = {
        name: {"value": 1.5, "unit": unit}
        for name, unit in contract.declared_metrics(BENCH, workload, trace).items()
    }
    device = {"platform": "tpu", "kind": "TPU v5 lite",
              "count": contract.cell(BENCH, workload)["chips"],
              "memory_peak_bytes": 9_000_000_000}
    obj = {"correct": True, "attempted": 100, "failed": 0,
           "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=2.5, window_s=3.0)
        obj["breakdown"] = {"device_ops": [["fusion.1", 1.2]],
                            "idle_gaps": [["unattributed: a b", 0.01]]}
    return obj


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", CELLS)
def test_good_line_is_accepted(workload, trace):
    obj = good_line(workload, trace)
    assert contract.validate(json.dumps(obj), workload, trace, BENCH) == obj


def _break(obj, fault):
    obj = copy.deepcopy(obj)
    first = next(iter(obj["metrics"]))
    if fault == "busy_zero":
        obj["device"]["busy_s"] = 0.0
    elif fault == "busy_over_window":
        obj["device"]["busy_s"] = obj["device"]["window_s"] * 3
    elif fault == "no_window":
        del obj["device"]["window_s"]
    elif fault == "metric_missing":
        del obj["metrics"][first]
    elif fault == "metric_null":
        obj["metrics"][first]["value"] = None
    elif fault == "metric_bare_number":
        obj["metrics"][first] = 1.5
    elif fault == "wrong_unit":
        obj["metrics"][first]["unit"] = "furlongs"
    elif fault == "undeclared_metric":
        obj["metrics"]["made_up"] = {"value": 1.0, "unit": "ms"}
    elif fault == "wrong_count":
        obj["device"]["count"] = 3
    elif fault == "no_peak":
        obj["device"]["memory_peak_bytes"] = None
    elif fault == "key_missing":
        del obj["attempted"]
    return obj


@pytest.mark.parametrize("fault", [
    "busy_zero", "busy_over_window", "no_window", "metric_missing",
    "metric_null", "metric_bare_number", "wrong_unit", "undeclared_metric",
    "wrong_count", "no_peak", "key_missing",
])
def test_each_fault_of_a_traced_line_is_refused(fault):
    # the cell and the mode PR 22 was refused on
    obj = _break(good_line("serve_ilm2_chat", 1), fault)
    with pytest.raises(contract.ContractError):
        contract.validate(json.dumps(obj), "serve_ilm2_chat", 1, BENCH)


def test_something_printed_after_the_line_is_refused():
    stdout = json.dumps(good_line("serve_ilm2_chat", 0)) + "\n(pid=7) replica stopped\n"
    with pytest.raises(contract.ContractError):
        contract.validate(contract.last_line(stdout), "serve_ilm2_chat", 0, BENCH)


def test_a_metric_of_the_other_trace_mode_does_not_stand_in():
    # per-layer metrics where end-to-end ones are due
    obj = good_line("serve_ilm2_chat", 1)
    with pytest.raises(contract.ContractError):
        contract.validate(json.dumps(obj), "serve_ilm2_chat", 0, BENCH)


def test_benchmark_json_keeps_the_limits_and_names_only_files_that_exist():
    assert contract.check_benchmark(BENCH) == []
    assert len(json.dumps(BENCH)) < 64 * 1024
    for c in BENCH["configs"]:
        with open(os.path.join(contract.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
    for w in BENCH["workloads"]:
        with open(os.path.join(contract.ROOT, "chipbench", "traffic",
                               w["traffic"] + ".json")) as f:
            job = json.load(f)["job"]
        assert os.path.isfile(os.path.join(contract.ROOT, "chipbench", "jobs", job + ".py"))


def test_the_per_layer_list_holds_one_entry_for_each_quantity_under_a_judged_metric():
    """The list's rule since PR 52: every entry names its cells, no two
    entries share (reader file, ``moves``) — a quantity that cells under
    different judged metrics report has one suffixed name for each and ONE
    reader, and no quantity has a second name — and a quarter of the 128
    places stays free for the readers of later PRs."""
    assert len(BENCH["per_layer"]) <= 96
    seen = {}
    for m in BENCH["per_layer"]:
        assert m.get("workloads"), m["name"]
        assert len(set(m["workloads"])) == len(m["workloads"]), m["name"]
        key = (os.path.basename(contract.reader_path(m["name"])), m["moves"])
        assert key not in seen, (m["name"], seen.get(key))
        seen[key] = m["name"]
    readers = {f[:-3] for f in os.listdir(
        os.path.join(contract.ROOT, "chipbench", "layer_metrics")) if f.endswith(".py")}
    assert readers == {reader[:-3] for reader, _ in seen}       # no reader without an entry


def test_bad_names_and_units_are_found():
    bad = copy.deepcopy(BENCH)
    bad["end_to_end"][0]["unit"] = "tokens per second"
    bad["workloads"][0]["name"] = "has space"
    bad["per_layer"][0]["moves"] = "nothing"
    faults = contract.check_benchmark(bad)
    assert len(faults) >= 3


def _job(workload):
    """A job result as jobs/*.py return it, every fact present."""
    return {
        "device": {"platform": "tpu", "kind": "TPU v5 lite",
                   "count": contract.cell(BENCH, workload)["chips"],
                   "memory_peak_bytes": 9e9},
        "setup_s": 30.0, "attempted": 10, "failed": 0, "correct": True,
        "end_to_end": {"train_tokens_per_s_per_chip": 25000.0,
                       "serve_tokens_per_s": 1000.0, "ttft_p95_ms": 300.0,
                       "itl_p95_ms": 50.0, "itl_tail_mean_ms": 20.0},
        "facts": {},
    }


@pytest.mark.parametrize("workload", CELLS)
def test_the_line_is_built_from_the_declared_list(workload):
    bench_line = run.build_line(BENCH, workload, 0, _job(workload), None, {})
    assert set(bench_line["metrics"]) == set(contract.declared_metrics(BENCH, workload, 0))
    contract.validate(json.dumps(bench_line), workload, 0, BENCH)


def test_a_value_that_was_not_measured_is_an_error_not_a_null():
    job = _job("serve_ilm2_chat")
    del job["end_to_end"]["itl_p95_ms"]
    with pytest.raises(RuntimeError, match="itl_p95_ms"):
        run.build_line(BENCH, "serve_ilm2_chat", 0, job, None, {})


def test_an_unknown_device_kind_has_no_default_peak():
    job = _job("train_gpt2m_1chip")
    job["device"]["kind"] = "TPU v9 imaginary"
    with pytest.raises(RuntimeError, match="peaks.json"):
        run.build_line(BENCH, "train_gpt2m_1chip", 1, job,
                       {"planes": [], "busy_s": 1.0, "window_s": 2.0}, {})
