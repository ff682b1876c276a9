"""The last line's contract and BENCHMARK.json's own limits."""

import copy
import importlib
import json
import os

import pytest

from chipbench import contract, run, startup_reduce

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


def test_the_numbers_compared_stand_beside_their_limits_as_the_lines_last_key():
    """``run.compared``: what the job's comparison with the plain reference
    read (``reference_*`` facts), under the tolerance's names, each beside
    its limit; a limit the job hands no reading for, a count of steps and
    the tolerance's prose are left out."""
    facts = {"reference_err_rms": 0.011, "reference_err_max": 0.054,
             "reference_swap_rate": 0.10, "reference_swapped_margin_max": 0.0054,
             "max_slots": 64, "reference_note": "text"}
    tolerance = {"rms": 0.013, "max": 0.18, "swap_rate_max": 0.2, "swapped_margin_max": 0.017,
                 "twin_rms": 0.004, "check_steps": 8, "strict": True, "why": "prose"}
    got = run.compared(facts, tolerance)
    assert got == {"rms": {"value": 0.011, "limit": 0.013},
                   "max": {"value": 0.054, "limit": 0.18},
                   "swap_rate_max": {"value": 0.10, "limit": 0.2},
                   "swapped_margin_max": {"value": 0.0054, "limit": 0.017}}
    assert run.compared({}, tolerance) == {}
    obj = dict(good_line("serve_ilm2_chat", 1), compared=got)
    assert contract.validate(json.dumps(obj), "serve_ilm2_chat", 1, BENCH) == obj
    for bad in ({"compared": got, **good_line("serve_ilm2_chat", 1)},          # not last
                dict(obj, compared={"rms": {"value": 0.011}}),                   # no limit
                dict(obj, compared=[0.011])):
        with pytest.raises(contract.ContractError, match="compared"):
            contract.validate(json.dumps(bad), "serve_ilm2_chat", 1, BENCH)


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
    reader, and no quantity has a second name — no reader file stands without
    an entry, and the list is no longer than the DRIVER's limit for it:
    ``contract.PER_LAYER_LIMIT``, 128, from the contract every builder is
    handed ("``per_layer``: 1 to 128 metrics of single layers").  The reserve
    PR 52 kept by a cap of 96 here refused the PRs it was kept for, which may
    not edit this file; since PR 58 it is a line in PERF.md section 3 (a
    ``benchmark`` PR prunes when the list passes 96)."""
    bench = contract.load_benchmark()
    assert len(bench["per_layer"]) <= contract.PER_LAYER_LIMIT == 128
    seen = {}
    for m in bench["per_layer"]:
        assert m.get("workloads"), m["name"]
        assert len(set(m["workloads"])) == len(m["workloads"]), m["name"]
        key = (os.path.basename(contract.reader_path(m["name"])), m["moves"])
        assert key not in seen, (m["name"], seen.get(key))
        seen[key] = m["name"]
    folder = os.path.dirname(contract.reader_path(bench["per_layer"][0]["name"]))
    readers = {f[:-3] for f in os.listdir(folder) if f.endswith(".py")}
    assert readers == {reader[:-3] for reader, _ in seen}       # no reader without an entry


def test_four_chip_cells_are_at_most_a_quarter_of_the_cells_or_one():
    """The driver's rule, held in this ONE place: of a benchmark's cells at
    most 25%, rounded down, may ask for four chips, and one always may."""
    bench = contract.load_benchmark()
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    assert contract.check_benchmark(dict(bench, workloads=[
        dict(w, chips=4) for w in bench["workloads"]])) != []


# ---- a thirteenth cell without an edit ---------------------------------------

#: the lists a made-up cell joins, by what its job could feed
JOINS = {
    "serving": (
        "serve_tokens_per_s", "decode_step_device_ms_p50.batch",
        "prefill_device_ms_p50.batch", "decode_batch_occupancy.batch",
        "device_idle_share.batch", "compiles_in_window.batch",
        "step_dispatch_ms_p50.batch", "step_deliver_ms_p50.batch",
        "step_serve_plane_ms_p50.batch", "gmm_time_share", "gmm_hbm_roofline_share",
        "moe_experts_touched_mean", "moe_held_assignment_share",
        "host_stall_share.batch", "host_stall_outside_share.batch",
        "gdn_step_time_share.olmoh",    # a mechanism shared: the entry that has the reader
    ),
    "four_chips": (
        "train_tokens_per_s_per_chip", "train_step_ms_p50", "mfu", "flash_attn_time_share",
        "collective_time_share", "device_idle_share.train", "compiles_in_window.train",
        "host_stall_share.train", "host_stall_outside_share.train",
    ),
}
#: and the seven readers it brings, appended at the list's END
BROUGHT = ("kda_step_time_share", "kda_step_hbm_roofline_share", "kda_scan_time_share",
           "kda_scan_roofline_share", "kda_step_mfu", "kda_state_bytes_step_mean",
           "kda_tokens_scanned_share")


def with_a_thirteenth(bench, kind):
    """A copy of ``bench`` with what a PR that adds a configuration brings:
    one ``configs`` entry, one cell, the cell appended to the lists of
    ``JOINS[kind]`` and the six ``setup_*``, and ``BROUGHT`` at the end."""
    bench = copy.deepcopy(bench)
    cell, judged = "made_up_cell", JOINS[kind][0]
    bench["configs"].append({
        "name": "made-up-13", "source": "https://example.org/made-up/config.json",
        "file": "chipbench/configs/made-up-13.json", "reduced": ["num_hidden_layers"],
        "why": "a configuration no PR brought: the shape tests must not mind it"})
    bench["workloads"].append({
        "name": cell, "config": "made-up-13", "traffic": "made_up_closed64",
        "chips": 4 if kind == "four_chips" else 1,
        "why": "a thirteenth cell: appended to the lists its job feeds, nothing edited"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in JOINS[kind] + startup_reduce.METRICS:
            m["workloads"].append(cell)
    bench["per_layer"] += [
        {"name": name + ".made", "unit": "%", "better": "higher", "source": "device_trace",
         "layer": "model step (models/llama.py)", "moves": judged, "workloads": [cell]}
        for name in BROUGHT]
    return bench


@pytest.fixture(scope="module")
def made_up_root(tmp_path_factory):
    """A root that holds the benchmark's three data folders — every file
    that is there, linked — and the files the made-up entries name."""
    root = tmp_path_factory.mktemp("thirteenth")
    for folder in ("configs", "traffic", "layer_metrics"):
        real = os.path.join(contract.ROOT, "chipbench", folder)
        os.makedirs(root / "chipbench" / folder)
        for name in os.listdir(real):
            if os.path.isfile(os.path.join(real, name)):
                os.symlink(os.path.join(real, name), root / "chipbench" / folder / name)
    (root / "chipbench" / "configs" / "made-up-13.json").write_text("{}")
    (root / "chipbench" / "traffic" / "made_up_closed64.json").write_text("{}")
    for name in BROUGHT:
        (root / "chipbench" / "layer_metrics" / (name + ".py")).write_text(
            "def read(ctx):\n    return None\n")
    return str(root)


def _shape_tests():
    """(module, test, arguments) of every test under ``tests/chipbench_suite``
    that holds ``BENCHMARK.json``'s lists to a shape."""
    own = [("test_chipbench_" + m, t, ()) for m, t in (
        ("contract", "test_the_per_layer_list_holds_one_entry_for_each_quantity_under_a_judged_metric"),
        ("contract", "test_four_chip_cells_are_at_most_a_quarter_of_the_cells_or_one"),
        ("glm", "test_benchmark_json_holds_the_cell_and_its_entries"),
        ("joyai", "test_the_benchmark_holds_the_configuration_the_cell_and_the_joy_metrics"),
        ("sdar", "test_my_benchmark_entries_are_there_in_this_order"),
        ("olmo_hybrid", "test_my_benchmark_entries_are_there_in_this_order"),
        ("longcat", "test_my_benchmark_entries_are_there_by_name_and_in_this_order"),
        ("mimo", "test_my_benchmark_entries_are_there_by_name"),
        ("stall", "test_the_eight_entries_stand_beside_device_idle_share_in_its_cells"),
        ("loadgen", "test_the_mixed_cell_is_judged_on_the_tail_mean_and_the_chat_cell_on_its_p95"),
        ("span_reduce", "TestReduceRun.test_the_sync_overhead_and_the_seven_aliases_are_gone"),
    )]
    own += [("test_chipbench_startup", "test_declared_with_a_reader_in_every_cell", (name,))
            for name in startup_reduce.METRICS]
    own += [("test_chipbench_span_reduce", "TestReduceRun.test_declared_with_a_reader", (name,))
            for name in sorted(importlib.import_module("test_chipbench_span_reduce").NEW_METRICS)]
    return own


@pytest.mark.parametrize("kind", sorted(JOINS))
@pytest.mark.parametrize("module, test, args", _shape_tests(),
                         ids=lambda v: v if isinstance(v, str) else ".".join(v))
def test_a_thirteenth_cell_fails_no_shape_test(made_up_root, monkeypatch, kind, module, test, args):
    """THE property PR 58 was for: a PR of any kind adds a configuration, its
    cell and its readers as new files and appended entries, joins the lists
    its job can feed, and no test under the benchmark's ``paths`` fails for
    it.  Every shape test of the suite runs here on a copy of the benchmark
    with a made-up thirteenth configuration — a one-chip serving cell, and a
    second case a four-chip training cell — with ``contract.load_benchmark``
    patched to return the copy, and ``contract.check_benchmark`` finds no
    fault in either.

    Not walked here, because they need the real files a real PR brings: that
    a configuration's file states the entry's ``source`` and ``reduced`` and
    its traffic names a job that exists
    (``test_benchmark_json_keeps_the_limits_and_names_only_files_that_exist``),
    a good line of the cell (``test_good_line_is_accepted``), and the new
    configuration's own test file (catalog row, cost functions, readers on
    recorded facts, the ``--rehearse`` walk)."""
    made_up = with_a_thirteenth(BENCH, kind)
    check, path = contract.check_benchmark, contract.reader_path
    monkeypatch.setattr(contract, "load_benchmark", lambda *a: copy.deepcopy(made_up))
    monkeypatch.setattr(contract, "check_benchmark",
                        lambda bench, root=made_up_root: check(bench, root))
    monkeypatch.setattr(contract, "reader_path",
                        lambda metric, root=made_up_root: path(metric, root))
    assert contract.check_benchmark(made_up) == []
    assert len(made_up["workloads"]) == len(BENCH["workloads"]) + 1
    names = [m["name"] for m in made_up["per_layer"]]
    assert names[len(BENCH["per_layer"]):] == [name + ".made" for name in BROUGHT]   # at the END
    target = importlib.import_module(module)
    for part in test.split("."):
        target = target() if isinstance(target, type) else target
        target = getattr(target, part)
    target(*args)


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
