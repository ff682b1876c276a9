"""What the driver reads: BENCHMARK.json and the last line of a run.

One place says what a cell's line must hold, and ``run.py`` applies it
to its own line before that line is written: ``validate`` raises
:class:`ContractError` for anything the driver would refuse as
``output_malformed`` (PR 22 was lost to exactly that).  Nothing here
imports jax.
"""

from __future__ import annotations

import json
import math
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCHMARK = os.path.join(ROOT, "BENCHMARK.json")

LINE_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACED_DEVICE_KEYS = ("window_s", "busy_s")
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
#: the most entries ``per_layer`` may hold: the driver's limit for the list
#: ("``per_layer``: 1 to 128 metrics of single layers", the contract every
#: builder is handed; PR 38 was refused ``manifest_invalid`` at 130)
PER_LAYER_LIMIT = 128


class ContractError(Exception):
    """The line (or BENCHMARK.json) is not what the driver accepts."""


def load_benchmark(path: str = BENCHMARK) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise ContractError(
        f"no workload {workload!r} in BENCHMARK.json (have "
        f"{[w['name'] for w in bench['workloads']]})"
    )


def config_entry(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise ContractError(f"no configuration {name!r} in BENCHMARK.json")


def declared_metrics(bench: dict, workload: str, trace: int) -> dict:
    """name -> unit of every metric this cell's line must carry: the
    end-to-end ones with ``--trace 0``, the per-layer ones with
    ``--trace 1``.  A metric without a ``workloads`` key is every
    cell's."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return {
        m["name"]: m["unit"]
        for m in group
        if "workloads" not in m or workload in m["workloads"]
    }


def reader_path(metric: str, root: str = ROOT):
    """The file that reads per-layer metric ``metric``:
    ``chipbench/layer_metrics/<metric>.py`` or, for a quantity split by
    cell kind (``device_idle_share.train`` / ``.chat``: one quantity,
    several names because its cells report different end-to-end
    metrics), the file of the name without its last suffix.  None if
    neither is there."""
    folder = os.path.join(root, "chipbench", "layer_metrics")
    for name in (metric, metric.rpartition(".")[0]):
        path = os.path.join(folder, name + ".py")
        if name and os.path.isfile(path):
            return path
    return None


def _number(x) -> bool:
    return (
        isinstance(x, (int, float)) and not isinstance(x, bool)
        and math.isfinite(x)
    )


def validate(line: str, workload: str, trace: int,
             bench: dict | None = None) -> dict:
    """Parse ``line`` (the last line of a run's stdout) and hold it to
    the contract for ``workload`` in this trace mode.  Returns the
    parsed object; raises ContractError naming the first fault."""
    bench = bench or load_benchmark()
    want_chips = cell(bench, workload)["chips"]
    if "\n" in line.strip("\n"):
        raise ContractError("the last line spans more than one line")
    try:
        obj = json.loads(line)
    except ValueError as e:
        raise ContractError(f"the last line is not JSON: {e}") from None
    if not isinstance(obj, dict):
        raise ContractError("the last line is not a JSON object")
    for k in LINE_KEYS:
        if k not in obj:
            raise ContractError(f"key {k!r} is missing from the line")
    if not isinstance(obj["correct"], bool):
        raise ContractError("'correct' is not true or false")
    for k in ("attempted", "failed"):
        if not isinstance(obj[k], int) or isinstance(obj[k], bool) or obj[k] < 0:
            raise ContractError(f"{k!r} is not a count: {obj[k]!r}")
    if obj["failed"] > obj["attempted"]:
        raise ContractError("more failed than attempted")

    want = declared_metrics(bench, workload, trace)
    got = obj["metrics"]
    if not isinstance(got, dict):
        raise ContractError("'metrics' is not an object")
    for name, unit in want.items():
        if name not in got:
            raise ContractError(
                f"metric {name!r} of workload {workload!r} is missing "
                f"(--trace {trace})"
            )
        m = got[name]
        if not isinstance(m, dict) or set(m) != {"value", "unit"}:
            raise ContractError(f"metric {name!r} is not {{value, unit}}: {m!r}")
        if not _number(m["value"]):
            raise ContractError(f"metric {name!r} has no finite value: {m!r}")
        if m["unit"] != unit:
            raise ContractError(
                f"metric {name!r} has unit {m['unit']!r}, declared {unit!r}"
            )
    extra = sorted(set(got) - set(want))
    if extra:
        raise ContractError(
            f"metrics {extra} are not declared for {workload!r} with "
            f"--trace {trace}"
        )

    dev = obj["device"]
    if not isinstance(dev, dict):
        raise ContractError("'device' is not an object")
    for k in DEVICE_KEYS:
        if k not in dev:
            raise ContractError(f"device.{k} is missing")
    if not isinstance(dev["platform"], str) or not isinstance(dev["kind"], str):
        raise ContractError("device.platform / device.kind are not strings")
    if dev["count"] != want_chips:
        raise ContractError(
            f"device.count is {dev['count']!r}, the cell asks for {want_chips}"
        )
    if not _number(dev["memory_peak_bytes"]) or dev["memory_peak_bytes"] <= 0:
        raise ContractError(
            f"device.memory_peak_bytes is {dev['memory_peak_bytes']!r}"
        )
    if trace:
        for k in TRACED_DEVICE_KEYS:
            if k not in dev:
                raise ContractError(f"device.{k} is missing in a traced run")
            if not _number(dev[k]):
                raise ContractError(f"device.{k} is not a number: {dev[k]!r}")
        if not 0 < dev["busy_s"] <= dev["window_s"]:
            raise ContractError(
                f"want 0 < busy_s <= window_s, got busy_s={dev['busy_s']} "
                f"window_s={dev['window_s']}"
            )
    if "breakdown" in obj:
        if not trace:
            raise ContractError("'breakdown' belongs to a traced run")
        bd = obj["breakdown"]
        if not isinstance(bd, dict) or set(bd) - {"device_ops", "idle_gaps"}:
            raise ContractError("'breakdown' has keys other than device_ops, idle_gaps")
        for k, rows in bd.items():
            if not isinstance(rows, list) or len(rows) > 10:
                raise ContractError(f"breakdown.{k} is not a list of at most 10")
            for row in rows:
                if (not isinstance(row, list) or len(row) != 2
                        or not isinstance(row[0], str) or not _number(row[1])):
                    raise ContractError(f"breakdown.{k} row {row!r} is not [name, seconds]")
    if "compared" in obj:
        # the numbers the comparison with the reference read, each beside
        # its limit: the driver ignores the key, a reader of a refused run
        # does not; it comes last
        if list(obj)[-1] != "compared" or not isinstance(obj["compared"], dict):
            raise ContractError("'compared' is not the line's last key, an object")
        for name, pair in obj["compared"].items():
            if (not isinstance(pair, dict) or set(pair) != {"value", "limit"}
                    or not all(_number(x) for x in pair.values())):
                raise ContractError(f"compared.{name} is not {{value, limit}}: {pair!r}")
    return obj


def last_line(stdout: str) -> str:
    """The last line of a captured stdout, as the driver takes it."""
    lines = stdout.rstrip("\n").split("\n")
    return lines[-1] if lines else ""


def check_benchmark(bench: dict, root: str = ROOT) -> list:
    """Faults of BENCHMARK.json against the limits the driver checks
    before any run, as a list of sentences (empty = none found).  Not
    the driver's own check: the subset a test here can hold."""
    faults = []

    def name_ok(s, what):
        if not isinstance(s, str) or not NAME_RE.match(s):
            faults.append(f"{what} {s!r} is not a name")

    def text_ok(s, what):
        if (not isinstance(s, str) or not 1 <= len(s) <= 200
                or "\n" in s or "\t" in s):
            faults.append(f"{what} is not 1-200 characters on one line")

    if set(bench) != {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}:
        faults.append(f"top-level keys are {sorted(bench)}")
        return faults
    paths = bench["paths"]
    under = lambda p: any(p == d or p.startswith(d + "/") for d in paths)  # noqa: E731
    if not 1 <= bench["run_seconds"] <= 51:
        faults.append("run_seconds outside 1..51")
    for word in bench["command"]:
        text_ok(word, f"command word {word!r}")
    names = set()
    for c in bench["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            faults.append(f"config {c.get('name')} has keys {sorted(c)}")
            continue
        name_ok(c["name"], "config")
        text_ok(c["source"], f"source of {c['name']}")
        text_ok(c["why"], f"why of {c['name']}")
        for k in c["reduced"]:
            name_ok(k, f"reduced key of {c['name']}")
        if not under(c["file"]) or not os.path.isfile(os.path.join(root, c["file"])):
            faults.append(f"config file {c['file']} is not a file under paths")
        names.add(c["name"])
    cells = set()
    for w in bench["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            faults.append(f"workload {w.get('name')} has keys {sorted(w)}")
            continue
        for k in ("name", "config", "traffic"):
            name_ok(w[k], f"workload {k}")
        text_ok(w["why"], f"why of {w['name']}")
        if w["chips"] not in (1, 4):
            faults.append(f"{w['name']} asks for {w['chips']} chips")
        if w["config"] not in names:
            faults.append(f"{w['name']} names unknown config {w['config']}")
        if not os.path.isfile(
            os.path.join(root, "chipbench", "traffic", w["traffic"] + ".json")
        ):
            faults.append(f"{w['name']}: no traffic file {w['traffic']}.json")
        cells.add(w["name"])
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    if four > max(1, len(bench["workloads"]) // 4):
        faults.append(f"{four} cells ask for 4 chips")
    if not 1 <= len(bench["per_layer"]) <= PER_LAYER_LIMIT:
        faults.append(f"per_layer holds {len(bench['per_layer'])} entries, "
                      f"outside 1..{PER_LAYER_LIMIT}")
    e2e = {m["name"] for m in bench["end_to_end"]}
    if "setup_s" not in e2e:
        faults.append("no setup_s")
    seen = set()
    for group, keys in (
        ("end_to_end", {"name", "unit", "better", "bound", "source"}),
        ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
    ):
        for m in bench[group]:
            if set(m) - {"workloads"} != keys:
                faults.append(f"{group} metric {m.get('name')} has keys {sorted(m)}")
                continue
            name_ok(m["name"], "metric")
            if m["name"] in seen:
                faults.append(f"metric {m['name']} appears twice")
            seen.add(m["name"])
            if not UNIT_RE.match(m["unit"]):
                faults.append(f"unit {m['unit']!r} of {m['name']}")
            if m["better"] not in ("lower", "higher"):
                faults.append(f"better of {m['name']}")
            if m["source"] not in SOURCES:
                faults.append(f"source of {m['name']}")
            for w in m.get("workloads", ()):
                if w not in cells:
                    faults.append(f"{m['name']} lists unknown cell {w}")
            if group == "end_to_end":
                if m["source"] not in ("host_clock", "device_trace"):
                    faults.append(f"end-to-end {m['name']} from {m['source']}")
                if not 0.01 <= m["bound"] <= 0.1:
                    faults.append(f"bound of {m['name']} outside 0.01..0.1")
            else:
                text_ok(m["layer"], f"layer of {m['name']}")
                if m["moves"] not in e2e:
                    faults.append(f"{m['name']} moves unknown {m['moves']}")
                    continue
                mover = next(x for x in bench["end_to_end"] if x["name"] == m["moves"])
                for w in m.get("workloads", cells):
                    if "workloads" in mover and w not in mover["workloads"]:
                        faults.append(
                            f"{m['name']} is reported in {w}, where "
                            f"{m['moves']} is not"
                        )
                if reader_path(m["name"], root) is None:
                    faults.append(f"no reader layer_metrics/{m['name']}.py")
    for w in cells:
        if len(declared_metrics(bench, w, 0)) < 2:
            faults.append(f"{w} has no end-to-end metric besides setup_s")
        if not declared_metrics(bench, w, 1):
            faults.append(f"{w} has no per-layer metric")
    return faults
