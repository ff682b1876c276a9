"""One command, one cell, one run::

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds everything by the names in ``BENCHMARK.json``: the cell's
configuration (``chipbench/configs/<config>.json``), its traffic mix or
job (``chipbench/traffic/<traffic>.json``, whose ``job`` names
``chipbench/jobs/<job>.py``) and, in a traced run, one reader per
per-layer metric (``chipbench/layer_metrics/<metric>.py``, or the file
of the name without its last ``.suffix``).  A new cell,
mix, job kind or metric is new files and new entries; nothing here is
edited.

This process never opens a jax backend: the chip belongs to the worker
or replica the raylet leases it to.  Its standard output carries
exactly one line, written last (see ``_end``).
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402

from chipbench import chips, contract, trace_reduce  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
#: a run must end inside the driver's 360 s; the first in a checkout,
#: which compiles, inside 1200 s.  Whether programs are cached is not
#: known before the run, so the watchdog takes the longer limit.
DEADLINE_S = 1150.0
#: the longest a run waits for the chips: at its start, for a process
#: before it to let go of them, and at its end, for its own to (a clean
#: ``ray_tpu.shutdown`` of the four-chip cell took 13.0-16.8 s, a killed
#: holder of one chip let go 5.9 s later: chip runs of PR 43's builder)
CHIPS_CEILING_S = 60.0


def _say(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def _load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def _stat(pid) -> list:
    """``/proc/<pid>/stat`` after the command's name: state, parent's
    pid, ...; empty where the process is gone."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()
    except (OSError, IndexError):
        return []


def _descendants(root: int) -> list:
    children: dict = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        stat = _stat(pid)
        if len(stat) > 1 and stat[1].isdigit():
            children.setdefault(int(stat[1]), []).append(int(pid))
    out, todo = [], [root]
    while todo:
        for kid in children.get(todo.pop(), []):
            out.append(kid)
            todo.append(kid)
    return out


def _alive(pid: int) -> bool:
    """In the process table and not a zombie: a zombie holds nothing,
    and one whose parent is gone is not this process's to reap."""
    return _stat(pid)[:1] not in ([], ["Z"])


def _stop_cluster(ceiling_s: float = CHIPS_CEILING_S) -> None:
    """Shut the cluster down, leave no process behind, and return once
    the chips it used can be opened again: the next run on this machine
    may start the moment this one has exited."""
    started = _descendants(os.getpid())
    try:
        import ray_tpu

        if ray_tpu.is_initialized():
            t = threading.Thread(target=ray_tpu.shutdown, daemon=True)
            t.start()
            t.join(20)
    except Exception:  # noqa: BLE001 — the kill below is the backstop
        traceback.print_exc()
    for pid in started:
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass
    # ONE wait against one ceiling: a killed worker's children are
    # orphans that only the host's init reaps, and a dead holder's
    # device files stay busy for seconds after it
    t0 = time.monotonic()
    while True:
        for pid in started:
            try:
                os.waitpid(pid, os.WNOHANG)
            except OSError:
                pass
        alive = [pid for pid in started if _alive(pid)]
        held = chips.busy()
        if not alive and not held:
            return
        if time.monotonic() - t0 >= ceiling_s:
            left = [f"pid(s) {alive} still alive"] if alive else []
            left += [chips.describe(held)] if held else []
            _say(f"{ceiling_s:g} s after the cluster was stopped: "
                 f"{'; '.join(left)}; ending all the same")
            return
        time.sleep(0.1)


_ENDING = threading.Lock()


def _end(real_stdout: int, line, code: int, scratch, last_words=None) -> None:
    """Stop everything, then write the line (if any) as the process's
    last act and leave without running atexit hooks: nothing can print
    after it.  ``last_words`` are standard error's last lines.  Runs
    once: the watchdog and the main thread may both arrive."""
    _ENDING.acquire()
    t0 = time.monotonic()
    _stop_cluster()
    _say(f"end: the cluster stopped and the chips free {time.monotonic() - t0:.1f} s "
         "after the run's work was done")
    if last_words:
        _say(last_words)
    if scratch:
        shutil.rmtree(scratch, ignore_errors=True)
    sys.stderr.flush()
    if line is not None:
        os.write(real_stdout, (line + "\n").encode())
    os._exit(code)


def _reader(metric: str):
    path = contract.reader_path(metric)
    if path is None:
        raise RuntimeError(f"no reader for per-layer metric {metric!r} under "
                           "chipbench/layer_metrics/")
    spec = importlib.util.spec_from_file_location(
        "chipbench_layer_metric_" + metric.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def build_line(bench: dict, workload: str, trace: int, job: dict,
               traced: dict | None, peaks: dict, rehearse: bool = False) -> dict:
    """The line's object from the cell's DECLARED metrics: a metric the
    run could not compute is an error, never a null or a missing key."""
    device = dict(job["device"])
    if device.get("memory_peak_bytes") is None and rehearse:
        device["memory_peak_bytes"] = 1  # the CPU backend reports none
    if device.get("memory_peak_bytes") is None:
        raise RuntimeError("the device reported no peak_bytes_in_use")
    want = contract.declared_metrics(bench, workload, trace)
    metrics = {}
    if not trace:
        values = dict(job["end_to_end"], setup_s=job["setup_s"])
        for name, unit in want.items():
            if values.get(name) is None:
                raise RuntimeError(f"end-to-end metric {name!r} was not measured")
            metrics[name] = {"value": values[name], "unit": unit}
    else:
        peak = peaks.get(device["kind"])
        if peak is None and rehearse:
            peak = next(iter(peaks.values()))
        if peak is None:
            raise RuntimeError(
                f"device kind {device['kind']!r} is not in chipbench/peaks.json; "
                "add it with its source, there is no default"
            )
        ctx = dict(traced, facts=job["facts"], peak=peak, device=device,
                   cell=contract.cell(bench, workload))
        for name, unit in want.items():
            value = _reader(name)(ctx)
            if value is None and rehearse:
                _say(f"rehearsal: {name} found nothing to read; 0 stands in")
                value = 0.0
            if value is None:
                raise RuntimeError(
                    f"per-layer metric {name!r}: its reader found nothing to read"
                )
            metrics[name] = {"value": value, "unit": unit}
        device["busy_s"], device["window_s"] = traced["busy_s"], traced["window_s"]
    line = {
        "correct": bool(job["correct"]) and job["failed"] == 0,
        "attempted": int(job["attempted"]), "failed": int(job["failed"]),
        "metrics": metrics, "device": device,
    }
    if trace:
        line["breakdown"] = {
            "device_ops": trace_reduce.top_ops(traced["planes"]),
            "idle_gaps": trace_reduce.idle_gaps(traced["planes"]),
        }
    return line


def compared(facts: dict, tolerance: dict) -> dict:
    """Each number the job's comparison with the plain reference handed
    over (``reference_*`` in its facts) beside its limit (the
    configuration's ``reference_tolerance``): ``{name: {"value", "limit"}}``
    under the tolerance's own names.  It goes into the line as its LAST
    key and onto standard error as its last lines, so that a run that is
    not correct says by how much in what the driver keeps of it."""
    out = {}
    for key, limit in tolerance.items():
        if not contract._number(limit):
            continue
        stem = key[:-4] if key.endswith(("_max", "_min")) else key
        for fact in ("reference_" + key, "reference_err_" + key, "reference_" + stem):
            if contract._number(facts.get(fact)):
                out[key] = {"value": facts[fact], "limit": limit}
                break
    return out


def reduce_trace(trace_dir: str, rehearse: bool, host_s=None) -> dict:
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
    planes = trace_reduce.device_planes(trace)
    if not planes and rehearse:
        planes = trace_reduce.rehearsal_device_planes(trace)
    if not planes:
        raise trace_reduce.TraceError(
            "the trace holds no device plane (planes: "
            f"{[p['name'] for p in trace['planes']]})"
        )
    busy_s, window_s = trace_reduce.busy(planes, host_s)
    return {"planes": planes, "busy_s": busy_s, "window_s": window_s}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="walk the control flow on the CPU at toy shapes with "
                         "fake chips; the line says platform cpu and its "
                         "numbers go nowhere (never in BENCHMARK.json's command)")
    args = ap.parse_args()

    # Everything that anyone prints from here on - forwarded worker and
    # replica logs, the profiler, shutdown, children that inherit fd 1 -
    # goes to stderr.  The real stdout is kept for the one line.
    sys.stdout.flush()
    real_stdout = os.dup(1)
    os.dup2(2, 1)
    sys.stdout = sys.stderr
    logging.basicConfig(stream=sys.stderr, level=logging.WARNING)

    scratch = None
    watchdog = threading.Timer(
        DEADLINE_S, lambda: (_say(f"deadline of {DEADLINE_S:.0f} s passed"),
                             _end(real_stdout, None, 1, scratch)))
    watchdog.daemon = True
    watchdog.start()
    try:
        bench = contract.load_benchmark()
        cell = contract.cell(bench, args.workload)
        cfg_file = contract.config_entry(bench, cell["config"])["file"]
        with open(os.path.join(contract.ROOT, cfg_file)) as f:
            config = json.load(f)
        traffic = _load_json("traffic", cell["traffic"] + ".json")
        peaks = _load_json("peaks.json")
        job_mod = importlib.import_module("chipbench.jobs." + traffic["job"])

        if args.rehearse:
            os.environ["JAX_PLATFORMS"] = "cpu"
            os.environ["RT_TPU_CHIPS_OVERRIDE"] = str(cell["chips"])
            os.environ["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={cell['chips']}"
            )
        scratch = tempfile.mkdtemp(prefix="chipbench_")  # under $TMPDIR
        trace_dir = os.path.join(contract.ROOT, ".chipbench_trace", args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if args.trace:
            os.makedirs(trace_dir)

        # the clock of ``setup_s`` starts where the chips were free: what
        # a process before this one still held is not this run's set-up
        waited = chips.wait_until_free(CHIPS_CEILING_S)
        if waited:
            _say(f"{args.workload}: waited {waited:.1f} s for the chips a process "
                 "before this one still held")

        import ray_tpu

        info = ray_tpu.init(session_dir=os.path.join(scratch, "session"))
        n_tpu = int(ray_tpu.cluster_resources().get("TPU", 0))
        _say(f"{args.workload}: {n_tpu} chip(s) found by {info['tpu_detected_by']}")
        if n_tpu < cell["chips"]:
            raise RuntimeError(
                f"the cell asks for {cell['chips']} TPU chip(s), ray_tpu.init() "
                f"found {n_tpu}; there is no CPU fallback"
            )
        job = job_mod.run({
            "cell": cell, "config": config, "traffic": traffic,
            "seed": args.seed, "seconds": args.seconds, "trace": args.trace,
            "rehearse": args.rehearse, "trace_dir": trace_dir,
            "storage_dir": os.path.join(scratch, "results"),
            "t_process_start": T_PROCESS_START + waited,
        })
        if not args.rehearse and (
            job["device"]["platform"] != "tpu" or job["device"]["count"] != cell["chips"]
        ):
            raise RuntimeError(f"the job ran on {job['device']}, not on "
                               f"{cell['chips']} TPU chip(s)")
        traced = reduce_trace(
            trace_dir, args.rehearse, job["facts"].get("trace_host_s")
        ) if args.trace else None
        shutil.rmtree(trace_dir, ignore_errors=True)
        obj = build_line(bench, args.workload, args.trace, job, traced, peaks,
                         args.rehearse)
        facts = {k: v for k, v in job["facts"].items()
                 if not isinstance(v, (list, dict))}
        facts["chips_waited_s"] = waited
        _say(f"facts: {json.dumps(facts)}")
        obj["compared"] = compared(facts, config.get("reference_tolerance", {}))
        last_words = f"correct {obj['correct']}, failed {obj['failed']}; compared: " + "; ".join(
            f"{k} {v['value']:.6g} (limit {v['limit']:g})" for k, v in obj["compared"].items())
        line = json.dumps(obj)
        contract.validate(line, args.workload, args.trace, bench)
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            raise RuntimeError("the parent process opened a jax backend")
    except BaseException:  # noqa: BLE001 — every failure ends in _end
        traceback.print_exc()
        watchdog.cancel()
        _end(real_stdout, None, 1, scratch)
    watchdog.cancel()
    _end(real_stdout, line, 0, scratch, last_words)


if __name__ == "__main__":
    main()
