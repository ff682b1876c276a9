"""The Ouro cell's benchmark side: the configuration file against the catalog
row it was written from (every key kept, nothing reduced) and its byte
arithmetic (``jax.eval_shape`` of the program's init, ``loop_cost`` by hand),
``BENCHMARK.json``'s entries (that mine are there, BY NAME: never as a list's
tail or as a count), the scope map and the four new readers on hand-made
planes and facts, the job's window arithmetic, its refusal of a program
without the loop, the comparison that decides ``correct`` on a toy cache —
honest, and with each of the four pieces left out of the reference — and the
cell walked on the CPU through the repo's own benchmark."""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import contract, loop_cost, loop_trace
from chipbench.jobs import serve_loop

CELL = "serve_ouro_reason_batch"
CONFIG = "ouro-2.6b"
TRAFFIC = "loop_reason_closed32"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
MINE = ("step_hbm_roofline_share.ouro", "loop_attn_time_share.ouro",
        "loop_attn_hbm_roofline_share.ouro", "loop_passes_per_row_step.ouro")
GENERIC = (
    "decode_step_device_ms_p50.batch", "prefill_device_ms_p50.batch",
    "decode_batch_occupancy.batch", "device_idle_share.batch", "compiles_in_window.batch",
    "step_dispatch_ms_p50.batch", "step_deliver_ms_p50.batch",
    "step_serve_plane_ms_p50.batch", "host_stall_share.batch",
    "host_stall_outside_share.batch",
)
#: the readers a CPU walk cannot feed (no device plane under the scopes)
NEED_A_DEVICE_PLANE = ("loop_attn_time_share.ouro", "loop_attn_hbm_roofline_share.ouro")


def reader(metric):
    path = contract.reader_path(metric)
    spec = importlib.util.spec_from_file_location(
        "reader_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def config_file():
    with open(os.path.join(contract.ROOT, "chipbench", "configs", CONFIG + ".json")) as f:
        return json.load(f)


# ---- the configuration and the cell -----------------------------------------

def test_the_configuration_is_the_published_model_whole():
    cfg = config_file()
    assert cfg["source"] == "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    assert cfg["reduced"] == [] and set(cfg["changed"]) == {"bytes"}
    assert (cfg["num_hidden_layers"], cfg["total_ut_steps"], cfg["early_exit_threshold"]) == (
        48, 4, 1)
    assert cfg["serving"] == {"max_slots": 16, "max_len": 256, "max_ongoing_requests": 1024}
    for setting in ("written_from", "attention", "initialisation", "sampling", "early_exit"):
        assert setting in cfg["assumed"], setting
    for said in ("arXiv:2510.25741", "modeling_ouro.py", "input_layernorm_2",
                 "UniversalTransformerCache", "early_exit_gate"):
        assert said in cfg["assumed"]["written_from"], said
    for promise in ("exactly max_new_tokens", "nothing is shed",
                    "every token's logits are pass 4's: no pass is skipped for any row"):
        assert promise in cfg["guarantees"], promise
    assert "one v5e chip" in cfg["deployment"] and "WHOLE" in cfg["deployment"]
    tol = cfg["reference_tolerance"]
    assert 0 < tol["rms"] < tol["max"] and 0 < tol["exit_mass_rel_max"] < 1
    assert tol["check_steps"] == 8
    for said in ("honest", "PR 63", "InternLM2", "one pass fewer", "shared"):
        assert said in tol["why"], said


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="the catalog is not installed here")
def test_every_key_of_the_catalog_row_is_kept_letter_for_letter():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    cfg = config_file()
    assert cfg["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if cfg.get(k, "absent") != v] == []
    assert (row["layers"], row["hidden_size"], row["dense_width"]) == (
        cfg["num_hidden_layers"], cfg["hidden_size"], cfg["intermediate_size"])


def test_the_program_gets_the_published_model_and_the_bytes_add_up():
    from ray_tpu.models import llama

    cfg = config_file()
    c = serve_loop.loop_config(cfg)
    assert c == llama.LlamaConfig.ouro_2_6b(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    tree = jax.eval_shape(lambda: llama.init(jax.random.key(0), c))
    held = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert held == loop_cost.held_params(cfg) == 2_667_974_657
    in_blocks = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree["blocks"]))
    assert in_blocks == loop_cost.blocks_params(cfg) == 2_466_643_968
    assert loop_cost.block_params(cfg) == 16_777_216 + 34_603_008 + 8_192 == 51_388_416
    assert loop_cost.head_params(cfg) == 100_663_296
    for number in ("51,388,416", "2,466,643,968", "201,326,592", "2,667,974,657",
                   "1,572,864", "6,442,450,944", "11.78 GB"):
        assert number in cfg["changed"]["bytes"], number
    cache = jax.eval_shape(lambda: llama.init_cache(c, 16, 256))
    size = {k: int(np.prod(v.shape)) * v.dtype.itemsize for k, v in cache.items()}
    assert loop_cost.cache_layers(cfg) == cache["k"].shape[0] == 192
    assert loop_cost.cache_bytes_per_token(cfg) == 192 * 2 * 2048 * 2 == 1_572_864
    assert size["k"] + size["v"] == 16 * 256 * 1_572_864 == 6_442_450_944
    # weights and K/V: 74% of the chip's 16 GB
    assert 11.7e9 < 2 * held + size["k"] + size["v"] < 11.8e9


def test_loop_cost_by_hand():
    cfg = config_file()
    # a step at 16 rows of 160 keys: the blocks four times, the head once, K/V
    visible = 192 * 16 * 160
    kv = (visible + 192 * 16) * 8192
    assert loop_cost.attention_bytes(visible, 16, cfg) == kv == 4_051_697_664
    assert loop_cost.weight_bytes(cfg) == 4 * 4_933_287_936 + 201_326_592
    assert loop_cost.step_bytes(cfg, visible, 16) == 4 * 4_933_287_936 + 201_326_592 + kv
    # ISSUE 63's reckoning: 23.9 GB, 29 ms at 819 GB/s; the looped weights 83% of it
    step = loop_cost.step_bytes(cfg, visible, 16)
    assert 23.8e9 < step < 24.1e9 and 29.0 < step / 819e9 * 1e3 < 29.5
    assert 0.81 < 4 * 4_933_287_936 / step < 0.84


def test_my_benchmark_entries_are_there_by_name():
    bench = contract.load_benchmark()
    assert contract.check_benchmark(bench) == []
    entry = contract.config_entry(bench, CONFIG)
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["reduced"] == [] and entry["source"] == config_file()["source"]
    cell = contract.cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, TRAFFIC, 1)
    for said in ("32 clients on 16 slots", "64/128", "128 new", "max_len 256", "4 times", "192"):
        assert said in cell["why"], said
    assert len(cell["why"]) <= 200
    assert [w["config"] for w in bench["workloads"]].count(CONFIG) == 1
    tokens = next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert CELL in tokens["workloads"]
    setup = [m for m in bench["per_layer"] if m["name"].startswith("setup_")]
    assert all(CELL in m["workloads"] for m in setup) and len(setup) == 6
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in MINE:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
        # a file of the entry's FULL name, beside the other cells' readers
        assert contract.reader_path(name).endswith(os.sep + name + ".py")
    assert [by_name[n]["source"] for n in MINE] == ["device_trace"] * 3 + ["program_counter"]
    assert by_name[MINE[0]]["layer"] == "model step (models/llama.py)"
    assert by_name[MINE[1]]["layer"] == by_name[MINE[2]]["layer"] == by_name[
        "full_attn_time_share.mimo"]["layer"]
    assert (by_name[MINE[3]]["unit"], by_name[MINE[3]]["better"]) == ("count", "lower")
    # the other whole-step readers are their own cells' files still
    assert contract.reader_path("step_hbm_roofline_share.olmoh").endswith(
        "step_hbm_roofline_share.py")
    assert contract.reader_path("step_hbm_roofline_share.solar").endswith(
        "step_hbm_roofline_share.solar.py")
    assert ({m["name"] for m in setup} | set(MINE) | set(GENERIC)) <= set(
        contract.declared_metrics(bench, CELL, 1))
    for name in GENERIC:
        assert len(by_name[name]["workloads"]) > 1 and CELL in by_name[name]["workloads"]
    assert set(contract.declared_metrics(bench, CELL, 0)) == {"serve_tokens_per_s", "setup_s"}


def test_the_traffic_is_the_issues():
    with open(os.path.join(contract.ROOT, "chipbench", "traffic", TRAFFIC + ".json")) as f:
        t = json.load(f)
    assert (t["job"], t["loop"], t["clients"], t["requests_per_client"]) == (
        "serve_loop", "closed", 32, 12)
    assert t["prompt_len"] == {"kind": "cycle", "values": [64, 128]}
    assert t["new_tokens"] == {"kind": "fixed", "value": 128}
    assert t["stagger"] == {"step": 8, "over": 16} and t["drain_s"] == 0
    assert (t["ramp_s"], t["trace_at_s"], t["trace_for_s"]) == (4, 6, 3)
    assert "population_seed" in t and "reasoning" in t["what"]
    serving = config_file()["serving"]
    assert t["clients"] == 2 * serving["max_slots"]              # a slot never waits
    assert 128 + 128 == serving["max_len"]
    assert t["stagger"]["step"] * t["stagger"]["over"] == 128
    from chipbench import loadgen
    from chipbench.jobs import serve_hybrid

    reqs = serve_hybrid._InTurn(loadgen).schedule(t, 2147486363, 40.0, serving["max_len"])
    assert [r.prompt_len for r in reqs[:4]] == [64, 128, 64, 128]
    assert [r.new_tokens for r in reqs[:16]] == list(range(8, 129, 8))
    assert {r.new_tokens for r in reqs[32:]} == {128}


# ---- the scope map and the readers -------------------------------------------

def hlo(program, lines):
    body = "\n".join(
        f'  %{name} = f32[8] fusion(%p0), metadata={{op_name="jit({program})/while/body/'
        f'closed_call/{path}"}}' for name, path in lines)
    return f"HloModule jit_{program}\n{body}\n  ROOT %tuple.9 = (f32[8]) tuple(%p0)\n"


DECODE = hlo("decode_step_rowwise", [
    ("fusion.1", "loop_pass/decode_attn/dot_general"),
    ("fusion.2", "loop_pass/decode_attn/loop_attn/kv_decode"),
    ("fusion.3", "loop_pass/decode_mlp/dot_general"),
    ("fusion.4", "loop_pass/cond/branch_1_fun/reduce_sum")])
PREFILL = hlo("prefill_into_slot", [
    ("fusion.5", "loop_pass/decode_attn/loop_attn/flash_attention"),
    ("fusion.6", "loop_pass/decode_mlp/dot_general")])


def plane(ops, modules):
    return {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops},
    ]}


def test_the_loop_is_found_by_its_scopes_and_split_by_pass():
    dec, pre = loop_trace.version(DECODE), loop_trace.version(PREFILL)
    assert dec["scopes"]["loop_attn"] == ["fusion.2"]
    assert dec["scopes"]["loop_pass"] == ["fusion.1", "fusion.2", "fusion.3", "fusion.4"]
    assert pre["scopes"]["loop_attn"] == ["fusion.5"]
    # one decode execution of 2 passes x 3 layers: each layer 10 + 20 + 30 ns,
    # pass 1's layers twice as slow; the pass's end (fusion.4) 5 ns
    ops, t = [], 100
    for p in range(2):
        for _layer in range(3):
            for name, d in (("fusion.1", 10), ("fusion.2", 20), ("fusion.3", 30)):
                ops.append((f"{name} = fusion", t, d * (p + 1), {}))
                t += d * (p + 1)
        ops.append(("fusion.4 = fusion", t, 5, {}))
        t += 5
    decode_end = t
    ops += [("fusion.5 = fusion", t + 100, 40, {}), ("fusion.6 = fusion", t + 140, 60, {})]
    modules = [("jit_decode_step_rowwise(7)", 100, decode_end - 100, {}),
               ("jit_prefill_into_slot(9)", t + 100, 100, {})]
    got = loop_trace.reduce([plane(ops, modules)],
                            {"decode_step_rowwise": [dec], "prefill_into_slot": [pre, pre]},
                            [64, 128], 2)
    assert got["decode_executions_traced"] == 1 and got["prefill_executions_traced"] == 1
    assert got["loop_attn_decode_device_s"] == pytest.approx((3 * 20 + 3 * 40) * 1e-9)
    assert got["loop_attn_device_s"] == pytest.approx((3 * 20 + 3 * 40 + 40) * 1e-9)
    assert got["loop_pass_decode_device_s"] == pytest.approx((decode_end - 100) * 1e-9)
    assert got["loop_pass_decode_device_s_by_pass"] == pytest.approx(
        [(3 * 60 + 5) * 1e-9, (3 * 120 + 5) * 1e-9])
    assert got["prefill_tokens_traced"] == (64 + 128) / 2     # both versions fit alike
    # the other scope maps are as they were
    from chipbench import gdn_trace, kda_trace

    assert gdn_trace.SCOPES == ("gdn_proj", "gdn_step", "gdn_scan", "gdn_out")
    assert len({gdn_trace.SCOPE_FILE, kda_trace.SCOPE_FILE, loop_trace.SCOPE_FILE}) == 3


def window_facts(**kw):
    steps = 1000
    f = {"model": serve_loop.model_facts(config_file()), "max_slots": 16,
         "decode_steps_in_window": steps, "prefills_in_window": 120,
         "kv_keys_visible_step": steps * 192 * 16 * 160,
         "loop_passes": 4 * (16 * steps + 120), "loop_row_steps": 16 * steps + 120,
         "loop_attn_decode_device_s": 0.6, "decode_device_s_traced": 3.0,
         "decode_executions_traced": 80}
    f.update(kw)
    return f


def test_the_four_new_readers_on_recorded_facts():
    planes = [plane([], [("jit_decode_step_rowwise(1)", 0, 36_000_000, {}),
                         ("jit_decode_step_rowwise(1)", 40_000_000, 36_000_000, {})])]
    ctx = {"facts": window_facts(), "peak": PEAK, "planes": planes}
    cfg = config_file()
    want = loop_cost.step_bytes(cfg, 192 * 16 * 160, 16)
    assert reader(MINE[0])(ctx) == pytest.approx(100.0 * want / 819e9 / 36e-3)
    assert reader(MINE[1])(ctx) == pytest.approx(20.0)
    kv = loop_cost.attention_bytes(192 * 16 * 160, 16, cfg)
    assert reader(MINE[2])(ctx) == pytest.approx(100.0 * kv * 80 / 819e9 / 0.6)
    assert reader(MINE[3])(ctx) == 4.0
    assert all(0 < reader(name)(ctx) <= 100 for name in MINE[:3])


@pytest.mark.parametrize("name, gone", [
    (MINE[0], "kv_keys_visible_step"), (MINE[0], "model"), (MINE[1], "loop_attn_decode_device_s"),
    (MINE[2], "loop_attn_decode_device_s"), (MINE[2], "decode_executions_traced"),
    (MINE[3], "loop_passes"), (MINE[3], "loop_row_steps"),
])
def test_a_reader_that_finds_nothing_says_none(name, gone):
    """What the parent commit gives: no such scope, no such counter."""
    facts = window_facts()
    del facts[gone]
    planes = [plane([], [("jit_decode_step_rowwise(1)", 0, 36_000_000, {})])]
    assert reader(name)({"facts": facts, "peak": PEAK, "planes": planes}) is None


def test_the_whole_steps_reader_leaves_the_other_cells_facts_alone():
    """Three cells' readers answer to ``step_hbm_roofline_share.<suffix>``: on
    another model's facts this one finds nothing."""
    facts = window_facts(model={"layer_types": ["linear_attention"], "num_hidden_layers": 1})
    planes = [plane([], [("jit_decode_step_rowwise(1)", 0, 36_000_000, {})])]
    assert reader(MINE[0])({"facts": facts, "peak": PEAK, "planes": planes}) is None
    assert reader(MINE[2])({"facts": facts, "peak": PEAK, "planes": planes}) is None


def test_the_window_is_the_second_stats_call_less_the_first(tmp_path):
    def stats(steps, prefills):
        rows = 16 * steps + prefills
        return {"decode_steps_total": steps, "admitted_total": prefills,
                "rows_stepped_total": 16 * steps + 96 * prefills,
                "kv_keys_visible_step": steps * 77, "kv_keys_read_step": steps * 128,
                "loop_passes": 4 * rows, "loop_row_steps": rows, "loop_exit_mass": 0.9 * rows,
                "cache_bytes": {"k": 1}, "platform": "tpu"}

    path = tmp_path / "loop_counters.jsonl"
    check = {"check": {"exit_mass_rel": 0.01, "loop_passes_off": 0}}
    lines = [check] + [serve_loop._counted(stats(*a)) for a in ((10, 2), (110, 6), (112, 6))]
    path.write_text("".join(json.dumps(x) + "\n" for x in lines))
    got = serve_loop._window(str(path))
    assert got["decode_steps_in_window"] == 100 and got["prefills_in_window"] == 4
    assert got["loop_row_steps"] == 1604 and got["loop_passes"] == 4 * 1604
    assert got["kv_keys_visible_step"] == 7700 and "cache_bytes" not in got
    assert got["reference_exit_mass_rel"] == 0.01 and got["reference_loop_passes_off"] == 0
    path.write_text("".join(json.dumps(x) + "\n" for x in lines[:2]))
    with pytest.raises(RuntimeError, match="wrote down 1 stats"):
        serve_loop._window(str(path))


def test_a_program_without_the_loop_is_refused_at_import():
    """What the parent commit does with the new cell: the job's import fails,
    before any cluster or chip."""
    code = (
        "import dataclasses, sys\n"
        "from ray_tpu.models import hf, llama\n"
        "fields = [(f.name, f.type, f) for f in dataclasses.fields(llama.LlamaConfig)"
        " if f.name not in ('loop_passes', 'sandwich_norm')]\n"
        "llama.LlamaConfig = dataclasses.make_dataclass('LlamaConfig', fields, frozen=True)\n"
        "del hf.ouro_fields\n"
        "import chipbench.jobs.serve_loop\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=contract.ROOT)
    run = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert run.returncode != 0
    assert "cannot run a looped configuration" in run.stderr
    assert "'loop_passes', 'ouro_fields', 'sandwich_norm'" in run.stderr


# ---- the comparison that decides ``correct`` ---------------------------------

@pytest.fixture(scope="module")
def checked():
    """A toy cache's check run, as ``LoopReplica.check_reference`` makes it:
    prompts of 16 and 32 ids through the two served programs and 4 steps of
    the full batch of four, in float32."""
    from chipbench.jobs.serve_dsa import check_prompt
    from ray_tpu.models import llama

    cfg = dict(config_file(), **serve_loop.REHEARSAL_MODEL)
    cfg.update(dtype="float32", param_dtype="float32")
    config = serve_loop.loop_config(cfg)
    params = jax.jit(lambda k: llama.init(k, config))(jax.random.key(5))
    params = jax.tree.map(lambda a: a * 4 if a.ndim > 2 else a, params)
    params["exit_gate"] = {"w": params["exit_gate"]["w"] * 8, "b": jnp.asarray([0.3])}
    prompts = [check_prompt(config, 5 + r, n) for r, n in enumerate([16, 32])]
    cache, out = serve_loop.system_run(
        params, config, llama.init_cache(config, 4, 64), 4, prompts, 4)
    return params, config, cache, out


TOLERANCE = {"rms": 3e-4, "max": 3e-3, "exit_mass_rel_max": 1e-3}


def test_the_comparison_passes_honest_and_refuses_what_it_must(checked):
    params, config, cache, out = checked
    got = serve_loop.against_reference(params, config, out)
    assert [len(r["ids"]) for r in out["rows"]] == [16 + 4, 32 + 4]
    assert all(r["logits"].shape == (5, 512) for r in out["rows"])
    # the check rows lie in slots 3 and 0, the other two idle
    assert [serve_loop.slot_of(r, 4) for r in range(2)] == [3, 0]
    assert [serve_loop.slot_of(r, 16) for r in range(2)] == [3, 8]
    k = np.asarray(cache["k"])
    assert k[:, 3, :20].any() and not k[:, 3, 20:].any() and not k[:, 1, 1:].any()
    assert out["calls"] == [(1, 0), (1, 0)] + [(4, 2)] * 4
    assert out["loop_passes"] == [3 * 18, 18] and got["loop_passes_off"] == 0
    assert got["exit_mass"] == pytest.approx(got["exit_mass_reference"], rel=1e-4)
    assert serve_loop.passes(got, TOLERANCE), got
    for fault in (dict(loop_passes_off=1), dict(exit_mass_rel=0.01),
                  dict(err={"rms": 1e-3, "max": 1e-3}), dict(err={"rms": 1e-4, "max": 1e-2})):
        assert not serve_loop.passes({**got, **fault}, TOLERANCE), fault


@pytest.mark.parametrize("piece, bent", [
    ("one pass fewer", dict(passes=2)),
    ("no final norm between the passes", dict(norm_between_passes=False)),
    ("pre-norm only: N2 and N4 dropped", dict(sandwich=False)),
    ("one cache layer a parameter layer, shared by the passes", dict(shared_cache=True)),
])
def test_each_piece_left_out_of_the_reference_fails_the_comparison(checked, piece, bent):
    params, config, _cache, out = checked
    off = serve_loop.against_reference(params, config, out, **bent)
    assert not serve_loop.passes(off, TOLERANCE), (piece, off)
    assert off["err"]["rms"] > 100 * TOLERANCE["rms"], (piece, off["err"])


# ---- the cell, walked on the CPU ---------------------------------------------

def walk(trace, seed):
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed", str(seed),
         "--seconds", "3", "--trace", str(trace), "--rehearse"],
        cwd=contract.ROOT, capture_output=True, text=True, timeout=160,
    )


def facts_of(stderr):
    return json.loads(next(ln for ln in stderr.splitlines()
                           if ln.startswith("[chipbench] facts: ")).split("facts: ", 1)[1])


@pytest.mark.limit(170)
def test_the_cell_walks_on_the_cpu_untraced():
    out = walk(0, 3000000063)
    assert out.returncode == 0, out.stderr[-3000:]
    line = contract.validate(contract.last_line(out.stdout), CELL, 0)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert "[serve_loop] reference check at [16, 32] + 4 steps" in out.stderr
    assert list(line)[-1] == "compared"
    assert {"rms", "max", "exit_mass_rel_max"} <= set(line["compared"])
    assert all(pair["value"] <= pair["limit"] for pair in line["compared"].values())
    facts = facts_of(out.stderr)
    for key in ("loop_passes", "loop_row_steps", "loop_exit_mass", "kv_keys_visible_step",
                "decode_steps_in_window", "prefills_in_window"):
        assert facts[key] > 0, key
    assert facts["loop_passes"] == 3 * facts["loop_row_steps"]
    assert facts["reference_loop_passes_off"] == 0 and facts["compiles_in_window"] == 0


@pytest.mark.limit(170)
def test_the_traced_walk_ends_in_a_valid_line():
    out = walk(1, 3000000064)
    assert out.returncode == 0, out.stderr[-3000:]
    line = contract.validate(contract.last_line(out.stdout), CELL, 1)
    assert line["correct"] and line["failed"] == 0
    assert set(MINE) | set(GENERIC) <= set(line["metrics"])
    silent = [ln.split("rehearsal: ")[1].split(" found")[0]
              for ln in out.stderr.splitlines() if "found nothing to read" in ln]
    # the whole step's reader and the counter's read on the CPU too; the
    # scope's two have no device plane here
    assert set(silent) & set(MINE) <= set(NEED_A_DEVICE_PLANE)
    # (the three ``step_*_ms_p50.batch`` align spans with the host's plane to
    # 2 ms, which a toy step of 4 ms beside five busy test workers does not
    # always allow: their own tests hold them)
    for name in ("compiles_in_window.batch", "host_stall_share.batch",
                 "host_stall_outside_share.batch"):
        assert name not in silent, name
    # the traced steps copy the cache's loop totals for their spans with a
    # program compiled at set-up: nothing compiles inside the window
    assert line["metrics"]["compiles_in_window.batch"]["value"] == 0
    assert line["metrics"]["loop_passes_per_row_step.ouro"]["value"] == 3.0
