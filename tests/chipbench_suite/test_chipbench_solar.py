"""The Solar-Open2 cell's benchmark side: the configuration file against the
catalog row it was cut from and its byte arithmetic (``jax.eval_shape`` of the
program's init, ``kda_cost`` by hand), ``BENCHMARK.json``'s entries (that mine
are there, BY NAME: never as a list's tail or as a set), the scope map and the
five new readers on hand-made planes and facts, the job's window arithmetic,
its refusal of a program without the per-channel rule, the comparison that
decides ``correct`` on a toy cache — honest, and with each piece of the
mathematics left out of the reference — and the cell walked on the CPU through
the repo's own benchmark."""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import contract, kda_cost, kda_trace
from chipbench.jobs import serve_kda, serve_swa

CELL = "serve_solar_longdoc_batch"
CONFIG = "solar-open2-ep8-l4"
TRAFFIC = "kda_longdoc_closed128"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
MINE = ("kda_step_time_share.solar", "kda_step_hbm_roofline_share.solar",
        "kda_scan_time_share.solar", "kda_scan_roofline_share.solar",
        "step_hbm_roofline_share.solar")
GENERIC = (
    "decode_step_device_ms_p50.batch", "prefill_device_ms_p50.batch",
    "decode_batch_occupancy.batch", "device_idle_share.batch", "compiles_in_window.batch",
    "step_dispatch_ms_p50.batch", "step_deliver_ms_p50.batch",
    "step_serve_plane_ms_p50.batch", "gmm_time_share", "gmm_hbm_roofline_share",
    "moe_experts_touched_mean", "moe_held_assignment_share",
    "host_stall_share.batch", "host_stall_outside_share.batch",
)
#: of those, the ones a CPU walk can read (the others need a device plane)
ON_THE_CPU = ("compiles_in_window.batch", "moe_held_assignment_share",
              "moe_experts_touched_mean", "step_dispatch_ms_p50.batch",
              "step_deliver_ms_p50.batch", "step_serve_plane_ms_p50.batch",
              "host_stall_share.batch", "host_stall_outside_share.batch")


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

def test_the_configuration_states_its_cut():
    cfg = config_file()
    assert cfg["source"] == (
        "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json")
    assert cfg["reduced"] == REDUCED and len(cfg["source"]) <= 200
    assert set(cfg["changed"]) == set(REDUCED) | {"bytes"}
    assert (cfg["num_hidden_layers"], cfg["num_hidden_layers_published"]) == (4, 48)
    assert (cfg["n_routed_experts"], cfg["n_routed_experts_published"]) == (40, 320)
    assert (cfg["vocab_size"], cfg["vocab_size_published"]) == (24576, 196608)
    assert cfg["chips_sharing_a_layer"] * cfg["n_routed_experts"] == 320
    # one whole period: the gated GQA layer and the three KDA layers behind it
    assert kda_cost.kinds(cfg) == [kda_cost.FULL] + [kda_cost.LINEAR] * 3
    assert cfg["gqa_layers"] == list(range(0, 48, 4))
    assert cfg["serving"] == {"max_slots": 64, "max_len": 16896, "max_ongoing_requests": 1024,
                              "linear_chunk": 64, "linear_segment": 1024}
    for setting in ("written_from", "block", "kda", "gqa", "moe", "router_bias",
                    "initialisation", "state_dtype", "chunk", "sampling"):
        assert setting in cfg["assumed"], setting
    assert "sigmoid scores" in cfg["assumed"]["moe"] and "1,280" in cfg["assumed"]["moe"]
    assert "elementwise" in cfg["assumed"]["gqa"] and "pre-norm" in cfg["assumed"]["block"]
    for promise in ("exactly max_new_tokens", "held expert is computed", "nothing is shed",
                    "every earlier key", "zero state", "added once"):
        assert promise in cfg["guarantees"], promise
    assert "8 chips" in cfg["deployment"] and "rank 0" in cfg["deployment"]
    tol = cfg["reference_tolerance"]
    assert 0 < tol["rms"] < tol["max"] and 0 < tol["swap_rate_max"] < 1
    assert tol["state_low_bits_min"] == 0.5 and tol["check_steps"] >= 4
    assert "honest" in tol["why"] and "PR 59" in tol["why"]


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="the catalog is not installed here")
def test_every_number_of_the_catalog_row_is_kept_or_listed():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Solar-Open2-250B")
    cfg = config_file()
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k, "absent") != v]
    assert sorted(differs) == sorted(cfg["reduced"]) == sorted(REDUCED)
    for width in ("hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim",
                  "num_attention_heads", "num_key_value_heads", "num_experts_per_tok",
                  "linear_attn_config"):
        assert cfg[width] == row["config"][width], width


def test_the_program_gets_the_published_widths_and_the_bytes_add_up():
    from ray_tpu.models import llama

    cfg = config_file()
    c = serve_kda.kda_config(cfg)
    assert c == llama.LlamaConfig.solar_open2(
        num_layers=4, vocab_size=24576, experts_held=40, rms_eps=1e-5,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, linear_segment=1024)
    tree = jax.eval_shape(lambda: llama.init(jax.random.key(0), c))
    held = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert held == kda_cost.held_params(cfg) == 3_308_353_344
    for number in ("3,308,353,344", "137,732,288", "109,051,904", "17,047,872",
                   "629,145,600", "201,326,592", "6.62 GB", "5.26 GB"):
        assert number in cfg["changed"]["bytes"], number
    cache = jax.eval_shape(lambda: llama.init_cache(c, 64, 16896))
    size = {k: int(np.prod(v.shape)) * v.dtype.itemsize for k, v in cache.items()}
    want = kda_cost.cache_bytes(cfg, 64, 16896)
    assert size["k"] + size["v"] == want["kv"] == 4_429_185_024
    assert size["gdn_state"] == want["state"] == 805_306_368
    assert size["gdn_conv"] == want["conv"] == 28_311_552
    # by hand
    assert kda_cost.kda_params(cfg) == (
        4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64 + 4 * 24576
        + 64 + 8192 + 128) == 137_732_288
    assert kda_cost.gqa_params(cfg) == 3 * 4096 * 8192 + 2 * 4096 * 1024 == 109_051_904
    assert kda_cost.ffn_fixed_params(cfg) == 4096 * 320 + 320 + 15_728_640 + 8192
    assert kda_cost.state_bytes(cfg) == 3 * 64 * 128 * 128 * 4


def test_my_benchmark_entries_are_there_by_name():
    bench = contract.load_benchmark()
    assert contract.check_benchmark(bench) == []
    entry = contract.config_entry(bench, CONFIG)
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["reduced"] == REDUCED and entry["source"] == config_file()["source"]
    cell = contract.cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, TRAFFIC, 1)
    for said in ("1/8", "1.6", "12.8", "full share", "4/48 layers", "12x"):
        assert said in cell["why"], said
    assert len(cell["why"]) <= 200
    assert [w["config"] for w in bench["workloads"]].count(CONFIG) == 1
    tokens = next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert CELL in tokens["workloads"]
    setup = [m for m in bench["per_layer"] if m["name"].startswith("setup_")]
    assert len(setup) == 6 and all(CELL in m["workloads"] for m in setup)
    names = [m["name"] for m in bench["per_layer"]]
    at = [names.index(name) for name in MINE]
    assert at == sorted(at)
    for name in MINE:
        m = bench["per_layer"][names.index(name)]
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
        assert (m["unit"], m["better"], m["source"]) == ("%", "higher", "device_trace")
        assert m["layer"] == "model step (models/llama.py)"
        # a file of the entry's FULL name: the suite's made-up thirteenth cell
        # brings readers named ``kda_step_time_share`` .. of its own (and its
        # fixture writes them THROUGH a link of that name into this folder), and
        # two entries that move one metric may not share a reader file
        assert contract.reader_path(name).endswith(os.sep + name + ".py")
    # the whole step's reader is this cell's own file, not Olmo-Hybrid's
    assert contract.reader_path(MINE[-1]).endswith("step_hbm_roofline_share.solar.py")
    assert contract.reader_path("step_hbm_roofline_share.olmoh").endswith(
        "step_hbm_roofline_share.py")
    assert ({m["name"] for m in setup} | set(MINE) | set(GENERIC)) <= set(
        contract.declared_metrics(bench, CELL, 1))
    for name in GENERIC:
        m = bench["per_layer"][names.index(name)]
        assert len(m["workloads"]) > 1 and m["moves"] == "serve_tokens_per_s"
    assert set(contract.declared_metrics(bench, CELL, 0)) == {"serve_tokens_per_s", "setup_s"}


def test_the_traffic_is_the_issues():
    with open(os.path.join(contract.ROOT, "chipbench", "traffic", TRAFFIC + ".json")) as f:
        t = json.load(f)
    assert (t["job"], t["loop"], t["clients"], t["requests_per_client"]) == (
        "serve_kda", "closed", 128, 4)
    assert t["prompt_len"] == {"kind": "cycle", "values": [4096, 16384]}
    assert t["new_tokens"] == {"kind": "fixed", "value": 512}
    assert t["stagger"] == {"step": 8, "over": 64} and t["drain_s"] == 0
    assert "population_seed" in t and "long reports" in t["what"]
    serving = config_file()["serving"]
    assert t["clients"] == 2 * serving["max_slots"]              # a slot never waits
    assert 16384 + 512 == serving["max_len"]
    assert t["stagger"]["step"] * t["stagger"]["over"] == 512


# ---- the cost functions, by hand ---------------------------------------------

def test_kda_cost_against_hand_counts():
    cfg = config_file()
    H, d = 64, 128
    assert kda_cost.layers(cfg, kda_cost.LINEAR) == 3 and kda_cost.layers(cfg, kda_cost.FULL) == 1
    # a token and a KDA layer: half a chunk's pairs five times over d, three
    # state-sized products
    assert kda_cost.scan_flops(cfg, 1.0, 64) == 2.0 * (32 * 5 * d + 3 * d * d) * H
    assert kda_cost.scan_bytes(cfg, 1.0) == 4.0 * (5 * d + 1) * H
    fixed = kda_cost.fixed_params(cfg)
    assert fixed == 3 * 137_732_288 + 109_051_904 + 4 * 17_047_872 + 4096 + 24576 * 4096
    step = kda_cost.step_bytes(cfg, 30.0, 64 * 10_000.0, 64)
    assert step == (2 * fixed + 2 * 4 * 30.0 * 15_728_640 + (640_000.0 + 64) * 4096
                    + 2 * 64 * (12_582_912 + 3 * 3 * 24576 * 2))
    # ISSUE 59's reckoning: ~12 ms at 819 GB/s
    assert 9.0 < step / 819e9 * 1e3 < 13.0


def hlo(program, lines):
    body = "\n".join(
        f'  %{name} = f32[8] fusion(%p0), metadata={{op_name="jit({program})/while/body/'
        f'closed_call/{path}"}}' for name, path in lines)
    return f"HloModule jit_{program}\n{body}\n  ROOT %tuple.9 = (f32[8]) tuple(%p0)\n"


DECODE = hlo("decode_step_rowwise", [
    ("fusion.1", "decode_attn/kda_proj/dot_general"),
    ("fusion.2", "decode_attn/kda_step/kda_step"),
    ("fusion.3", "decode_attn/kda_out/dot_general"),
    ("fusion.4", "decode_mlp/moe_experts/gmm")])
PREFILL = hlo("prefill_into_slot", [
    ("fusion.5", "decode_attn/kda_proj/dot_general"),
    ("fusion.6", "decode_attn/while/body/kda_scan/dot_general"),
    ("fusion.7", "decode_attn/while/body/kda_scan/while/body/dot_general")])


def plane(ops, modules):
    return {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops},
    ]}


def test_the_rule_is_found_by_its_scopes():
    dec, pre = kda_trace.version(DECODE), kda_trace.version(PREFILL)
    assert dec["scopes"]["kda_step"] == ["fusion.2"] and dec["scopes"]["kda_proj"] == ["fusion.1"]
    assert pre["scopes"]["kda_scan"] == ["fusion.6", "fusion.7"]
    ops = [(f"fusion.{i} = fusion", 1000 * i, 100 * i, {}) for i in range(1, 8)]
    modules = [("jit_decode_step_rowwise(7)", 0, 4500, {}),
               ("jit_prefill_into_slot(9)", 4900, 3000, {})]
    got = kda_trace.reduce([plane(ops, modules)],
                           {"decode_step_rowwise": [dec], "prefill_into_slot": [pre, pre]},
                           [4096, 16384])
    assert got["decode_executions_traced"] == 1 and got["prefill_executions_traced"] == 1
    assert got["kda_step_decode_device_s"] == pytest.approx(200e-9)
    assert got["kda_scan_device_s"] == pytest.approx(1300e-9)
    assert got["prefill_tokens_traced"] == (4096 + 16384) / 2     # both versions fit alike
    # the scalar rule's scopes are as they were
    from chipbench import gdn_trace

    assert gdn_trace.SCOPES == ("gdn_proj", "gdn_step", "gdn_scan", "gdn_out")
    assert gdn_trace.SCOPE_FILE == "gdn_scopes.json" != kda_trace.SCOPE_FILE


def window_facts(**kw):
    steps, prefills = 1500, 40
    f = {"model": serve_kda.model_facts(config_file()), "max_slots": 64, "linear_chunk": 64,
         "decode_steps_in_window": steps, "prefills_in_window": prefills,
         "moe_layer_steps": 4 * (steps + prefills),
         # steps that touch 31 experts a layer, prefills that touch all 40
         "moe_experts_touched_mean": (steps * 31 + prefills * 40) / (steps + prefills),
         "kv_keys_visible_step": steps * 64 * 10_000,
         "gdn_state_bytes_step": steps * 2 * 64 * 12_582_912,
         "kda_step_decode_device_s": 0.3, "decode_device_s_traced": 1.5,
         "kda_scan_device_s": 0.9, "prefill_device_s_traced": 1.2,
         "prefill_tokens_traced": 4096.0 + 16384.0, "decode_executions_traced": 120}
    f.update(kw)
    return f


def test_the_five_new_readers_on_recorded_facts():
    planes = [plane([], [("jit_decode_step_rowwise(1)", 0, 14_000_000, {}),
                         ("jit_decode_step_rowwise(1)", 20_000_000, 14_000_000, {})])]
    ctx = {"facts": window_facts(), "peak": PEAK, "planes": planes}
    assert reader(MINE[0])(ctx) == pytest.approx(20.0)
    state = 2 * 64 * 12_582_912 * 120
    assert reader(MINE[1])(ctx) == pytest.approx(100.0 * state / 819e9 / 0.3)
    assert reader(MINE[2])(ctx) == pytest.approx(75.0)
    cfg = config_file()
    need = max(kda_cost.scan_flops(cfg, 3 * 20480.0) / 197e12,
               kda_cost.scan_bytes(cfg, 3 * 20480.0) / 819e9)
    assert reader(MINE[3])(ctx) == pytest.approx(100.0 * need / 0.9)
    want = kda_cost.step_bytes(cfg, 31.0, 64 * 10_000.0, 64)
    assert reader(MINE[4])(ctx) == pytest.approx(100.0 * want / 819e9 / 14e-3)
    assert all(0 < reader(name)(ctx) <= 100 for name in MINE)


@pytest.mark.parametrize("name, gone", [
    (MINE[0], "kda_step_decode_device_s"), (MINE[1], "gdn_state_bytes_step"),
    (MINE[2], "kda_scan_device_s"), (MINE[3], "prefill_tokens_traced"),
    (MINE[4], "kv_keys_visible_step"), (MINE[4], "model"),
])
def test_a_reader_that_finds_nothing_says_none(name, gone):
    """What the parent commit gives: no such scope, no such counter."""
    facts = window_facts()
    del facts[gone]
    planes = [plane([], [("jit_decode_step_rowwise(1)", 0, 14_000_000, {})])]
    assert reader(name)({"facts": facts, "peak": PEAK, "planes": planes}) is None


def test_the_whole_steps_reader_leaves_olmo_hybrids_facts_alone():
    """Both cells' readers answer to ``step_hbm_roofline_share.<suffix>``: on
    the other model's facts this one finds nothing."""
    facts = window_facts(model={"layer_types": ["linear_attention"], "num_hidden_layers": 1})
    planes = [plane([], [("jit_decode_step_rowwise(1)", 0, 14_000_000, {})])]
    assert reader(MINE[4])({"facts": facts, "peak": PEAK, "planes": planes}) is None


def test_the_window_is_the_second_stats_call_less_the_first():
    def stats(steps, prefills):
        return {"moe_expert_tokens": [[steps * 3] * 4] * 4, "moe_layer_steps_total": 4 * steps,
                "moe_experts_touched_total": 4 * steps * 3, "moe_routed_pairs_total": steps * 400,
                "moe_held_pairs_total": steps * 48, "moe_rows_gathered_total": steps * 1024,
                "decode_steps_total": steps, "admitted_total": prefills,
                "gdn_rows_stepped": steps * 12, "gdn_tokens_scanned": prefills * 300,
                "gdn_tokens_padded": 0, "gdn_state_bytes_step": steps * 1000,
                "kv_keys_visible_step": steps * 77, "kv_keys_read_step": steps * 128,
                "gated_delta_step": "in_place"}

    got = serve_kda._window(stats(10, 2), stats(110, 6), None)
    assert got["decode_steps_in_window"] == 100 and got["prefills_in_window"] == 4
    assert got["moe_assignments"] == 100 * 48 and got["moe_dropped"] == 0
    assert got["moe_held_assignment_share"] == pytest.approx(12.0)
    assert got["gdn_state_bytes_step"] == 100_000 and got["kv_keys_visible_step"] == 7700
    assert got["gated_delta_step"] == "in_place"
    with pytest.raises(RuntimeError, match="no prefill of a recurrent layer"):
        serve_kda._window(stats(10, 2), dict(stats(110, 2), gdn_tokens_scanned=600), None)


def test_a_program_without_the_rule_is_refused_at_import():
    """What the parent commit does with the new cell: the job's import fails,
    before any cluster or chip."""
    code = (
        "import dataclasses, sys\n"
        "from ray_tpu.models import hf, llama\n"
        "fields = [(f.name, f.type, f) for f in dataclasses.fields(llama.LlamaConfig)"
        " if f.name not in ('linear_kind', 'linear_gate_rank', 'attn_output_gate')]\n"
        "llama.LlamaConfig = dataclasses.make_dataclass('LlamaConfig', fields, frozen=True)\n"
        "del hf.solar_open2_fields\n"
        "import chipbench.jobs.serve_kda\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=contract.ROOT)
    run = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert run.returncode != 0
    assert "cannot run a configuration with Kimi-delta-attention layers" in run.stderr
    assert "'attn_output_gate', 'linear_gate_rank', 'linear_kind', 'solar_open2_fields'" in run.stderr


# ---- the comparison that decides ``correct`` ---------------------------------

@pytest.fixture(scope="module")
def checked():
    """A toy cache's check run, as ``KdaReplica.check_reference`` makes it: the
    two served programs, their twin, the counter between — prompts of 16 and
    32 ids (two and four segments of eight) and 6 steps."""
    from ray_tpu.models import llama

    cfg = dict(config_file(), **serve_kda.REHEARSAL_MODEL)
    cfg.update(dtype="float32", param_dtype="float32",
               serving=dict(cfg["serving"], linear_chunk=4, linear_segment=8))
    config = serve_kda.kda_config(cfg)
    params = jax.jit(lambda k: llama.init(k, config))(jax.random.key(5))
    params = jax.tree.map(lambda a: a * 4 if a.ndim > 2 else a, params)
    prompts = [serve_kda.serve_dsa.check_prompt(config, 5 + r, n)
               for r, n in enumerate([16, 32])]
    cache, out = serve_kda.system_run(
        params, config, llama.init_cache(config, 4, 64), 4, prompts, 6)
    return params, config, cache, out


TOLERANCE = {"rms": 3e-4, "max": 3e-3, "swap_rate_max": 0.01, "swapped_margin_max": 1e-5,
             "twin_rms": 1e-5, "twin_max": 1e-4, "served_pairs_off_max": 0,
             "state_low_bits_min": 0.5}


@pytest.mark.limit(170)
def test_the_comparison_passes_honest_and_refuses_what_it_must(checked):
    params, config, cache, out = checked
    got = serve_kda.against_reference(params, config, out)
    assert [len(r["seq"]) for r in out["rows"]] == [16 + 6, 32 + 6]
    assert all(r["logits"].shape == (7, 512) and r["experts"].shape == (4, len(r["seq"]), 2)
               for r in out["rows"])
    assert got["twin_pairs_miscounted"] == 0 and got["served_pairs_off"] == 0
    assert got["held_choices"] > 0 and got["state_low_bits"] > 0.99
    assert serve_kda.passes(got, TOLERANCE), got
    for fault in (dict(twin_pairs_miscounted=1), dict(held_choices=0), dict(swap_rate=0.02),
                  dict(err={"rms": 1e-3, "max": 1e-3}), dict(state_low_bits=0.0)):
        assert not serve_kda.passes({**got, **fault}, TOLERANCE), fault
    # the empty rows remember: without the clearing between the two passes they
    # route otherwise in the second, and the counters say so
    prompts = [row["prompt"] for row in out["rows"]]
    from ray_tpu.models import llama
    _, dirty = serve_swa.system_run(
        params, config, llama.init_cache(config, 4, 64), 4, prompts, 6)
    assert dirty["served_pairs_off"] > 0 and dirty["twin_pairs_miscounted"] == 0
    # a state that passed through bfloat16 has no low bits left
    rounded = dict(cache, gdn_state=cache["gdn_state"].astype(jnp.bfloat16).astype(jnp.float32))
    assert serve_kda.state_low_bits(rounded, 2) == 0.0


@pytest.mark.limit(170)
@pytest.mark.parametrize("piece, bent", [
    ("no output gate on the GQA layer", dict(gqa_gate=False)),
    ("beta under 1", dict(neg_eigval=False)),
    ("one decay a head", dict(channel_decay=False)),
    ("no shared expert", dict(shared_expert=False)),
    ("the reference's state through bfloat16", dict(state_dtype="bfloat16")),
])
def test_each_piece_left_out_of_the_reference_fails_the_comparison(checked, piece, bent):
    params, config, _cache, out = checked
    off = serve_kda.against_reference(params, config, out, **bent)
    assert not serve_kda.passes(off, TOLERANCE), (piece, off["err"])


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
    out = walk(0, 3000000059)
    assert out.returncode == 0, out.stderr[-3000:]
    line = contract.validate(contract.last_line(out.stdout), CELL, 0)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert "[serve_kda] reference check at [16, 32] + 4 steps" in out.stderr
    assert list(line)[-1] == "compared" and {"rms", "max", "swap_rate_max"} <= set(line["compared"])
    assert all(pair["value"] <= pair["limit"] for pair in line["compared"].values())
    facts = facts_of(out.stderr)
    for key in ("gdn_rows_stepped", "gdn_tokens_scanned", "gdn_state_bytes_step",
                "kv_keys_visible_step", "moe_held_assignment_share",
                "decode_steps_in_window", "prefills_in_window"):
        assert facts[key] > 0, key
    assert facts["moe_dropped"] == 0 and facts["gated_delta_step"] == "xla"


@pytest.mark.limit(170)
def test_the_traced_walk_ends_in_a_valid_line():
    out = walk(1, 3000000060)
    assert out.returncode == 0, out.stderr[-3000:]
    line = contract.validate(contract.last_line(out.stdout), CELL, 1)
    assert line["correct"] and line["failed"] == 0
    assert set(MINE) | set(GENERIC) <= set(line["metrics"])
    silent = [ln.split("rehearsal: ")[1].split(" found")[0]
              for ln in out.stderr.splitlines() if "found nothing to read" in ln]
    # the whole step's reader reads on the CPU too (module times of the host's
    # plane); the scopes' have no device plane here
    assert MINE[4] not in silent
    for name in ON_THE_CPU:
        assert name not in silent, name
    assert line["metrics"]["compiles_in_window.batch"]["value"] == 0
    assert 0 < line["metrics"]["moe_held_assignment_share"]["value"] < 100
