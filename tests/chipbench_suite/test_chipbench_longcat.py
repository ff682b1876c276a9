"""The LongCat-Flash cell's benchmark side: the configuration file against
the catalog row it was cut from and its byte arithmetic, ``BENCHMARK.json``'s
new entries (that mine are there, by name and in this order), ``scmoe_cost``
by hand, the two new readers and the scope map on hand-made planes and
facts, the job's window arithmetic, its refusal of a program without the
fields, the comparison that decides ``correct`` on a toy cache, and the
cell walked on the CPU — traced with the generic readers it joined when the
list got room (PR 52: one entry for each quantity under a judged metric)."""

import importlib
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import contract, scmoe_cost, scmoe_trace
from chipbench import trace_reduce as tr
from chipbench.jobs import serve_scmoe

CELL = "serve_longcat_agent_batch"
CONFIG = "longcat-flash-omni-ep32-l4"
TRAFFIC = "scmoe_agent_closed128"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_layers", "n_routed_experts", "vocab_size"]
#: the cell's per-layer entries, in the order they were appended
MINE = ("scmoe_step_hbm_roofline_share.lcat", "moe_zero_choice_share.lcat")
#: the accepted entries the cell joined at PR 52 (the last two at PR 58), by a
#: data edit alone: their readers' facts are what the job has supplied since PR 49
GENERIC = (
    "decode_step_device_ms_p50.batch", "prefill_device_ms_p50.batch",
    "decode_batch_occupancy.batch", "device_idle_share.batch", "compiles_in_window.batch",
    "gmm_time_share", "gmm_hbm_roofline_share", "mla_attn_time_share",
    "mla_attn_hbm_roofline_share", "moe_held_assignment_share",
    "moe_experts_touched_mean", "step_dispatch_ms_p50.batch",
    "step_deliver_ms_p50.batch", "step_serve_plane_ms_p50.batch",
    # and at PR 58, beside ``device_idle_share.batch`` (their readers ask the GCS)
    "host_stall_share.batch", "host_stall_outside_share.batch",
)
#: of those, the ones a CPU walk can read (the others need a device plane)
ON_THE_CPU = ("compiles_in_window.batch", "moe_held_assignment_share",
              "moe_experts_touched_mean", "step_dispatch_ms_p50.batch",
              "step_deliver_ms_p50.batch", "step_serve_plane_ms_p50.batch",
              "host_stall_share.batch", "host_stall_outside_share.batch")


def config_file():
    with open(os.path.join(contract.ROOT, "chipbench", "configs", CONFIG + ".json")) as f:
        return json.load(f)


def reader(metric):
    path = contract.reader_path(metric)
    spec = importlib.util.spec_from_file_location("reader_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---- the configuration and the cell -----------------------------------------

def test_the_configuration_states_its_cut():
    cfg = config_file()
    assert cfg["source"] == (
        "https://huggingface.co/meituan-longcat/LongCat-Flash-Omni/blob/main/config.json")
    assert cfg["reduced"] == REDUCED and len(cfg["source"]) <= 200
    assert set(cfg["changed"]) == set(REDUCED) | {"bytes"}
    assert "encoders" in cfg["scope"] and "codec decoder" in cfg["scope"]
    assert (cfg["num_layers"], cfg["num_layers_published"]) == (4, 28)
    assert (cfg["n_routed_experts"], cfg["n_routed_experts_published"]) == (16, 512)
    assert (cfg["vocab_size"], cfg["vocab_size_published"]) == (16384, 131072)
    assert cfg["zero_expert_num"] == 256 and cfg["moe_topk"] == 12 and cfg["expert_offset"] == 0
    assert cfg["serving"] == {"max_slots": 64, "max_len": 5120, "max_ongoing_requests": 1024}
    for setting in ("router_matrix_bias", "norm_topk_prob", "identity_experts", "hidden_act",
                    "rope", "lora_scales", "attention", "block", "head", "weights"):
        assert setting in cfg["assumed"], setting
    for promise in ("exactly max_new_tokens", "held expert is computed", "identity choice",
                    "every visible key", "nothing is shed"):
        assert promise in cfg["guarantees"], promise
    assert "32 chips share each layer" in cfg["deployment"]
    assert "pipeline stages" in cfg["deployment"] and "rank 0" in cfg["deployment"]
    tol = cfg["reference_tolerance"]
    assert 0 < tol["rms"] < tol["max"] and 0 < tol["swap_rate_max"] < 1
    assert tol["swapped_margin_max"] > 0 and tol["check_steps"] >= 4 and "honest" in tol["why"]


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="the catalog is not installed here")
def test_every_number_of_the_catalog_row_is_kept_or_listed():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LongCat-Flash-Omni")
    cfg = config_file()
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k, "absent") != v]
    assert sorted(differs) == sorted(cfg["reduced"]) == sorted(REDUCED)
    for width in ("hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
                  "num_attention_heads", "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim",
                  "qk_nope_head_dim", "v_head_dim", "moe_topk", "zero_expert_num"):
        assert cfg[width] == row["config"][width], width


def test_the_program_gets_the_published_block_and_the_bytes_add_up():
    from ray_tpu.models import llama

    cfg = config_file()
    c = serve_scmoe.scmoe_config(cfg)
    assert c == llama.LlamaConfig.longcat_flash(
        num_layers=4, vocab_size=16384, experts_held=16, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    assert (c.embed_dim, c.num_heads, c.mlp_dim, c.expert_dim) == (6144, 64, 12288, 2048)
    assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim,
            c.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (c.block_form, c.router_scoring, c.router_outputs, c.experts_per_token,
            c.router_scale, c.experts_here) == ("shortcut", "softmax", 768, 12, 6.0, 16)
    assert c.mla_scale_q_lora and c.mla_scale_kv_lora and not c.router_norm_topk
    assert (c.rope_theta, c.rms_eps, c.cache_layers, c.expert_layers) == (1e7, 1e-5, 8, 4)
    # the byte arithmetic of ``changed``: the program's tree, the cost
    # functions and the file say the same
    n = llama.num_params(c)
    assert n == scmoe_cost.held_params(cfg) == 5_172_749_312
    shapes = jax.eval_shape(lambda: llama.init(jax.random.key(0), c))
    layer = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes["blocks"])) // 4
    assert layer == scmoe_cost.layer_params(cfg) == 1_242_854_144
    for number in ("5,172,749,312", "1,242,854,144", "638,874,368", "90,585,088",
                   "226,492,416", "4,719,360", "37,748,736", "10,240", "327,680"):
        assert number in cfg["changed"]["bytes"], number
    cache = jax.eval_shape(lambda: llama.init_cache(c, 64, 5120))
    assert {"ckv", "mla_keys", "moe_expert_tokens", "moe_experts_touched",
            "moe_layer_steps", "moe_zero_choices"} <= set(cache)   # a later PR may count more
    held = math.prod(cache["ckv"].shape) * cache["ckv"].dtype.itemsize
    assert held == 64 * 5120 * scmoe_cost.cache_bytes_per_token(cfg) == 3_355_443_200
    # 86% of the chip's 16 GB live
    assert 0.85 < (2 * n + held) / 16e9 < 0.87


def test_my_benchmark_entries_are_there_by_name_and_in_this_order():
    """My entries are there, with these cells and this reader: by name and by
    order among themselves — never by position from the end: a later PR
    appends behind them."""
    bench = contract.load_benchmark()
    assert contract.check_benchmark(bench) == []
    entry = contract.config_entry(bench, CONFIG)
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["reduced"] == REDUCED and entry["source"] == config_file()["source"]
    cell = contract.cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, TRAFFIC, 1)
    assert "1/32" in cell["why"] and "4/28 layers" in cell["why"] and len(cell["why"]) <= 200
    assert [w["config"] for w in bench["workloads"]].count(CONFIG) == 1
    tokens = next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert tokens["workloads"].index(CELL) > tokens["workloads"].index("serve_olmoh_doc_batch")
    names = [m["name"] for m in bench["per_layer"]]
    at = [names.index(name) for name in MINE]
    assert at == [at[0], at[0] + 1]                               # one run, unbroken
    assert at[0] > max(i for i, n in enumerate(names) if n.endswith(".olmoh"))  # behind PR 46's
    for name in MINE:
        m = bench["per_layer"][names.index(name)]
        assert CELL in m["workloads"] and m["moves"] == "serve_tokens_per_s"
        assert m["unit"] == "%" and m["better"] == "higher"
        assert m["layer"] == "model step (models/llama.py)"
        assert contract.reader_path(name).endswith(name.rpartition(".")[0] + ".py")
    assert bench["per_layer"][at[0]]["source"] == "device_trace"
    assert bench["per_layer"][at[1]]["source"] == "program_counter"
    setup = [m for m in bench["per_layer"] if m["name"].startswith("setup_")]
    assert len(setup) == 6 and all(CELL in m["workloads"] for m in setup)
    # mine are among them: a later PR declares further quantities in this cell
    assert set(MINE) | set(GENERIC) | {m["name"] for m in setup} <= set(
        contract.declared_metrics(bench, CELL, 1))
    assert set(contract.declared_metrics(bench, CELL, 0)) == {"serve_tokens_per_s", "setup_s"}
    for name in GENERIC:
        m = bench["per_layer"][names.index(name)]
        assert CELL in m["workloads"] and len(m["workloads"]) > 1       # joined
        assert m["moves"] == "serve_tokens_per_s"


def test_the_traffic_is_the_issues():
    with open(os.path.join(contract.ROOT, "chipbench", "traffic", TRAFFIC + ".json")) as f:
        t = json.load(f)
    assert (t["job"], t["loop"], t["clients"], t["requests_per_client"]) == (
        "serve_scmoe", "closed", 128, 4)
    assert t["prompt_len"] == {"kind": "cycle", "values": [2048, 4096]}
    assert t["new_tokens"] == {"kind": "fixed", "value": 1024}
    assert t["stagger"] == {"step": 16, "over": 64} and t["drain_s"] == 0
    assert t["trace_for_s"] == 3 and "agent" in t["what"]
    serving = config_file()["serving"]
    assert t["clients"] == 2 * serving["max_slots"]              # a slot never waits
    assert 4096 + 1024 <= serving["max_len"]
    assert t["stagger"]["step"] * t["stagger"]["over"] == 1024


# ---- the cost functions, by hand ---------------------------------------------

def test_scmoe_cost_against_hand_counts():
    cfg = config_file()
    assert scmoe_cost.latent_row_values(cfg) == 640 and scmoe_cost.cache_layers(cfg) == 8
    assert scmoe_cost.cache_bytes_per_token(cfg) == 8 * 640 * 2 == 10_240
    assert scmoe_cost.router_outputs(cfg) == 768
    assert scmoe_cost.attention_params(cfg) == (
        6144 * 1536 + 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 + 2 * 512 * 64 * 128
        + 64 * 128 * 6144 + 2 * 6144) == 90_585_088
    assert scmoe_cost.dense_params(cfg) == 3 * 6144 * 12288 == 226_492_416
    assert scmoe_cost.router_params(cfg) == 6144 * 768 + 768 == 4_719_360
    assert scmoe_cost.expert_params(cfg) == 3 * 6144 * 2048 == 37_748_736
    assert scmoe_cost.layer_fixed_params(cfg) == 638_874_368
    assert scmoe_cost.fixed_params(cfg) == 4 * 638_874_368 + 6144 + 16384 * 6144 == 2_656_166_912
    assert scmoe_cost.held_params(cfg) == (
        2_656_166_912 + 4 * 16 * 37_748_736 + 16384 * 6144) == 5_172_749_312
    assert scmoe_cost.rows_written(cfg, 1, 64) == 64 * 8
    # a step of 64 rows at 3,500 keys that touches 10 of 16 experts a layer:
    # 5.3 GB of fixed weights, 3.0 GB of experts, 2.3 GB of latent rows
    visible = 8 * 64 * 3500
    step = scmoe_cost.step_bytes(cfg, 40, visible, 64 * 8)
    assert step == 2 * (2_656_166_912 + 40 * 37_748_736) + (visible + 512) * 1280
    assert 10.5e9 < step < 10.8e9
    # 64 rows: 64 FLOP a fixed weight byte, far under the chip's 240
    flops = scmoe_cost.step_flops(cfg, 64, 16, visible)
    assert flops == 2.0 * (64 * 2_656_166_912 + 16 * 37_748_736 + visible * 64 * (1024 + 64))
    assert flops / step < 100


# ---- the scopes and the readers ----------------------------------------------

def hlo(program, lines):
    body = "\n".join(
        f'  %{name} = f32[8] fusion(%p0), metadata={{op_name="jit({program})/while/body/'
        f'closed_call/{path}"}}' for name, path in lines)
    return f"HloModule jit_{program}\n{body}\n  ROOT %tuple.9 = (f32[8]) tuple(%p0)\n"


DECODE = hlo("decode_step_rowwise", [
    ("fusion.1", "scmoe_attn0/mla_proj/rse,eq->rsq/dot_general"),
    ("fusion.2", "scmoe_attn0/mla_attn/latent_decode"),
    ("fusion.3", "scmoe_experts/moe_route/top_k"),
    ("fusion.4", "scmoe_experts/moe_experts/gmm"),
    ("fusion.5", "scmoe_experts/moe_zero/mul"),
    ("fusion.6", "scmoe_dense0/bse,em->bsm/dot_general"),
    ("fusion.7", "scmoe_attn1/mla_attn/latent_decode"),
    ("fusion.8", "scmoe_dense1/bsm,me->bse/dot_general"),
    ("fusion.9", "scmoe_experts/moe_combine/reduce_sum")])
GMM = "gmm.13 = custom-call:" + tr.PALLAS_TARGET


def plane(ops, modules):
    return {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops},
    ]}


def test_the_double_layers_parts_are_found_by_their_scopes():
    v = scmoe_trace.version(DECODE)
    assert v["scopes"]["scmoe_attn0"] == ["fusion.1", "fusion.2"]
    assert v["scopes"]["mla_attn"] == ["fusion.2", "fusion.7"]
    assert v["scopes"]["scmoe_experts"] == ["fusion.3", "fusion.4", "fusion.5", "fusion.9"]
    assert v["scopes"]["moe_zero"] == ["fusion.5"] and v["scopes"]["scmoe_dense1"] == ["fusion.8"]
    ops = [(f"fusion.{i} = fusion", 100 * i, 100 * i, {}) for i in range(1, 10)]
    ops = [(n, sum(d for _, _, d, _ in ops[:k]), d, st) for k, (n, _s, d, st) in enumerate(ops)]
    got = scmoe_trace.reduce([plane(ops, [("jit_decode_step_rowwise(7)", 0, 4500, {})])],
                             {"decode_step_rowwise": [v]})
    assert got["decode_executions_traced"] == 1
    assert got["mla_attn_device_s"] == got["mla_attn_decode_device_s"] == pytest.approx(900e-9)
    assert got["scmoe_attn0_decode_device_s"] == pytest.approx(300e-9)
    assert got["scmoe_experts_decode_device_s"] == pytest.approx((300 + 400 + 500 + 900) * 1e-9)
    assert got["moe_zero_device_s"] == pytest.approx(500e-9)
    assert got["scmoe_dense0_device_s"] == pytest.approx(600e-9)
    # the other kinds' scopes are as they were
    from chipbench import mtp_trace

    assert mtp_trace.SCOPES == ("mtp_draft", "mla_attn")


def window_facts(**kw):
    f = {"model": serve_scmoe.model_facts(config_file()), "max_slots": 64, "moe_itemsize": 2,
         "moe_embed": 6144, "moe_expert_dim": 2048, "compiles_in_window": 0,
         "decode_steps_in_window": 2000, "prefills_in_window": 90,
         # 2,000 steps that touch 10 experts a layer, 90 prefills that touch 16
         "moe_layer_steps": 4 * 2090, "moe_experts_touched_mean": (2000 * 40 + 90 * 64) / (4 * 2090),
         "moe_rows_per_layer_step_mean": 16.0, "moe_held_assignment_share": 2.08,
         "moe_zero_choices": 1, "moe_zero_choice_share": 33.1,
         "mla_keys_visible_step": 2000 * 8 * 64 * 3500}
    f.update(kw)
    return f


def test_the_two_new_readers_on_recorded_facts():
    peak = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    planes = [plane([("fusion.2 = fusion", 0, 4_000_000, {})],
                    [("jit_decode_step_rowwise(1)", 0, 15_000_000, {})])]
    ctx = {"facts": window_facts(), "busy_s": 2.5, "window_s": 3.0, "peak": peak,
           "planes": planes}
    step = scmoe_cost.step_bytes(ctx["facts"]["model"], 40, 8 * 64 * 3500, 64 * 8)
    got = reader(MINE[0])(ctx)
    assert got == pytest.approx(100 * step / 819e9 / 0.015) and 80 < got < 90
    assert reader(MINE[1])(ctx) == 33.1
    # a window of prefills alone touches no expert in a decode step: never negative
    ctx["facts"]["moe_experts_touched_mean"] = 1.0
    assert 0 < reader(MINE[0])(ctx) < got


def test_the_new_readers_find_nothing_on_a_program_without_the_block():
    """What the driver's traced run of the parent commit meets: no facts,
    no scopes — None, never an exception."""
    ctx = {"facts": {"max_slots": 32, "decode_steps_in_window": 100, "moe_experts_touched_mean": 8},
           "busy_s": 3.0, "window_s": 3.1,
           "peak": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "planes": [plane([], [("jit_decode_step_rowwise(1)", 0, 16_000_000, {})])]}
    for name in MINE:
        assert reader(name)(ctx) is None, name


def test_the_generic_device_readers_read_the_jobs_facts():
    """The accepted readers the cell joined, on a hand-made device plane
    and the facts the job supplies under the keys they read."""
    peak = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    ops = [(GMM, 1000, 400_000, {}), (GMM, 500_000, 400_000, {}),
           ("fusion.2 = fusion", 1_000_000, 4_000_000, {})]
    modules = [("jit_decode_step_rowwise(1)", 0, 15_000_000, {}),
               ("jit_prefill_into_slot(2)", 20_000_000, 300_000_000, {})]
    # ``tokens_while_traced`` / ``traced_client_s``: ``serve_moe.run``'s, which the job runs
    facts = window_facts(mla_attn_device_s=0.9, mla_attn_decode_device_s=0.6,
                         decode_executions_traced=200, tokens_while_traced=48,
                         traced_client_s=3.0)
    ctx = {"facts": facts, "busy_s": 2.5, "window_s": 3.0, "peak": peak,
           "planes": [plane(ops, modules)]}
    got = {name.removesuffix(".batch"): reader(name)(ctx) for name in GENERIC
           if name not in ON_THE_CPU or name == "compiles_in_window.batch"}
    assert got["decode_step_device_ms_p50"] == pytest.approx(15.0)
    assert got["prefill_device_ms_p50"] == pytest.approx(300.0)
    assert got["compiles_in_window"] == 0
    assert got["decode_batch_occupancy"] == pytest.approx(75.0)   # 48 tokens of 1 step x 64 rows
    assert got["device_idle_share"] == pytest.approx(100 * 0.5 / 3.0)
    assert got["gmm_time_share"] == pytest.approx(100 * 800e-6 / 2.5)
    assert 0 < got["gmm_hbm_roofline_share"] < 100
    assert got["mla_attn_time_share"] == pytest.approx(36.0)
    # 8 cache layers x 64 rows x 3,500 keys x 1,280 B a step, 200 steps in 0.6 s
    assert got["mla_attn_hbm_roofline_share"] == pytest.approx(
        100 * (8 * 64 * 3500 + 2 * 8 * 64) * 1280 * 200 / 819e9 / 0.6)
    assert 0 < got["mla_attn_hbm_roofline_share"] < 100


# ---- the job -----------------------------------------------------------------

def test_the_window_is_the_second_stats_call_less_the_first():
    def stats(steps, prefills):
        rows = steps * 64 + prefills * 3072
        return {"moe_expert_tokens": [[rows // 8] * 16] * 4,
                "moe_layer_steps_total": 4 * (steps + prefills),
                "moe_experts_touched_total": 4 * (steps * 10 + prefills * 16),
                "moe_routed_pairs_total": rows * 4 * 12,
                "moe_held_pairs_total": 4 * 16 * (rows // 8),
                "moe_zero_choices_total": rows * 4 * 4,
                "mla_keys_visible_step": steps * 8 * 64 * 3000,
                "mla_keys_read_step": steps * 8 * 64 * 3100,
                "decode_steps_total": steps, "admitted_total": prefills,
                "rows_stepped_total": rows, "peak_bytes_in_use": 1}

    config = serve_scmoe.scmoe_config(config_file())
    w = serve_scmoe._window(stats(40, 5), stats(2040, 95), config)
    assert w["decode_steps_in_window"] == 2000 and w["prefills_in_window"] == 90
    assert w["moe_layer_steps"] == 4 * 2090 and w["moe_dropped"] == 0
    assert w["moe_zero_choice_share"] == pytest.approx(100 / 3)
    assert w["moe_real_choices_per_token_mean"] == pytest.approx(8.0)
    assert w["moe_held_assignment_share"] == pytest.approx(100 * 4 * 16 / 8 / 48, rel=1e-3)
    assert w["mla_keys_visible_step"] == 2000 * 8 * 64 * 3000
    with pytest.raises(RuntimeError, match="no layer-step"):
        serve_scmoe._window(stats(40, 5), stats(40, 5), config)


def test_a_program_without_the_block_is_refused_at_import():
    """What the parent commit does with the new cell: the job's import
    fails, before any cluster or chip."""
    code = (
        "import dataclasses, sys\n"
        "from ray_tpu.models import llama\n"
        "fields = [(f.name, f.type, f) for f in dataclasses.fields(llama.LlamaConfig)"
        " if f.name not in ('block_form', 'zero_experts', 'mla_scale_q_lora',"
        " 'mla_scale_kv_lora')]\n"
        "llama.LlamaConfig = dataclasses.make_dataclass('LlamaConfig', fields, frozen=True)\n"
        "import chipbench.jobs.serve_scmoe\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=contract.ROOT)
    run = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert run.returncode != 0
    assert "cannot run a configuration of shortcut-connected double layers" in run.stderr


@pytest.fixture(scope="module")
def checked():
    """A toy cache's check run, as ``ScmoeReplica.check_reference`` makes
    it: the two served programs, their twin, the counters between."""
    from ray_tpu.models import llama

    cfg = dict(config_file(), **serve_scmoe.REHEARSAL_MODEL)
    cfg.update(dtype="float32", param_dtype="float32")
    config = serve_scmoe.scmoe_config(cfg)
    params = jax.jit(lambda k: llama.init(k, config))(jax.random.key(5))
    params = jax.tree.map(lambda a: a * 4 if a.ndim > 2 else a, params)
    prompts = [serve_scmoe.serve_dsa.check_prompt(config, 5 + r, n)
               for r, n in enumerate([16, 32])]
    cache, out = serve_scmoe.system_run(
        params, config, llama.init_cache(config, 4, 64), 4, prompts, 4)
    return params, config, cache, out


def test_the_comparison_passes_honest_and_refuses_what_it_must(checked):
    params, config, _cache, out = checked
    tolerance = {"rms": 3e-4, "max": 3e-3, "swap_rate_max": 0.01, "swapped_margin_max": 1e-5,
                 "twin_rms": 1e-5, "twin_max": 1e-4, "served_pairs_off_max": 0}
    got = serve_scmoe.against_reference(params, config, out)
    assert [len(r["seq"]) for r in out["rows"]] == [16 + 4, 32 + 4]
    assert all(r["logits"].shape == (5, 512) and r["experts"].shape == (2, len(r["seq"]), 4)
               for r in out["rows"])
    assert got["twin_pairs_miscounted"] == 0 and got["served_pairs_off"] == 0
    assert got["twin_err"]["max"] < 1e-5
    assert got["identity_choices"] > 0 and got["held_choices"] > 0
    assert serve_scmoe.passes(got, tolerance), got
    # free-running, the reference makes the same choices in float32
    free = serve_scmoe.against_reference(params, config, out, given=False)
    assert free["err"]["max"] < 3e-3 and free["swap_rate"] == 0.0
    for fault in (dict(twin_pairs_miscounted=1), dict(served_pairs_off=1),
                  dict(twin_err={"rms": 1e-6, "max": 1e-3}), dict(identity_choices=0), dict(held_choices=0), dict(swap_rate=0.02),
                  dict(swapped_margin_max=1e-4), dict(err={"rms": 1e-3, "max": 1e-3})):
        assert not serve_scmoe.passes({**got, **fault}, tolerance), fault
    # logits off; a program that left the identity term out (the reference
    # given no identity experts stands in for it)
    rows = out["rows"]
    noisy = dict(rows[1], logits=rows[1]["logits"] * 1.05)
    off = serve_scmoe.against_reference(params, config, dict(out, rows=[rows[0], noisy]))
    assert not serve_scmoe.passes(off, tolerance)
    import dataclasses

    blind = dataclasses.replace(config, num_experts=config.router_outputs, zero_experts=0)
    off = serve_scmoe.against_reference(params, blind, out)
    assert off["err"]["max"] > 30 * got["err"]["max"] and not serve_scmoe.passes(off, tolerance)


def test_the_router_bias_is_balanced_towards_even_loads(checked, capsys):
    from ray_tpu.models import llama

    params, config, _cache, _out = checked
    skewed = dict(params, blocks=dict(params["blocks"], router_bias=jnp.asarray(
        np.linspace(-0.05, 0.05, config.router_outputs)[None].repeat(2, 0), jnp.float32)))
    balanced, _ = serve_scmoe.balance_router(
        skewed, config, 3, llama.init_cache(config, 1, 256), 192)
    said = capsys.readouterr().out
    assert "router bias balanced on 24 prompts of 192" in said
    first, last = (float(x) for x in said.split("load / even sd ")[1].split(" (")[0].split(" -> "))
    assert last < 0.7 * first
    assert not np.array_equal(np.asarray(balanced["blocks"]["router_bias"]),
                              np.asarray(skewed["blocks"]["router_bias"]))


# ---- the cell, walked on the CPU ---------------------------------------------

@pytest.mark.limit(170)
def test_the_cell_walks_on_the_cpu_untraced():
    """``--rehearse``: toy shapes, fake chip, the whole control flow ends in
    one valid line."""
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed", "3000000017",
         "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=contract.ROOT, capture_output=True, text=True, timeout=160,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = contract.validate(contract.last_line(out.stdout), CELL, 0)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert "reference check at [16, 32] + 4 steps" in out.stderr
    assert "'block_form': 'shortcut'" in out.stderr            # the ``replica up:`` line


@pytest.mark.limit(170)
def test_the_traced_walk_reads_every_reader_the_cell_joined():
    """The traced walk: the line carries the cell's own two entries and the
    sixteen generic ones it joined, and those a CPU walk can read (counters,
    the engine's spans, the witness's stops) read a number."""
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed", "3000000018",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=contract.ROOT, capture_output=True, text=True, timeout=160,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = contract.validate(contract.last_line(out.stdout), CELL, 1)
    assert line["correct"] and line["failed"] == 0
    assert set(GENERIC) | set(MINE) <= set(line["metrics"])
    silent = [ln.split("rehearsal: ")[1].split(" found")[0]
              for ln in out.stderr.splitlines() if "found nothing to read" in ln]
    for name in ON_THE_CPU:
        assert name not in silent, name
    assert MINE[1] not in silent
    assert 5 < line["metrics"][MINE[1]]["value"] < 70           # 8 of a toy router's 24 outputs
    assert line["metrics"]["compiles_in_window.batch"]["value"] == 0
    facts = json.loads(next(ln for ln in out.stderr.splitlines()
                            if ln.startswith("[chipbench] facts: ")).split("facts: ", 1)[1])
    for key in ("moe_zero_choices", "moe_zero_choice_share", "moe_routed_assignments",
                "moe_held_assignment_share", "moe_experts_touched_mean", "mla_keys_visible_step",
                "decode_steps_in_window", "prefills_in_window", "moe_rows_per_layer_step_mean"):
        assert facts[key] > 0, key
    assert facts["moe_dropped"] == 0
    assert "keeps no record of its stops" not in out.stderr
