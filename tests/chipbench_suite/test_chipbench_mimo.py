"""The MiMo-V2-Flash cell's benchmark side: the configuration file against the
catalog row it was cut from and its byte arithmetic, ``BENCHMARK.json``'s
entries (that mine are there, BY NAME: never as a list's tail or as a set, so
that a later cell breaks nothing here), ``swa_cost`` by hand, the scope map
and the six new readers on hand-made planes and facts, the job's
window arithmetic, its refusal of a program without attention kinds, the
comparison that decides ``correct`` on a toy cache — honest, and with each
piece of the mathematics left out of the reference — and the cell walked on
the CPU through the repo's own benchmark."""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import contract, swa_cost, swa_trace
from chipbench.jobs import serve_swa

CELL = "serve_mimo_longmix_batch"
CONFIG = "mimo-v2-flash-ep16-l7"
TRAFFIC = "swa_longmix_closed128"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
#: the cell's per-layer entries, in the order they were appended
MINE = tuple(name + ".mimo" for name in swa_trace.SHARES)
#: the accepted entries the cell joined at PR 58, by a data edit alone (ISSUE 55
#: asked for the first twelve; a test of another cell held each list's last
#: cell to be its own): their readers' facts are what the job has supplied
#: since PR 55, and the last two ask the GCS
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
KERNELS = "kernels (ops/kv_decode_attention.py, ops/kv_prefill_attention.py)"


def reader(metric):
    path = contract.reader_path(metric)
    spec = importlib.util.spec_from_file_location("reader_" + metric.replace(".", "_"), path)
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
        "https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/config.json")
    assert cfg["reduced"] == REDUCED and len(cfg["source"]) <= 200
    assert set(cfg["changed"]) == set(REDUCED) | {"bytes"}
    assert "multi-token-prediction" in cfg["scope"] and "no keys for them" in cfg["scope"]
    assert (cfg["num_hidden_layers"], cfg["num_hidden_layers_published"]) == (7, 48)
    assert (cfg["n_routed_experts"], cfg["n_routed_experts_published"]) == (16, 256)
    assert (cfg["vocab_size"], cfg["vocab_size_published"]) == (19072, 152576)
    # layer 0, then one whole period: five window layers and the full one
    assert cfg["layers_kept"] == [0, 6, 7, 8, 9, 10, 11]
    assert [cfg["hybrid_layer_pattern"][i] for i in cfg["layers_kept"]] == [0, 1, 1, 1, 1, 1, 0]
    assert [cfg["moe_layer_freq"][i] for i in cfg["layers_kept"]] == [0, 1, 1, 1, 1, 1, 1]
    assert len(cfg["hybrid_layer_pattern"]) == len(cfg["moe_layer_freq"]) == 48
    assert cfg["serving"] == {"max_slots": 64, "max_len": 13312, "max_ongoing_requests": 1024}
    for setting in ("rotary", "qk_norm", "attention_value_scale", "window", "sink", "router",
                    "hidden_act", "block", "weights"):
        assert setting in cfg["assumed"], setting
    assert "20-50%" in cfg["assumed"]["weights"] and "BALANCED" in cfg["assumed"]["weights"]
    for promise in ("exactly max_new_tokens", "held expert is computed", "nothing is shed",
                    "every earlier key", "the 127 before it"):
        assert promise in cfg["guarantees"], promise
    assert "16 chips" in cfg["deployment"] and "rank 0" in cfg["deployment"]
    tol = cfg["reference_tolerance"]
    assert 0 < tol["rms"] < tol["max"] and 0 < tol["swap_rate_max"] < 1
    assert tol["swapped_margin_max"] > 0 and tol["check_steps"] >= 4 and "honest" in tol["why"]
    for piece in ("sink", "0.707", "window of 127", "window of 129", "bfloat16", "192"):
        assert piece in tol["why"], piece


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="the catalog is not installed here")
def test_every_number_of_the_catalog_row_is_kept_or_listed():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2-Flash")
    cfg = config_file()
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k, "absent") != v]
    assert sorted(differs) == sorted(cfg["reduced"]) == sorted(REDUCED)
    for width in ("hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim",
                  "v_head_dim", "swa_head_dim", "swa_v_head_dim", "num_attention_heads",
                  "num_key_value_heads", "swa_num_key_value_heads", "num_experts_per_tok",
                  "sliding_window", "partial_rotary_factor", "attention_value_scale"):
        assert cfg[width] == row["config"][width], width


def test_the_program_gets_the_published_widths_and_the_bytes_add_up():
    from ray_tpu.models import llama

    cfg = config_file()
    c = serve_swa.swa_config(cfg)
    kinds = (llama.FULL,) + (llama.SLIDING,) * 5 + (llama.FULL,)
    assert c == llama.LlamaConfig.mimo_v2_flash(
        num_layers=7, layer_types=kinds, vocab_size=19072, experts_held=16,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    assert (c.embed_dim, c.num_heads, c.head_dim, c.value_dim, c.rotary_dim) == (
        4096, 64, 192, 128, 64)
    assert (c.num_kv_heads, c.rope_theta, c.sliding) == (
        4, 5e6, llama.AttentionKind(8, 1e4, 128, True))
    assert (c.router_scoring, c.num_experts, c.experts_per_token, c.experts_here,
            c.router_norm_topk, c.router_scale, c.shared_expert_dim) == (
        "sigmoid", 256, 8, 16, True, 1.0, 0)
    assert (c.kv_layers, c.sliding_layers, c.expert_layers, c.first_dense_layers) == (2, 5, 6, 1)
    # the byte arithmetic of ``changed``: the program's tree, the cost
    # functions and the file say the same
    n = llama.num_params(c)
    assert n == swa_cost.held_params(cfg) == 3_429_955_392
    for number in ("3,429,892,096", "3,429,955,392", "63,296", "94,371,840", "89,128,960",
                   "201,326,592", "402,653,184", "1,048,576", "290,455,552", "498,073,600",
                   "492,830,720", "78,118,912", "5,120"):
        assert number in cfg["changed"]["bytes"], number
    cache = jax.eval_shape(lambda: llama.init_cache(c, 64, 13312))
    assert cache["k"].shape == (2, 64, 13312, 4 * 192)
    assert cache["v"].shape == (2, 64, 13312, 4 * 128)
    assert cache["swa_k"].shape == (5, 64, 128, 8 * 192)
    assert cache["swa_v"].shape == (5, 64, 128, 8 * 128)
    held = {k: int(np.prod(a.shape)) * a.dtype.itemsize for k, a in cache.items()}
    by_kind = swa_cost.cache_bytes(cfg, 64, 13312)
    assert held["k"] + held["v"] == by_kind[swa_cost.FULL] == 4_362_076_160
    assert held["swa_k"] + held["swa_v"] == by_kind[swa_cost.WINDOW] == 209_715_200
    # kept to max_len the window layers would hold 21.8 GB: more than the chip
    assert 5 * 64 * 13312 * swa_cost.key_values(cfg, swa_cost.WINDOW) * 2 == 21_810_380_800
    # 71% of the chip's 16 GB live
    assert 0.70 < (2 * n + sum(held.values())) / 16e9 < 0.73


def test_my_benchmark_entries_are_there_by_name():
    """The configuration, the cell and the lists it joined: by name — a later
    PR appends behind them, and nothing here looks at a list's end."""
    bench = contract.load_benchmark()
    assert contract.check_benchmark(bench) == []
    entry = contract.config_entry(bench, CONFIG)
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["reduced"] == REDUCED and entry["source"] == config_file()["source"]
    cell = contract.cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, TRAFFIC, 1)
    assert "1/16" in cell["why"] and "7/48 layers" in cell["why"] and len(cell["why"]) <= 200
    assert [w["config"] for w in bench["workloads"]].count(CONFIG) == 1
    tokens = next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert CELL in tokens["workloads"]
    setup = [m for m in bench["per_layer"] if m["name"].startswith("setup_")]
    assert len(setup) == 6 and all(CELL in m["workloads"] for m in setup)
    names = [m["name"] for m in bench["per_layer"]]
    at = [names.index(name) for name in MINE]
    assert at == sorted(at)                                      # in this order among themselves
    for name in MINE:
        m = bench["per_layer"][names.index(name)]
        assert CELL in m["workloads"] and m["moves"] == "serve_tokens_per_s"
        assert contract.reader_path(name).endswith(name.rpartition(".")[0] + ".py")
        share = name.endswith("_share.mimo")
        assert (m["unit"], m["better"]) == (
            ("%", "lower" if "time" in name else "higher") if share else ("x", "lower"))
        assert m["source"] == ("device_trace" if share else "program_counter")
        assert m["layer"] == ("model step (models/llama.py)" if "step" in name else KERNELS)
    assert ({m["name"] for m in setup} | set(MINE) | set(GENERIC)) <= set(
        contract.declared_metrics(bench, CELL, 1))
    for name in GENERIC:
        m = bench["per_layer"][names.index(name)]
        assert len(m["workloads"]) > 1 and m["moves"] == "serve_tokens_per_s"   # joined
    assert set(contract.declared_metrics(bench, CELL, 0)) == {"serve_tokens_per_s", "setup_s"}


def test_the_traffic_is_the_issues():
    with open(os.path.join(contract.ROOT, "chipbench", "traffic", TRAFFIC + ".json")) as f:
        t = json.load(f)
    assert (t["job"], t["loop"], t["clients"], t["requests_per_client"]) == (
        "serve_swa", "closed", 128, 4)
    assert t["prompt_len"] == {"kind": "cycle", "values": [2048, 12288]}
    assert t["new_tokens"] == {"kind": "fixed", "value": 1024}
    assert t["stagger"] == {"step": 16, "over": 64} and t["drain_s"] == 0
    assert (t["trace_at_s"], t["trace_for_s"]) == (6, 3) and "agent" in t["what"]
    serving = config_file()["serving"]
    assert t["clients"] == 2 * serving["max_slots"]              # a slot never waits
    assert 12288 + 1024 == serving["max_len"] > 10240            # the first cell past 10,240
    assert t["stagger"]["step"] * t["stagger"]["over"] == 1024


# ---- the cost functions, by hand ---------------------------------------------

def test_swa_cost_against_hand_counts():
    cfg = config_file()
    F, W = swa_cost.FULL, swa_cost.WINDOW
    assert swa_cost.kinds(cfg) == [F, W, W, W, W, W, F]
    assert (swa_cost.layers(cfg, F), swa_cost.layers(cfg, W)) == (2, 5)
    assert (swa_cost.expert_layers(cfg), swa_cost.dense_layers(cfg)) == (6, 1)
    assert swa_cost.attention_params(cfg, W) == (
        4096 * 64 * 192 + 4096 * 8 * 192 + 4096 * 8 * 128 + 64 * 128 * 4096) == 94_371_840
    assert swa_cost.attention_params(cfg, F) == (
        50_331_648 + 4096 * 4 * 192 + 4096 * 4 * 128 + 33_554_432) == 89_128_960
    assert swa_cost.dense_params(cfg) == 3 * 4096 * 16384 == 201_326_592
    assert swa_cost.router_params(cfg) == 4096 * 256 == 1_048_576
    assert swa_cost.expert_params(cfg) == 3 * 4096 * 2048 == 25_165_824
    assert swa_cost.small_params(cfg) == 7 * 2 * 4096 + 4096 + 5 * 64 + 6 * 256 == 63_296
    fixed = (2 * 89_128_960 + 5 * 94_371_840 + 201_326_592 + 6 * 1_048_576 + 63_296
             + 19072 * 4096)
    assert swa_cost.fixed_params(cfg) == fixed == 935_917_376
    assert swa_cost.held_params(cfg) == fixed + 6 * 16 * 25_165_824 + 19072 * 4096
    # a key: 4 x (192 + 128) values in a full layer, 8 x 320 in a window layer
    assert (swa_cost.key_values(cfg, F), swa_cost.key_values(cfg, W)) == (1280, 2560)
    # a step of 64 rows, half at 2.5k and half at 12.8k keys, that touches 14 of
    # 16 experts a layer: 1.9 GB fixed, 4.2 GB experts, 2.5 GB full K/V, 0.2 window
    full_keys = 2 * 32 * (2560 + 12800)
    window_keys = 5 * 64 * 128
    step = swa_cost.step_bytes(cfg, 6 * 14, full_keys, window_keys, 64)
    assert step == (2 * (fixed + 84 * 25_165_824) + (full_keys + 2 * 64) * 2560
                    + (window_keys + 5 * 64) * 5120)
    assert 8.7e9 < step < 8.9e9
    assert swa_cost.attention_bytes(cfg, F, full_keys, 64) / swa_cost.attention_bytes(
        cfg, W, window_keys, 64) > 10                            # the kinds differ by ten
    flops = swa_cost.step_flops(cfg, 64, 32, full_keys, window_keys)
    assert flops == 2.0 * (64 * fixed + 32 * 25_165_824 + (full_keys + window_keys) * 64 * 320)
    assert flops / step < 100                                    # far under the chip's 240
    # a 12,288-token prompt: 3.1 TFLOP a full layer, 0.064 a window layer
    assert swa_cost.prefill_attention_flops(cfg, F, 12288) == 2.0 * (
        12288 * 12289 // 2) * 64 * 320
    assert swa_cost.prefill_attention_flops(cfg, W, 12288) == 2.0 * (
        12288 * 128 - 128 * 127 // 2) * 64 * 320


# ---- the scopes and the six quantities -----------------------------------------

def hlo(program, lines):
    body = "\n".join(
        f'  %{name} = f32[8] fusion(%p0), metadata={{op_name="jit({program})/while/body/'
        f'closed_call/{path}"}}' for name, path in lines)
    return f"HloModule jit_{program}\n{body}\n  ROOT %tuple.9 = (f32[8]) tuple(%p0)\n"


DECODE = hlo("decode_step_rowwise", [
    ("fusion.1", "decode_attn/full_attn/scatter"),
    ("fusion.2", "decode_attn/full_attn/kv_decode"),
    ("fusion.3", "decode_mlp/moe_route/top_k"),
    ("fusion.4", "decode_mlp/moe_experts/gmm"),
    ("fusion.5", "decode_attn/swa_attn/kv_decode"),
    ("fusion.6", "decode_mlp/moe_combine/dot_general")])


def plane(ops, modules):
    return {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops},
    ]}


def test_the_two_kinds_are_found_by_their_scopes():
    v = swa_trace.version(DECODE)
    assert v["scopes"]["full_attn"] == ["fusion.1", "fusion.2"]
    assert v["scopes"]["swa_attn"] == ["fusion.5"]
    assert v["scopes"]["moe_experts"] == ["fusion.4"]
    ops = [(f"fusion.{i} = fusion", 1000 * i, 100 * i, {}) for i in range(1, 7)]
    got = swa_trace.reduce([plane(ops, [("jit_decode_step_rowwise(7)", 0, 9000, {})])],
                           {"decode_step_rowwise": [v]})
    assert got["decode_executions_traced"] == 1
    assert got["full_attn_device_s"] == got["full_attn_decode_device_s"] == pytest.approx(300e-9)
    assert got["swa_attn_decode_device_s"] == pytest.approx(500e-9)
    assert got["moe_combine_device_s"] == pytest.approx(600e-9)
    # the other kinds' scopes are as they were
    from chipbench import mtp_trace, scmoe_trace

    assert mtp_trace.SCOPES == ("mtp_draft", "mla_attn") and "mla_attn" in scmoe_trace.SCOPES


def window_facts(**kw):
    steps, prefills = 1500, 90
    chunks = prefills // 2 * (1 + 6)          # 2,048 a chunk: 1 and 6 expert calls a layer
    f = {"model": serve_swa.model_facts(config_file()), "max_slots": 64, "moe_itemsize": 2,
         "decode_steps_in_window": steps, "prefills_in_window": prefills,
         "moe_layer_steps": 6 * (steps + chunks),
         # steps that touch 14 experts a layer, prefill chunks that touch 16
         "moe_experts_touched_mean": (steps * 84 + chunks * 96) / (6 * (steps + chunks)),
         "full_keys_visible_step": steps * 2 * 32 * (2560 + 12800),
         "swa_keys_visible_step": steps * 5 * 64 * 128,
         "swa_pairs_visible_run": 5 * 45 * (2048 * 128 + 12288 * 128 - 2 * 8128),
         "swa_pairs_read_run": 5 * 45 * (31 + 191) * 128 * 128,
         "full_attn_device_s": 0.9, "full_attn_decode_device_s": 0.5,
         "swa_attn_device_s": 0.1, "swa_attn_decode_device_s": 0.06,
         "decode_executions_traced": 130}
    f.update(kw)
    return f


def test_the_six_new_readers_on_recorded_facts():
    planes = [plane([], [("jit_decode_step_rowwise(1)", 0, 12_000_000, {}),
                         ("jit_prefill_into_slot(2)", 20_000_000, 300_000_000, {})])]
    facts = window_facts()
    ctx = {"facts": facts, "busy_s": 2.5, "window_s": 3.0, "peak": PEAK, "planes": planes}
    got = {name.removesuffix(".mimo"): reader(name)(ctx) for name in MINE}
    assert got == swa_trace.layer_shares(planes, 2.5, facts, PEAK)
    assert set(got) == set(swa_trace.SHARES)
    model = facts["model"]
    assert got["full_attn_time_share"] == pytest.approx(36.0)
    assert got["swa_attn_time_share"] == pytest.approx(4.0)
    full = swa_cost.attention_bytes(model, swa_cost.FULL, 2 * 32 * 15360, 64)
    assert got["full_attn_hbm_roofline_share"] == pytest.approx(100 * full * 130 / 819e9 / 0.5)
    window = swa_cost.attention_bytes(model, swa_cost.WINDOW, 5 * 64 * 128, 64)
    assert got["swa_attn_hbm_roofline_share"] == pytest.approx(100 * window * 130 / 819e9 / 0.06)
    step = swa_cost.step_bytes(model, 84, 2 * 32 * 15360, 5 * 64 * 128, 64)
    assert got["swa_step_hbm_roofline_share"] == pytest.approx(100 * step / 819e9 / 0.012)
    assert all(0 < got[k] < 100 for k in swa_trace.SHARES if k.endswith("share"))
    # two tiles of 128 keys a tile of 128 queries: 2.0 but for the first tile
    assert 1.9 < got["swa_prefill_pairs_over_band"] < 2.02
    # the two served programs' medians, the prefills' two versions apart
    planes[0]["lines"][0]["events"] += [("jit_prefill_into_slot(3)", 400_000_000, 30_000_000, {}),
                                        ("jit_prefill_into_slot(3)", 500_000_000, 28_000_000, {})]
    assert swa_trace.program_ms(planes) == {
        "decode_step_device_ms_p50": 12.0, "prefill_short_device_ms_p50": 29.0,
        "prefill_long_device_ms_p50": 300.0}
    assert swa_trace.program_ms([plane([], [])]) == {}
    # a window of prefills alone touches no expert in a decode step: never negative
    less = swa_trace.layer_shares(planes, 2.5, window_facts(moe_experts_touched_mean=1.0), PEAK)
    assert 0 < less["swa_step_hbm_roofline_share"] < got["swa_step_hbm_roofline_share"]


def test_the_six_find_nothing_on_a_program_without_attention_kinds():
    """What a traced run of a program without attention kinds meets: no
    facts, no scopes — None, never an exception."""
    planes = [plane([], [("jit_decode_step_rowwise(1)", 0, 16_000_000, {})])]
    facts = {"max_slots": 32, "decode_steps_in_window": 100, "moe_experts_touched_mean": 8,
             "moe_itemsize": 2}
    ctx = {"facts": facts, "busy_s": 3.0, "window_s": 3.1, "peak": PEAK, "planes": planes}
    for name in MINE:
        assert reader(name)(ctx) is None, name
    assert swa_trace.layer_shares(planes, 3.0, facts, PEAK) == {}
    assert swa_trace.layer_shares(planes, 3.0, dict(facts, full_attn_device_s=0.3), PEAK) == {
        "full_attn_time_share": pytest.approx(10.0)}


# ---- the job -----------------------------------------------------------------

def test_the_window_is_the_second_stats_call_less_the_first():
    def stats(steps, prefills):
        rows = steps * 64 + prefills * 7168
        return {"moe_expert_tokens": [[rows // 32] * 16] * 6,
                "moe_layer_steps_total": 6 * (steps + 4 * prefills),
                "moe_experts_touched_total": 6 * (steps * 14 + 4 * prefills * 16),
                "moe_routed_pairs_total": rows * 6 * 8,
                "moe_held_pairs_total": 6 * 16 * (rows // 32),
                "full_keys_visible_step": steps * 2 * 64 * 7000,
                "full_keys_read_step": steps * 2 * 64 * 7100,
                "swa_keys_visible_step": steps * 5 * 64 * 128,
                "swa_keys_read_step": steps * 5 * 64 * 128,
                "full_pairs_visible_run": prefills * 2 * 1000,
                "full_pairs_read_run": prefills * 2 * 1100,
                "swa_pairs_visible_run": prefills * 5 * 100,
                "swa_pairs_read_run": prefills * 5 * 200,
                "kv_decode_attention": {"full": "streamed", "swa": "streamed"},
                "kv_prefill_attention": "flash",
                "decode_steps_total": steps, "admitted_total": prefills,
                "rows_stepped_total": rows, "peak_bytes_in_use": 1}

    config = serve_swa.swa_config(config_file())
    w = serve_swa._window(stats(40, 5), stats(1540, 95), config)
    assert w["decode_steps_in_window"] == 1500 and w["prefills_in_window"] == 90
    assert w["moe_layer_steps"] == 6 * (1500 + 360) and w["moe_dropped"] == 0
    assert w["moe_held_assignment_share"] == pytest.approx(100 * 16 / 32 / 8, rel=1e-3)
    assert w["full_keys_visible_step"] == 1500 * 2 * 64 * 7000
    assert w["swa_pairs_read_run"] / w["swa_pairs_visible_run"] == 2.0
    assert w["kv_decode_attention"] == {"full": "streamed", "swa": "streamed"}
    with pytest.raises(RuntimeError, match="no layer-step"):
        serve_swa._window(stats(40, 5), stats(40, 5), config)


def test_a_program_without_attention_kinds_is_refused_at_import():
    """What the parent commit does with the new cell: the job's import
    fails, before any cluster or chip."""
    code = (
        "import dataclasses, sys\n"
        "from ray_tpu.models import llama\n"
        "fields = [(f.name, f.type, f) for f in dataclasses.fields(llama.LlamaConfig)"
        " if f.name not in ('sliding', 'rotary_dim', 'value_scale')]\n"
        "llama.LlamaConfig = dataclasses.make_dataclass('LlamaConfig', fields, frozen=True)\n"
        "del llama.AttentionKind\n"
        "import chipbench.jobs.serve_swa\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=contract.ROOT)
    run = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert run.returncode != 0
    assert "cannot run a configuration whose attention layers are of two kinds" in run.stderr
    assert "'AttentionKind', 'rotary_dim', 'sliding', 'value_scale'" in run.stderr


@pytest.fixture(scope="module")
def checked():
    """A toy cache's check run, as ``SwaReplica.check_reference`` makes it:
    the two served programs, their twin, the counter between — prompts of 16
    and 32 ids and 20 steps over a window of 8: the slots turn past twice."""
    from ray_tpu.models import llama

    cfg = dict(config_file(), **serve_swa.REHEARSAL_MODEL)
    cfg.update(dtype="float32", param_dtype="float32")
    config = serve_swa.swa_config(cfg)
    params = jax.jit(lambda k: llama.init(k, config))(jax.random.key(5))
    params = jax.tree.map(lambda a: a * 4 if a.ndim > 2 else a, params)
    params = serve_swa.draw_sinks(params, config, 5)
    prompts = [serve_swa.serve_dsa.check_prompt(config, 5 + r, n)
               for r, n in enumerate([16, 32])]
    cache, out = serve_swa.system_run(
        params, config, llama.init_cache(config, 4, 64), 4, prompts, 20)
    return params, config, cache, out


TOLERANCE = {"rms": 3e-4, "max": 3e-3, "swap_rate_max": 0.01, "swapped_margin_max": 1e-5,
             "twin_rms": 1e-5, "twin_max": 1e-4, "served_pairs_off_max": 0}


@pytest.mark.limit(170)
def test_the_comparison_passes_honest_and_refuses_what_it_must(checked):
    params, config, _cache, out = checked
    got = serve_swa.against_reference(params, config, out)
    assert [len(r["seq"]) for r in out["rows"]] == [16 + 20, 32 + 20]
    assert all(r["logits"].shape == (21, 512) and r["experts"].shape == (6, len(r["seq"]), 2)
               for r in out["rows"])
    assert got["twin_pairs_miscounted"] == 0 and got["served_pairs_off"] == 0
    assert got["twin_err"]["max"] < 1e-5 and got["held_choices"] > 0
    assert serve_swa.passes(got, TOLERANCE), got
    # free-running, the reference makes the same choices in float32
    free = serve_swa.against_reference(params, config, out, given=False)
    assert free["err"]["max"] < 3e-3 and free["swap_rate"] == 0.0
    for fault in (dict(twin_pairs_miscounted=1), dict(served_pairs_off=1),
                  dict(twin_err={"rms": 1e-6, "max": 1e-3}), dict(held_choices=0),
                  dict(swap_rate=0.02), dict(swapped_margin_max=1e-4),
                  dict(err={"rms": 1e-3, "max": 1e-3})):
        assert not serve_swa.passes({**got, **fault}, TOLERANCE), fault
    rows = out["rows"]
    noisy = dict(rows[1], logits=rows[1]["logits"] * 1.05)
    off = serve_swa.against_reference(params, config, dict(out, rows=[rows[0], noisy]))
    assert not serve_swa.passes(off, TOLERANCE)


@pytest.mark.limit(170)
@pytest.mark.parametrize("piece, bent", [
    ("no sink", dict(sink=False)),
    ("no 0.707 on the values", dict(value_scale=1.0)),
    ("the window layers' base on the full layers", dict(rope_theta=1e4)),
    ("the full layers' base on the window layers", dict(swa_rope_theta=5e6)),
    ("a window of 7", dict(window=7)),
    ("a window of 9", dict(window=9)),
    ("all 12 values rotated", dict(rotary_dim=12)),
    ("the softmax in bfloat16", dict(softmax_dtype="bfloat16")),
])
def test_each_piece_left_out_of_the_reference_fails_the_comparison(checked, piece, bent):
    params, config, _cache, out = checked
    off = serve_swa.against_reference(params, config, out, **bent)
    assert not serve_swa.passes(off, TOLERANCE), (piece, off["err"])


def test_the_sinks_are_drawn_and_the_bias_is_balanced(checked, capsys):
    from ray_tpu.models import llama

    params, config, _cache, _out = checked
    sink = np.asarray(params["swa_blocks"]["sink"])
    assert sink.shape == (5, 4) and (sink >= np.log(2.0) - 1e-6).all()
    assert (sink <= np.log(8.0) + 1e-6).all() and sink.std() > 0.1
    assert serve_swa.expert_stacks(config) == [("swa_blocks", i) for i in range(5)] + [("blocks", 0)]
    skew = np.linspace(-0.05, 0.05, config.num_experts)[None]
    skewed = dict(params, **{
        name: dict(params[name], router_bias=jnp.asarray(
            skew.repeat(params[name]["router_bias"].shape[0], 0), jnp.float32))
        for name in ("swa_blocks", "blocks")})
    balanced, _ = serve_swa.balance_router(
        skewed, config, 3, llama.init_cache(config, 1, 256), 192)
    said = capsys.readouterr().out
    assert "router bias balanced on 24 prompts of 192" in said
    first, last = (float(x) for x in said.split("load / even sd ")[1].split(" (")[0].split(" -> "))
    assert last < 0.7 * first
    for name in ("swa_blocks", "blocks"):
        assert not np.array_equal(np.asarray(balanced[name]["router_bias"]),
                                  np.asarray(skewed[name]["router_bias"]))


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
    """``--rehearse``: toy shapes, fake chip, the whole control flow ends in
    one valid line."""
    out = walk(0, 3000000017)
    assert out.returncode == 0, out.stderr[-3000:]
    line = contract.validate(contract.last_line(out.stdout), CELL, 0)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert "reference check at [16, 32] + 4 steps" in out.stderr
    # the numbers compared, each beside its limit: the line's last key and
    # standard error's last line (PR 58)
    assert list(line)[-1] == "compared" and {"rms", "max", "swap_rate_max"} <= set(line["compared"])
    assert all(pair["value"] <= pair["limit"] for pair in line["compared"].values())
    assert out.stderr.rstrip().splitlines()[-1].startswith(
        "[chipbench] correct True, failed 0; compared: rms ")
    facts = facts_of(out.stderr)
    for key in ("full_keys_visible_step", "swa_keys_visible_step", "full_pairs_visible_run",
                "swa_pairs_read_run", "moe_held_assignment_share", "decode_steps_in_window",
                "prefills_in_window"):
        assert facts[key] > 0, key
    assert facts["moe_dropped"] == 0 and facts["kv_prefill_attention"] == "dense"
    assert facts["swa_keys_visible_step"] < facts["full_keys_visible_step"]


@pytest.mark.limit(170)
def test_the_traced_walk_ends_in_a_valid_line():
    """The traced walk: the line carries the cell's own six entries, the six
    set-up entries and the fourteen generic ones it joined at PR 58, and those
    a CPU walk can read (counters, the engine's spans, the witness's stops)
    read a number."""
    out = walk(1, 3000000018)
    assert out.returncode == 0, out.stderr[-3000:]
    line = contract.validate(contract.last_line(out.stdout), CELL, 1)
    assert line["correct"] and line["failed"] == 0
    assert {name for name in line["metrics"] if name.startswith("setup_")} == {
        "setup_cluster_start_s", "setup_worker_ready_s", "setup_chip_open_s",
        "setup_state_init_s", "setup_xla_build_s", "setup_xla_cache_miss_s"}
    assert set(MINE) | set(GENERIC) <= set(line["metrics"])
    # the counters' reader reads on the CPU too (the toy's dense body scores
    # the square: 3-4 times the band); the trace's have no device plane here
    silent = [ln.split("rehearsal: ")[1].split(" found")[0]
              for ln in out.stderr.splitlines() if "found nothing to read" in ln]
    assert "swa_prefill_pairs_over_band.mimo" not in silent
    for name in ON_THE_CPU:
        assert name not in silent, name
    assert line["metrics"]["compiles_in_window.batch"]["value"] == 0
    assert 0 < line["metrics"]["moe_held_assignment_share"]["value"] < 100
    assert "keeps no record of its stops" not in out.stderr
    assert 2 < line["metrics"]["swa_prefill_pairs_over_band.mimo"]["value"] < 6
    facts = facts_of(out.stderr)
    for key in ("moe_experts_touched_mean", "moe_rows_per_layer_step_mean",
                "tokens_while_traced", "traced_client_s", "swa_pairs_visible_run"):
        assert facts[key] > 0, key
    assert facts["compiles_in_window"] == 0
