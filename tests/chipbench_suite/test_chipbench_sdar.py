"""The SDAR-30B-A3B-Chat cell's benchmark side: the configuration file
against the catalog row it was cut from and its byte arithmetic,
``BENCHMARK.json``'s new entries (that mine are there, in this order),
``gqa_cost`` by hand, the scope map and the new readers on hand-made planes
and facts, the job's window arithmetic, its refusal of a program without the
fields, and the comparison that decides ``correct`` on a toy engine."""

import importlib
import json
import math
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from chipbench import contract, diffusion_trace, gqa_cost
from chipbench.jobs import serve_diffusion

CELL = "serve_sdar_diffusion_batch"
CONFIG = "sdar-30b-a3b-ep8"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim",
          "num_experts_per_tok", "num_attention_heads", "num_key_value_heads")
#: the cell's fourteen per-layer quantities by the entry that holds each since
#: PR 52 (one entry for each quantity under a judged metric): ten accepted
#: ones it shares with the other cells judged on tokens/s, four of its own
#: (PR 58 pruned ``diff_commit_forward_share.sdar`` and
#: ``diff_threshold_transfer_share.sdar``: 0.2 and 0.0 on every line of the
#: ledger, the block of four and the random weights; the job's facts keep both)
SHARED = ("decode_step_device_ms_p50.batch", "prefill_device_ms_p50.batch",
          "device_idle_share.batch", "compiles_in_window.batch",
          "spec_step_dispatch_ms_p50", "spec_step_deliver_ms_p50",
          "spec_step_serve_plane_ms_p50", "gmm_time_share", "gmm_hbm_roofline_share",
          "moe_held_assignment_share")
NEW_READERS = ("diff_tokens_per_row_forward_mean", "block_attn_time_share",
               "block_attn_hbm_roofline_share", "diff_step_hbm_roofline_share")
MINE = tuple(name + ".sdar" for name in NEW_READERS)


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
        "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json")
    assert sorted(cfg["reduced"]) == ["num_experts", "vocab_size"]
    assert not set(cfg["reduced"]) & set(WIDTHS)
    for key in cfg["reduced"]:
        assert key in cfg["changed"], key
    # depth is NOT cut: all 48 layers; the floors of the share
    assert cfg["num_hidden_layers"] == 48
    assert cfg["num_experts"] == 16 and cfg["num_experts_published"] == 128
    assert cfg["vocab_size"] * 8 == 151936
    assert "8 chips share each layer" in cfg["deployment"]
    assert "rank 0" in cfg["deployment"] and "experts 0-15" in cfg["deployment"]
    assert cfg["serving"] == {
        "max_slots": 32, "max_len": 1536, "max_ongoing_requests": 1024,
        "diffusion_block": 4, "denoising_steps": 4, "confidence_threshold": 0.9,
        "temperature": 1.0}
    for setting in ("block_length", "denoising_steps", "remasking", "confidence_threshold",
                    "temperature", "top_k", "top_p", "logit_shift", "mask_token",
                    "mask_excluded", "draws", "qk_norm", "rope"):
        assert setting in cfg["assumed"], setting
    for promise in ("exactly max_new_tokens", "the card's loop", "every committed key",
                    "FINAL tokens", "no dropped row", "nothing is shed"):
        assert promise in cfg["guarantees"], promise
    tol = cfg["reference_tolerance"]
    assert tol["check_steps"] == 15 and 0 < tol["rms"] < tol["max"] < 1
    assert len(cfg["source"]) <= 200


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="the catalog is not installed here")
def test_every_number_of_the_catalog_row_is_kept_or_listed():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "SDAR-30B-A3B-Chat")
    cfg = config_file()
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k, "absent") != v]
    assert sorted(differs) == sorted(cfg["reduced"])
    for width in WIDTHS:
        assert cfg[width] == row["config"][width], width


def test_the_program_gets_the_published_block_and_the_bytes_add_up():
    from ray_tpu.models import llama

    cfg = config_file()
    c = serve_diffusion.sdar_config(cfg)
    assert (c.embed_dim, c.num_heads, c.num_kv_heads, c.head_dim, c.num_layers) == (
        2048, 32, 4, 128, 48)
    assert c.head_dim != c.embed_dim // c.num_heads
    assert (c.num_experts, c.experts_held, c.experts_per_token, c.expert_dim) == (
        128, 16, 8, 768)
    assert c.qk_norm == "head" and c.router_scoring == "softmax" and c.router_norm_topk
    assert c.rope_theta == 1e6 and c.rms_eps == 1e-6 and not c.latent
    # the byte arithmetic of ``changed``: the program's tree, the cost
    # functions and the file say the same
    n = llama.num_params(c)
    assert n == gqa_cost.held_params(cfg) == 4_620_433_408
    assert "4,620,433,408" in cfg["changed"]["bytes"]
    shapes = jax.eval_shape(lambda: llama.init(jax.random.key(0), c))
    layer = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes["blocks"])) // 48
    assert layer == 94_638_336 and "94,638,336" in cfg["changed"]["bytes"]
    cache = jax.eval_shape(lambda: llama.init_cache(c, 32, 1536))
    kv = 2 * math.prod(cache["k"].shape) * 2
    assert kv == 32 * 1536 * gqa_cost.cache_bytes_per_token(cfg) == 4_831_838_208
    assert gqa_cost.cache_bytes_per_token(cfg) == 98_304
    assert {"k", "v", "moe_expert_tokens", "moe_experts_touched",
            "moe_layer_steps"} <= set(cache)            # a later PR may count more
    # 88% of the chip's 16 GB live
    assert 0.87 < (2 * n + kv) / 16e9 < 0.89


def test_my_benchmark_entries_are_there_in_this_order():
    """My entries are there, with these cells and this reader: by name and by order among themselves — never by position from the
    end: a later PR appends behind them."""
    bench = contract.load_benchmark()
    assert contract.check_benchmark(bench) == []
    entry = contract.config_entry(bench, CONFIG)
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["reduced"] == ["num_experts", "vocab_size"]
    cell = contract.cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "diffusion_gen_closed64", 1)
    assert [w["config"] for w in bench["workloads"]].count(CONFIG) == 1
    tokens = next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert CELL in tokens["workloads"]
    assert tokens["workloads"].index(CELL) > tokens["workloads"].index(
        "serve_joyai_reason_mtp")
    names = [m["name"] for m in bench["per_layer"]]
    at = [names.index(name) for name in MINE]
    assert at == list(range(at[0], at[0] + len(MINE)))          # one run, unbroken
    assert at[0] > max(i for i, n in enumerate(names) if n.startswith("setup_"))  # behind PR 34's
    for name in MINE + SHARED:
        m = bench["per_layer"][names.index(name)]
        assert CELL in m["workloads"] and m["moves"] == "serve_tokens_per_s"
        assert contract.reader_path(name) is not None, name
        if "roofline" in name:
            assert m["unit"] == "%" and m["better"] == "higher"
    # and set-up from the inside, as in every cell (PR 34's six, the cell appended)
    setup = [m["name"] for m in bench["per_layer"] if m["name"].startswith("setup_")]
    assert len(setup) == 6
    # mine are among them: a later PR declares further quantities in this cell
    assert set(MINE) | set(SHARED) | set(setup) <= set(
        contract.declared_metrics(bench, CELL, 1))
    assert set(contract.declared_metrics(bench, CELL, 0)) == {"serve_tokens_per_s", "setup_s"}


def test_the_traffic_is_the_issues():
    with open(os.path.join(contract.ROOT, "chipbench", "traffic",
                           "diffusion_gen_closed64.json")) as f:
        t = json.load(f)
    assert (t["job"], t["loop"], t["clients"], t["requests_per_client"]) == (
        "serve_diffusion", "closed", 64, 4)
    assert t["prompt_len"] == {"kind": "cycle", "values": [256, 512]}
    assert t["new_tokens"] == {"kind": "fixed", "value": 1024}
    assert t["stagger"] == {"step": 32, "over": 32} and t["drain_s"] == 0 and t["ramp_s"] == 6
    serving = config_file()["serving"]
    assert 512 + 1024 <= serving["max_len"] and serving["max_len"] % 4 == 0


# ---- the cost functions, by hand ---------------------------------------------

def test_gqa_cost_against_hand_counts():
    cfg = config_file()
    # one layer's attention: 8.39 + 2 x 1.05 + 8.39 M, the q/k norms and the two norms
    assert gqa_cost.attention_params(cfg) == (
        2048 * 32 * 128 + 2 * 2048 * 4 * 128 + 32 * 128 * 2048 + 2 * 128 + 2 * 2048
    ) == 18_878_720
    assert gqa_cost.expert_params(cfg) == 3 * 2048 * 768 == 4_718_592
    fixed = gqa_cost.fixed_params(cfg)
    assert fixed == 48 * (18_878_720 + 2048 * 128) + 2048 + 18992 * 2048 == 957_659_136
    assert gqa_cost.key_values(cfg) == 2 * 4 * 128
    # a step of 32 live rows at 900 keys, every held expert touched
    visible = 48 * 32 * 900
    written = gqa_cost.keys_written(cfg, 1, 32, 4)
    assert written == 48 * 32 * 4
    assert gqa_cost.attention_bytes(visible, written, cfg) == (visible + written) * 2048
    assert gqa_cost.attention_flops(visible, cfg, 4) == 4 * visible * 4 * 32 * 128
    step = gqa_cost.step_bytes(cfg, 48 * 16, visible, written)
    assert step == 2 * (fixed + 768 * 4_718_592) + (visible + written) * 2048
    # every weight but the embedding (9.09 GB) and 2.8 GB of K/V: ISSUE 36's
    # 9.09 + 4.83 is of a cache read WHOLE
    assert 9.16e9 - 2 * 18992 * 2048 < step - (visible + written) * 2048 < 9.17e9
    assert 11.5e9 < step < 12.5e9


# ---- the scope and the readers -----------------------------------------------

HLO = """
HloModule jit_decode_step_rowwise
  %fusion.1 = bf16[48,32,1536,4,128] fusion(%p0), metadata={op_name="jit(decode_step_rowwise)/diff_forward/while/body/closed_call/decode_attn/scatter"}
  %fusion.2 = f32[32,4,8,4,1536] fusion(%p1), metadata={op_name="jit(decode_step_rowwise)/diff_forward/while/body/closed_call/decode_attn/block_attn/bqkgd,btkd->bkgqt/dot_general"}
  %fusion.3 = bf16[32,4,4,8,128] fusion(%p1), metadata={op_name="jit(decode_step_rowwise)/diff_forward/while/body/closed_call/decode_attn/block_attn/bkgqt,btkd->bqkgd/dot_general"}
  %gmm.4 = bf16[1024,768] custom-call(%p0), metadata={op_name="jit(decode_step_rowwise)/diff_forward/while/body/closed_call/decode_mlp/moe_experts/gmm"}
  ROOT %tuple.5 = (bf16[64,2048]) tuple(%gmm.4)
"""


def plane(ops, modules):
    return {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops},
    ]}


def test_the_block_attention_is_found_by_its_scope():
    v = diffusion_trace.version(HLO)
    assert v["scopes"] == {"block_attn": ["fusion.2", "fusion.3"]}
    ops = [("fusion.1 = fusion", 0, 100, {}), ("fusion.2 = fusion", 100, 300, {}),
           ("fusion.3 = fusion", 500, 1000, {}), ("gmm.4 = custom-call", 1500, 200, {})]
    modules = [("jit_decode_step_rowwise(123)", 0, 2000, {}),
               ("jit_prefill_into_slot(9)", 3000, 500, {})]
    got = diffusion_trace.reduce([plane(ops, modules)], {"decode_step_rowwise": [v]})
    assert got["decode_executions_traced"] == 1
    assert got["block_attn_device_s"] == got["block_attn_decode_device_s"] == (
        pytest.approx(1300e-9))
    # the other kind's scopes are as they were
    from chipbench import mtp_trace

    assert mtp_trace.SCOPES == ("mtp_draft", "mla_attn")


def facts(**kw):
    cfg = {k: v for k, v in config_file().items() if isinstance(v, (int, float))}
    f = {"model": cfg, "max_slots": 32, "moe_itemsize": 2, "diffusion_block": 4,
         "decode_steps_in_window": 2000, "kv_keys_visible_step": 2000 * 48 * 32 * 900,
         "moe_experts_touched_mean": 16.0, "moe_layer_steps": 2000 * 48 + 60 * 48,
         "diff_tokens_per_row_forward_mean": 0.8, "diff_commit_forward_share": 0.2,
         "diff_threshold_transfer_share": 0.0}
    f.update(kw)
    return f


def test_the_readers_on_recorded_facts():
    peak = {"hbm_bytes_per_s": 819e9}
    planes = [plane([("fusion.2 = fusion", 0, 4_000_000, {})],
                    [("jit_decode_step_rowwise(1)", 0, 24_000_000, {})])]
    ctx = {"facts": facts(block_attn_device_s=0.9, block_attn_decode_device_s=0.8,
                          decode_executions_traced=80),
           "busy_s": 2.0, "window_s": 2.1, "peak": peak, "planes": planes}
    assert reader("diff_tokens_per_row_forward_mean.sdar")(ctx) == 0.8
    assert reader("block_attn_time_share.sdar")(ctx) == pytest.approx(45.0)
    per_step = (48 * 32 * 900 + 48 * 32 * 4) * 2048
    got = reader("block_attn_hbm_roofline_share.sdar")(ctx)
    assert got == pytest.approx(100 * per_step * 80 / 819e9 / 0.8) and 0 < got < 100
    cfg = ctx["facts"]["model"]
    touched = 16.0 * (2000 * 48 + 60 * 48) / 2000
    want = gqa_cost.step_bytes(cfg, touched, 48 * 32 * 900, 48 * 32 * 4)
    got = reader("diff_step_hbm_roofline_share.sdar")(ctx)
    assert got == pytest.approx(100 * want / 819e9 / 0.024) and 55 < got < 70
    assert reader("decode_step_device_ms_p50.batch")(ctx) == pytest.approx(24.0)


def test_the_new_readers_find_nothing_on_a_program_without_block_diffusion():
    ctx = {"facts": {"max_slots": 32}, "busy_s": 3.0, "window_s": 3.1,
           "peak": {"hbm_bytes_per_s": 819e9},
           "planes": [plane([], [("jit_decode_step_rowwise(1)", 0, 16_000_000, {})])]}
    for name in NEW_READERS:
        assert reader(name + ".sdar")(ctx) is None, name


# ---- the job -----------------------------------------------------------------

def test_the_window_arithmetic():
    cfg = serve_diffusion.sdar_config(dict(config_file(), **serve_diffusion.REHEARSAL_MODEL))
    assert (cfg.num_layers, cfg.num_experts, cfg.experts_held) == (2, 16, 4)

    def stats(steps, tokens, **kw):
        return dict({
            "moe_expert_tokens": [[tokens] * 4] * 2, "moe_layer_steps_total": 2 * steps,
            "moe_experts_touched_total": 8 * steps, "rows_stepped_total": 16 * steps,
            "decode_steps_total": steps, "diffusion_forwards_total": 3 * steps,
            "diffusion_commit_forwards_total": steps - 2,
            "diffusion_blocks_committed_total": steps - 2,
            "diffusion_tokens_unmasked_total": 2 * steps,
            "diffusion_threshold_transfers_total": steps // 2,
            "diffusion_tokens_emitted_total": 2 * steps,
            "diffusion_wasted_row_steps_total": 1, "kv_keys_visible_step": 100 * steps,
        }, **kw)

    w = serve_diffusion._window(stats(10, 5), stats(110, 105), cfg)
    assert w["decode_steps_in_window"] == 100 and w["moe_layer_steps"] == 200
    assert w["moe_assignments"] == 800 and w["moe_routed_assignments"] == 1600 * 2 * 4
    assert w["moe_dropped"] == 0 and w["moe_held_assignment_share"] == pytest.approx(6.25)
    assert w["diff_tokens_per_row_forward_mean"] == pytest.approx(2 / 3)
    assert w["diff_commit_forward_share"] == pytest.approx(1 / 3)
    assert w["diff_threshold_transfer_share"] == pytest.approx(0.25)
    assert w["kv_keys_visible_step"] == 10000 and w["diffusion_wasted_row_steps_total"] == 0
    # more rows computed than were routed: not correct
    assert serve_diffusion._window(stats(10, 5), stats(110, 2500), cfg)["moe_dropped"] > 0
    with pytest.raises(RuntimeError, match="live row"):
        serve_diffusion._window(stats(10, 5), stats(110, 105, diffusion_forwards_total=30), cfg)


def test_the_traffic_keeps_off_the_mask_row():
    from chipbench import loadgen

    gen = serve_diffusion._Slice(loadgen)
    req = loadgen.Request(7, None, None, 4000, 8, 11)   # no client: nothing paces it
    ids = gen.prompt_tokens(req, 64)
    assert max(ids) == 62 and min(ids) == 0
    done = loadgen.Outcome(req, 0.0, [0.1] * 8, [1] * 7 + [63], finished=True)
    assert gen.request_failed(done, 64) == "a token outside the vocabulary"
    done.tokens[-1] = 62
    assert gen.request_failed(done, 64) is None


def test_a_program_without_block_diffusion_is_refused_at_import():
    """What the parent commit does with the new cell: the job's import
    fails, before any cluster or chip."""
    code = (
        "import dataclasses, sys\n"
        "from ray_tpu.models import llama\n"
        "fields = [(f.name, f.type, f) for f in dataclasses.fields(llama.LlamaConfig)"
        " if f.name not in ('mask_block', 'head_dim')]\n"
        "llama.LlamaConfig = dataclasses.make_dataclass('LlamaConfig', fields, frozen=True)\n"
        "import chipbench.jobs.serve_diffusion\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=contract.ROOT)
    run = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert run.returncode != 0
    assert "cannot serve a model that generates by diffusion over blocks" in run.stderr


@pytest.fixture(scope="module")
def checked():
    """A toy engine's check run, as ``DiffusionReplica.check_reference``
    makes it: the two served programs, then the reference."""
    from ray_tpu.serve.llm import LLMEngine

    cfg = dict(config_file(), **serve_diffusion.REHEARSAL_MODEL)
    cfg.update(dtype="float32", param_dtype="float32")
    params = serve_diffusion.make_weights(cfg, 5, True)
    params = jax.tree.map(lambda a: a * 8 if a.ndim > 1 else a, params)
    eng = LLMEngine(params, serve_diffusion.sdar_config(cfg), max_slots=4, max_len=64,
                    diffusion_block=4, denoising_steps=4, temperature=1.0, seed=5)
    out = serve_diffusion.system_run(eng, 5, [16, 14], 10)
    return eng, out


def test_the_comparison_passes_honest_and_refuses_what_it_must(checked):
    eng, out = checked
    tolerance = {"rms": 1e-3, "max": 5e-3, "swap_rate_max": 0.05,
                 "swapped_margin_max": 1e-3, "commits_min": 2}
    settings = eng._step_options["settings"]

    def compare(run):
        return serve_diffusion.against_reference(
            eng.params, eng.config, eng._key, eng.temperature, settings, run)

    got = compare(out)
    assert got["replay_mismatches"] == got["delivery_mismatches"] == 0
    assert got["commits_min"] == 2 and got["err"]["max"] < 1e-3
    assert serve_diffusion.passes(got, tolerance)
    assert not serve_diffusion.passes(got, dict(tolerance, commits_min=3))
    assert int(np.asarray(eng._spec["left"]).sum()) == 0   # the check rows are empty again
    # an id delivered out of order, a candidate the keys do not give, logits off
    rows = out["rows"]
    swapped = dict(rows[0], emitted=rows[0]["emitted"][::-1])
    assert compare({"rows": [swapped, rows[1]]})["delivery_mismatches"] == 1
    first = dict(rows[1]["passes"][0], x0=rows[1]["passes"][0]["x0"] + 1)
    tampered = dict(rows[1], passes=[first] + rows[1]["passes"][1:])
    assert compare({"rows": [rows[0], tampered]})["replay_mismatches"] >= 1
    noisy = dict(rows[0]["passes"][3], logits=rows[0]["passes"][3]["logits"] * 1.05)
    off = dict(rows[0], passes=rows[0]["passes"][:3] + [noisy] + rows[0]["passes"][4:])
    assert not serve_diffusion.passes(compare({"rows": [off, rows[1]]}), tolerance)
