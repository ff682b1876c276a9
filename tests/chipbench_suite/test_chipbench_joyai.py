"""The JoyAI-LLM-Flash cell's benchmark side: the configuration file
against the catalog row it was cut from and its byte arithmetic,
``BENCHMARK.json``'s new entries, ``mla_cost`` by hand, the scope maps and
the new readers on hand-made planes and facts, the job's window arithmetic
and its refusal of a program without the fields, and the cell's walk on the
CPU."""

import importlib
import json
import math
import os
import subprocess
import sys

import jax
import pytest

from chipbench import contract, mla_cost, mtp_trace
from chipbench.jobs import serve_mtp

CELL = "serve_joyai_reason_mtp"
CONFIG = "joyai-llm-flash-ep32"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "q_lora_rank",
          "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "qk_head_dim",
          "v_head_dim", "head_dim", "num_experts_per_tok", "num_attention_heads")
#: the nine quantities PR 32 added, by the entry that holds each since PR 52
#: (one entry for each quantity under a judged metric): four are this cell's
#: alone, five it shares (SDAR runs the same stateful step, LongCat the same
#: latent attention)
NEW_METRICS = ("spec_acceptance_rate.joy", "spec_tokens_per_row_step_mean.joy",
               "mtp_draft_time_share.joy", "mla_attn_time_share",
               "mla_attn_hbm_roofline_share", "spec_step_hbm_roofline_share.joy",
               "spec_step_dispatch_ms_p50", "spec_step_deliver_ms_p50",
               "spec_step_serve_plane_ms_p50")
#: and the seven accepted quantities the cell reports beside them
SHARED = ("decode_step_device_ms_p50.batch", "prefill_device_ms_p50.batch",
          "device_idle_share.batch", "compiles_in_window.batch", "gmm_time_share",
          "gmm_hbm_roofline_share", "moe_held_assignment_share")


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
        "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json")
    assert sorted(cfg["reduced"]) == ["n_routed_experts", "vocab_size"]
    assert not set(cfg["reduced"]) & set(WIDTHS)
    for key in cfg["reduced"]:
        assert key in cfg["changed"], key
    # depth is NOT cut: all 40 layers and the module; the floors of the share
    assert cfg["num_hidden_layers"] == 40 and cfg["num_nextn_predict_layers"] == 1
    assert cfg["n_routed_experts"] == 8 and cfg["n_routed_experts_published"] == 256
    assert cfg["vocab_size"] * 8 == 129280
    assert "32 chips share each layer" in cfg["deployment"]
    assert "rank 0" in cfg["deployment"] and "experts 0-7" in cfg["deployment"]
    assert cfg["serving"] == {"max_slots": 32, "max_len": 4096,
                              "max_ongoing_requests": 1024,
                              "speculative_tokens": 1, "temperature": 1.0}
    for inference in ("mtp_module", "mtp_concat_order", "mtp_hidden", "mtp_position",
                      "acceptance", "draws"):
        assert inference in cfg["assumed"]
    assert "0.522" in cfg["assumed"]["acceptance"]
    for promise in ("exactly max_new_tokens", "distributed as the main model's",
                    "nothing is shed", "held expert is computed", "every visible key"):
        assert promise in cfg["guarantees"], promise
    assert len(cfg["source"]) <= 200


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="the catalog is not installed here")
def test_every_number_of_the_catalog_row_is_kept_or_listed():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "JoyAI-LLM-Flash")
    cfg = config_file()
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k, "absent") != v]
    assert sorted(differs) == sorted(cfg["reduced"])
    for width in WIDTHS:
        assert cfg[width] == row["config"][width], width


def test_the_program_gets_the_published_block_and_the_bytes_add_up():
    from ray_tpu.models import llama

    cfg = config_file()
    c = serve_mtp.joyai_config(cfg)
    assert (c.embed_dim, c.num_heads, c.mlp_dim, c.num_layers) == (2048, 32, 7168, 40)
    assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim,
            c.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (c.num_experts, c.experts_held, c.experts_per_token, c.expert_dim,
            c.shared_expert_dim, c.first_dense_layers) == (256, 8, 8, 768, 768, 1)
    assert c.index_topk == 0 and c.mtp_layers == 1 and c.cache_layers == 41
    assert c.rope_theta == 32e6 and c.rms_eps == 1e-6 and c.router_scale == 2.5
    # the byte arithmetic of ``changed``: the program's tree, the cost
    # functions and the file say the same
    n = llama.num_params(c)
    assert n == mla_cost.held_params(cfg) == 2_918_719_488
    assert "2,918,719,488" in cfg["changed"]["bytes"]
    cache = jax.eval_shape(lambda: llama.init_cache(c, 32, 4096))
    ckv = math.prod(cache["ckv"].shape) * 2
    assert ckv == 41 * 32 * 4096 * 1280 == 6_878_658_560
    assert mla_cost.latent_row_values(cfg) == 640 == cache["ckv"].shape[-1]
    assert {"ckv", "mla_keys", "moe_expert_tokens", "moe_experts_touched",
            "moe_layer_steps"} <= set(cache)            # a later PR may count more
    # 79% of the chip's 16 GB live
    assert 0.78 < (2 * n + ckv) / 16e9 < 0.81


def test_the_benchmark_holds_the_configuration_the_cell_and_the_joy_metrics():
    """My entries are there, with these cells and this reader — by name,
    never by position from the end or by how many cells there are."""
    bench = contract.load_benchmark()
    assert contract.check_benchmark(bench) == []
    assert [c["name"] for c in bench["configs"]].count(CONFIG) == 1
    cell = contract.cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "mtp_reason_closed64", 1)
    tokens = next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert CELL in tokens["workloads"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    declared = contract.declared_metrics(bench, CELL, 1)
    setup = {name for name in declared if name.startswith("setup_")}
    # mine are among them: a later PR declares further quantities in this cell
    assert set(NEW_METRICS) | set(SHARED) <= set(declared) - setup
    assert len(NEW_METRICS + SHARED) == 16
    for name in NEW_METRICS + SHARED:
        m = by_name[name]
        assert CELL in m["workloads"] and m["moves"] == "serve_tokens_per_s"
        stem = name.rpartition(".")[0] or name
        assert os.path.basename(contract.reader_path(name)) == stem + ".py", name
    for name in ("mla_attn_hbm_roofline_share", "spec_step_hbm_roofline_share.joy"):
        assert by_name[name]["unit"] == "%"


def test_the_traffic_is_the_issues():
    with open(os.path.join(contract.ROOT, "chipbench", "traffic",
                           "mtp_reason_closed64.json")) as f:
        t = json.load(f)
    assert (t["job"], t["loop"], t["clients"], t["requests_per_client"]) == (
        "serve_mtp", "closed", 64, 4)
    assert t["prompt_len"] == {"kind": "cycle", "values": [512, 1536]}
    assert t["new_tokens"] == {"kind": "fixed", "value": 2048}
    assert t["stagger"] == {"step": 64, "over": 32} and t["drain_s"] == 0
    assert 1536 + 2048 <= config_file()["serving"]["max_len"]


# ---- the cost functions, by hand ---------------------------------------------

def test_mla_cost_against_hand_counts():
    cfg = config_file()
    # one layer's attention: 3.15 + 9.44 + 1.18 + 2 x 2.10 + 8.39 M and the norms
    assert mla_cost.attention_params(cfg) == (
        2048 * 1536 + 1536 + 1536 * 32 * 192 + 2048 * 576 + 512
        + 2 * 512 * 32 * 128 + 32 * 128 * 2048 + 2 * 2048) == 26_351_616
    assert mla_cost.expert_params(cfg) == 3 * 2048 * 768 == 4_718_592
    fixed = mla_cost.fixed_params(cfg)
    assert fixed == (
        41 * 26_351_616 + 3 * 2048 * 7168 + 40 * (2048 * 256 + 256 + 4_718_592)
        + 3 * 2048 + 2 * 2048 * 2048 + 2048 + 16160 * 2048)
    # a step that touches 6.5 of its 8 experts a layer, rows at 2,000 keys
    visible = 41 * 32 * 2000
    written = mla_cost.rows_written(cfg, 1, 32)
    assert written == 41 * 32 * 2
    assert mla_cost.attention_bytes(visible, written, 640) == (visible + written) * 1280
    step = mla_cost.step_bytes(cfg, 40 * 6.5, visible, written)
    assert step == 2 * (fixed + 260 * 4_718_592) + (visible + written) * 1280
    assert 8e9 < step < 10.5e9                 # ISSUE 32 reckoned 9-10 GB a step


# ---- the scopes and the readers ----------------------------------------------

HLO = """
HloModule jit_decode_step_rowwise
  %fusion.1 = bf16[64,2048] fusion(%p0), metadata={op_name="jit(decode_step_rowwise)/mtp_draft/dot_general"}
  %latent_verify.2 = bf16[32,64,512] custom-call(%p1), metadata={op_name="jit(decode_step_rowwise)/mtp_draft/decode_attn/mla_attn/latent_verify"}
  %latent_verify.3 = bf16[32,64,512] custom-call(%p1), metadata={op_name="jit(decode_step_rowwise)/spec_verify/while/body/closed_call/decode_attn/mla_attn/latent_verify"}
  %fusion.4 = bf16[64,2048] fusion(%p0), metadata={op_name="jit(decode_step_rowwise)/spec_verify/while/body/closed_call/decode_mlp/moe_route/sort"}
  ROOT %tuple.5 = (bf16[64,2048]) tuple(%fusion.4)
"""


def plane(ops, modules):
    return {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops},
    ]}


def test_nested_scopes_are_each_counted_whole():
    v = mtp_trace.version(HLO)
    assert v["names"] == ["fusion.1", "latent_verify.2", "latent_verify.3", "fusion.4",
                          "tuple.5"]
    assert v["scopes"]["mtp_draft"] == ["fusion.1", "latent_verify.2"]
    assert v["scopes"]["mla_attn"] == ["latent_verify.2", "latent_verify.3"]
    ops = [("fusion.1 = fusion", 0, 100, {}), ("latent_verify.2 = custom-call", 100, 300, {}),
           ("latent_verify.3 = custom-call", 500, 1000, {}), ("fusion.4 = fusion", 1500, 200, {})]
    modules = [("jit_decode_step_rowwise(123)", 0, 2000, {}),
               ("jit_prefill_into_slot(9)", 3000, 500, {})]
    got = mtp_trace.reduce([plane(ops, modules)], {"decode_step_rowwise": [v]})
    assert got["decode_executions_traced"] == 1
    assert got["mtp_draft_device_s"] == pytest.approx(400e-9)
    assert got["mla_attn_device_s"] == got["mla_attn_decode_device_s"] == pytest.approx(1300e-9)


def facts(**kw):
    cfg = {k: v for k, v in config_file().items() if isinstance(v, (int, float))}
    f = {"model": cfg, "max_slots": 32, "moe_itemsize": 2, "decode_steps_in_window": 2000,
         "mla_keys_visible_step": 2000 * 41 * 32 * 2000, "mla_keys_read_step": 1,
         "moe_experts_touched_mean": 6.5, "moe_layer_steps": 2000 * 40 + 100 * 40,
         "spec_acceptance_rate": 52.4, "spec_tokens_per_row_step_mean": 1.52}
    f.update(kw)
    return f


def test_the_readers_on_recorded_facts():
    peak = {"hbm_bytes_per_s": 819e9}
    step_ops = [("latent_verify.3 = custom-call", 0, 4_000_000, {})]
    planes = [plane(step_ops, [("jit_decode_step_rowwise(1)", 0, 18_000_000, {})] * 1)]
    ctx = {"facts": facts(mtp_draft_device_s=0.09, mla_attn_device_s=0.6,
                          mla_attn_decode_device_s=0.5, decode_executions_traced=160),
           "busy_s": 3.0, "window_s": 3.1, "peak": peak, "planes": planes}
    assert reader("spec_acceptance_rate.joy")(ctx) == 52.4
    assert reader("spec_tokens_per_row_step_mean.joy")(ctx) == 1.52
    assert reader("mtp_draft_time_share.joy")(ctx) == pytest.approx(3.0)
    assert reader("mla_attn_time_share")(ctx) == pytest.approx(20.0)
    per_step = (41 * 32 * 2000 + 41 * 32 * 2) * 1280
    assert reader("mla_attn_hbm_roofline_share")(ctx) == pytest.approx(
        100 * per_step * 160 / 819e9 / 0.5)
    cfg = ctx["facts"]["model"]
    touched = 6.5 * (2000 * 40 + 100 * 40) / 2000
    want = mla_cost.step_bytes(cfg, touched, 41 * 32 * 2000, 41 * 32 * 2)
    got = reader("spec_step_hbm_roofline_share.joy")(ctx)
    assert got == pytest.approx(100 * want / 819e9 / 0.018) and 55 < got < 80
    assert reader("decode_step_device_ms_p50.batch")(ctx) == pytest.approx(18.0)


def test_host_step_times_come_from_the_spans_alone(monkeypatch):
    """Three steps, one of which admitted a request: the medians are over
    the other two, and no trace is asked for anything."""
    from chipbench import span_reduce

    def step(i, admitted, build, dispatch, deliver, yield_):
        root = {"name": "llm.step", "span_id": f"s{i}", "parent_id": None,
                "start_ns": i * 10**9, "end_ns": i * 10**9 + 10**8,
                "attributes": {"admitted": admitted}}
        kids, at = [], root["start_ns"]
        for name, ms in (("build", build), ("dispatch", dispatch), ("sync", 10.0),
                         ("deliver", deliver), ("yield", yield_)):
            kids.append({"name": "llm.step." + name, "span_id": f"s{i}{name}",
                         "parent_id": root["span_id"], "start_ns": at,
                         "end_ns": at + int(ms * 1e6), "attributes": {}})
            at = kids[-1]["end_ns"]
        return [root] + kids

    spans = step(0, 0, 0.1, 1.5, 0.2, 5.0) + step(1, 1, 0.1, 90.0, 0.3, 6.0) + step(
        2, 0, 0.1, 1.7, 0.4, 4.0)
    monkeypatch.setattr(span_reduce, "fetch", lambda ctx: {"spans": spans, "metrics": []})
    ctx = {}
    assert reader("spec_step_dispatch_ms_p50")(ctx) == pytest.approx(1.7)
    assert reader("spec_step_deliver_ms_p50")(ctx) == pytest.approx(0.3)
    assert reader("spec_step_serve_plane_ms_p50")(ctx) == pytest.approx(4.5)
    monkeypatch.setattr(span_reduce, "fetch", lambda ctx: None)
    assert reader("spec_step_dispatch_ms_p50")({}) is None


def test_the_readers_find_nothing_on_a_program_without_the_module(monkeypatch):
    from chipbench import span_reduce

    monkeypatch.setattr(span_reduce, "fetch", lambda ctx: None)
    ctx = {"facts": {"max_slots": 32}, "busy_s": 3.0, "window_s": 3.1,
           "peak": {"hbm_bytes_per_s": 819e9},
           "planes": [plane([], [("jit_decode_step_rowwise(1)", 0, 16_000_000, {})])]}
    for name in NEW_METRICS:
        assert reader(name)(ctx) is None, name


# ---- the job -----------------------------------------------------------------

def test_the_window_arithmetic():
    from ray_tpu.models.llama import LlamaConfig

    cfg = serve_mtp.joyai_config(dict(config_file(), **serve_mtp.REHEARSAL_MODEL))
    assert isinstance(cfg, LlamaConfig) and cfg.cache_layers == 4

    def stats(steps, tokens, **kw):
        return dict({
            "moe_expert_tokens": [[tokens] * 4] * 3, "moe_layer_steps_total": 3 * steps,
            "moe_experts_touched_total": 9 * steps, "rows_stepped_total": 8 * steps,
            "decode_steps_total": steps, "spec_drafted_total": 3 * steps,
            "spec_accepted_total": steps, "spec_tokens_emitted_total": 4 * steps,
            "spec_wasted_row_steps_total": 1, "mla_keys_visible_step": 100 * steps,
            "mla_keys_read_step": 128 * steps,
        }, **kw)

    w = serve_mtp._window(stats(10, 5), stats(110, 105), cfg)
    assert w["decode_steps_in_window"] == 100 and w["moe_layer_steps"] == 300
    assert w["moe_assignments"] == 1200 and w["moe_routed_assignments"] == 800 * 3 * 4
    assert w["moe_dropped"] == 0
    assert w["spec_acceptance_rate"] == pytest.approx(100 / 3)
    assert w["spec_tokens_per_row_step_mean"] == pytest.approx(4 / 3)
    assert w["mla_keys_visible_step"] == 10000 and w["spec_wasted_row_steps_total"] == 0
    # more rows computed than were routed: not correct
    assert serve_mtp._window(stats(10, 5), stats(110, 2500), cfg)["moe_dropped"] > 0
    with pytest.raises(RuntimeError, match="drafted"):
        serve_mtp._window(stats(10, 5), stats(110, 105, spec_drafted_total=30), cfg)


def test_a_program_without_the_module_is_refused_at_import():
    """What the parent commit does with the new cell: the job's import
    fails, before any cluster or chip."""
    code = (
        "import dataclasses, sys\\n"
        "from ray_tpu.models import llama\\n"
        "fields = [(f.name, f.type, f) for f in dataclasses.fields(llama.LlamaConfig)"
        " if f.name != 'mtp_layers']\\n"
        "llama.LlamaConfig = dataclasses.make_dataclass('LlamaConfig', fields, frozen=True)\\n"
        "import chipbench.jobs.serve_mtp\\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=contract.ROOT)
    run = subprocess.run([sys.executable, "-c", code.replace("\\n", "\n")], env=env,
                         capture_output=True, text=True, timeout=120)
    assert run.returncode != 0
    assert "cannot serve a model with its multi-token-prediction module" in run.stderr


def test_the_cell_walks_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=contract.ROOT)
    env.pop("BENCH_RUN", None)
    run = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed", "3000000019",
         "--seconds", "4", "--trace", "1", "--rehearse"],
        cwd=contract.ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-3000:]
    line = json.loads(contract.last_line(run.stdout))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    bench = contract.load_benchmark()
    want = contract.declared_metrics(bench, CELL, 1)
    assert set(line["metrics"]) == set(want)
    assert line["metrics"]["compiles_in_window.batch"]["value"] == 0
    assert line["metrics"]["spec_acceptance_rate.joy"]["value"] > 50
    assert 1.0 < line["metrics"]["spec_tokens_per_row_step_mean.joy"]["value"] <= 2.0
    assert "replay_mismatches': 0" in run.stderr
    # the drafting steps record their five parts: the host's share is read
    assert line["metrics"]["spec_step_dispatch_ms_p50"]["value"] > 0
    assert line["metrics"]["spec_step_serve_plane_ms_p50"]["value"] > 0
    # the two readings beside ``device_idle_share.batch`` the cell joined at
    # PR 58: read from the witness's record on the CPU too
    share = line["metrics"]["host_stall_share.batch"]["value"]
    assert 0 <= line["metrics"]["host_stall_outside_share.batch"]["value"] <= share < 100
    assert "keeps no record of its stops" not in run.stderr
