"""The GLM-5 cell's benchmark side: the configuration file against the
catalog row it was cut from, ``BENCHMARK.json``'s new entries, the
sparse-attention path's cost functions by hand, the scope maps and the
four new readers on hand-made planes and facts, the job's window
arithmetic and its refusal of a program without the fields, and the
cell's walk on the CPU."""

import importlib
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import contract, dsa_cost, dsa_trace
from chipbench.jobs import serve_dsa

CELL = "serve_glm5_long_batch"
CONFIG = "glm-5-ep16-l6"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "q_lora_rank",
          "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "qk_head_dim",
          "v_head_dim", "head_dim", "index_head_dim", "index_n_heads", "index_topk",
          "num_experts_per_tok", "num_attention_heads")


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
    assert cfg["source"] == "https://huggingface.co/zai-org/GLM-5/blob/main/config.json"
    assert sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"])
    assert not set(cfg["reduced"]) & set(WIDTHS)
    for key in cfg["reduced"]:
        assert key in cfg["changed"], key
    # floors: four layers after the dense one, 8 experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] == 16 >= 8 and cfg["n_routed_experts_published"] == 256
    assert cfg["vocab_size"] * 8 == 154880
    assert "16 chips share each layer" in cfg["deployment"]
    assert "rank 0" in cfg["deployment"] and "experts 0-15" in cfg["deployment"]
    assert cfg["serving"] == {"max_slots": 32, "max_len": 10240, "max_ongoing_requests": 1024}
    for departure in ("indexer_hadamard", "indexer_fp8", "indexer_weights", "indexer_norm"):
        assert departure in cfg["assumed"]
    assert "num_nextn_predict_layers" in cfg["changed"]     # the multi-token head
    assert len(cfg["source"]) <= 200


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="the catalog is not installed here")
def test_every_number_of_the_catalog_row_is_kept_or_listed():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
    cfg = config_file()
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k, "absent") != v]
    assert sorted(differs) == sorted(cfg["reduced"])
    for width in WIDTHS:
        assert cfg[width] == row["config"][width], width


def test_the_program_gets_the_published_block():
    c = serve_dsa.dsa_config(config_file())
    assert (c.embed_dim, c.num_heads, c.mlp_dim, c.num_layers) == (6144, 64, 12288, 6)
    assert (c.q_lora_rank, c.kv_lora_rank) == (2048, 512)
    assert (c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim) == (192, 64, 256)
    assert (c.index_n_heads, c.index_head_dim, c.index_topk) == (32, 128, 2048)
    assert (c.num_experts, c.experts_per_token, c.expert_dim) == (256, 8, 2048)
    assert (c.experts_held, c.expert_offset, c.shared_expert_dim) == (16, 0, 2048)
    assert (c.router_scoring, c.router_norm_topk, c.router_scale) == ("sigmoid", True, 2.5)
    assert c.first_dense_layers == 1 and c.vocab_size == 19360 and c.rope_theta == 1e6
    assert c.latent and not c.tie_embeddings and c.dtype == jnp.bfloat16
    with pytest.raises(RuntimeError):
        serve_dsa.dsa_config(dict(config_file(), n_group=8))


#: the cell's seventeen per-layer quantities by the entry that holds each
#: since PR 52 (one entry for each quantity under a judged metric): three of
#: its own, fourteen it shares with the other cells judged on tokens/s
MINE = ("sparse_attn_time_share.glm", "sparse_attn_hbm_roofline_share.glm",
        "dsa_selected_share_mean.glm")
SHARED = ("decode_step_device_ms_p50.batch", "prefill_device_ms_p50.batch",
          "decode_batch_occupancy.batch", "device_idle_share.batch",
          "compiles_in_window.batch", "step_dispatch_ms_p50.batch",
          "step_deliver_ms_p50.batch", "step_serve_plane_ms_p50.batch",
          "idle_gap_attributed_share.batch", "gmm_time_share", "gmm_hbm_roofline_share",
          "moe_experts_touched_mean", "moe_expert_load_max_over_mean",
          "moe_held_assignment_share")


def test_benchmark_json_holds_the_cell_and_its_entries():
    """My entries are there, with these cells and this reader — by name,
    never by position or by how many cells the benchmark has."""
    bench = contract.load_benchmark()
    assert contract.check_benchmark(bench) == []
    cell = contract.cell(bench, CELL)
    assert cell == {**cell, "config": CONFIG, "traffic": "dsa_long_closed64", "chips": 1}
    entry = contract.config_entry(bench, CONFIG)
    assert entry["file"] == "chipbench/configs/" + CONFIG + ".json"
    assert sorted(entry["reduced"]) == sorted(config_file()["reduced"])
    assert [w["config"] for w in bench["workloads"]].count(CONFIG) == 1
    assert set(contract.declared_metrics(bench, CELL, 0)) == {"serve_tokens_per_s", "setup_s"}
    glm = contract.declared_metrics(bench, CELL, 1)
    setup = {name for name in glm if name.startswith("setup_")}
    # mine are among them: a later PR declares further quantities in this cell
    assert set(MINE) | set(SHARED) <= set(glm) - setup and len(MINE + SHARED) == 17
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in MINE + SHARED:
        m = by_name[name]
        assert CELL in m["workloads"] and m["moves"] == "serve_tokens_per_s"
        stem = name.rpartition(".")[0] or name
        assert os.path.basename(contract.reader_path(name)) == stem + ".py"
    with open(os.path.join(contract.ROOT, "chipbench", "traffic", "dsa_long_closed64.json")) as f:
        mix = json.load(f)
    assert mix["job"] == "serve_dsa" and mix["loop"] == "closed" and mix["clients"] == 64
    assert mix["prompt_len"] == {"kind": "cycle", "values": [4096, 8192]}
    assert mix["new_tokens"] == {"kind": "fixed", "value": 2048}
    # staggered so that one slot frees every 64 steps of the 2,048
    assert mix["stagger"] == {"step": 64, "over": 32}
    assert max(mix["prompt_len"]["values"]) + 2048 <= config_file()["serving"]["max_len"]


# ---- cost functions ----------------------------------------------------------

def test_the_sparse_paths_bytes_by_hand():
    # one row at position 8,000 in one layer: 8,001 index keys of 128 bf16
    # values, 2,048 latent rows of 576, one new row of each written
    assert dsa_cost.index_bytes(8001, 128) == 8001 * 256 == 2_048_256
    assert dsa_cost.gather_bytes(2048, 576) == 2048 * 1152 == 2_359_296
    assert dsa_cost.write_bytes(1, 128, 576) == 1408
    assert dsa_cost.sparse_attention_bytes(8001, 2048, 1, 128, 576) == 4_408_960
    # a decode step of the cell: 32 rows x 6 layers at 7,168 keys on average
    step = dsa_cost.sparse_attention_bytes(32 * 6 * 7168, 32 * 6 * 2048, 32 * 6, 128, 576)
    assert step == 32 * 6 * (7168 * 256 + 2048 * 1152 + 1408) == 805_576_704
    # below index_topk everything visible is selected: the bytes follow pos
    assert dsa_cost.sparse_attention_bytes(100, 100, 1, 128, 576) == 100 * 1408 + 1408


# ---- scope maps and the trace ------------------------------------------------

HLO = """
%fused_computation.8 (param_0.26: bf16[6,32,10240,640]) -> bf16[65536,640] {
  ROOT %gather.1 = bf16[65536,640]{1,0} gather(%param_0.26), metadata={op_name="jit(decode_step_rowwise)/while/body/closed_call/decode_attn/dsa_select/gather" stack_frame_id=9}
}
  %fusion.583 = bf16[65536,640]{1,0:T(8,128)(2,1)S(1)} fusion(%a, %b), kind=kLoop, calls=%fused_computation.8, metadata={op_name="jit(decode_step_rowwise)/while/body/closed_call/decode_attn/dsa_select/gather" stack_frame_id=151}, backend_config={"x":"y"}
  %sort.83 = (f32[32,10240]{1,0}, s32[32,10240]{1,0}) sort(%c, %d), metadata={op_name="jit(decode_step_rowwise)/while/body/closed_call/decode_attn/dsa_select/top_k"}
  %fusion.580 = f32[32,10240]{1,0} fusion(%e), kind=kOutput, metadata={op_name="jit(decode_step_rowwise)/while/body/closed_call/decode_attn/dsa_index/...qjd,...td->...qjt/dot_general"}
  %fusion.590 = f32[32]{0} fusion(%f), metadata={op_name="jit(decode_step_rowwise)/while/body/closed_call/decode_attn/bshd,hde->bse/dot_general"}
  ROOT %fusion.600 = bf16[32,64,512]{2,1,0} fusion(%g), metadata={op_name="jit(decode_step_rowwise)/while/body/closed_call/decode_attn/mla_attn/rhk,rkc->rhc/dot_general"}
  %gmm.12 = bf16[256,2048]{1,0} custom-call(%h), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode_step_rowwise)/while/body/closed_call/decode_mlp/moe_experts/pallas_call"}
  %copy.1 = s32[32]{0} copy(%i)
"""


def test_scope_maps_come_from_the_programs_text():
    assert dsa_trace.scope_of("jit(f)/while/body/decode_attn/dsa_index/dot_general") == "dsa_index"
    assert dsa_trace.scope_of("jit(f)/decode_attn/mla_attn/while/body/dsa_select/x") == "dsa_select"
    assert dsa_trace.scope_of("jit(f)/decode_attn/dot_general") is None
    assert dsa_trace.scope_of("jit(f)/my_dsa_index_like/x") is None
    got = dsa_trace.scope_map(HLO)
    assert got == {"gather.1": "dsa_select", "fusion.583": "dsa_select", "sort.83": "dsa_select",
                   "fusion.580": "dsa_index", "fusion.600": "mla_attn"}
    assert dsa_trace.scope_map(HLO, ("moe_experts",)) == {"gmm.12": "moe_experts"}
    whole = dsa_trace.version(HLO)
    assert whole["scopes"] == got
    assert whole["names"] == ["gather.1", "fusion.583", "sort.83", "fusion.580", "fusion.590",
                              "fusion.600", "gmm.12", "copy.1"]
    assert dsa_trace.program_of("jit_decode_step_rowwise(4604659647685780638)") == "decode_step_rowwise"


def planes():
    """Two decode executions of 1,000 ns around one prefill of 4,000."""
    ms = [["jit_decode_step_rowwise(1)", 0.0, 1000.0, {}],
          ["jit_prefill_into_slot(2)", 2000.0, 4000.0, {}],
          ["jit_decode_step_rowwise(1)", 7000.0, 1000.0, {}],
          ["jit__argmax(3)", 8100.0, 10.0, {}]]
    ops = [
        ["while.1 = while", 0.0, 1000.0, {}],
        ["fusion.580 = fusion", 100.0, 100.0, {}],        # dsa_index
        ["sort.83 = sort", 200.0, 150.0, {}],             # dsa_select
        ["fusion.583 = fusion", 350.0, 250.0, {}],        # dsa_select
        ["fusion.600 = fusion", 600.0, 50.0, {}],         # mla_attn
        ["gmm.12 = custom-call:tpu_custom_call", 700.0, 200.0, {}],
        # the prefill: the same NAMES mean other operations there
        ["fusion.580 = fusion", 2000.0, 500.0, {}],       # not in the prefill's map
        ["fusion.7 = fusion", 2500.0, 1500.0, {}],        # mla_attn in the prefill
        ["call.2 = call", 2500.0, 2000.0, {}],            # mla_attn, covers fusion.7
        ["fusion.580 = fusion", 7100.0, 100.0, {}],
        ["fusion.583 = fusion", 7350.0, 250.0, {}],
        ["fusion.583 = fusion", 8100.0, 5.0, {}],         # inside another program
    ]
    return [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": ms}, {"name": "XLA Ops", "events": ops}]}]


# the prefill in two versions (two prompt lengths) that number their
# instructions differently: fusion.7 is attention's in the one the trace
# ran and the experts' in the other, fusion.580 the other way round
RAN = {"names": ["fusion.580", "fusion.7", "call.2"],
       "scopes": {"fusion.7": "mla_attn", "call.2": "mla_attn"}}
OTHER = {"names": ["fusion.580", "fusion.7", "fusion.9"],
         "scopes": {"fusion.580": "mla_attn", "fusion.9": "dsa_index"}}
MAPS = {"decode_step_rowwise": [dsa_trace.version(HLO)], "prefill_into_slot": [OTHER, RAN]}


def test_an_execution_is_read_with_its_own_versions_scopes():
    seen = {"fusion.580", "fusion.7", "call.2"}
    assert dsa_trace.scopes_for([OTHER, RAN], seen) == RAN["scopes"]
    assert dsa_trace.scopes_for([RAN, OTHER], seen | {"not.an.instruction"}) == RAN["scopes"]
    assert dsa_trace.scopes_for([RAN, OTHER], {"fusion.9"}) == OTHER["scopes"]
    assert dsa_trace.scopes_for([], seen) == {}
    # versions that cannot be told apart give what they agree on, and a
    # name that one of them has under no scope is not agreed on
    twin = {"names": RAN["names"], "scopes": {"fusion.7": "mla_attn", "fusion.580": "dsa_index"}}
    assert dsa_trace.scopes_for([RAN, twin], seen) == {"fusion.7": "mla_attn"}
    assert dsa_trace.scopes_for([twin, RAN], seen) == {"fusion.7": "mla_attn"}


def test_the_trace_joined_with_the_maps():
    got = dsa_trace.reduce(planes(), MAPS)
    assert got["decode_executions_traced"] == 2
    assert got["sparse_attn_decode_device_s"] == pytest.approx((550 + 350) * 1e-9)
    assert got["sparse_attn_device_s"] == pytest.approx((550 + 350 + 2000) * 1e-9)
    assert got["dsa_scope_s.dsa_index"] == pytest.approx(200e-9)
    assert got["dsa_scope_s.dsa_select"] == pytest.approx(650e-9)
    assert got["dsa_scope_s.mla_attn"] == pytest.approx(2050e-9)
    assert dsa_trace.facts("/nonexistent/trace/dir") == {}


def test_the_four_new_readers():
    facts = dict(dsa_trace.reduce(planes(), MAPS), max_slots=32, dsa_layers=6,
                 decode_steps_in_window=1000, dsa_index_key_bytes=256,
                 dsa_latent_row_bytes=1152,
                 dsa_visible_step=1000 * 32 * 6 * 7168, dsa_selected_step=1000 * 32 * 6 * 2048,
                 dsa_selected_share_mean=30.5, moe_held_assignment_share=6.1)
    ctx = {"facts": facts, "busy_s": 6000e-9, "window_s": 8110e-9,
           "peak": {"hbm_bytes_per_s": 819e9}}
    assert reader("sparse_attn_time_share.glm")(ctx) == pytest.approx(100 * 2900 / 6000)
    # 805,576,704 bytes a step (the hand count above) x 2 executions / 900 ns / 819 GB/s
    assert reader("sparse_attn_hbm_roofline_share.glm")(ctx) == pytest.approx(
        100 * 805_576_704 * 2 / 819e9 / 900e-9)
    assert reader("dsa_selected_share_mean.glm")(ctx) == 30.5
    assert reader("moe_held_assignment_share")(ctx) == 6.1
    # a program without the path (the parent commit): nothing to read, no raise
    bare = {"facts": {"max_slots": 32}, "busy_s": 1.0, "window_s": 2.0,
            "peak": {"hbm_bytes_per_s": 819e9}}
    for name in ("sparse_attn_time_share.glm", "sparse_attn_hbm_roofline_share.glm",
                 "dsa_selected_share_mean.glm", "moe_held_assignment_share"):
        assert reader(name)(bare) is None


# ---- the job ------------------------------------------------------------------

def test_the_windows_counters_become_the_readers_facts():
    cfg = serve_dsa.dsa_config(config_file())
    before = {"moe_expert_tokens": np.zeros((5, 16), int).tolist(), "moe_layer_steps_total": 10,
              "moe_experts_touched_total": 100, "rows_stepped_total": 1000,
              "decode_steps_total": 5, "dsa_visible_step": 10, "dsa_selected_step": 10,
              "dsa_visible_run": 0, "dsa_selected_run": 0, "cache_bytes": {"ckv": 1}}
    tokens = np.full((5, 16), 2)
    tokens[0, 0] = 8
    after = {"moe_expert_tokens": tokens.tolist(), "moe_layer_steps_total": 10 + 5 * 20,
             "moe_experts_touched_total": 100 + 1000, "rows_stepped_total": 1000 + 20 * 32,
             "decode_steps_total": 25, "dsa_visible_step": 10 + 4000, "dsa_selected_step": 10 + 1000,
             "dsa_visible_run": 6000, "dsa_selected_run": 4000, "cache_bytes": {"ckv": 1}}
    w = serve_dsa._window(before, after, cfg)
    assert w["moe_layer_steps"] == 100 and w["moe_assignments"] == 166
    assert w["moe_experts_touched_mean"] == 10.0
    assert w["moe_rows_per_layer_step_mean"] == 1.66
    assert w["moe_routed_assignments"] == 640 * 5 * 8
    assert w["moe_held_assignment_share"] == pytest.approx(100 * 166 / 25600)
    assert w["moe_dropped"] == 0
    assert w["decode_steps_in_window"] == 20
    assert w["dsa_visible_step"] == 4000 and w["dsa_selected_run"] == 4000
    assert w["dsa_selected_share_mean"] == pytest.approx(100 * 5000 / 10000)
    assert (w["dsa_index_key_bytes"], w["dsa_latent_row_bytes"], w["dsa_layers"]) == (256, 1152, 6)
    with pytest.raises(RuntimeError):
        serve_dsa._window(before, dict(before), cfg)


def test_what_decides_correct():
    tol = config_file()["reference_tolerance"]
    good = {"err": {"rms": tol["rms"] / 2, "max": tol["max"] / 2}, "set_size_ok": True,
            "set_overlap": (1 + tol["set_overlap_min"]) / 2, "sets_equal": tol["sets_equal_min"],
            "swap_rate": tol["swap_rate_max"] / 2,
            "swapped_margin_max": tol["swapped_margin_max"] / 2, "twin_logits_differing": 0}
    assert serve_dsa.passes(good, tol)
    assert not serve_dsa.passes({**good, "err": {"rms": tol["rms"] * 1.01, "max": 0.0}}, tol)
    assert not serve_dsa.passes({**good, "err": {"rms": 0.0, "max": tol["max"] * 1.01}}, tol)
    assert not serve_dsa.passes({**good, "set_size_ok": False}, tol)      # attends to more
    assert not serve_dsa.passes({**good, "set_overlap": tol["set_overlap_min"] - 0.001}, tol)
    assert not serve_dsa.passes({**good, "sets_equal": tol["sets_equal_min"] / 2}, tol)
    assert not serve_dsa.passes({**good, "swap_rate": tol["swap_rate_max"] * 2}, tol)
    # a clear call overturned, though few are
    assert not serve_dsa.passes({**good, "swapped_margin_max": tol["swapped_margin_max"] * 1.01}, tol)
    # the choices came from a program that is not the served one to the bit
    assert not serve_dsa.passes({**good, "twin_logits_differing": 1}, tol)
    # the limits lie between the chip's two readings (reference/glm_dsa.py)
    assert 0.051 < tol["rms"] < 0.163 and 0.26 < tol["max"] < 0.70
    assert 0.9827 < tol["set_overlap_min"] < 0.9969 and 0.008 < tol["sets_equal_min"] < 0.084
    assert 0.119 < tol["swap_rate_max"] < 0.70 and 0.0150 < tol["swapped_margin_max"] < 0.0252


def test_the_mantissa_cut_is_float8s():
    x = jnp.asarray(np.random.default_rng(0).normal(0, 0.02, 4096), jnp.bfloat16)
    y = serve_dsa.cut_mantissa({"w": x, "n": jnp.arange(3)})
    assert y["w"].dtype == jnp.bfloat16 and np.array_equal(y["n"], np.arange(3))
    a, b = np.asarray(x, np.float32), np.asarray(y["w"], np.float32)
    rel = np.abs(b - a) / np.abs(a)
    assert 2.0 ** -6 < rel.mean() < rel.max() <= 2.0 ** -4 + 1e-6
    # three bits of mantissa: every value is a multiple of an eighth of its power of two
    mant, _ = np.frexp(b)
    assert np.array_equal(mant * 16, np.round(mant * 16))
    assert np.array_equal(np.asarray(serve_dsa.cut_mantissa(
        {"w": jnp.asarray([1.0, 1.06, 1.07, -3.3], jnp.float32)})["w"]), [1.0, 1.0, 1.125, -3.25])


def test_a_program_without_the_fields_is_refused_at_import(monkeypatch):
    """What the parent commit does with this PR's benchmark files beside
    it: ``run.py`` imports the job before it starts a cluster, and the
    import raises."""
    import dataclasses

    from ray_tpu.models import llama

    @dataclasses.dataclass(frozen=True)
    class Before:
        vocab_size: int = 1
        num_experts: int = 0
        qk_norm: bool = False

    monkeypatch.setattr(llama, "LlamaConfig", Before)
    try:
        with pytest.raises(RuntimeError, match="kv_lora_rank"):
            importlib.reload(serve_dsa)
    finally:
        monkeypatch.undo()
        importlib.reload(serve_dsa)
    assert serve_dsa.DSA_FIELDS[1] == "kv_lora_rank"


@pytest.mark.parametrize("module,name", [
    ("chipbench.jobs.serve_moe", "_moe_window"), ("chipbench.jobs.serve_moe", "MoeReplica"),
    ("chipbench.loadgen", "summarize"),
])
def test_a_harness_without_what_the_job_exchanges_is_refused_at_import(monkeypatch, module, name):
    """``run`` is ``serve_moe.run`` with six of that module's names
    exchanged: a later ``serve_moe`` that renames one must not be served
    with its own, silently."""
    monkeypatch.delattr(importlib.import_module(module), name)
    try:
        with pytest.raises(RuntimeError, match=name):
            importlib.reload(serve_dsa)
    finally:
        monkeypatch.undo()
        importlib.reload(serve_dsa)
    assert set(serve_dsa._exchanged()) <= set(vars(serve_dsa.serve_moe))


@pytest.mark.limit(170)
def test_the_cell_walks_on_the_cpu_traced():
    """``--rehearse --trace 1``: toy shapes, fake chip, the whole control
    flow — replica, reference check with its scope maps, warm-up, ramp,
    window, trace, every reader — ends in one valid line."""
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed", "3000000017",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=contract.ROOT, capture_output=True, text=True, timeout=160,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = contract.validate(contract.last_line(out.stdout), CELL, 1)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"]["compiles_in_window.batch"]["value"] == 0
    assert 0 < line["metrics"]["dsa_selected_share_mean.glm"]["value"] < 100
    assert 0 < line["metrics"]["moe_held_assignment_share"]["value"] < 100
    assert "reference check at 24 + 2 tokens" in out.stderr
    # the two readings beside ``device_idle_share.batch`` the cell joined at
    # PR 58: read from the witness's record on the CPU too
    share = line["metrics"]["host_stall_share.batch"]["value"]
    assert 0 <= line["metrics"]["host_stall_outside_share.batch"]["value"] <= share < 100
    assert "keeps no record of its stops" not in out.stderr
