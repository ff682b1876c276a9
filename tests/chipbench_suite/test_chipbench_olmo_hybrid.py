"""The Olmo-Hybrid-7B cell's benchmark side: the configuration file against
the catalog row it was cut from and its byte arithmetic, ``BENCHMARK.json``'s
new entries (that mine are there, in this order), ``gdn_cost`` by hand, the
scope map and the new readers on hand-made planes and facts, the job's window
arithmetic, its refusal of a program without the fields, the comparison that
decides ``correct`` on a toy cache, and the cell walked on the CPU."""

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

from chipbench import contract, gdn_cost, gdn_trace
from chipbench.jobs import serve_hybrid

CELL = "serve_olmoh_doc_batch"
CONFIG = "olmo-hybrid-7b-l16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads",
          "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
          "linear_value_head_dim", "linear_conv_kernel_dim", "vocab_size")
#: the cell's thirteen per-layer quantities by the entry that holds each since
#: PR 52 (one entry for each quantity under a judged metric): eight accepted
#: ones it shares with the other cells judged on tokens/s, five of its own
SHARED = ("decode_step_device_ms_p50.batch", "prefill_device_ms_p50.batch",
          "device_idle_share.batch", "compiles_in_window.batch",
          "decode_batch_occupancy.batch", "step_dispatch_ms_p50.batch",
          "step_deliver_ms_p50.batch", "step_serve_plane_ms_p50.batch")
NEW_READERS = ("gdn_step_time_share", "gdn_step_hbm_roofline_share", "gdn_scan_time_share",
               "gdn_scan_roofline_share", "step_hbm_roofline_share")
MINE = tuple(name + ".olmoh" for name in NEW_READERS)


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
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json")
    assert cfg["reduced"] == ["num_hidden_layers"] and len(cfg["source"]) <= 200
    assert set(cfg["changed"]) == {"num_hidden_layers", "bytes"}
    assert cfg["num_hidden_layers"] == 16 and len(cfg["layer_types"]) == 32
    run = cfg["layer_types"][:16]
    assert run == (["linear_attention"] * 3 + ["full_attention"]) * 4   # four whole periods
    assert cfg["rope_parameters"] == {"rope_theta": None}
    assert cfg["serving"] == {"max_slots": 32, "max_len": 2560,
                              "max_ongoing_requests": 1024, "linear_chunk": 64}
    for setting in ("rope", "block", "qk_norm", "beta", "decay", "conv", "gated_norm",
                    "initialisation", "state_dtype", "chunk", "sampling"):
        assert setting in cfg["assumed"], setting
    for promise in ("exactly max_new_tokens", "OWN tokens only", "from the zero state",
                    "no multiple of the chunk", "nothing is shed"):
        assert promise in cfg["guarantees"], promise
    assert "holds the model WHOLE" in cfg["deployment"]
    assert "second pipeline stage" in cfg["deployment"]
    tol = cfg["reference_tolerance"]
    assert tol["check_steps"] == 32 and 0 < tol["rms"] < tol["max"]
    assert 0 < tol["state_low_bits_min"] < 1 and "honest" in tol["why"]


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="the catalog is not installed here")
def test_every_number_of_the_catalog_row_is_kept_or_listed():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Olmo-Hybrid-7B")
    cfg = config_file()
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k, "absent") != v]
    assert differs == cfg["reduced"] == ["num_hidden_layers"]
    for width in WIDTHS:
        assert cfg[width] == row["config"][width], width


def test_the_program_gets_the_published_block_and_the_bytes_add_up():
    from ray_tpu.models import llama

    cfg = config_file()
    c = serve_hybrid.hybrid_config(cfg)
    assert (c.embed_dim, c.num_heads, c.num_kv_heads, c.head_dim, c.mlp_dim) == (
        3840, 30, 30, 128, 11008)
    assert (c.num_layers, c.linear_layers, c.kv_layers) == (16, 12, 4)
    assert (c.linear_num_heads, c.linear_key_head_dim, c.linear_value_head_dim,
            c.linear_conv_kernel, c.linear_chunk) == (30, 96, 192, 4, 64)
    assert c.rope_theta is None and c.post_norm and c.qk_norm is True
    assert c.linear_neg_eigval and c.rms_eps == 1e-6 and not c.tie_embeddings
    # the byte arithmetic of ``changed``: the program's tree, the cost
    # functions and the file say the same
    n = llama.num_params(c)
    assert n == gdn_cost.held_params(cfg) == 4_100_788_944
    shapes = jax.eval_shape(lambda: llama.init(jax.random.key(0), c))

    def layer(stack, layers):
        return sum(math.prod(a.shape) for a in jax.tree.leaves(shapes[stack])) // layers

    assert layer("gdn_blocks", 12) == gdn_cost.linear_params(cfg) == 215_570_172
    assert layer("blocks", 4) == gdn_cost.full_params(cfg) == 185_809_920
    for number in ("4,100,788,944", "215,570,172", "185,809,920", "88,750,332",
                   "61,440", "26,542,080"):
        assert number in cfg["changed"]["bytes"], number
    cache = jax.eval_shape(lambda: llama.init_cache(c, 32, 2560))
    assert {"k", "v", "gdn_state", "gdn_conv", "gdn_counts"} <= set(cache)   # a later PR may count more

    def held(*names):
        return sum(math.prod(cache[k].shape) * cache[k].dtype.itemsize for k in names)

    assert held("k", "v") == 32 * 2560 * gdn_cost.kv_bytes_per_token(cfg) == 5_033_164_800
    assert held("gdn_state") == 32 * gdn_cost.state_bytes(cfg) == 849_346_560
    assert held("gdn_conv") == 32 * gdn_cost.conv_bytes(cfg) == 26_542_080
    # 88% of the chip's 16 GB live
    assert 0.87 < (2 * n + held("k", "v", "gdn_state", "gdn_conv")) / 16e9 < 0.89


def test_my_benchmark_entries_are_there_in_this_order():
    """My entries are there, with these cells and this reader: by name and by order among themselves — never by position from the
    end: a later PR appends behind them."""
    bench = contract.load_benchmark()
    assert contract.check_benchmark(bench) == []
    entry = contract.config_entry(bench, CONFIG)
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["reduced"] == ["num_hidden_layers"] and entry["source"] == config_file()["source"]
    cell = contract.cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "hybrid_doc_closed64", 1)
    assert [w["config"] for w in bench["workloads"]].count(CONFIG) == 1
    tokens = next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert CELL in tokens["workloads"]
    assert tokens["workloads"].index(CELL) > tokens["workloads"].index(
        "serve_sdar_diffusion_batch")
    names = [m["name"] for m in bench["per_layer"]]
    at = [names.index(name) for name in MINE]
    assert at == list(range(at[0], at[0] + len(MINE)))          # one run, unbroken
    assert at[0] > max(i for i, n in enumerate(names) if n.endswith(".sdar"))  # behind PR 36's
    for name in MINE + SHARED:
        m = bench["per_layer"][names.index(name)]
        assert CELL in m["workloads"] and m["moves"] == "serve_tokens_per_s"
        assert contract.reader_path(name) is not None, name
        if "roofline" in name:
            assert m["unit"] == "%" and m["better"] == "higher"
    for name in NEW_READERS:   # readers of their own, not a suffix's
        assert contract.reader_path(name + ".olmoh").endswith(name + ".py")
    setup = [m for m in bench["per_layer"] if m["name"].startswith("setup_")]
    assert len(setup) == 6 and all(CELL in m["workloads"] for m in setup)
    # mine are among them: a later PR declares further quantities in this cell
    assert set(MINE) | set(SHARED) | {m["name"] for m in setup} <= set(
        contract.declared_metrics(bench, CELL, 1))
    assert set(contract.declared_metrics(bench, CELL, 0)) == {"serve_tokens_per_s", "setup_s"}


def test_the_traffic_is_the_issues():
    with open(os.path.join(contract.ROOT, "chipbench", "traffic",
                           "hybrid_doc_closed64.json")) as f:
        t = json.load(f)
    assert (t["job"], t["loop"], t["clients"]) == ("serve_hybrid", "closed", 64)
    assert t["prompt_len"] == {"kind": "cycle", "values": [1024, 2048]}
    assert t["new_tokens"] == {"kind": "fixed", "value": 512} and t["drain_s"] == 0
    serving = config_file()["serving"]
    assert 2048 + 512 <= serving["max_len"]
    assert 64 * t["requests_per_client"] > 3 * 70          # more than a window finishes
    assert serve_hybrid.RAGGED_PROMPT_LEN % serving["linear_chunk"]


# ---- the cost functions, by hand ---------------------------------------------

def test_gdn_cost_against_hand_counts():
    cfg = config_file()
    assert gdn_cost.layers(cfg, gdn_cost.LINEAR) == 12 and gdn_cost.layers(cfg, gdn_cost.FULL) == 4
    assert gdn_cost.conv_channels(cfg) == 2 * 30 * 96 + 30 * 192 == 11_520
    assert gdn_cost.ffn_params(cfg) == 3 * 3840 * 11008 + 2 * 3840 == 126_819_840
    assert gdn_cost.linear_params(cfg) == (
        2 * 3840 * 2880 + 2 * 3840 * 5760 + 5760 * 3840 + 2 * 3840 * 30
        + 4 * 11520 + 30 + 30 + 192 + 126_819_840) == 215_570_172
    assert gdn_cost.full_params(cfg) == 4 * 3840 * 3840 + 2 * 3840 + 126_819_840 == 185_809_920
    assert gdn_cost.step_params(cfg) == (
        12 * 215_570_172 + 4 * 185_809_920 + 3840 + 100352 * 3840) == 3_715_437_264
    assert gdn_cost.held_params(cfg) == 3_715_437_264 + 100352 * 3840 == 4_100_788_944
    assert gdn_cost.kv_bytes_per_token(cfg) == 4 * 2 * 3840 * 2 == 61_440
    assert gdn_cost.state_bytes(cfg) == 12 * 30 * 96 * 192 * 4 == 26_542_080
    assert gdn_cost.conv_bytes(cfg) == 12 * 3 * 11520 * 2 == 829_440
    # a step of 32 live rows at 1,800 keys: 7.4 GB of weights, 3.5 GB of K/V,
    # 1.7 GB of state, as ISSUE 46 reckons it
    visible = 4 * 32 * 1800
    step = gdn_cost.step_bytes(cfg, visible, 32)
    assert step == (2 * 3_715_437_264 + (visible + 32 * 4) * 15_360
                    + 2 * 32 * (26_542_080 + 829_440))
    assert 12.5e9 < step < 12.9e9
    # the chunked rule, a token and layer: 30 heads x (32 x (288 + 384) + 3 x 96 x 192)
    assert gdn_cost.scan_flops(cfg, 1) == 2 * 30 * (32 * 672 + 55_296) == 4_608_000
    assert gdn_cost.scan_bytes(cfg, 1) == 4 * 30 * (192 + 384 + 2) == 69_360
    assert gdn_cost.scan_flops(cfg, 12 * 2048) == 12 * 2048 * 4_608_000


# ---- the scopes and the readers ----------------------------------------------

def hlo(program, lines):
    body = "\n".join(
        f'  %{name} = f32[8] fusion(%p0), metadata={{op_name="jit({program})/while/body/'
        f'closed_call/decode_attn/{path}"}}' for name, path in lines)
    return f"HloModule jit_{program}\n{body}\n  ROOT %tuple.9 = (f32[8]) tuple(%p0)\n"


DECODE = hlo("decode_step_rowwise", [
    ("fusion.1", "gdn_proj/rse,ehd->rshd/dot_general"), ("fusion.2", "gdn_step/reduce_sum"),
    ("fusion.3", "gdn_step/add"), ("fusion.4", "gdn_out/rshv,hve->rse/dot_general"),
    ("fusion.5", "kv_decode/pallas_call")])
SHORT = hlo("prefill_into_slot", [
    ("fusion.1", "gdn_proj/conv"), ("fusion.7", "gdn_scan/while/body/dot_general"),
    ("fusion.8", "gdn_out/mul")])
LONG = hlo("prefill_into_slot", [
    ("fusion.1", "gdn_proj/conv"), ("fusion.11", "gdn_scan/while/body/dot_general"),
    ("fusion.12", "gdn_scan/bhnij,bhnjk->bhnik/dot_general"), ("fusion.8", "decode_mlp/mul")])


def plane(ops, modules):
    return {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops},
    ]}


def test_the_recurrent_layers_parts_are_found_by_their_scopes():
    v = gdn_trace.version(DECODE)
    assert v["scopes"] == {"gdn_proj": ["fusion.1"], "gdn_step": ["fusion.2", "fusion.3"],
                           "gdn_scan": [], "gdn_out": ["fusion.4"]}
    versions = {"decode_step_rowwise": [v],
                "prefill_into_slot": [gdn_trace.version(SHORT), gdn_trace.version(LONG)]}
    ops = [("fusion.1 = fusion", 0, 100, {}), ("fusion.2 = fusion", 100, 300, {}),
           ("fusion.3 = fusion", 500, 200, {}), ("fusion.5 = custom-call", 800, 100, {}),
           # a prefill of the long version, then one of the short
           ("fusion.11 = fusion", 2000, 4000, {}), ("fusion.12 = fusion", 6000, 1000, {}),
           ("fusion.8 = fusion", 7000, 500, {}),
           ("fusion.7 = fusion", 10_000, 2000, {}), ("fusion.8 = fusion", 12_000, 100, {})]
    modules = [("jit_decode_step_rowwise(123)", 0, 1000, {}),
               ("jit_prefill_into_slot(77)", 2000, 6000, {}),
               ("jit_prefill_into_slot(78)", 10_000, 3000, {})]
    got = gdn_trace.reduce([plane(ops, modules)], versions, [1024, 2048])
    assert got["decode_executions_traced"] == 1 and got["prefill_executions_traced"] == 2
    assert got["decode_device_s_traced"] == pytest.approx(1000e-9)
    assert got["prefill_device_s_traced"] == pytest.approx(9000e-9)
    assert got["prefill_tokens_traced"] == 2048 + 1024      # each by its version
    assert got["gdn_step_device_s"] == got["gdn_step_decode_device_s"] == pytest.approx(500e-9)
    assert got["gdn_scan_device_s"] == pytest.approx(7000e-9)
    # fusion.8 is gdn_out's in the short version alone
    assert got["gdn_out_device_s"] == pytest.approx(100e-9)
    assert got["gdn_proj_device_s"] == pytest.approx(100e-9)
    # the other kinds' scopes are as they were
    from chipbench import mtp_trace

    assert mtp_trace.SCOPES == ("mtp_draft", "mla_attn")


def facts(**kw):
    cfg = config_file()
    model = {k: v for k, v in cfg.items()
             if isinstance(v, (int, float)) or k == "layer_types"}
    f = {"model": model, "max_slots": 32, "linear_chunk": 64,
         "decode_steps_in_window": 1500, "kv_keys_visible_step": 1500 * 4 * 32 * 1800,
         "gdn_state_bytes_step": 1500 * 2 * 32 * 26_542_080,
         "gdn_rows_stepped": 1500 * 32 * 12, "gdn_tokens_scanned": 70 * 1536 * 12}
    f.update(kw)
    return f


def test_the_readers_on_recorded_facts():
    peak = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    planes = [plane([("fusion.2 = fusion", 0, 4_000_000, {})],
                    [("jit_decode_step_rowwise(1)", 0, 20_000_000, {})])]
    ctx = {"facts": facts(gdn_step_decode_device_s=0.45, decode_device_s_traced=2.0,
                          decode_executions_traced=100, gdn_scan_device_s=0.06,
                          prefill_device_s_traced=0.4, prefill_tokens_traced=3 * 2048 + 1024),
           "busy_s": 2.5, "window_s": 3.0, "peak": peak, "planes": planes}
    assert reader("gdn_step_time_share.olmoh")(ctx) == pytest.approx(22.5)
    got = reader("gdn_step_hbm_roofline_share.olmoh")(ctx)
    assert got == pytest.approx(100 * 2 * 32 * 26_542_080 * 100 / 819e9 / 0.45) and 0 < got < 100
    assert reader("gdn_scan_time_share.olmoh")(ctx) == pytest.approx(15.0)
    token_layers = (3 * 2048 + 1024) * 12
    need = max(token_layers * 4_608_000 / 197e12, token_layers * 69_360 / 819e9)
    got = reader("gdn_scan_roofline_share.olmoh")(ctx)
    assert got == pytest.approx(100 * need / 0.06) and 0 < got < 100
    step = gdn_cost.step_bytes(ctx["facts"]["model"], 4 * 32 * 1800, 32)
    got = reader("step_hbm_roofline_share.olmoh")(ctx)
    assert got == pytest.approx(100 * step / 819e9 / 0.020) and 70 < got < 85
    assert reader("decode_step_device_ms_p50.batch")(ctx) == pytest.approx(20.0)


def test_the_new_readers_find_nothing_on_a_program_without_the_layers():
    """What the driver's traced run of the parent commit meets: no facts,
    no scopes — None, never an exception."""
    ctx = {"facts": {"max_slots": 32}, "busy_s": 3.0, "window_s": 3.1,
           "peak": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "planes": [plane([], [("jit_decode_step_rowwise(1)", 0, 16_000_000, {})])]}
    for name in NEW_READERS:
        assert reader(name + ".olmoh")(ctx) is None, name


# ---- the job -----------------------------------------------------------------

def test_the_window_is_the_second_stats_call_less_the_first(tmp_path):
    def call(steps, prefills):
        return {"gdn_rows_stepped": steps * 32 * 12, "gdn_tokens_scanned": prefills * 1536 * 12,
                "gdn_tokens_padded": 0, "gdn_state_bytes_step": steps * 2 * 32 * 26_542_080,
                "kv_keys_visible_step": steps * 1000, "kv_keys_read_step": steps * 1200,
                "decode_steps_total": steps, "rows_stepped_total": steps * 32,
                "admitted_total": prefills, "peak_bytes_in_use": 1, "platform": "tpu"}

    path = tmp_path / "counters.jsonl"
    with open(path, "w") as f:
        for stats in (call(40, 5), call(1540, 75), call(1560, 76)):
            f.write(json.dumps(serve_hybrid._counted(stats)) + "\n")
    w = serve_hybrid._window(str(path))
    assert w["decode_steps_in_window"] == 1500 and w["prefills_in_window"] == 70
    assert w["gdn_state_bytes_step"] == 1500 * 2 * 32 * 26_542_080
    assert w["gdn_tokens_scanned"] == 70 * 1536 * 12 and w["kv_keys_visible_step"] == 1_500_000
    assert "peak_bytes_in_use" not in w and "platform" not in w
    with open(path, "w") as f:
        f.write(json.dumps(serve_hybrid._counted(call(40, 5))) + "\n")
    with pytest.raises(RuntimeError, match="1 stats"):
        serve_hybrid._window(str(path))


def test_a_program_without_the_recurrent_layers_is_refused_at_import():
    """What the parent commit does with the new cell: the job's import
    fails, before any cluster or chip."""
    code = (
        "import dataclasses, sys\n"
        "from ray_tpu.models import llama\n"
        "fields = [(f.name, f.type, f) for f in dataclasses.fields(llama.LlamaConfig)"
        " if not f.name.startswith(('layer_types', 'linear_', 'post_norm'))]\n"
        "llama.LlamaConfig = dataclasses.make_dataclass('LlamaConfig', fields, frozen=True)\n"
        "import chipbench.jobs.serve_hybrid\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=contract.ROOT)
    run = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert run.returncode != 0
    assert "cannot run a configuration with linear-attention layers" in run.stderr


@pytest.fixture(scope="module")
def checked():
    """A toy cache's check run, as ``HybridReplica.check_reference`` makes
    it: the two served programs, then the reference."""
    from ray_tpu.models import llama

    cfg = dict(config_file(), **serve_hybrid.REHEARSAL_MODEL)
    cfg.update(dtype="float32", param_dtype="float32",
               serving=dict(cfg["serving"], linear_chunk=4))
    config = serve_hybrid.hybrid_config(cfg)
    params = serve_hybrid.make_weights(cfg, 5, True)
    params = jax.tree.map(lambda a: a * 4 if a.ndim > 2 else a, params)
    cache, out = serve_hybrid.system_run(
        params, config, llama.init_cache(config, 4, 64), 4, 5, [16, 32, 27], 8)
    return params, config, cache, out


def test_the_comparison_passes_honest_and_refuses_what_it_must(checked):
    params, config, _cache, out = checked
    tolerance = {"rms": 3e-4, "max": 3e-3, "state_low_bits_min": 0.5}
    got = serve_hybrid.against_reference(params, config, out)
    assert [len(r["ids"]) for r in out["rows"]] == [16 + 8, 32 + 8, 27 + 8]
    assert all(r["logits"].shape == (9, 512) for r in out["rows"])
    assert got["state_low_bits"] > 0.99 and serve_hybrid.passes(got, tolerance), got
    # a state that passed through bfloat16; logits off; a token that was not fed
    assert not serve_hybrid.passes(dict(got, state_low_bits=0.0), tolerance)
    rows = out["rows"]
    noisy = dict(rows[1], logits=rows[1]["logits"] * 1.05)
    off = serve_hybrid.against_reference(params, config, dict(out, rows=[rows[0], noisy, rows[2]]))
    assert not serve_hybrid.passes(off, tolerance)
    other = dict(rows[2], ids=rows[2]["ids"][:20] + [(rows[2]["ids"][20] + 1) % 512]
                 + rows[2]["ids"][21:])
    off = serve_hybrid.against_reference(params, config, dict(out, rows=[rows[0], rows[1], other]))
    assert off["err"]["max"] > 10 * got["err"]["max"] and not serve_hybrid.passes(off, tolerance)


def test_the_first_check_row_is_a_slot_that_served_another_request(checked):
    """Its logits are those of a run in which no request came before it."""
    from ray_tpu.models import llama

    params, config, _cache, out = checked
    ids = out["rows"][0]["ids"]
    fresh, _ = llama.prefill_into_slot(
        params, jnp.asarray([ids[:16]], jnp.int32), llama.init_cache(config, 4, 64),
        jnp.int32(0), config)
    np.testing.assert_array_equal(out["rows"][0]["logits"][0], np.asarray(fresh[0]))


def test_a_state_rounded_between_calls_shows_in_its_low_bits(checked):
    from chipbench.jobs.serve_dsa import cut_mantissa
    from ray_tpu.models import llama

    params, config, _cache, _out = checked

    def through_bfloat16(cache):
        return dict(cache, gdn_state=cut_mantissa(cache["gdn_state"], 7))

    _, out = serve_hybrid.system_run(
        params, config, llama.init_cache(config, 4, 64), 4, 5, [16, 32, 27], 2,
        through_bfloat16)
    assert out["state_low_bits"] == 0.0


@pytest.mark.limit(170)
@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_walks_on_the_cpu(trace):
    """``--rehearse``: toy shapes, fake chip, the whole control flow —
    replica, reference check with its scope maps, warm-up, ramp, window,
    the counters' file, trace, every reader — ends in one valid line."""
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed", "3000000017",
         "--seconds", "3", "--trace", str(trace), "--rehearse"],
        cwd=contract.ROOT, capture_output=True, text=True, timeout=160,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = contract.validate(contract.last_line(out.stdout), CELL, trace)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert "reference check at [16, 32, 27] + 4 steps" in out.stderr
    assert '"gdn_rows_stepped": ' in out.stderr and '"decode_steps_in_window": ' in out.stderr
    if trace:
        assert line["metrics"]["compiles_in_window.batch"]["value"] == 0
        assert line["metrics"]["step_hbm_roofline_share.olmoh"]["value"] > 0
        # the two readings beside ``device_idle_share.batch`` the cell joined at
        # PR 58: read from the witness's record on the CPU too
        share = line["metrics"]["host_stall_share.batch"]["value"]
        assert 0 <= line["metrics"]["host_stall_outside_share.batch"]["value"] <= share < 100
        assert "keeps no record of its stops" not in out.stderr
