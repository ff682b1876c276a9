"""OLMoE through the decode replica's programs against
``chipbench/reference/olmoe.py``, at a small size on the CPU (on the
chip ``jobs/serve_moe.py`` makes the same comparison at the published
widths, before any traffic); the expert layer's cost functions by hand;
the two trace readers on hand-made planes."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import contract, moe_cost, trace_reduce as tr
from chipbench.reference import errors, within
from chipbench.reference import olmoe as ref_olmoe
from ray_tpu.models import llama

POSITIONS = [23, 24, 25]


def tolerance():
    path = os.path.join(contract.ROOT, "chipbench", "configs", "olmoe-1b-7b-l12.json")
    with open(path) as f:
        return json.load(f)["reference_tolerance"]


def small(dtype):
    """8 experts, top-2, 2 layers, MHA 4 x 16 with QK-norm."""
    return llama.LlamaConfig.tiny(
        num_kv_heads=4, mlp_dim=0, num_experts=8, experts_per_token=2,
        expert_dim=48, qk_norm=True, dtype=dtype, param_dtype=dtype,
    )


def weights(cfg, strong_experts=False):
    """Seeded weights with QK-norm scales away from 1 (at 1 a missing
    norm would still change the result, a wrong scale would not).

    ``strong_experts``: at this toy width llama.init's N(0, 0.02)
    leaves the expert layer a thousandth of the residual stream, so a
    fault in it is invisible in the logits however tight the tolerance.
    The four "must be refused" cases scale the expert matrices up until
    the layer carries about as much as attention does, which is where
    it stands at the published widths."""
    params = llama.init(jax.random.key(5), cfg)
    b = params["blocks"]
    for i, name in enumerate(("q_norm", "k_norm")):
        b[name] = (1 + 0.3 * jax.random.normal(
            jax.random.key(i + 1), b[name].shape)).astype(b[name].dtype)
    if strong_experts:
        b["w_gate"], b["w_up"], b["w_down"] = (
            b["w_gate"] * 8, b["w_up"] * 8, b["w_down"] * 16)
    return params


def system(cfg, params, seed=1, slots=3, slot=1):
    """Prefill of 24 tokens into one slot of the engine's cache, then
    two decode steps: logits at positions 23, 24, 25 and the tokens."""
    seq = np.random.default_rng(seed).integers(0, cfg.vocab_size, 24).tolist()
    cache = llama.init_cache(cfg, slots, 64)
    logits, cache = llama.prefill_into_slot(
        params, jnp.asarray([seq], jnp.int32), cache, jnp.int32(slot), cfg)
    out = [logits[0]]
    for _ in range(2):
        seq.append(int(jnp.argmax(out[-1])))
        tokens = np.zeros((slots,), np.int32)
        pos = np.zeros((slots,), np.int32)
        tokens[slot], pos[slot] = seq[-1], len(seq) - 1
        logits, cache = llama.decode_step_rowwise(
            params, jnp.asarray(tokens), cache, jnp.asarray(pos), cfg)
        out.append(logits[slot])
    return jnp.stack(out), seq


def reference(cfg, params, seq, positions=POSITIONS, **kw):
    return ref_olmoe.forward(
        params, jnp.asarray(seq, jnp.int32), cfg.rope_theta, cfg.rms_eps,
        kw.pop("top_k", cfg.experts_per_token), positions=positions,
        head_rows=64, **kw)


def test_float32_prefill_decode_and_forward_match_the_reference_and_its_choices():
    cfg = small(jnp.float32)
    params = weights(cfg)
    logits, seq = system(cfg, params)
    ref, routing = reference(cfg, params, seq)
    assert ref.shape == (3, cfg.vocab_size)
    assert routing["experts"].shape == (2, 26, 2) and routing["margin"].shape == (2, 26)
    assert within(errors(logits, ref), ref_olmoe.FLOAT32_TOLERANCE)
    tokens = jnp.asarray([seq], jnp.int32)
    full = llama.forward(params, tokens, cfg)[0][jnp.asarray(POSITIONS)]
    assert within(errors(full, ref), ref_olmoe.FLOAT32_TOLERANCE)
    # the same experts, in the same order of falling probability
    chose = np.asarray(llama.expert_choices(params, tokens, cfg))[:, 0]
    np.testing.assert_array_equal(chose, np.asarray(routing["experts"]))
    assert float(routing["margin"].min()) > 0


@pytest.mark.parametrize("fault", [
    "one_expert_left_out", "renormalised_weights", "no_qk_norm", "shifted_position",
])
def test_the_chips_tolerance_refuses_a_wrong_expert_layer_in_bfloat16(fault):
    """bf16 system against the float32 reference: inside the tolerance
    the chip is held to, and outside it as soon as the reference is one
    that leaves an expert out (top-1 of top-2), renormalises the top-k
    weights, skips the QK-norm, or is read one position early."""
    cfg = small(jnp.bfloat16)
    params = weights(cfg, strong_experts=True)
    logits, seq = system(cfg, params)
    tol = tolerance()
    honest = errors(logits, reference(cfg, params, seq)[0])
    assert within(honest, tol), honest
    wrong = {
        "one_expert_left_out": dict(top_k=1),
        "renormalised_weights": dict(renormalise=True),
        "no_qk_norm": dict(qk_norm=False),
        "shifted_position": dict(positions=[22, 23, 24]),
    }[fault]
    broken = errors(logits, reference(cfg, params, seq, **wrong)[0])
    assert not within(broken, tol), (fault, broken)
    assert broken["rms"] > 4 * honest["rms"]


def test_the_chips_tolerance_refuses_the_next_precision_down():
    """The configuration states bf16.  The same weights rounded to the
    nearest precision below it (float8, e4m3: 3 bits of mantissa for
    bf16's 7) and served in bf16 must come out as not correct."""
    cfg = small(jnp.bfloat16)
    params = weights(cfg, strong_experts=True)
    logits, seq = system(cfg, params)
    ref = reference(cfg, params, seq)[0]
    rounded = jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), params)
    logits8, _ = system(cfg, rounded)
    tol = tolerance()
    assert within(errors(logits, ref), tol)
    # compared at the honest run's tokens: positions 23..25 of ``seq``
    lower = errors(logits8[:1], ref[:1])
    assert not within(lower, tol), lower


def test_moe_cost_by_hand():
    # 4 rows, K = 8, N = 16: 2 * 4 * 8 * 16 operations
    assert moe_cost.gmm_flops(4, 8, 16) == 1024
    # bf16: 4 x 8 in, 3 touched matrices of 8 x 16, 4 x 16 out
    assert moe_cost.gmm_bytes(4, 8, 16, 3) == 2 * (32 + 3 * 128 + 64)
    assert moe_cost.gmm_bytes(4, 8, 16, 3, itemsize=4) == 4 * (32 + 384 + 64)
    # a layer-step: gate and up (E -> M), down (M -> E)
    rows, e, m, touched = 256, 2048, 1024, 63.1
    layer = moe_cost.expert_layer_bytes(rows, e, m, touched)
    assert layer == 2 * (
        2 * (rows * e + touched * e * m + rows * m)
        + (rows * m + touched * m * e + rows * e))
    assert moe_cost.expert_layer_flops(rows, e, m) == 3 * 2 * rows * e * m
    assert moe_cost.mean_gmm_call_bytes(rows, e, m, touched) == layer / 3
    # OLMoE's decode step: 63.1 experts x 3 x 4.19 MB and next to nothing else
    assert layer == pytest.approx(63.1 * 3 * 2048 * 1024 * 2, rel=0.01)
    # fewer experts touched, fewer bytes: never from an assumed 64
    assert moe_cost.expert_layer_bytes(rows, e, m, 40) < 0.65 * layer


def _reader(name):
    spec = importlib.util.spec_from_file_location(name, contract.reader_path(name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _plane(ops):
    return {"name": "/device:TPU:0", "lines": [
        {"name": tr.MODULES_LINE, "events": []},
        {"name": tr.OPS_LINE, "events": [list(o) + [{}] for o in ops]},
    ]}


GMM = "gmm.13 = custom-call:" + tr.PALLAS_TARGET


def test_gmm_readers_on_a_hand_made_trace():
    # 1 ms window: two kernel executions of 200 us each, one other op
    planes = [_plane([
        (GMM, 0.0, 200e3), ("fusion.7 = fusion", 200e3, 300e3),
        (GMM.replace(".13", ".14"), 600e3, 200e3), ("copy.1 = copy", 990e3, 10e3),
    ])]
    busy_s, window_s = tr.busy(planes)
    facts = {"moe_experts_touched_mean": 50.0, "moe_rows_per_layer_step_mean": 256.0,
             "moe_embed": 2048, "moe_expert_dim": 1024, "moe_itemsize": 2}
    ctx = {"planes": planes, "busy_s": busy_s, "window_s": window_s, "facts": facts,
           "peak": {"hbm_bytes_per_s": 819e9}}
    assert _reader("gmm_time_share")(ctx) == pytest.approx(100 * 400 / 710)
    per_call = moe_cost.mean_gmm_call_bytes(256.0, 2048, 1024, 50.0)
    assert _reader("gmm_hbm_roofline_share")(ctx) == pytest.approx(
        100 * 2 * per_call / 819e9 / 400e-6)
    assert _reader("moe_experts_touched_mean")(ctx) == 50.0


def test_gmm_readers_find_nothing_in_a_program_without_the_expert_layer():
    planes = [_plane([("fusion.7 = fusion", 0.0, 300e3),
                      ("flash_fwd.3 = custom-call:" + tr.PALLAS_TARGET, 300e3, 100e3)])]
    busy_s, window_s = tr.busy(planes)
    ctx = {"planes": planes, "busy_s": busy_s, "window_s": window_s, "facts": {},
           "peak": {"hbm_bytes_per_s": 819e9}}
    for name in ("gmm_time_share", "gmm_hbm_roofline_share",
                 "moe_experts_touched_mean", "moe_expert_load_max_over_mean"):
        assert _reader(name)(ctx) is None, name


def test_the_job_refuses_at_import_a_program_without_expert_fields(monkeypatch):
    import dataclasses
    import importlib
    import sys

    import ray_tpu.models.llama as program

    @dataclasses.dataclass(frozen=True)
    class Older:
        vocab_size: int = 1

    monkeypatch.setattr(program, "LlamaConfig", Older)
    monkeypatch.delitem(sys.modules, "chipbench.jobs.serve_moe", raising=False)
    with pytest.raises(RuntimeError, match="expert configuration"):
        importlib.import_module("chipbench.jobs.serve_moe")
    monkeypatch.undo()
    sys.modules.pop("chipbench.jobs.serve_moe", None)
    assert importlib.import_module("chipbench.jobs.serve_moe").moe_config


def test_the_configuration_file_keeps_every_published_number():
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 16,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304,
    }
    path = os.path.join(contract.ROOT, "chipbench", "configs", "olmoe-1b-7b-l12.json")
    with open(path) as f:
        ours = json.load(f)
    differs = {k for k, v in published.items() if ours.get(k, "missing") != v}
    assert differs == set(ours["reduced"]) == {"num_hidden_layers"}
    from chipbench.jobs.serve_moe import moe_config

    cfg = moe_config(ours)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.expert_dim) == (64, 8, 1024)
    assert cfg.qk_norm and cfg.num_heads == cfg.num_kv_heads == 16 and cfg.head_dim == 128
    # 12 layers: 5.24 B parameters, of which the experts are 4.83 B
    assert llama.num_params(cfg) == pytest.approx(5.24e9, rel=0.005)
