"""Parameters, bytes and operations of a decoder whose layers are of two
mixer kinds — the gated delta rule (``linear_attention``) and softmax
attention over a K/V cache (``full_attention``) — counted from the
configuration's numbers and from what the program counted, whatever
implements them.  The benchmark's own copy, like ``flops.py``,
``moe_cost.py``, ``mla_cost.py`` and ``gqa_cost.py``.

``cfg`` is the configuration file's keys (``chipbench/configs/
olmo-hybrid-7b-l16.json``); the layers that are run are the first
``num_hidden_layers`` entries of its ``layer_types``.

A DECODE STEP has to read every weight matrix of every layer and the
output head once (the embedding gives 32 rows), the K and V rows of every
key its live rows can see in the FULL layers, and, in the LINEAR layers,
every row's recurrent state — read once and written once, whoever owns
the slot: the program steps all rows — and its convolution tail likewise.
Memory-bound throughout: 32 token rows do 32 multiply-adds a weight, the
state's update two a value.

A PREFILL's CHUNKED RULE (``scan_flops`` / ``scan_bytes``, a token and a
linear layer): inside a chunk of C tokens the lower halves of K K^T and Q
K^T (C / 2 x d_k multiply-adds each a token and head), the triangular
solve applied to the values and to the decayed keys (C / 2 x (d_v + d_k)),
the chunk's own part of the output (C / 2 x d_v), and against the carried
state three d_k x d_v products (the state's part of the values, of the
output, and the state's update); its bytes are q, k, v, the two gates in
and o out, float32 as the program hands them over.  The projections and
the convolution in front and the gated norm and W_o behind are the
layer's, not the rule's.
"""

from __future__ import annotations

LINEAR, FULL = "linear_attention", "full_attention"


def layers(cfg: dict, kind: str) -> int:
    """Layers of ``kind`` among those that are run."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]]).count(kind)


def conv_channels(cfg: dict) -> int:
    return (cfg["linear_num_key_heads"] * 2 * cfg["linear_key_head_dim"]
            + cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"])


def ffn_params(cfg: dict) -> int:
    """The dense SwiGLU and the block's two norms."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"] + 2 * cfg["hidden_size"]


def linear_params(cfg: dict) -> int:
    """One gated-delta-rule layer: Wq, Wk, Wv, Wz, Wo, Wa, Wb, the
    convolution's taps, A_log, dt_bias, the gated norm's scale; the SwiGLU
    and the two norms."""
    E, H = cfg["hidden_size"], cfg["linear_num_value_heads"]
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    values = H * cfg["linear_value_head_dim"]
    mixer = (2 * E * keys + 2 * E * values + values * E + 2 * E * H
             + cfg["linear_conv_kernel_dim"] * conv_channels(cfg) + 2 * H
             + cfg["linear_value_head_dim"])
    return mixer + ffn_params(cfg)


def full_params(cfg: dict) -> int:
    """One full-attention layer: Wq, Wk, Wv, Wo, the whole-projection q and
    k norms; the SwiGLU and the two norms."""
    E = cfg["hidden_size"]
    D = E // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * D, cfg["num_key_value_heads"] * D
    return 2 * E * q + 2 * E * kv + q + kv + ffn_params(cfg)


def step_params(cfg: dict) -> int:
    """Parameters a decode step reads: every layer, the final norm and the
    output head.  Left out: the embedding (a row a token)."""
    E = cfg["hidden_size"]
    return (layers(cfg, LINEAR) * linear_params(cfg) + layers(cfg, FULL) * full_params(cfg)
            + E + cfg["vocab_size"] * E)


def held_params(cfg: dict) -> int:
    """Everything the chip holds: ``step_params`` and the embedding."""
    return step_params(cfg) + cfg["vocab_size"] * cfg["hidden_size"]


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one token, over the full layers."""
    D = cfg["hidden_size"] // cfg["num_attention_heads"]
    return layers(cfg, FULL) * 2 * cfg["num_key_value_heads"] * D * itemsize


def state_bytes(cfg: dict) -> int:
    """The recurrent state of one slot, over the linear layers: a (d_k,
    d_v) float32 matrix a head."""
    return (layers(cfg, LINEAR) * cfg["linear_num_value_heads"]
            * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"] * 4)


def conv_bytes(cfg: dict, itemsize: int = 2) -> int:
    """The convolution tails of one slot, over the linear layers."""
    return (layers(cfg, LINEAR) * (cfg["linear_conv_kernel_dim"] - 1)
            * conv_channels(cfg) * itemsize)


def step_bytes(cfg: dict, keys_visible: float, slots: int, itemsize: int = 2) -> float:
    """One decode step.  ``keys_visible``: keys the step's live rows could
    see, summed over (full layer, row), as the engine counts them;
    ``slots``: rows the step updates (all of them)."""
    key = kv_bytes_per_token(cfg, itemsize) // layers(cfg, FULL)
    return (
        itemsize * step_params(cfg)
        + (float(keys_visible) + slots * layers(cfg, FULL)) * key   # read; the new rows written
        + 2 * slots * (state_bytes(cfg) + conv_bytes(cfg, itemsize))
    )


def scan_flops(cfg: dict, token_layers: float, chunk: int = 64) -> float:
    """The chunked rule over ``token_layers`` (token, linear layer)s."""
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    macs = chunk // 2 * (3 * dk + 2 * dv) + 3 * dk * dv
    return 2.0 * macs * cfg["linear_num_value_heads"] * float(token_layers)


def scan_bytes(cfg: dict, token_layers: float) -> float:
    """q, k, v, log alpha and beta in, o out, float32."""
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return 4.0 * (2 * dk + 2 * dv + 2) * cfg["linear_num_value_heads"] * float(token_layers)
