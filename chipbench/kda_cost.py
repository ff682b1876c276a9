"""Parameters, bytes and operations of a decoder of Kimi-delta-attention
layers (``linear_attention``: a recurrent state a head whose decay is a
vector over the key channels) three to one with gated full attention, every
layer over an expert layer of which this chip holds a share, counted from
the configuration's numbers and from what the program counted, whatever
implements them.  The benchmark's own copy, like ``gdn_cost.py`` and
``swa_cost.py``.

``cfg`` is the configuration file's keys (``chipbench/configs/
solar-open2-ep8-l4.json``): layer ``i`` of the first ``num_hidden_layers`` is
full attention where ``i in gqa_layers``; ``n_routed_experts`` experts are
HELD of the ``n_routed_experts_published`` the router routes over.

A DECODE STEP has to read every matrix outside the experts and the output
head once, of the held experts the matrices of those that own at least one
row, the K and V of every key its live rows can see in the full layers, and
in the KDA layers every row's recurrent state — read once and written once,
whoever owns the slot — and its convolution tail likewise.

A PREFILL's CHUNKED RULE (``scan_flops`` / ``scan_bytes``, a token and a KDA
layer): ``gdn_cost.py``'s count — inside a chunk of C tokens the lower halves
of the decayed K K^T and Q K^T, the triangular solve applied to values and
decayed keys, the chunk's own part of the output, and three d_k x d_v
products against the carried state.  A decay a channel changes no product's
size; what it adds are exponentials (C / 2 x d_k a token and head inside the
sub-chunks), which no peak is stated for and which are not counted.  Its
bytes are q, k, v, the decay (d_k a head) and beta in and o out, float32.
"""

from __future__ import annotations

LINEAR, FULL = "linear_attention", "full_attention"


def kinds(cfg: dict) -> list:
    """The kind of every layer that is run."""
    gqa = set(cfg["gqa_layers"])
    return [FULL if i in gqa else LINEAR for i in range(cfg["num_hidden_layers"])]


def layers(cfg: dict, kind: str) -> int:
    return kinds(cfg).count(kind)


def _lin(cfg: dict):
    lin = cfg["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


def conv_channels(cfg: dict) -> int:
    H, d, _ = _lin(cfg)
    return 3 * H * d


def kda_params(cfg: dict) -> int:
    """One KDA mixer: Wq, Wk, Wv, Wo, the two low-rank gates (rank = the
    head's size), Wb, the convolution's taps, A_log, dt_bias a channel, the
    gated norm's scale."""
    E = cfg["hidden_size"]
    H, d, K = _lin(cfg)
    return (4 * E * H * d + 2 * (E * d + d * H * d) + E * H + K * conv_channels(cfg)
            + H + H * d + d)


def gqa_params(cfg: dict) -> int:
    """One gated full-attention mixer: Wq, Wo, the output gate; Wk, Wv."""
    E, D = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * D, cfg["num_key_value_heads"] * D
    return 3 * E * q + 2 * E * kv


def expert_params(cfg: dict) -> int:
    """One routed (or the shared) expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def ffn_fixed_params(cfg: dict) -> int:
    """What every layer reads beside its mixer and its routed experts: the
    router and its selection bias over ALL experts, the shared expert(s), the
    two norms."""
    E, X = cfg["hidden_size"], cfg["n_routed_experts_published"]
    return E * X + X + cfg["n_shared_experts"] * expert_params(cfg) + 2 * E


def fixed_params(cfg: dict) -> int:
    """Parameters a decode step reads whatever the routing: every mixer,
    every layer's ``ffn_fixed_params``, the final norm and the output head.
    Left out: the embedding (a row a token)."""
    E = cfg["hidden_size"]
    return (layers(cfg, LINEAR) * kda_params(cfg) + layers(cfg, FULL) * gqa_params(cfg)
            + cfg["num_hidden_layers"] * ffn_fixed_params(cfg) + E + cfg["vocab_size"] * E)


def held_params(cfg: dict) -> int:
    """Everything the chip holds: ``fixed_params``, the held experts of every
    layer and the embedding."""
    return (fixed_params(cfg)
            + cfg["num_hidden_layers"] * cfg["n_routed_experts"] * expert_params(cfg)
            + cfg["vocab_size"] * cfg["hidden_size"])


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one token, over the full layers."""
    return (layers(cfg, FULL) * 2 * cfg["num_key_value_heads"] * cfg["head_dim"]
            * itemsize)


def state_bytes(cfg: dict) -> int:
    """The recurrent state of one slot, over the KDA layers: a (d, d)
    float32 matrix a head."""
    H, d, _ = _lin(cfg)
    return layers(cfg, LINEAR) * H * d * d * 4


def conv_bytes(cfg: dict, itemsize: int = 2) -> int:
    """The convolution tails of one slot, over the KDA layers."""
    _, _, K = _lin(cfg)
    return layers(cfg, LINEAR) * (K - 1) * conv_channels(cfg) * itemsize


def cache_bytes(cfg: dict, slots: int, max_len: int, itemsize: int = 2) -> dict:
    """What the engine's cache holds, by leaf."""
    return {
        "kv": kv_bytes_per_token(cfg, itemsize) * slots * max_len,
        "state": state_bytes(cfg) * slots,
        "conv": conv_bytes(cfg, itemsize) * slots,
    }


def step_bytes(cfg: dict, experts_touched: float, keys_visible: float, slots: int,
               itemsize: int = 2) -> float:
    """One decode step.  ``experts_touched``: held experts with at least one
    row, a layer; ``keys_visible``: keys the step's live rows could see,
    summed over (full layer, row), as the engine counts them; ``slots``: rows
    the step updates (all of them)."""
    key = kv_bytes_per_token(cfg, itemsize) // max(1, layers(cfg, FULL))
    return (
        itemsize * fixed_params(cfg)
        + itemsize * cfg["num_hidden_layers"] * float(experts_touched) * expert_params(cfg)
        + (float(keys_visible) + slots * layers(cfg, FULL)) * key   # read; the new rows written
        + 2 * slots * (state_bytes(cfg) + conv_bytes(cfg, itemsize))
    )


def scan_flops(cfg: dict, token_layers: float, chunk: int = 64) -> float:
    """The chunked rule over ``token_layers`` (token, KDA layer)s."""
    H, d, _ = _lin(cfg)
    macs = chunk // 2 * 5 * d + 3 * d * d
    return 2.0 * macs * H * float(token_layers)


def scan_bytes(cfg: dict, token_layers: float) -> float:
    """q, k, v, the decay (a channel) and beta in, o out, float32."""
    H, d, _ = _lin(cfg)
    return 4.0 * (5 * d + 1) * H * float(token_layers)
