"""Parameters, bytes and operations of a decoder whose attention layers are
of two KINDS — full attention and a sliding window with a learned sink, each
with its own KV heads — over sparse experts (MiMo-V2-Flash's), counted from
the configuration file's numbers and from what the program counted.  The
benchmark's own copy, like ``flops.py``, ``moe_cost.py`` and ``scmoe_cost.py``.

The file's ``layers_kept`` names the published layers this chip keeps;
``hybrid_layer_pattern`` (0 full, 1 window) and ``moe_layer_freq`` (0 dense, 1
experts) say what each is.  An attention layer holds W_q (E x H x D), W_k (E
x KV x D), W_v (E x KV x D_v) and W_o (H x D_v x E), KV the kind's own, two
block norms and, a window layer, a sink a query head; a dense layer one
SwiGLU of ``intermediate_size``; an expert layer a router over
``n_routed_experts_published`` experts with its selection bias and the
``n_routed_experts`` experts HELD here, each a SwiGLU of
``moe_intermediate_size``.

A cached KEY of a layer is one K row and one V row, KV x (D + D_v) values: a
full layer keeps every position's, a window layer ``sliding_window`` rolling
slots a row.

THE WHOLE DECODE STEP has to read every matrix it touches at least once:
every attention, the dense layer, every router, the final norm and the
output head — whatever the routing — and of the held experts those that own
at least one row (``experts_touched``, as the program counted them); the
K/V of every key a row's query may see, in every layer of either kind; and
it writes one new key a slot and layer.  The embedding's rows and the
activations are noise beside them.  A share of BANDWIDTH: 64 token rows do 64
FLOP a weight byte against the chip's 240.
"""

from __future__ import annotations

FULL, WINDOW = "full", "window"


def kinds(cfg: dict) -> list:
    """The kept layers' attention kinds, in layer order."""
    return [WINDOW if cfg["hybrid_layer_pattern"][i] else FULL for i in cfg["layers_kept"]]


def layers(cfg: dict, kind: str) -> int:
    return kinds(cfg).count(kind)


def expert_layers(cfg: dict) -> int:
    return sum(1 for i in cfg["layers_kept"] if cfg["moe_layer_freq"][i])


def dense_layers(cfg: dict) -> int:
    return len(cfg["layers_kept"]) - expert_layers(cfg)


def kv_heads(cfg: dict, kind: str) -> int:
    return cfg["swa_num_key_value_heads" if kind == WINDOW else "num_key_value_heads"]


def key_values(cfg: dict, kind: str) -> int:
    """Values one cached key of a layer of ``kind`` holds: its K row and
    its V row."""
    return kv_heads(cfg, kind) * (cfg["head_dim"] + cfg["v_head_dim"])


def attention_params(cfg: dict, kind: str) -> int:
    """One attention layer of ``kind``: the four projections."""
    E, H = cfg["hidden_size"], cfg["num_attention_heads"]
    D, Dv, KV = cfg["head_dim"], cfg["v_head_dim"], kv_heads(cfg, kind)
    return E * H * D + E * KV * D + E * KV * Dv + H * Dv * E


def small_params(cfg: dict) -> int:
    """What the projections and matrices leave out: two block norms a layer,
    a sink a query head of every window layer that has one, the routers'
    selection biases and the final norm."""
    sinks = cfg["num_attention_heads"] if cfg["add_swa_attention_sink_bias"] else 0
    return (2 * cfg["hidden_size"] * len(cfg["layers_kept"]) + cfg["hidden_size"]
            + sinks * layers(cfg, WINDOW)
            + cfg["n_routed_experts_published"] * expert_layers(cfg))


def dense_params(cfg: dict) -> int:
    """A dense layer's SwiGLU: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg: dict) -> int:
    """The router's matrix (its bias is among ``small_params``)."""
    return cfg["hidden_size"] * cfg["n_routed_experts_published"]


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def fixed_params(cfg: dict) -> int:
    """Parameters every step touches: every attention, the dense layers,
    the routers, the norms, sinks and biases, and the output head.  Left
    out: the routed experts (by what was touched) and the embedding (a few
    rows a step)."""
    return (sum(attention_params(cfg, kind) for kind in kinds(cfg))
            + dense_layers(cfg) * dense_params(cfg)
            + expert_layers(cfg) * router_params(cfg) + small_params(cfg)
            + cfg["vocab_size"] * cfg["hidden_size"])


def held_params(cfg: dict) -> int:
    """Everything this chip holds: ``fixed_params``, the held experts and
    the embedding."""
    return (fixed_params(cfg)
            + expert_layers(cfg) * cfg["n_routed_experts"] * expert_params(cfg)
            + cfg["vocab_size"] * cfg["hidden_size"])


def cache_bytes(cfg: dict, slots: int, max_len: int, itemsize: int = 2) -> dict:
    """The K/V a cache of ``slots`` rows holds, by kind: a full layer every
    one of ``max_len`` positions, a window layer ``sliding_window`` slots."""
    return {
        FULL: layers(cfg, FULL) * slots * max_len * key_values(cfg, FULL) * itemsize,
        WINDOW: (layers(cfg, WINDOW) * slots * cfg["sliding_window"]
                 * key_values(cfg, WINDOW) * itemsize),
    }


def attention_bytes(cfg: dict, kind: str, keys_visible: float, slots: int,
                    itemsize: int = 2) -> float:
    """What one decode step's attention of ``kind`` has to move:
    ``keys_visible`` keys (summed over the kind's layers and the rows, as
    the program counted them) read, and one key a slot and layer written."""
    return (float(keys_visible) + slots * layers(cfg, kind)) * key_values(cfg, kind) * itemsize


def step_bytes(cfg: dict, experts_touched: float, full_keys_visible: float,
               window_keys_visible: float, slots: int, itemsize: int = 2) -> float:
    """One decode step.  ``experts_touched``: held experts with at least one
    row, summed over the step's expert layers; ``*_keys_visible``: keys the
    step's rows could see, summed over (layer of the kind, row)."""
    return (
        itemsize * (fixed_params(cfg) + experts_touched * expert_params(cfg))
        + attention_bytes(cfg, FULL, full_keys_visible, slots, itemsize)
        + attention_bytes(cfg, WINDOW, window_keys_visible, slots, itemsize)
    )


def step_flops(cfg: dict, rows: int, held_pairs: float, full_keys_visible: float,
               window_keys_visible: float) -> float:
    """Multiply-adds x 2 of one decode step of ``rows`` token rows: every
    fixed matrix for every row, an expert's three matrices for each of the
    ``held_pairs`` (token, choice) pairs that fell on a held expert, and
    scores (D) and mix (D_v) over the visible keys, a query head."""
    per_key = cfg["num_attention_heads"] * (cfg["head_dim"] + cfg["v_head_dim"])
    return 2.0 * (rows * fixed_params(cfg) + held_pairs * expert_params(cfg)
                  + (full_keys_visible + window_keys_visible) * per_key)


def prefill_attention_flops(cfg: dict, kind: str, tokens: int) -> float:
    """Multiply-adds x 2 of one layer's attention over a prompt of
    ``tokens``: the (query, key) pairs inside the mask — the causal
    triangle, or the window's band — a query head."""
    w = min(cfg["sliding_window"], tokens) if kind == WINDOW else tokens
    pairs = tokens * w - w * (w - 1) // 2
    return 2.0 * pairs * cfg["num_attention_heads"] * (cfg["head_dim"] + cfg["v_head_dim"])
