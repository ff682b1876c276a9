"""``ops/gated_delta.py``: the chunked form equals the rule token by token
and ``transformers``' recurrent reference (the one public implementation on
this machine), whatever the run's length and wherever it starts; padding is
the identity; a prefill followed by steps is the longer prefill."""

import numpy as np
import pytest

import jax.numpy as jnp

from ray_tpu.ops import gated_delta

B, H, DK, DV = 2, 3, 8, 16


def inputs(S, seed=0, strong_decay=True):
    """Seeded (q, k, v, log_alpha, beta, state0) as a linear layer hands
    them over: unit keys, queries scaled by 1/sqrt(d_k), beta in (0, 2)."""
    rng = np.random.default_rng(seed)
    f = np.float32
    q = rng.normal(size=(B, S, H, DK)).astype(f)
    k = rng.normal(size=(B, S, H, DK)).astype(f)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(B, S, H, DV)).astype(f)
    top = 16.0 if strong_decay else 0.05
    log_alpha = -np.exp(rng.uniform(np.log(1e-3), np.log(top), size=(B, S, H))).astype(f)
    beta = (2.0 / (1.0 + np.exp(-rng.normal(size=(B, S, H))))).astype(f)
    state0 = rng.normal(size=(B, H, DK, DV)).astype(f)
    return q, k, v, log_alpha, beta, state0


@pytest.fixture(scope="module")
def torch_rule():
    torch = pytest.importorskip("torch")
    module = pytest.importorskip("transformers.models.qwen3_next.modeling_qwen3_next")

    def rule(q, k, v, log_alpha, beta, state0):
        # it scales the query itself; beta arrives doubled already
        o, state = module.torch_recurrent_gated_delta_rule(
            *(torch.tensor(x) for x in (q, k, v, log_alpha, beta)),
            initial_state=torch.tensor(state0), output_final_state=True)
        return o.numpy(), state.numpy()

    return rule


@pytest.mark.parametrize("from_zero", [True, False], ids=["zero", "carried"])
@pytest.mark.parametrize("S, chunk", [(8, 4), (11, 4), (128, 64), (133, 64), (5, 64)])
def test_scan_equals_the_rule_token_by_token(torch_rule, S, chunk, from_zero):
    q, k, v, g, beta, state0 = inputs(S, seed=S)
    if from_zero:
        state0 = np.zeros_like(state0)
    o, state = gated_delta.scan(q / np.sqrt(DK), k, v, g, beta, state0, chunk=chunk)
    o_rec, state_rec = gated_delta.recurrent(q / np.sqrt(DK), k, v, g, beta, state0)
    np.testing.assert_allclose(o, o_rec, atol=2e-5)
    np.testing.assert_allclose(state, state_rec, atol=2e-5)
    o_t, state_t = torch_rule(q, k, v, g, beta, state0)
    np.testing.assert_allclose(o, o_t, atol=2e-5)
    np.testing.assert_allclose(state, state_t, atol=2e-5)


@pytest.mark.parametrize("S", [12, 70])
def test_a_slow_decay_keeps_the_chunks_coupled(torch_rule, S):
    """alpha near 1: a chunk's result hangs on every token before it, and
    the triangular system is far from the identity."""
    q, k, v, g, beta, state0 = inputs(S, seed=3, strong_decay=False)
    k[:, 1::2] = k[:, ::2][:, : k[:, 1::2].shape[1]]     # repeated keys: large off-diagonals
    o, state = gated_delta.scan(q, k, v, g, beta, state0, chunk=8)
    o_t, state_t = torch_rule(q * np.sqrt(DK), k, v, g, beta, state0)
    np.testing.assert_allclose(o, o_t, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(state, state_t, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("S, steps", [(9, 3), (64, 5)])
def test_scan_then_step_is_the_longer_scan(S, steps):
    q, k, v, g, beta, state0 = inputs(S + steps, seed=7)
    o_all, state_all = gated_delta.scan(q, k, v, g, beta, state0, chunk=4)
    o, state = gated_delta.scan(q[:, :S], k[:, :S], v[:, :S], g[:, :S], beta[:, :S],
                                state0, chunk=4)
    outs = [o]
    for t in range(S, S + steps):
        o_t, state = gated_delta.step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], state)
        outs.append(o_t[:, None])
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), o_all, atol=2e-5)
    np.testing.assert_allclose(state, state_all, atol=2e-5)


@pytest.mark.parametrize("real", [(7, 10), (1, 4), (8, 9)])
def test_padding_leaves_the_state_of_the_real_tokens(real):
    """Rows of different real lengths in one run: each row's final state is
    that of its own real tokens, whatever lies behind them."""
    S = 10
    q, k, v, g, beta, state0 = inputs(S, seed=11)
    valid = np.arange(S)[None, :] < np.asarray(real)[:, None]
    o, state = gated_delta.scan(q, k, v, g, beta, state0, valid, chunk=4)
    for b, n in enumerate(real):
        part = slice(b, b + 1)
        o_b, state_b = gated_delta.scan(
            q[part, :n], k[part, :n], v[part, :n], g[part, :n], beta[part, :n],
            state0[part], chunk=4)
        np.testing.assert_allclose(state[b], state_b[0], atol=1e-6)
        np.testing.assert_allclose(o[b, :n], o_b[0], atol=1e-6)


def test_the_step_is_the_rule_as_written():
    """``step`` takes both contractions of the OLD state; the rule writes
    them of the decayed and of the updated one."""
    q, k, v, g, beta, state = inputs(1, seed=5)
    q, k, v, g, beta = (x[:, 0] for x in (q, k, v, g, beta))
    o, new = gated_delta.step(q, k, v, g, beta, state)
    decayed = np.exp(g)[..., None, None] * state
    seen = np.einsum("bhkv,bhk->bhv", decayed, k)
    want = decayed + k[..., None] * (beta[..., None] * (v - seen))[..., None, :]
    np.testing.assert_allclose(new, want, atol=1e-6)
    np.testing.assert_allclose(o, np.einsum("bhkv,bhk->bhv", want, q), atol=1e-6)
    assert new.dtype == jnp.float32 and o.dtype == jnp.float32


# ---- the one-token update as ONE pass over the cache's leaf (the kernel) ----

def layer_inputs(L, rows, heads, dk, dv, seed):
    """Seeded (q, k, v, log_alpha, beta) of one token of every row and the
    stacked states (L, rows, heads, dk, dv) of L layers."""
    rng = np.random.default_rng(seed)
    f = np.float32
    q = rng.normal(size=(rows, heads, dk)).astype(f)
    k = rng.normal(size=(rows, heads, dk)).astype(f)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(rows, heads, dv)).astype(f)
    log_alpha = -np.exp(rng.uniform(np.log(1e-3), np.log(16.0), size=(rows, heads))).astype(f)
    beta = (2.0 / (1.0 + np.exp(-rng.normal(size=(rows, heads))))).astype(f)
    states = rng.normal(size=(L, rows, heads, dk, dv)).astype(f)
    return q, k, v, log_alpha, beta, states


def low_bits(x):
    """Share of the non-zero float32 words with a bit set below bfloat16's
    sixteen."""
    words = np.asarray(x, np.float32).view(np.uint32)
    return float(((words & 0xFFFF) != 0).sum() / max(1, (words != 0).sum()))


#: (layers, rows, heads, d_k, d_v): whole (8, 128) tiles a row — a head a
#: tile (one layer; odd heads, several layers), a head pair three tiles
#: (Olmo-Hybrid's 192), two heads a tile, eight heads a tile
TILE_WHOLE = [(1, 2, 2, 8, 128), (3, 2, 3, 16, 128), (2, 3, 4, 8, 192),
              (2, 2, 6, 24, 64), (3, 2, 8, 8, 16)]
VALUES = ["random", "decay e^-20", "beta 0", "beta 2", "zero state", "low bits"]


@pytest.mark.parametrize("values", VALUES)
@pytest.mark.parametrize("shape", TILE_WHOLE, ids=lambda s: "x".join(map(str, s)))
def test_the_kernel_is_the_step_where_the_state_lies(torch_rule, shape, values):
    """``step_in_place`` (interpret mode here: the very kernel) on the LAST
    layer of a stacked, packed leaf against ``step`` and ``transformers``'
    recurrent rule on that layer's states; the other layers bit for bit
    what they were."""
    L, rows, heads, dk, dv = shape
    assert gated_delta.implementation(rows, heads, dk, dv) == "in_place"
    q, k, v, g, beta, states = layer_inputs(*shape, seed=len(values) + sum(shape))
    layer = L - 1
    if values == "decay e^-20":
        g[:] = -20.0
    elif values == "beta 0":
        beta[:] = 0.0
    elif values == "beta 2":
        beta[:] = 2.0
    elif values == "zero state":          # a free slot's, or a row just admitted
        states[layer] = 0.0
    elif values == "low bits":
        assert low_bits(states) > 0.99
    q = q / np.sqrt(dk).astype(np.float32)
    o_step, new_step = gated_delta.step(q, k, v, g, beta, states[layer])
    o, leaf = gated_delta.step_in_place(
        q, k, v, g, beta, gated_delta.packed(jnp.asarray(states)), jnp.int32(layer))
    assert o.dtype == leaf.dtype == jnp.float32 and leaf.shape == (L, rows, dk, heads * dv)
    after = np.asarray(gated_delta.unpacked(leaf, heads))
    np.testing.assert_array_equal(after[:layer], states[:layer])      # nothing else moved
    np.testing.assert_allclose(o, o_step, atol=2e-6)
    np.testing.assert_allclose(after[layer], new_step, atol=2e-6)
    o_t, new_t = torch_rule(*(x[:, None] for x in (q * np.sqrt(dk), k, v, g, beta)), states[layer])
    np.testing.assert_allclose(o, o_t[:, 0], atol=2e-5)
    np.testing.assert_allclose(after[layer], new_t, atol=2e-5)
    alpha = np.asarray(jnp.exp(g))[..., None, None]
    if values == "beta 0":                # nothing written: the decayed state to the bit
        np.testing.assert_array_equal(after[layer], alpha * states[layer])
    if values == "zero state":            # k (x) beta v, to the bit
        np.testing.assert_array_equal(
            after[layer], k[..., None] * (beta[..., None] * v)[..., None, :])
    if values in ("low bits", "random", "beta 2"):
        assert low_bits(after[layer]) > 0.99          # float32 kept, not bfloat16's sixteen


@pytest.mark.parametrize("rows, heads, dk, dv, body", [
    (32, 30, 96, 192, "in_place"),        # Olmo-Hybrid's, the cell's
    (4, 4, 8, 16, "xla"),                 # tier-1's toy: four heads are half a lane tile
    (2, 3, 8, 192, "xla"),                # an odd head of 192 ends inside a tile
    (2, 2, 12, 128, "xla"),               # d_k no whole sublane tile
    (2, 2, 136, 128, "xla"),              # d_k beyond the one transpose a row
])
def test_the_body_is_chosen_from_the_shape(rows, heads, dk, dv, body):
    assert gated_delta.implementation(rows, heads, dk, dv) == body
    if body == "xla":                     # ``step_layer`` still runs it, packed
        q, k, v, g, beta, states = layer_inputs(2, rows, heads, dk, dv, seed=1)
        o, leaf = gated_delta.step_layer(
            q, k, v, g, beta, gated_delta.packed(jnp.asarray(states)), jnp.int32(1))
        o_step, new_step = gated_delta.step(q, k, v, g, beta, states[1])
        np.testing.assert_allclose(o, o_step, atol=2e-6)
        after = np.asarray(gated_delta.unpacked(leaf, heads))
        np.testing.assert_allclose(after[1], new_step, atol=2e-6)
        np.testing.assert_array_equal(after[0], states[0])
