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
