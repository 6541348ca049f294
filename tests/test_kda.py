"""Kimi Delta Attention's two forms (`ops/kda.py`) at a small size: the
chunked form is held to the recurrence - the definition, written here token
by token - with and without an entering state, at a gate range whose naive
factoring overflows; the one-token step is the same recurrence; the state's
dtype is the caller's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distrifuser_tpu.ops import kda

H, K, V = 3, 16, 8
HI = jax.lax.Precision.HIGHEST


def recurrence(q, k, v, g, beta, state):
    """S' = Diag(exp g) S; u = beta (v - S'^T k); S = S' + k u^T; o = S^T q,
    one token after another."""
    out = []
    for t in range(q.shape[0]):
        decayed = jnp.exp(g[t])[:, :, None] * state
        u = beta[t][:, None] * (v[t] - jnp.einsum("hkv,hk->hv", decayed, k[t],
                                                  precision=HI))
        state = decayed + k[t][:, :, None] * u[:, None, :]
        out.append(jnp.einsum("hkv,hk->hv", state, q[t], precision=HI))
    return jnp.stack(out), state


def inputs(t, seed=0, rate=(0.002, 1.0, 16.0)):
    """q, k normalised as the model hands them over; the gate at the
    published range: a head's rate from ``rate`` (exp A_log up to 16) times
    a softplus of a unit normal, so one head barely decays and one loses
    tens of e-folds a row."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (t, H, K))) * K ** -0.5
    k = unit(jax.random.normal(ks[1], (t, H, K)))
    v = jax.random.normal(ks[2], (t, H, V))
    g = -jnp.asarray(rate)[None, :, None] * jax.nn.softplus(
        jax.random.normal(ks[3], (t, H, K)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (t, H)))
    state = jax.random.normal(ks[5], (H, K, V))
    return q, k, v, g, beta, state


def close(a, b, tol=5e-6):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert a.shape == b.shape and np.isfinite(a).all()
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1.0), (
        np.abs(a - b).max(), np.abs(b).max())


@pytest.mark.parametrize("entering", ["zero_state", "entering_state"])
@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_the_chunked_form_is_the_recurrence(chunk, entering):
    q, k, v, g, beta, state = inputs(64)
    if entering == "zero_state":
        state = jnp.zeros_like(state)
    want_o, want_s = recurrence(q, k, v, g, beta, state)
    o, s = jax.jit(kda.chunked, static_argnames="chunk")(
        q, k, v, g, beta, state, chunk=chunk)
    close(o, want_o)
    close(s, want_s)
    assert o.dtype == s.dtype == jnp.float32


def test_the_gate_range_of_the_test_overflows_the_factored_form():
    """What the direct pair sums are for: exp(-Gamma), the operand a matmul
    of (k_t exp Gamma_t) . (k_i exp -Gamma_i) would need, is infinite inside
    one chunk at this range, and the chunked form's results are finite."""
    q, k, v, g, beta, state = inputs(64)
    gamma = jnp.cumsum(g, axis=0)
    assert float(gamma.min()) < -800.0
    assert not np.isfinite(np.asarray(jnp.exp(-gamma))).all()
    o, s = kda.chunked(q, k, v, g, beta, state, chunk=64)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(
        np.asarray(s)).all()


def test_the_entering_state_reaches_the_output():
    q, k, v, g, beta, state = inputs(16)
    with_state, _ = kda.chunked(q, k, v, g, beta, state, chunk=8)
    without, _ = kda.chunked(q, k, v, g, beta, jnp.zeros_like(state), chunk=8)
    # the slow head (rate 0.002) remembers it to the last row
    assert np.abs(np.asarray(with_state - without))[-1, 0].max() > 1e-2


@pytest.mark.parametrize("chunks", [34, 48])
def test_spans_of_chunks_carry_the_state_between_them(chunks, monkeypatch):
    """More chunks than a span holds: 34 = 2 spans of 17 > `SPAN_CHUNKS` is
    not taken, 34 -> spans of 2; 48 -> 3 spans of 16."""
    monkeypatch.setattr(kda, "SPAN_CHUNKS", 16)
    q, k, v, g, beta, state = inputs(4 * chunks, seed=3)
    want_o, want_s = recurrence(q, k, v, g, beta, state)
    o, s = kda.chunked(q, k, v, g, beta, state, chunk=4)
    close(o, want_o)
    close(s, want_s)


def test_one_step_is_the_recurrence_and_two_halves_are_the_whole():
    q, k, v, g, beta, state = inputs(32, seed=1)
    want_o, want_s = recurrence(q, k, v, g, beta, state)
    s, out = state, []
    for t in range(32):
        o, s = kda.step(s, q[t], k[t], v[t], g[t], beta[t])
        out.append(o)
    close(jnp.stack(out), want_o)
    close(s, want_s)
    # a prefix's state is what its suffix's first chunk starts from
    first, mid = kda.chunked(q[:16], k[:16], v[:16], g[:16], beta[:16], state,
                             chunk=8)
    second, end = kda.chunked(q[16:], k[16:], v[16:], g[16:], beta[16:], mid,
                              chunk=8)
    close(jnp.concatenate([first, second]), want_o)
    close(end, want_s)


def test_the_steps_state_keeps_its_dtype_and_bfloat16_costs_precision():
    q, k, v, g, beta, state = inputs(24, seed=2, rate=(0.002, 0.01, 0.05))
    want_o, _ = recurrence(q, k, v, g, beta, state)
    errors = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        s, out = state.astype(dtype), []
        for t in range(24):
            o, s = kda.step(s, q[t], k[t], v[t], g[t], beta[t])
            out.append(o)
        assert s.dtype == dtype and o.dtype == jnp.float32
        errors[dtype] = float(jnp.abs(jnp.stack(out) - want_o).max())
    assert errors[jnp.float32] < 1e-5 < 1e-3 < errors[jnp.bfloat16]


def test_a_sequence_that_is_not_whole_chunks_is_refused():
    q, k, v, g, beta, state = inputs(20)
    with pytest.raises(ValueError, match="not a multiple"):
        kda.chunked(q, k, v, g, beta, state, chunk=8)
