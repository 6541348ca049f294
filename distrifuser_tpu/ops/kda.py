"""Kimi Delta Attention (KDA): linear attention by the delta rule with a
decay of its own for every key channel, over ONE sequence (no batch axis) -
the one-token recurrence, and its chunked form for a whole prompt or for a
suffix entering the state its prefix left.

A head holds a matrix state S [K, V] (key channels by value channels).  A
token brings a query and a key q, k [K] (L2-normalised by the caller, q
scaled), a value v [V], a log decay g [K] <= 0 a key channel and a step
beta in (0, 1):

    S' = Diag(exp g_t) S_{t-1}                 every key channel decays alone
    u_t = beta_t (v_t - S'^T k_t)              what the state lacks of v_t
    S_t = S' + k_t u_t^T                       the rank-one delta
    o_t = S_t^T q_t

which is S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t
v_t^T.  `step` is that recurrence - the definition - and `chunked` its block
form (the WY representation of the product of the (I - beta k k^T), Yang et
al. 2024, with the per-channel decay of Kimi Linear, 2025): with Gamma_t the
sum of g_1 .. g_t inside a chunk of C rows that enters with S_0,

    A_ti = beta_t sum_c k_tc k_ic exp(Gamma_tc - Gamma_ic)        i < t
    (I + A) [U | W] = Diag(beta) [V | K * exp(Gamma)]     forward substitution
    U~ = U - W S_0
    o_t = S_0^T (q_t * exp Gamma_t)
          + sum_{i<=t} (sum_c q_tc k_ic exp(Gamma_tc - Gamma_ic)) u~_i
    S_C = Diag(exp Gamma_C) S_0 + sum_i (k_i * exp(Gamma_C - Gamma_i)) u~_i^T

so a prefill is matmuls, one triangular solve a chunk and T / C sequential
steps (the pair sums and solves of `SPAN_CHUNKS` chunks at a time).  The rows
of U~ are the recurrence's own u_t.

**Only exponents <= 0 are formed.**  A scalar decay a head would factor out
of the pair sums (exp(Gamma_t) exp(-Gamma_i), two matmul operands); a decay
a CHANNEL leaves ``(k_t * exp Gamma_t) . (k_i * exp -Gamma_i)``, and
exp(-Gamma) overflows float32 (e^88) within a few rows at the published
gate (a head's rate up to 16 a unit step: |g| reaches the tens a row).  Here
the two pair sums are computed DIRECTLY - the [C, C, K] array of
exp(Gamma_t - Gamma_i)
for i <= t, the rest masked before the exponential, multiplied and summed
over the channel in the same pass (one chunk at a time, so the array is a
chunk's) - and every other exponent is Gamma_t, or Gamma_C - Gamma_i: sums of
g over a run of rows, all <= 0.  No exponent is bounded above 0 by anything
but its sign; a decay below e^-87 rounds to 0, which is its value to float32.
No sub-chunk factoring (about a sub-chunk's first row the exponent would
reach 15 |g| > 88); the price is C^2 K exponentials a head and chunk where
the factored form has 2 C K, on the VPU beside matmuls of C^2 K multiplies.

Everything here is float32, matmuls at `Precision.HIGHEST`: the state
integrates a whole context and is corrected by differences (v - S^T k).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
_HI = lax.Precision.HIGHEST


def step(state, q, k, v, g, beta):
    """One token through the recurrence.  ``state`` [H, K, V] (its dtype is
    kept: float32 as served); ``q`` / ``k`` / ``g`` [H, K], ``v`` [H, V],
    ``beta`` [H].  -> (o [H, V] float32, the new state).

    The state is read twice and written once: S'^T k and S'^T q come out of
    ONE pass over it (o = S'^T q + u (k . q), the same number as S_t^T q),
    the update out of the other."""
    s = state.astype(F32)
    q, k, v, beta = (x.astype(F32) for x in (q, k, v, beta))
    alpha = jnp.exp(g.astype(F32))
    read = jnp.einsum("hkv,hjk->hjv", s, jnp.stack([alpha * k, alpha * q], 1),
                      precision=_HI)
    u = beta[:, None] * (v - read[:, 0])
    o = read[:, 1] + u * jnp.sum(k * q, axis=-1, keepdims=True)
    new = alpha[:, :, None] * s + k[:, :, None] * u[:, None, :]
    return o, new.astype(state.dtype)


def _pair_sums(q, k, gamma):
    """One chunk: q, k, gamma [H, C, K] -> (sum_c k_t k_i exp(Gamma_t -
    Gamma_i), the same with q_t) [H, C, C], rows t, columns i, zero where
    i > t."""
    c = q.shape[1]
    seen = jnp.tril(jnp.ones((c, c), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(
        seen, gamma[:, :, None, :] - gamma[:, None, :, :], -jnp.inf))
    ki = (k[:, None, :, :] * decay)  # [H, t, i, K]
    return (jnp.sum(k[:, :, None, :] * ki, axis=-1),
            jnp.sum(q[:, :, None, :] * ki, axis=-1))


# chunks whose pair sums and solves are computed together (the largest
# divisor of a sequence's chunks up to this): what a prompt's temporaries are
# sized by - at 126 chunks in one go the 8064-token instruction's prefill
# took 3.3 GB beside 13.3 GB of weights (compiled for v5e, PR 38)
SPAN_CHUNKS = 16


def _span(state, xs, *, chunk: int):
    """``xs`` = q, k, v, g, beta over a span of whole chunks [S * C, H, *]
    entering ``state`` -> (the state after it, o [S * C, H, V])."""
    rows, h, _ = xs[0].shape
    n = rows // chunk

    def chunks(x):  # [S * C, H, *] -> [S, H, C, *]
        x = x.astype(F32).reshape((n, chunk) + x.shape[1:])
        return jnp.swapaxes(x, 1, 2)

    q, k, v, g, beta = (chunks(x) for x in xs)
    gamma = jnp.cumsum(g, axis=2)
    kk, qk = lax.map(lambda a: _pair_sums(*a), (q, k, gamma))
    strictly = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    a = beta[..., None] * jnp.where(strictly, kk, 0.0)
    rhs = beta[..., None] * jnp.concatenate([v, k * jnp.exp(gamma)], axis=-1)
    # (I + A) is unit lower triangular: the solve is forward substitution
    uw = lax.linalg.triangular_solve(
        a + jnp.eye(chunk, dtype=F32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    u, w = jnp.split(uw, [v.shape[-1]], axis=-1)
    q_in = q * jnp.exp(gamma)  # a query against the entering state
    to_end = gamma[:, :, -1:, :]  # [S, H, 1, K]
    k_out = k * jnp.exp(to_end - gamma)  # a key's mark on the leaving state

    def carry(s, xs):
        u, w, qk, q_in, k_out, whole = xs
        u = u - jnp.einsum("hck,hkv->hcv", w, s, precision=_HI)
        o = (jnp.einsum("hck,hkv->hcv", q_in, s, precision=_HI)
             + jnp.einsum("hti,hiv->htv", qk, u, precision=_HI))
        s = whole[:, :, None] * s + jnp.einsum("hck,hcv->hkv", k_out, u,
                                               precision=_HI)
        return s, o

    state, o = lax.scan(carry, state,
                        (u, w, qk, q_in, k_out, jnp.exp(to_end[:, :, 0])))
    return state, jnp.swapaxes(o, 1, 2).reshape(rows, h, -1)


def chunked(q, k, v, g, beta, state, *, chunk: int):
    """The recurrence over T tokens entering ``state`` [H, K, V] (zeros at
    the start of a sequence), ``chunk`` rows at a time.

    ``q`` / ``k`` / ``g`` [T, H, K], ``v`` [T, H, V], ``beta`` [T, H]; T a
    multiple of ``chunk``.  -> (o [T, H, V] float32, the state after the
    last token [H, K, V] float32)."""
    t, h, _ = q.shape
    if t % chunk:
        raise ValueError(f"sequence length {t} is not a multiple of the "
                         f"chunk size {chunk}")
    n = t // chunk
    spans = n // max(s for s in range(1, SPAN_CHUNKS + 1) if n % s == 0)
    state, o = lax.scan(
        functools.partial(_span, chunk=chunk), state.astype(F32),
        tuple(x.reshape((spans, t // spans) + x.shape[1:])
              for x in (q, k, v, g, beta)))
    return o.reshape(t, h, -1), state
