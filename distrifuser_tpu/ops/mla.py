"""Multi-head latent attention (MLA): one attention, two forms, and the
rotary embedding of its 64-wide part.

A position is cached as ONE normalised latent ``c`` [C] and ONE rotated key
part ``k_pe`` [R], shared by all heads.  Per head h the key is
``[c W_UK,h | k_pe]`` and the value ``c W_UV,h``; with a query
``[q_nope | q_pe]`` the score is

    (q_nope . (c W_UK,h) + q_pe . k_pe) * scale

and the two forms are two ways round the same products:

*materialised* (`materialised_attention`): a prompt's per-head keys and
values are expanded from its latents once (by the caller) and the queries
attend to them - key and value of UNEQUAL widths (128 + 64 against 128) -
causally, `QUERY_BLOCK` queries at a time, so that no [H, T, T] array of
the whole prompt exists: at most [H, block, T] logits are alive.  Un-windowed:
every block sees the keys of all T positions under its mask.

*absorbed* (`absorbed_attention`): the up-projections are folded into the
query and the output (by the caller: ``q_lat = q_nope W_UK,h^T``,
``out = (sum_s p_s c_s) W_UV,h``) and the H query heads attend to the cache
rows themselves - one "KV head" whose key [C + R] and value [C] are two
views of the same rows: the limit case of grouped-query attention.  Nothing
is expanded: a decode step reads C + R numbers a position, not
H * (128 + 64 + 128).

Both compute the softmax in float32 and feed the MXU the model dtype, like
`ops/attention.py causal_gqa_sdpa`; one XLA route each.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def rotary_interleaved(x, positions, theta: float):
    """Rotary embedding over the whole last axis, pairs ``(2 i, 2 i + 1)``
    (``rope_interleave``): x [T, ..., R] at ``positions`` [T]; pair i turns
    by ``position * theta^(-2 i / R)``.  float32 inside, the result in
    ``x``'s dtype."""
    half = x.shape[-1] // 2
    angle = positions.astype(F32)[:, None] * theta ** (
        -jnp.arange(half, dtype=F32) / half)
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pairs = x.astype(F32).reshape(x.shape[:-1] + (half, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


# Queries a block of either form.  Timed alone on one v5e at H = 32 (my chip
# runs, PR 34): a block's [H, rows, S] float32 logits are 134 MB at 128 rows
# of S = 8192 and every pass of the softmax goes through HBM - a request's
# 128 entering rows took 12.6 ms a layer in one block, 0.92 / 0.57 / 0.54 /
# 0.68 ms in blocks of 64 / 32 / 16 / 8; a prompt of 8064 tokens 777 ms a
# layer in blocks of 128, 72 / 54 / 67 ms in blocks of 64 / 32 / 16.
QUERY_BLOCK = 32


def materialised_attention(q_nope, q_pe, k_nope, k_pe, v, *, scale: float,
                           block: int = QUERY_BLOCK):
    """A whole prompt from position 0, causal: ``q_nope`` / ``k_nope``
    [T, H, Dn], ``q_pe`` [T, H, R] and the heads' shared ``k_pe`` [T, R]
    (both rotated), ``v`` [T, H, Dv] -> [T, H, Dv].  Query i sees keys
    0 .. i; the queries go ``block`` at a time (the largest divisor of T
    that ``block`` holds)."""
    t, h, _ = q_nope.shape
    block = math.gcd(t, block)
    keys = jnp.arange(t)

    def one(args):
        first, qn, qp = args
        logits = (jnp.einsum("bhd,shd->hbs", qn, k_nope,
                             preferred_element_type=F32)
                  + jnp.einsum("bhr,sr->hbs", qp, k_pe,
                               preferred_element_type=F32)) * scale
        visible = keys[None, :] <= (first + jnp.arange(block))[:, None]
        w = jax.nn.softmax(jnp.where(visible[None], logits, -jnp.inf), axis=-1)
        return jnp.einsum("hbs,shd->bhd", w.astype(v.dtype), v)

    out = lax.map(one, (jnp.arange(0, t, block), _blocks(q_nope, block),
                        _blocks(q_pe, block)))
    return out.reshape((t, h, v.shape[-1]))


def absorbed_attention(q_lat, q_pe, c, k_pe, *, q_positions, scale: float,
                       block: int = QUERY_BLOCK):
    """Queries against the latent cache itself: ``q_lat`` [T, H, C] (the
    up-projection already folded in), ``q_pe`` [T, H, R] (rotated); the
    cache ``c`` [S, C] and ``k_pe`` [S, R], of which query i sees rows
    0 .. q_positions[i] - rows not written yet are never read into the
    result.  -> the attended latents [T, H, C], for the caller to take
    through W_UV.  More than ``block`` queries (a suffix entering a cache)
    go ``block`` at a time, as in the materialised form."""
    c, k_pe = c.astype(q_lat.dtype), k_pe.astype(q_pe.dtype)
    rows = jnp.arange(c.shape[0])

    def one(args):
        ql, qp, positions = args
        logits = (jnp.einsum("thc,sc->hts", ql, c, preferred_element_type=F32)
                  + jnp.einsum("thr,sr->hts", qp, k_pe,
                               preferred_element_type=F32)) * scale
        visible = rows[None, :] <= positions[:, None]
        w = jax.nn.softmax(jnp.where(visible[None], logits, -jnp.inf), axis=-1)
        return jnp.einsum("hts,sc->thc", w.astype(c.dtype), c)

    t = q_lat.shape[0]
    block = math.gcd(t, block)
    if block == t:
        return one((q_lat, q_pe, q_positions))
    out = lax.map(one, (_blocks(q_lat, block), _blocks(q_pe, block),
                        _blocks(q_positions, block)))
    return out.reshape(q_lat.shape)


def _blocks(x, block: int):
    """[T, ...] -> [T // block, block, ...]."""
    return x.reshape((x.shape[0] // block, block) + x.shape[1:])
