"""Selective state-space ops (Mamba-2 / SSD): the depthwise causal
convolution, the chunked scan for a sequence (from its start, or entering
the state its prefix left), and the one-token recurrence, all over ONE
sequence (no batch axis).

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        S: [H, P, N]
    y_t = S_t C_t

with H heads of width P, N state columns, and B / C shared by the H // G
heads of each of G groups.  `ssd_chunked` is the block decomposition of that
recurrence (Dao & Gu 2024, "minimal SSD"): inside a chunk of L tokens the
output is a masked [L, L] matmul, and only one state per chunk is carried
from chunk to chunk, so a prefill is matmuls plus T / L sequential steps.
`ssd_step` is the recurrence itself, for decoding through the state.

Everything here is float32: the state integrates thousands of small
increments, and the decays exp(dt A) sit close to 1.  On a TPU a float32
einsum runs in one bfloat16 pass unless told otherwise, so the einsums name
`Precision.HIGHEST`; their FLOPs are a few percent of the projections
around them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
_HI = lax.Precision.HIGHEST


def causal_conv1d(x, kernel, bias, tail):
    """Depthwise causal convolution over time.

    ``x`` [T, C]; ``kernel`` [K, C] (tap ``i`` multiplies the input K-1-i
    steps back, torch ``Conv1d(groups=C, padding=K-1)`` order); ``bias``
    [C] (None: the convolution has none); ``tail`` [K-1, C], the inputs
    that came before ``x`` (zeros at the start of a sequence).  Returns
    (y [T, C] float32, the new tail).  The same code serves a prefill (T
    tokens) and a decode step (T = 1)."""
    k, t = kernel.shape[0], x.shape[0]
    padded = jnp.concatenate([tail.astype(x.dtype), x], axis=0)
    y = None if bias is None else bias.astype(F32)
    for i in range(k):
        tap = padded[i:i + t].astype(F32) * kernel[i].astype(F32)
        y = tap if y is None else y + tap
    return y, padded[t:]


def _by_group(a, groups):
    """[..., H, *rest] -> [..., G, H // G, *rest] on the axis after time."""
    return a.reshape(a.shape[:2] + (groups, a.shape[2] // groups)
                     + a.shape[3:])


def ssd_chunked(x, dt, a, b, c, *, chunk, state=None):
    """The recurrence over T tokens entering ``state`` [H, P, N] (None: a
    zero state, the start of a sequence).

    ``x`` [T, H, P], ``dt`` [T, H] (after softplus), ``a`` [H] (negative),
    ``b`` / ``c`` [T, G, N]; T a multiple of ``chunk``.  Only the carry from
    chunk to chunk starts elsewhere, so a sequence cut at a chunk boundary
    is, chunk for chunk, the arithmetic of the whole one.
    Returns (y [T, H, P], the state after the last token [H, P, N]),
    float32."""
    t, h, p = x.shape
    g, n = b.shape[1:]
    if t % chunk:
        raise ValueError(f"sequence length {t} is not a multiple of the "
                         f"chunk size {chunk}")
    nc, k = t // chunk, h // g
    x, dt, b, c = (v.astype(F32) for v in (x, dt, b, c))
    da = (dt * a.astype(F32)).reshape(nc, chunk, g, k)
    xdt = _by_group((x * dt[..., None]).reshape(nc, chunk, h, p), g)
    b = b.reshape(nc, chunk, g, n)
    c = c.reshape(nc, chunk, g, n)
    cum = jnp.cumsum(da, axis=1)  # [nc, L, G, K]: log decay from chunk start

    # inside a chunk: y_l += sum_{s<=l} (C_l . B_s) exp(cum_l - cum_s) xdt_s
    seg = cum[:, :, None] - cum[:, None, :]  # [nc, l, s, G, K]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[None, :, :, None, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("clgn,csgn->clsg", c, b, precision=_HI)
    y = jnp.einsum("clsgk,csgkp->clgkp", cb[..., None] * decay, xdt,
                   precision=_HI)

    # what each chunk adds to the state at its own end
    to_end = jnp.exp(cum[:, -1:] - cum)  # [nc, L, G, K]
    added = jnp.einsum("csgn,csgkp->cgkpn", b, xdt * to_end[..., None],
                       precision=_HI)
    whole = jnp.exp(cum[:, -1])  # [nc, G, K]: a chunk's total decay

    def carry(state, chunk_terms):
        add, dec = chunk_terms
        return state * dec[..., None, None] + add, state

    start = (jnp.zeros((g, k, p, n), F32) if state is None
             else state.astype(F32).reshape(g, k, p, n))
    last, entering = lax.scan(carry, start, (added, whole))
    # the state a chunk entered with, decayed to each of its tokens
    y = y + jnp.einsum("clgn,cgkpn->clgkp", c, entering,
                       precision=_HI) * jnp.exp(cum)[..., None]
    return y.reshape(t, h, p), last.reshape(h, p, n)


def ssd_step(state, x, dt, a, b, c):
    """One token through the recurrence.  ``state`` [H, P, N] (its dtype is
    kept: float32 as served); ``x`` [H, P], ``dt`` [H], ``a`` [H], ``b`` /
    ``c`` [G, N].  Returns (y [H, P] float32, the new state)."""
    h = x.shape[0]
    per_head = h // b.shape[0]
    x, dt = x.astype(F32), dt.astype(F32)
    bh = jnp.repeat(b.astype(F32), per_head, axis=0)  # [H, N]
    ch = jnp.repeat(c.astype(F32), per_head, axis=0)
    new = (state.astype(F32) * jnp.exp(dt * a.astype(F32))[:, None, None]
           + (dt[:, None] * x)[:, :, None] * bh[:, None, :])
    y = jnp.sum(new * ch[:, None, :], axis=-1)
    return y, new.astype(state.dtype)
