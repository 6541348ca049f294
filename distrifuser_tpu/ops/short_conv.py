"""The gated short convolution as a token mixer (LFM2's ``conv`` layers): two
multiplicative gates round a depthwise causal convolution of a few taps, no
activation, no recurrence behind it.

    [B | C | x] = h W_in                      three [T, d], in THIS order
    g   = B * x                               the first gate
    c_t = sum_i k[i] * g_{t - (K-1) + i}      per channel, causal, no bias
    y   = C * c                               the second gate

What crosses a call is the TAIL: the last K - 1 rows of the GATED inputs
``g`` (not of ``h``, not of ``x``) - bounded, whatever the length, and a
value: a suffix's first rows and a decode step's one row start from it.  The
taps are `ops/ssm.py causal_conv1d`, the code the Mamba-2 and KDA mixers run
in front of their recurrences; here the convolution is the whole mixer.
"""

from __future__ import annotations

import jax.numpy as jnp

from .ssm import causal_conv1d

F32 = jnp.float32


def gated_short_conv(bcx, kernel, tail):
    """``bcx`` [T, 3 d], the input projection's output ``[B | C | x]``;
    ``kernel`` [K, d] (tap ``i`` multiplies the gated input K-1-i steps
    back); ``tail`` [K-1, d], the gated inputs before these rows (zeros at
    the start of a sequence).  One function for a prompt (T rows from a zero
    tail), a suffix entering a tail, and a decode step (T = 1).
    -> (y [T, d] in ``bcx``'s dtype - the taps and the second gate in
    float32 -, the new tail [K-1, d] in ``bcx``'s dtype)."""
    b, c, x = jnp.split(bcx, 3, axis=-1)
    conv, tail = causal_conv1d(b * x, kernel, None, tail)
    return (c.astype(F32) * conv).astype(bcx.dtype), tail
