"""Sparse experts, one chip's share: a router over ALL experts, and the part
of the result that the experts held here give.

Expert parallelism splits an expert layer's experts over the chips that share
the layer; every chip routes every token over the full published router
width, and computes, for the tokens routed to ITS experts, those experts'
weighted outputs.  Summed over the chips (the exchange a deployment runs
between them) that is the layer's routed output.  This module is the
per-chip part and nothing else: on one chip the layer runs without its
exchange, and what the absent experts would have added is left out.

`local_expert_sum` is a grouped matmul: the (token, expert) assignments that
fall on held experts are sorted by expert, each expert's rows go through its
own pair of matrices (`lax.ragged_dot`, which the TPU compiler lowers to a
grouped Mosaic kernel that visits only the non-empty groups' weights - at
one decoded token, the two or three experts that token chose here, not all
that are held), and the rows are summed back per token under the router's
weights.  Tokens are routed unevenly and none is dropped: there is no
capacity.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
# Below 64 rows the TPU compiler lowers `ragged_dot` to one dense masked
# matmul over EVERY group - all the held experts' weights read for one
# decoded token (seen in the program compiled for a v5e, PR 27) - and from 64
# rows on to the grouped kernel that visits only the groups that have rows.
# A decode step's 22 assignment rows are therefore padded with rows that
# belong to no group.
MIN_GROUPED_ROWS = 64


def route(u, router_kernel, score_bias, *, top_k: int, scale: float):
    """Sigmoid router with a selection-only bias (DeepSeek-V3 style,
    ``n_group`` 1: no group limit).

    ``u`` [T, D]; ``router_kernel`` [D, E]; ``score_bias`` [E].  In float32
    throughout: s = sigmoid(u W); the ``top_k`` largest of s + bias are
    chosen; their weights are ``scale * s_i / sum of the chosen s``.
    Returns (expert ids [T, top_k] int32, weights [T, top_k] float32)."""
    logits = jnp.dot(u.astype(F32), router_kernel.astype(F32),
                     precision=lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(s + score_bias.astype(F32), top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    weights = scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), weights


def local_expert_sum(x, idx, weights, w1, w2, *, first_expert: int):
    """sum over the chosen experts HELD HERE of w_i * relu(x W1_i)^2 W2_i.

    ``x`` [T, d]; ``idx`` / ``weights`` [T, k] from `route` (ids over all
    experts); ``w1`` [E_local, d, f], ``w2`` [E_local, f, d]: experts
    ``first_expert .. first_expert + E_local - 1``.  Returns ([T, d]
    float32, how many of the T * k assignments fell on held experts)."""
    t, k = idx.shape
    e_local = w1.shape[0]
    local = idx - first_expert
    held = (local >= 0) & (local < e_local)
    # held assignments first, grouped by expert; the others after them
    group = jnp.where(held, local, e_local).reshape(-1)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=e_local + 1)[:e_local].astype(
        jnp.int32)
    n_held = jnp.sum(sizes)
    rows = x[order // k]
    if t * k < MIN_GROUPED_ROWS:
        rows = jnp.pad(rows, ((0, MIN_GROUPED_ROWS - t * k), (0, 0)))
    hidden = lax.ragged_dot(rows, w1, sizes, preferred_element_type=F32)
    hidden = jnp.square(jax.nn.relu(hidden)).astype(x.dtype)
    out = lax.ragged_dot(hidden, w2, sizes, preferred_element_type=F32)[:t * k]
    # rows past the last group belong to no expert held here
    in_a_group = jnp.arange(t * k) < n_held
    out = jnp.where(in_a_group[:, None],
                    out * weights.reshape(-1)[order][:, None], 0.0)
    # back to assignment order (a gather, not a scatter-add), k rows a token
    back = jnp.argsort(order)
    return out[back].reshape(t, k, -1).sum(axis=1), n_held
