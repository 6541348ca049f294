"""EVA attention of ONE sequence: an exact causal window beside pooled chunk
summaries of everything before it, in one softmax.

Positions fall into windows of ``window`` (``w(t) = t // window``) and chunks
of ``chunk`` (a chunk never straddles a window).  Per head two learned
vectors ``phi``, ``mu``.  A complete chunk ``j`` of rotated keys and values
is pooled into one row (`chunk_summaries`):

    a = softmax_m(phi . k_m)   float32, over the chunk's positions m
    ks_j = sum_m a_m k_m + mu        vs_j = sum_m a_m v_m

and the query at ``t`` attends, in ONE float32 softmax of q . key / sqrt(D),
over the keys of its own window up to itself - exact, causal - and the
summaries of every chunk of an EARLIER window; the values are the matching
rows.  Nothing of an earlier window is ever read exactly, so what a decode
step reads is bounded: a ring of ``window`` rows and a summary table that
gains one row every ``chunk`` positions.

Two forms, both XLA, both through that state: `prefill_attention` takes T
positions into it - a prompt from position 0 enters an empty one, a suffix
enters what a prefix left - and goes over the queries block by block
(`lax.map`), each block against its own window's keys and the summary rows,
so no [heads, T, T] array exists; `decode_attention` is one query row
against the ring and the table, what is not visible masked out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def chunk_summaries(k, v, phi, mu, *, chunk: int):
    """k, v [T, H, D] (T a multiple of ``chunk``), phi, mu [H, D] ->
    summary keys and values [T // chunk, H, D] in k's dtype."""
    t, h, d = k.shape
    kc = k.reshape(t // chunk, chunk, h, d)
    vc = v.reshape(t // chunk, chunk, h, d)
    a = jax.nn.softmax(jnp.einsum("jmhd,hd->jmh", kc, phi,
                                  preferred_element_type=F32), axis=1)
    ks = jnp.einsum("jmh,jmhd->jhd", a, kc.astype(F32)) + mu.astype(F32)
    vs = jnp.einsum("jmh,jmhd->jhd", a, vc.astype(F32))
    return ks.astype(k.dtype), vs.astype(v.dtype)


def _joint_softmax(q, keys, values, visible):
    """One softmax over several key sets: q [B, H, D]; keys[i], values[i]
    [S_i, H, D]; visible[i] [B, S_i] -> [B, H, D] in q's dtype."""
    scale = q.shape[-1] ** -0.5
    logits = jnp.concatenate([
        jnp.where(vis[None], jnp.einsum(
            "bhd,shd->hbs", q, k, preferred_element_type=F32) * scale,
            -jnp.inf)
        for k, vis in zip(keys, visible)], axis=-1)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out, at = 0.0, 0
    for v in values:
        out = out + jnp.einsum("hbs,shd->bhd", w[..., at:at + v.shape[0]], v,
                               preferred_element_type=F32)
        at += v.shape[0]
    return out.astype(q.dtype)


def prefill_attention(q, k, v, ks, vs, ring_k, ring_v, table_k, table_v, *,
                      position: int, window: int, chunk: int,
                      block: int = 256):
    """T positions from ``position`` on, ENTERING a decode state: q, k, v
    [T, H, D] (rotated) and ks, vs [T // chunk, H, D], the summaries of the
    chunks they complete; the ring [window, H, D], of which rows
    0 .. position % window - 1 hold the window ``position`` lies in so far,
    and the summary table [rows, H, D], of which the rows of the chunks
    before ``position`` are written.  ``position`` and T are whole chunks
    and static; the new positions may cross window boundaries.  A prompt
    from position 0 is the case of a state with nothing in it.

    -> (out [T, H, D]; the ring as position + T finds it - the rows of its
    unfinished window, zeros behind them - K and V; the table with the new
    rows, K and V).

    The queries go ``block`` at a time (``block`` divides ``window``; blocks
    are cut at multiples of ``block``, so none straddles a window: the
    first is padded in front, the last behind), each block against the
    ``window`` keys of its own window - ring rows before the new ones in
    the first - and the summary rows up to the last new one: at most
    [H, block, window + (position + T) // chunk] logits are alive."""
    t, h, d = q.shape
    block = min(block, window)
    if window % block or window % chunk:
        raise ValueError(f"window {window} must hold whole blocks of {block} "
                         f"and whole chunks of {chunk}")
    if position % chunk or t % chunk:
        raise ValueError(f"{t} positions from {position} on are not whole "
                         f"chunks of {chunk}")
    end = position + t
    if end > table_k.shape[0] * chunk:
        raise ValueError(f"the summary table's {table_k.shape[0]} rows do "
                         f"not reach position {end}")
    first_window = position // window
    held = position - first_window * window  # ring rows before the new ones
    # the window `end` lies in is the last: empty where `end` starts it
    n_windows = end // window - first_window + 1

    def by_window(ring, new):
        rows = jnp.concatenate([ring[:held], new])
        return jnp.pad(rows, ((0, n_windows * window - held - t), (0, 0),
                              (0, 0))).reshape(n_windows, window, h, d)

    kw, vw = by_window(ring_k, k), by_window(ring_v, v)
    table_k = lax.dynamic_update_slice_in_dim(table_k, ks, position // chunk, 0)
    table_v = lax.dynamic_update_slice_in_dim(table_v, vs, position // chunk, 0)
    seen_k, seen_v = table_k[:end // chunk], table_v[:end // chunk]
    lead = held % block  # rows in front of the first query, to a whole block
    n_blocks = -(-(lead + t) // block)
    qb = jnp.pad(q, ((lead, n_blocks * block - lead - t), (0, 0), (0, 0))
                 ).reshape(n_blocks, block, h, d)
    per_window = window // chunk

    def one(args):
        i, qi = args
        start = held - lead + i * block  # counted from the first window's row 0
        w = start // window
        # position inside the window of each query of the block
        at = start - w * window + jnp.arange(block)
        exact = jnp.arange(window)[None, :] <= at[:, None]
        earlier = jnp.broadcast_to(
            jnp.arange(seen_k.shape[0])[None, :] < per_window * (
                first_window + w), (block, seen_k.shape[0]))
        return _joint_softmax(qi, (kw[w], seen_k), (vw[w], seen_v),
                              (exact, earlier))

    out = lax.map(one, (jnp.arange(n_blocks), qb))
    return (out.reshape(n_blocks * block, h, d)[lead:lead + t],
            kw[-1], vw[-1], table_k, table_v)


def decode_attention(q, ring_k, ring_v, table_k, table_v, *, position,
                     window: int, chunk: int):
    """One query (q [H, D], rotated) at ``position`` against the decode
    state: the ring [window, H, D], of which rows 0 .. position % window
    are visible (its own row already written), and the summary table
    [max_len // chunk, H, D], of which the rows of earlier windows are
    -> [H, D]."""
    in_ring = jnp.arange(window) <= position % window
    in_table = jnp.arange(table_k.shape[0]) < (
        position // window) * (window // chunk)
    return _joint_softmax(q[None], (ring_k, table_k), (ring_v, table_v),
                          (in_ring[None], in_table[None]))[0]
