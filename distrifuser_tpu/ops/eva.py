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

Two forms, both through that state.  `prefill_attention` (XLA) takes T
positions into it - a prompt from position 0 enters an empty one, a suffix
enters what a prefix left - and goes over the queries block by block
(`lax.map`), each block against its own window's keys and the summary rows,
so no [heads, T, T] array exists.  A decode step - one query row against
the ring and the table - goes through `step_attention`, which takes one of
two routes by what the call can observe:

`decode_attention`, the XLA form and the plain one: every row the state
holds under a mask, whatever the position - the whole ring and the whole
table cross the HBM each step.  Every call off the TPU takes it, and a
shape the kernel's blocks do not divide; it is what the kernel is held to.

`streamed_decode_attention`, one Pallas TPU kernel: the position a scalar,
ring and table left in HBM as the decode loop carries them, the rows the
query may see - ring rows ``0 .. t % window``, the summary rows of earlier
windows - fetched once, in blocks, and ONE online softmax over both key sets
while a block is in VMEM; rows past the position (after a roll: the
previous window's keys and values, finite and plausible) are neither
fetched nor computed on.  Off the TPU it runs only interpreted, from tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


# -- a decode step in one pass over the rows the query may see ----------------

# `streamed_decode_attention`: ring or summary rows a block (one step of the
# kernel's loop; a window adds window // chunk summary rows, whole blocks)
# and the blocks its buffers hold (`_SLOTS - 1` arriving while one is
# computed on).  Timed alone on one v5e at H = 32, D = 128, window 2048, 16
# layers' rings and tables carried by a loop that writes a row into each
# before its call (my chip runs, PR 37): at positions 3900-4027 (1920-2048
# ring rows + 128 summary rows in view) a call takes 50.9 / 51.7 / 54.3 us in
# ring blocks of 128 / 256 / 512 rows through 3 buffers (the last block 128
# rows a copy; 50.9 at 128 through 2 or 4) - 34 MB at 676 GB/s: the block's
# two matmuls and the softmax between them hide under its 2 MB of DMA from
# 128 rows on -, at positions 4100-4227 (1-256 ring rows + 256 summary rows)
# 13.3 / 14.6 / 16.5 (larger blocks compute on rows of the last one that
# they do not need); the XLA form 61.5 wherever the position is.
_BLOCK_ROWS = 128
_SLOTS = 3
# contract the last axis of both operands: rows of queries against rows of
# keys, nothing transposed in memory
_NT = (((1,), (1,)), ((), ()))


def _streamed_kernel(pos_ref, q_ref, ring_k, ring_v, table_k, table_v,
                     out_ref, rows_ref, k_buf, v_buf, sems, *, window, chunk,
                     scale):
    """One query's heads [H, D] in VMEM, its position in SMEM, the ring
    [window, H, D] and the table [rows, H, D], K and V, left in HBM ->
    out [H, D], rows [1] = the ring and summary rows fetched.

    Every head has its own keys, so a block's rows go through the MXU as
    [rows * H, D] against ALL H queries at once and the products of a query
    with another head's keys are masked out of the softmax (-inf: weight 0
    into the value product): H times the arithmetic the heads need, on a
    unit that is otherwise idle, and no per-head relayout of the block."""
    slots, block, heads, d = k_buf.shape
    position = pos_ref[0]
    seen = position % window + 1  # ring rows the query may see, its own too
    n_full = seen // block  # ring blocks whose every row is visible
    tail = seen - n_full * block  # rows of the block its own row lies in
    n_table = position // window * (window // chunk) // block
    # blocks in the order they are attended to: the ring's whole ones, the
    # table's, then the ring block the query's own row lies in (if no block
    # ends on it) - one softmax, whatever the order
    n_plain = n_full + n_table
    n_blocks = n_plain + (tail > 0)
    rows_ref[0] = n_blocks * block

    def copies(j, act):
        b = j % slots

        def pair(k_hbm, v_hbm, first):
            src = pl.ds(first * block, block)
            act(pltpu.make_async_copy(k_hbm.at[src], k_buf.at[b],
                                      sems.at[b, 0]))
            act(pltpu.make_async_copy(v_hbm.at[src], v_buf.at[b],
                                      sems.at[b, 1]))

        in_table = (j >= n_full) & (j < n_plain)

        @pl.when(in_table)
        def _():
            pair(table_k, table_v, j - n_full)

        @pl.when(~in_table & (j < n_blocks))  # past them nothing is fetched
        def _():
            pair(ring_k, ring_v, jnp.minimum(j, n_full))

    for j in range(slots - 1):
        copies(j, lambda copy: copy.start())
    q = q_ref[...]
    own_head = (lax.broadcasted_iota(jnp.int32, (heads, block * heads), 1)
                % heads == lax.broadcasted_iota(
                    jnp.int32, (heads, block * heads), 0))

    def attend(j, m, l, acc, *, visible=None):
        k = k_buf[j % slots].reshape(block * heads, d).astype(q.dtype)
        v = v_buf[j % slots].reshape(block * heads, d).astype(q.dtype)
        keep = own_head
        if visible is not None:
            # rows past the query's hold the previous window's keys and
            # values: out of the scores, and out of the value product too -
            # a weight of 0 would not keep a NaN out
            row = lax.broadcasted_iota(jnp.int32, (block * heads, 1), 0)
            v = jnp.where(row < visible * heads, v, jnp.zeros_like(v))
            col = lax.broadcasted_iota(jnp.int32, (1, block * heads), 1)
            keep = keep & (col < visible * heads)
        s = jnp.where(keep, lax.dot_general(
            q, k, _NT, preferred_element_type=F32) * scale, -jnp.inf)
        # online softmax in float32; the MXU takes the weights in the
        # queries' dtype, as the XLA form's second einsum does
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        return (m_new, alpha * l + jnp.sum(p, axis=-1, keepdims=True),
                alpha * acc + jnp.dot(p.astype(v.dtype), v,
                                      preferred_element_type=F32))

    def step(j, carry):
        copies(j + slots - 1, lambda copy: copy.start())
        copies(j, lambda copy: copy.wait())
        return attend(j, *carry)

    m, l, acc = lax.fori_loop(0, n_plain, step, (
        jnp.full((heads, 1), -jnp.inf, F32), jnp.zeros((heads, 1), F32),
        jnp.zeros(out_ref.shape, F32)))

    @pl.when(tail == 0)  # a block ends on the query's row
    def _():
        out_ref[...] = (acc / l).astype(out_ref.dtype)

    @pl.when(tail > 0)
    def _():
        copies(n_plain, lambda copy: copy.wait())
        _, l_last, acc_last = attend(n_plain, m, l, acc, visible=tail)
        out_ref[...] = (acc_last / l_last).astype(out_ref.dtype)


def _blocks_divide(window: int, chunk: int, block: int) -> bool:
    """Whether the window holds whole blocks of ``block`` rows and gains
    whole blocks of summary rows."""
    return window % block == 0 and window // chunk % block == 0


@functools.partial(jax.jit, static_argnames=("window", "chunk", "block_rows",
                                             "interpret"))
def streamed_decode_attention(q, ring_k, ring_v, table_k, table_v, *,
                              position, window: int, chunk: int,
                              block_rows: int = _BLOCK_ROWS,
                              interpret: bool = False):
    """`decode_attention` as one Pallas TPU kernel, against the WHOLE ring
    and table arrays as the decode loop carries them (never a slice: that
    would be a copy in front of the call): ring rows ``0 .. position %
    window`` cross the HBM once, in blocks of ``block_rows`` of which
    `_SLOTS` are in flight, as far as the position reaches; then the summary
    rows of earlier windows, whole such blocks; one online softmax over
    both, per head: float32 scores, float32 running max and sum, the weights
    in the queries' dtype into the value product, a float32 accumulator, one
    division at the end.  Blocks the query sees no row of are neither
    fetched nor computed on; the rows past its own in the last ring block
    are masked out of scores and values.
    -> (out [H, D] in ``q``'s dtype, the ring + summary rows fetched).
    ``interpret`` runs it on the CPU."""
    h, d = q.shape
    block = block_rows
    if not _blocks_divide(window, chunk, block) or ring_k.shape[0] != window:
        raise ValueError(f"streamed_decode_attention: blocks of {block} rows "
                         f"do not divide a ring of {ring_k.shape[0]} rows, "
                         f"window {window}, and the {window // chunk} "
                         f"summary rows a window adds")
    buffer_bytes = 2 * _SLOTS * block * h * d * ring_k.dtype.itemsize
    out, rows = pl.pallas_call(
        functools.partial(_streamed_kernel, window=window, chunk=chunk,
                          scale=d ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # the query's position, in SMEM
            grid=(1,),
            in_specs=[pl.BlockSpec((h, d), lambda i, *_: (0, 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * 4,
            out_specs=[pl.BlockSpec((h, d), lambda i, *_: (0, 0)),
                       pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=[pltpu.VMEM((_SLOTS, block, h, d), ring_k.dtype),
                            pltpu.VMEM((_SLOTS, block, h, d), ring_v.dtype),
                            pltpu.SemaphoreType.DMA((_SLOTS, 2))]),
        out_shape=[jax.ShapeDtypeStruct((h, d), q.dtype),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=buffer_bytes + (16 << 20)),
        interpret=pltpu.InterpretParams() if interpret else False,
        # the device op's name: `lm.eva.attn` stays in its op_name, which is
        # how the benchmark's `eva_attn_ms_per_byte` finds it
        name="eva_state_attention",
    )(jnp.asarray(position, jnp.int32).reshape(1), q,
      # held to the HBM: left to itself the compiler moves one layer's ring
      # and table into VMEM for the row's write and copies them back, 67 MB
      # a step that every other transfer of the step then queues behind
      *(pltpu.with_memory_space_constraint(a, pltpu.HBM)
        for a in (ring_k, ring_v, table_k, table_v)))
    return out, rows[0]


def step_attention(q, ring_k, ring_v, table_k, table_v, *, position,
                   window: int, chunk: int):
    """A decode step's attention by the route the call asks for -> (out
    [H, D], the ring + summary rows it read).

    On a TPU, heads of whole lanes and a window that whole blocks divide go
    through `streamed_decode_attention`, which reads the rows in view;
    everything else - another backend, an odd shape - is `decode_attention`,
    which reads every row the state holds."""
    if (_blocks_divide(window, chunk, _BLOCK_ROWS)
            and ring_k.shape[0] == window and q.shape[-1] % 128 == 0
            and jax.devices()[0].platform == "tpu"):
        return streamed_decode_attention(
            q, ring_k, ring_v, table_k, table_v, position=position,
            window=window, chunk=chunk)
    return decode_attention(
        q, ring_k, ring_v, table_k, table_v, position=position, window=window,
        chunk=chunk), window + table_k.shape[0]
