"""Grouped-query attention of a FEW query rows against a whole KV cache: one
attention, two routes, and `cache_attention` takes one by the call's shape.

The cache is a layer's ``k`` / ``v`` [Hkv, max_len, D], KV-head major, as a
decode loop carries them; each of the T query rows [Hq, D] sees cache rows
``0 .. limits[i]`` (its own position for causal attention, the end of its
block for attention by blocks) and ``Hq / Hkv`` query heads share a KV head.

`ops/attention.py gqa_sdpa_by_query_block`, the XLA form - two einsums round
a float32 softmax.  Both einsums have the cache as an operand and the
softmax between them needs a whole row's maximum first, so the compiler
cannot fuse them: keys and values cross the HBM whole, under the mask,
wherever the limits are, and an einsum's operand cannot be held to a memory
space - the compiler is free to move whole caches through VMEM round the
rows' write in front of it.  It serves any number of queries (a prompt, a
suffix entering a cache) and every call off the TPU.

`streamed_gqa_attention`, one Pallas TPU kernel, a grouped-query sibling of
`ops/mla.py streamed_attention`: the T * Hq / Hkv query rows of every KV
head stay in VMEM, the cache rows ``0 .. max(limits)`` come through VMEM in
blocks, once - a block is the same rows of all KV heads, keys and values a
copy each -, scores, online softmax and the weighted sum of a block done
before the next is needed; rows beyond the furthest limit are neither
fetched nor computed on, and both cache operands are held to the HBM, so the
rows' write in front of the call lands in place.  Off the TPU it runs only
interpreted, from tests.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import gqa_sdpa_by_query_block

F32 = jnp.float32

# `streamed_gqa_attention`: cache rows a block (one step of the kernel's
# loop: that many rows of every KV head, keys and values), the blocks its
# ring holds (`_RING - 1` arriving while one is computed on), the rows a
# copy of the LAST block brings, which is fetched only as far as the
# furthest limit reaches, and the query rows a KV head (T * Hq / Hkv) the
# kernel takes: they and a block's float32 scores [Hkv, rows, block] are
# what it keeps in VMEM beside the ring.  Timed alone on one v5e at 32 query
# heads over 4 KV heads of 128, caches of 8704 rows, 24 of them carried by a
# loop that writes the sweep's rows into each before its call (my chip runs,
# PR 43; us a call at T = 4 / T = 8 rows, position 8192: 8320 rows fetched):
# 26.3 / 25.6 in blocks of 256 through a ring of 4, 26.9 / 26.0 at 512
# through 3, 27.0 / 26.1 at 512 through 4, 28.1 / 27.2 at 1088 through 3,
# 28.8 / 28.3 at 2176 through 2; the XLA form 25.9 / 32.5 wherever the
# position is.  Larger blocks are SLOWER: the kernel is the stream - with the
# arithmetic taken out it takes 25.9 / 24.7 at 512 through 3, the same with a
# copy a KV head - and what a smaller block shortens is the wait for the
# first block before any arithmetic and the last block's arithmetic after
# the last byte.  (MAX_QUERY_ROWS: 32 and 64 are the served shapes and the
# timed ones; 128 compiles for the v5e and fits, untimed.)
_MAX_BLOCK_ROWS = 256
_RING = 4
_SUB_ROWS = 128
MAX_QUERY_ROWS = 128
# per KV head, rows of queries against rows of the cache: the last axis of
# both contracted, nothing transposed in memory ...
_QK = (((2,), (2,)), ((0,), (0,)))
# ... and the weights [Hkv, rows, block] against the values [Hkv, block, D]
_PV = (((2,), (1,)), ((0,), (0,)))


def _sweep_kernel(limits_ref, q_ref, k_hbm, v_hbm, out_ref, rows_ref,
                  k_buf, v_buf, sems, *, group, sub):
    """Every KV head's query rows [Hkv, R, D] in VMEM (row ``r`` of a head
    is query ``r // group``), the queries' limits [T] in SMEM, the caches
    left in HBM as [Hkv, blocks, block, D] -> out [Hkv, R, D], rows [1] =
    the cache rows fetched of each KV head."""
    ring, hkv, block, d = k_buf.shape
    r = q_ref.shape[1]
    t = r // group
    subs = block // sub
    # (a limit past the cache's last row sees the whole cache, as under the
    # XLA form's mask; no copy reaches beyond the arrays)
    limits = [jnp.minimum(limits_ref[i], k_hbm.shape[1] * block - 1)
              for i in range(t)]
    nearest = functools.reduce(jnp.minimum, limits)
    furthest = functools.reduce(jnp.maximum, limits)
    n_full = (nearest + 1) // block  # blocks every query sees every row of
    n_whole = (furthest + 1) // block  # blocks fetched whole
    last_subs = (furthest + 1 - n_whole * block + sub - 1) // sub
    rows_ref[0] = n_whole * block + last_subs * sub
    caches = ((k_hbm, k_buf), (v_hbm, v_buf))

    def whole_copies(j):
        b = j % ring
        return [pltpu.make_async_copy(hbm.at[:, j], buf.at[b], sems.at[b, i, 0])
                for i, (hbm, buf) in enumerate(caches)]

    def last_copies(act):
        """The block the furthest limit lies in (if no block ends on it),
        ``sub`` rows a copy, as far as that limit reaches."""
        b = n_whole % ring
        for n in range(subs):
            @pl.when(n < last_subs)
            def _():
                rows = pl.ds(n * sub, sub)
                for i, (hbm, buf) in enumerate(caches):
                    act(pltpu.make_async_copy(hbm.at[:, n_whole, rows],
                                              buf.at[b, :, rows],
                                              sems.at[b, i, n]))

    def start(j):
        @pl.when(j < n_whole)
        def _():
            for copy in whole_copies(j):
                copy.start()

        @pl.when(j == n_whole)  # past it nothing is fetched
        def _():
            last_copies(lambda copy: copy.start())

    for j in range(ring - 1):  # ring - 1 blocks ahead
        start(j)
    q = q_ref[...]
    scale = d ** -0.5
    # each query row's limit [1, R, 1]
    query = lax.broadcasted_iota(jnp.int32, (1, r, 1), 1)
    limit = jnp.full((1, r, 1), limits[0], jnp.int32)
    for i in range(1, t):
        limit = jnp.where(query >= i * group, limits[i], limit)

    def attend(j, m, l, acc, *, masked):
        # a block is widened after it arrives
        k = k_buf[j % ring].astype(q.dtype)
        v = v_buf[j % ring].astype(q.dtype)
        s = lax.dot_general(q, k, _QK, preferred_element_type=F32) * scale
        if masked:
            # rows past the furthest limit were never fetched (or never
            # written) and may hold anything: a weight of 0 would not keep
            # a NaN out
            row = j * block + lax.broadcasted_iota(jnp.int32, (1, block, 1),
                                                   1)
            v = jnp.where(row <= furthest, v, jnp.zeros_like(v))
            col = j * block + lax.broadcasted_iota(jnp.int32, (1, 1, block),
                                                   2)
            s = jnp.where(col <= limit, s, -jnp.inf)
        # online softmax in float32; the MXU takes the weights in the
        # queries' dtype, as the XLA form's second einsum does
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        return (m_new, alpha * l + jnp.sum(p, axis=-1, keepdims=True),
                alpha * acc + lax.dot_general(p.astype(v.dtype), v, _PV,
                                              preferred_element_type=F32))

    def step(j, carry, *, masked):
        start(j + ring - 1)
        for copy in whole_copies(j):
            copy.wait()
        return attend(j, *carry, masked=masked)

    carry = (jnp.full((hkv, r, 1), -jnp.inf, F32),
             jnp.zeros((hkv, r, 1), F32), jnp.zeros((hkv, r, d), F32))
    carry = lax.fori_loop(0, n_full,
                          functools.partial(step, masked=False), carry)
    # whole blocks that the nearest limit ends before: queries of two
    # blocks in one call, at most one such block between their limits
    m, l, acc = lax.fori_loop(n_full, n_whole,
                              functools.partial(step, masked=True), carry)

    @pl.when(last_subs == 0)  # a block ends on the furthest limit
    def _():
        out_ref[...] = (acc / l).astype(out_ref.dtype)

    @pl.when(last_subs > 0)
    def _():
        last_copies(lambda copy: copy.wait())
        _, l_last, acc_last = attend(n_whole, m, l, acc, masked=True)
        out_ref[...] = (acc_last / l_last).astype(out_ref.dtype)


def _block_rows(max_len: int) -> int:
    """The largest multiple of `_SUB_ROWS` rows, up to `_MAX_BLOCK_ROWS`,
    that divides the cache; 0: none does."""
    return next((b for b in range(_MAX_BLOCK_ROWS, 0, -_SUB_ROWS)
                 if max_len % b == 0), 0)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def streamed_gqa_attention(q, k, v, limits, *, block_rows: int = None,
                           interpret: bool = False):
    """`gqa_sdpa_by_query_block` for a few queries ``q`` [T, Hq, D] against
    the WHOLE cache arrays ``k`` / ``v`` [Hkv, max_len, D] as the decode
    loop carries them (never a slice: that would be a copy in front of the
    call), query i seeing rows ``0 .. limits[i]`` (``limits`` [T] int32, in
    any order), as one Pallas TPU kernel: the rows ``0 .. max(limits)`` of
    all KV heads cross the HBM once, in blocks of ``block_rows`` (default
    `_block_rows`) of which a ring is in flight, the last one `_SUB_ROWS` at
    a time (or the largest divisor of a smaller block) as far as the
    furthest limit reaches: float32 scores, an online softmax in float32,
    the weights in the queries' dtype into the MXU, a float32 accumulator.
    Rows beyond the furthest limit are neither fetched nor computed on; a
    cache held a precision below ``q`` is read in ``q``'s dtype.
    -> (out [T, Hq, D] in ``q``'s dtype, the cache rows fetched of each KV
    head).  ``interpret`` runs it on the CPU."""
    t, hq, d = q.shape
    hkv, max_len, _ = k.shape
    block = block_rows or _block_rows(max_len)
    if hq % hkv:
        raise ValueError(f"{hq} query heads over {hkv} KV heads")
    if not block or max_len % block:
        raise ValueError(f"streamed_gqa_attention: blocks of {block} rows "
                         f"do not divide a cache of {max_len}")
    group = hq // hkv
    r = t * group
    sub = math.gcd(block, _SUB_ROWS)
    buffer_bytes = (2 * _RING * hkv * block * d * k.dtype.itemsize
                    + 4 * hkv * r * (block + d) * 4)
    out, rows = pl.pallas_call(
        functools.partial(_sweep_kernel, group=group, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # the queries' limits, in SMEM
            grid=(1,),
            in_specs=[pl.BlockSpec((hkv, r, d), lambda i, *_: (0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((hkv, r, d), lambda i, *_: (0, 0, 0)),
                       pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=[pltpu.VMEM((_RING, hkv, block, d), k.dtype),
                            pltpu.VMEM((_RING, hkv, block, d), v.dtype),
                            pltpu.SemaphoreType.DMA((_RING, 2,
                                                     block // sub))]),
        out_shape=[jax.ShapeDtypeStruct((hkv, r, d), q.dtype),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=buffer_bytes + (16 << 20)),
        interpret=pltpu.InterpretParams() if interpret else False,
        # the device op's name: `lm.attn` stays in its op_name, which is how
        # the benchmark's `sdar_attn_ms_per_token` finds it
        name="gqa_cache_attention",
    )(jnp.asarray(limits, jnp.int32),
      # a KV head's query rows together: [T, Hkv, group, D] -> [Hkv, R, D]
      q.reshape(t, hkv, group, d).swapaxes(0, 1).reshape(hkv, r, d),
      # a block is one index of the second axis (a bitcast: whole tiles);
      # held to the HBM: left to itself the compiler moves whole caches into
      # VMEM for the rows' write in front of the call and copies them back
      *(pltpu.with_memory_space_constraint(
          a.reshape(hkv, -1, block, d), pltpu.HBM) for a in (k, v)))
    return (out.reshape(hkv, t, group, d).swapaxes(0, 1).reshape(t, hq, d),
            rows[0])


def cache_attention(q, k, v, *, limits, visible: int = None):
    """``q`` [T, Hq, D] against ``k`` / ``v`` [Hkv, S, D] - a layer's whole
    cache, or a prompt's own keys and values -, query i seeing rows
    ``0 .. limits[i]``, by the route the call's shape asks for -> (out
    [T, Hq, D], the cache rows `streamed_gqa_attention` fetched of each KV
    head: 0 on the XLA route).

    Few queries (at most `MAX_QUERY_ROWS` rows a KV head: a decode pass, or
    two sharing a sweep) against rows that whole blocks divide, heads of
    whole lanes, on a TPU go through `streamed_gqa_attention`; everything
    else - a prompt, a suffix entering a cache, an odd shape, another
    backend - is `gqa_sdpa_by_query_block` over the first ``visible`` rows
    (static; None: all of them under the mask)."""
    t, hq, d = q.shape
    hkv, s, _ = k.shape
    if (t * (hq // hkv) <= MAX_QUERY_ROWS and _block_rows(s)
            and d % 128 == 0 and jax.devices()[0].platform == "tpu"):
        return streamed_gqa_attention(q, k, v, limits)
    return gqa_sdpa_by_query_block(
        q, k[:, :visible], v[:, :visible],
        q_positions=limits), jnp.zeros((), jnp.int32)
