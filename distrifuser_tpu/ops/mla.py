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
`ops/attention.py causal_gqa_sdpa`.  The materialised form has one route,
XLA's.  The absorbed form has two, and `cache_attention` takes one by the
call's shape:

`absorbed_attention`, the XLA form - two einsums round a float32 softmax.
Both einsums have the cache as an operand and the softmax between them
needs a whole row's maximum first, so the compiler cannot fuse them: the
cache crosses the HBM twice, all of it under the mask whatever the position.
It serves several queries at once (the rows of a suffix entering a cache,
`QUERY_BLOCK` at a time) and every call off the TPU.

`streamed_attention`, one Pallas TPU kernel - ONE query (a decode step: 512
of them a request) against the whole cache arrays the loop carries: the
position is a scalar, the latent rows written so far come through VMEM in
blocks, once, and a block is key and value while it is there (scores,
online softmax and the weighted sum before the next block is needed); rows
beyond the position are not fetched.  Off the TPU it runs only interpreted,
from tests.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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
    """Queries against the latent cache itself, the XLA form: ``q_lat``
    [T, H, C] (the up-projection already folded in), ``q_pe`` [T, H, R]
    (rotated); the cache ``c`` [S, C] and ``k_pe`` [S, R], of which query i
    sees rows 0 .. q_positions[i] - rows not written yet are never read into
    the result.  -> the attended latents [T, H, C], for the caller to take
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


# -- the absorbed form in one pass over the rows written so far ---------------

# `streamed_attention`: cache rows a block (one step of the kernel's loop),
# the blocks its ring holds (`_RING - 1` arriving while one is computed on),
# and the rows a copy of the LAST block brings, which is fetched only as far
# as the position reaches.  Timed alone on one v5e at H = 32, C = 512,
# positions 8192 onward of 8704, 24 caches carried by a loop that writes a
# row into each before its call (my chip runs, PR 35): a call takes 19.8 /
# 15.1 / 15.8 us in blocks of 256 / 512 / 1088 rows through a ring of 3 (an
# earlier form of the kernel: 33.4 us at 128) - a block's two small matmuls
# and the softmax between them are a chain of ~0.6 us however few rows it
# has, which hides under the block's DMA (0.71 us, 740 GB/s) from 512 rows
# on -, 15.2 at 512 through a ring of 4 (the earlier form: 17.8 through 2),
# 16.0 at 2176 through 2; the XLA form there 33.0.  At positions 1024 onward
# it takes 5.3 us (the XLA form 33.0 wherever the position is).
_MAX_BLOCK_ROWS = 512
_RING = 3
_SUB_ROWS = 128
# contract the last axis of both operands: rows of queries against rows of
# the cache, nothing transposed in memory
_NT = (((1,), (1,)), ((), ()))


def _streamed_kernel(pos_ref, q_lat_ref, q_pe_ref, c_hbm, k_pe_hbm, out_ref,
                     rows_ref, c_buf, k_pe_buf, sems, *, scale, sub):
    """One query's H heads [H, C] / [H, R] in VMEM, its position in SMEM,
    the cache left in HBM as [blocks, block, C] and [blocks, block, R]
    -> out [H, C], rows [1] = the latent rows fetched."""
    ring, block, _ = c_buf.shape
    subs = block // sub
    position = pos_ref[0]
    n_full = (position + 1) // block  # blocks whose every row is visible
    last_subs = (position + 1 - n_full * block + sub - 1) // sub
    rows_ref[0] = n_full * block + last_subs * sub

    def block_copy(j):
        b = j % ring
        return pltpu.make_async_copy(c_hbm.at[j], c_buf.at[b], sems.at[b, 0])

    def last_copies(act):
        """The block that holds the query's own row (if no block ends on
        it), ``sub`` rows a copy, as far as the position reaches."""
        b = n_full % ring
        for k in range(subs):
            @pl.when(k < last_subs)
            def _():
                rows = pl.ds(k * sub, sub)
                act(pltpu.make_async_copy(c_hbm.at[n_full, rows],
                                          c_buf.at[b, rows], sems.at[b, k]))

    def start(j):
        @pl.when(j < n_full)
        def _():
            block_copy(j).start()

        @pl.when(j == n_full)  # past it nothing is fetched
        def _():
            last_copies(lambda copy: copy.start())

    # the 64-wide rotated keys come whole, in one copy (a DMA cannot take a
    # window of rows out of an array narrower than 128 lanes), ...
    keys = pltpu.make_async_copy(k_pe_hbm, k_pe_buf, sems.at[ring, 0])
    keys.start()
    for j in range(ring - 1):  # ... the latents ring - 1 blocks ahead
        start(j)
    q_lat, q_pe = q_lat_ref[...], q_pe_ref[...]

    def attend(j, m, l, acc, *, masked):
        # a block is widened after it arrives: key and value are these rows
        c = c_buf[j % ring].astype(q_lat.dtype)
        k_pe = k_pe_buf[j].astype(q_pe.dtype)
        if masked:
            # rows past the query's were never written (or never fetched)
            # and may hold anything: a weight of 0 would not keep a NaN out
            row = j * block + lax.broadcasted_iota(jnp.int32, (block, 1), 0)
            c = jnp.where(row <= position, c, jnp.zeros_like(c))
        s = (lax.dot_general(q_lat, c, _NT, preferred_element_type=F32)
             + lax.dot_general(q_pe, k_pe, _NT, preferred_element_type=F32)
             ) * scale
        if masked:
            col = j * block + lax.broadcasted_iota(jnp.int32, (1, block), 1)
            s = jnp.where(col <= position, s, -jnp.inf)
        # online softmax in float32; the MXU takes the weights in the
        # queries' dtype, as the XLA form's second einsum does
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        return (m_new, alpha * l + jnp.sum(p, axis=-1, keepdims=True),
                alpha * acc + jnp.dot(p.astype(c.dtype), c,
                                      preferred_element_type=F32))

    def step(j, carry):
        start(j + ring - 1)
        block_copy(j).wait()
        return attend(j, *carry, masked=False)

    heads = q_lat.shape[0]
    keys.wait()
    m, l, acc = lax.fori_loop(0, n_full, step, (
        jnp.full((heads, 1), -jnp.inf, F32), jnp.zeros((heads, 1), F32),
        jnp.zeros(out_ref.shape, F32)))

    @pl.when(last_subs == 0)  # a block ends on the query's row
    def _():
        out_ref[...] = (acc / l).astype(out_ref.dtype)

    @pl.when(last_subs > 0)
    def _():
        last_copies(lambda copy: copy.wait())
        _, l_last, acc_last = attend(n_full, m, l, acc, masked=True)
        out_ref[...] = (acc_last / l_last).astype(out_ref.dtype)


def _block_rows(max_len: int) -> int:
    """The largest multiple of 128 rows, up to `_MAX_BLOCK_ROWS`, that
    divides the cache; 0: none does."""
    return next((b for b in range(_MAX_BLOCK_ROWS, 0, -128)
                 if max_len % b == 0), 0)


@functools.partial(jax.jit, static_argnames=("scale", "block_rows",
                                             "interpret"))
def streamed_attention(q_lat, q_pe, c, k_pe, position, *, scale: float,
                       block_rows: int = None, interpret: bool = False):
    """`absorbed_attention` for ONE query (``q_lat`` [1, H, C], ``q_pe``
    [1, H, R]) at ``position`` against the WHOLE cache arrays ``c``
    [max_len, C] / ``k_pe`` [max_len, R] as the decode loop carries them
    (never a slice: that would be a copy in front of the call), as one Pallas
    TPU kernel: the latent rows written so far - ``0 .. position`` - cross
    the HBM once, in blocks of ``block_rows`` (default `_block_rows`) of
    which a ring is in flight, the last one `_SUB_ROWS` at a time (or the
    largest divisor of a smaller block) as far as the position reaches, and
    each block serves as key and as value while it is in VMEM: float32
    scores, an online softmax in float32, the weights in the queries' dtype
    into the MXU, a float32 accumulator.  Rows beyond the position are
    neither fetched nor computed on (the 64-wide ``k_pe`` comes whole: a
    ninth of the bytes).
    -> (the attended latents [1, H, C] in ``q_lat``'s dtype, the latent rows
    fetched).  ``interpret`` runs it on the CPU."""
    t, h, c_dim = q_lat.shape
    max_len, r = k_pe.shape
    block = block_rows or _block_rows(max_len)
    sub = math.gcd(block, _SUB_ROWS)
    if t != 1:
        raise ValueError(f"streamed_attention takes one query, not {t}")
    if not block or max_len % block:
        raise ValueError(f"streamed_attention: blocks of {block} rows do "
                         f"not divide a cache of {max_len}")
    buffer_bytes = (_RING * block * c_dim * c.dtype.itemsize
                    + max_len * max(r, 128) * k_pe.dtype.itemsize)
    out, rows = pl.pallas_call(
        functools.partial(_streamed_kernel, scale=scale, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # the query's position, in SMEM
            grid=(1,),
            in_specs=[pl.BlockSpec((h, c_dim), lambda i, *_: (0, 0)),
                      pl.BlockSpec((h, r), lambda i, *_: (0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((h, c_dim), lambda i, *_: (0, 0)),
                       pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=[pltpu.VMEM((_RING, block, c_dim), c.dtype),
                            pltpu.VMEM((max_len // block, block, r),
                                       k_pe.dtype),
                            pltpu.SemaphoreType.DMA((_RING + 1,
                                                     block // sub))]),
        out_shape=[jax.ShapeDtypeStruct((h, c_dim), q_lat.dtype),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=buffer_bytes + (16 << 20)),
        interpret=pltpu.InterpretParams() if interpret else False,
        # the device op's name: `lm.mla.attn` stays in its op_name, which is
        # how the benchmark's `mla_attn_ms_per_token` finds it
        name="latent_cache_attention",
    )(jnp.asarray(position, jnp.int32).reshape(1), q_lat[0], q_pe[0],
      # a block is one index of a leading axis (a bitcast: whole tiles); held
      # to the HBM: left to itself the compiler moves some layers' whole
      # caches into VMEM for the row's write in front of the call and copies
      # them back (31 / 53 MB a step in the served Kimi / Kanana programs)
      *(pltpu.with_memory_space_constraint(a, pltpu.HBM)
        for a in (c.reshape(-1, block, c_dim), k_pe.reshape(-1, block, r))))
    return out[None], rows[0]


def cache_attention(q_lat, q_pe, c, k_pe, position, *, scale: float,
                    visible: int = None):
    """The absorbed form for T queries at ``position`` onward against a
    layer's whole cache, by the route the call's shape asks for -> (the
    attended latents [T, H, C], the latent rows `streamed_attention`
    fetched: 0 on the XLA route).

    One query (a decode step) on a TPU goes through `streamed_attention`;
    everything else - more queries, a cache no block divides, another
    backend - is `absorbed_attention` over the cache's first ``visible``
    rows (static; None: all of them under the mask)."""
    t = q_lat.shape[0]
    if (t == 1 and _block_rows(c.shape[0]) and q_lat.shape[-1] % 128 == 0
            and jax.devices()[0].platform == "tpu"):
        return streamed_attention(q_lat, q_pe, c, k_pe, position,
                                  scale=scale)
    return absorbed_attention(
        q_lat, q_pe, c[:visible], k_pe[:visible],
        q_positions=position + jnp.arange(t), scale=scale), jnp.zeros(
            (), jnp.int32)
