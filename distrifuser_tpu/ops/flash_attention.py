"""Pallas flash attention for TPU: the fused-SDPA native kernel.

The reference reaches fused attention through torch's
F.scaled_dot_product_attention (cuDNN/FlashAttention,
/root/reference/distrifuser/modules/pp/attn.py:87,153) — SURVEY.md §2.10 maps
that native dependency to a Pallas kernel here.  Online-softmax tiling:

* grid (batch*heads, Lq/Bq, Lk/Bk); the innermost grid dim walks KV blocks
  sequentially while Pallas double-buffers their HBM->VMEM streams;
* fp32 running max / normalizer / accumulator in VMEM scratch, carried
  across KV steps, finalized on the last one;
* logits never materialize beyond one (Bq, Bk) tile — O(L) memory instead of
  the O(L^2) probability matrix, which is what makes >=2048px patch
  attention (16k-65k tokens) fit.

`flash_sdpa` is a drop-in for ops.attention.sdpa; attention.py routes long,
block-aligned sequences on TPU to a flash kernel and everything else (small
cross-attention over 77 text tokens) to the XLA softmax path.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale,
                  kv_len=None):
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0]  # [Bq, D]
    k = k_ref[0]  # [Bk, D]
    v = v_ref[0]  # [Bk, D]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [Bq, Bk] fp32
    if kv_len is not None:
        # alignment-padding support: KV columns at or beyond the real
        # length are masked out of the softmax, so padding K/V up to a
        # block multiple is numerically exact (pad q rows are the caller's
        # to slice off).  One iota+compare+select per tile — negligible
        # against the dot.
        bk = s.shape[1]
        col = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < kv_len, s, _NEG_INF)

    m_prev = m_scr[:, :1]  # [Bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)  # [Bq, Bk]
    corr = jnp.exp(m_prev - m_new)  # [Bq, 1]

    l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[:] = acc_scr[:] * corr + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == nk - 1)
    def _():
        o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "block_q", "block_k"))
def upstream_flash_sdpa(q, k, v, segment_ids=None, *, heads: int,
                        block_q: int = None, block_k: int = None):
    """jax.experimental's tuned TPU flash kernel under the sdpa signature.

    The upstream kernel (pallas/ops/tpu/flash_attention) carries
    per-generation block-size defaults; ``block_q``/``block_k`` override
    them (forward blocks only — inference has no backward pass), letting
    the chip campaign's tune phase sweep this kernel the same way it
    sweeps the in-repo one.  ``segment_ids`` is the upstream SegmentIds
    pair (cross-segment attention masked) — padded_flash_sdpa's pad mask.
    """
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
        flash_attention,
    )

    b, lq, c = q.shape
    lk = k.shape[1]
    d = c // heads

    def to_heads(x, l):
        return x.reshape(b, l, heads, d).transpose(0, 2, 1, 3)

    block_sizes = None
    if block_q is not None or block_k is not None:
        bq = min(block_q or 512, lq)
        bk = min(block_k or 1024, lk)
        block_sizes = BlockSizes(block_q=bq, block_k_major=bk, block_k=bk,
                                 block_b=1)
    o = flash_attention(
        to_heads(q, lq), to_heads(k, lk), to_heads(v, lk),
        segment_ids=segment_ids,
        causal=False, sm_scale=1.0 / d**0.5, block_sizes=block_sizes,
    )
    return o.transpose(0, 2, 1, 3).reshape(b, lq, c)


@functools.partial(jax.jit, static_argnames=("heads", "block_q", "block_k",
                                             "interpret", "kv_len"))
def flash_sdpa(q, k, v, *, heads: int, block_q: int = DEFAULT_BLOCK_Q,
               block_k: int = DEFAULT_BLOCK_K, interpret: bool = False,
               kv_len: int = None):
    """Drop-in for ops.attention.sdpa: [B, L, C] inputs, H heads.

    Requires Lq % block_q == 0 and Lk % block_k == 0 (attention.py checks
    before routing here).  ``kv_len`` (static): treat only the first
    ``kv_len`` KV positions as real — the alignment-padding mask for
    unaligned sequences (SD3's 4250-token joint stream padded to 4352).
    """
    b, lq, c = q.shape
    lk = k.shape[1]
    d = c // heads
    scale = 1.0 / d**0.5

    def to_heads(x, l):
        return (
            x.reshape(b, l, heads, d).transpose(0, 2, 1, 3).reshape(b * heads, l, d)
        )

    qh, kh, vh = to_heads(q, lq), to_heads(k, lk), to_heads(v, lk)

    grid = (b * heads, lq // block_q, lk // block_k)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, kv_len=kv_len),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * heads, lq, d), q.dtype),
        scratch_shapes=[
            # (block_q, 128): fp32 lane width — same layout the upstream TPU
            # kernel uses for its m/l scratch (MIN_BLOCK_SIZE=128)
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running normalizer
            pltpu.VMEM((block_q, d), jnp.float32),  # output accumulator
        ],
        # batch*heads and q-blocks are independent; only the KV walk carries
        # the online-softmax state.  Without this, Mosaic treats every grid
        # dim as sequential ("arbitrary"), which blocks its cross-iteration
        # pipelining — the prime suspect in the round-2 2x slowdown vs XLA.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qh, kh, vh)

    return out.reshape(b, heads, lq, d).transpose(0, 2, 1, 3).reshape(b, lq, c)


def padding_segment_ids(b: int, lq: int, lq_pad: int, lk: int, lk_pad: int):
    """Upstream-kernel ``SegmentIds`` encoding the alignment-pad mask.

    Real tokens are segment 0, pad tokens segment 1; the upstream kernel
    masks cross-segment attention, so a real query row attends exactly the
    first ``lk`` KV positions — the same statement as the in-repo kernel's
    static ``kv_len`` mask (pad query rows attend pad KV, compute garbage,
    and are the caller's to slice off).  Split out of ``padded_flash_sdpa``
    so the mask semantics are testable on CI without a Mosaic compile
    (tests/test_flash_attention.py).
    """
    from jax.experimental.pallas.ops.tpu.flash_attention import SegmentIds

    seg_q = (jnp.arange(lq_pad) >= lq).astype(jnp.int32)
    seg_kv = (jnp.arange(lk_pad) >= lk).astype(jnp.int32)
    return SegmentIds(
        q=jnp.broadcast_to(seg_q, (b, lq_pad)),
        kv=jnp.broadcast_to(seg_kv, (b, lk_pad)),
    )


def padded_flash_sdpa(q, k, v, *, heads: int, align: int = 128,
                      interpret: bool = False, impl: str = None):
    """Flash attention for UNALIGNED sequence lengths via pad-and-mask.

    Long sequences whose length is not a lane multiple (SD3's 4096+154
    joint stream) would otherwise run XLA's chunked softmax (~11% MFU in
    the 2026-07-31 trace) — the padded kernel keeps the MXU on aligned
    tiles while a mask keeps the numerics exact: pad KV columns get -inf
    logits (zero softmax weight), pad query rows compute garbage and are
    sliced off.

    ``impl``: "upstream" (segment-ids mask, ``padding_segment_ids``) or
    "inrepo" (static kv_len mask).  Resolution: the ``impl`` argument,
    else DISTRIFUSER_TPU_PADDED_IMPL, else — honoring the operator's
    kernel-wide DISTRIFUSER_TPU_FLASH_IMPL=inrepo pin — "inrepo", else
    "upstream" (the model-level A/B at SD3-medium 1024²: upstream 8.32 s
    vs inrepo 13.54 s vs chunked XLA 20.17 s; the two kernels agree to
    5e-4 on chip).  The resolved kernel runs or the call raises; the
    in-repo kernel is reachable only as an explicit route, never as a
    fallback.  ``interpret`` exists for the in-repo kernel only.
    """
    # lazy import avoids a cycle: attention.py only imports this module
    # inside function bodies
    from .attention import _largest_dividing_tile

    impl = impl or os.environ.get("DISTRIFUSER_TPU_PADDED_IMPL")
    if impl is None and os.environ.get("DISTRIFUSER_TPU_FLASH_IMPL") == "inrepo":
        impl = "inrepo"
    impl = impl or "upstream"
    if impl not in ("upstream", "inrepo"):
        # loud: a typo here would silently cost SD3 its 39% (8.3 vs 13.5 s)
        raise ValueError(
            f"DISTRIFUSER_TPU_PADDED_IMPL/impl must be 'upstream' or "
            f"'inrepo', got {impl!r}")
    b, lq, c = q.shape
    lk = k.shape[1]
    lq_pad = -(-lq // align) * align
    lk_pad = -(-lk // align) * align
    qp = jnp.pad(q, ((0, 0), (0, lq_pad - lq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, lk_pad - lk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, lk_pad - lk), (0, 0)))

    if impl == "upstream":
        if interpret:
            raise ValueError(
                "padded_flash_sdpa: interpret mode exists for the in-repo "
                "kernel only; pass impl='inrepo'"
            )
        seg = padding_segment_ids(b, lq, lq_pad, lk, lk_pad)
        out = upstream_flash_sdpa(
            qp, kp, vp, seg, heads=heads,
            block_q=_largest_dividing_tile(256, lq_pad),
            block_k=_largest_dividing_tile(1024, lk_pad),
        )
        return out[:, :lq]

    # padded lengths are 128-multiples, so the shared helper never returns
    # None here (the 128 lane minimum always divides)
    out = flash_sdpa(
        qp, kp, vp, heads=heads,
        block_q=_largest_dividing_tile(256, lq_pad),
        block_k=_largest_dividing_tile(256, lk_pad),
        interpret=interpret, kv_len=None if lk_pad == lk else lk,
    )
    return out[:, :lq]
