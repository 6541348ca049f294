"""Flash attention for TPU: the fused-SDPA native kernels.

The reference reaches fused attention through torch's
F.scaled_dot_product_attention (cuDNN/FlashAttention,
/root/reference/distrifuser/modules/pp/attn.py:87,153) — SURVEY.md §2.10 maps
that native dependency to a Pallas kernel here.  Two kernels, one routing
table (ops/sdpa_routing.py):

* ``flash_sdpa`` — the in-repo kernel, **sequence-minor**.  XLA's TPU layout
  assignment writes every projection output ``[B, L, C]`` with the tokens on
  the lanes (layout ``{1,2,0}``, physically ``[B, C, L]``), and ``to_out``
  reads its input the same way.  The kernel takes Q, K, V and writes O as
  ``[B*H, D, L]`` — a bitcast of what is already in HBM — so no layout copy
  stands before or behind it (the upstream kernel's ``[B, H, L, D]`` costs
  three transposes in and one out per call, lane-padded from d=64/72 to
  128).  Grid ``(B*H, Lq/block_q)``, both parallel; one head's whole K and V
  stay in VMEM across its q blocks; the KV walk is a loop INSIDE the kernel
  over ``block_k`` chunks, unrolled so that the scheduler overlaps one
  chunk's softmax (VPU/EUP) with the next chunk's Q·Kᵀ (MXU) — a grid step
  per KV block, as the upstream kernel has, runs them one after the other.
  Online softmax with fp32 running max / sum / accumulator carried as loop
  values; the 1/sqrt(d) scale rides in the exponent (``exp2((s-m)·c)``:
  no pass over the score tile), normalisation happens once at the end.
* ``upstream_flash_sdpa`` — jax.experimental's kernel under the same
  signature, for the shapes the table leaves with it (very long sequences,
  the segment-masked padded route).

``flash_sdpa`` is a drop-in for ops.attention.sdpa, which runs the kernel
ops/sdpa_routing.py names for the call's shape, with the tiles fitted to
its lengths (``largest_dividing_tile``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG_INF = -1e30
# KV chunks per iteration of the in-kernel loop, as straight-line code: the
# overlap of one chunk's softmax with the next chunk's matmul exists only
# inside a basic block (1.57 ms unrolled vs 2.06 ms rolled a call at
# L=4096, d=72: one v5e, PR 25).  Up to this many chunks there is no loop.
_KV_UNROLL = 8
# what one kernel instance may ask of the 128 MiB of VMEM a v5e core has
_VMEM_CEILING = 100 << 20


def largest_dividing_tile(preferred: int, length: int):
    """Largest power-of-2 tile <= ``preferred`` that divides ``length``.

    Walks down from the power-of-2 floor of min(preferred, length) by
    halving; returns None below 128 (the TPU lane minimum) — callers treat
    that as "no usable tile".
    """
    tile = 1 << (min(preferred, length).bit_length() - 1)
    while tile >= 128:
        if length % tile == 0:
            return tile
        tile //= 2
    return None


def _flash_kernel(qT_ref, kT_ref, vT_ref, oT_ref, *, scale, block_k, kv_len):
    """One (head, q block): qT [D, Bq], the head's kT / vT [D, Lk] -> oT."""
    d, bq = qT_ref.shape[1], qT_ref.shape[2]
    n_full, tail = divmod(kv_len, block_k)
    q = qT_ref[0].T  # [Bq, D]: one small transpose per q block
    # logits stay the raw fp32 products; the softmax scale is folded into
    # the exponent's base-2 conversion, which exp pays anyway
    c = scale * math.log2(math.e)

    def chunk(start, carry, masked=False):
        m, l, acc = carry
        kt = kT_ref[0, :, pl.ds(start, block_k)]  # [D, Bk]
        vt = vT_ref[0, :, pl.ds(start, block_k)]
        s = jax.lax.dot_general(q, kt, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            # alignment padding: KV columns at or beyond the real length
            # leave the softmax (pad q rows are the caller's to slice off)
            col = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col < kv_len, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2((s - m_new) * c)  # [Bq, Bk]
        corr = jnp.exp2((m - m_new) * c)  # [Bq, 1]
        l = corr * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = corr * acc + jax.lax.dot_general(
            p.astype(vt.dtype), vt, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [Bq, D]
        return m_new, l, acc

    carry = (jnp.full((bq, 1), _NEG_INF, jnp.float32),
             jnp.zeros((bq, 1), jnp.float32),
             jnp.zeros((bq, d), jnp.float32))
    n_loop = n_full // _KV_UNROLL if n_full > _KV_UNROLL else 0
    if n_loop:
        def group(g, carry):
            base = pl.multiple_of(g * (_KV_UNROLL * block_k), block_k)
            for j in range(_KV_UNROLL):
                carry = chunk(base + j * block_k, carry)
            return carry

        carry = jax.lax.fori_loop(0, n_loop, group, carry)
    for j in range(n_loop * _KV_UNROLL, n_full):
        carry = chunk(j * block_k, carry)
    if tail:
        # the one chunk the mask crosses; whole chunks beyond it never run
        carry = chunk(n_full * block_k, carry, masked=True)
    _, l, acc = carry
    oT_ref[0] = (acc / l).T.astype(oT_ref.dtype)  # [D, Bq]


@functools.partial(jax.jit, static_argnames=("heads", "block_q", "block_k"))
def upstream_flash_sdpa(q, k, v, segment_ids=None, *, heads: int,
                        block_q: int = None, block_k: int = None):
    """jax.experimental's tuned TPU flash kernel under the sdpa signature.

    The upstream kernel (pallas/ops/tpu/flash_attention) carries
    per-generation block-size defaults; ``block_q``/``block_k`` override
    them (forward blocks only — inference has no backward pass).
    ``segment_ids`` is the upstream SegmentIds pair (cross-segment attention
    masked) — padded_flash_sdpa's pad mask.
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
    before routing here); Lq != Lk is fine (patch parallelism: local Q,
    gathered KV).  ``kv_len`` (static): treat only the first ``kv_len`` KV
    positions as real — the alignment-padding mask for unaligned sequences
    (SD3's 4250-token joint stream padded to 4352).  A head's K and V have
    to fit VMEM whole: a longer sequence is refused, not run some other way.
    """
    b, lq, c = q.shape
    lk = k.shape[1]
    d = c // heads
    kv_len = lk if kv_len is None else kv_len
    if lq % block_q:
        raise ValueError(f"flash_sdpa: block_q={block_q} must divide Lq={lq}")
    if lk % block_k or not 0 < kv_len <= lk:
        raise ValueError(f"flash_sdpa: block_k={block_k} must divide Lk={lk}, "
                         f"and kv_len={kv_len} lie in (0, Lk]")

    item = jnp.dtype(q.dtype).itemsize
    d_pad = -(-d // 16) * 16
    live = min(-(-kv_len // block_k), _KV_UNROLL)
    vmem = (2 * 2 * d_pad * (lk + block_q) * item  # q, k, v, o: two buffers
            + live * block_q * block_k * 10  # s, p in fp32, p in bf16
            + 3 * block_q * 128 * 4)  # m, l, acc
    if vmem > _VMEM_CEILING:
        raise ValueError(
            f"flash_sdpa: Lk={lk} at d={d}, tiles {block_q}x{block_k} needs "
            f"~{vmem >> 20} MiB of VMEM (one head's K and V stay resident); "
            f"over {_VMEM_CEILING >> 20} MiB. Take smaller tiles, or route "
            "this shape to 'upstream'.")

    def seq_minor(x, l):  # [B, L, C] -> [B*H, D, L]: a bitcast of {1,2,0}
        return x.reshape(b, l, heads, d).transpose(0, 2, 3, 1).reshape(
            b * heads, d, l)

    kv_spec = pl.BlockSpec((1, d, lk), lambda g, i: (g, 0, 0))
    q_spec = pl.BlockSpec((1, d, block_q), lambda g, i: (g, 0, i))
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=1.0 / d**0.5, block_k=block_k,
                          kv_len=kv_len),
        grid=(b * heads, lq // block_q),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * heads, d, lq), q.dtype),
        # every (head, q block) is independent: the KV walk is inside
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=vmem + (8 << 20),
        ),
        interpret=interpret,
        # the device op's name: the benchmark's attention metrics match
        # `^flash` / `attention`, and the trace counts its calls
        name="flash_attention_seq_minor",
    )(seq_minor(q, lq), seq_minor(k, lk), seq_minor(v, lk))
    return out.reshape(b, heads, d, lq).transpose(0, 3, 1, 2).reshape(b, lq, c)


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
                      interpret: bool = False, impl: str = "upstream"):
    """Flash attention for UNALIGNED sequence lengths via pad-and-mask.

    Long sequences whose length is not a lane multiple (SD3's 4096+154
    joint stream) would otherwise run XLA's chunked softmax (~11% MFU in
    the 2026-07-31 trace) — the padded kernel keeps the MXU on aligned
    tiles while a mask keeps the numerics exact: pad KV columns get -inf
    logits (zero softmax weight), pad query rows compute garbage and are
    sliced off.

    ``impl``: "upstream" (segment-ids mask, ``padding_segment_ids``), the
    default, or "inrepo" (static kv_len mask).  The model-level A/B at
    SD3-medium 1024², 2026-07: upstream 8.32 s vs inrepo 13.54 s vs chunked
    XLA 20.17 s, the two kernels agreeing to 5e-4 on chip — against the
    in-repo kernel of that date; the sequence-minor one that replaced it in
    PR 25 has not been A/B'd on this route.  The named kernel runs or the
    call raises; the in-repo kernel is reachable only as an explicit route,
    never as a fallback.  ``interpret`` exists for the in-repo kernel only.
    """
    if impl not in ("upstream", "inrepo"):
        # loud: a typo here would silently cost SD3 its 39% (8.3 vs 13.5 s)
        raise ValueError(
            f"padded_flash_sdpa: impl must be 'upstream' or 'inrepo', "
            f"got {impl!r}")
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
            block_q=largest_dividing_tile(256, lq_pad),
            block_k=largest_dividing_tile(1024, lk_pad),
        )
        return out[:, :lq]

    # padded lengths are 128-multiples, so the shared helper never returns
    # None here (the 128 lane minimum always divides)
    out = flash_sdpa(
        qp, kp, vp, heads=heads,
        block_q=largest_dividing_tile(256, lq_pad),
        block_k=largest_dividing_tile(256, lk_pad),
        interpret=interpret, kv_len=None if lk_pad == lk else lk,
    )
    return out[:, :lq]
