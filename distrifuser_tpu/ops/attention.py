"""Attention: dense core + displaced-patch self-attention + cached cross-attention.

TPU-native re-design of the reference's PP attention modules
(/root/reference/distrifuser/modules/pp/attn.py):

* K and V projections are fused into one ``to_kv`` matmul (attn.py:23-39) —
  one bigger MXU op instead of two.
* `patch_self_attention` (attn.py:107-195): Q from the local row-patch only;
  KV over the *full* sequence, assembled in sync phase by a fresh all-gather
  (warmup, attn.py:132-134) and in stale phase from the carried gathered KV
  with this device's slot overwritten by its fresh KV (attn.py:135-140).
* `cross_attention` (attn.py:42-104): text KV is constant across denoising
  steps, so it is computed once per generation (`precompute_text_kv` at the
  pipeline level — the reference caches at counter==0) and fed in; no
  communication, sequence dim of Q is sharded for free.

The attention core computes softmax in fp32 and feeds the MXU with the model
dtype.  A Pallas flash-attention kernel can swap in under the same signature
for long sequences (ops/flash_attention.py).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.collectives import all_gather
from ..parallel.context import PatchContext
from . import flash_attention, sdpa_routing
from .linear import linear


# Above this many fp32 logit elements (B*H*Lq*Lk), the unfused softmax path
# chunks queries so the full score matrix never materializes — the path for
# shapes no flash route covers (CPU, odd shapes, env-disabled).
# 2^28 elements = 1 GiB of fp32 logits.
_CHUNK_LOGITS_ELEMS = 1 << 28


def _sdpa_xla(q, k, v, scale):
    """[B, Lq, H, D] x [B, Lk, H, D] -> [B, Lq, H, D], fp32 softmax.

    The QK product accumulates straight into fp32 (preferred_element_type)
    rather than rounding logits to bf16 first — the softmax upcast needed
    fp32 anyway, so this costs nothing and matches the flash kernels'
    in-kernel fp32 logits."""
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)


@jax.named_scope("attn")
def sdpa(q, k, v, *, heads: int):
    """Scaled dot-product attention over [B, L, C] tensors with H heads.

    The analog of F.scaled_dot_product_attention (attn.py:87,153): the
    kernel `sdpa_routing.route` names for this shape — a Pallas flash kernel
    (ops/flash_attention.py), its tiles fitted to the call here — or XLA
    einsum+softmax, with query chunking once the score matrix would exceed
    ~1 GiB (on the CPU, the VAE's 65k-token single-head mid attention at
    2048x2048, where materializing L^2 logits cannot fit).

    The routed kernel runs or the call raises: a kernel that fails to trace
    or compile is never replaced by another implementation behind the
    caller's back (SD3's padded route is 8.3 s vs 20.2 s on the XLA path it
    used to fall to — a silent fall-through is a 2.4x slower program that
    still "works").
    """
    b, lq, c = q.shape
    lk = k.shape[1]
    platform = jax.devices()[0].platform
    route = sdpa_routing.route(lq, lk, c, heads, platform)
    fit = flash_attention.largest_dividing_tile
    if route.impl == "padded":
        return flash_attention.padded_flash_sdpa(q, k, v, heads=heads,
                                                 impl=route.kernel)
    if route.impl == "upstream":
        if platform == "cpu":
            raise ValueError(
                "sdpa route 'upstream' (jax.experimental's Mosaic flash "
                "kernel) needs a TPU; on the CPU platform the in-repo "
                "kernel runs in interpret mode (DISTRIFUSER_TPU_FLASH=1)"
            )
        # a row's tiles hold for its whole range but may not divide THIS
        # call's lengths (the kernel would assert at trace).  A
        # non-dividing tile cannot simply be dropped: the kernel fills
        # a lone None with its hardcoded 512/1024 defaults, which may
        # themselves not divide (e.g. Lk=57600 % 1024 != 0) — so fit
        # each tile down to the largest power-of-2 divisor, and if
        # either cannot be fitted pass NO tiles (full upstream
        # per-generation defaults) rather than a mixed pair.
        ubq, ubk = route.block_q, route.block_k
        if ubq or ubk:
            ubq, ubk = fit(ubq or 512, lq), fit(ubk or 1024, lk)
            if ubq is None or ubk is None:
                ubq = ubk = None
        return flash_attention.upstream_flash_sdpa(
            q, k, v, heads=heads, block_q=ubq, block_k=ubk)
    if route.impl == "inrepo":
        # same fitting as above, and for the patch path's local Lq.  Mosaic
        # kernels only compile for TPU; on the CPU platform (tests) the
        # kernel runs in interpret mode.  Nothing on a "tpu" platform
        # reaches interpret=True.
        return flash_attention.flash_sdpa(
            q, k, v, heads=heads,
            block_q=fit(route.block_q or flash_attention.DEFAULT_BLOCK_Q, lq),
            block_k=fit(route.block_k or flash_attention.DEFAULT_BLOCK_K, lk),
            interpret=platform == "cpu",
        )
    if route.impl != "xla":
        raise ValueError(f"sdpa: no kernel for route {route!r}")
    d = c // heads
    scale = 1.0 / d**0.5
    q = q.reshape(b, lq, heads, d)
    k = k.reshape(b, lk, heads, d)
    v = v.reshape(b, lk, heads, d)
    if b * heads * lq * lk > _CHUNK_LOGITS_ELEMS and lq > 1:
        n_chunks = 1
        while (
            b * heads * (lq // n_chunks) * lk > _CHUNK_LOGITS_ELEMS
            and n_chunks < lq
        ):
            n_chunks *= 2
        # pad queries to uniform chunks (odd Lq must still chunk — that is
        # exactly where the OOM protection matters); padded rows attend to
        # real keys, produce garbage, and are sliced off
        lq_pad = -(-lq // n_chunks) * n_chunks
        qp = jnp.pad(q, ((0, 0), (0, lq_pad - lq), (0, 0), (0, 0)))
        qc = qp.reshape(b, n_chunks, lq_pad // n_chunks, heads, d)
        if n_chunks <= 16:
            # static unroll: lax.map is a scan whose carried output
            # re-writes the whole buffer with a dynamic-update-slice every
            # iteration — 16.6% of SD3's step time in the r5 trace (the
            # 4250-token joint sequence chunks 4-way here).  Unrolled
            # chunks concatenate instead and XLA schedules them freely.
            out = jnp.concatenate(
                [_sdpa_xla(qc[:, i], k, v, scale) for i in range(n_chunks)],
                axis=1,
            )  # [B, lq_pad, H, D]
            out = out[:, :lq]
        else:
            # very deep chunking (65k-token single-head VAE attention):
            # keep the rolled loop to bound compile size
            out = jax.lax.map(
                lambda qi: _sdpa_xla(qi, k, v, scale), jnp.moveaxis(qc, 1, 0)
            )  # [n_chunks, B, lq_pad/n, H, D]
            out = jnp.moveaxis(out, 0, 1).reshape(b, lq_pad, heads, d)[:, :lq]
    else:
        out = _sdpa_xla(q, k, v, scale)
    return out.reshape(b, lq, c)


def split_kv(kv):
    """Split a fused [..., 2C] KV into (K, V) (attn.py:78,142)."""
    return jnp.split(kv, 2, axis=-1)


def attention(p, x, *, heads: int, encoder_hidden_states=None):
    """Dense (single-device) attention block: q/kv projections + sdpa + out proj.

    Residual connections live in the transformer block, matching diffusers'
    BasicTransformerBlock (the reference's Attention has
    residual_connection=False there).
    """
    enc = x if encoder_hidden_states is None else encoder_hidden_states
    q = linear(p["to_q"], x)
    k, v = split_kv(linear(p["to_kv"], enc))
    return linear(p["to_out"], sdpa(q, k, v, heads=heads))


def patch_self_attention(p, x, ctx: PatchContext, name: str, *, heads: int):
    """Sequence-parallel self-attention with one-step-stale remote KV.

    ``x``: local row-patch tokens [B, L_local, C].  Carry state per layer:
    the gathered per-peer KV [n, B, L_local, 2C].
    """
    q = linear(p["to_q"], x)
    kv = linear(p["to_kv"], x)  # [B, L, 2C] fresh local
    if ctx.n == 1:
        full_kv = kv
    elif ctx.is_sync:
        with jax.named_scope("stale_kv"):
            gathered = all_gather(kv, ctx.axis)  # [n, B, L, 2C]
            ctx.emit(name, gathered, kind="attn")
            full_kv = _flatten_seq(gathered)
    else:
        with jax.named_scope("stale_kv"):
            gathered = ctx.stale(name)
            # fresh local slot + stale peer slots (attn.py:135-138)
            gathered = lax.dynamic_update_index_in_dim(
                gathered, kv, ctx.split_idx(), 0)
            full_kv = _flatten_seq(gathered)
            if ctx.refresh:
                ctx.emit_refresh_gather(name, kv, kind="attn")
    k, v = split_kv(full_kv)
    return linear(p["to_out"], sdpa(q, k, v, heads=heads))


def _flatten_seq(gathered):
    """[n, B, L, C] -> [B, n*L, C] preserving patch order."""
    n, b, l, c = gathered.shape
    return jnp.moveaxis(gathered, 0, 1).reshape(b, n * l, c)


def cross_attention(
    p,
    x,
    *,
    heads: int,
    encoder_hidden_states=None,
    cached_kv: Optional[jnp.ndarray] = None,
):
    """Cross-attention over text tokens; KV cached across steps (attn.py:42-104).

    Works identically dense and patch-parallel: Q rows are local, text KV is
    replicated, so no communication is ever needed.
    """
    q = linear(p["to_q"], x)
    if cached_kv is None:
        assert encoder_hidden_states is not None
        cached_kv = linear(p["to_kv"], encoder_hidden_states)
    k, v = split_kv(cached_kv)
    return linear(p["to_out"], sdpa(q, k, v, heads=heads))


def causal_gqa_sdpa(q, k, v, *, q_positions):
    """Causal attention of ONE sequence with fewer KV heads than query heads
    (grouped-query): each KV head serves ``Hq // Hkv`` query heads.

    ``q`` [T, Hq, D]; ``k`` / ``v`` [S, Hkv, D] - a whole prompt (S = T) or
    a cache of which only the first rows are filled; ``q_positions`` [T]:
    query ``i`` sees keys ``0 .. q_positions[i]``, so rows of a cache not
    written yet are never read into the result.  Softmax in float32, the
    MXU fed the model dtype, like `_sdpa_xla`.  One XLA route, beside the
    table-routed `sdpa`: a language model's prefill here is a thousand
    tokens in one layer of eleven, and its decode step one query row
    against a cache - neither is where a kernel would be felt.
    """
    t, hq, d = q.shape
    s, hkv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"{hq} query heads over {hkv} KV heads")
    q = q.reshape(t, hkv, hq // hkv, d)
    logits = jnp.einsum("tkgd,skd->kgts", q, k,
                        preferred_element_type=jnp.float32) / d**0.5
    visible = jnp.arange(s)[None, :] <= q_positions[:, None]
    logits = jnp.where(visible[None, None], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("kgts,skd->tkgd", w, v).reshape(t, hq, d)


# Queries a block of `gqa_sdpa_by_query_block`: `ops/mla.py QUERY_BLOCK`'s
# timings at 32 query heads against 8192 keys hold here (a block's
# [32, rows, S] float32 logits go through HBM at every pass of the softmax)
GQA_QUERY_BLOCK = 32


def gqa_sdpa_by_query_block(q, k, v, *, q_positions,
                            block: int = GQA_QUERY_BLOCK):
    """`causal_gqa_sdpa` without the [Hq, T, S] array: the queries go
    ``block`` at a time (the largest divisor of T that ``block`` holds), so
    that at most [Hq, block, S] float32 logits are alive - a prompt of
    thousands of rows, a suffix entering a cache, or the few rows of a
    decode pass (T <= ``block``: one block, no loop).

    ``q`` [T, Hq, D]; ``k`` / ``v`` [Hkv, S, D], KV-HEAD MAJOR - the layout
    of a cache whose rows are a position's [D] numbers, so that a row of few
    KV heads pads no tile; ``q_positions`` [T]: query ``i`` sees keys
    ``0 .. q_positions[i]`` - its own position for causal attention, the
    last position of its block for attention by blocks (both directions
    inside a block, everything before it) - so rows of a cache not written
    yet are never read into the result.  ``k`` / ``v`` may be held a
    precision below ``q``: they are read in ``q``'s dtype.  Softmax in
    float32, the MXU fed the model dtype.  -> [T, Hq, D]."""
    t, hq, d = q.shape
    hkv, s, _ = k.shape
    if hq % hkv:
        raise ValueError(f"{hq} query heads over {hkv} KV heads")
    k, v = k.astype(q.dtype), v.astype(q.dtype)
    keys = jnp.arange(s)

    def one(args):
        qb, positions = args
        qb = qb.reshape(qb.shape[0], hkv, hq // hkv, d)
        logits = jnp.einsum("tkgd,ksd->kgts", qb, k,
                            preferred_element_type=jnp.float32) / d**0.5
        visible = keys[None, :] <= positions[:, None]
        w = jax.nn.softmax(jnp.where(visible[None, None], logits, -jnp.inf),
                           axis=-1).astype(v.dtype)
        return jnp.einsum("kgts,ksd->tkgd", w, v).reshape(-1, hq, d)

    block = math.gcd(t, block)
    if block == t:
        return one((q, q_positions))
    out = lax.map(one, (q.reshape(t // block, block, hq, d),
                        q_positions.reshape(t // block, block)))
    return out.reshape(t, hq, d)
