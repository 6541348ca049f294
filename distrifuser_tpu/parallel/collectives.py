"""Named-axis collective helpers over the ICI mesh.

TPU-native replacements for the reference's NCCL collective surface
(SURVEY.md §2.2; /root/reference/distrifuser/utils.py:170-179 and the module
files): sync/async `dist.all_gather` -> `lax.all_gather` over a named mesh
axis, `dist.all_reduce(SUM)` -> `lax.psum`, and — new here, because ICI makes
neighbor exchange first-class — the conv halo exchange uses `lax.ppermute`
with a *non-wrapping* permutation instead of gathering every peer's boundary
to every device (the reference allocates an n-peer buffer per conv,
pp/conv2d.py:58-67, but only ever reads the two neighbors' rows,
pp/conv2d.py:72-88).

All helpers must be called inside `shard_map` with the axis bound.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.config import SP_AXIS


def all_gather(x, axis: str = SP_AXIS):
    """Gather per-device blocks along `axis` into a new leading dim [n, ...]."""
    return lax.all_gather(x, axis)


def all_gather_seq(x, axis: str = SP_AXIS):
    """Gather sequence-sharded [B, L_local, C] into full [B, n*L_local, C]."""
    return lax.all_gather(x, axis, axis=1, tiled=True)


def psum_mean(x, axis: str = SP_AXIS):
    """Average over the axis (reference all_reduce(SUM)/n, pp/groupnorm.py:79-80).
    `lax.pmean` reads the peer count off the bound mesh axis itself."""
    return lax.pmean(x, axis)


def psum(x, axis: str = SP_AXIS):
    """Sum over the axis (reference all_reduce(SUM), tp/attention.py:159).
    The tensor-parallel partial-sum reduce: every TP matmul/conv shard
    contributes its local partial and reads back the full activation —
    per-layer, synchronous, the defining cost of the TP layout (the
    reason displaced patches win at small world sizes, SURVEY.md §2.6).
    Routed through here so distrilint's collective-containment checker
    keeps every raw `lax` collective inside the accounted helper
    surface."""
    return lax.psum(x, axis)


def ring_perm(n: int):
    """Wrapping next-neighbor permutation along a ring axis: device i
    sends to i+1 mod n.  Single source of truth for the ring-attention
    chunk rotation (ops/ring_attention.py) and its software-pipelined
    decomposition: hop h delivers device ``r-h mod n``'s chunk to rank
    ``r``, so n-1 hops cover every peer exactly once."""
    return [(i, (i + 1) % n) for i in range(n)]


def ring_shift(x, n: int, axis: str = SP_AXIS):
    """One ring hop: every device hands ``x`` to its next neighbor and
    receives the previous neighbor's.  The unit the pipelined ring
    attention overlaps — each hop's ppermute is issued BEFORE the compute
    that consumes the previous hop's arrival, so its wire time hides
    behind that chunk's matmuls (FastUSP-style kernel-level
    compute/communication overlap, arXiv 2602.10940)."""
    return lax.ppermute(x, axis, perm=ring_perm(n))


def neighbor_perms(n: int):
    """Non-wrapping neighbor permutations along the patch axis:
    ``(down, up)`` = (send to next device, send to previous device).  Edge
    devices have no source and receive zeros from ppermute — the image-border
    zero padding of a global conv.  Single source of truth for the halo edge
    convention (used by halo_exchange and the batched flush in
    parallel/context.py)."""
    down = [(i, i + 1) for i in range(n - 1)]
    up = [(i + 1, i) for i in range(n - 1)]
    return down, up


@jax.named_scope("halo")
def exchange_boundary_rows(bottom, top, n: int, axis: str = SP_AXIS):
    """ppermute already-extracted boundary tensors to spatial neighbors:
    ``(from_prev, from_next)`` = (previous device's ``bottom``, next
    device's ``top``).  Edge devices receive zeros.  Factored out of
    ``halo_exchange`` so the compressed refresh path (parallel/compress.py
    payload + fp32 scale pairs) rides the exact same edge convention."""
    down, up = neighbor_perms(n)
    from_prev = lax.ppermute(bottom, axis, perm=down)
    from_next = lax.ppermute(top, axis, perm=up)
    return from_prev, from_next


def halo_exchange(x, halo: int, n: int, axis: str = SP_AXIS):
    """Exchange boundary rows with spatial neighbors along the patch axis.

    ``x`` is the local row-patch [B, h, W, C] (NHWC).  Returns
    ``(from_prev, from_next)``: the previous device's *bottom* `halo` rows and
    the next device's *top* `halo` rows, each [B, halo, W, C].  Edge devices
    receive zeros, which coincides exactly with the zero row-padding a global
    conv would apply at the image border — the reference reproduces this with
    explicit F.pad at ranks 0 / n-1 (pp/conv2d.py:73-78).
    """
    if halo == 0 or n == 1:
        zeros = jnp.zeros(x.shape[:1] + (halo,) + x.shape[2:], x.dtype)
        return zeros, zeros
    return exchange_boundary_rows(x[:, -halo:], x[:, :halo], n, axis)


@jax.named_scope("out_gather")
def gather_rows(patch, axis: str = SP_AXIS):
    """Reassemble row-sharded [B, h, W, C] patches into the full [B, H, W, C].

    The per-step output gather of the reference models
    (distri_sdxl_unet_pp.py:162-169: world all_gather + torch.cat on dim 2).
    """
    return lax.all_gather(patch, axis, axis=1, tiled=True)


@jax.named_scope("out_gather")
def gather_cols(patch, axis: str = SP_AXIS):
    """Column-split variant used by naive patch parallelism (split_scheme='col',
    naive_patch_sdxl.py:119-122)."""
    return lax.all_gather(patch, axis, axis=2, tiled=True)
