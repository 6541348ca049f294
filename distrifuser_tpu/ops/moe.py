"""Sparse experts, one chip's share: a router over ALL experts, and the part
of the result that the experts held here give.

Expert parallelism splits an expert layer's experts over the chips that share
the layer; every chip routes every token over the full published router
width, and computes, for the tokens routed to ITS experts, those experts'
weighted outputs.  Summed over the chips (the exchange a deployment runs
between them) that is the layer's routed output.  This module is the
per-chip part and nothing else: on one chip the layer runs without its
exchange, and what the absent experts would have added is left out.

`local_expert_sum` has two forms and takes one by the rows of the call.

A prompt (thousands of rows) is a grouped matmul: the (token, expert)
assignments that fall on held experts are sorted by expert, each expert's
rows go through its own pair of matrices (`lax.ragged_dot`, which the TPU
compiler lowers to a grouped Mosaic kernel that visits only the non-empty
groups' weights), and the rows are summed back per token under the router's
weights.  Tokens are routed unevenly and none is dropped: there is no
capacity.

A decode step (one token, `k` assignment rows, two or three of them on held
experts) is bandwidth work on those experts' weights and nothing else, and
the grouped kernel is built for the other case: below `MIN_GROUPED_ROWS` rows
the compiler even lowers it to one dense matmul over EVERY held expert.  On a
TPU such a call goes to `gather_expert_sum`, one Pallas kernel a layer: the
ids, the router's weights and which slots are held are scalars; each
chosen-and-held expert's `w1[e]`, `w2[e]` are fetched from HBM by id, once,
in f-tiles that a ring of buffers keeps in flight; both mat-vecs and the
activation between them run on a tile while the next ones arrive - no sort,
no group sizes, no padding, no 64-row intermediate.  Off the TPU (the CPU
tests) the grouped matmul serves small calls too, and the kernel runs only
interpreted, from tests.

What an expert computes between its two matrices is a static argument of
both forms, ``activation``: ``"relu2"`` - ``relu(x W1)^2 W2``, ``W1``
[d, f] - or ``"silu"``, the gated form of three matrices -
``(silu(x G) * (x U)) W2`` with gate and up held as ONE fused ``W1`` =
``[G | U]`` [d, 2 f]: columns ``0 .. f - 1`` the gate's, ``f .. 2 f - 1`` the
up-projection's, column ``j`` of one meeting column ``j`` of the other.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
# the experts' activations: how many f-wide column groups W1 holds
_W1_GROUPS = {"relu2": 1, "silu": 2}
# Below 64 rows the TPU compiler lowers `ragged_dot` to one dense masked
# matmul over EVERY group - all the held experts' weights read for one
# decoded token (seen in the program compiled for a v5e, PR 27) - and from 64
# rows on to the grouped kernel that visits only the groups that have rows.
# Calls below it take the gather kernel.
MIN_GROUPED_ROWS = 64


def route(u, router_kernel, score_bias=None, *, top_k: int,
          scale: float = 1.0, scoring: str = "sigmoid",
          denominator_eps: float = 0.0):
    """A router over ALL experts, in float32 throughout.

    ``u`` [T, D]; ``router_kernel`` [D, E].  ``scoring`` "sigmoid"
    (DeepSeek-V3 style, ``n_group`` 1: no group limit): s = sigmoid(u W), a
    selection-only ``score_bias`` [E] added for the choice alone; "softmax"
    (Qwen3-MoE style, ``norm_topk_prob``): s = softmax(u W) over all E, no
    bias.  The ``top_k`` largest of s (+ bias) are chosen; their weights are
    ``scale * s_i / (sum of the chosen s + denominator_eps)`` - the constant
    static, and with the default 0.0 not in the program at all.
    Returns (expert ids [T, top_k] int32, weights [T, top_k] float32)."""
    logits = jnp.dot(u.astype(F32), router_kernel.astype(F32),
                     precision=lax.Precision.HIGHEST)
    if scoring == "sigmoid":
        s = jax.nn.sigmoid(logits)
    elif scoring == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"a router scores by sigmoid or softmax, not "
                         f"{scoring!r}")
    select = s if score_bias is None else s + score_bias.astype(F32)
    _, idx = lax.top_k(select, top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    total = jnp.sum(chosen, axis=-1, keepdims=True)
    if denominator_eps:
        total = total + denominator_eps
    return idx.astype(jnp.int32), scale * chosen / total


def balanced_bias(scores, *, top_k: int, rounds: int, step: float = 0.02):
    """The selection bias as load balancing leaves it, for one layer's
    ``scores`` [T, E] (float32 sigmoid scores of a calibration sequence): the
    auxiliary-loss-free rule - after a batch, b_e moves down where expert e
    was chosen more than its share and up where less - run ``rounds`` times
    with a step that decays to nothing.  -> [E] float32."""
    t, e = scores.shape
    share = t * top_k / e

    def nudge(i, bias):
        select = scores + bias
        least = lax.top_k(select, top_k)[0][:, -1:]
        load = jnp.sum(select >= least, axis=0).astype(F32)
        return bias - step * (1.0 - i / rounds) * jnp.clip(
            (load - share) / share, -1.0, 1.0)

    return lax.fori_loop(0, rounds, nudge, jnp.zeros((e,), F32))


def _activate(hidden, activation: str):
    """float32 ``hidden`` = x W1 [..., groups * f] -> what W2 takes
    [..., f], float32."""
    if activation == "relu2":
        return jnp.square(jnp.maximum(hidden, 0.0))
    if activation == "silu":
        gate, up = jnp.split(hidden, 2, axis=-1)
        return gate * jax.nn.sigmoid(gate) * up
    raise ValueError(f"an expert's activation is one of "
                     f"{sorted(_W1_GROUPS)}, not {activation!r}")


# The gather kernel's share of the 128 MiB of VMEM a v5e core has: a ring of
# `_RING` (w1 tile, w2 tile) buffers, `_RING - 1` of them arriving while one
# is computed on.  Timed alone on one v5e (PR 28, 2.74 held experts a call):
# tiles 384 / 896 / 2688 at 45.5-46.0 / 46.0-46.2 / 47.8-48.0 us, rings of
# 2, 3, 4 alike: any tile that lets the first one land early will do.
_MAX_TILE = 1024
_RING = 3


def _gather_kernel(idx_ref, wts_ref, x_ref, w1_hbm, w2_hbm, out_ref, n_ref,
                   held_ref, w1_buf, w2_buf, sems, *, first_expert, tile,
                   activation):
    """All T * k assignment slots of a call: idx / wts [T * k] in SMEM, x
    [T, d] in VMEM, the held experts' w1 [E, d, groups * f] / w2 [E, f, d]
    left in HBM -> out [T, d] float32, n [1] = the expert blocks fetched."""
    n_slots = idx_ref.shape[0]
    e_local, f, d = w2_hbm.shape
    groups = _W1_GROUPS[activation]
    t = x_ref.shape[0]
    k = n_slots // t
    n_tiles = f // tile
    ring = w1_buf.shape[0]

    # the slots whose expert is held here, in slot order (scalar core)
    def scan(s, n):
        e = idx_ref[s] - first_expert
        held = (e >= 0) & (e < e_local)

        @pl.when(held)
        def _():
            held_ref[n] = s
        return n + held.astype(jnp.int32)

    n_held = lax.fori_loop(0, n_slots, scan, jnp.int32(0))
    n_ref[0] = n_held
    # one chunk = one f-tile of one held slot's expert: w1[e][:, tile] and
    # w2[e][tile, :], the split exact because the activation is elementwise
    n_chunks = n_held * n_tiles

    def copies(c):
        e = idx_ref[held_ref[c // n_tiles]] - first_expert
        b = c % ring
        if n_tiles == 1:  # the expert whole, gate and up in one copy
            return (pltpu.make_async_copy(w1_hbm.at[e], w1_buf.at[b],
                                          sems.at[0, b]),
                    pltpu.make_async_copy(w2_hbm.at[e], w2_buf.at[b],
                                          sems.at[groups, b]))
        off = pl.multiple_of((c % n_tiles) * tile, 128)
        w2_copy = pltpu.make_async_copy(w2_hbm.at[e, pl.ds(off, tile), :],
                                        w2_buf.at[b], sems.at[groups, b])
        if groups == 1:
            return (pltpu.make_async_copy(w1_hbm.at[e, :, pl.ds(off, tile)],
                                          w1_buf.at[b], sems.at[0, b]),
                    w2_copy)
        # a gate tile lands beside the up tile of the same columns
        return tuple(pltpu.make_async_copy(
            w1_hbm.at[e, :, pl.ds(pl.multiple_of(g * f + off, 128), tile)],
            w1_buf.at[b, :, pl.ds(g * tile, tile)], sems.at[g, b])
            for g in range(groups)) + (w2_copy,)

    for c in range(ring - 1):  # ring - 1 chunks in flight from here on
        @pl.when(c < n_chunks)
        def _():
            for copy in copies(c):
                copy.start()

    x = x_ref[...]
    row = lax.broadcasted_iota(jnp.int32, (t, 1), 0)

    def step(c, acc):
        @pl.when(c + ring - 1 < n_chunks)
        def _():
            for copy in copies(c + ring - 1):
                copy.start()
        for copy in copies(c):
            copy.wait()
        b = c % ring
        slot = held_ref[c // n_tiles]
        # at one row the MXU and a VPU multiply-reduce both hide under the
        # tile's DMA (45.9-46.2 / 46.2-48.0 us a call); the MXU form is the
        # grouped path's arithmetic, hidden rounded to bf16 and all
        hidden = jnp.dot(x, w1_buf[b], preferred_element_type=F32)
        hidden = _activate(hidden, activation).astype(x.dtype)
        out = jnp.dot(hidden, w2_buf[b], preferred_element_type=F32)
        # the slot's token takes it, under the router's weight
        return acc + jnp.where(row == slot // k, wts_ref[slot], 0.0) * out

    out_ref[...] = lax.fori_loop(0, n_chunks, step, jnp.zeros((t, d), F32))


@functools.partial(jax.jit, static_argnames=(
    "first_expert", "activation", "tile", "interpret"))
def gather_expert_sum(x, idx, weights, w1, w2, *, first_expert: int,
                      activation: str = "relu2", tile: int = None,
                      interpret: bool = False):
    """`local_expert_sum` for a few tokens, as one Pallas TPU kernel: every
    chosen-and-held expert's matrices fetched from HBM by id, once, in
    ``tile`` columns of f at a time (default: the largest multiple of 128
    that divides f, up to `_MAX_TILE`; of a gated expert's fused ``w1`` the
    gate's tile and the up-projection's tile of the same columns); a slot
    whose expert lies elsewhere costs a scalar comparison and no DMA.  bf16
    (the weights' dtype) into the MXU, float32 accumulation, float32 out,
    like the grouped path.  Needs d and f in multiples of 128;
    ``interpret`` runs it on the CPU."""
    t, k = idx.shape
    groups = _W1_GROUPS[activation]
    _, f, d = w2.shape
    if w1.shape[1:] != (d, groups * f):
        raise ValueError(f"gather_expert_sum: {activation} experts of w2 "
                         f"{w2.shape} need w1 [E, {d}, {groups * f}], not "
                         f"{w1.shape}")
    if tile is None:
        tile = next((c for c in range(min(f, _MAX_TILE), 0, -128)
                     if f % c == 0), 0)
    if d % 128 or not tile or f % tile or tile % 128:
        raise ValueError(f"gather_expert_sum: d={d} and tile={tile} (f={f}) "
                         "must be multiples of 128, and tile divide f")
    ring_bytes = ((groups + 1) * _RING * d * tile
                  * jnp.dtype(w1.dtype).itemsize)
    out, n = pl.pallas_call(
        functools.partial(_gather_kernel, first_expert=first_expert,
                          tile=tile, activation=activation),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # ids and router weights, in SMEM
            grid=(1,),
            in_specs=[pl.BlockSpec((t, d), lambda i, *_: (0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((t, d), lambda i, *_: (0, 0)),
                       pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=[pltpu.SMEM((t * k,), jnp.int32),
                            pltpu.VMEM((_RING, d, groups * tile), w1.dtype),
                            pltpu.VMEM((_RING, tile, d), w2.dtype),
                            pltpu.SemaphoreType.DMA((groups + 1, _RING))]),
        out_shape=[jax.ShapeDtypeStruct((t, d), F32),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=ring_bytes + (16 << 20)),
        interpret=pltpu.InterpretParams() if interpret else False,
        # the device op's name: `lm.moe.experts` stays in its op_name, which
        # is how the benchmark's `moe_experts_ms_per_token` finds it
        name="expert_gather_matvec",
    )(idx.reshape(-1), weights.reshape(-1).astype(F32), x, w1, w2)
    return out, n[0]


def local_expert_sum(x, idx, weights, w1, w2, *, first_expert: int,
                     activation: str = "relu2"):
    """sum over the chosen experts HELD HERE of w_i * act(x W1_i) W2_i:
    ``activation`` "relu2" gives ``relu(x W1_i)^2``, "silu" the gated
    ``silu(x G_i) * (x U_i)`` of a fused ``W1_i = [G_i | U_i]`` (the module's
    docstring has the layout).

    ``x`` [T, d]; ``idx`` / ``weights`` [T, k] from `route` (ids over all
    experts); ``w1`` [E_local, d, f] (gated: [E_local, d, 2 f]), ``w2``
    [E_local, f, d]: experts ``first_expert .. first_expert + E_local - 1``.
    Returns ([T, d] float32, how many of the T * k assignments fell on held
    experts).

    Fewer than `MIN_GROUPED_ROWS` assignments (a decode step, or the few
    rows of a block-diffusion decode pass) on a TPU go through
    `gather_expert_sum` - which fetches per held (token, expert)
    ASSIGNMENT: an expert two rows of the call chose crosses the HBM twice
    -; everything else is the grouped matmul."""
    t, k = idx.shape
    _, f, d = w2.shape
    if (t * k < MIN_GROUPED_ROWS and d % 128 == 0 and f % 128 == 0
            and jax.devices()[0].platform == "tpu"):
        return gather_expert_sum(x, idx, weights, w1, w2,
                                 first_expert=first_expert,
                                 activation=activation)
    e_local = w1.shape[0]
    local = idx - first_expert
    held = (local >= 0) & (local < e_local)
    # held assignments first, grouped by expert; the others after them
    group = jnp.where(held, local, e_local).reshape(-1)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=e_local + 1)[:e_local].astype(
        jnp.int32)
    n_held = jnp.sum(sizes)
    hidden = lax.ragged_dot(x[order // k], w1, sizes,
                            preferred_element_type=F32)
    hidden = _activate(hidden, activation).astype(x.dtype)
    out = lax.ragged_dot(hidden, w2, sizes, preferred_element_type=F32)
    # rows past the last group belong to no expert held here
    in_a_group = jnp.arange(t * k) < n_held
    out = jnp.where(in_a_group[:, None],
                    out * weights.reshape(-1)[order][:, None], 0.0)
    # back to assignment order (a gather, not a scatter-add), k rows a token
    back = jnp.argsort(order)
    return out[back].reshape(t, k, -1).sum(axis=1), n_held
