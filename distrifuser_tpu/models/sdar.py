"""SDAR-style language model (``model_type: sdar_moe``, as SDAR-30B-A3B-Chat
publishes it: a Qwen3-MoE block trained to generate by DIFFUSION OVER
BLOCKS): grouped-query attention with an RMS norm on every query and key
head, softmax-routed gated-SiLU experts with no shared expert and no dense
layer, visibility by block, and a decode loop whose trip is a block of
positions denoised together against a KV cache and then committed to it by
a pass of its own.

    x <- x + Attn(RMSNorm(x));  x <- x + Experts(RMSNorm(x))      eps 1e-6
    logits = RMSNorm(x) W_head                 float32, untied embedding

``Attn``, with ``h`` the normed input [T, d] at positions ``pos``:

    q = rope(RMSNorm_head(h W_q -> [T, Hq, D]; q_norm [D]), pos)
    k = rope(RMSNorm_head(h W_k -> [T, Hkv, D]; k_norm [D]), pos)
    v = h W_v -> [T, Hkv, D]
    a_i = sum_j softmax_j(q_i . k_j / sqrt(D) | j // B <= i // B) v_j
    x <- x + concat_heads(a) W_o

rotate-half rotary embedding over all D (theta 1e6), ``Hq / Hkv`` query
heads a KV head, the softmax in float32.  **Visibility is by block, not by
position**: with ``B = block_length``, position ``i`` sees position ``j``
iff ``j // B <= i // B`` - both directions inside its own block, everything
before it (`ops/gqa_cache.py cache_attention`, with the last position of a
query's block as each row's limit: a decode sweep's few rows against the
whole cache take one single-pass kernel on a TPU, a prompt or a suffix
entering a cache `ops/attention.py gqa_sdpa_by_query_block`).  The per-head
norm has no key in the published config: it is the family's (Qwen3-MoE).

``Experts``: ``p = softmax(u W_g)`` over ALL experts in float32, the
``num_experts_per_tok`` largest chosen, weights ``p_e / sum of the chosen
p`` (``norm_topk_prob``; no bias, no scale: `ops/moe.py route`, scoring
"softmax"); of the chosen, the experts HELD HERE each
``(silu(u G_e) * (u U_e)) D_e`` (gate | up fused, `ops/moe.py`).

Expert parallelism is in the configuration, as in `models/deepseek_v3.py`:
``n_local_experts`` of ``num_experts`` are held (``first_local_expert``
onward), the router keeps its full width, and what absent experts would add
is left out.  The vocabulary may be a slice: ids, logits, the choice and the
confidence are then over the slice.

**Generation** (`decode`; the family's published procedure with
``block_length`` B, ``denoising_steps`` T, static low-confidence remasking,
temperature 0 - neither size is in the config: both are assumed).  For each
block of B positions after the prompt:

1. the block starts as B MASK ids;
2. T times, a DENOISE pass: the block's B rows go through the stack
   against the cache rows before the block and the block's own keys and
   values; the logits at a masked position predict THAT position (no
   shift); at every still-masked position the candidate is the largest
   logit and its confidence the softmax probability of it; the ``B / T``
   most confident masked positions take their candidates (ties to the
   lower position);
3. once more, the COMMIT pass: the finished block goes through the stack
   and ITS keys and values are what the cache keeps - those of a pass that
   still held a MASK are not the keys and values of the final block.  No
   head: nobody reads its logits.

A pass writes its block's keys and values into the cache rows the block
will own and reads rows ``0 .. end of the block``: no position before the
commit pass's write reads those rows, so a denoise pass leaves nothing in
the cache that anything sees.  **A pass is a block's B rows through the
stack; a SWEEP is a trip over the stack's weights, and two passes may share
one**: a block's commit pass and its successor's first denoise pass are the
2B rows of one sweep at the finished block's position.  Under the block
rule the first B see keys up to their block's end and the last B - MASK ids
- up to theirs, which includes the first B's keys and values OF THE SAME
LAYER, written into the cache before that layer's attention reads it:
layer by layer the first B rows compute what a commit pass alone computes
and the last B what a first denoise pass alone computes, and weights and
cache cross the memory once for both (the experts in a call a block:
`moe_layer`).  The head, the unmasking and the record take the last B rows.
Block 0's first denoise pass and the last block's commit pass have no
partner: ``blocks * T + 1`` sweeps for ``blocks * (T + 1)`` passes.  The
stack is traced three times - a denoise pass alone (the inner loop over a
block's passes, which block 0 enters at pass 0 and every later block at
pass 1), the shared sweep, the last commit pass (a conditional's branches)
-, all blocks one loop on the device.

Departures, each also in the benchmark configuration's ``assumed``: the
MASK id is the LAST id of the held vocabulary (published:
151669, which a slice does not hold); it is never chosen - its logit is
left out of candidate and confidence (a trained model does not emit it; a
seeded one would once in a vocabulary's worth of tokens, and that block
would never finish); only the static rule is built (the threshold rule
fixes a data-dependent number of positions a pass).

State across calls: per layer the cache ``k`` / ``v``
[Hkv, max_len, D] (KV-head major: a row is one position's D numbers) -
written before it is read; rows not written yet are never read into a
result - and, for the record, the experts every committed position chose in
every layer [layers, max_len, top_k].  One sequence at a time.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import moe
from ..ops.gqa_cache import cache_attention
from . import lm_common
from .language_model import LanguageModel
from .lm_common import F32, rms_norm
from .weights import params_nbytes

# counters the generation returns with its ids; ``tokens_reused``: of the
# positions the cache covers after prefill, those a cache handed in already
# covered; ``denoise_passes`` / ``commit_passes``: a block's rows through the
# stack, with the head and without; ``stack_sweeps``: trips over the stack's
# weights - the passes less the sweeps two passes share (with B rows a pass
# and ``blocks`` blocks: (passes - sweeps) / (blocks - 1) of the commit
# passes rode a denoise pass); ``expert_assignments`` (and
# ``_held``: those on experts held here): (row, layer, chosen expert)
# triples of the prefill's rows and of every pass's; ``experts_fetched``:
# expert weight blocks the DECODE PASSES' expert calls fetched - one per
# held assignment (`ops/moe.py gather_expert_sum`), so an expert two rows
# of a block chose counts twice: what the calls moved, beside the DISTINCT
# held experts a pass's rows chose, which the record gives;
# ``kv_cache_bytes``: every layer's keys and values; ``kv_rows_fetched``:
# cache rows of each KV head that the decode sweeps' attention fetched
# through `ops/gqa_cache.py streamed_gqa_attention`, summed over layers and
# sweeps (over ``stack_sweeps`` x layers: the rows in view, to a copy's 128;
# 0 on the XLA route, which reads every row the cache holds under its mask)
COUNTERS = ("tokens_prefilled", "tokens_reused", "tokens_decoded",
            "denoise_passes", "commit_passes", "expert_assignments",
            "expert_assignments_held", "experts_fetched", "kv_cache_bytes",
            "stack_sweeps", "kv_rows_fetched")


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    num_hidden_layers: int = 48
    vocab_size: int = 151936
    hidden_size: int = 2048
    rms_norm_eps: float = 1e-6
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1000000.0
    # experts
    moe_intermediate_size: int = 768
    num_experts: int = 128  # the router's width
    n_local_experts: int = 128  # held here ...
    first_local_expert: int = 0  # ... from this one on
    num_experts_per_tok: int = 8
    # generation (assumed: the published config gives neither)
    block_length: int = 4
    denoising_steps: int = 4
    # a prompt's length is a multiple of this (so is what a snapshot of its
    # prefix covers); whole blocks
    prefill_block: int = 128
    # the KV cache's dtype; None: the parameters'
    cache_dtype: Optional[str] = None
    # False leaves the commit pass out - the cache keeps the last denoise
    # pass's keys and values: NOT the model, the control that shows the pass
    # is mathematics (`benchmark/calibrate_sdar.py`, the tests)
    commit_pass: bool = True

    def __post_init__(self):
        if self.head_dim % 2:
            raise ValueError("the rotary embedding turns halves")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are whole groups of KV heads")
        if (self.first_local_expert + self.n_local_experts
                > self.num_experts):
            raise ValueError("the held experts lie outside the router")
        if self.block_length % self.denoising_steps:
            raise ValueError("a denoise pass fixes block_length / "
                             "denoising_steps positions: a whole number")
        if self.prefill_block % self.block_length:
            raise ValueError("a prompt is whole blocks")

    @property
    def mask_id(self) -> int:
        """The id a position holds until it is fixed: the last of the held
        vocabulary."""
        return self.vocab_size - 1

    def language_model(self) -> LanguageModel:
        """This model as the rewrite stage takes it: ids of words (the
        MASK id kept out of them); a suffix can enter the cache its prefix
        left; ids come a block at a time."""
        return LanguageModel(self, prefill, decode, COUNTERS,
                             self.prefill_block, self.vocab_size - 1,
                             prefill_from=prefill,
                             decode_multiple=self.block_length)


def sdar_config_from_json(d: Dict[str, Any]) -> SdarConfig:
    """From the published config.json keys, plus what a cut adds to them:
    ``num_experts`` counts the experts HELD and ``expert_parallel``
    (``{"chips": n, "index": i}``) says of how many shares this is which, so
    the router is ``chips`` times as wide; ``num_hidden_layers`` layers from
    the first are served; ``block_length``, ``denoising_steps``,
    ``prefill_block``, ``cache_dtype`` and ``commit_pass`` are ours."""
    lm_common.refuse_unbuilt(d, {
        "model_type": "sdar_moe", "mlp_only_layers": [],
        "decoder_sparse_step": 1, "use_sliding_window": False,
        "rope_scaling": None, "attention_bias": False,
        "norm_topk_prob": True, "hidden_act": "silu",
        "tie_word_embeddings": False})
    return SdarConfig(**{**lm_common.config_fields(SdarConfig, d),
                         **lm_common.expert_share(d, "num_experts")})


# -- parameters ---------------------------------------------------------------


def layer_shapes(cfg: SdarConfig) -> Dict[str, Any]:
    d, f = cfg.hidden_size, cfg.moe_intermediate_size
    hq, hkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    attn = {"q": {"kernel": (d, hq * hd)}, "k": {"kernel": (d, hkv * hd)},
            "v": {"kernel": (d, hkv * hd)},
            "q_norm": {"scale": (hd,)}, "k_norm": {"scale": (hd,)},
            "o_proj": {"kernel": (hq * hd, d)}}
    ffn = {"router": {"kernel": (d, cfg.num_experts)},
           "experts": {"w1": (cfg.n_local_experts, d, 2 * f),
                       "w2": (cfg.n_local_experts, f, d)}}
    return {"attn_norm": {"scale": (d,)}, "attn": attn,
            "ffn_norm": {"scale": (d,)}, "ffn": ffn}


def param_shapes(cfg: SdarConfig) -> Dict[str, Any]:
    """The parameter tree with a shape tuple at every leaf.  An expert's
    gate | up are held as one fused kernel: the same parameters and
    arithmetic."""
    d = cfg.hidden_size
    return {"embed": (cfg.vocab_size, d),
            "layers": [layer_shapes(cfg)] * cfg.num_hidden_layers,
            "final_norm": {"scale": (d,)},
            "head": {"kernel": (d, cfg.vocab_size)}}


# What a SEEDED model's per-head query and key norms scale by (a trained
# model's are learned).  With scales of one the attention logits q . k /
# sqrt(D) are N(0, 1) and every query's softmax is flat over its thousands
# of keys: a cache kept a precision too low is averaged away (on the chip a
# float8 cache moved the logits by a tenth of what bf16 itself does, PR 41).
# At 1.5 the logits spread by 2.25, a query attends to some tens of its 8192
# keys, and the cache's precision shows in what the routers choose.  Not
# higher: at 2 and above a seeded stack of 24 layers is chaotic under bf16
# (a flipped near-tie in one layer's attention moves the next layer's) and
# the served logits lose the float32 reference altogether (relative error
# 0.3 at 2, 1.2 at 3).
SEEDED_QK_NORM_SCALE = 1.5


def init_leaf(key, name: str, shape, cfg: SdarConfig, dtype):
    """One leaf by its name (a norm's scale goes by the norm's): norm scales
    ones - the per-head query and key norms' `SEEDED_QK_NORM_SCALE` -, the
    embedding N(0, 0.02^2), kernels N(0, 1 / fan_in)."""
    if name in ("q_norm", "k_norm"):
        return jnp.full(shape, SEEDED_QK_NORM_SCALE, dtype)
    if name.endswith("_norm"):
        return jnp.ones(shape, dtype)
    if name == "embed":
        return (0.02 * jax.random.normal(key, shape, F32)).astype(dtype)
    return (jax.random.normal(key, shape, F32) / math.sqrt(shape[-2])
            ).astype(dtype)


def named_leaves(cfg: SdarConfig):
    """([(a leaf's name - its own key; its norm's for a scale -, its
    shape)], the tree's structure)."""
    return lm_common.named_leaves(
        param_shapes(cfg),
        name=lambda keys: keys[-2] if keys[-1] == "scale" else keys[-1])


def init_sdar_params(key, cfg: SdarConfig, dtype=F32):
    return lm_common.init_params(key, cfg, dtype, named_leaves=named_leaves,
                                 init_leaf=init_leaf)


# -- layers -------------------------------------------------------------------


def rotary_half(x, positions, theta: float):
    """Rotate-half rotary embedding over the whole last axis: x [T, H, D] at
    ``positions`` [T]; the pair (x[i], x[i + D/2]) turns by
    ``position * theta^(-2 i / D)``.  float32 inside, the result in ``x``'s
    dtype."""
    half = x.shape[-1] // 2
    angle = positions.astype(F32)[:, None] * theta ** (
        -jnp.arange(half, dtype=F32) / half)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    xf = x.astype(F32)
    lo, hi = xf[..., :half], xf[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                           axis=-1).astype(x.dtype)


def sees_up_to(positions, block: int):
    """The last position each of ``positions`` sees: the end of its block."""
    return positions // block * block + block - 1


def attention_layer(p, cfg: SdarConfig, x, cache, position,
                    visible: Optional[int] = None):
    """x [T, d] (whole blocks) at ``position`` onward; its keys and values
    are written into ``cache`` {"k", "v"} [Hkv, max_len, D] first.

    ``visible`` None and ``position`` 0 (static): a whole prompt, over its
    own keys.  Otherwise against the cache's first ``visible`` rows (static,
    at least position + T): a suffix entering it, or - all of them, under
    the mask - a sweep of the decode loop, whose few rows take the
    single-pass kernel where there is a TPU (`ops/gqa_cache.py
    cache_attention` routes by the call's shape).
    -> (the layer's output [T, d], the cache, the cache rows of each KV head
    the kernel fetched: 0 on the XLA route)."""
    t = x.shape[0]
    hq, hkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    positions = position + jnp.arange(t)
    with jax.named_scope("lm.attn.proj"):
        q = rms_norm(p["q_norm"]["scale"],
                     (x @ p["q"]["kernel"]).reshape(t, hq, hd),
                     cfg.rms_norm_eps)
        k = rms_norm(p["k_norm"]["scale"],
                     (x @ p["k"]["kernel"]).reshape(t, hkv, hd),
                     cfg.rms_norm_eps)
        q = rotary_half(q, positions, cfg.rope_theta)
        k = rotary_half(k, positions, cfg.rope_theta).swapaxes(0, 1)
        v = (x @ p["v"]["kernel"]).reshape(t, hkv, hd).swapaxes(0, 1)
    with jax.named_scope("lm.attn"):
        cache = {
            "k": lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), position, axis=1),
            "v": lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), position, axis=1)}
        if visible is None:
            if position:
                raise ValueError(f"position {position} needs the cache of "
                                 f"the tokens before it")
            keys, values = k, v
        else:
            keys, values = cache["k"], cache["v"]
        out, fetched = cache_attention(
            q, keys, values, limits=sees_up_to(positions, cfg.block_length),
            visible=visible)
    with jax.named_scope("lm.attn.proj"):
        return (out.reshape(t, hq * hd) @ p["o_proj"]["kernel"], cache,
                fetched)


def moe_layer(p, cfg: SdarConfig, u, calls: int = 1):
    """-> (out [T, d] float32, how many of the T * top_k assignments fell on
    experts held here, the experts each row chose [T, top_k]).  The rows go
    to the experts in ``calls`` equal parts, a call each: the two blocks of
    a shared sweep each take the kernel a pass's few rows take
    (`ops/moe.py MIN_GROUPED_ROWS`), as they did a pass apiece."""
    with jax.named_scope("lm.moe.router"):
        idx, weights = moe.route(u, p["router"]["kernel"],
                                 top_k=cfg.num_experts_per_tok,
                                 scoring="softmax")
    with jax.named_scope("lm.moe.experts"):
        parts = [moe.local_expert_sum(
            *part, p["experts"]["w1"], p["experts"]["w2"],
            first_expert=cfg.first_local_expert, activation="silu")
            for part in zip(*(jnp.split(a, calls)
                              for a in (u, idx, weights)))]
    return (jnp.concatenate([routed for routed, _ in parts]),
            sum(held for _, held in parts), idx)


def head(params, cfg: SdarConfig, x):
    """x [T, d] -> float32 logits [T, V] over the held vocabulary."""
    return lm_common.head(params, x, cfg.rms_norm_eps)


# -- prefill, pass, generation -------------------------------------------------


def empty_state(cfg: SdarConfig, max_len: int, dtype):
    """The state with nothing in it and room for ``max_len`` positions."""
    dtype = jnp.dtype(cfg.cache_dtype or dtype)
    rows = jnp.zeros((cfg.num_key_value_heads, max_len, cfg.head_dim), dtype)
    return {"cache": [{"k": rows, "v": rows}] * cfg.num_hidden_layers,
            "experts": jnp.zeros((cfg.num_hidden_layers, max_len,
                                  cfg.num_experts_per_tok), jnp.int32)}


def _forward(params, cfg: SdarConfig, ids, state, position, visible,
             expert_calls: int = 1):
    """The stack over ids [T] (whole blocks) at ``position`` onward through
    the state, every layer's experts in ``expert_calls`` calls (`moe_layer`)
    -> (hidden [T, d], the new state, held expert assignments, the experts
    the rows chose [layers, T, top_k], the cache rows the layers' attention
    fetched: `attention_layer`)."""
    x = params["embed"][ids]
    caches, chosen = [], []
    held = fetched = jnp.zeros((), jnp.int32)
    for lp, cache in zip(params["layers"], state["cache"]):
        out, cache, rows = attention_layer(
            lp["attn"], cfg, rms_norm(lp["attn_norm"]["scale"], x,
                                      cfg.rms_norm_eps), cache, position,
            visible)
        x = x + out
        out, n, idx = moe_layer(
            lp["ffn"], cfg, rms_norm(lp["ffn_norm"]["scale"], x,
                                     cfg.rms_norm_eps), expert_calls)
        x = x + out.astype(x.dtype)
        caches.append(cache)
        chosen.append(idx)
        held = held + n.astype(jnp.int32)
        fetched = fetched + rows
    chosen = jnp.stack(chosen)
    experts = lax.dynamic_update_slice_in_dim(state["experts"], chosen,
                                              position, axis=1)
    return x, {"cache": caches, "experts": experts}, held, chosen, fetched


def assignments(cfg: SdarConfig, rows: int) -> int:
    return rows * cfg.num_hidden_layers * cfg.num_experts_per_tok


_count = functools.partial(lm_common.count, COUNTERS)


def prefill(params, cfg: SdarConfig, ids, *, max_len: int, state=None,
            position: int = 0, counters=None):
    """ids [T] (T a multiple of ``prefill_block``) at ``position`` onward,
    under the block rule -> (float32 logits at the last id [V] - of no use
    to `decode`, which starts from MASK ids -, the state, the `COUNTERS` so
    far int32, the experts the T ids chose [layers, T, top_k]).

    `models/language_model.py`'s ``prefill`` and ``prefill_from`` both: a
    prompt from position 0 attends over its own keys, a suffix entering
    ``state`` against the cache's first ``position + T`` rows (of
    ``tokens_prefilled``, ``tokens_reused`` = ``position``)."""
    t = ids.shape[0]
    visible = None if state is None else position + t
    state, counters = lm_common.enter_state(
        state, counters, COUNTERS, position=position,
        empty=lambda: empty_state(cfg, max_len, params["embed"].dtype),
        room=lambda state: state["cache"][0]["k"].shape[1],
        needed=max(max_len, position + t))
    x, state, held, chosen, _ = _forward(params, cfg, ids, state, position,
                                         visible)
    counters = _count(
        counters, put={"tokens_reused": position,
                       "kv_cache_bytes": params_nbytes(state["cache"])},
        tokens_prefilled=t, expert_assignments=assignments(cfg, t),
        expert_assignments_held=held)
    return head(params, cfg, x[-1:])[0], state, counters, chosen


@jax.named_scope("lm.sdar.unmask")
def unmask(cfg: SdarConfig, logits, block_ids):
    """Static low-confidence remasking, greedy: ``logits`` [B, V] float32 of
    a denoise pass over ``block_ids`` [B] (MASK where not fixed yet) ->
    (the block with its ``B / T`` most confident masked positions fixed to
    their candidates, those positions [B / T]).  The MASK id's own logit is
    left out of candidate and confidence; ties go to the lower position."""
    masked = block_ids == cfg.mask_id
    logits = jnp.where(jnp.arange(logits.shape[-1]) == cfg.mask_id, -jnp.inf,
                       logits)
    candidate = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # the softmax probability of the largest logit
    confidence = 1.0 / jnp.sum(
        jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True)), axis=-1)
    order = jnp.argsort(-jnp.where(masked, confidence, -1.0), stable=True)
    fixed = order[:cfg.block_length // cfg.denoising_steps]
    return block_ids.at[fixed].set(candidate[fixed]), fixed


def decode(params, cfg: SdarConfig, logits, state, counters, *,
           position: int, new_tokens: int):
    """Generation by diffusion over blocks through the state, on the device
    from first block to last (the module's docstring has the procedure):
    ``new_tokens / block_length`` blocks, each ``denoising_steps`` denoise
    passes and one commit pass, a block's commit pass and its successor's
    first denoise pass ONE sweep of the stack over both blocks' rows.
    ``logits`` - what the prefill returned - are NOT read: a block starts
    from MASK ids, and a masked position's logits predict that position.
    -> (ids [new_tokens] int32, the float32 logits each was fixed from
    [new_tokens, V], the record {"fixed_in_pass" [new_tokens]: the denoise
    pass of its block that fixed each id; "denoise_experts"
    [blocks, T, B, layers, top_k]: the experts every denoise pass's rows
    chose; "experts" [layers, max_len, top_k]: those EVERY committed
    position so far chose - the prompt's, a snapshot's too}, the state, the
    counters)."""
    del logits
    size, steps = cfg.block_length, cfg.denoising_steps
    if new_tokens % size:
        raise ValueError(f"new_tokens {new_tokens} is not whole blocks of "
                         f"{size}")
    blocks = new_tokens // size
    masks = jnp.full((size,), cfg.mask_id, jnp.int32)

    def sweep(rows, state, counters, start, **passes):
        """One trip over the stack's weights: ``rows`` the ids of one block
        (a pass) or of two (a commit pass and the next block's first denoise
        pass), each block's experts a call of their own - a pass's few rows
        take the gather kernel, which fetches an expert's weights per held
        ASSIGNMENT."""
        n = rows.shape[0] // size
        x, state, held, chosen, fetched = _forward(
            params, cfg, rows, state, start,
            state["cache"][0]["k"].shape[1], expert_calls=n)
        return x, state, chosen, _count(
            counters, stack_sweeps=1, kv_rows_fetched=fetched,
            expert_assignments=assignments(cfg, n * size),
            expert_assignments_held=held, experts_fetched=held, **passes)

    def fix(b, t, block_ids, x, chosen, out):
        """What denoise pass ``t`` of block ``b`` does with its rows out of
        the stack: the head, the positions it fixes, the record."""
        fixed_from, fixed_in, denoise_experts = out
        logits = head(params, cfg, x)
        block_ids, fixed = unmask(cfg, logits, block_ids)
        with jax.named_scope("lm.sdar.unmask"):
            fixed_from = fixed_from.at[b * size + fixed].set(logits[fixed])
            fixed_in = fixed_in.at[b * size + fixed].set(t)
        denoise_experts = lax.dynamic_update_slice(
            denoise_experts, chosen.swapaxes(0, 1)[None, None],
            (b, t, 0, 0, 0))
        return block_ids, (fixed_from, fixed_in, denoise_experts)

    def shared(b, done, state, out, counters):
        """The finished block ``b``'s commit pass and its successor's first
        denoise pass, one sweep: the successor's rows see the keys and
        values the same sweep commits, layer by layer."""
        x, state, chosen, counters = sweep(
            jnp.concatenate([done, masks]), state, counters,
            position + b * size, commit_passes=1, denoise_passes=1)
        block_ids, out = fix(b + 1, 0, masks, x[size:], chosen[:, size:], out)
        return block_ids, state, out, counters

    def alone(b, done, state, out, counters):
        """The last block's commit pass: no successor, and no head."""
        _, state, _, counters = sweep(done, state, counters,
                                      position + b * size, commit_passes=1)
        return masks, state, out, counters

    def block(b, carry):
        """``block_ids``: block ``b`` as the sweep it shared with the block
        before it left it - its first denoise pass made - or, block 0 and
        every block of the control, all MASK ids."""
        block_ids, state, ids, out, counters = carry

        def denoise(t, inner):
            """A denoise pass alone."""
            block_ids, state, out, counters = inner
            x, state, chosen, counters = sweep(
                block_ids, state, counters, position + b * size,
                denoise_passes=1)
            block_ids, out = fix(b, t, block_ids, x, chosen, out)
            return block_ids, state, out, counters

        first = jnp.where(b == 0, 0, 1) if cfg.commit_pass else 0
        block_ids, state, out, counters = lax.fori_loop(
            first, steps, denoise, (block_ids, state, out, counters))
        ids = lax.dynamic_update_slice_in_dim(ids, block_ids, b * size, axis=0)
        counters = _count(counters, tokens_decoded=size)
        if cfg.commit_pass:
            block_ids, state, out, counters = lax.cond(
                b < blocks - 1, shared, alone, b, block_ids, state, out,
                counters)
        else:
            block_ids = masks
        return block_ids, state, ids, out, counters

    _, state, ids, out, counters = lax.fori_loop(0, blocks, block, (
        masks, state, jnp.zeros((new_tokens,), jnp.int32),
        (jnp.zeros((new_tokens, cfg.vocab_size), F32),
         jnp.zeros((new_tokens,), jnp.int32),
         jnp.zeros((blocks, steps, size, cfg.num_hidden_layers,
                    cfg.num_experts_per_tok), jnp.int32)), counters))
    fixed_from, fixed_in, denoise_experts = out
    record = {"fixed_in_pass": fixed_in, "denoise_experts": denoise_experts,
              "experts": state["experts"]}
    return ids, fixed_from, record, state, counters


def generate(params, cfg: SdarConfig, ids, new_tokens: int):
    """Prefill, then generation by blocks -> (new ids, the logits each was
    fixed from, the counters, the record `decode` returns)."""
    return lm_common.generate(cfg.language_model(), params, ids,
                              new_tokens)[:4]

