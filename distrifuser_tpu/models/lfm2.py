"""LFM2 causal language model (``model_type: lfm2_moe``, as LFM2-24B-A2B
publishes it): THREE kinds of layer in one stack - a gated short convolution
as the token mixer in three layers of four (`ops/short_conv.py`),
grouped-query attention with heads of 64 in the fourth, and behind either a
gated MLP (the ``num_dense_layers`` leading layers) or sigmoid-routed
gated-SiLU experts with no shared expert (`ops/moe.py`) -, a head tied to
the embedding, with prefill (of a whole prompt, or of a suffix through the
state its prefix left), a one-token step through the state, and a greedy
decode loop that stays on the device.

One layer ``l``, ``x`` [T, d], RMS norms with plain scales, eps ``norm_eps``:

    h = RMSNorm(x; operator_norm)
    conv layer (``layer_types[l] == "conv"``):
      [B | C | z] = h W_in                     three [T, d], in THIS order
      g   = B * z                              the first gate
      c_t = sum_{i<K} k[i] * g_{t-(K-1)+i}     per channel: depthwise, causal,
                                               K = conv_L_cache taps, no
                                               bias, no activation
      m   = (C * c) W_out                      the second gate
    attention layer (``"full_attention"``):
      q = rope(RMSNorm_head(h W_q -> [T, Hq, D]; q_norm [D]), pos)
      k = rope(RMSNorm_head(h W_k -> [T, Hkv, D]; k_norm [D]), pos)
      v = h W_v -> [T, Hkv, D]
      a_i = sum_{j<=i} softmax_j(q_i . k_j / sqrt(D)) v_j     float32 softmax
      m   = concat_heads(a) W_o
    x = x + m
    u = RMSNorm(x; ffn_norm)
    l <  num_dense_layers:  x = x + (silu(u G) * (u U)) D
    l >= num_dense_layers:  s = sigmoid(u W_g) over ALL experts, float32
        S = the top_k largest of s + b        (b: expert_bias, selection only)
        w_e = routed_scaling_factor * s_e / (sum_S s + 1e-6)
        x = x + sum_{e in S, held here} w_e (silu(u G_e) * (u U_e)) D_e

then the final RMS norm (the published tree calls it ``embedding_norm``; it
is applied AFTER the last layer; here ``final_norm``, the siblings' name) and
the head, ``logits = x_norm E^T`` with ``E`` the embedding: one leaf
[V, d], read by rows going in and contracted over its second axis going out
(`models/lm_common.py head`).  D = d / Hq = 64; rotate-half rotary
embedding over all D, ``Hq / Hkv`` query heads a KV head.

**Assumed** (the catalog's row drops the key or the config does not say;
each also in the benchmark configuration's ``assumed``): the tied head; the
chunk order ``B | C | x`` of the input projection; the per-head q/k norms;
rotate-half pairing; the router weights' ``1e-6``; the final norm's place;
an expert's gate | up held as ONE fused kernel (`ops/moe.py`).  A
multi-token or any other head: none is published, none is built.

Expert parallelism is in the configuration, as in the sibling expert models:
``n_local_experts`` of ``num_experts`` are held (``first_local_expert``
onward), the router keeps its full width, and what absent experts would add
is left out.  The vocabulary may be a slice: ids, logits and the greedy
choice are then over the slice.

State across calls, two kinds in one carry: a conv layer's is BOUNDED - the
tail [K - 1, d] of GATED inputs ``g`` (not of ``h``) - and is a VALUE: a
suffix's first rows and a decode step's one row start from it; an attention
layer's GROWS - a KV cache whose rows are written before they are read.
**A cache row holds `kv_pack` KV heads side by side** - ``k`` / ``v``
[Hkv / pack, max_len, pack * D], pack = 128 / D = 2: a 64-wide row would be
padded to the 128 lanes of a tile, in memory and in every decode step's
bytes; two heads a row fill it.  The queries of a KV head are widened to the
row with zeros where the row's other head lies (so a score is the head's own
q . k: exact), the weighted sum of a row's values is computed for both heads
and each query keeps its own head's half.  To `ops/gqa_cache.py
cache_attention` that is grouped-query attention of ``Hq`` heads over
``Hkv / pack`` KV heads of 128 - the shape its single-pass kernel serves on
a TPU -, whose softmax scale 1 / sqrt(pack * D) the queries make up for by
sqrt(pack), applied in float32 before they are rounded.  And, for the
record, the experts every position chose [E layers, max_len, top_k].  One
sequence at a time (no batch axis).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import moe
from ..ops.gqa_cache import cache_attention
from ..ops.short_conv import gated_short_conv
from . import deepseek_v3 as dsv3
from . import lm_common
from .language_model import LanguageModel
from .lm_common import F32, gated_mlp, rms_norm
from .sdar import SEEDED_QK_NORM_SCALE, rotary_half

# counters the generation returns with its ids: `models/deepseek_v3.py
# COUNTERS` under their names there; ``state_bytes`` is the whole decode
# state AS HELD - every conv layer's tail and every attention layer's cache,
# its rows at the width they are held with -, ``cache_rows_fetched`` the
# cache rows of each two-head row-group the decode steps' attention fetched
# where it is the single-pass kernel (`ops/gqa_cache.py
# streamed_gqa_attention`), summed over steps and layers - 0 on the XLA
# route, which reads every row of the cache under its mask
COUNTERS = dsv3.COUNTERS
KINDS = ("conv", "full_attention")
# the published pattern: two leading conv layers, then attention, conv, conv,
# conv to the end (attention at 2, 6, .., 38)
_PUBLISHED_TYPES = tuple(KINDS[i % 4 == 2] for i in range(40))
_ROUTER_EPS = 1e-6
_LANES = 128
# What a SEEDED model's mixers scale by, so that the stack keeps its float32
# reference under bf16 AND a cache kept a precision too low shows (a trained
# model's kernels are learned).  With every kernel N(0, 1 / fan_in) a conv
# mixer's output has unit variance but is a product of three projections of
# its input - an error in the stream passes through all three, sqrt(3) of a
# linear map's share - and fifteen of them make a seeded stack of twenty
# layers chaotic: the served logits stood 4.8% from the float32 reference,
# and a float8 cache moved that by a twentieth.  An attention mixer's output
# is an AVERAGE over the hundreds of keys a seeded query attends to, a
# twelfth of the other sublayers' scale, so the cache hardly reached the
# logits.  The taps at a quarter and the attention's output projection at
# four put both near the scale of the feed-forward sublayers between them (my
# chip runs, PR 45, one seed, median relative error of the logits sound /
# float8 cache: 0.0486 / 0.0508 as N(0, 1 / fan_in) leaves them; taps 0.5,
# 0.25, 0.125 alone 0.0423 / 0.0467, 0.0290 / 0.0340, 0.0214 / 0.0266; taps
# 0.25 with the projection at 2, 4, 8, 16, 32: 0.0288 / 0.0393, 0.0164 /
# 0.0224, 0.0134 / 0.0174, 0.0128 / 0.0162, 0.0125 / 0.0158 - past 4 the
# attention's common part swamps the keys and the cache shows less again;
# q/k norms at 2, 2.5, 3 instead of `models/sdar.py SEEDED_QK_NORM_SCALE`
# beside these two: 0.43, 0.76, 0.90 - no reference left).
SEEDED_TAP_SCALE = 0.25
SEEDED_ATTN_OUT_SCALE = 4.0


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    num_hidden_layers: int = 40
    vocab_size: int = 65536
    hidden_size: int = 2048
    norm_eps: float = 1e-5
    # the kind of every published layer; the first num_hidden_layers are served
    layer_types: Tuple[str, ...] = _PUBLISHED_TYPES
    conv_L_cache: int = 3  # the convolution's taps
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    rope_theta: float = 1000000.0
    # feed-forward
    num_dense_layers: int = 2
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_experts: int = 64  # the router's width
    n_local_experts: int = 64  # held here ...
    first_local_expert: int = 0  # ... from this one on
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    # a prompt's length is a multiple of this (so is what a snapshot of its
    # prefix covers)
    prefill_block: int = 128
    # the KV caches' dtype; None: the parameters'
    cache_dtype: Optional[str] = None
    # False enters every state with ZERO tails - a suffix entering a
    # snapshot, decoding entering the prefill's state: NOT the model, the
    # control that shows the carried tails are mathematics
    # (`benchmark/calibrate_lfm2.py`, the tests)
    carry_conv_tails: bool = True

    def __post_init__(self):
        if len(self.layer_types) < self.num_hidden_layers:
            raise ValueError("layer_types names no kind for some layer of "
                             "the stack")
        unknown = set(self.kinds) - set(KINDS)
        if unknown:
            raise ValueError(f"a layer is one of {KINDS}, not "
                             f"{sorted(unknown)}")
        if self.hidden_size % self.num_attention_heads or self.head_dim % 2:
            raise ValueError("heads divide the hidden size, and the rotary "
                             "embedding turns halves")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are whole groups of KV heads")
        if (self.first_local_expert + self.n_local_experts
                > self.num_experts):
            raise ValueError("the held experts lie outside the router")

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The kind of every layer served, first to last."""
        return tuple(self.layer_types[:self.num_hidden_layers])

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_pack(self) -> int:
        """KV heads that share one cache row: as many heads of ``head_dim``
        as a tile's 128 lanes hold (and the KV heads divide into)."""
        return math.gcd(self.num_key_value_heads,
                        max(1, _LANES // self.head_dim))

    @property
    def n_expert_layers(self) -> int:
        return max(0, self.num_hidden_layers - self.num_dense_layers)

    # the name `models/deepseek_v3.py`'s head reads the norms' eps by
    rms_norm_eps = property(lambda self: self.norm_eps)

    def language_model(self) -> LanguageModel:
        """This model as the rewrite stage takes it: ids of words; a suffix
        can enter the state its prefix left."""
        return LanguageModel(self, prefill, decode, COUNTERS,
                             self.prefill_block, self.vocab_size,
                             prefill_from=prefill)


def lfm2_config_from_json(d: Dict[str, Any]) -> Lfm2Config:
    """From the published config.json keys, plus what a cut adds to them:
    ``num_experts`` counts the experts HELD and ``expert_parallel``
    (``{"chips": n, "index": i}``) says of how many shares this is which, so
    the router is ``chips`` times as wide; ``num_hidden_layers`` layers from
    the first are served, their kinds read from the published
    ``layer_types`` as far as that; ``prefill_block``, ``cache_dtype`` and
    ``carry_conv_tails`` are ours."""
    rope = d.get("rope_parameters", {})
    lm_common.refuse_unbuilt(d, {
        "model_type": "lfm2_moe", "conv_bias": False, "use_expert_bias": True,
        "norm_topk_prob": True, "tie_word_embeddings": True})
    lm_common.refuse_unbuilt(rope, {"rope_type": "default"})
    return Lfm2Config(**{
        **lm_common.config_fields(Lfm2Config, d),
        "layer_types": tuple(d["layer_types"]),
        "rope_theta": float(rope.get("rope_theta", Lfm2Config.rope_theta)),
        **lm_common.expert_share(d, "num_experts")})


# -- parameters ---------------------------------------------------------------


def _kernel(*shape) -> Dict[str, Any]:
    return {"kernel": shape}


def layer_shapes(cfg: Lfm2Config, i: int) -> Dict[str, Any]:
    d, hd = cfg.hidden_size, cfg.head_dim
    if cfg.kinds[i] == "conv":
        mixer = {"in_proj": _kernel(d, 3 * d),
                 "conv": _kernel(cfg.conv_L_cache, d),
                 "out_proj": _kernel(d, d)}
    else:
        hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
        mixer = {"q": _kernel(d, hq * hd), "k": _kernel(d, hkv * hd),
                 "v": _kernel(d, hkv * hd), "q_norm": {"scale": (hd,)},
                 "k_norm": {"scale": (hd,)}, "o_proj": _kernel(hq * hd, d)}
    if i < cfg.num_dense_layers:
        f = cfg.intermediate_size
        ffn = {"gate_up": _kernel(d, 2 * f), "down": _kernel(f, d)}
    else:
        f = cfg.moe_intermediate_size
        ffn = {"router": _kernel(d, cfg.num_experts),
               "expert_bias": (cfg.num_experts,),
               "experts": {"w1": (cfg.n_local_experts, d, 2 * f),
                           "w2": (cfg.n_local_experts, f, d)}}
    return {"operator_norm": {"scale": (d,)}, "mixer": mixer,
            "ffn_norm": {"scale": (d,)}, "ffn": ffn}


def param_shapes(cfg: Lfm2Config) -> Dict[str, Any]:
    """The parameter tree with a shape tuple at every leaf.  No ``head``:
    it is the embedding.  gate | up of the dense MLP and of every expert are
    held as one fused kernel: the same parameters and arithmetic."""
    return {"embed": (cfg.vocab_size, cfg.hidden_size),
            "layers": [layer_shapes(cfg, i)
                       for i in range(cfg.num_hidden_layers)],
            "final_norm": {"scale": (cfg.hidden_size,)}}


def init_leaf(key, name: str, shape, cfg: Lfm2Config, dtype):
    """One leaf by its name (a norm's scale goes by the norm's, a kernel by
    its projection's): norm scales ones - the per-head query and key norms'
    `models/sdar.py SEEDED_QK_NORM_SCALE`, for its reason: a seeded query's
    softmax over thousands of keys is otherwise flat and hides the cache's
    precision -, the selection bias small, the embedding N(0, 0.02^2),
    kernels N(0, 1 / fan_in) (the taps [K, d] have a fan-in of K), the taps
    times `SEEDED_TAP_SCALE` and the attention's output projection times
    `SEEDED_ATTN_OUT_SCALE`."""
    if name in ("q_norm", "k_norm"):
        return jnp.full(shape, SEEDED_QK_NORM_SCALE, dtype)
    if name.endswith("_norm"):
        return jnp.ones(shape, dtype)
    if name in ("expert_bias", "embed"):
        return (0.02 * jax.random.normal(key, shape, F32)).astype(dtype)
    scale = {"conv": SEEDED_TAP_SCALE, "o_proj": SEEDED_ATTN_OUT_SCALE}
    return (jax.random.normal(key, shape, F32) * scale.get(name, 1.0)
            / math.sqrt(shape[-2])).astype(dtype)


def named_leaves(cfg: Lfm2Config):
    """([(a leaf's name - its own key; its norm's for a scale, its
    projection's for a kernel -, its shape)], the tree's structure)."""
    return lm_common.named_leaves(
        param_shapes(cfg),
        name=lambda keys: keys[-2] if keys[-1] in ("scale", "kernel")
        else keys[-1])


def init_lfm2_params(key, cfg: Lfm2Config, dtype=F32):
    return lm_common.init_params(key, cfg, dtype, named_leaves=named_leaves,
                                 init_leaf=init_leaf)


# -- layers -------------------------------------------------------------------


def conv_layer(p, cfg: Lfm2Config, x, tail):
    """x [T, d] (normed) through a gated short convolution that enters
    ``tail`` [K - 1, d] -> (the mixer's output [T, d], the tail after the
    last row)."""
    with jax.named_scope("lm.conv.proj"):
        bcx = x @ p["in_proj"]["kernel"]
    with jax.named_scope("lm.conv"):
        y, tail = gated_short_conv(bcx, p["conv"]["kernel"], tail)
    with jax.named_scope("lm.conv.proj"):
        return y @ p["out_proj"]["kernel"], tail


def pack_rows(a, pack: int):
    """Keys or values [T, Hkv, D] -> [Hkv / pack, T, pack * D], as a cache
    holds them: ``pack`` adjacent KV heads side by side in one row,
    row-group major."""
    t, hkv, d = a.shape
    return a.reshape(t, hkv // pack, pack * d).swapaxes(0, 1)


def widen_queries(q, pack: int, group: int):
    """q [T, Hq, D] -> [T, Hq, pack * D]: each head's D numbers in the slot
    its KV head has in a cache row, zeros in the row's other slots."""
    t, hq, d = q.shape
    slots = jnp.eye(pack, dtype=q.dtype)[:, None, :, None]  # [pack,1,pack,1]
    q = q.reshape(t, hq // (pack * group), pack, group, 1, d) * slots
    return q.reshape(t, hq, pack * d)


def own_slots(out, pack: int, group: int):
    """out [T, Hq, pack * D], the weighted sums over whole cache rows ->
    [T, Hq, D]: each head's own slot of them."""
    t, hq, wide = out.shape
    out = out.reshape(t, hq // (pack * group), pack, group, pack, wide // pack)
    return jnp.stack([out[:, :, s, :, s] for s in range(pack)],
                     axis=2).reshape(t, hq, wide // pack)


def attention_layer(p, cfg: Lfm2Config, x, cache, position,
                    visible: Optional[int] = None):
    """x [T, d] at ``position`` onward, causal; its keys and values are
    written into ``cache`` {"k", "v"} [Hkv / pack, max_len, pack * D] first
    (None: a prompt with no cache to leave).

    ``visible`` None and ``position`` 0 (static): a whole prompt, over its
    own keys.  Otherwise against the cache's first ``visible`` rows (static,
    at least position + T): a suffix entering it, or - ``visible`` None at a
    traced position: all of them, under the mask - a decode step, whose one
    row takes the single-pass kernel where there is a TPU (`ops/gqa_cache.py
    cache_attention` routes by the call's shape).
    -> (the layer's output [T, d], the cache, the cache rows the kernel
    fetched: 0 on the XLA route)."""
    t = x.shape[0]
    hq, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    pack, group = cfg.kv_pack, hq // hkv
    positions = position + jnp.arange(t)
    with jax.named_scope("lm.attn.proj"):
        q = rms_norm(p["q_norm"]["scale"],
                     (x @ p["q"]["kernel"]).reshape(t, hq, hd), cfg.norm_eps)
        k = rms_norm(p["k_norm"]["scale"],
                     (x @ p["k"]["kernel"]).reshape(t, hkv, hd), cfg.norm_eps)
        # the softmax scale of a row pack * D wide, made up for in float32
        q = (rotary_half(q.astype(F32), positions, cfg.rope_theta)
             * math.sqrt(pack)).astype(x.dtype)
        k = pack_rows(rotary_half(k, positions, cfg.rope_theta), pack)
        v = pack_rows((x @ p["v"]["kernel"]).reshape(t, hkv, hd), pack)
    with jax.named_scope("lm.attn"):
        if cache is not None:
            cache = {
                "k": lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), position, axis=1),
                "v": lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), position, axis=1)}
        if visible is None and isinstance(position, int):
            if position:
                raise ValueError(f"position {position} needs the cache of "
                                 f"the tokens before it")
            keys, values = k, v
        else:
            keys, values = cache["k"], cache["v"]
        out, fetched = cache_attention(
            widen_queries(q, pack, group), keys, values, limits=positions,
            visible=visible)
        out = own_slots(out, pack, group)
    with jax.named_scope("lm.attn.proj"):
        return (out.reshape(t, hq * hd) @ p["o_proj"]["kernel"], cache,
                fetched)


def moe_layer(p, cfg: Lfm2Config, u):
    """-> (out [T, d] float32, how many of the T * top_k assignments fell on
    experts held here, the experts each token chose [T, top_k])."""
    with jax.named_scope("lm.moe.router"):
        idx, weights = moe.route(
            u, p["router"]["kernel"], p["expert_bias"],
            top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
            denominator_eps=_ROUTER_EPS)
    with jax.named_scope("lm.moe.experts"):
        routed, held = moe.local_expert_sum(
            u, idx, weights, p["experts"]["w1"], p["experts"]["w2"],
            first_expert=cfg.first_local_expert, activation="silu")
    return routed, held, idx


def _mix(lp, cfg: Lfm2Config, x, layer, position, visible, *, kind: str):
    """A layer's first half -> (x + Mixer(RMSNorm(x)), the layer's state,
    the cache rows an attention layer's kernel fetched)."""
    h = rms_norm(lp["operator_norm"]["scale"], x, cfg.norm_eps)
    if kind == "conv":
        out, tail = conv_layer(lp["mixer"], cfg, h, layer["tail"])
        return (x + out, {"tail": tail.astype(layer["tail"].dtype)},
                jnp.zeros((), jnp.int32))
    out, layer, fetched = attention_layer(lp["mixer"], cfg, h, layer,
                                          position, visible)
    return x + out, layer, fetched


def feed_forward(lp, cfg: Lfm2Config, x):
    """A layer's second half -> (x + FFN(RMSNorm(x)), held assignments or
    None, the experts chosen [T, top_k] or None)."""
    u = rms_norm(lp["ffn_norm"]["scale"], x, cfg.norm_eps)
    if "router" in lp["ffn"]:
        out, held, idx = moe_layer(lp["ffn"], cfg, u)
        return x + out.astype(x.dtype), held, idx
    with jax.named_scope("lm.mlp"):
        return x + gated_mlp(lp["ffn"], u), None, None


# -- prefill, step, generation ------------------------------------------------


def _empty_tail(cfg: Lfm2Config, dtype):
    return {"tail": jnp.zeros((cfg.conv_L_cache - 1, cfg.hidden_size), dtype)}


def empty_state(cfg: Lfm2Config, max_len: int, dtype):
    """The state with nothing in it and room for ``max_len`` positions."""
    pack = cfg.kv_pack
    rows = jnp.zeros((cfg.num_key_value_heads // pack, max_len,
                      pack * cfg.head_dim), jnp.dtype(cfg.cache_dtype or dtype))
    return {"layers": [_empty_tail(cfg, dtype) if kind == "conv"
                       else {"k": rows, "v": rows} for kind in cfg.kinds],
            "experts": jnp.zeros((cfg.n_expert_layers, max_len,
                                  cfg.num_experts_per_tok), jnp.int32)}


def _entered(cfg: Lfm2Config, state):
    """The state as a call enters it: as handed in - or, the control, with
    its tails not carried."""
    if cfg.carry_conv_tails:
        return state
    return dict(state, layers=[
        jax.tree.map(jnp.zeros_like, layer) if "tail" in layer else layer
        for layer in state["layers"]])


# `models/deepseek_v3.py`'s stack, prefill and decode with this module's
# mixers AND feed-forward in: a PROMPT from position 0 starts every
# convolution from a zero tail and attends over its own keys; a SUFFIX
# entering a state starts from each conv layer's tail and attends against the
# caches' first ``position + T`` rows; a DECODE STEP is a conv layer's one row
# from its tail and an attention layer's one query row against its cache (on
# a TPU the rows written so far in one pass, else the whole cache under its
# mask).  The counters are `COUNTERS` [7].
STACK = dsv3.Stack(
    COUNTERS, empty_state, layers="layers",
    mixers=lambda cfg: [functools.partial(_mix, kind=kind)
                        for kind in cfg.kinds],
    feed_forward=feed_forward)


def prefill(params, cfg: Lfm2Config, ids, *, max_len: int, state=None,
            position: int = 0, counters=None):
    """`models/language_model.py`'s ``prefill`` and ``prefill_from`` both:
    `models/deepseek_v3.py prefill` over `STACK`, the state entered as
    `_entered` has it."""
    return dsv3.prefill(
        params, cfg, ids, max_len=max_len, position=position,
        counters=counters, stack=STACK,
        state=None if state is None else _entered(cfg, state))


def decode(params, cfg: Lfm2Config, logits, state, counters, *,
           position: int, new_tokens: int):
    """`models/deepseek_v3.py decode` over `STACK`: greedy decoding through
    the state, on the device from first token to last."""
    return dsv3.decode(params, cfg, logits, _entered(cfg, state), counters,
                       position=position, new_tokens=new_tokens, stack=STACK)


def generate(params, cfg: Lfm2Config, ids, new_tokens: int):
    """Prefill, then greedy decoding -> (new ids, the logits they were
    chosen from, the counters, the experts every position chose
    [E layers, T + new_tokens, top_k])."""
    return lm_common.generate(cfg.language_model(), params, ids,
                              new_tokens)[:4]
