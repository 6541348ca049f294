"""DeepSeek-V3-style causal language model (``model_type: deepseek_v3``, as
Kanana-2-30B-A3B publishes it): multi-head latent attention (`ops/mla.py`)
over a cache of one 512-wide latent and one 64-wide rotated key a position,
one leading dense layer and then sparse-expert layers of gated-SiLU experts
(`ops/moe.py`), with prefill (of a whole prompt, or of a suffix through the
cache its prefix left), a one-token step through the cache, and a greedy
decode loop that stays on the device.

    x <- x + Attn(RMSNorm(x));  x <- x + FFN(RMSNorm(x))      eps 1e-6
    logits = RMSNorm(x) W_head                 float32, untied embedding

``Attn``, with ``h`` the normed input, per position t:
``q_t = h_t W_q`` -> H heads of ``[q_nope 128 | q_pe 64]`` (no query
latent: ``q_lora_rank`` null); ``[c_t | k_pe_t] = h_t W_kva`` (512 | 64);
``c_t <- RMSNorm(c_t)``; rotary embedding (pairs ``(2 i, 2 i + 1)``, the
64-wide part only, absolute position) on every head's ``q_pe`` and on the
one shared ``k_pe_t``; per head ``k_nope = c W_UK``, ``v = c W_UV``;
``score = (q_nope . k_nope + q_pe . k_pe) / sqrt(192)``; causal softmax in
float32; ``x <- x + concat_h(o) W_o``.  A PROMPT takes the materialised
form by query block, a SUFFIX entering a cache and a DECODE STEP the
absorbed form (``q_nope W_UK^T`` against the cached latents themselves, the
attended latents through ``W_UV``): the same numbers, nothing expanded.
Under ``mla_use_nope`` (Kimi Linear's full layers: `models/kimi_linear.py`
calls `attention_layer` with its own configuration) nothing is rotated and
the 64-wide parts are used as projected.
``kv_b_proj`` [512, H * (128 + 128)] is held as its two per-head halves,
``k_up`` [H, 128, 512] and ``v_up`` [H, 512, 128] - the layout the absorbed
form multiplies by, so a decode step slices no weight.

``FFN``: layers below ``first_k_dense_replace`` a gated MLP
``(silu(h G) * h U) D``; the others a router over ALL experts in float32
(`ops/moe.py route`: sigmoid scores, the top_k largest of score + bias,
weights normalised over the chosen and scaled), the experts HELD HERE each
``(silu(h G_i) * h U_i) D_i`` (gate | up fused, `ops/moe.py`), plus the
``n_shared_experts`` shared experts as ONE gated MLP of their summed width.

Expert parallelism is in the configuration, as in `models/nemotron_h.py`:
``n_local_experts`` of ``n_routed_experts`` are held (``first_local_expert``
onward), the router keeps its full width, and what absent experts would add
is left out.  The vocabulary may be a slice: ids, logits and the greedy
choice are then over the slice.

State across calls: per layer the latent cache ``c`` [max_len, 512] and
``k_pe`` [max_len, 64] - 576 numbers a position, written before they are
read; rows not written yet are never read into a result - and, for the
record, the experts every position chose in every expert layer
[E layers, max_len, top_k].  One sequence at a time (no batch axis).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import mla, moe
from . import lm_common
from .language_model import LanguageModel
from .lm_common import F32, gated_mlp, rms_norm
from .weights import params_nbytes

# counters the generation returns with its ids; ``tokens_reused``: of the
# positions the cache covers after prefill, those a cache handed in already
# covered - entered, not computed in this request; ``state_bytes``: the
# latent cache of every layer; ``cache_rows_fetched``: the cache rows the
# decode steps' attention fetched where it is the single-pass kernel
# (`ops/mla.py streamed_attention`), summed over steps and layers - 0 on the
# XLA route, which reads every row of the cache, twice, under its mask
COUNTERS = ("tokens_prefilled", "tokens_reused", "tokens_decoded",
            "expert_assignments", "expert_assignments_held", "state_bytes",
            "cache_rows_fetched")


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    num_hidden_layers: int = 48
    vocab_size: int = 128256
    hidden_size: int = 2048
    rms_norm_eps: float = 1e-6
    # attention
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1000000.0
    # the 64-wide part of queries and keys is left as projected: no rotary
    # embedding, no position in the attention at all (``mla_use_nope``)
    mla_use_nope: bool = False
    # feed-forward
    first_k_dense_replace: int = 1
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    n_shared_experts: int = 2
    n_routed_experts: int = 128  # the router's width
    n_local_experts: int = 128  # held here ...
    first_local_expert: int = 0  # ... from this one on
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.448
    # a prompt's length is a multiple of this (so is what a snapshot of its
    # prefix covers)
    prefill_block: int = 128
    # the latent cache's dtype; None: the parameters'
    cache_dtype: Optional[str] = None

    def __post_init__(self):
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotary part is made of pairs")
        if (self.first_local_expert + self.n_local_experts
                > self.n_routed_experts):
            raise ValueError("the held experts lie outside the router")

    @property
    def n_expert_layers(self) -> int:
        return max(0, self.num_hidden_layers - self.first_k_dense_replace)

    @property
    def softmax_scale(self) -> float:
        return 1.0 / math.sqrt(self.qk_nope_head_dim + self.qk_rope_head_dim)

    def language_model(self) -> LanguageModel:
        """This model as the rewrite stage takes it: ids of words; a suffix
        can enter the cache its prefix left."""
        return LanguageModel(self, prefill, decode, COUNTERS,
                             self.prefill_block, self.vocab_size,
                             prefill_from=prefill)


def deepseek_v3_config_from_json(d: Dict[str, Any]) -> DeepseekV3Config:
    """From the published config.json keys, plus what a cut adds to them:
    ``n_routed_experts`` counts the experts HELD and ``expert_parallel``
    (``{"chips": n, "index": i}``) says of how many shares this is which, so
    the router is ``chips`` times as wide; ``num_hidden_layers`` layers from
    the first are served; ``prefill_block`` and ``cache_dtype`` are ours."""
    lm_common.refuse_unbuilt(d, {
        "model_type": "deepseek_v3", "q_lora_rank": None,
        "rope_scaling": None, "rope_interleave": True, "n_group": 1,
        "topk_group": 1, "scoring_func": "sigmoid", "norm_topk_prob": True,
        "hidden_act": "silu", "attention_bias": False,
        "tie_word_embeddings": False, "moe_layer_freq": 1})
    return DeepseekV3Config(**{
        **lm_common.config_fields(DeepseekV3Config, d),
        **lm_common.expert_share(d, "n_routed_experts")})


# -- parameters ---------------------------------------------------------------


def _gated_mlp_shapes(d: int, f: int) -> Dict[str, Any]:
    return {"gate_up": {"kernel": (d, 2 * f)}, "down": {"kernel": (f, d)}}


def layer_shapes(cfg: DeepseekV3Config, dense: bool) -> Dict[str, Any]:
    d, h = cfg.hidden_size, cfg.num_attention_heads
    lat, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    attn = {
        "q": {"kernel": (d, h * (cfg.qk_nope_head_dim + rope))},
        "kv_a": {"kernel": (d, lat + rope)},
        "kv_norm": {"scale": (lat,)},
        "k_up": (h, cfg.qk_nope_head_dim, lat),
        "v_up": (h, lat, cfg.v_head_dim),
        "o_proj": {"kernel": (h * cfg.v_head_dim, d)},
    }
    if dense:
        ffn = _gated_mlp_shapes(d, cfg.intermediate_size)
    else:
        f = cfg.moe_intermediate_size
        ffn = {
            "router": {"kernel": (d, cfg.n_routed_experts)},
            "e_score_correction_bias": (cfg.n_routed_experts,),
            "experts": {"w1": (cfg.n_local_experts, d, 2 * f),
                        "w2": (cfg.n_local_experts, f, d)},
            "shared": _gated_mlp_shapes(d, cfg.n_shared_experts * f),
        }
    return {"attn_norm": {"scale": (d,)}, "attn": attn,
            "ffn_norm": {"scale": (d,)}, "ffn": ffn}


def param_shapes(cfg: DeepseekV3Config) -> Dict[str, Any]:
    """The parameter tree with a shape tuple at every leaf.  gate | up are
    held as one fused kernel and ``kv_b_proj`` as its per-head halves: the
    same parameters and arithmetic."""
    d = cfg.hidden_size
    return {
        "embed": (cfg.vocab_size, d),
        "layers": [layer_shapes(cfg, i < cfg.first_k_dense_replace)
                   for i in range(cfg.num_hidden_layers)],
        "final_norm": {"scale": (d,)},
        "head": {"kernel": (d, cfg.vocab_size)},
    }


def init_leaf(key, name: str, shape, cfg: DeepseekV3Config, dtype):
    """One leaf by its name: norm scales ones, the selection bias small,
    the embedding N(0, 0.02^2), kernels N(0, 1 / fan_in) (``k_up``
    [H, 128, 512] maps FROM the latent: its fan-in is its last axis)."""
    if name == "scale":
        return jnp.ones(shape, dtype)
    if name in ("e_score_correction_bias", "embed"):
        return (0.02 * jax.random.normal(key, shape, F32)).astype(dtype)
    fan_in = shape[-1] if name == "k_up" else shape[-2]
    return (jax.random.normal(key, shape, F32) / math.sqrt(fan_in)
            ).astype(dtype)


def named_leaves(cfg: DeepseekV3Config):
    """([(a leaf's own name, its shape)], the tree's structure)."""
    return lm_common.named_leaves(param_shapes(cfg))


def init_deepseek_v3_params(key, cfg: DeepseekV3Config, dtype=F32):
    return lm_common.init_params(key, cfg, dtype, named_leaves=named_leaves,
                                 init_leaf=init_leaf)


# -- layers -------------------------------------------------------------------


@jax.named_scope("lm.mla.proj")
def _queries_and_latents(p, cfg: DeepseekV3Config, x, positions):
    """x [T, d] -> q_nope [T, H, 128], q_pe [T, H, 64], the normalised
    latents c [T, 512] and the shared k_pe [T, 64]; q_pe and k_pe rotated
    unless the configuration says ``mla_use_nope``."""
    t = x.shape[0]
    q = (x @ p["q"]["kernel"]).reshape(t, cfg.num_attention_heads, -1)
    q_nope, q_pe = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    c, k_pe = jnp.split(x @ p["kv_a"]["kernel"], [cfg.kv_lora_rank], axis=-1)
    c = rms_norm(p["kv_norm"]["scale"], c, cfg.rms_norm_eps)
    if cfg.mla_use_nope:
        return q_nope, q_pe, c, k_pe
    return (q_nope, mla.rotary_interleaved(q_pe, positions, cfg.rope_theta),
            c, mla.rotary_interleaved(k_pe, positions, cfg.rope_theta))


def attention_layer(p, cfg: DeepseekV3Config, x, cache, position,
                    visible: Optional[int] = None):
    """x [T, d] at ``position`` onward; its latents are written into
    ``cache`` {"c", "k_pe"} first (None: a prompt with no cache to leave).

    ``visible`` None and ``position`` 0 (static): a whole prompt, the
    materialised form over its own keys.  Otherwise the absorbed form
    against the cache (`ops/mla.py cache_attention`: a decode step on a TPU
    in one pass over the rows written so far; else the XLA form over the
    cache's first ``visible`` rows - static, at least position + T - or,
    ``visible`` None, all of them under the mask).
    -> (the layer's output [T, d], the cache, the cache rows the single-pass
    kernel fetched: 0 on every other route)."""
    t = x.shape[0]
    positions = position + jnp.arange(t)
    q_nope, q_pe, c, k_pe = _queries_and_latents(p, cfg, x, positions)
    materialised = visible is None and isinstance(position, int)
    fetched = jnp.zeros((), jnp.int32)
    if materialised and position:
        raise ValueError(f"position {position} needs the cache of the "
                         f"tokens before it")
    if cache is not None:
        with jax.named_scope("lm.mla.attn"):
            cache = {
                "c": lax.dynamic_update_slice_in_dim(
                    cache["c"], c.astype(cache["c"].dtype), position, axis=0),
                "k_pe": lax.dynamic_update_slice_in_dim(
                    cache["k_pe"], k_pe.astype(cache["k_pe"].dtype), position,
                    axis=0)}
    if materialised:
        with jax.named_scope("lm.mla.proj"):
            k_nope = jnp.einsum("sc,hdc->shd", c, p["k_up"])
            v = jnp.einsum("sc,hcd->shd", c, p["v_up"])
        with jax.named_scope("lm.mla.attn"):
            out = mla.materialised_attention(
                q_nope, q_pe, k_nope, k_pe, v, scale=cfg.softmax_scale)
    else:
        with jax.named_scope("lm.mla.proj"):
            q_lat = jnp.einsum("thd,hdc->thc", q_nope, p["k_up"])
        with jax.named_scope("lm.mla.attn"):
            attended, fetched = mla.cache_attention(
                q_lat, q_pe, cache["c"], cache["k_pe"], position,
                scale=cfg.softmax_scale, visible=visible)
        with jax.named_scope("lm.mla.proj"):
            out = jnp.einsum("thc,hcd->thd", attended, p["v_up"])
    with jax.named_scope("lm.mla.proj"):
        return out.reshape(t, -1) @ p["o_proj"]["kernel"], cache, fetched


def moe_layer(p, cfg: DeepseekV3Config, u):
    """-> (out [T, d], how many of the T * top_k assignments fell on experts
    held here, the experts each token chose [T, top_k])."""
    with jax.named_scope("lm.moe.router"):
        idx, weights = moe.route(
            u, p["router"]["kernel"], p["e_score_correction_bias"],
            top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor)
    with jax.named_scope("lm.moe.experts"):
        routed, held = moe.local_expert_sum(
            u, idx, weights, p["experts"]["w1"], p["experts"]["w2"],
            first_expert=cfg.first_local_expert, activation="silu")
    with jax.named_scope("lm.moe.shared"):
        shared = gated_mlp(p["shared"], u)
    return routed.astype(u.dtype) + shared, held, idx


def _attend(lp, cfg: DeepseekV3Config, x, cache, position, visible):
    """A layer's first half -> (x + Attn(RMSNorm(x)), the cache, the cache
    rows its attention fetched)."""
    out, cache, fetched = attention_layer(
        lp["attn"], cfg, rms_norm(lp["attn_norm"]["scale"], x,
                                  cfg.rms_norm_eps), cache, position, visible)
    return x + out, cache, fetched


def feed_forward(lp, cfg: DeepseekV3Config, x):
    """A layer's second half -> (x + FFN(RMSNorm(x)), held assignments or
    None, the experts chosen [T, top_k] or None)."""
    u = rms_norm(lp["ffn_norm"]["scale"], x, cfg.rms_norm_eps)
    if "router" in lp["ffn"]:
        out, held, idx = moe_layer(lp["ffn"], cfg, u)
        return x + out, held, idx
    with jax.named_scope("lm.mlp"):
        return x + gated_mlp(lp["ffn"], u), None, None


def head(params, cfg: DeepseekV3Config, x):
    """x [T, d] -> float32 logits [T, V] over the held vocabulary."""
    return lm_common.head(params, x, cfg.rms_norm_eps)


# -- prefill, step, generation ------------------------------------------------


def empty_state(cfg: DeepseekV3Config, max_len: int, dtype):
    """The state with nothing in it and room for ``max_len`` positions."""
    dtype = jnp.dtype(cfg.cache_dtype or dtype)
    layer = {"c": jnp.zeros((max_len, cfg.kv_lora_rank), dtype),
             "k_pe": jnp.zeros((max_len, cfg.qk_rope_head_dim), dtype)}
    return {"cache": [layer] * cfg.num_hidden_layers,
            "experts": jnp.zeros((cfg.n_expert_layers, max_len,
                                  cfg.num_experts_per_tok), jnp.int32)}


class Stack(NamedTuple):
    """What `prefill` and `decode` drive: this module's stack (`STACK`), or a
    sibling's that keeps the record and these counters and puts mixers of
    its own in (`models/kimi_linear.py`) - and a feed-forward half of its
    own too (`models/lfm2.py`)."""
    counters: Tuple[str, ...]  # `COUNTERS`, more names after them
    empty_state: Callable  # (cfg, max_len, dtype) -> {layers: [...], "experts"}
    layers: str = "cache"  # the state's key of what the mixers carry
    # cfg -> a layer's first half each, of `_attend`'s signature
    mixers: Callable = lambda cfg: itertools.repeat(_attend)
    # (cfg, t) -> what else a prefill of t tokens moves the counters by
    prefilled: Callable = lambda cfg, t: {}
    # a layer's second half, of `feed_forward`'s signature
    feed_forward: Callable = feed_forward


STACK = Stack(COUNTERS, empty_state)


def _forward(params, cfg: DeepseekV3Config, ids, state, position, visible,
             stack: Stack = STACK):
    """The stack over ids [T] at ``position`` onward through the state ->
    (hidden [T, d], the new state, held expert assignments, the cache rows
    the layers' attention fetched)."""
    x = params["embed"][ids]
    layers, chosen = [], []
    held = fetched = jnp.zeros((), jnp.int32)
    for lp, layer, mix in zip(params["layers"], state[stack.layers],
                              stack.mixers(cfg)):
        x, layer, rows = mix(lp, cfg, x, layer, position, visible)
        x, n, idx = stack.feed_forward(lp, cfg, x)
        layers.append(layer)
        fetched = fetched + rows
        if idx is not None:
            held = held + n.astype(jnp.int32)
            chosen.append(idx)
    experts = state["experts"]
    if chosen:
        experts = lax.dynamic_update_slice_in_dim(
            experts, jnp.stack(chosen), position, axis=1)
    return x, {stack.layers: layers, "experts": experts}, held, fetched


def assignments(cfg: DeepseekV3Config, tokens: int) -> int:
    return tokens * cfg.n_expert_layers * cfg.num_experts_per_tok


def prefill(params, cfg: DeepseekV3Config, ids, *, max_len: int, state=None,
            position: int = 0, counters=None, stack: Stack = STACK):
    """ids [T] (T a multiple of ``prefill_block``) at ``position`` onward,
    computed in full -> (float32 logits after the last token [V], the
    state, the `COUNTERS` so far [7] int32, the experts the T tokens chose
    [E layers, T, top_k]).

    `models/language_model.py`'s ``prefill`` and ``prefill_from`` both: a
    prompt from position 0 takes the materialised form, a suffix entering
    ``state`` the absorbed form against the cache's first ``position + T``
    rows (of ``tokens_prefilled``, ``tokens_reused`` = ``position``)."""
    t = ids.shape[0]
    visible = None if state is None else position + t
    state, counters = lm_common.enter_state(
        state, counters, stack.counters, position=position,
        empty=lambda: stack.empty_state(cfg, max_len, params["embed"].dtype),
        room=lambda state: state["experts"].shape[1],
        needed=max(max_len, position + t))
    x, state, held, _ = _forward(params, cfg, ids, state, position, visible,
                                 stack)
    counters = lm_common.count(
        stack.counters, counters, put={
            "tokens_reused": position,
            "state_bytes": params_nbytes(state[stack.layers])},
        tokens_prefilled=t, expert_assignments=assignments(cfg, t),
        expert_assignments_held=held, **stack.prefilled(cfg, t))
    chosen = state["experts"][:, position:position + t]
    return head(params, cfg, x[-1:])[0], state, counters, chosen


def decode(params, cfg: DeepseekV3Config, logits, state, counters, *,
           position: int, new_tokens: int, stack: Stack = STACK):
    """Greedy decoding through the state, on the device from first token to
    last: ``new_tokens`` times the largest logit is taken and the token goes
    through the stack, by the absorbed form against the cache (on a TPU
    the rows written so far in one pass, else the whole cache under its
    mask).  ``logits`` follow the token at ``position - 1``.
    -> (ids [new_tokens] int32, the float32 logits each was chosen from
    [new_tokens, V], the experts EVERY position so far chose
    [E layers, max_len, top_k] - the prompt's, a snapshot's too -, the
    state, the counters)."""
    def step(token, state, at):
        x, state, held, fetched = _forward(params, cfg, token, state, at,
                                           None, stack)
        return head(params, cfg, x)[0], state, dict(
            tokens_decoded=1, expert_assignments=assignments(cfg, 1),
            expert_assignments_held=held, cache_rows_fetched=fetched), None

    ids, chosen_from, _, state, counters = lm_common.greedy_decode(
        step, logits, state, counters, names=stack.counters,
        position=position, new_tokens=new_tokens)
    return ids, chosen_from, state["experts"], state, counters


def generate(params, cfg: DeepseekV3Config, ids, new_tokens: int):
    """Prefill, then greedy decoding -> (new ids, the logits they were
    chosen from, the counters, the experts every position chose
    [E layers, T + new_tokens, top_k])."""
    return lm_common.generate(cfg.language_model(), params, ids,
                              new_tokens)[:4]


# -- the routers' balance, for seeded weights -----------------------------------


@functools.partial(jax.jit, static_argnames=("cfg", "rounds"))
def _balancing_layer(lp, x, *, cfg: DeepseekV3Config, rounds: int):
    """One layer of the calibration pass -> (its output, an expert layer's
    balanced bias or None).  One compiled program a kind of layer."""
    x, _, _ = _attend(lp, cfg, x, None, 0, None)
    return balanced_feed_forward(lp, cfg, x, rounds)


def balanced_feed_forward(lp, cfg, x, rounds: int):
    """A layer's second half in the calibration pass, x [T, d] after its
    mixer -> (the layer's output with its router balanced over these T
    tokens, the balanced bias; None where the layer has no router)."""
    bias = None
    if "router" in lp["ffn"]:
        u = rms_norm(lp["ffn_norm"]["scale"], x, cfg.rms_norm_eps)
        scores = jax.nn.sigmoid(jnp.dot(
            u.astype(F32), lp["ffn"]["router"]["kernel"].astype(F32),
            precision=lax.Precision.HIGHEST))
        bias = moe.balanced_bias(
            scores, top_k=cfg.num_experts_per_tok, rounds=rounds).astype(
                lp["ffn"]["e_score_correction_bias"].dtype)
        lp = dict(lp, ffn=dict(lp["ffn"], e_score_correction_bias=bias))
    x, _, _ = feed_forward(lp, cfg, x)
    return x, bias


def balanced_selection_bias(params, cfg: DeepseekV3Config, ids, *,
                            rounds: int = 300):
    """Every expert layer's ``e_score_correction_bias`` as load balancing
    leaves it: `models/nemotron_h.py balanced_selection_bias` for this
    stack - the same fit (`ops/moe.py balanced_bias`), layer after layer
    over the calibration sequence ``ids`` [T], each balanced before the
    next sees its output.  Returns one [n_routed_experts] bias an expert
    layer, in the stored dtype."""
    return lm_common.balanced_biases(params["embed"][ids], (
        functools.partial(_balancing_layer, lp, cfg=cfg, rounds=rounds)
        for lp in params["layers"]))
