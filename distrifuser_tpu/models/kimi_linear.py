"""Kimi-Linear causal language model (``model_type: kimi_linear``, as
Kimi-Linear-48B-A3B publishes it): two kinds of layer in one stack - Kimi
Delta Attention (`ops/kda.py`: linear attention by the delta rule with a
decay a key channel, a matrix state a head) and, one layer in four,
multi-head latent attention WITHOUT a position embedding (`ops/mla.py`, as
`models/deepseek_v3.py attention_layer` computes it under ``mla_use_nope``) -
over one leading dense layer and then sparse-expert layers of gated-SiLU
experts (`ops/moe.py`, `models/deepseek_v3.py feed_forward`), with prefill
(of a whole prompt, or of a suffix through the state its prefix left), a
one-token step through the state, and a greedy decode loop that stays on the
device.

    x <- x + Mixer(RMSNorm(x));  x <- x + FFN(RMSNorm(x))     eps 1e-5
    logits = RMSNorm(x) W_head                 float32, untied embedding

``Mixer`` of a KDA layer (``linear_attn_config.kda_layers``, 1-indexed),
with ``h`` the normed input, H heads of K = V = ``head_dim`` channels:

    q, k, v = silu(conv4(h W_q)), silu(conv4(h W_k)), silu(conv4(h W_v))
        a depthwise causal convolution of ``short_conv_kernel_size`` taps,
        no bias, each; q and k L2-normalised a head, q scaled by K^-1/2
    g = -exp(A_log)[head] * softplus(h W_fa W_fb + dt_bias)   [H, K], <= 0
    beta = sigmoid(h W_b)                                     [H]
    S <- (I - beta k k^T) Diag(exp g) S + beta k v^T;  o = S^T q   (float32)
    y = (RMSNorm_V(o) * w_norm * sigmoid(h W_ga W_gb)) W_o

``W_q | W_k | W_v`` are held as ONE kernel ``qkv`` [d, 3 H K] with one
convolution kernel [taps, 3 H K] (and so one tail of taps - 1 rows: the three
tails side by side), ``W_fa | W_ga | W_b`` as one kernel ``gates_in``
[d, 2 r + H] (r = ``head_dim``): the same parameters and arithmetic, three
and three matmuls a decode step fewer.  A PROMPT and a SUFFIX entering a
state take the chunked form (``kda_chunk`` rows a chunk), a DECODE STEP the
recurrence itself.

``Mixer`` of a full-attention layer (``full_attn_layers``): latent
attention at DeepSeek-V3's shapes - see `models/deepseek_v3.py`, whose
layer this is, with the 64-wide part of queries and keys left as projected:
position reaches the model through the KDA layers' recurrence alone.

``FFN``: `models/deepseek_v3.py feed_forward` - below
``first_k_dense_replace`` a gated MLP, then a sigmoid router over ALL
``num_experts`` in float32, the experts HELD HERE, one shared expert.

Expert parallelism is in the configuration, as in its siblings:
``n_local_experts`` of ``num_experts`` are held (``first_local_expert``
onward), the router keeps its full width, and what absent experts would add
is left out.  The vocabulary may be a slice.

State across calls, two kinds in one carry: a KDA layer's is BOUNDED - the
matrix state ``s`` [H, K, V] in ``state_dtype`` (float32) and the
convolution's tail ``conv`` [taps - 1, 3 H K] (the projections' own
outputs, in their dtype) - and is a VALUE: a suffix's first chunk starts
from it; a full layer's GROWS - the latent cache ``c`` [max_len, 512],
``k_pe`` [max_len, 64], rows written before they are read.  And, for the
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

from ..ops import kda, ssm
from . import deepseek_v3 as dsv3
from . import lm_common
from .language_model import LanguageModel
from .lm_common import F32

# counters the generation returns with its ids: `models/deepseek_v3.py
# COUNTERS` under their names there (``state_bytes``: the whole decode state,
# the KDA layers' matrix states and tails and the full layers' latent
# caches), and ``kda_chunks``: the chunks the chunked form ran, summed over
# KDA layers and, like ``tokens_prefilled``, over everything the state covers
COUNTERS = dsv3.COUNTERS + ("kda_chunks",)
_L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    num_hidden_layers: int = 27
    vocab_size: int = 163840
    hidden_size: int = 2304
    rms_norm_eps: float = 1e-5
    # linear_attn_config: which layers (1-indexed) are of which kind, and
    # the KDA layers' heads
    kda_layers: Tuple[int, ...] = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15,
                                   17, 18, 19, 21, 22, 23, 25, 26)
    full_attn_layers: Tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    # latent attention
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    mla_use_nope: bool = True
    # feed-forward
    first_k_dense_replace: int = 1
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_shared_experts: int = 1
    num_experts: int = 256  # the router's width
    n_local_experts: int = 256  # held here ...
    first_local_expert: int = 0  # ... from this one on
    num_experts_per_token: int = 8
    routed_scaling_factor: float = 2.446
    # a prompt's length is a multiple of this (so is what a snapshot of its
    # prefix covers), itself whole chunks of the chunked form
    prefill_block: int = 128
    kda_chunk: int = 64
    # the matrix states' dtype, and the latent caches' (None: the parameters')
    state_dtype: str = "float32"
    cache_dtype: Optional[str] = None

    def __post_init__(self):
        if self.prefill_block % self.kda_chunk:
            raise ValueError("a prefill block is whole chunks of the KDA form")
        if (self.first_local_expert + self.n_local_experts
                > self.num_experts):
            raise ValueError("the held experts lie outside the router")
        kinds = set(self.kda_layers) | set(self.full_attn_layers)
        if not kinds >= set(range(1, self.num_hidden_layers + 1)):
            raise ValueError("kda_layers and full_attn_layers name no kind "
                             "for some layer of the stack")

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The kind of every layer served, first to last."""
        return tuple("kda" if i in self.kda_layers else "mla"
                     for i in range(1, self.num_hidden_layers + 1))

    @property
    def n_expert_layers(self) -> int:
        return max(0, self.num_hidden_layers - self.first_k_dense_replace)

    @property
    def softmax_scale(self) -> float:
        return 1.0 / math.sqrt(self.qk_nope_head_dim + self.qk_rope_head_dim)

    # the names `models/deepseek_v3.py`'s layers read these sizes by
    n_routed_experts = property(lambda self: self.num_experts)
    num_experts_per_tok = property(lambda self: self.num_experts_per_token)
    n_shared_experts = property(lambda self: self.num_shared_experts)

    def language_model(self) -> LanguageModel:
        """This model as the rewrite stage takes it: ids of words; a suffix
        can enter the state its prefix left."""
        return LanguageModel(self, prefill, decode, COUNTERS,
                             self.prefill_block, self.vocab_size,
                             prefill_from=prefill)


def kimi_linear_config_from_json(d: Dict[str, Any]) -> KimiLinearConfig:
    """From the published config.json keys, plus what a cut adds to them:
    ``num_experts`` counts the experts HELD and ``expert_parallel``
    (``{"chips": n, "index": i}``) says of how many shares this is which, so
    the router is ``chips`` times as wide; ``num_hidden_layers`` layers from
    the first are served, their kinds read from ``linear_attn_config``'s
    published lists as far as that; ``prefill_block``, ``kda_chunk``,
    ``state_dtype`` and ``cache_dtype`` are ours."""
    lm_common.refuse_unbuilt(d, {
        "model_type": "kimi_linear", "q_lora_rank": None,
        "rope_scaling": None, "num_expert_group": 1, "topk_group": 1,
        "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
        "hidden_act": "silu", "tie_word_embeddings": False,
        "moe_layer_freq": 1, "num_nextn_predict_layers": 0})
    linear = d["linear_attn_config"]
    return KimiLinearConfig(
        kda_layers=tuple(linear["kda_layers"]),
        full_attn_layers=tuple(linear["full_attn_layers"]),
        kda_num_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        short_conv_kernel_size=linear["short_conv_kernel_size"],
        **{**lm_common.config_fields(KimiLinearConfig, d),
           **lm_common.expert_share(d, "num_experts")})


# -- parameters ---------------------------------------------------------------


def _kda_shapes(cfg: KimiLinearConfig) -> Dict[str, Any]:
    d, h, r = cfg.hidden_size, cfg.kda_num_heads, cfg.kda_head_dim
    wide = h * cfg.kda_head_dim  # keys and values are as wide
    return {
        "qkv": {"kernel": (d, 3 * wide)},
        "conv": {"kernel": (cfg.short_conv_kernel_size, 3 * wide)},
        "gates_in": {"kernel": (d, 2 * r + h)},  # W_fa | W_ga | W_b
        "f_b": {"kernel": (r, wide)},
        "A_log": (h,),
        "dt_bias": (wide,),
        "g_b": {"kernel": (r, wide)},
        "o_norm": {"scale": (cfg.kda_head_dim,)},
        "o_proj": {"kernel": (wide, d)},
    }


def param_shapes(cfg: KimiLinearConfig) -> Dict[str, Any]:
    """The parameter tree with a shape tuple at every leaf: `models/
    deepseek_v3.py`'s, a KDA layer's mixer in the place of its ``attn``."""
    layers = []
    for i, kind in enumerate(cfg.kinds):
        layer = dsv3.layer_shapes(cfg, i < cfg.first_k_dense_replace)
        if kind == "kda":
            layer["attn"] = _kda_shapes(cfg)
        layers.append(layer)
    d = cfg.hidden_size
    return {"embed": (cfg.vocab_size, d), "layers": layers,
            "final_norm": {"scale": (d,)},
            "head": {"kernel": (d, cfg.vocab_size)}}


def init_leaf(key, name: str, shape, cfg: KimiLinearConfig, dtype):
    """One leaf by its name: the gate's two parameters as published -
    ``A_log`` the log of a rate uniform in [1, 16], ``dt_bias`` the inverse
    softplus of a step log-uniform in [0.001, 0.1] - and every other by
    `models/deepseek_v3.py init_leaf` (a convolution kernel [taps, C] has a
    fan-in of its taps)."""
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0)
                       ).astype(dtype)
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3),
                                        math.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return dsv3.init_leaf(key, name, shape, cfg, dtype)


def named_leaves(cfg: KimiLinearConfig):
    """([(a leaf's own name, its shape)], the tree's structure)."""
    return lm_common.named_leaves(param_shapes(cfg))


def init_kimi_linear_params(key, cfg: KimiLinearConfig, dtype=F32):
    return lm_common.init_params(key, cfg, dtype, named_leaves=named_leaves,
                                 init_leaf=init_leaf)


# -- layers -------------------------------------------------------------------


def _l2_normalised(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                         + _L2_EPS)


def kda_layer(p, cfg: KimiLinearConfig, x, state):
    """x [T, d] (normed) through a KDA mixer that enters ``state`` {"s",
    "conv"} -> (the mixer's output [T, d], the state after the last token).
    One token goes through the recurrence, more through the chunked form."""
    t = x.shape[0]
    h, dk, r = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_head_dim
    with jax.named_scope("lm.kda.proj"):
        qkv = x @ p["qkv"]["kernel"]
        f_in, gate_in, b = jnp.split(x @ p["gates_in"]["kernel"], [r, 2 * r],
                                     axis=-1)
        f = f_in @ p["f_b"]["kernel"]
        gate = gate_in @ p["g_b"]["kernel"]
    with jax.named_scope("lm.kda.conv"):
        conv, tail = ssm.causal_conv1d(
            qkv, p["conv"]["kernel"], jnp.zeros((qkv.shape[-1],), F32),
            state["conv"])
        q, k, v = jnp.split(jax.nn.silu(conv).reshape(t, 3 * h, dk), 3, axis=1)
        q, k = _l2_normalised(q) * dk ** -0.5, _l2_normalised(k)
    with jax.named_scope("lm.kda.gate"):
        g = -jnp.exp(p["A_log"].astype(F32))[:, None] * jax.nn.softplus(
            f.astype(F32).reshape(t, h, dk)
            + p["dt_bias"].astype(F32).reshape(h, dk))
        beta = jax.nn.sigmoid(b.astype(F32))
    with jax.named_scope("lm.kda.recur"):
        if t == 1:
            o, s = kda.step(state["s"], q[0], k[0], v[0], g[0], beta[0])
            o = o[None]
        else:
            o, s = kda.chunked(q, k, v, g, beta, state["s"],
                               chunk=cfg.kda_chunk)
            s = s.astype(state["s"].dtype)
    with jax.named_scope("lm.kda.norm"):
        o = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + cfg.rms_norm_eps)
        o = (o * p["o_norm"]["scale"].astype(F32)
             * jax.nn.sigmoid(gate.astype(F32).reshape(t, h, dk)))
    with jax.named_scope("lm.kda.proj"):
        out = o.reshape(t, -1).astype(x.dtype) @ p["o_proj"]["kernel"]
    return out, {"s": s, "conv": tail.astype(state["conv"].dtype)}


def _mix(lp, cfg: KimiLinearConfig, x, state, position, visible, *, kind: str):
    """A layer's first half -> (x + Mixer(RMSNorm(x)), the layer's state,
    the cache rows a full layer's attention fetched)."""
    u = lm_common.rms_norm(lp["attn_norm"]["scale"], x, cfg.rms_norm_eps)
    if kind == "kda":
        out, state = kda_layer(lp["attn"], cfg, u, state)
        return x + out, state, jnp.zeros((), jnp.int32)
    out, state, fetched = dsv3.attention_layer(lp["attn"], cfg, u, state,
                                               position, visible)
    return x + out, state, fetched


# -- prefill, step, generation ------------------------------------------------


def _empty_kda(cfg: KimiLinearConfig, dtype):
    h, dk = cfg.kda_num_heads, cfg.kda_head_dim
    return {"s": jnp.zeros((h, dk, dk), jnp.dtype(cfg.state_dtype)),
            "conv": jnp.zeros((cfg.short_conv_kernel_size - 1, 3 * h * dk),
                              dtype)}


def empty_state(cfg: KimiLinearConfig, max_len: int, dtype):
    """The state with nothing in it and room for ``max_len`` positions:
    `models/deepseek_v3.py`'s, a KDA layer's in the place of its cache."""
    state = dsv3.empty_state(cfg, max_len, dtype)
    return {"layers": [_empty_kda(cfg, dtype) if kind == "kda" else cache
                       for kind, cache in zip(cfg.kinds, state["cache"])],
            "experts": state["experts"]}


# `models/deepseek_v3.py`'s stack, prefill and decode with this module's
# mixers in: a PROMPT from position 0 takes the KDA layers' chunked form from
# zero states and the full layers' materialised form; a SUFFIX's first chunk
# starts from each KDA layer's matrix state and tail, and the full layers
# take the absorbed form against the cache's first ``position + T`` rows; a
# DECODE STEP the KDA layers' recurrence and the absorbed form.  The
# counters are `COUNTERS` [8].
STACK = dsv3.Stack(
    COUNTERS, empty_state, layers="layers",
    mixers=lambda cfg: [functools.partial(_mix, kind=kind)
                        for kind in cfg.kinds],
    prefilled=lambda cfg, t: {
        "kda_chunks": t // cfg.kda_chunk * cfg.kinds.count("kda")})
prefill = functools.partial(dsv3.prefill, stack=STACK)
decode = functools.partial(dsv3.decode, stack=STACK)


def generate(params, cfg: KimiLinearConfig, ids, new_tokens: int):
    """Prefill, then greedy decoding -> (new ids, the logits they were
    chosen from, the counters, the experts every position chose
    [E layers, T + new_tokens, top_k])."""
    return lm_common.generate(cfg.language_model(), params, ids,
                              new_tokens)[:4]


# -- the routers' balance, for seeded weights --------------------------------


@functools.partial(jax.jit, static_argnames=("cfg", "kind", "rounds"))
def _balancing_layer(lp, x, *, cfg: KimiLinearConfig, kind: str, rounds: int):
    """One layer of the calibration pass, from a state with nothing in it
    -> (its output, an expert layer's balanced bias or None).  One compiled
    program a kind of layer."""
    state = _empty_kda(cfg, x.dtype) if kind == "kda" else None
    x, _, _ = _mix(lp, cfg, x, state, 0, None, kind=kind)
    return dsv3.balanced_feed_forward(lp, cfg, x, rounds)


def balanced_selection_bias(params, cfg: KimiLinearConfig, ids, *,
                            rounds: int = 300):
    """Every expert layer's ``e_score_correction_bias`` as load balancing
    leaves it: `models/deepseek_v3.py balanced_selection_bias` for this
    stack, layer after layer over the calibration sequence ``ids`` [T] (whole
    chunks of the KDA form).  Returns one [num_experts] bias an expert layer,
    in the stored dtype."""
    return lm_common.balanced_biases(params["embed"][ids], (
        functools.partial(_balancing_layer, lp, cfg=cfg, kind=kind,
                          rounds=rounds)
        for lp, kind in zip(params["layers"], cfg.kinds)))
