"""Nemotron-H: a stack whose every layer is ONE mixer - Mamba-2 (``M``),
grouped-query attention (``*``) or latent sparse experts (``E``) - with
prefill, a one-token step through two kinds of state side by side, and a
greedy decode loop that stays on the device.

    x <- x + mixer(RMSNorm(x))          eps 1e-5, no bias but the conv's
    logits = RMSNorm(x) W_head          untied embedding and head

``M``  [z | xBC | dt] = u W_in;  xBC <- silu(conv4(xBC) + b) = [x | B | C];
       dt <- softplus(dt + dt_bias), A = -exp(A_log); the selective scan of
       ops/ssm.py per head (B, C shared by the heads of a group);
       y <- y + D x;  y <- RMSNorm per group of (y * silu(z));  out = y W_out.
``*``  q, k, v = u W_qkv; causal softmax(q k^T / sqrt(d)) v with each KV head
       serving Hq / Hkv query heads; NO position embedding (the family's
       published modelling code applies none).
``E``  router over ALL experts in float32 (ops/moe.py `route`);
       l = u W_down; the experts HELD HERE each give relu(l W1_i)^2 W2_i;
       routed = (their weighted sum) W_up; one shared expert
       relu(u V1)^2 V2 on the full width; out = routed + shared.

Expert parallelism is in the configuration: ``n_local_experts`` of
``n_routed_experts`` are held (``first_local_expert`` onward), the router
keeps its full width, and what absent experts would add is left out - the
partial result goes on to the next layer, as on one chip of the deployment
before its exchange.  The vocabulary may be a slice: ids, logits and the
greedy choice are then over the slice.

State across calls: per ``M`` layer the SSM state [H, P, N]
(``state_dtype``, float32) and the last conv_kernel - 1 columns of xBC; per
``*`` layer a KV cache [max_len, Hkv, D].  A prompt's suffix can enter the
state its prefix left (``prefill`` at a ``position``): the scan's carry
starts from the SSM state, the convolution's left context is the tail, the
attention reads the cache's rows.  One sequence at a time (no batch axis).
The multi-token-prediction module of the published model is not built (its
config does not say how the hidden state and the next token's embedding are
joined).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import moe, ssm
from ..ops.attention import causal_gqa_sdpa
from . import lm_common
from .language_model import LanguageModel
from .lm_common import F32, rms_norm

# counters the generation returns with its ids
COUNTERS = ("tokens_prefilled", "tokens_reused", "tokens_decoded",
            "expert_assignments", "expert_assignments_held")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    pattern: str  # one letter a layer: M, * or E
    vocab_size: int = 131072
    hidden_size: int = 4096
    norm_eps: float = 1e-5
    # M
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # *
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # E
    n_routed_experts: int = 512  # the router's width
    n_local_experts: int = 512  # held here ...
    first_local_expert: int = 0  # ... from this one on
    num_experts_per_tok: int = 22
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    state_dtype: str = "float32"

    def __post_init__(self):
        if not self.pattern or set(self.pattern) - set("M*E"):
            raise ValueError(f"layer pattern {self.pattern!r}: one of M, *, "
                             "E a layer")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("mamba_num_heads must divide into n_groups")
        if (self.first_local_expert + self.n_local_experts
                > self.n_routed_experts):
            raise ValueError("the held experts lie outside the router")

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    def language_model(self) -> LanguageModel:
        """This model as the rewrite stage takes it: ids of words; beside
        ids and logits it records the experts every token chose; a suffix
        can enter the state its prefix left."""
        return LanguageModel(
            self, prefill, decode, COUNTERS, self.chunk_size, self.vocab_size,
            prefill_from=prefill)


def nemotron_h_config_from_json(d: Dict[str, Any]) -> NemotronHConfig:
    """From the published config.json keys, plus what a cut adds to them:
    ``n_routed_experts`` counts the experts HELD and ``expert_parallel``
    (``{"chips": n, "index": i}``) says of how many shares this is which, so
    the router is ``chips`` times as wide; ``layer_offset`` is where in
    ``hybrid_override_pattern`` the ``num_hidden_layers`` served layers
    start."""
    start = int(d.get("layer_offset", 0))
    pattern = d["hybrid_override_pattern"][start:start + int(
        d["num_hidden_layers"])]
    if len(pattern) != int(d["num_hidden_layers"]):
        raise ValueError("hybrid_override_pattern is shorter than "
                         "layer_offset + num_hidden_layers")
    if d.get("mlp_hidden_act", "relu2") != "relu2" or d.get(
            "mamba_hidden_act", "silu") != "silu":
        raise ValueError("only relu2 experts and silu Mamba are built")
    if int(d.get("n_group", 1)) != 1 or int(d.get("n_shared_experts", 1)) != 1:
        raise ValueError("only n_group 1 and one shared expert are built")
    return NemotronHConfig(**{
        **lm_common.config_fields(NemotronHConfig, d),
        **lm_common.expert_share(d, "n_routed_experts"), "pattern": pattern})


# -- parameters ---------------------------------------------------------------


def _layer_shapes(cfg: NemotronHConfig, kind: str) -> Dict[str, Any]:
    d = cfg.hidden_size
    if kind == "M":
        di, h = cfg.mamba_inner, cfg.mamba_num_heads
        return {
            "in_proj": {"kernel": (d, di + cfg.conv_dim + h)},
            "conv": {"kernel": (cfg.conv_kernel, cfg.conv_dim),
                     "bias": (cfg.conv_dim,)},
            "dt_bias": (h,), "A_log": (h,), "D": (h,),
            "norm": {"scale": (di,)},
            "out_proj": {"kernel": (di, d)},
        }
    if kind == "*":
        hq, hkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim)
        return {"qkv": {"kernel": (d, (hq + 2 * hkv) * hd)},
                "o_proj": {"kernel": (hq * hd, d)}}
    lat, f = cfg.moe_latent_size, cfg.moe_intermediate_size
    fs = cfg.moe_shared_expert_intermediate_size
    return {
        "router": {"kernel": (d, cfg.n_routed_experts)},
        "e_score_correction_bias": (cfg.n_routed_experts,),
        "down": {"kernel": (d, lat)}, "up": {"kernel": (lat, d)},
        "experts": {"w1": (cfg.n_local_experts, lat, f),
                    "w2": (cfg.n_local_experts, f, lat)},
        "shared": {"fc1": {"kernel": (d, fs)}, "fc2": {"kernel": (fs, d)}},
    }


def param_shapes(cfg: NemotronHConfig) -> Dict[str, Any]:
    """The parameter tree with a shape tuple at every leaf."""
    d = cfg.hidden_size
    return {
        "embed": (cfg.vocab_size, d),
        "layers": [{"norm": {"scale": (d,)},
                    "mixer": _layer_shapes(cfg, kind)}
                   for kind in cfg.pattern],
        "final_norm": {"scale": (d,)},
        "head": {"kernel": (d, cfg.vocab_size)},
    }


def init_leaf(key, name: str, shape, cfg: NemotronHConfig, dtype):
    """One leaf by its name, the published initialisers where they matter
    to the arithmetic: ``A_log`` = log U(1, 16), ``dt_bias`` the inverse
    softplus of a log-uniform time step, ``D`` and norm scales ones, the
    selection bias small, the embedding N(0, 0.02^2), kernels
    N(0, 1 / fan_in)."""
    if name in ("scale", "D"):
        return jnp.ones(shape, dtype)
    if name == "bias":
        return jnp.zeros(shape, dtype)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0)
                       ).astype(dtype)
    if name == "dt_bias":
        lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, F32, lo, hi)),
                         cfg.time_step_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if name == "e_score_correction_bias":
        return (0.02 * jax.random.normal(key, shape, F32)).astype(dtype)
    if name == "embed":
        return (0.02 * jax.random.normal(key, shape, F32)).astype(dtype)
    std = 1.0 / math.sqrt(shape[-2])
    return (std * jax.random.normal(key, shape, F32)).astype(dtype)


def named_leaves(cfg: NemotronHConfig):
    """([(a leaf's own name, its shape)], the tree's structure)."""
    return lm_common.named_leaves(param_shapes(cfg))


def init_nemotron_h_params(key, cfg: NemotronHConfig, dtype=F32):
    return lm_common.init_params(key, cfg, dtype, named_leaves=named_leaves,
                                 init_leaf=init_leaf)


# -- layers -------------------------------------------------------------------


def _mamba_inputs(p, cfg, u):
    """u [T, D] -> (z [T, di], xBC before the convolution [T, conv_dim],
    dt after softplus [T, H] float32)."""
    di = cfg.mamba_inner
    proj = u @ p["in_proj"]["kernel"]
    z, xbc, dt = jnp.split(proj, [di, di + cfg.conv_dim], axis=-1)
    dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"].astype(F32))
    return z, xbc, dt


def _mamba_split(cfg, xbc):
    """silu'd conv output [T, conv_dim] -> x [T, H, P], B, C [T, G, N]."""
    t = xbc.shape[0]
    gn = cfg.n_groups * cfg.ssm_state_size
    x, b, c = jnp.split(xbc, [cfg.mamba_inner, cfg.mamba_inner + gn], axis=-1)
    return (x.reshape(t, cfg.mamba_num_heads, cfg.mamba_head_dim),
            b.reshape(t, cfg.n_groups, cfg.ssm_state_size),
            c.reshape(t, cfg.n_groups, cfg.ssm_state_size))


def _mamba_output(p, cfg, y, x, z, dtype):
    """y + D x, gated by silu(z), normed per group, projected out."""
    y = y + p["D"].astype(F32)[:, None] * x
    y = y.reshape(y.shape[0], cfg.mamba_inner) * jax.nn.silu(z.astype(F32))
    y = rms_norm(p["norm"]["scale"], y, cfg.norm_eps, groups=cfg.n_groups)
    return y.astype(dtype) @ p["out_proj"]["kernel"]


@jax.named_scope("lm.mamba")
def mamba_prefill(p, cfg: NemotronHConfig, u, state=None):
    """A sequence (u [T, D], T whole chunks) entering the layer's ``state``
    {"ssm", "conv"} (None: an empty one, the start of a sequence) -> (out
    [T, D], the state after its last row)."""
    z, xbc, dt = _mamba_inputs(p, cfg, u)
    tail = (jnp.zeros((cfg.conv_kernel - 1, cfg.conv_dim), xbc.dtype)
            if state is None else state["conv"])
    conv, tail = ssm.causal_conv1d(xbc, p["conv"]["kernel"],
                                   p["conv"]["bias"], tail)
    x, b, c = _mamba_split(cfg, jax.nn.silu(conv))
    a = -jnp.exp(p["A_log"].astype(F32))
    y, new = ssm.ssd_chunked(x, dt, a, b, c, chunk=cfg.chunk_size,
                             state=None if state is None else state["ssm"])
    out = _mamba_output(p, cfg, y, x, z, u.dtype)
    return out, {"ssm": new.astype(cfg.state_dtype), "conv": tail}


@jax.named_scope("lm.mamba")
def mamba_step(p, cfg: NemotronHConfig, u, state):
    """One token (u [1, D]) through the layer's state."""
    z, xbc, dt = _mamba_inputs(p, cfg, u)
    conv, tail = ssm.causal_conv1d(xbc, p["conv"]["kernel"],
                                   p["conv"]["bias"], state["conv"])
    x, b, c = _mamba_split(cfg, jax.nn.silu(conv))
    a = -jnp.exp(p["A_log"].astype(F32))
    y, new = ssm.ssd_step(state["ssm"], x[0], dt[0], a, b[0], c[0])
    out = _mamba_output(p, cfg, y[None], x, z, u.dtype)
    return out, {"ssm": new, "conv": tail}


@jax.named_scope("lm.attn")
def attention_layer(p, cfg: NemotronHConfig, u, cache, position):
    """u [T, D] at positions ``position .. position + T - 1``; its keys and
    values are written into ``cache`` {"k", "v"} [max_len, Hkv, D] first,
    and the queries then read the cache."""
    t = u.shape[0]
    hq, hkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    q, k, v = jnp.split(u @ p["qkv"]["kernel"],
                        [hq * hd, (hq + hkv) * hd], axis=-1)
    cache = {
        "k": lax.dynamic_update_slice_in_dim(
            cache["k"], k.reshape(t, hkv, hd), position, axis=0),
        "v": lax.dynamic_update_slice_in_dim(
            cache["v"], v.reshape(t, hkv, hd), position, axis=0)}
    out = causal_gqa_sdpa(q.reshape(t, hq, hd), cache["k"], cache["v"],
                          q_positions=position + jnp.arange(t))
    return out.reshape(t, hq * hd) @ p["o_proj"]["kernel"], cache


def moe_layer(p, cfg: NemotronHConfig, u):
    """-> (out [T, D], how many of the T * top_k assignments fell on experts
    held here, the experts each token chose [T, top_k])."""
    with jax.named_scope("lm.moe.router"):
        idx, weights = moe.route(
            u, p["router"]["kernel"], p["e_score_correction_bias"],
            top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor)
    with jax.named_scope("lm.moe.experts"):
        latent = u @ p["down"]["kernel"]
        routed, held = moe.local_expert_sum(
            latent, idx, weights, p["experts"]["w1"], p["experts"]["w2"],
            first_expert=cfg.first_local_expert, activation="relu2")
        routed = routed.astype(u.dtype) @ p["up"]["kernel"]
    with jax.named_scope("lm.moe.shared"):
        hidden = jnp.square(jax.nn.relu(u @ p["shared"]["fc1"]["kernel"]))
        shared = hidden @ p["shared"]["fc2"]["kernel"]
    return routed + shared, held, idx


def head(params, cfg: NemotronHConfig, x):
    """x [T, D] -> float32 logits [T, V] over the held vocabulary."""
    return lm_common.head(params, x, cfg.norm_eps)


# -- prefill, step, generation ------------------------------------------------


def empty_cache(cfg: NemotronHConfig, max_len: int, dtype):
    shape = (max_len, cfg.num_key_value_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _forward(params, cfg: NemotronHConfig, ids, state, position):
    """The stack over ids [T] at ``position`` onward -> (hidden [T, D], the
    new state, held expert assignments, the experts chosen [E layers, T,
    top_k]).  ``state`` has one entry a layer: an ``M`` layer's {"ssm",
    "conv"} (None: a sequence from nothing), a ``*`` layer's cache, None for
    ``E``.  Several rows go through an ``M`` layer by the chunked scan,
    whether or not they enter a state; one row through a state is a decode
    step."""
    x = params["embed"][ids]
    new_state, chosen, held = [], [], jnp.zeros((), jnp.int32)
    for kind, lp, st in zip(cfg.pattern, params["layers"], state):
        u = rms_norm(lp["norm"]["scale"], x, cfg.norm_eps)
        if kind == "M" and (st is None or ids.shape[0] > 1):
            out, st = mamba_prefill(lp["mixer"], cfg, u, st)
        elif kind == "M":
            out, st = mamba_step(lp["mixer"], cfg, u, st)
        elif kind == "*":
            out, st = attention_layer(lp["mixer"], cfg, u, st, position)
        else:
            out, n, idx = moe_layer(lp["mixer"], cfg, u)
            held = held + n.astype(jnp.int32)
            chosen.append(idx)
        new_state.append(st)
        x = x + out
    chosen = jnp.stack(chosen) if chosen else jnp.zeros(
        (0, ids.shape[0], cfg.num_experts_per_tok), jnp.int32)
    return x, new_state, held, chosen


@functools.partial(jax.jit, static_argnames=("cfg", "kind", "rounds"))
def _balancing_layer(lp, x, *, cfg: NemotronHConfig, kind: str,
                     rounds: int, step: float = 0.02):
    """One layer of the calibration pass -> (x + mixer, the E layer's
    balanced bias or None).  One compiled program a KIND of layer."""
    u = rms_norm(lp["norm"]["scale"], x, cfg.norm_eps)
    mixer, bias = lp["mixer"], None
    if kind == "M":
        out, _ = mamba_prefill(mixer, cfg, u)
    elif kind == "*":
        out, _ = attention_layer(
            mixer, cfg, u, empty_cache(cfg, x.shape[0], x.dtype), 0)
    else:
        scores = jax.nn.sigmoid(jnp.dot(
            u.astype(F32), mixer["router"]["kernel"].astype(F32),
            precision=lax.Precision.HIGHEST))
        bias = moe.balanced_bias(
            scores, top_k=cfg.num_experts_per_tok, rounds=rounds, step=step
        ).astype(mixer["e_score_correction_bias"].dtype)
        out, _, _ = moe_layer(dict(mixer, e_score_correction_bias=bias),
                              cfg, u)
    return x + out, bias


def balanced_selection_bias(params, cfg: NemotronHConfig, ids, *,
                            rounds: int = 300):
    """Every E layer's ``e_score_correction_bias`` as load balancing leaves
    it: over the sequence ``ids`` [T], each expert chosen about equally
    often.  The published model's bias is trained by the auxiliary-loss-free
    rule - after a batch, b_e moves down where expert e was chosen more than
    its share and up where less - and this is that rule run to its fixed
    point on one calibration sequence, layer after layer (a layer's inputs
    depend on the layers before it, so each is balanced before the next
    sees its output; the fit of one layer's bias is `ops/moe.py
    balanced_bias`).  For seeded weights: without it a random router loads
    any fixed 64 of its 512 experts by +-4% from seed to seed, where a
    trained one loads them alike (+-1.4% after this, on other tokens).
    Returns one [n_routed_experts] bias an E layer, in the stored dtype."""
    return lm_common.balanced_biases(params["embed"][ids], (
        functools.partial(_balancing_layer, lp, cfg=cfg, kind=kind,
                          rounds=rounds)
        for kind, lp in zip(cfg.pattern, params["layers"])))


def _assignments(cfg: NemotronHConfig, tokens: int) -> int:
    return tokens * cfg.pattern.count("E") * cfg.num_experts_per_tok


def prefill(params, cfg: NemotronHConfig, ids, *, max_len: int, state=None,
            position: int = 0, counters=None):
    """ids [T] (T a multiple of ``chunk_size``) at ``position`` onward,
    computed in full -> (float32 logits after the last token [V], the state
    with room for ``max_len`` positions, the `COUNTERS` so far [5] int32,
    the experts the T tokens chose [E layers, T, top_k]).

    `models/language_model.py`'s ``prefill`` and ``prefill_from`` both: a
    prompt from position 0 starts every layer from nothing; a suffix enters
    ``state`` as the prefill of the ``position`` ids before it left it (a
    whole number of chunks, so the scan is cut where it carries one state
    anyway; of ``tokens_prefilled``, ``tokens_reused`` = ``position``)."""
    t = ids.shape[0]
    needed = max(max_len, position + t)
    state, counters = lm_common.enter_state(
        state, counters, COUNTERS, position=position,
        empty=lambda: [
            empty_cache(cfg, max_len, params["embed"].dtype)
            if kind == "*" else None for kind in cfg.pattern],
        room=lambda state: min(
            (st["k"].shape[0] for kind, st in zip(cfg.pattern, state)
             if kind == "*"), default=needed),
        needed=needed)
    x, state, held, chosen = _forward(params, cfg, ids, state, position)
    counters = lm_common.count(
        COUNTERS, counters, put={"tokens_reused": position},
        tokens_prefilled=t, expert_assignments=_assignments(cfg, t),
        expert_assignments_held=held)
    return head(params, cfg, x[-1:])[0], state, counters, chosen


def decode(params, cfg: NemotronHConfig, logits, state, counters, *,
           position: int, new_tokens: int):
    """Greedy decoding through the state, on the device from first token to
    last: ``new_tokens`` times the largest logit is taken and the token goes
    through the stack.  ``logits`` follow the token at ``position - 1``.
    -> (ids [new_tokens] int32, the float32 logits each was chosen from
    [new_tokens, V], the experts each chose on its way through the stack
    [new_tokens, E layers, top_k], the state, the counters)."""
    n_e, k = cfg.pattern.count("E"), cfg.num_experts_per_tok

    def step(token, state, at):
        x, state, held, chosen = _forward(params, cfg, token, state, at)
        return head(params, cfg, x)[0], state, dict(
            tokens_decoded=1, expert_assignments=_assignments(cfg, 1),
            expert_assignments_held=held), chosen.reshape(n_e, k)

    return lm_common.greedy_decode(
        step, logits, state, counters, names=COUNTERS, position=position,
        new_tokens=new_tokens,
        record=jnp.zeros((new_tokens, n_e, k), jnp.int32))


def generate(params, cfg: NemotronHConfig, ids, new_tokens: int):
    """Prefill, then greedy decoding -> (new ids, the logits they were
    chosen from, the counters, the experts every token but the last new one
    chose [E layers, T + new_tokens - 1, top_k])."""
    new_ids, chosen_from, counters, experts, _, chosen = lm_common.generate(
        cfg.language_model(), params, ids, new_tokens)
    chosen = jnp.concatenate([chosen, experts[:-1].swapaxes(0, 1)], axis=1)
    return new_ids, chosen_from, counters, chosen
