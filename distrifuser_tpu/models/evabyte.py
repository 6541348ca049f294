"""EvaByte: a byte-level causal language model whose attention is EVA
(`ops/eva.py`) - an exact 2048-byte window beside pooled 16-byte chunk
summaries of everything before it, in one softmax - with prefill (of a
whole prompt, or of a suffix through the state its prefix left), a one-byte
step through a BOUNDED decode state, and a greedy decode loop that stays on
the device.

    h <- h + Attn(RMSNorm(h))                       h in float32
    h <- h + W_down(silu(W_gate x) * W_up x),       x = RMSNorm(h)
    logits = RMSNorm(h) W_head                      float32, 8 x 320 columns

``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)`` on the served-dtype
cast of ``h`` (the residual stream stays float32 under bf16 layers:
``fp32_skip_add``); no bias anywhere.  ``Attn``: q, k, v = x W_qkv, 32 heads
of 128, as many KV heads; rotary embedding on q and k by absolute position
(theta 1e5, whole head, rotate-half) BEFORE the pooling; EVA; then W_o.
The head predicts eight bytes a position: block ``i`` of its columns is
byte ``t + 1 + i``.  Greedy decoding takes block 0, one byte a step; the
other seven are computed and returned with the logits (self-speculative
multi-byte decoding is not run: ROADMAP R7c).

State across calls, a layer: a ring ``k``, ``v`` [window, H, D] (rotated,
served dtype), written at ``t % window``, of which rows ``0 .. t % window``
are visible - so a window boundary empties it without a write - and a
summary table ``ks``, ``vs`` [ceil(max_len / chunk), H, D], row ``j``
written when position ``chunk * j + chunk - 1`` is, of which the rows of
earlier windows are visible.  One sequence at a time (no batch axis).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import eva
from . import lm_common
from .language_model import LanguageModel
from .lm_common import F32
from .weights import params_nbytes

# counters the generation returns with its ids; ``bytes_reused``: of the
# positions the state covers after prefill, those a state handed in already
# covered - entered, not computed in this request; ``state_rows_read``: the
# ring and summary rows the decode steps' attention read, summed over layers
# and steps (`ops/eva.py step_attention`: the rows in view on a TPU, every
# row held elsewhere)
COUNTERS = ("bytes_prefilled", "bytes_decoded", "summaries_written",
            "windows_rolled", "state_bytes", "bytes_reused",
            "state_rows_read")


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    num_hidden_layers: int = 32
    vocab_size: int = 320
    byte_offset: int = 64  # byte b is id b + 64; ids 0-63 are special
    hidden_size: int = 4096
    num_attention_heads: int = 32
    intermediate_size: int = 11008
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8
    rms_norm_eps: float = 1e-5
    rope_theta: float = 100000.0
    fp32_skip_add: bool = True

    def __post_init__(self):
        if self.hidden_size % self.num_attention_heads or self.head_dim % 2:
            raise ValueError("hidden_size must divide into heads of even size")
        if self.window_size % self.chunk_size:
            raise ValueError("a chunk never straddles a window: window_size "
                             "must be a multiple of chunk_size")
        if self.byte_offset + 256 > self.vocab_size:
            raise ValueError("the vocabulary must hold byte_offset + 256 ids")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def language_model(self) -> LanguageModel:
        """This model as the rewrite stage takes it."""
        return LanguageModel(self, prefill, decode, COUNTERS, self.chunk_size,
                             self.vocab_size, self.byte_offset,
                             prefill_from=prefill)


def evabyte_config_from_json(d: Dict[str, Any]) -> EvaByteConfig:
    """From the published config.json keys (``byte_offset`` is ours: the
    config does not give the tokenizer's)."""
    lm_common.refuse_unbuilt(d, {
        "attention_class": "eva", "hidden_act": "silu",
        "norm_add_unit_offset": True, "fp32_logits": True,
        "mixedp_attn": True, "attention_bias": False, "fp32_ln": False,
        "rope_scaling": None, "tie_word_embeddings": False})
    if d.get("num_key_value_heads", d["num_attention_heads"]) != d[
            "num_attention_heads"]:
        raise ValueError("EVA is built with as many KV heads as query heads")
    return EvaByteConfig(**lm_common.config_fields(EvaByteConfig, d))


# -- parameters ---------------------------------------------------------------


def param_shapes(cfg: EvaByteConfig) -> Dict[str, Any]:
    """The parameter tree with a shape tuple at every leaf.  q | k | v and
    gate | up are each held as one fused kernel: the same parameters and
    arithmetic."""
    d, f = cfg.hidden_size, cfg.intermediate_size
    h, hd = cfg.num_attention_heads, cfg.head_dim
    layer = {
        "attn_norm": {"scale": (d,)},
        "attn": {"qkv": {"kernel": (d, 3 * d)}, "o_proj": {"kernel": (d, d)},
                 "phi": (h, hd), "mu": (h, hd)},
        "mlp_norm": {"scale": (d,)},
        "mlp": {"gate_up": {"kernel": (d, 2 * f)}, "down": {"kernel": (f, d)}},
    }
    return {
        "embed": (cfg.vocab_size, d),
        "layers": [layer] * cfg.num_hidden_layers,
        "final_norm": {"scale": (d,)},
        "head": {"kernel": (d, cfg.num_pred_heads * cfg.vocab_size)},
    }


def init_leaf(key, name: str, shape, cfg: EvaByteConfig, dtype):
    """One leaf by its name: norm offsets ``w`` zeros (the scale is 1 + w),
    the embedding N(0, 0.02^2), the pooling vectors phi and mu
    N(0, 1 / head_dim) (so that phi . k is of order one and the pooling is
    neither uniform nor one-hot), kernels N(0, 1 / fan_in)."""
    if name == "scale":
        return jnp.zeros(shape, dtype)
    if name == "embed":
        return (0.02 * jax.random.normal(key, shape, F32)).astype(dtype)
    fan_in = cfg.head_dim if name in ("phi", "mu") else shape[-2]
    return (jax.random.normal(key, shape, F32) / math.sqrt(fan_in)
            ).astype(dtype)


def named_leaves(cfg: EvaByteConfig):
    """([(a leaf's own name, its shape)], the tree's structure)."""
    return lm_common.named_leaves(param_shapes(cfg))


def init_evabyte_params(key, cfg: EvaByteConfig, dtype=F32):
    return lm_common.init_params(key, cfg, dtype, named_leaves=named_leaves,
                                 init_leaf=init_leaf)


# -- layers -------------------------------------------------------------------


def unit_offset_norm(w, x, eps: float):
    """x / sqrt(mean(x^2) + eps) * (1 + w): the stored ``w`` is the scale's
    offset from one."""
    return lm_common.rms_norm(1.0 + w.astype(F32), x, eps)


def rotary(x, positions, theta: float):
    """Rotary embedding over the whole head, rotate-half: x [T, H, D] at
    ``positions`` [T]; float32 inside, the result in ``x``'s dtype."""
    half = x.shape[-1] // 2
    freqs = positions.astype(F32)[:, None] * theta ** (
        -jnp.arange(half, dtype=F32) / half)
    cos, sin = jnp.cos(freqs)[:, None, :], jnp.sin(freqs)[:, None, :]
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


@jax.named_scope("lm.eva.proj")
def _qkv(p, cfg: EvaByteConfig, x, positions):
    """x [T, d] -> q, k (rotated), v [T, H, D]."""
    t = x.shape[0]
    q, k, v = (a.reshape(t, cfg.num_attention_heads, cfg.head_dim)
               for a in jnp.split(x @ p["qkv"]["kernel"], 3, axis=-1))
    return (rotary(q, positions, cfg.rope_theta),
            rotary(k, positions, cfg.rope_theta), v)


@jax.named_scope("lm.eva.proj")
def _out(p, attended):
    return attended.reshape(attended.shape[0], -1) @ p["o_proj"]["kernel"]


def empty_state(cfg: EvaByteConfig, max_len: int, dtype):
    """A layer's decode state with nothing in it and room for ``max_len``
    positions."""
    heads = (cfg.num_attention_heads, cfg.head_dim)
    ring = jnp.zeros((cfg.window_size,) + heads, dtype)
    table = jnp.zeros((-(-max_len // cfg.chunk_size),) + heads, dtype)
    return {"k": ring, "v": ring, "ks": table, "vs": table}


def attention_prefill(p, cfg: EvaByteConfig, x, state, position: int):
    """x [T, d] at ``position`` onward (both whole chunks, ``position``
    static) through the layer's state, which covers the positions before:
    -> (out [T, d], the state as position + T finds it, 0: no row read by
    a decode step)."""
    window, chunk = cfg.window_size, cfg.chunk_size
    q, k, v = _qkv(p, cfg, x, position + jnp.arange(x.shape[0]))
    with jax.named_scope("lm.eva.pool"):
        ks, vs = eva.chunk_summaries(k, v, p["phi"], p["mu"], chunk=chunk)
    with jax.named_scope("lm.eva.attn"):
        out, ring_k, ring_v, table_k, table_v = eva.prefill_attention(
            q, k, v, ks, vs, state["k"], state["v"], state["ks"], state["vs"],
            position=position, window=window, chunk=chunk)
    return _out(p, out), {"k": ring_k, "v": ring_v, "ks": table_k,
                          "vs": table_v}, 0


def attention_step(p, cfg: EvaByteConfig, x, state, position):
    """One byte (x [1, d]) at ``position`` through the layer's state: its
    key and value go into the ring first; if it completes a chunk, the
    chunk's summary goes into the table; then the query reads both
    (`ops/eva.py step_attention`: on a TPU the rows in view in one pass,
    else every row under a mask) -> (out [1, d], the state, the ring +
    summary rows read)."""
    window, chunk = cfg.window_size, cfg.chunk_size
    position = jnp.asarray(position, jnp.int32)
    q, k, v = _qkv(p, cfg, x, position[None])
    at = position % window
    ring_k = lax.dynamic_update_slice_in_dim(state["k"], k, at, axis=0)
    ring_v = lax.dynamic_update_slice_in_dim(state["v"], v, at, axis=0)
    with jax.named_scope("lm.eva.pool"):
        start, row = at // chunk * chunk, position // chunk
        ks, vs = eva.chunk_summaries(
            lax.dynamic_slice_in_dim(ring_k, start, chunk),
            lax.dynamic_slice_in_dim(ring_v, start, chunk),
            p["phi"], p["mu"], chunk=chunk)
        complete = position % chunk == chunk - 1
        table_k, table_v = (
            lax.dynamic_update_slice_in_dim(table, jnp.where(
                complete, new, lax.dynamic_slice_in_dim(table, row, 1)),
                row, axis=0)
            for table, new in ((state["ks"], ks), (state["vs"], vs)))
    with jax.named_scope("lm.eva.attn"):
        out, rows = eva.step_attention(q[0], ring_k, ring_v, table_k, table_v,
                                       position=position, window=window,
                                       chunk=chunk)
    return _out(p, out[None]), {"k": ring_k, "v": ring_v, "ks": table_k,
                                "vs": table_v}, rows


mlp = jax.named_scope("lm.mlp")(lm_common.gated_mlp)


@jax.named_scope("lm.head")
def head(params, cfg: EvaByteConfig, h):
    """h [T, d] -> float32 logits [T, num_pred_heads * vocab_size]."""
    x = unit_offset_norm(params["final_norm"]["scale"],
                         h.astype(params["head"]["kernel"].dtype),
                         cfg.rms_norm_eps)
    return jnp.dot(x, params["head"]["kernel"], preferred_element_type=F32)


# -- prefill, step, generation ------------------------------------------------


def _forward(params, cfg: EvaByteConfig, ids, state, position, attend):
    """The stack over ids [T] at ``position`` onward, through the state (one
    entry a layer) -> (the residual stream [T, d], the new state, the state
    rows the layers' decode attention read).  ``attend``:
    `attention_prefill` (T whole chunks) or `attention_step` (one byte)."""
    dtype = params["embed"].dtype
    # fp32_skip_add false keeps the stream where the published code keeps
    # it without the switch, in bfloat16: a precision below the stated one
    h = params["embed"][ids].astype(F32 if cfg.fp32_skip_add else jnp.bfloat16)
    new_state, rows_read = [], 0
    for lp, st in zip(params["layers"], state):
        x = unit_offset_norm(lp["attn_norm"]["scale"], h.astype(dtype),
                             cfg.rms_norm_eps)
        out, st, rows = attend(lp["attn"], cfg, x, st, position)
        new_state.append(st)
        rows_read = rows_read + rows
        h = h + out.astype(h.dtype)
        x = unit_offset_norm(lp["mlp_norm"]["scale"], h.astype(dtype),
                             cfg.rms_norm_eps)
        h = h + mlp(lp["mlp"], x).astype(h.dtype)
    return h, new_state, rows_read


def prefill(params, cfg: EvaByteConfig, ids, *, max_len: int, state=None,
            position: int = 0, counters=None):
    """ids [T] (T a multiple of ``chunk_size``) at ``position`` onward,
    computed in full -> (float32 logits after the last byte [8 * V], the
    decode state, the `COUNTERS` so far [7] int32, ()).

    `models/language_model.py`'s ``prefill`` and ``prefill_from`` both: of
    the ``bytes_prefilled`` positions the state returned covers,
    ``bytes_reused`` = ``position`` came with the state handed in."""
    t, chunk = ids.shape[0], cfg.chunk_size
    if t % chunk or position % chunk:  # before the pooling reshapes by chunk
        raise ValueError(f"{t} bytes from position {position} on are not "
                         f"whole chunks of {chunk}")
    state, counters = lm_common.enter_state(
        state, counters, COUNTERS, position=position, of="bytes",
        empty=lambda: [empty_state(cfg, max_len, params["embed"].dtype)
                       ] * cfg.num_hidden_layers,
        room=lambda state: state[0]["ks"].shape[0] * chunk, needed=max_len)
    end = position + t
    h, state, _ = _forward(params, cfg, ids, state, position,
                           attention_prefill)
    counters = lm_common.count(
        COUNTERS, counters,
        put={"state_bytes": params_nbytes(state), "bytes_reused": position},
        bytes_prefilled=t,
        summaries_written=end // chunk - position // chunk,
        windows_rolled=end // cfg.window_size - position // cfg.window_size)
    return head(params, cfg, h[-1:])[0], state, counters, ()


def decode(params, cfg: EvaByteConfig, logits, state, counters, *,
           position: int, new_tokens: int):
    """Greedy decoding through the state, on the device from first byte to
    last: ``new_tokens`` times the largest logit of block 0 is taken and
    the byte goes through the stack.  ``logits`` follow the byte at
    ``position - 1``.  -> (ids [new_tokens] int32, the float32 logits each
    was chosen from, all eight blocks [new_tokens, 8 * V], (), the state,
    the counters)."""
    window, chunk = cfg.window_size, cfg.chunk_size

    def step(token, state, at):
        h, state, rows_read = _forward(params, cfg, token, state, at,
                                       attention_step)
        return head(params, cfg, h)[0], state, dict(
            bytes_decoded=1, summaries_written=at % chunk == chunk - 1,
            windows_rolled=at % window == window - 1,
            state_rows_read=rows_read), None

    ids, chosen_from, _, state, counters = lm_common.greedy_decode(
        step, logits, state, counters, names=COUNTERS, position=position,
        new_tokens=new_tokens,
        pick=lambda logits: jnp.argmax(logits[:cfg.vocab_size]))
    return ids, chosen_from, (), state, counters


def generate(params, cfg: EvaByteConfig, ids, new_tokens: int):
    """Prefill, then greedy decoding -> (new ids, the logits they were
    chosen from, the counters, the state)."""
    new_ids, chosen_from, counters, _, state, _ = lm_common.generate(
        cfg.language_model(), params, ids, new_tokens)
    return new_ids, chosen_from, counters, state
