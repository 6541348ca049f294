"""What every language model's module has, written once.

A model's module (`nemotron_h`, `evabyte`, `deepseek_v3`, `kimi_linear`,
`sdar`, `lfm2`) writes its configuration, ``param_shapes``, ``init_leaf``, its
layers, the stack (``_forward``), its one-token step and the NAMES of its
counters.  From here it takes the seeded tree, the norm, the head, the
counters' arithmetic by name, the greedy loop, the entry into a state,
``generate`` of a `LanguageModel` record, the calibration's driver and the
two refusals of its ``*_config_from_json``.  Nothing here knows a model.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


# -- a configuration from published keys --------------------------------------


def refuse_unbuilt(d: Dict[str, Any], built: Dict[str, Any]) -> None:
    """Raise for a published setting of ``d`` that differs from the one value
    ``built`` says the module computes."""
    for key, want in built.items():
        if d.get(key, want) != want:
            raise ValueError(f"only {key} = {want!r} is built, the "
                             f"configuration says {d[key]!r}")


def expert_share(d: Dict[str, Any], key: str) -> Dict[str, int]:
    """``d[key]`` counts the experts HELD and ``expert_parallel``
    (``{"chips": n, "index": i}``) says of how many shares this is which
    -> the configuration's three fields: the router's width under ``key``,
    the experts held and the first of them."""
    ep = d.get("expert_parallel", {"chips": 1, "index": 0})
    held = int(d[key])
    return {key: held * int(ep["chips"]), "n_local_experts": held,
            "first_local_expert": held * int(ep["index"])}


def config_fields(cls, d: Dict[str, Any]) -> Dict[str, Any]:
    """The entries of ``d`` that are fields of the dataclass ``cls``."""
    return {f.name: d[f.name] for f in dataclasses.fields(cls)
            if f.name in d}


# -- the seeded tree ----------------------------------------------------------


def named_leaves(shapes, name: Callable = lambda keys: keys[-1]):
    """A tree with a shape tuple at every leaf -> ([(the leaf's name, its
    shape)] in flatten order, the tree's structure).  ``name`` takes the
    keys on the way to a leaf: its own key unless the model says else."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    return [(name([str(getattr(k, "key", k)) for k in path]), shape)
            for path, shape in leaves], treedef


def init_params(key, cfg, dtype, *, named_leaves: Callable,
                init_leaf: Callable):
    """The model's tree, seeded: ONE split of ``key`` over the leaves in
    flatten order, leaf ``i`` made from key ``i`` by the model's
    ``init_leaf`` rule for its name."""
    leaves, treedef = named_leaves(cfg)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        init_leaf(k, name, shape, cfg, dtype)
        for k, (name, shape) in zip(keys, leaves)])


# -- layers -------------------------------------------------------------------


def rms_norm(scale, x, eps: float, groups: int = 1):
    """RMSNorm in float32 over the last axis, or over each of ``groups``
    equal parts of it; the result in ``x``'s dtype."""
    xf = x.astype(F32)
    if groups > 1:
        xf = xf.reshape(x.shape[:-1] + (groups, x.shape[-1] // groups))
    xf = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (xf.reshape(x.shape) * scale.astype(F32)).astype(x.dtype)


def gated_mlp(p, x):
    """(silu(x G) * (x U)) D, gate | up one fused kernel, the product in
    float32."""
    gate, up = jnp.split(x @ p["gate_up"]["kernel"], 2, axis=-1)
    hidden = jax.nn.silu(gate.astype(F32)) * up.astype(F32)
    return hidden.astype(x.dtype) @ p["down"]["kernel"]


@jax.named_scope("lm.head")
def head(params, x, eps: float):
    """x [T, d] -> float32 logits [T, V] over the held vocabulary.  A tree
    with no ``head`` ties it to the embedding: ``embed`` [V, d] contracted
    over its SECOND axis, no transposed copy in memory."""
    x = rms_norm(params["final_norm"]["scale"], x, eps)
    if "head" not in params:
        return lax.dot_general(x, params["embed"], (((1,), (1,)), ((), ())),
                               preferred_element_type=F32)
    return jnp.dot(x, params["head"]["kernel"], preferred_element_type=F32)


# -- the counters, by name ----------------------------------------------------


def count(names: Sequence[str], counters, put=None, **add):
    """``counters`` - int32, one entry a name of ``names``, in their order -
    with each counter named in ``put`` holding its value and each keyword's
    moved by its amount.  Where a counter stands is known here alone."""
    put = put or {}
    unknown = (set(put) | set(add)) - set(names)
    if unknown:
        raise KeyError(f"no counter named {sorted(unknown)} among {names}")
    for name, value in put.items():
        counters = counters.at[names.index(name)].set(value)
    for name, amount in add.items():
        counters = counters.at[names.index(name)].add(
            jnp.asarray(amount).astype(jnp.int32))
    return counters


# -- prefill's preface, the greedy loop, generation ---------------------------


def enter_state(state, counters, names: Sequence[str], *, position: int,
                empty: Callable, room: Callable, needed: int,
                of: str = "tokens"):
    """The state and counters a prefill at ``position`` starts from: a prompt
    from 0 enters ``empty()`` with every counter zero; a suffix enters the
    state handed in, which must have ``room(state)`` for ``needed``
    positions."""
    if state is None:
        if position:
            raise ValueError(f"position {position} needs the state of the "
                             f"{of} before it")
        return empty(), jnp.zeros((len(names),), jnp.int32)
    if room(state) < needed:
        raise ValueError(f"the state handed in has no room for {needed} "
                         f"positions")
    return state, counters


def greedy_decode(step: Callable, logits, state, counters, *,
                  names: Sequence[str], position, new_tokens: int,
                  pick: Callable = jnp.argmax, record=None):
    """Greedy decoding, ONE loop on the device: ``new_tokens`` times the id
    ``pick(logits)`` is taken and goes through the model's
    ``step(token [1] int32, state, at) -> (float32 logits, state, {counter:
    amount}, what the model records of this id)``.  ``logits`` follow the id
    at ``position - 1``; ``record`` [new_tokens, ...] (None: nothing)
    receives each id's row.  -> (ids [new_tokens] int32, the logits each was
    chosen from [new_tokens, ...], the record, the state, the counters)."""

    def body(i, carry):
        logits, state, ids, chosen_from, record, counters = carry
        token = pick(logits).astype(jnp.int32)
        ids = ids.at[i].set(token)
        chosen_from = lax.dynamic_update_slice_in_dim(
            chosen_from, logits[None], i, axis=0)
        logits, state, moved, row = step(token[None], state, position + i)
        if record is not None:
            record = lax.dynamic_update_slice_in_dim(record, row[None], i,
                                                     axis=0)
        return (logits, state, ids, chosen_from, record,
                count(names, counters, **moved))

    _, state, ids, chosen_from, record, counters = lax.fori_loop(
        0, new_tokens, body,
        (logits, state, jnp.zeros((new_tokens,), jnp.int32),
         jnp.zeros((new_tokens,) + logits.shape, F32), record, counters))
    return ids, chosen_from, record, state, counters


def generate(model, params, ids, new_tokens: int):
    """Prefill, then decoding, of a `LanguageModel` record -> (new ids, the
    logits each was chosen from, the counters, what ``decode`` records, the
    state, what ``prefill`` records of the prompt)."""
    t = ids.shape[0]
    logits, state, counters, of_prompt = model.prefill(
        params, model.config, ids, max_len=t + new_tokens)
    new_ids, chosen_from, record, state, counters = model.decode(
        params, model.config, logits, state, counters, position=t,
        new_tokens=new_tokens)
    return new_ids, chosen_from, counters, record, state, of_prompt


# -- the routers' balance, for seeded weights ---------------------------------


def balanced_biases(x, layers: Iterable[Callable]):
    """The calibration pass's driver: ``x`` through each of ``layers`` in
    turn (``layer(x) -> (its output, its router's balanced bias or None)``:
    each is balanced before the next sees its output) -> the biases."""
    biases = []
    for layer in layers:
        x, bias = layer(x)
        if bias is not None:
            biases.append(bias)
    return biases
