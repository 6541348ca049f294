"""The contract of `models/language_model.py`, held over every `LanguageModel`
record the repo has, each at a small size with seeded weights: what needs no
reference of the model's own.  A suffix through the state its prefix left
is the prefill of all the ids; a position without a state and a state
without room are refused; ``new_tokens`` off the model's ``decode_multiple``
is refused; generation is the same twice; the counters have the record's
names, written BY NAME (`models/lm_common.py count`) to the values the tree
before `lm_common` returned; the compiled prompt program holds no [T, T]
array; the seeded weights are that tree's, leaf for leaf.  What compares a
model with its float32 reference stays in the model's own test file.  A new
model adds one entry to `MODELS` (and its lines of
`tests/data/language_models.json`, which `python tests/test_language_models.py`
prints)."""

import functools
import hashlib
import json
import os
import re
import sys
from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from distrifuser_tpu.models import (  # noqa: E402
    deepseek_v3,
    evabyte,
    kimi_linear,
    lfm2,
    nemotron_h,
    sdar,
)

RECORDED = os.path.join(ROOT, "tests", "data", "language_models.json")


class Model(NamedTuple):
    config: Any
    init: Callable  # (key, config) -> the seeded tree
    # (position, T) of suffixes that enter the state of the ids before them
    splits: Tuple[Tuple[int, int], ...] = ((24, 16),)
    # the prompt's attention goes by blocks of queries (or by windows)
    blocked: bool = True


_LATENT = dict(
    vocab_size=96, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_attention_heads=4, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, n_local_experts=4,
    first_local_expert=4, prefill_block=8)
W, C = 32, 4  # EvaByte's window and chunk here
MODELS = {
    "nemotron_h": Model(nemotron_h.NemotronHConfig(
        pattern="MEM*E", vocab_size=256, hidden_size=64, mamba_num_heads=4,
        mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=8,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        n_routed_experts=64, n_local_experts=8, first_local_expert=24,
        num_experts_per_tok=6, moe_latent_size=32, moe_intermediate_size=48,
        moe_shared_expert_intermediate_size=64),
        nemotron_h.init_nemotron_h_params, splits=(
            (32, 8),  # one chunk of the scan enters the state of several
            (8, 32),  # several enter the state of one
            (0, 40)),  # from zero: no state to enter
        # one `*` layer in eleven, over a prompt of a thousand ids
        # (`ops/attention.py causal_gqa_sdpa`), and a request's 128 enter
        blocked=False),
    "evabyte": Model(evabyte.EvaByteConfig(
        num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
        intermediate_size=96, window_size=W, chunk_size=C),
        evabyte.init_evabyte_params, splits=(
            (W + 2 * C, 3 * C),  # inside one window
            (2 * W - 2 * C, 5 * C),  # across a window boundary
            (2 * W, 3 * C),  # from a window boundary
            (0, W + 4 * C),  # from zero: no state to enter
            (W - C, 2 * W + 3 * C),  # longer than a window
            (W + C, W - C))),  # to a window boundary
    "deepseek_v3": Model(deepseek_v3.DeepseekV3Config(
        num_hidden_layers=4, n_shared_experts=2, n_routed_experts=16,
        num_experts_per_tok=3, **_LATENT),
        deepseek_v3.init_deepseek_v3_params),
    "kimi_linear": Model(kimi_linear.KimiLinearConfig(
        num_hidden_layers=5, kda_layers=(1, 2, 3, 5), full_attn_layers=(4,),
        kda_num_heads=4, kda_head_dim=16, num_experts=16,
        num_experts_per_token=3, kda_chunk=4, **_LATENT),
        kimi_linear.init_kimi_linear_params),
    "sdar": Model(sdar.SdarConfig(
        num_hidden_layers=3, vocab_size=96, hidden_size=64,
        moe_intermediate_size=32, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, num_experts=16,
        n_local_experts=4, first_local_expert=4, num_experts_per_tok=3,
        prefill_block=8), sdar.init_sdar_params),
    "lfm2": Model(lfm2.Lfm2Config(
        num_hidden_layers=5, vocab_size=96, hidden_size=64,
        intermediate_size=96, moe_intermediate_size=32, num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, num_experts=16,
        n_local_experts=4, first_local_expert=4, num_experts_per_tok=3,
        prefill_block=8), lfm2.init_lfm2_params, splits=(
            (24, 16),
            (37, 3))),  # a suffix inside the convolution's 3-tap window
}
ENTERING = [name for name, m in MODELS.items() if m.splits]
SPLITS = [pytest.param(name, *split, id=f"{name}-{split[0]}+{split[1]}")
          for name in ENTERING for split in MODELS[name].splits]
T, NEW = 40, 12  # a prompt and what is decoded after it: whole blocks of all


def record(name):
    return MODELS[name].config.language_model()


@functools.lru_cache(maxsize=None)
def params(name, seed=3):
    return MODELS[name].init(jax.random.PRNGKey(seed), MODELS[name].config)


def token_ids(name, n, seed=5):
    lm = record(name)
    return jax.random.randint(jax.random.PRNGKey(seed), (n,),
                              lm.byte_offset or 0, lm.vocab_size)


def close(a, b, tol=1e-5):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1.0), (
        np.abs(a - b).max(), np.abs(b).max())


def named(name, counters):
    lm = record(name)
    assert counters.shape == (len(lm.counters),)
    assert counters.dtype == jnp.int32
    return dict(zip(lm.counters, np.asarray(counters).tolist()))


def one(names, ending):
    (name,) = [n for n in names if n.endswith(ending)]
    return name


@functools.lru_cache(maxsize=None)
def programs(name, t, new_tokens):
    """The record's prefill of ``t`` ids with room for ``new_tokens`` more,
    and its decode of those, each jitted."""
    lm = record(name)
    return (jax.jit(lambda p, ids: lm.prefill(p, lm.config, ids,
                                              max_len=t + new_tokens)),
            jax.jit(lambda p, logits, state, counters: lm.decode(
                p, lm.config, logits, state, counters, position=t,
                new_tokens=new_tokens)))


def prefill_and_decode(name, prompt, new_tokens=NEW):
    """-> (the counters after the prefill, `decode`'s results)."""
    prefill, decode = programs(name, len(prompt), new_tokens)
    logits, state, counters, _ = prefill(params(name), prompt)
    return counters, decode(params(name), logits, state, counters)


# -- a suffix entering the state its prefix left -------------------------------


@pytest.mark.parametrize("name,position,t", SPLITS)
def test_prefill_from_over_a_state_is_prefill_of_all_the_ids(name, position,
                                                             t):
    """The prompt prefilled whole, and its first ``position`` ids prefilled,
    then the other T through that state: the same logits, the same state
    leaf by leaf, the same record of those T, the same counters but for the
    ids reused, the same ids decoded from either - and the state handed in
    is read, not consumed: a second suffix enters it."""
    lm, p = record(name), params(name)
    cfg, end = lm.config, position + t
    ids = token_ids(name, end, seed=11)
    new = -(-(2 * lm.prompt_multiple + 1) // lm.decode_multiple
            ) * lm.decode_multiple
    room = end + new
    prefill, decode = programs(name, end, new)
    whole = prefill(p, ids)
    state = counters = before = None
    if position:
        _, state, counters, _ = jax.jit(lambda p, ids: lm.prefill(
            p, cfg, ids, max_len=room))(p, ids[:position])
        before = jax.tree.map(np.asarray, (state, counters))

    @jax.jit
    def enter(ids, state):
        return lm.prefill_from(p, cfg, ids, max_len=room, state=state,
                               counters=counters, position=position)

    entered = enter(ids[position:], state)
    close(entered[0], whole[0])
    for a, b in zip(jax.tree.leaves(entered[1]), jax.tree.leaves(whole[1]),
                    strict=True):
        close(a, b)
    for a, b in zip(jax.tree.leaves(entered[3]), jax.tree.leaves(whole[3]),
                    strict=True):
        assert np.array_equal(a, np.asarray(b)[:, position:])
    got, want = named(name, entered[2]), named(name, whole[2])
    reused = one(lm.counters, "_reused")
    assert got.pop(reused) == position and want.pop(reused) == 0
    assert got == want
    assert got[one(lm.counters, "_prefilled")] == end
    a, b = (decode(p, *out[:3]) for out in (entered, whole))
    assert np.array_equal(a[0], b[0])
    close(a[1], b[1])
    if not position:
        return
    for a, b in zip(jax.tree.leaves((state, counters)),
                    jax.tree.leaves(before), strict=True):
        assert np.array_equal(np.asarray(a), b)
    other = token_ids(name, t, seed=9)
    close(enter(other, state)[0],
          prefill(p, jnp.concatenate([ids[:position], other]))[0])
    # a suffix that ignored the state it enters would not be the prefill
    wrong = enter(ids[position:], jax.tree.map(jnp.zeros_like, state))
    assert float(jnp.abs(wrong[0] - whole[0]).max()) > 1e-3


@pytest.mark.parametrize("name", ENTERING)
def test_a_position_without_a_state_is_refused(name):
    lm = record(name)
    with pytest.raises(ValueError, match="needs the state"):
        lm.prefill_from(params(name), lm.config, token_ids(name, 16),
                        max_len=48, position=16)


@pytest.mark.parametrize("name", ENTERING)
def test_a_state_without_room_is_refused(name):
    lm, p = record(name), params(name)
    _, state, counters, _ = lm.prefill(p, lm.config, token_ids(name, 16),
                                       max_len=16)
    with pytest.raises(ValueError, match="no room"):
        lm.prefill_from(p, lm.config, token_ids(name, 16), max_len=32,
                        state=state, counters=counters, position=16)


# -- decoding ------------------------------------------------------------------


@pytest.mark.parametrize("name", MODELS)
def test_new_tokens_off_the_models_multiple_are_refused(name):
    """A model that decodes a block of positions together takes whole
    blocks; one that decodes id by id takes any number."""
    lm = record(name)
    new = lm.decode_multiple + 1
    if lm.decode_multiple > 1:
        with pytest.raises(ValueError, match="whole blocks"):
            prefill_and_decode(name, token_ids(name, T), new)
    else:
        _, (ids, chosen_from, *_) = prefill_and_decode(
            name, token_ids(name, T), new)
        assert ids.shape == (new,) and chosen_from.shape[0] == new


@pytest.mark.parametrize("name", MODELS)
def test_generation_is_the_same_twice(name):
    from distrifuser_tpu.models import lm_common

    run = jax.jit(lambda p, i: lm_common.generate(record(name), p, i, NEW))
    first, again = (run(params(name), token_ids(name, T)) for _ in range(2))
    for a, b in zip(jax.tree.leaves(first), jax.tree.leaves(again),
                    strict=True):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    ids = np.asarray(first[0])
    assert ids.shape == (NEW,) and ids.dtype == np.int32
    lm = record(name)
    assert ids.min() >= 0 and ids.max() < lm.vocab_size
    assert first[1].shape[0] == NEW and first[1].dtype == jnp.float32


@pytest.mark.parametrize("name", MODELS)
def test_the_counters_carry_the_records_names_and_count_what_is_decoded(name):
    lm = record(name)
    before, (*_, after) = prefill_and_decode(name, token_ids(name, T))
    before, after = named(name, before), named(name, after)
    decoded, prefilled = (one(lm.counters, end)
                          for end in ("_decoded", "_prefilled"))
    assert (before[prefilled], before[decoded]) == (T, 0)
    assert (after[prefilled], after[decoded]) == (T, NEW)
    assert all(after[n] >= before[n] for n in lm.counters)


# -- the counters are written by name ------------------------------------------


def test_the_helper_refuses_a_name_the_model_does_not_count():
    from distrifuser_tpu.models import lm_common

    for name in MODELS:
        names = record(name).counters
        zeros = jnp.zeros((len(names),), jnp.int32)
        with pytest.raises(KeyError, match="no counter named"):
            lm_common.count(names, zeros, tokens_imagined=1)
        with pytest.raises(KeyError, match="no counter named"):
            lm_common.count(names, zeros, put={"tokens_imagined": 1})
        moved = lm_common.count(names, zeros, put={names[0]: 7},
                                **{names[-1]: 2, names[1]: True})
        assert np.asarray(moved).tolist() == (
            [7, 1] + [0] * (len(names) - 3) + [2])


def served_counters(name):
    """Every counter by its name, after the prefill and after the decode of
    one prompt - and, where a suffix can enter a state, after it did."""
    lm, p = record(name), params(name)
    prompt = token_ids(name, T)
    before, (*_, after) = prefill_and_decode(name, prompt)
    out = {"prefill": named(name, before), "decode": named(name, after)}
    if lm.prefill_from is not None:
        _, state, counters, _ = jax.jit(lambda p, ids: lm.prefill(
            p, lm.config, ids, max_len=T + NEW))(p, prompt[:24])
        out["prefill_from"] = named(name, jax.jit(
            lambda p, ids, state, counters: lm.prefill_from(
                p, lm.config, ids, max_len=T + NEW, state=state,
                counters=counters, position=24))(
                    p, prompt[24:], state, counters)[2])
    return out


@pytest.mark.parametrize("name", MODELS)
def test_every_counter_holds_under_its_name_what_it_held_before(name):
    """The values are the parent tree's (PR 43), which wrote its counters by
    position: a name that moved, or took another's amount, shows here."""
    with open(RECORDED) as f:
        assert served_counters(name) == json.load(f)[name]["counters"]


# -- the seeded weights ----------------------------------------------------------


def leaf_digests(name):
    tree = MODELS[name].init(jax.random.PRNGKey(0), MODELS[name].config)
    return {jax.tree_util.keystr(path): hashlib.sha256(
        np.asarray(leaf).tobytes()).hexdigest()[:12]
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("name", MODELS)
def test_seeded_weights_are_the_parents_leaf_for_leaf(name):
    """The same key to the same leaf: every leaf of `PRNGKey(0)`'s tree, bit
    for bit what the tree before `lm_common.init_params` made of it."""
    with open(RECORDED) as f:
        want = json.load(f)[name]["leaves"]
    got = leaf_digests(name)
    assert list(got) == list(want)
    assert {k: v for k, v in got.items() if want[k] != v} == {}


# -- the compiled prompt program -------------------------------------------------


@pytest.mark.parametrize("name", [n for n in ENTERING if MODELS[n].blocked])
def test_no_array_of_all_positions_squared_in_the_prompts_program(name):
    """Blocked by queries (and EVA by windows): the compiled prefill of 1024
    ids holds no array with the prompt's length twice among its dims."""
    lm, t = record(name), 1024
    text = jax.jit(lambda p, i: lm.prefill(p, lm.config, i, max_len=t)[0]
                   ).lower(params(name), jnp.zeros((t,), jnp.int32)
                           ).compile().as_text()
    shapes = {tuple(int(n) for n in dims.split(","))
              for dims in re.findall(r"\[((?:\d+,)+\d+)\]", text)}
    assert any(t in s for s in shapes)  # the prompt's rows are there
    assert not [s for s in shapes if s.count(t) >= 2]


if __name__ == "__main__":  # the file of recorded values, from THIS tree
    json.dump({name: {"counters": served_counters(name),
                      "leaves": leaf_digests(name)} for name in MODELS},
              sys.stdout, indent=1)
