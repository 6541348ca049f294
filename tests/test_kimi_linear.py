"""The Kimi-Linear language model (Kimi-Linear-48B-A3B's published keys) at
a small size, seeded weights: prefill and decode through BOTH kinds of state
- the KDA layers' matrix states and convolution tails, the full layers'
latent caches - against the plain reference's one full forward
(`benchmark/reference`) by logits; the rewriter's snapshot, which holds
a recurrent state and a latent cache side by side (a suffix entering one:
`tests/test_language_models.py`, every model's); latent attention without
its rotary embedding, and Kanana's path left as it was; one chip's share of
the experts against the uncut layer; the issue's arithmetic."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import deepseek_v3_sdxl as latent_ref  # noqa: E402
from benchmark.reference import kimi_linear_sdxl as ref  # noqa: E402
from distrifuser_tpu.models import deepseek_v3 as dsv3  # noqa: E402
from distrifuser_tpu.models import kimi_linear as lm  # noqa: E402
from distrifuser_tpu.ops import mla, moe  # noqa: E402

# the published keys, small: 5 layers (KDA KDA KDA MLA KDA, the first
# dense), 16 experts of which share 1 of 4 holds 4, chunks of 4 rows
JSON = {
    "model_type": "kimi_linear", "num_hidden_layers": 5, "vocab_size": 96,
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "q_lora_rank": None, "rope_scaling": None,
    "rope_theta": 10000, "rms_norm_eps": 1e-5, "mla_use_nope": True,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 16,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 4, "short_conv_kernel_size": 4},
    "num_experts": 4, "expert_parallel": {"chips": 4, "index": 1},
    "num_shared_experts": 1, "num_experts_per_token": 3,
    "routed_scaling_factor": 2.446, "moe_router_activation_func": "sigmoid",
    "moe_renormalize": True, "num_expert_group": 1, "topk_group": 1,
    "use_grouped_topk": True, "num_nextn_predict_layers": 0,
    "prefill_block": 8, "kda_chunk": 4,
}
CFG = lm.kimi_linear_config_from_json(JSON)
T, NEW = 40, 12


def init(dtype=jnp.float32, cfg=CFG):
    p = lm.init_kimi_linear_params(jax.random.PRNGKey(3), cfg, dtype)
    # norm scales away from their initial one
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 64))
    for lp in p["layers"]:
        inner = lp["attn"].get("kv_norm") or lp["attn"]["o_norm"]
        for norm in (lp["attn_norm"], lp["ffn_norm"], inner):
            norm["scale"] = (1.0 + 0.1 * jax.random.normal(
                next(keys), norm["scale"].shape)).astype(dtype)
    p["final_norm"]["scale"] = (1.0 + 0.1 * jax.random.normal(
        next(keys), p["final_norm"]["scale"].shape)).astype(dtype)
    return p


@pytest.fixture(scope="module")
def params():
    return init()


def token_ids(n, seed=5):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                                         CFG.vocab_size))


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1.0), (
        np.abs(a - b).max(), np.abs(b).max())


def reference_logits(params, ids, first=0, served_experts=None):
    with jax.default_matmul_precision("highest"):
        return ref.LanguageModel(JSON).logits(params, ids, first=first,
                                              served_experts=served_experts)


def leaf_count(tree):
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))


# -- the arithmetic -----------------------------------------------------------


def test_the_parameter_arithmetic_of_the_cut_from_the_programs_shapes():
    published = lm.KimiLinearConfig()
    assert published.kinds.count("kda") == 20 and published.kinds.count(
        "mla") == 7 and published.kinds[3::4] == ("mla",) * 6
    shapes = lm.param_shapes(published)
    dense, kda_layer, mla_layer = (shapes["layers"][i] for i in (0, 1, 3))
    assert leaf_count(kda_layer["attn"]) == 39_514_272
    assert leaf_count(mla_layer["attn"]) == 29_114_880
    assert leaf_count(kda_layer["ffn"]["experts"]) == 256 * 7_077_888
    assert leaf_count(kda_layer) - leaf_count(kda_layer["ffn"]["experts"]) \
        == 47_186_848
    assert leaf_count(mla_layer) - leaf_count(mla_layer["ffn"]["experts"]) \
        == 36_787_456
    assert leaf_count(kda_layer) == 1_859_126_176
    assert leaf_count(mla_layer) == 1_848_726_784
    assert leaf_count(dense) == 103_219_872
    assert leaf_count(shapes["embed"]) + leaf_count(shapes["head"]) \
        == 754_974_720
    assert leaf_count(shapes) == 49_122_681_728
    # one chip of eight, 12 of 27 layers, an eighth of the vocabulary
    held = lm.kimi_linear_config_from_json({
        "num_hidden_layers": 12, "num_experts": 32, "vocab_size": 20480,
        "linear_attn_config": dict(JSON["linear_attn_config"], head_dim=128,
                                   num_heads=32),
        "expert_parallel": {"chips": 8, "index": 0}})
    assert (held.num_experts, held.n_local_experts, held.first_local_expert,
            held.n_expert_layers) == (256, 32, 0, 11)
    assert held.kinds == ("kda", "kda", "kda", "mla") * 3
    shapes = lm.param_shapes(held)
    assert leaf_count(shapes["layers"][1]) == 273_679_264
    assert leaf_count(shapes["layers"][3]) == 263_279_872
    assert leaf_count(shapes["embed"]) + leaf_count(shapes["head"]) \
        == 94_371_840
    assert leaf_count(shapes) == 3_176_867_744
    # two kinds of state: nine bounded ones, three that grow with the length
    state = jax.eval_shape(lambda: lm.empty_state(held, 8704, jnp.bfloat16))
    sizes = [sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(layer))
             for layer in state["layers"]]
    assert sizes[0] == 32 * 128 * 128 * 4 + 3 * 12288 * 2
    assert sizes[3] == 8704 * 576 * 2
    assert sum(sizes[i] for i in (3, 7, 11)) == 30_081_024
    assert 9 * 32 * 128 * 128 * 4 == 18_874_368


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("rope_scaling", {"type": "yarn"}),
    ("num_expert_group", 8), ("moe_router_activation_func", "softmax"),
    ("moe_renormalize", False), ("num_nextn_predict_layers", 1),
    ("model_type", "deepseek_v3"), ("tie_word_embeddings", True)])
def test_a_setting_that_is_not_built_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        lm.kimi_linear_config_from_json(dict(JSON, **{key: value}))


def test_a_stack_the_published_lists_do_not_cover_is_refused():
    with pytest.raises(ValueError, match="name no kind"):
        lm.kimi_linear_config_from_json(dict(JSON, num_hidden_layers=28))
    with pytest.raises(ValueError, match="whole chunks"):
        lm.kimi_linear_config_from_json(dict(JSON, kda_chunk=3))


def test_the_gates_two_parameters_are_initialised_as_published():
    key = jax.random.PRNGKey(0)
    a_log = lm.init_leaf(key, "A_log", (4096,), CFG, jnp.float32)
    assert 0.0 <= float(a_log.min()) and float(a_log.max()) <= np.log(16.0)
    assert float(jnp.exp(a_log).mean()) == pytest.approx(8.5, abs=0.3)
    dt = jax.nn.softplus(lm.init_leaf(key, "dt_bias", (4096,), CFG,
                                      jnp.float32))
    assert 1e-3 * 0.999 <= float(dt.min()) and float(dt.max()) <= 1e-1 * 1.001
    assert float(jnp.log(dt).mean()) == pytest.approx(
        (np.log(1e-3) + np.log(1e-1)) / 2, abs=0.1)
    # every other leaf by the sibling's rule: a convolution's fan-in its taps
    conv = lm.init_leaf(key, "kernel", (4, 4096), CFG, jnp.float32)
    assert float(conv.std()) == pytest.approx(0.5, rel=0.05)


# -- the stack against the reference -----------------------------------------


def test_prefill_then_decode_through_both_kinds_of_state_is_the_full_forward(
        params):
    """Logits, not tokens.  Tolerance 3e-5 of the largest logit: float32
    against float32, the difference is the order of sums - the chunked form
    against the token-by-token recurrence, the absorbed form against
    materialised keys - through 5 layers."""
    ids = token_ids(T)
    new_ids, chosen_from, counters, experts = jax.jit(
        lambda p, i: lm.generate(p, CFG, i, NEW))(params, jnp.asarray(ids))
    seq = np.concatenate([ids, np.asarray(new_ids)[:-1]])
    want, slack = reference_logits(params, seq, first=T - 1,
                                   served_experts=np.asarray(
                                       experts)[:, :len(seq)])
    close(chosen_from, want, tol=3e-5)
    assert slack <= 1e-5
    assert np.array_equal(np.asarray(new_ids), np.asarray(want).argmax(-1))
    # ... and the reference's own choice of experts is the program's
    free, _ = reference_logits(params, seq, first=T - 1)
    close(chosen_from, free, tol=3e-5)
    names = dict(zip(lm.COUNTERS, np.asarray(counters).tolist()))
    assert names == {
        "tokens_prefilled": T, "tokens_reused": 0, "tokens_decoded": NEW,
        "expert_assignments": (T + NEW) * 4 * 3,
        "expert_assignments_held": names["expert_assignments_held"],
        "state_bytes": 4 * (4 * 16 * 16 + 3 * 192) * 4 + (T + NEW) * 40 * 4,
        "cache_rows_fetched": 0, "kda_chunks": 4 * T // 4}
    held = (np.asarray(experts) >= 4) & (np.asarray(experts) < 8)
    assert names["expert_assignments_held"] == int(held.sum()) > 0


def test_the_counters_are_the_siblings_and_the_new_one_is_last():
    assert lm.COUNTERS[:7] == dsv3.COUNTERS
    assert lm.COUNTERS[7:] == ("kda_chunks",)


def test_the_served_path_in_bfloat16_is_near_the_reference():
    """bfloat16 weights and activations, float32 matrix states: the served
    dtype's drive at a small size (a dtype bug in the carry shows here, not
    in the float32 tests).  Tolerance: bf16 has 8 bits of mantissa; through
    5 layers the logits' relative RMS error reads ~0.01."""
    p = init(jnp.bfloat16)
    ids = token_ids(T, seed=6)
    new_ids, chosen_from, _, experts = jax.jit(
        lambda p, i: lm.generate(p, CFG, i, NEW))(p, jnp.asarray(ids))
    assert chosen_from.dtype == jnp.float32
    seq = np.concatenate([ids, np.asarray(new_ids)[:-1]])
    want, _ = reference_logits(p, seq, first=T - 1, served_experts=np.asarray(
        experts)[:, :len(seq)])
    err = np.sqrt(np.mean(np.square(np.asarray(chosen_from) - np.asarray(
        want)))) / np.asarray(want).std()
    assert err < 0.05, err
    state = jax.eval_shape(lambda: lm.prefill(p, CFG, jnp.asarray(ids),
                                              max_len=T + NEW)[1])
    assert state["layers"][0]["s"].dtype == jnp.float32
    assert state["layers"][0]["conv"].dtype == jnp.bfloat16
    assert state["layers"][3]["c"].dtype == jnp.bfloat16


def test_a_matrix_state_in_bfloat16_moves_the_logits(params):
    """The benchmark's control at a small size: `state_dtype` bfloat16
    changes nothing but the KDA states' precision, and the logits move by
    four orders of magnitude more than float32's rounding."""
    ids = token_ids(T)
    low = lm.kimi_linear_config_from_json(dict(JSON, state_dtype="bfloat16"))
    sound = lm.generate(params, CFG, jnp.asarray(ids), NEW)[1]
    moved = lm.generate(params, low, jnp.asarray(ids), NEW)[1]
    assert 1e-3 < float(jnp.abs(sound - moved).max() / jnp.abs(sound).max())


# -- the snapshot ------------------------------------------------------------


def test_the_rewriter_snapshots_the_instruction_for_this_model_too(params):
    """`PromptRewriter` is handed this model as a value and PR 32's seam is
    untouched: the instruction's whole blocks are prefilled once, every
    request enters the snapshot - recurrent states and latent caches side by
    side -, and the ids are those of a full prefill."""
    from distrifuser_tpu.pipelines import (
        PromptRewriter,
        RewriteSpec,
        SimpleTokenizer,
    )

    spec = RewriteSpec(instruction_tokens=36, user_tokens=4, new_tokens=8,
                       prompt_tokens=4, instruction_seed=2)
    rw = PromptRewriter(CFG, params, spec, [SimpleTokenizer(1000)])
    assert rw._prefix_len == 32  # whole blocks of 8, some left to take
    out = rw(["a red fox"])
    first = rw.served[-1]
    kept = jax.tree.map(np.asarray, rw.snapshot())
    rw(["two blue birds over a lake"])
    second = rw.served[-1]
    for a, b in zip(jax.tree.leaves(rw.snapshot()), jax.tree.leaves(kept)):
        assert np.array_equal(np.asarray(a), b)
    state, _, of_prefix = rw.snapshot()
    assert of_prefix.shape[1] == 32  # the record of the snapshot's ids
    assert sorted(state["layers"][0]) == ["conv", "s"]
    assert sorted(state["layers"][3]) == ["c", "k_pe"]
    names = dict(zip(lm.COUNTERS, np.asarray(second.counters).tolist()))
    assert names["tokens_reused"] == 32 and names["tokens_prefilled"] == 40
    assert names["tokens_decoded"] == 8
    assert names["kda_chunks"] == 4 * 40 // 4  # the snapshot's 32 among them
    assert out[0].shape == (1, 77)
    # a full prefill of the same ids decodes the same tokens
    logits, state, counters, _ = rw._prefill(params, second.prompt_ids)
    new_ids, chosen_from, *_ = rw._decode(params, logits, state, counters,
                                          rw._tables)
    assert np.array_equal(new_ids, second.new_ids)
    close(chosen_from, second.logits, tol=1e-5)
    assert not np.array_equal(first.prompt_ids, second.prompt_ids)


# -- latent attention without a position -------------------------------------


def test_nope_leaves_the_64_wide_part_as_projected_and_kananas_path_as_it_was(
        params):
    """`mla_use_nope` is the model's own key: under it q_pe and k_pe are the
    projections' outputs; without it (Kanana's configuration, where the key
    is absent) `_queries_and_latents` is bit for bit the rotated one."""
    p = params["layers"][3]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(11), (T, CFG.hidden_size))
    positions = 7 + jnp.arange(T)
    q_nope, q_pe, c, k_pe = dsv3._queries_and_latents(p, CFG, x, positions)
    q = (x @ p["q"]["kernel"]).reshape(T, CFG.num_attention_heads, -1)
    kv = x @ p["kv_a"]["kernel"]
    assert np.array_equal(q_pe, q[..., CFG.qk_nope_head_dim:])
    assert np.array_equal(k_pe, kv[:, CFG.kv_lora_rank:])
    # Kanana's: the same shapes, the key at its default
    kanana = dsv3.DeepseekV3Config(
        hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
    assert kanana.mla_use_nope is False
    assert dsv3.deepseek_v3_config_from_json(
        {"n_routed_experts": 4}).mla_use_nope is False
    r_nope, r_pe, r_c, r_k = dsv3._queries_and_latents(p, kanana, x,
                                                       positions)
    assert np.array_equal(r_nope, q_nope)
    assert np.array_equal(r_pe, mla.rotary_interleaved(
        q[..., 16:], positions, kanana.rope_theta))
    assert np.array_equal(r_k, mla.rotary_interleaved(
        kv[:, 32:], positions, kanana.rope_theta))
    assert not np.array_equal(r_pe, q_pe)
    # the key, where a configuration gives it, reaches the switch
    assert dsv3.deepseek_v3_config_from_json(
        {"n_routed_experts": 4, "mla_use_nope": True}).mla_use_nope is True


def test_a_full_layer_is_the_references_unrotated_latent_attention(params):
    """The program's layer under NoPE, both forms, against the reference's
    materialised keys and values - and NOT the rotating reference's."""
    lp = params["layers"][3]
    x = jax.random.normal(jax.random.PRNGKey(12), (T, CFG.hidden_size))
    shape = ref.lm_shape(JSON)
    with jax.default_matmul_precision("highest"):
        want = ref.latent_attention(lp["attn"], shape, x)
        rotated = latent_ref.latent_attention(
            lp["attn"], dict(shape, theta=10000.0), x)
    out, _, _ = dsv3.attention_layer(lp["attn"], CFG, x, None, 0)
    close(out, want, tol=1e-5)
    assert float(jnp.abs(rotated - want).max()) > 1e-2
    cache = lm.empty_state(CFG, T, jnp.float32)["layers"][3]
    out, cache, _ = dsv3.attention_layer(lp["attn"], CFG, x[:24], cache, 0,
                                         visible=24)
    more, _, _ = dsv3.attention_layer(lp["attn"], CFG, x[24:], cache, 24,
                                      visible=T)
    close(jnp.concatenate([out, more]), want, tol=1e-5)


def test_a_kda_layer_is_the_references_token_by_token_recurrence(params):
    lp = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(13), (T, CFG.hidden_size))
    shape = ref.lm_shape(JSON)
    with jax.default_matmul_precision("highest"):
        o = jnp.concatenate([ref.kda_heads(lp["attn"], shape, x, first, 2)
                             for first in (0, 2)], axis=1)
        want = ref.kda_output(lp["attn"], shape, x, o)
    state = lm._empty_kda(CFG, jnp.float32)
    out, state = lm.kda_layer(lp["attn"], CFG, x, state)
    close(out, want, tol=1e-5)
    # one more token, by the recurrence, from the state the chunks left
    more = jax.random.normal(jax.random.PRNGKey(14), (1, CFG.hidden_size))
    step, _ = lm.kda_layer(lp["attn"], CFG, more, state)
    with jax.default_matmul_precision("highest"):
        both = jnp.concatenate([x, more])
        o = ref.kda_heads(lp["attn"], shape, both, 0, 4)
        want = ref.kda_output(lp["attn"], shape, both, o)
    close(step, want[-1:], tol=1e-5)


# -- the experts --------------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer(params):
    """What the four chips that share an expert layer each compute of it -
    the routed part of their own 4 of the 16 experts - summed, with the
    shared expert (every chip computes it alike) counted ONCE, is what the
    uncut reference gives for the whole layer."""
    e_all, held = CFG.num_experts, CFG.n_local_experts
    d, f = CFG.hidden_size, CFG.moe_intermediate_size
    k = iter(jax.random.split(jax.random.PRNGKey(8), 4))
    layer = params["layers"][1]["ffn"]
    w1 = jax.random.normal(next(k), (e_all, d, 2 * f)) / d ** 0.5
    w2 = jax.random.normal(next(k), (e_all, f, d)) / f ** 0.5
    u = jax.random.normal(next(k), (T, d))
    total, n_held = jnp.zeros((T, d)), 0
    for share in range(e_all // held):
        cfg = lm.kimi_linear_config_from_json(dict(
            JSON, expert_parallel={"chips": 4, "index": share}))
        assert cfg.first_local_expert == share * held
        part = dict(layer, experts={
            "w1": w1[share * held:(share + 1) * held],
            "w2": w2[share * held:(share + 1) * held]})
        idx, weights = moe.route(
            u, part["router"]["kernel"], part["e_score_correction_bias"],
            top_k=cfg.num_experts_per_token, scale=cfg.routed_scaling_factor)
        routed, n = moe.local_expert_sum(
            u, idx, weights, part["experts"]["w1"], part["experts"]["w2"],
            first_expert=cfg.first_local_expert, activation="silu")
        total, n_held = total + routed, n_held + int(n)
        # the layer as one chip runs it: its routed part plus the shared
        out, n_layer, _ = dsv3.moe_layer(part, cfg, u)
        close(out, routed + dsv3.gated_mlp(layer["shared"], u), tol=1e-5)
        assert int(n_layer) == int(n)
    assert n_held == T * CFG.num_experts_per_token  # every assignment, once
    uncut = dict(JSON, num_experts=e_all,
                 expert_parallel={"chips": 1, "index": 0})
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(dict(layer, experts={"w1": w1, "w2": w2}),
                              ref.lm_shape(uncut), u)
    close(total + dsv3.gated_mlp(layer["shared"], u), want, tol=2e-5)


def test_balanced_selection_bias_evens_the_held_experts_load(params):
    ids = jnp.asarray(token_ids(512, seed=21))
    biases = lm.balanced_selection_bias(params, CFG, ids)
    assert len(biases) == CFG.n_expert_layers
    assert all(b.shape == (CFG.num_experts,) for b in biases)

    def spread(p):
        _, _, _, experts = lm.prefill(p, CFG, ids, max_len=512)
        loads = [np.bincount(np.asarray(e).reshape(-1),
                             minlength=CFG.num_experts) for e in experts]
        return max(float(ld.max() / ld.mean()) for ld in loads)

    balanced = jax.tree.map(lambda a: a, params)
    for lp, b in zip(balanced["layers"][CFG.first_k_dense_replace:], biases):
        lp["ffn"] = dict(lp["ffn"], e_score_correction_bias=b)
    assert spread(balanced) < 1.1 < spread(params)


# -- the scopes --------------------------------------------------------------


def test_the_decode_step_carries_every_named_scope(params):
    ids = jnp.asarray(token_ids(T))
    logits, state, counters, _ = lm.prefill(params, CFG, ids, max_len=T + NEW)
    text = jax.jit(lambda p, lg, s, c: lm.decode(
        p, CFG, lg, s, c, position=T, new_tokens=NEW)).lower(
            params, logits, state, counters).compile().as_text()
    for scope in ("lm.kda.proj", "lm.kda.conv", "lm.kda.gate", "lm.kda.recur",
                  "lm.kda.norm", "lm.mla.proj", "lm.mla.attn",
                  "lm.moe.router", "lm.moe.experts", "lm.moe.shared",
                  "lm.mlp", "lm.head"):
        assert f"/{scope}/" in text, scope
