"""The LFM2 language model (LFM2-24B-A2B's published keys) at a small size,
seeded weights: prefill and decode through BOTH kinds of state - the conv
layers' tails of gated inputs, the attention layers' KV caches, two KV heads
a row - against the plain reference's one cache-less forward
(`benchmark/reference`) by logits and by the experts chosen; a prompt split
at every position of the 3-tap window; what the tail holds; the two controls
(tails not carried, caches in float8); stacks other than the published
pattern; one chip's share of the experts against the uncut layer; the ops
this model brought - the gated short convolution, the router's constant, the
tied head, a 64-wide head against a cache of 128-wide rows -; the issue's
arithmetic."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import lfm2_sdxl as ref  # noqa: E402
from distrifuser_tpu.models import lfm2 as lm  # noqa: E402
from distrifuser_tpu.models import lm_common  # noqa: E402
from distrifuser_tpu.ops import gqa_cache, moe, short_conv, ssm  # noqa: E402
from distrifuser_tpu.ops.attention import gqa_sdpa_by_query_block  # noqa: E402

PUBLISHED_TYPES = ["full_attention" if i % 4 == 2 else "conv"
                   for i in range(40)]
# the published keys, small: 7 layers (conv conv ATTN conv conv conv ATTN,
# the first two dense), 16 experts of which share 1 of 4 holds 4, heads of
# 16 over 2 KV heads (a cache row holds both)
JSON = {
    "model_type": "lfm2_moe", "num_hidden_layers": 7, "vocab_size": 96,
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_dense_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "layer_types": PUBLISHED_TYPES,
    "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
    "norm_topk_prob": True, "use_expert_bias": True,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "num_experts": 4, "expert_parallel": {"chips": 4, "index": 1},
    "num_experts_per_tok": 3, "routed_scaling_factor": 1,
    "max_position_embeddings": 128000, "prefill_block": 8,
}
CFG = lm.lfm2_config_from_json(JSON)
T, NEW = 40, 12


def init(dtype=jnp.float32, cfg=CFG):
    p = lm.init_lfm2_params(jax.random.PRNGKey(3), cfg, dtype)
    # norm scales away from their initial one
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 64))
    norms = [lp[n] for lp in p["layers"] for n in ("operator_norm",
                                                   "ffn_norm")]
    for norm in norms + [p["final_norm"]]:
        norm["scale"] = (1.0 + 0.1 * jax.random.normal(
            next(keys), norm["scale"].shape)).astype(dtype)
    return p


@pytest.fixture(scope="module")
def params():
    return init()


def token_ids(n, seed=5, cfg=CFG):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                                         cfg.vocab_size))


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1.0), (
        np.abs(a - b).max(), np.abs(b).max())


def rel_error(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1.0))


def reference_logits(params, ids, first=0, served_experts=None, json=JSON):
    with jax.default_matmul_precision("highest"):
        return ref.LanguageModel(json).logits(params, ids, first=first,
                                              served_experts=served_experts)


def leaf_count(tree):
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))


def generate(params, cfg, prompt, new_tokens=NEW):
    return jax.jit(lambda p, i: lm.generate(p, cfg, i, new_tokens))(
        params, jnp.asarray(prompt))


def prefill(cfg, params, ids, *, max_len=T, state=None, counters=None,
            position=0):
    """`lm.prefill`, compiled."""
    return jax.jit(lambda p, i, s, c: lm.prefill(
        p, cfg, i, max_len=max_len, state=s, counters=c,
        position=position))(params, jnp.asarray(ids), state, counters)


def served_against_reference(params, cfg, json, prompt, new_tokens=NEW):
    """-> (the served logits, the reference's over prompt + served ids and
    the served choice of experts, the router slack)."""
    ids, logits, _, experts = generate(params, cfg, prompt, new_tokens)
    all_ids = np.concatenate([prompt, np.asarray(ids)[:-1]])
    want, slack = reference_logits(
        params, all_ids, first=len(prompt) - 1,
        served_experts=np.asarray(experts)[:, :len(all_ids)], json=json)
    return logits, want, slack


# -- the arithmetic -----------------------------------------------------------


def test_the_parameter_arithmetic_of_the_cut_from_the_programs_shapes():
    published = lm.Lfm2Config()
    assert list(published.kinds) == PUBLISHED_TYPES
    assert (published.kinds.count("conv"),
            published.kinds.count("full_attention")) == (30, 10)
    assert (published.head_dim, published.kv_pack) == (64, 2)
    shapes = lm.param_shapes(published)
    dense, conv, attn = (shapes["layers"][i] for i in (0, 3, 2))
    assert leaf_count(conv["mixer"]) == 16_783_360
    assert leaf_count(attn["mixer"]) == 10_485_888
    assert leaf_count(conv["ffn"]["experts"]) == 64 * 9_437_184
    assert leaf_count(conv["ffn"]) - leaf_count(conv["ffn"]["experts"]) \
        == 131_136
    assert leaf_count(dense) == 89_139_200
    assert leaf_count(conv) == 620_898_368
    assert leaf_count(attn) == 614_600_896
    assert leaf_count(shapes["embed"]) == 134_217_728 and "head" not in shapes
    assert leaf_count(shapes) == 23_843_661_440
    # chip 0 of four, 20 of 40 layers, a quarter of the vocabulary
    held = lm.lfm2_config_from_json({
        "num_hidden_layers": 20, "num_experts": 16, "vocab_size": 16384,
        "layer_types": PUBLISHED_TYPES,
        "expert_parallel": {"chips": 4, "index": 0}})
    assert (held.num_experts, held.n_local_experts, held.first_local_expert,
            held.n_expert_layers) == (64, 16, 0, 18)
    assert (held.kinds.count("conv"), held.kinds.count("full_attention")) \
        == (15, 5)
    shapes = lm.param_shapes(held)
    assert leaf_count(shapes["layers"][3]) == 167_913_536
    assert leaf_count(shapes["layers"][2]) == 161_616_064
    assert leaf_count(shapes) == 3_202_791_168
    # two kinds of state: fifteen bounded ones, five that grow with the
    # length - their rows 128 wide, two KV heads each: nothing padded
    state = jax.eval_shape(lambda: lm.empty_state(held, 8704, jnp.bfloat16))
    sizes = [sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(layer))
             for layer in state["layers"]]
    assert sizes[0] == 2 * 2048 * 2
    assert state["layers"][2]["k"].shape == (4, 8704, 128)
    assert sizes[2] == 2 * 8 * 8704 * 64 * 2
    assert sum(sizes) == 5 * 17_825_792 + 15 * 8192 == 89_251_840


def test_what_the_module_does_not_build_is_refused():
    for key, value in (("conv_bias", True), ("use_expert_bias", False),
                       ("norm_topk_prob", False), ("model_type", "lfm2"),
                       ("tie_word_embeddings", False)):
        with pytest.raises(ValueError, match=f"only {key}"):
            lm.lfm2_config_from_json(dict(JSON, **{key: value}))
    with pytest.raises(ValueError, match="only rope_type"):
        lm.lfm2_config_from_json(dict(JSON, rope_parameters={
            "rope_theta": 1e6, "rope_type": "yarn"}))
    with pytest.raises(ValueError, match="a layer is one of"):
        lm.lfm2_config_from_json(dict(
            JSON, layer_types=["conv", "sliding_attention"] * 4))
    with pytest.raises(ValueError, match="names no kind"):
        lm.lfm2_config_from_json(dict(JSON, layer_types=["conv"] * 3))
    assert (CFG.num_experts, CFG.n_local_experts, CFG.first_local_expert) \
        == (16, 4, 4)
    assert CFG.kinds == ("conv", "conv", "full_attention", "conv", "conv",
                         "conv", "full_attention")
    assert CFG.rope_theta == 1e6 and CFG.kv_pack == 2


# -- the system against the reference ------------------------------------------


def test_prefill_and_decode_through_tails_and_caches_are_the_references_forward(
        params):
    """The logits of every served position, and the experts chosen: the
    reference routes for itself here (no served choice handed in) and must
    choose what the program chose at every position of every expert layer."""
    prompt = token_ids(T)
    ids, logits, counters, experts = generate(params, CFG, prompt)
    all_ids = np.concatenate([prompt, np.asarray(ids)[:-1]])
    want, _ = reference_logits(params, all_ids, first=T - 1)
    close(logits, want)
    forced, slack = reference_logits(
        params, all_ids, first=T - 1,
        served_experts=np.asarray(experts)[:, :len(all_ids)])
    assert slack == 0.0  # the program's choice IS the reference's
    close(forced, want, tol=1e-6)
    c = dict(zip(lm.COUNTERS, np.asarray(counters).tolist()))
    assert (c["tokens_prefilled"], c["tokens_reused"], c["tokens_decoded"]) \
        == (T, 0, NEW)
    assert c["expert_assignments"] == (T + NEW) * 5 * 3
    held = (np.asarray(experts)[:, :T + NEW] >= 4) & (
        np.asarray(experts)[:, :T + NEW] < 8)
    assert c["expert_assignments_held"] == held.sum()
    # five tails [2, 64] and two caches k, v [1, 52, 32], float32
    assert c["state_bytes"] == 4 * (5 * 2 * 64 + 2 * 2 * 52 * 32)
    assert c["cache_rows_fetched"] == 0  # the XLA route


@pytest.mark.parametrize("rows", [1, 2, 3])
def test_a_suffix_of_few_rows_entering_a_tail_is_the_whole(params, rows):
    """A prompt split at every position of the 3-tap window: the last 1, 2,
    3 ids through the state the ids before them left."""
    ids = jnp.asarray(token_ids(T, seed=7))
    whole = prefill(CFG, params, ids)
    _, state, counters, _ = prefill(CFG, params, ids[:T - rows])
    entered = prefill(CFG, params, ids[T - rows:], state=state,
                      counters=counters, position=T - rows)
    close(entered[0], whole[0])
    for a, b in zip(jax.tree.leaves(entered[1]), jax.tree.leaves(whole[1]),
                    strict=True):
        close(a, b)


def test_the_tail_holds_the_gated_inputs(params):
    """Layer 0's tail after a prompt: the last two rows of g = B * z - not
    of the normed input h, which a control that kept it would hold."""
    ids = jnp.asarray(token_ids(T, seed=9))
    _, state, _, _ = prefill(CFG, params, ids)
    lp = params["layers"][0]
    h = ref.rms_norm(lp["operator_norm"]["scale"], params["embed"][ids],
                     CFG.norm_eps)
    b, _, z = jnp.split(h @ lp["mixer"]["in_proj"]["kernel"], 3, axis=-1)
    tail = state["layers"][0]["tail"]
    close(tail, (b * z)[-2:], tol=1e-5)
    assert rel_error(tail, h[-2:]) > 0.1


def variant(**over):
    json = dict(JSON, **over)
    return lm.lfm2_config_from_json(json), json


@pytest.mark.parametrize("over, floor", [
    ({"carry_conv_tails": False}, 0.05),
    ({"cache_dtype": "float8_e4m3fn"}, 2e-3)])
def test_a_control_reads_outside_the_tolerance(params, over, floor):
    """The tails not carried into decoding (what a wrong `prefill_from`
    would compute), the caches a precision below: neither is the model."""
    cfg, _ = variant(**over)
    prompt = token_ids(T, seed=13)
    logits, want, _ = served_against_reference(params, cfg, JSON, prompt)
    assert rel_error(logits, want) > floor
    sound, want, _ = served_against_reference(params, CFG, JSON, prompt)
    close(sound, want)


def test_the_references_float8_forward_is_the_program_with_a_float8_cache(
        params):
    """`lm_cache_float8_nearness` compares the served logits with the
    reference's forward over keys and values rounded to float8 for every
    query past the prompt: what the program computes with such a cache (the
    prompt attends over its own keys) - and far from the forward as stated."""
    cfg, _ = variant(cache_dtype="float8_e4m3fn")
    prompt = token_ids(T, seed=25)
    ids, logits, _, experts = generate(params, cfg, prompt)
    all_ids = np.concatenate([prompt, np.asarray(ids)[:-1]])
    routing = np.asarray(experts)[:, :len(all_ids)]
    with jax.default_matmul_precision("highest"):
        model = ref.LanguageModel(JSON)
        stated, _ = model.logits(params, all_ids, T - 1, routing)
        low, _ = model.logits(params, all_ids, T - 1, routing, float8_from=T)
    assert rel_error(logits, low) < 1e-4 < 2e-3 < rel_error(logits, stated)


def test_tails_not_carried_into_a_suffix_are_not_the_prefill(params):
    cfg, _ = variant(carry_conv_tails=False)
    ids = jnp.asarray(token_ids(T, seed=15))
    whole = prefill(CFG, params, ids)[0]
    _, state, counters, _ = prefill(cfg, params, ids[:24])
    entered = prefill(cfg, params, ids[24:], state=state, counters=counters,
                      position=24)[0]
    assert rel_error(entered, whole) > 1e-3


@pytest.mark.parametrize("name, over, kinds, n_expert_layers", [
    ("all-conv", {"layer_types": ["conv"] * 7}, ("conv",) * 7, 5),
    ("all-attention", {"layer_types": ["full_attention"] * 7},
     ("full_attention",) * 7, 5),
    ("no-dense-layer", {"num_dense_layers": 0}, CFG.kinds, 7),
    ("all-dense", {"num_dense_layers": 7}, CFG.kinds, 0)])
def test_other_patterns_build_the_stack_they_describe(name, over, kinds,
                                                      n_expert_layers):
    cfg, json = variant(**over)
    assert cfg.kinds == kinds and cfg.n_expert_layers == n_expert_layers
    p = init(cfg=cfg)
    assert ["router" in lp["ffn"] for lp in p["layers"]] == [
        i >= cfg.num_dense_layers for i in range(7)]
    assert [("in_proj" in lp["mixer"]) for lp in p["layers"]] == [
        k == "conv" for k in kinds]
    state = jax.eval_shape(lambda: lm.empty_state(cfg, 16, jnp.float32))
    assert [sorted(layer) for layer in state["layers"]] == [
        ["tail"] if k == "conv" else ["k", "v"] for k in kinds]
    logits, want, slack = served_against_reference(
        p, cfg, json, token_ids(24, seed=17), new_tokens=6)
    close(logits, want)
    assert slack == 0.0


def test_in_bfloat16_the_served_logits_keep_the_float32_reference():
    p = init(jnp.bfloat16)
    logits, want, slack = served_against_reference(p, CFG, JSON,
                                                   token_ids(T, seed=19))
    err = np.sqrt(np.mean(np.square(np.asarray(logits) - np.asarray(want)),
                          axis=1)) / np.asarray(want).std(axis=1)
    assert np.median(err) < 0.05 and slack < 0.05, (np.median(err), slack)


def test_generation_through_the_kernels_route_is_the_xla_routes(
        params, monkeypatch):
    """What a TPU gives a decode step - `ops/gqa_cache.py
    streamed_gqa_attention`, interpreted here, blocks of 4 cache rows, one
    query row of 4 heads over ONE row-group of two KV heads - in the place
    of `cache_attention` for every one-row call against a cache: the same
    ids from the same logits, and the counter the rows in view."""
    prompt = jnp.asarray(token_ids(T, seed=21))
    want_ids, want_logits, _, want_experts = generate(params, CFG, prompt)

    def as_on_a_tpu(q, k, v, *, limits, visible=None):
        if q.shape[0] > 1:
            return gqa_cache.cache_attention(q, k, v, limits=limits,
                                             visible=visible)
        assert k.shape == (1, T + NEW, 32)  # the whole cache, never a slice
        return gqa_cache.streamed_gqa_attention(q, k, v, limits,
                                                block_rows=4, interpret=True)

    monkeypatch.setattr(lm, "cache_attention", as_on_a_tpu)
    ids, logits, counters, experts = jax.block_until_ready(
        generate(params, CFG, prompt))
    assert np.array_equal(ids, want_ids)
    close(logits, want_logits, tol=1e-5)
    assert np.array_equal(experts, want_experts)
    c = dict(zip(lm.COUNTERS, np.asarray(counters).tolist()))
    # a step at position p fetches rows 0 .. p to a copy's 4, in 2 layers
    assert c["cache_rows_fetched"] == 2 * sum(
        -(-(p + 1) // 4) * 4 for p in range(T, T + NEW))


# -- the share and the model -----------------------------------------------------


def test_the_four_shares_add_up_to_the_uncut_expert_layer(params):
    """Experts 4 k .. 4 k + 3 each: the parts the 4 shares give of one
    expert layer add up to what the uncut reference gives for the whole
    layer (no shared expert to count once)."""
    whole_cfg = lm.lfm2_config_from_json(dict(
        JSON, num_experts=16, expert_parallel={"chips": 1, "index": 0}))
    whole = init(cfg=whole_cfg)["layers"][3]["ffn"]
    u = jax.random.normal(jax.random.PRNGKey(23), (24, 64))
    parts = []
    for index in range(4):
        cfg = lm.lfm2_config_from_json(dict(
            JSON, expert_parallel={"chips": 4, "index": index}))
        lo = cfg.first_local_expert
        assert (lo, cfg.n_local_experts) == (4 * index, 4)
        share = dict(whole, experts={
            name: w[lo:lo + 4] for name, w in whole["experts"].items()})
        routed, held, idx = lm.moe_layer(share, cfg, u)
        assert held == np.sum((np.asarray(idx) >= lo)
                              & (np.asarray(idx) < lo + 4))
        parts.append(routed)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(whole, ref.lm_shape(dict(
            JSON, num_experts=16, expert_parallel={"chips": 1, "index": 0})),
            u)
    close(sum(parts), want)
    assert rel_error(parts[0], want) > 0.1  # one share is not the layer


# -- the ops ---------------------------------------------------------------------


def plain_gated_conv(bcx, kernel, tail):
    """The published formula, row by row, in float64."""
    bcx, kernel, tail = (np.asarray(a, np.float64) for a in (bcx, kernel,
                                                              tail))
    b, c, z = np.split(bcx, 3, axis=-1)
    g = np.concatenate([tail, b * z])
    k = kernel.shape[0]
    conv = np.stack([sum(kernel[i] * g[t + i] for i in range(k))
                     for t in range(bcx.shape[0])])
    return c * conv, g[-(k - 1):]


@pytest.mark.parametrize("rows, taps", [(12, 3), (1, 3), (2, 3), (5, 4)])
def test_the_gated_short_convolution_is_the_plain_formula(rows, taps):
    """Prompt form (a zero tail), a suffix entering a tail and one row,
    alike; the tail that leaves is of the gated inputs."""
    keys = jax.random.split(jax.random.PRNGKey(rows), 3)
    bcx = jax.random.normal(keys[0], (rows, 3 * 16))
    kernel = jax.random.normal(keys[1], (taps, 16))
    for tail in (jnp.zeros((taps - 1, 16)),
                 jax.random.normal(keys[2], (taps - 1, 16))):
        y, left = short_conv.gated_short_conv(bcx, kernel, tail)
        want, want_tail = plain_gated_conv(bcx, kernel, tail)
        close(y, want, tol=1e-5)
        close(left, want_tail, tol=1e-6)
    # row by row through the tail is the whole
    tail, out = jnp.zeros((taps - 1, 16)), []
    for t in range(rows):
        y, tail = short_conv.gated_short_conv(bcx[t:t + 1], kernel, tail)
        out.append(y)
    close(jnp.concatenate(out), plain_gated_conv(
        bcx, kernel, np.zeros((taps - 1, 16)))[0], tol=1e-5)


def test_a_convolution_without_bias_is_the_one_with_a_zero_bias():
    """`ops/ssm.py causal_conv1d`: ``bias`` None is no array in the program;
    with a bias the result is bit for bit what it was (Nemotron's, Kimi's)."""
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    x, kernel, tail = (jax.random.normal(keys[0], (9, 8)),
                       jax.random.normal(keys[1], (4, 8)),
                       jax.random.normal(keys[2], (3, 8)))
    bias = jax.random.normal(keys[3], (8,))
    none, zero = (ssm.causal_conv1d(x, kernel, b, tail)
                  for b in (None, jnp.zeros((8,))))
    assert np.array_equal(none[0], zero[0]) and np.array_equal(none[1],
                                                               zero[1])
    # the sum in the order the parent took it: bias, then tap 0, 1, ..
    padded = jnp.concatenate([tail, x])
    want = bias
    for i in range(4):
        want = want + padded[i:i + 9] * kernel[i]
    assert np.array_equal(ssm.causal_conv1d(x, kernel, bias, tail)[0], want)


@pytest.mark.parametrize("scoring, bias", [("sigmoid", True),
                                           ("sigmoid", False),
                                           ("softmax", False)])
def test_the_routers_constant_is_the_published_formula_and_absent_by_default(
        scoring, bias):
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    u = jax.random.normal(keys[0], (11, 32))
    kernel = jax.random.normal(keys[1], (32, 16)) / 4
    b = 0.1 * jax.random.normal(keys[2], (16,)) if bias else None
    idx, weights = moe.route(u, kernel, b, top_k=4, scale=1.5,
                             scoring=scoring, denominator_eps=1e-6)
    logits = np.asarray(u, np.float64) @ np.asarray(kernel, np.float64)
    s = (1 / (1 + np.exp(-logits)) if scoring == "sigmoid" else
         np.exp(logits) / np.exp(logits).sum(-1, keepdims=True))
    chosen = np.take_along_axis(s, np.asarray(idx), axis=-1)
    want_idx = np.argsort(-(s + (0 if b is None else np.asarray(b))),
                          axis=-1)[:, :4]
    assert np.array_equal(np.sort(idx, -1), np.sort(want_idx, -1))
    close(weights, 1.5 * chosen / (chosen.sum(-1, keepdims=True) + 1e-6),
          tol=1e-5)
    # the four callers, which name no constant: the parent's expression
    plain_idx, plain = moe.route(u, kernel, b, top_k=4, scale=1.5,
                                 scoring=scoring)
    s32 = (jax.nn.sigmoid if scoring == "sigmoid" else jax.nn.softmax)(
        jnp.dot(u, kernel, precision=jax.lax.Precision.HIGHEST))
    c32 = jnp.take_along_axis(s32, plain_idx, axis=-1)
    assert np.array_equal(plain,
                          1.5 * c32 / jnp.sum(c32, axis=-1, keepdims=True))
    assert np.array_equal(plain_idx, idx)
    text = jax.jit(lambda u: moe.route(u, kernel, b, top_k=4,
                                       scoring=scoring)).lower(u).as_text()
    assert "1.000000e-06" not in text and "9.99999997E-7" not in text


def test_the_tied_head_is_the_embeddings_transpose_and_an_untied_one_is_as_it_was():
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    x = jax.random.normal(keys[0], (5, 64))
    tree = {"embed": jax.random.normal(keys[1], (96, 64)),
            "final_norm": {"scale": jnp.full((64,), 1.1)}}
    normed = lm_common.rms_norm(tree["final_norm"]["scale"], x, 1e-5)
    with jax.default_matmul_precision("highest"):
        close(lm_common.head(tree, x, 1e-5), normed @ tree["embed"].T,
              tol=1e-6)
        kernel = jax.random.normal(keys[2], (64, 96))
        untied = dict(tree, head={"kernel": kernel})
        assert np.array_equal(lm_common.head(untied, x, 1e-5), jnp.dot(
            normed, kernel, preferred_element_type=jnp.float32))
    # no transposed copy of the embedding in the program
    text = jax.jit(lambda t, x: lm_common.head(t, x, 1e-5)).lower(
        tree, x).as_text()
    assert "transpose" not in text


@pytest.mark.parametrize("rows, position", [(1, 37), (1, 8), (3, 20)])
def test_heads_of_64_against_rows_of_128_are_the_plain_grouped_attention(
        rows, position):
    """32 query heads over 8 KV heads of 64, two KV heads a cache row:
    widened queries against [4, S, 128] rows - through the XLA form and
    through the single-pass kernel, interpreted - are
    `gqa_sdpa_by_query_block` over the 64-wide heads themselves."""
    hq, hkv, d, s, pack = 32, 8, 64, 48, 2
    keys = jax.random.split(jax.random.PRNGKey(position), 3)
    q = jax.random.normal(keys[0], (rows, hq, d))
    k = jax.random.normal(keys[1], (s, hkv, d))
    v = jax.random.normal(keys[2], (s, hkv, d))
    limits = position + jnp.arange(rows)
    want = gqa_sdpa_by_query_block(q, k.swapaxes(0, 1), v.swapaxes(0, 1),
                                   q_positions=limits)
    wide = lm.widen_queries(q * np.sqrt(pack), pack, hq // hkv)
    rows_k, rows_v = lm.pack_rows(k, pack), lm.pack_rows(v, pack)
    assert wide.shape == (rows, hq, 128) and rows_k.shape == (4, s, 128)
    out, fetched = gqa_cache.cache_attention(wide, rows_k, rows_v,
                                             limits=limits)
    close(lm.own_slots(out, pack, hq // hkv), want, tol=1e-5)
    assert fetched == 0
    out, fetched = jax.block_until_ready(gqa_cache.streamed_gqa_attention(
        wide, rows_k, rows_v, limits, block_rows=16, interpret=True))
    close(lm.own_slots(out, pack, hq // hkv), want, tol=1e-5)
    assert fetched == -(-(position + rows) // 16) * 16
