"""The DeepSeek-V3-style language model (Kanana-2's published keys) at a
small size, seeded weights: prefill and decode through the latent cache
against the plain reference's one full forward (`benchmark/reference`) by
logits; the two forms of latent attention against each other; the rewriter's
snapshot (a suffix entering one: `tests/test_language_models.py`, every
model's); one chip's share of the experts against the uncut
layer; the gated form of the expert kernels; the decode step's single-pass
attention kernel (interpreted) against the XLA form, alone and as the
model's route; the issue's arithmetic."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import deepseek_v3_sdxl as ref  # noqa: E402
from distrifuser_tpu.models import deepseek_v3 as lm  # noqa: E402
from distrifuser_tpu.ops import mla, moe  # noqa: E402

# the published keys, small: 4 layers (one dense), 16 experts of which
# share 1 of 4 holds 4, queries 8 at a time
JSON = {
    "model_type": "deepseek_v3", "num_hidden_layers": 4, "vocab_size": 96,
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "q_lora_rank": None, "rope_scaling": None,
    "rope_interleave": True, "rope_theta": 1000000, "rms_norm_eps": 1e-6,
    "n_routed_experts": 4, "expert_parallel": {"chips": 4, "index": 1},
    "n_shared_experts": 2, "num_experts_per_tok": 3,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "prefill_block": 8,
}
CFG = lm.deepseek_v3_config_from_json(JSON)
T, NEW = 40, 12


def init(dtype=jnp.float32):
    p = lm.init_deepseek_v3_params(jax.random.PRNGKey(3), CFG, dtype)
    # norm scales away from their initial one
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 64))
    for lp in p["layers"]:
        for norm in (lp["attn_norm"], lp["ffn_norm"], lp["attn"]["kv_norm"]):
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
    published = lm.DeepseekV3Config()
    shapes = lm.param_shapes(published)
    dense, expert = shapes["layers"][0], shapes["layers"][1]
    assert leaf_count(dense["attn"]) == 26_345_984
    assert leaf_count(expert["ffn"]["experts"]) == 128 * 4_718_592
    assert leaf_count(expert) - leaf_count(expert["ffn"]["experts"]) \
        == 36_049_536
    assert leaf_count(expert) == 640_029_312
    assert leaf_count(dense) == 64_098_816
    assert leaf_count(shapes["embed"]) + leaf_count(shapes["head"]) \
        == 525_336_576
    assert leaf_count(shapes) == 30_670_815_104
    # one chip of eight, 24 of 48 layers, an eighth of the vocabulary
    held = lm.deepseek_v3_config_from_json({
        "num_hidden_layers": 24, "n_routed_experts": 16,
        "vocab_size": 16032, "expert_parallel": {"chips": 8, "index": 0}})
    assert (held.n_routed_experts, held.n_local_experts,
            held.first_local_expert, held.n_expert_layers) == (128, 16, 0, 23)
    shapes = lm.param_shapes(held)
    assert leaf_count(shapes["layers"][1]) == 111_547_008
    assert leaf_count(shapes) == 2_695_349_120
    # the latent cache: 576 numbers a position and layer
    state = jax.eval_shape(lambda: lm.empty_state(held, 8704, jnp.bfloat16))
    assert sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(
        state["cache"])) == 24 * 8704 * 576 * 2 == 240_648_192


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("rope_scaling", {"type": "yarn"}),
    ("rope_interleave", False), ("n_group", 8), ("scoring_func", "softmax"),
    ("model_type", "deepseek_v2")])
def test_a_setting_that_is_not_built_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        lm.deepseek_v3_config_from_json(dict(JSON, **{key: value}))


# -- the served path against the reference ------------------------------------


def served(params, ids):
    # (the interpreted kernel's callbacks run JAX ops of their own: wait for
    # them before this thread dispatches more)
    return jax.block_until_ready(jax.jit(
        lambda p, i: lm.generate(p, CFG, i, NEW))(params, jnp.asarray(ids)))


# cache rows a block of the interpreted kernel: the decoded positions
# T .. T + NEW - 1 cross two block boundaries
BLOCK = 4


@pytest.fixture(params=["xla_form", "kernel_interpreted"])
def route(request, monkeypatch):
    """Which form a ONE-query call of `mla.cache_attention` takes: what the
    CPU gives it (the XLA form) or what a TPU would (the single-pass kernel,
    interpreted here)."""
    if request.param == "kernel_interpreted":
        by_shape = mla.cache_attention

        def kernel_for_one_query(q_lat, q_pe, c, k_pe, position, **kw):
            if q_lat.shape[0] != 1:
                return by_shape(q_lat, q_pe, c, k_pe, position, **kw)
            return mla.streamed_attention(
                q_lat, q_pe, c, k_pe, position, scale=kw["scale"],
                block_rows=BLOCK, interpret=True)

        monkeypatch.setattr(mla, "cache_attention", kernel_for_one_query)
    return request.param


def rows_fetched(route, steps, layers=CFG.num_hidden_layers):
    """`cache_rows_fetched` after decode steps at the positions ``steps``."""
    if route == "xla_form":
        return 0
    return sum(layers * (p // BLOCK + 1) * BLOCK for p in steps)


def test_prefill_then_decode_through_the_cache_is_the_full_forward(
        params, route):
    """float32, tight: the prompt by the materialised form, every decoded
    token by the absorbed form against the cache (either route), against ONE
    full forward of the reference over prompt + served ids - teacher-forced
    over the served ids, and with the reference's OWN choice of experts (in
    float32 both choose alike)."""
    ids = token_ids(T)
    new_ids, chosen_from, counters, experts = served(params, ids)
    all_ids = np.concatenate([ids, np.asarray(new_ids)[:-1]])
    want, slack = reference_logits(params, all_ids, first=T - 1)
    assert want.shape == chosen_from.shape == (NEW, CFG.vocab_size)
    close(chosen_from, want, tol=2e-5)
    assert np.array_equal(np.asarray(want).argmax(1), np.asarray(new_ids))
    # ... and over the served choice: the same logits, no slack
    forced, slack = reference_logits(
        params, all_ids, first=T - 1,
        served_experts=np.asarray(experts)[:, :len(all_ids)])
    close(forced, want, tol=1e-6)
    assert slack <= 1e-6
    assert np.asarray(counters).tolist() == [
        T, 0, NEW, (T + NEW) * 3 * 3, int(np.sum(
            (np.asarray(experts) >= 4) & (np.asarray(experts) < 8))),
        4 * (T + NEW) * (32 + 8) * 4,
        rows_fetched(route, range(T, T + NEW))]


def test_the_counters_keep_their_places_and_the_new_one_is_last():
    assert lm.COUNTERS[:6] == (
        "tokens_prefilled", "tokens_reused", "tokens_decoded",
        "expert_assignments", "expert_assignments_held", "state_bytes")
    assert lm.COUNTERS[6:] == ("cache_rows_fetched",)
    assert CFG.language_model().counters == lm.COUNTERS


def test_the_served_path_in_bfloat16_is_near_the_reference():
    """bf16 weights, cache and activations against the float32 reference of
    the same (bf16-valued) weights, teacher-forced over ids and experts.
    Tolerance: every product rounds to 8 bits of mantissa (2^-9 relative)
    and four layers add some dozens of such roundings in quadrature: the
    per-position error reads ~1-2% of the logits' spread here (the cell's
    limits are set from chip readings in the same way), and a float32 run
    of the same path reads 1e-6 - so 5% holds the path without passing a
    broken one."""
    params = init(jnp.bfloat16)
    ids = token_ids(T)
    new_ids, chosen_from, _, experts = served(params, ids)
    all_ids = np.concatenate([ids, np.asarray(new_ids)[:-1]])
    want, slack = reference_logits(
        params, all_ids, first=T - 1,
        served_experts=np.asarray(experts)[:, :len(all_ids)])
    readings, _, rel = ref.logit_readings(chosen_from, want)
    assert readings["lm_logit_rel_rmse_worst"] < 0.05, readings
    assert readings["lm_logit_rel_rmse_median"] > 1e-4  # it IS bf16
    assert slack < 0.05


def test_absorbed_is_materialised_on_the_same_weights(params, route):
    """Every position's hidden state by the materialised form (a prompt) and
    by the absorbed form: the same prompt ENTERING an empty cache, all its
    rows visible under the mask, and - the one-query route - its last tokens
    going through the cache one step each."""
    ids = jnp.asarray(token_ids(T))
    state = lm.empty_state(CFG, T + 8, jnp.float32)
    x_mat, s_mat, _, none = lm._forward(params, CFG, ids, state, 0, None)
    x_abs, s_abs, _, entering = lm._forward(params, CFG, ids, state, 0, T)
    close(x_abs, x_mat, tol=1e-5)
    for a, b in zip(jax.tree.leaves(s_abs), jax.tree.leaves(s_mat)):
        close(a, b, tol=1e-5)
    assert int(none) == int(entering) == 0  # neither is a decode step
    cut = T - 6
    _, stepped, _, _ = lm._forward(params, CFG, ids[:cut], state, 0, None)
    fetched = 0
    for i in range(cut, T):
        x, stepped, _, rows = jax.block_until_ready(jax.jit(
            lambda p, t, s, i: lm._forward(p, CFG, t, s, i, None))(
                params, ids[i:i + 1], stepped, i))
        close(x[0], x_mat[i], tol=1e-5)
        fetched += int(rows)
    assert fetched == rows_fetched(route, range(cut, T))
    for a, b in zip(jax.tree.leaves(stepped), jax.tree.leaves(s_mat)):
        close(a, b, tol=1e-5)


def test_the_two_forms_of_the_op_agree_and_blocks_do_not_matter():
    h, dn, r, c_dim, t = 4, 16, 8, 32, 24
    k = iter(jax.random.split(jax.random.PRNGKey(0), 8))
    q_nope, q_pe = (jax.random.normal(next(k), (t, h, d)) for d in (dn, r))
    c, k_pe = (jax.random.normal(next(k), (t, d)) for d in (c_dim, r))
    k_up = jax.random.normal(next(k), (h, dn, c_dim)) / c_dim ** 0.5
    v_up = jax.random.normal(next(k), (h, c_dim, dn)) / c_dim ** 0.5
    scale = (dn + r) ** -0.5
    k_nope = jnp.einsum("sc,hdc->shd", c, k_up)
    v = jnp.einsum("sc,hcd->shd", c, v_up)
    want = mla.materialised_attention(q_nope, q_pe, k_nope, k_pe, v,
                                      scale=scale, block=t)
    for block in (8, 12, 128):
        close(mla.materialised_attention(q_nope, q_pe, k_nope, k_pe, v,
                                         scale=scale, block=block), want,
              tol=1e-6)
    # against the definition: per-head keys 24 wide, values 16 wide
    keys = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None], (t, h, r))], -1)
    logits = jnp.einsum("thd,shd->hts", jnp.concatenate([q_nope, q_pe], -1),
                        keys) * scale
    logits = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], logits,
                       -jnp.inf)
    close(want, jnp.einsum("hts,shd->thd", jax.nn.softmax(logits, -1), v),
          tol=1e-6)
    # absorbed, against a cache with rows behind the prompt's: unwritten
    # rows (whatever they hold) are not read into the result
    q_lat = jnp.einsum("thd,hdc->thc", q_nope, k_up)
    junk = 1e3 * jnp.ones((8, c_dim))
    attended = mla.absorbed_attention(
        q_lat, q_pe, jnp.concatenate([c, junk]),
        jnp.concatenate([k_pe, 1e3 * jnp.ones((8, r))]),
        q_positions=jnp.arange(t), scale=scale)
    close(jnp.einsum("thc,hcd->thd", attended, v_up), want, tol=1e-5)
    # ... and the absorbed form's own query blocks do not matter either
    for block in (8, 24):
        close(mla.absorbed_attention(
            q_lat, q_pe, c, k_pe, q_positions=jnp.arange(t), scale=scale,
            block=block), attended, tol=1e-6)


# (heads, the query's position, the query's dtype, the cache's): a cache of
# 512 rows in blocks of 256, the last block fetched 128 rows a copy - the
# first row, one before / at / one after a block boundary, the same around a
# copy's boundary, the last row; the cell's 32 heads; the cache a precision
# below the queries
KERNEL_CASES = {
    "first_row": (4, 0, "float32", "float32"),
    "one_before_a_boundary": (4, 255, "float32", "float32"),
    "at_a_boundary": (4, 256, "float32", "float32"),
    "one_after_a_boundary": (4, 257, "float32", "float32"),
    "one_before_a_copys_boundary": (4, 383, "float32", "float32"),
    "at_a_copys_boundary": (4, 384, "float32", "float32"),
    "last_row": (4, 511, "float32", "float32"),
    "heads_32_bfloat16": (32, 300, "bfloat16", "bfloat16"),
    "heads_32_at_a_boundary_bfloat16": (32, 256, "bfloat16", "bfloat16"),
    "cache_float8": (4, 300, "bfloat16", "float8_e4m3fn"),
    "heads_32_cache_float8_last_row": (32, 511, "bfloat16", "float8_e4m3fn"),
}


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_streamed_kernel_against_the_xla_form_and_a_dense_softmax(case):
    """`streamed_attention` (interpreted here) is `absorbed_attention` and a
    dense float32 softmax over the rows written so far; the rows beyond the
    position hold NaN and never reach the result; it says how many latent
    rows it fetched: the whole blocks before the position's and of that one
    the copies up to the position."""
    h, position, dtype, cache_dtype = KERNEL_CASES[case]
    max_len, block, sub, c_dim, r, scale = 512, 256, 128, 32, 8, 0.2
    k = iter(jax.random.split(jax.random.PRNGKey(12), 4))
    q_lat = jax.random.normal(next(k), (1, h, c_dim)).astype(dtype)
    q_pe = jax.random.normal(next(k), (1, h, r)).astype(dtype)
    written = (jnp.arange(max_len) <= position)[:, None]
    c, k_pe = (jnp.where(written, jax.random.normal(next(k), (max_len, d)),
                         jnp.nan).astype(cache_dtype) for d in (c_dim, r))
    assert bool(jnp.isnan(c.astype(jnp.float32)).any()) == (
        position + 1 < max_len)
    got, rows = jax.block_until_ready(mla.streamed_attention(
        q_lat, q_pe, c, k_pe, position, scale=scale, block_rows=block,
        interpret=True))
    assert got.shape == q_lat.shape and got.dtype == q_lat.dtype
    assert int(rows) == -(-(position + 1) // sub) * sub
    # the XLA form multiplies the unwritten rows by a weight of 0: give it
    # zeros there
    c0, k_pe0 = (jnp.where(written, a.astype(jnp.float32), 0).astype(
        cache_dtype) for a in (c, k_pe))
    tol = 1e-6 if dtype == "float32" else 2e-2  # weights rounded to bf16
    close(got, mla.absorbed_attention(
        q_lat, q_pe, c0, k_pe0, q_positions=jnp.asarray([position]),
        scale=scale), tol=tol)
    q = np.concatenate([np.asarray(q_lat, np.float64),
                        np.asarray(q_pe, np.float64)], -1)
    seen = np.concatenate([np.asarray(c0, np.float64),
                           np.asarray(k_pe0, np.float64)], -1)[:position + 1]
    logits = np.einsum("thd,sd->ths", q, seen) * scale
    w = np.exp(logits - logits.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    close(got, np.einsum("ths,sc->thc", w, seen[:, :c_dim]), tol=tol)


def test_a_cache_no_block_divides_is_refused_and_takes_the_xla_form():
    q_lat, q_pe = jnp.zeros((1, 4, 128)), jnp.zeros((1, 4, 8))
    c, k_pe = jnp.zeros((100, 128)), jnp.zeros((100, 8))
    with pytest.raises(ValueError, match="not divide"):
        mla.streamed_attention(q_lat, q_pe, c, k_pe, 3, scale=1.0,
                               interpret=True)
    with pytest.raises(ValueError, match="not divide"):
        mla.streamed_attention(q_lat, q_pe, c, k_pe, 3, scale=1.0,
                               block_rows=48, interpret=True)
    with pytest.raises(ValueError, match="one query"):
        mla.streamed_attention(jnp.zeros((2, 4, 128)), jnp.zeros((2, 4, 8)),
                               c, k_pe, 3, scale=1.0, block_rows=50,
                               interpret=True)
    # off the TPU every call is the XLA form, and says it fetched nothing
    out, rows = mla.cache_attention(q_lat, q_pe, c, k_pe, 3, scale=1.0)
    assert out.shape == q_lat.shape and int(rows) == 0


def test_rotary_turns_pairs_and_keeps_the_relative_position():
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 3, 8))
    got = mla.rotary_interleaved(x, jnp.arange(6), 1e6)
    close(got, ref.rotary(x, 1e6), tol=1e-6)
    close(got[0], x[0])  # position 0 turns nothing
    # pair i of position p by p * theta^(-2i/R): pair 0 by p radians
    p = 4
    want = (x[p, :, 0] * np.cos(p) - x[p, :, 1] * np.sin(p),
            x[p, :, 1] * np.cos(p) + x[p, :, 0] * np.sin(p))
    close(got[p, :, 0], want[0], tol=1e-6)
    close(got[p, :, 1], want[1], tol=1e-6)
    # q . k depends on the distance alone
    q = mla.rotary_interleaved(jnp.tile(x[:1], (6, 1, 1)), jnp.arange(6), 1e6)
    k = mla.rotary_interleaved(jnp.tile(x[1:2], (6, 1, 1)), jnp.arange(6), 1e6)
    close(jnp.sum(q[1] * k[3]), jnp.sum(q[2] * k[4]), tol=1e-5)


# -- the snapshot (a suffix entering one: `tests/test_language_models.py`) -----


def test_the_rewriter_snapshots_the_instruction_for_this_model_too(params):
    """`PromptRewriter` is handed this model as a value: the instruction's
    whole blocks are prefilled once, every request enters the snapshot, and
    the ids are those of a full prefill."""
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
    rw(["two blue birds over a lake"])
    second = rw.served[-1]
    names = dict(zip(lm.COUNTERS, np.asarray(second.counters).tolist()))
    assert names["tokens_reused"] == 32 and names["tokens_prefilled"] == 40
    assert names["tokens_decoded"] == 8
    assert out[0].shape == (1, 77)
    # a full prefill of the same ids decodes the same tokens
    logits, state, counters, _ = rw._prefill(params, second.prompt_ids)
    new_ids, chosen_from, *_ = rw._decode(params, logits, state, counters,
                                          rw._tables)
    assert np.array_equal(new_ids, second.new_ids)
    close(chosen_from, second.logits, tol=1e-5)
    assert not np.array_equal(first.prompt_ids, second.prompt_ids)


# -- the experts --------------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer(params):
    """What the four chips that share an expert layer each compute of it -
    the routed part of their own 4 of the 16 experts - summed, with the
    shared experts (every chip computes them alike) counted ONCE, is what
    the uncut reference gives for the whole layer."""
    e_all, held = CFG.n_routed_experts, CFG.n_local_experts
    d, f = CFG.hidden_size, CFG.moe_intermediate_size
    k = iter(jax.random.split(jax.random.PRNGKey(8), 4))
    layer = params["layers"][1]["ffn"]
    w1 = jax.random.normal(next(k), (e_all, d, 2 * f)) / d ** 0.5
    w2 = jax.random.normal(next(k), (e_all, f, d)) / f ** 0.5
    u = jax.random.normal(next(k), (T, d))
    total, n_held = jnp.zeros((T, d)), 0
    for share in range(e_all // held):
        cfg = lm.deepseek_v3_config_from_json(dict(
            JSON, expert_parallel={"chips": 4, "index": share}))
        part = dict(layer, experts={
            "w1": w1[share * held:(share + 1) * held],
            "w2": w2[share * held:(share + 1) * held]})
        with jax.named_scope("share"):
            idx, weights = moe.route(
                u, part["router"]["kernel"], part["e_score_correction_bias"],
                top_k=cfg.num_experts_per_tok,
                scale=cfg.routed_scaling_factor)
            routed, n = moe.local_expert_sum(
                u, idx, weights, part["experts"]["w1"], part["experts"]["w2"],
                first_expert=cfg.first_local_expert, activation="silu")
        total, n_held = total + routed, n_held + int(n)
        # the layer as one chip runs it: its routed part plus the shared
        out, n_layer, _ = lm.moe_layer(part, cfg, u)
        close(out, routed + lm.gated_mlp(layer["shared"], u), tol=1e-5)
        assert int(n_layer) == int(n)
    assert n_held == T * CFG.num_experts_per_tok  # every assignment, once
    uncut = dict(JSON, n_routed_experts=e_all,
                 expert_parallel={"chips": 1, "index": 0})
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(dict(layer, experts={"w1": w1, "w2": w2}),
                              ref.lm_shape(uncut), u)
    close(total + lm.gated_mlp(layer["shared"], u), want, tol=2e-5)


def _gated_dense_loop(x, idx, weights, w1, w2, first):
    t, d = x.shape
    e_local = w1.shape[0]
    dense = np.zeros((t, d), np.float32)
    for ti in range(t):
        for e, w in zip(np.asarray(idx[ti]), np.asarray(weights[ti])):
            if first <= e < first + e_local:
                gate, up = jnp.split(jnp.dot(
                    x[ti], w1[e - first],
                    preferred_element_type=jnp.float32), 2)
                hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
                dense[ti] += w * np.asarray(jnp.dot(
                    hidden, w2[e - first],
                    preferred_element_type=jnp.float32))
    return dense


# (first held expert, the tile of f, tokens): the expert whole in one copy,
# and in f-tiles where a gate tile must meet the up tile of the same columns
GATED_CASES = {"whole_expert": (0, None, 1), "two_tiles": (8, 128, 2),
               "three_tiles_three_tokens": (16, 128, 3)}


@pytest.mark.parametrize("case", GATED_CASES)
def test_gated_gather_kernel_against_the_grouped_path_and_a_dense_loop(case):
    """`gather_expert_sum` with gated-SiLU experts (interpreted here) is
    `local_expert_sum`'s grouped matmul and a dense loop over the held
    experts, in bf16 with float32 accumulation, and counts alike."""
    first, tile, t = GATED_CASES[case]
    d, e_local, e_all, k = 256, 8, 32, 6
    f = 256 if tile is None else 128 * (2 + (t == 3))
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    x = jax.random.normal(keys[0], (t, d), jnp.bfloat16)
    w1 = (jax.random.normal(keys[1], (e_local, d, 2 * f)) * d ** -0.5).astype(
        jnp.bfloat16)
    w2 = (jax.random.normal(keys[2], (e_local, f, d)) * f ** -0.5).astype(
        jnp.bfloat16)
    # every token: three held experts (the range's two ends among them) and
    # three from outside, the neighbours of the range first
    rng = np.random.default_rng(3)
    rows = []
    for _ in range(t):
        inside = [first, first + e_local - 1,
                  first + int(rng.integers(1, e_local - 1))]
        outside = [e for e in (first - 1, first + e_local) if 0 <= e < e_all]
        while len(outside) < 3:
            e = int(rng.integers(0, e_all))
            if not first <= e < first + e_local and e not in outside:
                outside.append(e)
        rows.append(rng.permutation(inside + outside[:3]))
    idx = jnp.asarray(np.stack(rows), jnp.int32)
    weights = jax.random.uniform(keys[3], (t, k), jnp.float32, 0.05, 0.5)
    assert t * k < moe.MIN_GROUPED_ROWS

    # (the interpreter's callbacks run JAX ops of their own: wait for them
    # before this thread dispatches more)
    got, n = jax.block_until_ready(moe.gather_expert_sum(
        x, idx, weights, w1, w2, first_expert=first, activation="silu",
        tile=tile, interpret=True))
    grouped, n_grouped = moe.local_expert_sum(
        x, idx, weights, w1, w2, first_expert=first, activation="silu")
    assert got.dtype == jnp.float32
    assert int(n) == int(n_grouped) == 3 * t
    close(got, grouped, tol=1e-2)  # hidden rounds to bf16 before W2
    close(got, _gated_dense_loop(x, idx, weights, w1, w2, first), tol=1e-2)


def test_a_gated_w1_of_the_wrong_width_is_refused():
    x = jnp.zeros((1, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="need w1"):
        moe.gather_expert_sum(
            x, jnp.zeros((1, 2), jnp.int32), jnp.ones((1, 2)),
            jnp.zeros((4, 128, 128), jnp.bfloat16),
            jnp.zeros((4, 128, 128), jnp.bfloat16), first_expert=0,
            activation="silu", interpret=True)


def test_balanced_selection_bias_evens_the_held_experts_load(params):
    ids = jnp.asarray(token_ids(512, seed=21))
    biases = lm.balanced_selection_bias(params, CFG, ids)
    assert len(biases) == CFG.n_expert_layers
    assert all(b.shape == (CFG.n_routed_experts,) for b in biases)

    def spread(p):
        _, _, _, experts = lm.prefill(p, CFG, ids, max_len=512)
        loads = [np.bincount(np.asarray(e).reshape(-1),
                             minlength=CFG.n_routed_experts) for e in experts]
        return max(float(ld.max() / ld.mean()) for ld in loads)

    balanced = jax.tree.map(lambda a: a, params)
    for lp, b in zip(balanced["layers"][CFG.first_k_dense_replace:], biases):
        lp["ffn"] = dict(lp["ffn"], e_score_correction_bias=b)
    assert spread(balanced) < 1.1 < spread(params)
