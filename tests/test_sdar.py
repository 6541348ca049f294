"""The SDAR-style language model (SDAR-30B-A3B-Chat's published keys) at a
small size, seeded weights: prefill and generation by diffusion over blocks
through the KV cache against the plain reference's one cache-less forward
over the served trajectory (`benchmark/reference`) - logits of every fixed
id, the position fixed in every pass, the experts chosen (a suffix entering
a snapshot: `tests/test_language_models.py`, every model's); the block rule; the commit pass, alone and sharing a sweep of the
stack with the next block's first denoise pass; the MASK id; one chip's
share of the experts against the uncut layer; what the configuration
refuses."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import sdar_sdxl as ref  # noqa: E402
from distrifuser_tpu.models import sdar as lm  # noqa: E402

# the published keys, small: 3 layers, 16 experts of which share 1 of 4
# holds 4, 2 query heads a KV head, blocks of 4 in 4 passes
JSON = {
    "model_type": "sdar_moe", "num_hidden_layers": 3, "vocab_size": 96,
    "hidden_size": 64, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "rope_theta": 1000000, "rms_norm_eps": 1e-6, "rope_scaling": None,
    "num_experts": 4, "expert_parallel": {"chips": 4, "index": 1},
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
    "use_sliding_window": False, "attention_bias": False,
    "block_length": 4, "denoising_steps": 4, "prefill_block": 8,
}
CFG = lm.sdar_config_from_json(JSON)
T, NEW = 40, 12


def init(cfg=CFG, dtype=jnp.float32):
    p = lm.init_sdar_params(jax.random.PRNGKey(3), cfg, dtype)
    # norm scales away from their initial one
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 64))

    def moved(norm):
        norm["scale"] = (1.0 + 0.1 * jax.random.normal(
            next(keys), norm["scale"].shape)).astype(dtype)

    for lp in p["layers"]:
        for norm in (lp["attn_norm"], lp["ffn_norm"], lp["attn"]["q_norm"],
                     lp["attn"]["k_norm"]):
            moved(norm)
    moved(p["final_norm"])
    return p


@pytest.fixture(scope="module")
def params():
    return init()


def token_ids(n, seed=5, cfg=CFG):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                                         cfg.mask_id))


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1.0), (
        np.abs(a - b).max(), np.abs(b).max())


def served_against_reference(params, cfg, json, prompt, new_tokens=NEW):
    """Generation through the cache, then the reference's comparison over
    what it served -> (the readings, ids, the record)."""
    new_ids, logits, counters, record = jax.jit(
        lambda p, i: lm.generate(p, cfg, i, new_tokens))(
            params, jnp.asarray(prompt))
    with jax.default_matmul_precision("highest"):
        readings, agree, _ = ref.compare_served(
            ref.LanguageModel(json), params, prompt, new_ids, logits, record)
    return readings, agree, np.asarray(new_ids), record, np.asarray(counters)


def test_config_from_the_published_keys():
    assert (CFG.num_experts, CFG.n_local_experts,
            CFG.first_local_expert) == (16, 4, 4)
    assert CFG.mask_id == 95
    model = CFG.language_model()
    assert model.vocab_size == 95  # the MASK id is no id of a text
    assert model.decode_multiple == 4 and model.prompt_multiple == 8
    assert model.prefill_from is model.prefill
    shapes = lm.param_shapes(CFG)
    assert shapes["layers"][0]["attn"]["q_norm"] == {"scale": (16,)}
    assert shapes["layers"][0]["ffn"]["experts"]["w1"] == (4, 64, 64)
    assert "shared" not in shapes["layers"][0]["ffn"]


@pytest.mark.parametrize("key,value", [
    ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
    ("use_sliding_window", True), ("rope_scaling", {"type": "yarn"}),
    ("attention_bias", True), ("norm_topk_prob", False)])
def test_what_the_module_does_not_build_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        lm.sdar_config_from_json(dict(JSON, **{key: value}))


def test_blocks_that_passes_or_prompts_do_not_divide_are_refused():
    with pytest.raises(ValueError, match="whole number"):
        lm.sdar_config_from_json(dict(JSON, denoising_steps=3))
    with pytest.raises(ValueError, match="whole blocks"):
        lm.sdar_config_from_json(dict(JSON, block_length=3,
                                      denoising_steps=3))


def test_generation_through_the_cache_is_the_references_forward(params):
    """Logits of every fixed id against the reference's view of the pass
    that fixed it, the position fixed in every pass, the experts every
    pass's rows chose: float32 against float32."""
    prompt = token_ids(T)
    readings, agree, new_ids, record, counters = served_against_reference(
        params, CFG, JSON, prompt)
    assert readings["lm_logit_rel_rmse_worst"] < 1e-5, readings
    assert readings["lm_router_slack_worst"] == 0.0
    assert readings["lm_unmask_slack_worst"] == 0.0
    assert agree == 1.0
    assert new_ids.shape == (NEW,) and (new_ids != CFG.mask_id).all()
    # every pass of every block fixed one position
    fixed = np.asarray(record["fixed_in_pass"]).reshape(-1, 4)
    assert (np.sort(fixed, axis=1) == np.arange(4)).all()
    assert np.asarray(record["denoise_experts"]).shape == (3, 4, 4, 3, 3)
    c = dict(zip(lm.COUNTERS, counters.tolist()))
    assert (c["tokens_prefilled"], c["tokens_reused"],
            c["tokens_decoded"]) == (T, 0, NEW)
    assert (c["denoise_passes"], c["commit_passes"]) == (12, 3)
    assert c["expert_assignments"] == (T + 15 * 4) * 3 * 3
    held = ((np.asarray(record["experts"])[:, :T + NEW] // 4) == 1).sum() + (
        (np.asarray(record["denoise_experts"]) // 4) == 1).sum()
    assert c["expert_assignments_held"] == held
    # the passes' calls fetch an expert a held assignment
    assert c["experts_fetched"] == (
        (np.asarray(record["experts"])[:, T:T + NEW] // 4) == 1).sum() + (
        (np.asarray(record["denoise_experts"]) // 4) == 1).sum()
    assert c["kv_cache_bytes"] == 3 * 2 * 2 * (T + NEW) * 16 * 4


def test_the_reference_alone_generates_the_same_ids(params):
    """Not teacher-forced: the reference's own procedure - a block of MASK
    ids appended to the committed sequence, the most confident masked
    position fixed after each forward - arrives at the served ids."""
    prompt = token_ids(T, seed=9)
    new_ids, _, _, record = jax.jit(lambda p, i: lm.generate(p, CFG, i, NEW))(
        params, jnp.asarray(prompt))
    model = ref.LanguageModel(JSON)
    seq, fixed_in = list(prompt), []
    with jax.default_matmul_precision("highest"):
        for _ in range(NEW // 4):
            block = [CFG.mask_id] * 4
            order = {}
            for t in range(4):
                logits = np.array(model.logits(
                    params, np.asarray(seq + block))[-4:], np.float64)
                logits[:, CFG.mask_id] = -np.inf
                conf = logits.max(1) - np.log(np.exp(logits).sum(1))
                masked = [j for j in range(4) if block[j] == CFG.mask_id]
                j = max(masked, key=lambda j: (conf[j], -j))
                block[j], order[j] = int(logits[j].argmax()), t
            seq += block
            fixed_in += [order[j] for j in range(4)]
    assert seq[T:] == np.asarray(new_ids).tolist()
    assert fixed_in == np.asarray(record["fixed_in_pass"]).tolist()


def test_a_position_sees_its_whole_block_and_nothing_after_it(params):
    """The second layer's keys of a position move with an id iff the
    position sees that id's position: its own block's (both directions) and
    every earlier one's, none of a later block's."""
    ids = token_ids(16, seed=11)

    def second_layer_keys(ids):
        _, state, _, _ = lm.prefill(params, CFG, jnp.asarray(ids), max_len=16)
        return np.asarray(state["cache"][1]["k"])  # [Hkv, 16, D]

    base = second_layer_keys(ids)
    for changed in (5, 6, 9):
        other = ids.copy()
        other[changed] = (other[changed] + 1) % CFG.mask_id
        moved = np.abs(second_layer_keys(other) - base).max(axis=(0, 2)) > 1e-6
        first_of_its_block = changed // 4 * 4
        assert not moved[:first_of_its_block].any(), (changed, moved)
        assert moved[first_of_its_block:].all(), (changed, moved)


def test_blocks_of_one_are_plain_causal_decoding_of_a_mask_predictor():
    """B = 1, T = 1: every id is the largest logit AT a MASK id appended to
    the sequence so far, under causal visibility."""
    json = dict(JSON, block_length=1, denoising_steps=1)
    cfg = lm.sdar_config_from_json(json)
    params = init(cfg)
    prompt = token_ids(16, seed=13)
    new_ids, logits, counters, record = jax.jit(
        lambda p, i: lm.generate(p, cfg, i, 5))(params, jnp.asarray(prompt))
    assert (np.asarray(record["fixed_in_pass"]) == 0).all()
    c = dict(zip(lm.COUNTERS, np.asarray(counters).tolist()))
    assert (c["denoise_passes"], c["commit_passes"]) == (5, 5)
    model = ref.LanguageModel(json)
    seq = list(prompt)
    with jax.default_matmul_precision("highest"):
        for i in range(5):
            want = model.logits(params, np.asarray(seq + [cfg.mask_id]))[-1]
            close(logits[i], want)
            seq.append(int(new_ids[i]))
            assert seq[-1] == int(np.asarray(want)[:cfg.mask_id].argmax())


def test_without_the_commit_pass_the_cache_is_not_the_final_blocks(params):
    """The control: the cache keeping the last denoise pass's keys and
    values (one position of the block still a MASK id there) reads four
    orders of magnitude outside what the sound program reads."""
    json = dict(JSON, commit_pass=False)
    cfg = lm.sdar_config_from_json(json)
    readings, _, _, _, counters = served_against_reference(
        params, cfg, json, token_ids(T))
    assert readings["lm_logit_rel_rmse_median"] > 1e-2, readings
    c = dict(zip(lm.COUNTERS, counters.tolist()))
    assert (c["denoise_passes"], c["commit_passes"]) == (12, 0)


def pass_by_pass(params, cfg, prompt, new_tokens):
    """The procedure with nothing shared: every denoise pass and every
    commit pass a trip of the stack of its own over its block's rows,
    ``denoising_steps + 1`` a block -> (ids, the logits each was fixed from,
    the pass that fixed each)."""
    size, max_len = cfg.block_length, len(prompt) + new_tokens
    _, state, _, _ = lm.prefill(params, cfg, jnp.asarray(prompt),
                                max_len=max_len)
    ids, fixed_from, fixed_in = [], {}, {}
    for start in range(len(prompt), max_len, size):
        block = jnp.full((size,), cfg.mask_id, jnp.int32)
        for t in range(cfg.denoising_steps):
            x, state, *_ = lm._forward(params, cfg, block, state, start,
                                         max_len)
            logits = lm.head(params, cfg, x)
            block, fixed = lm.unmask(cfg, logits, block)
            for j in np.asarray(fixed).tolist():
                fixed_from[start + j], fixed_in[start + j] = logits[j], t
        _, state, *_ = lm._forward(params, cfg, block, state, start, max_len)
        ids += np.asarray(block).tolist()
    order = sorted(fixed_from)
    return (ids, np.stack([fixed_from[i] for i in order]),
            [fixed_in[i] for i in order])


@pytest.mark.parametrize("blocks_of, prompt_len, new_tokens", [
    (4, T, NEW), (1, 16, 5)])
def test_a_shared_sweep_is_the_two_passes_it_holds(blocks_of, prompt_len,
                                                   new_tokens):
    """Ids, the logits they were fixed from and the pass that fixed each
    against T + 1 sweeps a block: the last rows of a shared sweep see the
    keys and values the same sweep commits, layer by layer.  With blocks of
    one in one pass every sweep but the first and the last commits one id
    and predicts the next."""
    cfg = lm.sdar_config_from_json(dict(JSON, block_length=blocks_of,
                                        denoising_steps=blocks_of))
    params = init(cfg)
    prompt = token_ids(prompt_len, seed=19)
    new_ids, logits, counters, record = jax.jit(
        lambda p, i: lm.generate(p, cfg, i, new_tokens))(
            params, jnp.asarray(prompt))
    ids, fixed_from, fixed_in = pass_by_pass(params, cfg, prompt, new_tokens)
    assert np.asarray(new_ids).tolist() == ids
    close(logits, fixed_from, tol=1e-5)
    assert np.asarray(record["fixed_in_pass"]).tolist() == fixed_in
    c = dict(zip(lm.COUNTERS, np.asarray(counters).tolist()))
    assert (c["denoise_passes"], c["commit_passes"], c["stack_sweeps"]) == (
        new_tokens, new_tokens // blocks_of, new_tokens + 1)


@pytest.mark.parametrize("new_tokens", [4, 12])
def test_the_cache_after_generation_is_the_whole_sequences_prefill(
        params, new_tokens):
    """A shared sweep's first rows ARE a commit - and the last block's commit
    pass, alone, is one: every layer's keys and values of prompt + new ids,
    row for row, and the experts the rows chose.  One block: no sweep is
    shared."""
    prompt = jnp.asarray(token_ids(T, seed=23))
    max_len = T + new_tokens
    logits, state, counters, _ = lm.prefill(params, CFG, prompt,
                                            max_len=max_len)
    new_ids, _, record, state, counters = jax.jit(
        lambda p, s, c: lm.decode(p, CFG, logits, s, c, position=T,
                                  new_tokens=new_tokens))(
            params, state, counters)
    _, whole, _, chosen = lm.prefill(
        params, CFG, jnp.concatenate([prompt, new_ids]), max_len=max_len)
    for got, want in zip(state["cache"], whole["cache"]):
        close(got["k"], want["k"], tol=1e-5)
        close(got["v"], want["v"], tol=1e-5)
    assert np.array_equal(record["experts"], chosen)
    c = dict(zip(lm.COUNTERS, np.asarray(counters).tolist()))
    assert c["stack_sweeps"] == new_tokens + 1


@pytest.mark.parametrize("commit, commits, sweeps", [
    (True, 3, 13), (False, 0, 12)])
def test_passes_are_counted_as_before_and_sweeps_beside_them(
        params, commit, commits, sweeps):
    """A pass is a block's rows through the stack, whatever it shares:
    T a block with the head and one without; the sweeps are one fewer a
    block with a successor - and as many as the passes in the control."""
    cfg = lm.sdar_config_from_json(dict(JSON, commit_pass=commit))
    _, _, counters, record = jax.jit(
        lambda p, i: lm.generate(p, cfg, i, NEW))(
            params, jnp.asarray(token_ids(T, seed=29)))
    c = dict(zip(lm.COUNTERS, np.asarray(counters).tolist()))
    assert (c["denoise_passes"], c["commit_passes"],
            c["stack_sweeps"]) == (12, commits, sweeps)
    assert c["tokens_decoded"] == NEW
    assert c["expert_assignments"] == (T + (12 + commits) * 4) * 3 * 3
    assert np.asarray(record["denoise_experts"]).shape == (3, 4, 4, 3, 3)


@pytest.mark.parametrize("commit, sweeps", [(True, 13), (False, 12)])
def test_generation_through_the_kernels_route_is_the_xla_routes(
        params, monkeypatch, commit, sweeps):
    """What a TPU gives a decode sweep - `ops/gqa_cache.py
    streamed_gqa_attention`, interpreted here, blocks of 4 cache rows - in
    the place of `cache_attention` for every call against a cache with few
    rows (a pass's 4, a shared sweep's 8; the prompt keeps the XLA form):
    the same ids fixed in the same passes from the same logits, and the
    counter `kv_rows_fetched` the rows in view - a sweep at position p
    fetches rows 0 .. the end of its last block, in every layer; 0 on the
    XLA route."""
    from distrifuser_tpu.ops import gqa_cache

    cfg = lm.sdar_config_from_json(dict(JSON, commit_pass=commit))
    prompt = jnp.asarray(token_ids(T, seed=31))
    want_ids, want_logits, counters, want = jax.jit(
        lambda p, i: lm.generate(p, cfg, i, NEW))(params, prompt)
    c = dict(zip(lm.COUNTERS, np.asarray(counters).tolist()))
    assert c["kv_rows_fetched"] == 0 and c["stack_sweeps"] == sweeps

    def as_on_a_tpu(q, k, v, *, limits, visible=None):
        if visible is None or q.shape[0] > 8:
            return gqa_cache.cache_attention(q, k, v, limits=limits,
                                             visible=visible)
        assert k.shape[1] == T + NEW  # the whole cache, never a slice
        return gqa_cache.streamed_gqa_attention(q, k, v, limits,
                                                block_rows=4, interpret=True)

    monkeypatch.setattr(lm, "cache_attention", as_on_a_tpu)
    ids, logits, counters, record = jax.block_until_ready(jax.jit(
        lambda p, i: lm.generate(p, cfg, i, NEW))(params, prompt))
    assert np.array_equal(ids, want_ids)
    close(logits, want_logits, tol=1e-5)
    for name in ("fixed_in_pass", "denoise_experts", "experts"):
        assert np.array_equal(record[name], want[name]), name
    c = dict(zip(lm.COUNTERS, np.asarray(counters).tolist()))
    # block b's sweeps start at T + 4 b; the one it shares with block b + 1
    # reaches four rows further
    starts = [T + 4 * b for b in range(NEW // 4)]
    if commit:
        # (block 0 makes four denoise passes alone, the later ones three)
        rows = (4 * (starts[0] + 4) + (starts[0] + 8)
                + sum(3 * (p + 4) + (p + 8) for p in starts[1:-1])
                + 3 * (starts[-1] + 4) + (starts[-1] + 4))
    else:
        rows = sum(4 * (p + 4) for p in starts)
    assert c["stack_sweeps"] == sweeps
    assert c["kv_rows_fetched"] == rows * cfg.num_hidden_layers


def test_the_mask_id_is_never_chosen(params):
    """A head whose MASK column is a loud copy of column 0 - the largest
    logit wherever column 0's is positive: the id is still never a
    candidate, and every block finishes."""
    loud = jax.tree.map(lambda a: a, params)
    loud["head"] = {"kernel": (0.01 * params["head"]["kernel"]).at[
        :, CFG.mask_id].set(params["head"]["kernel"][:, 0] * 100.0)}
    new_ids, logits, _, record = jax.jit(
        lambda p, i: lm.generate(p, CFG, i, NEW))(
            loud, jnp.asarray(token_ids(T)))
    new_ids, logits = np.asarray(new_ids), np.asarray(logits)
    assert (logits.argmax(1) == CFG.mask_id).any()
    assert (new_ids != CFG.mask_id).all()
    assert (new_ids == logits[:, :CFG.mask_id].argmax(1)).all()
    fixed = np.asarray(record["fixed_in_pass"]).reshape(-1, 4)
    assert (np.sort(fixed, axis=1) == np.arange(4)).all()


def test_equal_confidences_fix_the_lower_position():
    logits = jnp.zeros((4, CFG.vocab_size)).at[:, 7].set(3.0)
    block = jnp.asarray([CFG.mask_id, 5, CFG.mask_id, CFG.mask_id])
    out, fixed = lm.unmask(CFG, logits, block)
    assert np.asarray(fixed).tolist() == [0]
    assert np.asarray(out).tolist() == [7, 5, CFG.mask_id, CFG.mask_id]
    two = lm.SdarConfig(block_length=4, denoising_steps=2, vocab_size=96)
    out, fixed = lm.unmask(two, logits.at[3, 7].set(4.0), block)
    assert np.asarray(fixed).tolist() == [3, 0]
    assert np.asarray(out).tolist() == [7, 5, two.mask_id, 7]


def test_new_tokens_that_are_not_whole_blocks_are_refused(params):
    from distrifuser_tpu.pipelines import (
        PromptRewriter,
        RewriteSpec,
        SimpleTokenizer,
    )

    with pytest.raises(ValueError, match="whole blocks"):
        lm.generate(params, CFG, jnp.asarray(token_ids(T)), 10)
    with pytest.raises(ValueError, match="multiple of the 4 ids"):
        PromptRewriter(CFG, params, RewriteSpec(16, 8, 10, 4),
                       [SimpleTokenizer(1000)])
    rewriter = PromptRewriter(CFG, params, RewriteSpec(16, 8, 12, 4),
                              [SimpleTokenizer(1000)])
    # the MASK id is no id of an instruction or of a caller's word
    assert rewriter.instruction.max() < CFG.mask_id
    assert rewriter.lm_ids("a red fox").max() < CFG.mask_id


def test_the_rewriter_serves_the_blocks_record(params):
    from distrifuser_tpu.pipelines import (
        PromptRewriter,
        RewriteSpec,
        SimpleTokenizer,
    )

    json = dict(JSON, rewrite={"instruction_tokens": 20, "user_tokens": 4,
                               "new_tokens": 8, "prompt_tokens": 4,
                               "instruction_seed": 2})
    rewriter = PromptRewriter(CFG, params, RewriteSpec(**json["rewrite"]),
                              [SimpleTokenizer(1000)])
    out = rewriter(["a red fox jumps"])
    assert out[0].shape == (1, 77)
    served = rewriter.served[-1]
    assert np.array_equal(served.prompt_ids,
                          ref.prompt_ids(json, "a red fox jumps"))
    assert rewriter._prefix_len == 16
    c = dict(zip(lm.COUNTERS, np.asarray(served.counters).tolist()))
    assert (c["tokens_prefilled"], c["tokens_reused"]) == (24, 16)
    with jax.default_matmul_precision("highest"):
        readings, agree, _ = ref.compare_served(
            ref.LanguageModel(json), params, served.prompt_ids,
            served.new_ids, served.logits, served.experts[1])
    assert readings["lm_logit_rel_rmse_worst"] < 1e-5 and agree == 1.0
    assert readings["lm_unmask_slack_worst"] == 0.0


def test_the_shares_parts_add_up_to_the_uncut_expert_layer(params):
    """Four shares of 4 experts each route over all 16 alike and compute
    their own experts' part: the parts add up to what the uncut reference
    gives for the whole layer (no shared expert to count once)."""
    whole_json = dict(JSON, num_experts=16,
                      expert_parallel={"chips": 1, "index": 0})
    d, f = CFG.hidden_size, CFG.moe_intermediate_size
    keys = jax.random.split(jax.random.PRNGKey(17), 4)
    u = jax.random.normal(keys[0], (24, d))
    whole = {"router": {"kernel": jax.random.normal(keys[1], (d, 16)) * 0.3},
             "experts": {
                 "w1": jax.random.normal(keys[2], (16, d, 2 * f)) * d ** -0.5,
                 "w2": jax.random.normal(keys[3], (16, f, d)) * f ** -0.5}}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(whole, ref.lm_shape(whole_json), u)
    total, held_total = 0.0, 0
    for share in range(4):
        cfg = lm.sdar_config_from_json(
            dict(JSON, expert_parallel={"chips": 4, "index": share}))
        part = {"router": whole["router"], "experts": jax.tree.map(
            lambda w: w[4 * share:4 * share + 4], whole["experts"])}
        out, held, idx = lm.moe_layer(part, cfg, u)
        total, held_total = total + out, held_total + int(held)
    close(total, want, tol=1e-5)
    assert held_total == 24 * 3  # every assignment fell on one share


def test_seeded_leaves_go_by_their_names():
    p = lm.init_sdar_params(jax.random.PRNGKey(0), CFG)
    attn = p["layers"][0]["attn"]
    assert (np.asarray(attn["q_norm"]["scale"])
            == lm.SEEDED_QK_NORM_SCALE).all()
    assert (np.asarray(attn["k_norm"]["scale"])
            == lm.SEEDED_QK_NORM_SCALE).all()
    for norm in (p["layers"][0]["attn_norm"], p["layers"][2]["ffn_norm"],
                 p["final_norm"]):
        assert (np.asarray(norm["scale"]) == 1.0).all()
    assert abs(float(np.asarray(p["embed"]).std()) - 0.02) < 2e-3
    assert abs(float(np.asarray(attn["q"]["kernel"]).std()) - 64 ** -0.5) \
        < 1e-2
    names = [name for name, _ in lm.named_leaves(CFG)[0]]
    assert {"embed", "kernel", "w1", "w2", "q_norm", "k_norm", "attn_norm",
            "ffn_norm", "final_norm"} == set(names)

