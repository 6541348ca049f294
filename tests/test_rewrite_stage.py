"""The think-then-rewrite stage on the request path: `PromptRewriter` in
front of a tiny SDXL pipeline, through `InferenceServer` end to end."""

import dataclasses

import jax
import numpy as np
import pytest

from distrifuser_tpu import DistriConfig
from distrifuser_tpu.models import evabyte
from distrifuser_tpu.models import nemotron_h as lm
from distrifuser_tpu.models.clip import (
    CLIPTextConfig,
    init_clip_params,
    tiny_clip_config,
)
from distrifuser_tpu.models.unet import init_unet_params, tiny_config
from distrifuser_tpu.models.vae import init_vae_params, tiny_vae_config
from distrifuser_tpu.pipelines import (
    DistriSDXLPipeline,
    PromptRewriter,
    RewriteSpec,
    SimpleTokenizer,
)
from distrifuser_tpu.serve import ExecKey, InferenceServer, ServeConfig
from distrifuser_tpu.serve.executors import pipeline_executor_factory

LM = lm.NemotronHConfig(
    pattern="MEM*E", vocab_size=300, hidden_size=32, mamba_num_heads=4,
    mamba_head_dim=8, n_groups=2, ssm_state_size=8, chunk_size=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    n_routed_experts=16, n_local_experts=4, first_local_expert=4,
    num_experts_per_tok=4, moe_latent_size=16, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=32)
SPEC = RewriteSpec(instruction_tokens=10, user_tokens=6, new_tokens=12,
                   prompt_tokens=5, instruction_seed=1)
STEPS = 2

# a byte-level model that can take a suffix into its prefix's state: windows
# of 32 bytes, chunks of 4
BYTES = evabyte.EvaByteConfig(
    num_hidden_layers=2, hidden_size=32, num_attention_heads=4,
    intermediate_size=48, window_size=32, chunk_size=4)


def from_zero(config):
    """The same model, said to have no entering prefill."""

    class FromZero(type(config)):
        def language_model(self):
            return super().language_model()._replace(prefill_from=None)

    return FromZero(**dataclasses.asdict(config))


@pytest.fixture(scope="module")
def compiles():
    """The names of the programs JAX compiles, in order, from here on."""
    names = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, seconds, **kw: names.append(kw.get("fun_name"))
        if event == "/jax/core/compile/backend_compile_duration" else None)
    return names


def build(devices, rewriter, **cfg_kw):
    dcfg = DistriConfig(devices=devices[:1], height=128, width=128,
                        warmup_steps=1, **cfg_kw)
    tc1 = tiny_clip_config(hidden=16)
    tc2 = CLIPTextConfig(vocab_size=1000, hidden_size=16, num_hidden_layers=2,
                         num_attention_heads=4, intermediate_size=32,
                         projection_dim=32)
    ucfg, vcfg = tiny_config(cross_attention_dim=32, sdxl=True), \
        tiny_vae_config()
    return DistriSDXLPipeline.from_params(
        dcfg, ucfg, init_unet_params(jax.random.PRNGKey(0), ucfg), vcfg,
        init_vae_params(jax.random.PRNGKey(1), vcfg), [tc1, tc2],
        [init_clip_params(jax.random.PRNGKey(2), tc1),
         init_clip_params(jax.random.PRNGKey(3), tc2)],
        rewriter=((LM, lm.init_nemotron_h_params(jax.random.PRNGKey(9), LM),
                   SPEC) if rewriter else None))


def server(devices, rewriter=True, **kw):
    def factory(key: ExecKey):
        return build(devices, rewriter, do_classifier_free_guidance=key.cfg)

    config = ServeConfig(max_batch_size=1, batch_window_s=0.0,
                         buckets=((128, 128),), default_steps=STEPS,
                         warmup_buckets=((128, 128, STEPS),), **kw)
    return InferenceServer(pipeline_executor_factory(factory), config,
                           model_id="tiny-rewrite-sdxl", scheduler="euler",
                           mesh_plan="dp1.cfg1.sp1")


def test_the_rewriters_ids_are_the_tokenizers_of_the_decimal_words():
    toks = [SimpleTokenizer(1000), SimpleTokenizer(777)]
    rw = PromptRewriter(LM, lm.init_nemotron_h_params(
        jax.random.PRNGKey(9), LM), SPEC, toks)
    ids = rw.lm_ids("A red fox")
    assert ids.shape == (16,) and ids.dtype == np.int32
    # the instruction, then the words cut or repeated to user_tokens
    assert np.array_equal(ids[:10], rw.instruction)
    assert np.array_equal(ids[10:13], ids[13:16]) and (ids < 300).all()
    assert np.array_equal(rw.lm_ids("")[10:], np.zeros(6, np.int32))
    out = rw(["a red fox"])
    served = rw.served[-1]
    text = " ".join(str(i) for i in np.asarray(served.new_ids)[-5:])
    for tok, got in zip(toks, out):
        assert isinstance(got, jax.Array) and got.shape == (1, 77)
        assert np.array_equal(np.asarray(got), tok([text]))
    assert np.array_equal(served.prompt_ids, rw.lm_ids("a red fox"))
    assert served.logits.shape == (12, 300)
    assert np.array_equal(np.asarray(served.logits).argmax(1),
                          np.asarray(served.new_ids))
    two = rw(["a red fox", "blue"])
    assert two[0].shape == (2, 77)
    assert np.array_equal(np.asarray(two[0][0]), np.asarray(out[0][0]))
    assert "lm.mamba" in rw.decode_program_text()


@pytest.mark.parametrize("instruction,user,reused", [
    (60, 24, 60),  # the suffix runs 60 -> 84 over the window boundary at 64
    (58, 14, 56),  # an instruction of broken chunks: its whole ones
    (32, 4, 32)])  # the snapshot ends a window: an empty ring, a grown table
def test_the_instruction_is_prefilled_once_and_entered_by_every_request(
        compiles, instruction, user, reused):
    spec = RewriteSpec(instruction_tokens=instruction, user_tokens=user,
                       new_tokens=12, prompt_tokens=8, instruction_seed=1)
    params = evabyte.init_evabyte_params(jax.random.PRNGKey(2), BYTES)
    toks = [SimpleTokenizer(1000), SimpleTokenizer(777)]
    rw = PromptRewriter(BYTES, params, spec, toks)
    full = PromptRewriter(from_zero(BYTES), params, spec, toks)
    assert rw._snapshot is None and full.snapshot() is None
    prompts = ["a red fox", "an old sailor by the sea", "a red fox"]
    outs, marks = [], []
    for prompt in prompts:
        mark = len(compiles)
        outs.append(jax.block_until_ready(rw([prompt])))
        marks.append(compiles[mark:])
        counters = dict(zip(rw.lm.counters,
                            np.asarray(rw.served[-1].counters).tolist()))
        assert counters["bytes_reused"] == reused
        assert counters["bytes_prefilled"] == instruction + user
        assert counters["bytes_decoded"] == 12
        assert len(rw.served[-1].prompt_ids) == instruction + user
        # ... and through the full prefill: the same bytes, the same ids
        want = full([prompt])
        theirs = full.served[-1]
        assert np.array_equal(np.asarray(rw.served[-1].new_ids),
                              np.asarray(theirs.new_ids))
        for got, ids in zip(outs[-1], want):
            assert np.array_equal(np.asarray(got), np.asarray(ids))
        np.testing.assert_allclose(np.asarray(rw.served[-1].logits),
                                   np.asarray(theirs.logits), atol=2e-5)
        their = dict(zip(full.lm.counters,
                         np.asarray(theirs.counters).tolist()))
        assert their.pop("bytes_reused") == 0
        assert their.items() <= counters.items()
    # the snapshot: made by the first request, read by all, consumed by none
    state, _, of_prefix = rw.snapshot()
    assert of_prefix == ()  # this model records nothing beside ids and logits
    assert all(not leaf.is_deleted() for leaf in jax.tree.leaves(state))
    assert sum(leaf.nbytes for leaf in jax.tree.leaves(state)) == \
        counters["state_bytes"]
    for a, b in zip(outs[0], outs[2]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(outs[0][0]), np.asarray(outs[1][0]))
    # one program a name, the request's still `rewrite_prefill`; after the
    # first request nothing compiles
    assert (rw._prefix._cache_size(), rw._prefill._cache_size(),
            rw._decode._cache_size()) == (1, 1, 1)
    text = rw._prefill.lower(rw.params, rw.lm_ids("x")[reused:],
                             rw.snapshot()).as_text()
    assert "module @jit_rewrite_prefill " in text
    assert {"jit(rewrite_prefix)", "jit(rewrite_prefill)",
            "jit(rewrite_decode)"} <= set(marks[0]), marks
    assert marks[1:] == [[], []], marks
    rw.drop_snapshot()
    assert rw._snapshot is None


@pytest.mark.parametrize("instruction,user,reused", [
    (10, 6, 8),  # a request's one chunk: the instruction's end, the caller's
    (20, 4, 16)])  # a snapshot of several chunks of the scan
def test_a_scan_models_record_is_of_the_whole_prompt_snapshot_or_not(
        compiles, instruction, user, reused):
    """Nemotron enters the snapshot's SSM states, convolution tails and KV
    cache; what it records of the prompt - the experts every position chose,
    which the cell's reference teacher-forces position by position - comes
    back for ALL the prompt's positions, the snapshot's in front, as the
    full prefill records them."""
    spec = RewriteSpec(instruction_tokens=instruction, user_tokens=user,
                       new_tokens=12, prompt_tokens=5, instruction_seed=1)
    params = lm.init_nemotron_h_params(jax.random.PRNGKey(9), LM)
    toks = [SimpleTokenizer(1000)]
    rw = PromptRewriter(LM, params, spec, toks)
    full = PromptRewriter(from_zero(LM), params, spec, toks)
    assert (rw._prefix_len, full._prefix_len) == (reused, 0)
    marks = []
    for prompt in ["a red fox", "an old sailor by the sea"]:
        mark = len(compiles)
        got = jax.block_until_ready(rw([prompt]))
        marks.append(compiles[mark:])
        want = full([prompt])
        served, theirs = rw.served[-1], full.served[-1]
        of_prompt = np.asarray(served.experts[0])
        assert of_prompt.shape == (LM.pattern.count("E"), instruction + user,
                                   LM.num_experts_per_tok)
        assert np.array_equal(of_prompt, np.asarray(theirs.experts[0]))
        assert np.array_equal(np.asarray(served.experts[1]),
                              np.asarray(theirs.experts[1]))
        assert np.array_equal(np.asarray(served.new_ids),
                              np.asarray(theirs.new_ids))
        assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_allclose(np.asarray(served.logits),
                                   np.asarray(theirs.logits), atol=2e-5)
        counters, their = (dict(zip(r.lm.counters, np.asarray(
            r.served[-1].counters).tolist())) for r in (rw, full))
        assert counters.pop("tokens_reused") == rw._prefix_len
        assert their.pop("tokens_reused") == 0 and counters == their
        assert counters["tokens_prefilled"] == instruction + user
    # the instruction's program compiled once, by the first request; the
    # join is inside the request's own program: nothing new a request
    assert marks[0].count("jit(rewrite_prefix)") == 1 and marks[1] == []
    assert (rw._prefix._cache_size(), rw._prefill._cache_size(),
            rw._decode._cache_size()) == (1, 1, 1)
    state, _, of_prefix = rw.snapshot()
    assert of_prefix.shape[1] == reused
    assert all(not leaf.is_deleted() for leaf in jax.tree.leaves(state))


def test_a_model_without_the_entering_form_is_served_as_before(compiles):
    """A record that offers no `prefill_from` (Nemotron's, said to have
    none): no snapshot, and the prefill program is, instruction for
    instruction, the one a rewriter without any of this lowers."""
    params = lm.init_nemotron_h_params(jax.random.PRNGKey(9), LM)
    rw = PromptRewriter(from_zero(LM), params, SPEC, [SimpleTokenizer(1000)])
    assert rw.lm.prefill_from is None and rw.snapshot() is None
    before = len(compiles)
    rw(["a red fox"])
    assert rw._snapshot is None and rw._prefix._cache_size() == 0
    assert "jit(rewrite_prefix)" not in compiles[before:]
    assert "jit(rewrite_prefill)" in compiles[before:]
    ids = rw.lm_ids("a red fox")

    def rewrite_prefill(params, ids):
        return lm.prefill(params, LM, ids, max_len=len(ids) + SPEC.new_tokens)

    assert rw._prefill.lower(params, ids).as_text() == jax.jit(
        rewrite_prefill).lower(params, ids).as_text()
    counters = dict(zip(rw.lm.counters,
                        np.asarray(rw.served[-1].counters).tolist()))
    assert counters["tokens_reused"] == 0
    assert counters["tokens_prefilled"] == len(ids)


def test_a_rewriter_needs_the_word_hash_and_whole_chunks():
    params = lm.init_nemotron_h_params(jax.random.PRNGKey(9), LM)
    with pytest.raises(ValueError, match="multiple of the scan"):
        PromptRewriter(LM, params, RewriteSpec(10, 5, 12, 5),
                       [SimpleTokenizer(1000)])
    with pytest.raises(ValueError, match="prompt_tokens"):
        PromptRewriter(LM, params, RewriteSpec(10, 6, 4, 5),
                       [SimpleTokenizer(1000)])
    with pytest.raises(ValueError, match="word hash"):
        PromptRewriter(LM, params, SPEC, [object()])


@pytest.mark.parametrize("kind", ["whole", "staged"])
def test_rewrite_stage_through_the_server(devices8, kind):
    kw = {"pipeline_stages": True} if kind == "staged" else {}
    with server(devices8, **kw) as srv:
        a = srv.submit("a red fox in the forest", height=128, width=128,
                       guidance_scale=0.0, seed=3).result(timeout=600)
        b = srv.submit("a red fox in the forest", height=128, width=128,
                       guidance_scale=0.0, seed=3).result(timeout=600)
        c = srv.submit("an old sailor", height=128, width=128,
                       guidance_scale=5.0, seed=3).result(timeout=600)
    assert np.array_equal(a.output, b.output)  # the same request, the same bytes
    assert np.isfinite(c.output).all() and not np.array_equal(
        a.output, c.output)
    if kind == "whole":
        assert tuple(a.stage_s) == InferenceServer.STAGE_CLOCKS
        assert a.stage_s["rewrite"] > 0 and a.stage_s["dispatch"] > 0
        assert sum(a.stage_s.values()) <= a.execute_s


def test_the_rewrite_changes_what_the_encoders_see(devices8):
    plain, rewriting = build(devices8, False), build(devices8, True)
    kw = dict(num_inference_steps=STEPS, seed=1, output_type="np")
    a = plain("a red fox", **kw).images[0]
    b = rewriting("a red fox", **kw).images[0]
    assert a.shape == b.shape and not np.array_equal(a, b)
    report = rewriting.weight_report()["per_component_nbytes"]
    assert report["rewriter"] > 0 and "rewriter" not in \
        plain.weight_report()["per_component_nbytes"]
    stages = rewriting.prepare_stages(STEPS)
    assert stages.rewrite is not None and plain.prepare_stages(
        STEPS).rewrite is None
    # the stage run by the caller, or by encode itself: the same embeddings
    ids = stages.rewrite(["a red fox"])
    e1 = stages.encode(["a red fox"], [""], ids)
    e2 = stages.encode(["a red fox"], [""])
    for x, y in zip(jax.tree.leaves(e1), jax.tree.leaves(e2)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_several_chips_are_refused(devices8):
    with pytest.raises(NotImplementedError, match="one chip"):
        DistriSDXLPipeline.from_params(
            DistriConfig(devices=devices8[:2], height=128, width=128),
            None, None, None, None, [], [], rewriter=(LM, None, SPEC))
