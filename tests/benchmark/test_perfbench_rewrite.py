"""The rewrite cell's own pieces: the control its logit limit must catch,
the readers of the language model's programs, and the arithmetic of the cut
(the manifest, reference and rehearsal tests take the cell in as one more
case of their parametrised tests)."""

import argparse
import json
import os
import re
import types

import jax
import pytest
from _util import BENCH, manifest

import run as bench_run

CELL = "nemotron-sdxl-1024-rewrite"
CONFIG = "nemotron-3-super-sdxl-rewrite"


def published():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_the_cut_is_the_one_stated():
    from benchmark.families import nemotron_h_sdxl as fam

    config = published()
    assert config["published"] == {
        "num_hidden_layers": 88, "n_routed_experts": 512,
        "vocab_size": 131072, "num_nextn_predict_layers": 1}
    assert sorted(config["reduced"]) == sorted(config["published"])
    assert "8 chips share each layer" in config["deployment"]
    # every width as published
    for key, value in {
            "hidden_size": 4096, "mamba_num_heads": 128, "mamba_head_dim": 64,
            "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4,
            "chunk_size": 128, "num_attention_heads": 32,
            "num_key_value_heads": 2, "head_dim": 128,
            "num_experts_per_tok": 22, "moe_latent_size": 1024,
            "moe_intermediate_size": 2688, "routed_scaling_factor": 5,
            "moe_shared_expert_intermediate_size": 5376}.items():
        assert config[key] == value, key
    assert len(config["hybrid_override_pattern"]) == 88
    family = fam.Family(config)
    cfg = family.lm_config
    assert cfg.pattern == "MEMEMEMEM*E"  # one whole period: 5 M, 5 E, 1 *
    assert (cfg.n_routed_experts, cfg.n_local_experts, cfg.vocab_size) == (
        512, 64, 16384)
    from distrifuser_tpu.models.nemotron_h import param_shapes

    count = fam._leaf_count(param_shapes(cfg))
    assert round(count / 1e6, 1) == 2752.3  # 5.50 GB in bfloat16
    sizes = {kind: fam._leaf_count(
        param_shapes(cfg)["layers"][cfg.pattern.index(kind)]["mixer"])
        for kind in "M*E"}
    assert round(sizes["M"] / 1e6, 2) == 109.64
    assert round(sizes["*"] / 1e6, 2) == 35.65
    assert round((sizes["E"] - 64 * 5.505024e6) / 1e6, 2) == 54.53
    # a decode step: 856.5 M of mixers, 2.75 experts a layer, state, head
    step = family.decode_step_bytes()
    assert 2.02e9 <= step["total"] <= 2.06e9, step
    assert round(step["state"] / 1e6) == 42
    more = family.decode_step_bytes(held_per_token=3.75)["total"]
    assert round((more - step["total"]) / 1e6) == round(5 * 2 * 5.505024)
    assert family.step_cost(1024, 1024)["flops"] < 7e12  # one UNet row


def test_the_traffic_is_solo_1024s_with_24_word_prompts():
    solo = bench_run.load_json("traffic", "solo-1024.json")
    mine = bench_run.load_json("traffic", "solo-1024-rewrite.json")
    for key in ("arrivals", "tail", "trace", "distri"):
        assert mine[key] == solo[key], key
    assert mine["serve"] == dict(solo["serve"], warmup_cfg=False)
    assert mine["request"] == dict(solo["request"], prompt_words=[24, 24])
    cell = next(c for c in manifest()["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "solo-1024-rewrite"


def test_a_state_kept_in_bfloat16_is_not_correct(capsys):
    """The control of the logit limits at a size a test holds: the cell as
    committed but for the SSM state a precision below the float32 the
    configuration states.  The run goes through, the late positions' logit
    reading fails its limit - one of the cell's limits, not each - and
    `correct` is false."""
    spec = bench_run.resolve_cell(CELL, rehearse=True)
    spec["config"] = bench_run.merged(spec["config"],
                                      {"state_dtype": "bfloat16"})
    args = argparse.Namespace(workload=CELL, seed=12, seconds=1.0, trace=0,
                              rehearse=True)
    capsys.readouterr()
    assert bench_run.run(args, spec) == 0
    out = capsys.readouterr().out
    last = json.loads(out.splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0
    line = next(ln for ln in out.splitlines() if ln.startswith("lm logits"))
    assert dict(re.findall(r"(lm_\w+) value=\S+ limit=\S+ (\w+)", line)) == {
        "lm_logit_rel_rmse_median": "ok", "lm_logit_rel_rmse_late": "FAILED",
        "lm_logit_rel_rmse_worst": "ok", "lm_router_slack_worst": "ok"}
    failed = re.search(r"checks: \d+ made, failed: (.*)", out).group(1)
    assert re.fullmatch(r"\['image_rel_rmse\[request \d+\]'\]", failed), failed


def test_readers_find_nothing_without_a_rewriter():
    from benchmark.harness import lm_readers as R

    bench = types.SimpleNamespace(family=object(), traced=[{"ok": True}])
    ctx = {"bench": bench, "trace": {"devices": {}}}
    assert R.module_ms(ctx, "decode") is None
    assert R.scope_ms_per_token(ctx, "lm.mamba") is None
    assert R.moe_local_per_token(ctx) is None
    assert R.decode_roofline(ctx) is None


def test_scopes_are_read_from_the_compiled_programs_text():
    from benchmark.harness import lm_readers as R

    text = '''
  %fusion.7 = f32[4,8]{1,0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(rewrite_decode)/jit(main)/while/body/lm.mamba/mul" source_file="x.py"}
  ROOT %dot.1 = f32[4]{0} dot(%b, %c), metadata={op_name="jit(rewrite_decode)/jit(main)/while/body/lm.head/dot_general"}
  %bare = f32[] constant(0)
'''
    assert R.scope_of_instruction(text) == {
        "fusion.7": "jit(rewrite_decode)/jit(main)/while/body/lm.mamba/mul",
        "dot.1": "jit(rewrite_decode)/jit(main)/while/body/lm.head/"
                 "dot_general"}

    def f(x):
        with jax.named_scope("lm.mamba"):
            return jax.numpy.tanh(x) * 2.0

    compiled = jax.jit(f).lower(jax.numpy.ones((4, 4))).compile().as_text()
    assert any("/lm.mamba/" in scope for scope in
               R.scope_of_instruction(compiled).values())


@pytest.mark.parametrize("name", ["lm_logit_rel_rmse_median",
                                  "lm_logit_rel_rmse_late",
                                  "lm_logit_rel_rmse_worst",
                                  "lm_router_slack_worst",
                                  "image_rel_rmse"])
def test_every_limit_is_written_with_its_reason(name):
    limits = bench_run.load_json("limits", CELL + ".json")
    for section in (limits, limits["rehearse"]):
        assert section[name]["limit"] > 0 and len(section[name]["why"]) > 20
    assert limits["rehearse"][name]["limit"] <= limits[name]["limit"]
