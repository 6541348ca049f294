"""The latent-attention rewrite cell's own pieces: the arithmetic of the cut,
the traffic it reuses, the control its logit limits must catch, and the
readers of its programs' counters and scopes (the manifest, reference and
rehearsal tests take the cell in as one more case of their parametrised
tests)."""

import argparse
import hashlib
import json
import os
import re
import types

import numpy as np
import pytest
from _util import BENCH, manifest

import run as bench_run

CELL = "kanana-sdxl-1024-rewrite"
CONFIG = "kanana-2-30b-sdxl-rewrite"
LIMITS = ["lm_logit_rel_rmse_median", "lm_logit_rel_rmse_late",
          "lm_logit_rel_rmse_worst", "lm_router_slack_worst",
          "image_rel_rmse"]
NEW_METRICS = {
    "kanana_prefill_ms", "kanana_decode_ms_per_token",
    "kanana_decode_roofline", "mla_attn_ms_per_token",
    "mla_proj_ms_per_token", "mla_cache_mb", "kanana_moe_local_per_token",
    "kanana_moe_experts_ms_per_token"}


def published():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_the_cut_is_the_one_stated():
    from benchmark.families import deepseek_v3_sdxl as fam
    from distrifuser_tpu.models.deepseek_v3 import param_shapes

    config = published()
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 48, "n_routed_experts": 128,
        "vocab_size": 128256, "parameters": 30_670_815_104}
    for key in config["reduced"]:
        assert config[key] == config["held"][key]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (24, 16, 16032)
    assert config["expert_parallel"] == {"chips": 8, "index": 0}
    assert "8 chips share each layer" in config["deployment"]
    assert "2 pipeline stages" in config["deployment"]
    # every width as published (the catalog's keys, whole)
    for key, value in {
            "hidden_size": 2048, "intermediate_size": 6144,
            "moe_intermediate_size": 768, "kv_lora_rank": 512,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "qk_head_dim": 192, "v_head_dim": 128, "head_dim": 64,
            "num_attention_heads": 32, "num_key_value_heads": 32,
            "num_experts_per_tok": 6, "n_shared_experts": 2,
            "first_k_dense_replace": 1, "q_lora_rank": None,
            "rope_scaling": None, "rope_interleave": True,
            "rope_theta": 1000000, "rms_norm_eps": 1e-6,
            "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
            "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
            "norm_topk_prob": True, "model_type": "deepseek_v3",
            "max_position_embeddings": 32768, "hidden_act": "silu",
            "moe_layer_freq": 1, "attention_bias": False,
            "tie_word_embeddings": False}.items():
        assert config[key] == value, key
    # ... and the counts from the program's own shapes

    def count(cfg):
        return fam._leaf_count(param_shapes(fam.Family(cfg).lm_config))

    held = fam.Family(config)
    assert count(config) == config["held"]["parameters"] == 2_695_349_120
    whole = dict(config, num_hidden_layers=48, n_routed_experts=128,
                 vocab_size=128256, expert_parallel={"chips": 1, "index": 0})
    assert count(whole) == config["published"]["parameters"]
    # a decode step: the weights outside the routed experts once, 0.75
    # experts a token and expert layer, ~8450 rows of 1152 B a layer
    step = held.decode_step_bytes()
    assert step["weights"] == 2 * (24 * (26_345_984 + 4_096) + 37_748_736
                                   + 23 * (262_272 + 9_437_184))
    assert step["routed_experts"] == 0.75 * 23 * 4_718_592 * 2
    assert step["latent_cache"] == 24 * 8449.5 * 1152
    assert step["head_and_embedding"] == 2 * (2048 * 16032 + 2 * 2048)
    assert sum(v for k, v in step.items() if k != "total") == step["total"]
    assert 2.24e9 <= step["total"] <= 2.26e9, step
    assert held.decode_step_bytes(1.5)["routed_experts"] == 2 * step[
        "routed_experts"]
    assert held.step_cost(1024, 1024)["flops"] < 7e12  # one UNet row
    # the rewrite: 8064 ids snapshotted, 128 a request, cache 8192 .. 8703
    rw = config["rewrite"]
    prompt = rw["instruction_tokens"] + rw["user_tokens"]
    assert (prompt, prompt % 128, rw["new_tokens"]) == (8192, 0, 512)
    assert 4 * prompt == config["max_position_embeddings"]
    assert min(rw["instruction_tokens"], prompt - 1) // 128 * 128 == 8064


def test_the_traffic_file_is_the_rewrite_cells_unchanged():
    with open(os.path.join(BENCH, "traffic", "solo-1024-rewrite.json"),
              "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()
    assert digest == ("8afea56392986303422c2191c508191f"
                      "1cf6a7664eed7a8a3ebb13ff8bc98a61"), digest
    m = manifest()
    cells = {c["name"]: c for c in m["workloads"]}
    assert cells[CELL] == m["workloads"][-1]  # appended
    assert cells[CELL]["traffic"] == "solo-1024-rewrite"
    assert cells[CELL]["chips"] == 1 and cells[CELL]["config"] == CONFIG
    assert m["configs"][-1]["name"] == CONFIG
    new = [p for p in m["per_layer"] if p.get("workloads") == [CELL]]
    assert {p["name"] for p in new} == NEW_METRICS
    assert new == m["per_layer"][-len(new):]


def test_a_latent_cache_in_float8_is_not_correct_and_every_metric_reads(
        capsys):
    """The control of the logit limits at a size a test holds: the cell as
    committed but for `cache_dtype` float8_e4m3fn, the latent cache a
    precision below the one the configuration states.  The traced run goes
    through, every new per-layer metric reads a number, the median logit
    reading fails its limit - one of the cell's limits, not each - and
    `correct` is false."""
    spec = bench_run.resolve_cell(CELL, rehearse=True)
    spec["config"] = bench_run.merged(spec["config"],
                                      {"cache_dtype": "float8_e4m3fn"})
    args = argparse.Namespace(workload=CELL, seed=12, seconds=1.0, trace=1,
                              rehearse=True)
    capsys.readouterr()
    assert bench_run.run(args, spec) == 0
    out = capsys.readouterr().out
    last = json.loads(out.splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0
    line = next(ln for ln in out.splitlines() if ln.startswith("lm logits"))
    assert dict(re.findall(r"(lm_\w+) value=\S+ limit=\S+ (\w+)", line)) == {
        "lm_logit_rel_rmse_median": "FAILED", "lm_logit_rel_rmse_late": "ok",
        "lm_logit_rel_rmse_worst": "ok", "lm_router_slack_worst": "ok"}
    failed = re.search(r"checks: \d+ made, failed: (.*)", out).group(1)
    assert re.fullmatch(r"\['image_rel_rmse\[request \d+\]'\]", failed), failed
    assert NEW_METRICS <= set(last["metrics"])
    values = {k: last["metrics"][k]["value"] for k in NEW_METRICS}
    assert all(v > 0 for v in values.values()), values
    # a quarter of the bytes of the committed cell's cache: 1 B a number
    lm = spec["config"]
    rw = lm["rewrite"]
    rows = rw["instruction_tokens"] + rw["user_tokens"] + rw["new_tokens"]
    assert values["mla_cache_mb"] * 1e6 == lm["num_hidden_layers"] * rows * (
        lm["kv_lora_rank"] + lm["qk_rope_head_dim"])
    assert 0.2 < values["kanana_moe_local_per_token"] < 1.5  # ~6 * 4 / 32


def test_readers_find_nothing_without_this_rewriter():
    from benchmark.harness import eva_readers as E
    from benchmark.harness import mla_readers as R

    for rewriter in (None, types.SimpleNamespace(  # a model of another kind
            lm=types.SimpleNamespace(counters=("tokens_prefilled",)),
            served=[object()])):
        bench = types.SimpleNamespace(
            family=types.SimpleNamespace(rewriter=rewriter),
            traced=[{"ok": True}])
        ctx = {"bench": bench, "trace": {"devices": {}}}
        assert R.moe_local_per_token(ctx) is None
        assert R.decode_roofline(ctx) is None
        assert E.state_mb(ctx) is None
    # ... and nothing of a family with no rewriter at all
    ctx = {"bench": types.SimpleNamespace(family=object(), traced=[]),
           "trace": None}
    assert R.decode_roofline(ctx) is None and E.state_mb(ctx) is None


def test_scopes_and_counters_are_read_from_the_rewriters_own_programs():
    """The decode program of a small rewriter, compiled: its text holds ops
    under each of the language model's named scopes; the counters say the
    snapshot engaged, and the cache reader reads the cache's size."""
    import jax

    from benchmark.harness import eva_readers as E
    from benchmark.harness import lm_readers as L
    from benchmark.harness import mla_readers as R
    from benchmark.reference import deepseek_v3_sdxl as ref
    from distrifuser_tpu.models import deepseek_v3 as lm
    from distrifuser_tpu.pipelines import (
        PromptRewriter,
        RewriteSpec,
        SimpleTokenizer,
    )

    config = bench_run.merged(published(), published()["rehearse"])
    cfg = lm.deepseek_v3_config_from_json(config)
    rewriter = PromptRewriter(
        cfg, lm.init_deepseek_v3_params(jax.random.PRNGKey(0), cfg),
        RewriteSpec(**config["rewrite"]), [SimpleTokenizer(1000)])
    out = rewriter(["a red fox"])
    assert out[0].shape == (1, 77)
    scopes = set(L.scope_of_instruction(rewriter.decode_program_text())
                 .values())
    for name in ("lm.mla.proj", "lm.mla.attn", "lm.moe.router",
                 "lm.moe.experts", "lm.moe.shared", "lm.mlp", "lm.head"):
        assert any(f"/{name}/" in s for s in scopes), name
    ctx = {"bench": types.SimpleNamespace(
        family=types.SimpleNamespace(rewriter=rewriter))}
    rw = config["rewrite"]
    prompt = rw["instruction_tokens"] + rw["user_tokens"]
    total = prompt + rw["new_tokens"]
    assert E.state_mb(ctx) * 1e6 == cfg.num_hidden_layers * total * 4 * (
        cfg.kv_lora_rank + cfg.qk_rope_head_dim)
    counters = R._counters(ctx)
    assert counters["tokens_reused"] == rewriter._prefix_len == 40
    assert counters["tokens_prefilled"] == prompt
    assert counters["tokens_decoded"] == rw["new_tokens"]
    assert counters["expert_assignments"] == total * cfg.n_expert_layers * \
        cfg.num_experts_per_tok
    served = rewriter.served[-1]
    experts = np.asarray(served.experts[1])
    assert experts.shape == (cfg.n_expert_layers, total,
                             cfg.num_experts_per_tok)
    held = (experts >= cfg.first_local_expert) & (
        experts < cfg.first_local_expert + cfg.n_local_experts)
    assert counters["expert_assignments_held"] == int(held.sum())
    assert R.moe_local_per_token(ctx) == pytest.approx(
        held.sum() / (total * cfg.n_expert_layers))
    assert np.array_equal(served.prompt_ids,
                          ref.prompt_ids(config, "a red fox"))


@pytest.mark.parametrize("name", LIMITS)
def test_every_limit_is_written_with_its_reason(name):
    limits = bench_run.load_json("limits", CELL + ".json")
    for section in (limits, limits["rehearse"]):
        assert section[name]["limit"] > 0 and len(section[name]["why"]) > 20
    assert set(limits) == set(LIMITS) | {"rehearse"}
    assert "readings" in limits[name]
