"""The linear-attention rewrite cell's own pieces: the arithmetic of the cut,
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

CELL = "kimi-sdxl-1024-rewrite"
CONFIG = "kimi-linear-48b-sdxl-rewrite"
LIMITS = ["lm_logit_rel_rmse_median", "lm_logit_rel_rmse_late",
          "lm_logit_rel_rmse_worst", "lm_router_slack_worst",
          "image_rel_rmse"]
NEW_METRICS = {
    "kimi_prefill_ms", "kimi_decode_ms_per_token", "kimi_decode_roofline",
    "kda_recur_ms_per_token", "kda_proj_ms_per_token",
    "kimi_mla_attn_ms_per_token", "kda_state_mb", "kimi_moe_local_per_token",
    "kimi_moe_experts_ms_per_token"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def published():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_the_cut_is_the_one_stated():
    from benchmark.families import kimi_linear_sdxl as fam
    from distrifuser_tpu.models.kimi_linear import param_shapes

    config = published()
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840,
        "parameters": 49_122_681_728}
    for key in config["reduced"]:
        assert config[key] == config["held"][key]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (12, 32, 20480)
    assert config["expert_parallel"] == {"chips": 8, "index": 0}
    assert "8 chips share each layer" in config["deployment"]
    # every width as published (the catalog's keys, whole)
    for key, value in {
            "hidden_size": 2304, "intermediate_size": 9216,
            "moe_intermediate_size": 1024, "kv_lora_rank": 512,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "v_head_dim": 128, "head_dim": 72, "num_attention_heads": 32,
            "num_key_value_heads": 32, "num_experts_per_token": 8,
            "num_shared_experts": 1, "first_k_dense_replace": 1,
            "q_lora_rank": None, "rope_scaling": None, "rope_theta": 10000,
            "rms_norm_eps": 1e-5, "routed_scaling_factor": 2.446,
            "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
            "num_expert_group": 1, "topk_group": 1, "use_grouped_topk": True,
            "model_type": "kimi_linear", "model_max_length": 1048576,
            "hidden_act": "silu", "moe_layer_freq": 1, "mla_use_nope": True,
            "num_nextn_predict_layers": 0,
            "tie_word_embeddings": False}.items():
        assert config[key] == value, key
    linear = config["linear_attn_config"]
    assert linear["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert len(linear["kda_layers"]) == 20 and linear["num_heads"] == 32
    assert (linear["head_dim"], linear["short_conv_kernel_size"]) == (128, 4)
    assert config["state_dtype"] == "float32"
    for point in ("kda_short_convolution", "kda_qk_norm_and_scale",
                  "kda_gate", "kda_gated_norm", "mla_nope", "fused_kernels"):
        assert len(config["assumed"][point]) > 40, point
    # ... and the counts from the program's own shapes

    def count(cfg):
        return fam._leaf_count(param_shapes(fam.Family(cfg).lm_config))

    held = fam.Family(config)
    assert held.lm_config.kinds == ("kda", "kda", "kda", "mla") * 3
    assert count(config) == config["held"]["parameters"] == 3_176_867_744
    whole = dict(config, num_hidden_layers=27, num_experts=256,
                 vocab_size=163840, expert_parallel={"chips": 1, "index": 0})
    assert count(whole) == config["published"]["parameters"]
    # a decode step: the weights outside the routed experts once, 1 expert a
    # token and expert layer, nine 2 MB states read and written, ~8450 rows
    # of 1152 B a full layer
    step = held.decode_step_bytes()
    assert step["weights"] == 2 * (8 * 47_186_848 + 3 * 36_787_456
                                   + 103_219_872)
    assert step["routed_experts"] == 1.0 * 11 * 7_077_888 * 2
    assert step["kda_state"] == 2 * 9 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)
    assert step["latent_cache"] == 3 * 8449.5 * 1152
    assert step["head_and_embedding"] == 2 * (2304 * 20480 + 2 * 2304)
    assert sum(v for k, v in step.items() if k != "total") == step["total"]
    assert 1.49e9 <= step["total"] <= 1.51e9, step
    assert held.decode_step_bytes(1.5)["routed_experts"] == 1.5 * step[
        "routed_experts"]
    low = fam.Family(dict(config, state_dtype="bfloat16"))
    assert low.decode_step_bytes()["kda_state"] < 0.6 * step["kda_state"]
    assert held.step_cost(1024, 1024)["flops"] < 7e12  # one UNet row
    # the rewrite: Kanana's block - 8064 ids snapshotted, 128 a request
    rw = config["rewrite"]
    assert rw == bench_run.load_json(
        "configs", "kanana-2-30b-sdxl-rewrite.json")["rewrite"]
    prompt = rw["instruction_tokens"] + rw["user_tokens"]
    assert (prompt, prompt % 128, rw["new_tokens"]) == (8192, 0, 512)
    assert min(rw["instruction_tokens"], prompt - 1) // 128 * 128 == 8064
    assert 8064 // config["kda_chunk"] == 126


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_catalog_key_is_in_the_file_at_its_value_but_the_reduced():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    config = published()
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    # no width among the reduced keys
    assert not [k for k in config["reduced"] if k.endswith(("_dim", "_rank"))
                or "size" in k.replace("vocab_size", "")]


def test_the_cell_and_its_metrics_are_appended_and_the_traffic_is_unchanged():
    with open(os.path.join(BENCH, "traffic", "solo-1024-rewrite.json"),
              "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()
    assert digest == ("8afea56392986303422c2191c508191f"
                      "1cf6a7664eed7a8a3ebb13ff8bc98a61"), digest
    m = manifest()
    cells = {c["name"]: c for c in m["workloads"]}
    assert cells[CELL]["traffic"] == "solo-1024-rewrite"
    assert cells[CELL]["chips"] == 1 and cells[CELL]["config"] == CONFIG
    names = [c["name"] for c in m["workloads"]]
    assert names.index(CELL) > names.index("kanana-sdxl-1024-rewrite")
    configs = [c["name"] for c in m["configs"]]
    assert configs.index(CONFIG) > configs.index("kanana-2-30b-sdxl-rewrite")
    new = [p for p in m["per_layer"] if p.get("workloads") == [CELL]]
    assert {p["name"] for p in new} == NEW_METRICS
    first = m["per_layer"].index(new[0])
    assert new == m["per_layer"][first:first + len(new)]
    assert first > [p["name"] for p in m["per_layer"]].index(
        "kanana_moe_experts_ms_per_token")
    assert all(p["moves"] == "image_s" for p in new)
    # every other metric's list of cells is as it was: this cell in none
    assert not [p["name"] for p in m["per_layer"]
                if CELL in p.get("workloads", []) and p not in new]


def test_matrix_states_in_bfloat16_are_not_correct_and_every_metric_reads(
        capsys):
    """The control of the logit limits at a size a test holds: the cell as
    committed but for `state_dtype` bfloat16, the KDA layers' matrix states
    a precision below the float32 the configuration states.  The traced run
    goes through, every new per-layer metric reads a number, the median
    logit reading fails its limit - one of the cell's limits, not each - and
    `correct` is false."""
    spec = bench_run.resolve_cell(CELL, rehearse=True)
    spec["config"] = bench_run.merged(spec["config"],
                                      {"state_dtype": "bfloat16"})
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
    # the state the loop holds: three KDA layers' matrix states at 2 B a
    # number and their float32 tails, one latent cache in float32
    lm = spec["config"]
    rw, linear = lm["rewrite"], lm["linear_attn_config"]
    rows = rw["instruction_tokens"] + rw["user_tokens"] + rw["new_tokens"]
    wide = linear["num_heads"] * linear["head_dim"]
    assert values["kda_state_mb"] * 1e6 == 3 * (
        wide * linear["head_dim"] * 2 + 3 * 3 * wide * 4) + rows * 4 * (
            lm["kv_lora_rank"] + lm["qk_rope_head_dim"])
    assert 0.3 < values["kimi_moe_local_per_token"] < 2.0  # ~8 * 4 / 32


def test_readers_find_nothing_without_this_rewriter():
    from benchmark.harness import eva_readers as E
    from benchmark.harness import lm_readers as L
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
    # ... and nothing of a family with no rewriter at all, as the parent of
    # this PR is for every reader the new metrics name
    ctx = {"bench": types.SimpleNamespace(family=object(), traced=[]),
           "trace": None}
    assert R.decode_roofline(ctx) is None and E.state_mb(ctx) is None
    assert L.module_ms(ctx, "decode", per_token=True) is None
    assert L.scope_ms_per_token(ctx, "lm.kda.recur") is None


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_new_metric_names_a_reader_the_harness_already_has(name):
    """Data, not code: every new per-layer metric is read by a function an
    earlier PR wrote."""
    spec = bench_run.load_json("layer_metrics", name + ".json")
    assert spec["workloads"] == [CELL] and spec["moves"] == "image_s"
    assert spec["reader"] in {
        "harness.lm_readers:module_ms",
        "harness.lm_readers:scope_ms_per_token",
        "harness.mla_readers:decode_roofline",
        "harness.mla_readers:moe_local_per_token",
        "harness.eva_readers:state_mb"}
    assert len(spec["what"]) > 60
    if spec["reader"].endswith("scope_ms_per_token"):
        assert spec["params"]["scope"] in (
            "lm.kda.recur", "lm.kda.proj", "lm.mla.attn", "lm.moe.experts")


def test_scopes_and_counters_are_read_from_the_rewriters_own_programs():
    """The decode program of a small rewriter, compiled: its text holds ops
    under each of the language model's named scopes; the counters say the
    snapshot engaged, and the state reader reads both kinds of state."""
    import jax

    from benchmark.harness import eva_readers as E
    from benchmark.harness import lm_readers as L
    from benchmark.harness import mla_readers as R
    from benchmark.reference import kimi_linear_sdxl as ref
    from distrifuser_tpu.models import kimi_linear as lm
    from distrifuser_tpu.pipelines import (
        PromptRewriter,
        RewriteSpec,
        SimpleTokenizer,
    )

    config = bench_run.merged(published(), published()["rehearse"])
    cfg = lm.kimi_linear_config_from_json(config)
    assert cfg.kinds == ("kda", "kda", "kda", "mla")
    assert (cfg.num_experts, cfg.n_local_experts) == (32, 4)
    rewriter = PromptRewriter(
        cfg, lm.init_kimi_linear_params(jax.random.PRNGKey(0), cfg),
        RewriteSpec(**config["rewrite"]), [SimpleTokenizer(1000)])
    out = rewriter(["a red fox"])
    assert out[0].shape == (1, 77)
    scopes = set(L.scope_of_instruction(rewriter.decode_program_text())
                 .values())
    for name in ("lm.kda.proj", "lm.kda.conv", "lm.kda.gate", "lm.kda.recur",
                 "lm.kda.norm", "lm.mla.proj", "lm.mla.attn", "lm.moe.router",
                 "lm.moe.experts", "lm.moe.shared", "lm.mlp", "lm.head"):
        assert any(f"/{name}/" in s for s in scopes), name
    ctx = {"bench": types.SimpleNamespace(
        family=types.SimpleNamespace(rewriter=rewriter))}
    rw = config["rewrite"]
    prompt = rw["instruction_tokens"] + rw["user_tokens"]
    total = prompt + rw["new_tokens"]
    wide = cfg.kda_num_heads * cfg.kda_head_dim
    assert E.state_mb(ctx) * 1e6 == 3 * 4 * (
        wide * cfg.kda_head_dim + 3 * 3 * wide) + total * 4 * (
            cfg.kv_lora_rank + cfg.qk_rope_head_dim)
    counters = R._counters(ctx)
    assert counters["tokens_reused"] == rewriter._prefix_len == 40
    assert counters["tokens_prefilled"] == prompt
    assert counters["tokens_decoded"] == rw["new_tokens"]
    assert counters["kda_chunks"] == 3 * prompt // cfg.kda_chunk
    assert counters["expert_assignments"] == total * cfg.n_expert_layers * \
        cfg.num_experts_per_token
    served = rewriter.served[-1]
    experts = np.asarray(served.experts[1])
    assert experts.shape == (cfg.n_expert_layers, total,
                             cfg.num_experts_per_token)
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


def test_the_reference_shares_nothing_with_the_programs_ops():
    """Plain float32 `jax.numpy`: the reference's source names no module of
    `distrifuser_tpu`, no chunk, no cache and no kernel route."""
    with open(os.path.join(BENCH, "reference", "kimi_linear_sdxl.py")) as f:
        source = f.read()
    body = source.split('"""', 2)[2]  # past the module's docstring
    code = "\n".join(line.split("#")[0] for line in body.splitlines())
    assert "import distrifuser_tpu" not in code
    assert "from distrifuser_tpu" not in code
    for word in ("pallas", "triangular_solve", "ragged_dot", "bfloat16"):
        assert word not in code, word
