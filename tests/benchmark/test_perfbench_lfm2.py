"""The convolution-attention rewrite cell's own pieces: the arithmetic of the
cut, the traffic it reuses, the two controls its limits must catch, and the
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

CELL = "lfm2-sdxl-1024-rewrite"
CONFIG = "lfm2-24b-a2b-sdxl-rewrite"
LIMITS = ["lm_logit_rel_rmse_median", "lm_logit_rel_rmse_late",
          "lm_logit_rel_rmse_worst", "lm_router_slack_worst",
          "lm_cache_float8_nearness", "image_rel_rmse"]
# in the manifest's order
NEW_METRICS = [
    "lfm2_prefill_ms", "lfm2_decode_ms_per_token",
    "lfm2_conv_proj_ms_per_token", "lfm2_conv_ms_per_token",
    "lfm2_attn_ms_per_token", "lfm2_mlp_ms_per_token",
    "lfm2_moe_experts_ms_per_token", "lfm2_moe_local_per_token",
    "lfm2_state_mb", "lfm2_cache_staged_mb_per_token",
    "lfm2_decode_roofline"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def published():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_the_cut_is_the_one_stated():
    from benchmark.families import lfm2_sdxl as fam
    from distrifuser_tpu.models.lfm2 import param_shapes

    config = published()
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 40, "num_experts": 64, "vocab_size": 65536,
        "parameters": 23_843_661_440}
    for key in config["reduced"]:
        assert config[key] == config["held"][key]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (20, 16, 16384)
    assert config["expert_parallel"] == {"chips": 4, "index": 0}
    assert "4 chips (one v5e 2x2 host) share each layer" in config[
        "deployment"] and "two pipeline stages of 20" in config["deployment"]
    for point in ("tied_head", "conv_chunk_order", "short_convolution",
                  "qk_head_norms", "rotary_pairing", "router", "final_norm",
                  "fused_kernels", "cache_layout", "controls"):
        assert len(config["assumed"][point]) > 40, point
    assert "1e-6" in config["assumed"]["router"]
    assert "SEEDED_QK_NORM_SCALE" in config["weights"]
    # ... and the counts from the program's own shapes

    def count(cfg):
        return fam._leaf_count(param_shapes(fam.Family(cfg).lm_config))

    held = fam.Family(config)
    kinds = held.lm_config.kinds
    assert kinds == ("conv", "conv") + ("full_attention", "conv", "conv",
                                        "conv") * 4 + ("full_attention",
                                                       "conv")
    assert (kinds.count("conv"), kinds.count("full_attention")) == (15, 5)
    shapes = param_shapes(held.lm_config)["layers"]
    assert ["router" in layer["ffn"] for layer in shapes] == (
        [False] * 2 + [True] * 18)
    assert count(config) == config["held"]["parameters"] == 3_202_791_168
    assert round(2 * count(config) / 1e9, 2) == config["held"][
        "gigabytes_bf16"]
    whole = dict(config, num_hidden_layers=40, num_experts=64,
                 vocab_size=65536, expert_parallel={"chips": 1, "index": 0})
    assert count(whole) == config["published"]["parameters"]
    # a decode step: the weights outside the routed experts once, 1 expert a
    # token and expert layer, fifteen 8 KB tails read and written, ~8450
    # rows of 2 x 4 x 256 B an attention layer, the tied matrix once
    step = held.decode_step_bytes()
    assert step["weights"] == 2 * (
        15 * 16_783_360 + 5 * 10_485_888 + 20 * 4096 + 18 * 131_136
        + 2 * 72_351_744)
    assert step["routed_experts"] == 1.0 * 18 * 9_437_184 * 2
    assert step["conv_tails"] == 2 * 15 * 2 * 2048 * 2
    assert step["kv_cache"] == 5 * 8449.5 * 2 * 4 * 128 * 2
    assert step["head_and_embedding"] == 2 * (16384 * 2048 + 2 * 2048)
    assert sum(v for k, v in step.items() if k != "total") == step["total"]
    assert 1.38e9 <= step["total"] <= 1.42e9, step
    assert held.decode_step_bytes(1.5)["routed_experts"] == 1.5 * step[
        "routed_experts"]
    assert held.step_cost(1024, 1024)["flops"] < 7e12  # one UNet row
    # the rewrite: the sibling expert cells' lengths, a seed of its own
    rw = config["rewrite"]
    kanana = bench_run.load_json(
        "configs", "kanana-2-30b-sdxl-rewrite.json")["rewrite"]
    assert dict(rw, instruction_seed=0) == dict(kanana, instruction_seed=0)
    assert rw["instruction_seed"] == 45
    prompt = rw["instruction_tokens"] + rw["user_tokens"]
    assert (prompt, prompt % 128, rw["new_tokens"]) == (8192, 0, 512)
    assert min(rw["instruction_tokens"], prompt - 1) // 128 * 128 == 8064


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_catalog_key_is_in_the_file_at_its_value_but_the_reduced():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
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


def test_the_cell_and_its_metrics_are_in_the_manifest_and_the_traffic_is_unchanged():
    """Presence, and order among themselves - never "last", never "exactly
    these": a later PR appends its own."""
    with open(os.path.join(BENCH, "traffic", "solo-1024-rewrite.json"),
              "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()
    assert digest == ("8afea56392986303422c2191c508191f"
                      "1cf6a7664eed7a8a3ebb13ff8bc98a61"), digest
    m = manifest()
    cells = {c["name"]: c for c in m["workloads"]}
    assert cells[CELL]["traffic"] == "solo-1024-rewrite"
    assert cells[CELL]["chips"] == 1 and cells[CELL]["config"] == CONFIG
    configs = {c["name"]: c for c in m["configs"]}
    assert configs[CONFIG]["file"] == f"benchmark/configs/{CONFIG}.json"
    assert configs[CONFIG]["reduced"] == published()["reduced"]
    assert configs[CONFIG]["source"] == published()["source"]
    names = [p["name"] for p in m["per_layer"]]
    assert [n for n in names if n in NEW_METRICS] == NEW_METRICS
    for p in m["per_layer"]:
        if p["name"] in NEW_METRICS:
            assert p["workloads"] == [CELL] and p["moves"] == "image_s"


@pytest.mark.parametrize("control, failed, passed", [
    ({"cache_dtype": "float8_e4m3fn"},
     {"lm_logit_rel_rmse_median", "lm_cache_float8_nearness"},
     {"lm_logit_rel_rmse_late", "lm_logit_rel_rmse_worst",
      "lm_router_slack_worst"}),
    ({"carry_conv_tails": False}, {"lm_logit_rel_rmse_worst"}, set())])
def test_a_control_is_not_correct_and_every_metric_reads(capsys, control,
                                                         failed, passed):
    """The two controls of the logit limits at a size a test holds: the cell
    as committed but for the KV caches in float8, a precision below the one
    the configuration states - and but for the conv tails, not carried into
    the suffix and into decoding.  The traced run goes through, every new
    per-layer metric reads a number, one of the cell's limits fails (the
    float8 cache by the reading made for it - the served logits do not lie
    nearer the reference's forward as stated than its forward over float8
    keys and values - and, at this size, by the median; by no other) and
    `correct` is false."""
    spec = bench_run.resolve_cell(CELL, rehearse=True)
    spec["config"] = bench_run.merged(spec["config"], control)
    args = argparse.Namespace(workload=CELL, seed=12, seconds=1.0, trace=1,
                              rehearse=True)
    capsys.readouterr()
    assert bench_run.run(args, spec) == 0
    out = capsys.readouterr().out
    last = json.loads(out.splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0
    line = next(ln for ln in out.splitlines() if ln.startswith("lm logits"))
    verdicts = dict(re.findall(r"(lm_\w+) value=\S+ limit=\S+ (\w+)", line))
    assert failed <= {k for k, v in verdicts.items() if v == "FAILED"}
    assert passed <= {k for k, v in verdicts.items() if v == "ok"}
    said = re.search(r"checks: \d+ made, failed: (.*)", out).group(1)
    assert re.fullmatch(r"\['image_rel_rmse\[request \d+\]'\]", said), said
    assert set(NEW_METRICS) <= set(last["metrics"])
    values = {k: last["metrics"][k]["value"] for k in NEW_METRICS}
    assert values.pop("lfm2_cache_staged_mb_per_token") == 0.0  # no VMEM here
    assert all(v > 0 for v in values.values()), values
    # the state the loop holds, as held: three tails [2, 64] float32, one
    # cache of keys and values [1, rows, 32] in its own dtype
    lm = spec["config"]
    rw = lm["rewrite"]
    rows = rw["instruction_tokens"] + rw["user_tokens"] + rw["new_tokens"]
    cache_itemsize = 1 if "cache_dtype" in control else 4
    assert values["lfm2_state_mb"] * 1e6 == (
        3 * 2 * 64 * 4 + 2 * rows * 32 * cache_itemsize)
    assert 0.3 < values["lfm2_moe_local_per_token"] < 2.0  # ~3 * 4 / 16


def test_readers_find_nothing_without_this_rewriter():
    from benchmark.harness import eva_readers as E
    from benchmark.harness import lfm2_readers as Z
    from benchmark.harness import lm_readers as L
    from benchmark.harness import mla_readers as R

    for rewriter in (None, types.SimpleNamespace(  # a model of another kind
            lm=types.SimpleNamespace(counters=("tokens_prefilled",)),
            config=types.SimpleNamespace(num_key_value_heads=4),
            served=[object()])):
        bench = types.SimpleNamespace(
            family=types.SimpleNamespace(rewriter=rewriter),
            traced=[{"ok": True}])
        ctx = {"bench": bench, "trace": {"devices": {}}}
        assert R.moe_local_per_token(ctx) is None
        assert R.decode_roofline(ctx) is None
        assert E.state_mb(ctx) is None
        assert Z.cache_staged_mb_per_token(ctx) is None
    # ... and nothing of a family with no rewriter at all, as the parent of
    # this PR is for every reader the new metrics name
    ctx = {"bench": types.SimpleNamespace(family=object(), traced=[]),
           "trace": None}
    assert R.decode_roofline(ctx) is None and E.state_mb(ctx) is None
    assert Z.cache_staged_mb_per_token(ctx) is None
    assert L.module_ms(ctx, "decode", per_token=True) is None
    assert L.scope_ms_per_token(ctx, "lm.conv") is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_names_its_reader_and_says_what_it_reads(name):
    """Data over readers an earlier PR wrote - but for the count of staged
    caches, whose shape no reader that was there knows."""
    spec = bench_run.load_json("layer_metrics", name + ".json")
    assert spec["workloads"] == [CELL] and spec["moves"] == "image_s"
    assert spec["reader"] in {
        "harness.lm_readers:module_ms",
        "harness.lm_readers:scope_ms_per_token",
        "harness.mla_readers:decode_roofline",
        "harness.mla_readers:moe_local_per_token",
        "harness.eva_readers:state_mb",
        "harness.lfm2_readers:cache_staged_mb_per_token"}
    assert (spec["reader"].split(":")[0] == "harness.lfm2_readers") == (
        name == "lfm2_cache_staged_mb_per_token")
    assert len(spec["what"]) > 60
    if spec["reader"].endswith("scope_ms_per_token"):
        assert spec["params"]["scope"] in (
            "lm.conv.proj", "lm.conv", "lm.attn", "lm.mlp", "lm.moe.experts")


def test_scopes_and_counters_are_read_from_the_rewriters_own_programs():
    """The decode program of a small rewriter, compiled: its text holds ops
    under each of the language model's named scopes; the counters say the
    snapshot engaged, and the state reader reads both kinds of state."""
    import jax

    from benchmark.harness import eva_readers as E
    from benchmark.harness import lfm2_readers as Z
    from benchmark.harness import lm_readers as L
    from benchmark.harness import mla_readers as R
    from benchmark.reference import lfm2_sdxl as ref
    from distrifuser_tpu.models import lfm2 as lm
    from distrifuser_tpu.pipelines import (
        PromptRewriter,
        RewriteSpec,
        SimpleTokenizer,
    )

    config = bench_run.merged(published(), published()["rehearse"])
    cfg = lm.lfm2_config_from_json(config)
    assert cfg.kinds == ("conv", "conv", "full_attention", "conv")
    assert (cfg.num_experts, cfg.n_local_experts, cfg.kv_pack) == (16, 4, 2)
    rewriter = PromptRewriter(
        cfg, lm.init_lfm2_params(jax.random.PRNGKey(0), cfg),
        RewriteSpec(**config["rewrite"]), [SimpleTokenizer(1000)])
    out = rewriter(["a red fox"])
    assert out[0].shape == (1, 77)
    scopes = set(L.scope_of_instruction(rewriter.decode_program_text())
                 .values())
    for name in ("lm.conv.proj", "lm.conv", "lm.attn.proj", "lm.attn",
                 "lm.mlp", "lm.moe.router", "lm.moe.experts", "lm.head"):
        assert any(f"/{name}/" in s for s in scopes), name
    ctx = {"bench": types.SimpleNamespace(
        family=types.SimpleNamespace(rewriter=rewriter))}
    rw = config["rewrite"]
    prompt = rw["instruction_tokens"] + rw["user_tokens"]
    total = prompt + rw["new_tokens"]
    assert E.state_mb(ctx) * 1e6 == 4 * (3 * 2 * 64 + 2 * total * 32)
    assert Z.cache_staged_mb_per_token(ctx) == 0.0
    counters = R._counters(ctx)
    assert counters["tokens_reused"] == rewriter._prefix_len == 40
    assert counters["tokens_prefilled"] == prompt
    assert counters["tokens_decoded"] == rw["new_tokens"]
    assert counters["cache_rows_fetched"] == 0
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


def test_the_reference_shares_nothing_with_the_programs_ops():
    """Plain float32 `jax.numpy`: the reference's source names no module of
    `distrifuser_tpu`, no tail, no cache and no kernel route."""
    with open(os.path.join(BENCH, "reference", "lfm2_sdxl.py")) as f:
        source = f.read()
    body = source.split('"""', 2)[2]  # past the module's docstring
    code = "\n".join(line.split("#")[0] for line in body.splitlines())
    assert "import distrifuser_tpu" not in code
    assert "from distrifuser_tpu" not in code
    for word in ("pallas", "ragged_dot", "bfloat16", "dynamic_update_slice",
                 "causal_conv1d"):
        assert word not in code, word
