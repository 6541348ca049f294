"""The block-diffusion rewrite cell's own pieces: the arithmetic of the cut,
its place in the manifest, the two controls its limits must catch, and the
readers of its programs' counters, record and scopes (the manifest,
reference and rehearsal tests take the cell in as one more case of their
parametrised tests)."""

import argparse
import json
import os
import re
import types

import numpy as np
import pytest
from _util import BENCH, manifest

import run as bench_run

CELL = "sdar-sdxl-1024-rewrite"
CONFIG = "sdar-30b-a3b-sdxl-rewrite"
LIMITS = ["lm_logit_rel_rmse_median", "lm_logit_rel_rmse_late",
          "lm_logit_rel_rmse_worst", "lm_router_slack_worst",
          "lm_unmask_slack_worst", "image_rel_rmse"]
# in the manifest's order
NEW_METRICS = [
    "sdar_prefill_ms", "sdar_decode_ms_per_token", "sdar_pass_ms",
    "sdar_passes_per_token", "sdar_attn_ms_per_token",
    "sdar_moe_experts_ms_per_token", "sdar_unmask_ms_per_token",
    "sdar_moe_local_per_pass", "sdar_experts_fetched_per_pass",
    "sdar_kv_cache_mb", "sdar_decode_roofline"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# what each control reads outside its limit in the CPU rehearsal, and whether
# nothing else may: the precision control fails by ONE limit, not by each;
# the missing pass by every reading that looks at what later blocks see
CONTROLS = {
    "cache_float8": ({"cache_dtype": "float8_e4m3fn"},
                     {"lm_logit_rel_rmse_median"}, True),
    "no_commit_pass": ({"commit_pass": False},
                       {"lm_logit_rel_rmse_median", "lm_router_slack_worst",
                        "lm_unmask_slack_worst"}, False),
}


def published():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_the_cut_is_the_one_stated():
    from benchmark.families import sdar_sdxl as fam
    from distrifuser_tpu.models.sdar import param_shapes

    config = published()
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936,
        "parameters": 30_532_122_624}
    for key in config["reduced"]:
        assert config[key] == config["held"][key]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (24, 16, 18992)
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["expert_parallel"] == {"chips": 8, "index": 0}
    assert "8 chips share each layer" in config["deployment"]
    assert "2 pipeline stages of 24 layers" in config["deployment"]
    # every width as published
    for key, value in {
            "hidden_size": 2048, "intermediate_size": 6144,
            "moe_intermediate_size": 768, "head_dim": 128,
            "num_attention_heads": 32, "num_key_value_heads": 4,
            "num_experts_per_tok": 8, "norm_topk_prob": True,
            "decoder_sparse_step": 1, "mlp_only_layers": [],
            "rope_scaling": None, "rope_theta": 1000000,
            "rms_norm_eps": 1e-6, "model_type": "sdar_moe",
            "use_sliding_window": False, "sliding_window": None,
            "attention_bias": False, "hidden_act": "silu",
            "max_position_embeddings": 32768, "max_window_layers": 48,
            "tie_word_embeddings": False}.items():
        assert config[key] == value, key
    assert (config["block_length"], config["denoising_steps"],
            config["commit_pass"], config["cache_dtype"]) == (4, 4, True,
                                                              None)
    for point in ("block_length", "denoising_steps", "remasking", "mask_id",
                  "commit_pass", "qk_norm", "fused_kernels", "rotary_pairing",
                  "router_dtype", "control"):
        assert len(config["assumed"][point]) > 40, point
    # ... and the counts from the program's own shapes

    def count(cfg):
        return fam._leaf_count(param_shapes(fam.Family(cfg).lm_config))

    held = fam.Family(config)
    cfg = held.lm_config
    assert (cfg.num_experts, cfg.n_local_experts, cfg.first_local_expert,
            cfg.mask_id) == (128, 16, 0, 18991)
    assert count(config) == config["held"]["parameters"] == 2_349_113_344
    assert config["held"]["gigabytes_bf16"] == 4.7
    whole = dict(config, num_hidden_layers=48, num_experts=128,
                 vocab_size=151936, expert_parallel={"chips": 1, "index": 0})
    assert count(whole) == config["published"]["parameters"]
    # a pass: the weights outside the experts once, 3.6 distinct held experts
    # a layer, ~8450 cache rows of 2 KB a layer, the head in 4 passes of 5
    step = held.decode_step_bytes()
    assert step["weights"] == 2 * 24 * 19_140_864
    distinct = 16 * (1 - (15 / 16) ** 4)
    assert step["routed_experts"] == pytest.approx(
        24 * distinct * 4_718_592 * 2)
    assert step["kv_cache"] == pytest.approx(
        24 * 2048 * (8192 + 254 + 4 + 4 / 5))
    assert step["head_and_embedding"] == pytest.approx(
        0.8 * 2 * (2048 * 18992 + 2048) + 4 * 2048 * 2)
    assert sum(v for k, v in step.items() if k != "total") == step["total"]
    assert 2.1e9 <= step["total"] <= 2.3e9, step
    assert held.decode_step_bytes(2.0)["routed_experts"] == \
        24 * 2.0 * 4_718_592 * 2
    assert held.step_cost(1024, 1024)["flops"] < 7e12  # one UNet row
    # the rewrite: the sibling cells' block - 8064 ids snapshotted, 128 a
    # request - with this file's own instruction; 128 blocks of 4
    rw = config["rewrite"]
    kanana = bench_run.load_json(
        "configs", "kanana-2-30b-sdxl-rewrite.json")["rewrite"]
    assert dict(rw, instruction_seed=0) == dict(kanana, instruction_seed=0)
    prompt = rw["instruction_tokens"] + rw["user_tokens"]
    assert (prompt, prompt % 128, rw["new_tokens"] % 4) == (8192, 0, 0)
    assert min(rw["instruction_tokens"], prompt - 1) // 128 * 128 == 8064


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_catalog_key_is_in_the_file_at_its_value_but_the_reduced():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
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


def test_the_cell_and_its_metrics_are_in_the_manifest():
    """Presence, and order among themselves: a later PR appends after them,
    or adds a metric that lists this cell."""
    m = manifest()
    cells = {c["name"]: c for c in m["workloads"]}
    assert cells[CELL]["traffic"] == "solo-1024-rewrite"
    assert cells[CELL]["chips"] == 1 and cells[CELL]["config"] == CONFIG
    configs = {c["name"]: c for c in m["configs"]}
    assert configs[CONFIG]["file"] == f"benchmark/configs/{CONFIG}.json"
    assert configs[CONFIG]["reduced"] == published()["reduced"]
    assert configs[CONFIG]["source"] == published()["source"]
    listing = [p for p in m["per_layer"] if CELL in p.get("workloads", [])]
    names = [p["name"] for p in listing]
    assert [n for n in names if n in NEW_METRICS] == NEW_METRICS
    for p in listing:
        if p["name"] in NEW_METRICS:
            assert p["workloads"] == [CELL] and p["moves"] == "image_s"
    # the metrics every cell reports are reported here too
    assert not [p["name"] for p in m["per_layer"]
                if "workloads" in p and not p["workloads"]]


@pytest.mark.parametrize("control", CONTROLS)
def test_a_control_is_not_correct_and_every_metric_reads(capsys, control):
    """The cell as committed but for one key - the KV cache a precision
    below the stated one, or the commit pass left out - at a size a test
    holds: the traced run goes through, every new per-layer metric reads a
    number, the limits named fail and the others hold, `correct` is
    false."""
    change, failing, nothing_else = CONTROLS[control]
    spec = bench_run.resolve_cell(CELL, rehearse=True)
    spec["config"] = bench_run.merged(spec["config"], change)
    args = argparse.Namespace(workload=CELL, seed=12, seconds=1.0, trace=1,
                              rehearse=True)
    capsys.readouterr()
    assert bench_run.run(args, spec) == 0
    out = capsys.readouterr().out
    last = json.loads(out.splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0
    line = next(ln for ln in out.splitlines() if ln.startswith("lm logits"))
    verdicts = dict(re.findall(r"(lm_\w+) value=\S+ limit=\S+ (\w+)", line))
    assert set(verdicts) == set(LIMITS) - {"image_rel_rmse"}
    failed = {k for k, v in verdicts.items() if v == "FAILED"}
    assert failed >= failing and (failed == failing or not nothing_else)
    failed = re.search(r"checks: \d+ made, failed: (.*)", out).group(1)
    assert re.fullmatch(r"\['image_rel_rmse\[request \d+\]'\]", failed), failed
    assert set(NEW_METRICS) <= set(last["metrics"])
    values = {k: last["metrics"][k]["value"] for k in NEW_METRICS}
    assert all(v > 0 for v in values.values()), values
    lm = spec["config"]
    rw = lm["rewrite"]
    rows = rw["instruction_tokens"] + rw["user_tokens"] + rw["new_tokens"]
    itemsize = 1 if control == "cache_float8" else 4
    assert values["sdar_kv_cache_mb"] * 1e6 == lm["num_hidden_layers"] * (
        2 * lm["num_key_value_heads"] * rows * lm["head_dim"] * itemsize)
    assert values["sdar_passes_per_token"] == (
        1.0 if control == "no_commit_pass" else 1.25)
    assert values["sdar_pass_ms"] * values["sdar_passes_per_token"] == \
        pytest.approx(values["sdar_decode_ms_per_token"])
    # 4 rows x 8 of a share-symmetric router 32 wide, 4 held: exactly one
    # assignment a row, and the calls fetch an expert an assignment
    assert values["sdar_moe_local_per_pass"] == 4.0
    assert values["sdar_experts_fetched_per_pass"] == 4.0


def test_readers_find_nothing_without_this_rewriter():
    from benchmark.harness import lm_readers as L
    from benchmark.harness import sdar_readers as R

    readers = (R.passes_per_token, R.pass_ms, R.kv_cache_mb,
               R.experts_fetched_per_pass, R.moe_local_per_pass,
               R.decode_roofline)
    for rewriter in (None, types.SimpleNamespace(  # a model of another kind
            lm=types.SimpleNamespace(counters=(
                "tokens_prefilled", "tokens_reused", "tokens_decoded",
                "expert_assignments", "expert_assignments_held",
                "state_bytes")), served=[object()])):
        bench = types.SimpleNamespace(
            family=types.SimpleNamespace(rewriter=rewriter),
            traced=[{"ok": True}])
        ctx = {"bench": bench, "trace": {"devices": {}}}
        assert [reader(ctx) for reader in readers] == [None] * len(readers)
    # ... and nothing of a family with no rewriter at all, as the parent of
    # this PR is for every reader the new metrics name
    ctx = {"bench": types.SimpleNamespace(family=object(), traced=[]),
           "trace": None}
    assert [reader(ctx) for reader in readers] == [None] * len(readers)
    assert L.module_ms(ctx, "decode", per_token=True) is None
    assert L.scope_ms_per_token(ctx, "lm.sdar.unmask") is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_names_its_reader_and_what_it_reads(name):
    spec = bench_run.load_json("layer_metrics", name + ".json")
    entry = next(p for p in manifest()["per_layer"] if p["name"] == name)
    for key in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert spec[key] == entry[key], key
    assert spec["workloads"] == [CELL] and spec["moves"] == "image_s"
    module, func = spec["reader"].split(":")
    assert module in ("harness.lm_readers", "harness.sdar_readers")
    import importlib

    assert callable(getattr(importlib.import_module(f"benchmark.{module}"),
                            func))
    assert len(spec["what"]) > 60
    if func == "scope_ms_per_token":
        assert spec["params"]["scope"] in ("lm.attn", "lm.moe.experts",
                                           "lm.sdar.unmask")
    if name.endswith("_roofline"):
        assert spec["unit"] == "%" and spec["better"] == "higher"


def test_the_seeded_router_is_share_symmetric():
    """Every row of every pass loads every share alike: the rehearsal's
    routers are one [d, 4] block eight times over, and a row's 8 experts
    are one a share."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from benchmark.families import _common as F
    from benchmark.families import sdar_sdxl as fam
    from distrifuser_tpu.ops import moe

    config = bench_run.merged(published(), published()["rehearse"])
    family = fam.Family(config)
    cfg = family.lm_config
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("x",))
    params = fam.init_lm_on_device(cfg, F.seed_key(3, fam.LM_STREAM),
                                   jnp.float32, mesh)
    kernel = np.asarray(params["layers"][1]["ffn"]["router"]["kernel"])
    assert kernel.shape == (64, 32)
    blocks = kernel.reshape(64, 8, 4)
    assert (blocks == blocks[:, :1]).all() and np.ptp(kernel[:, :4]) > 0
    u = jax.random.normal(jax.random.PRNGKey(1), (50, 64))
    idx, weights = moe.route(u, jnp.asarray(kernel), top_k=8,
                             scoring="softmax")
    idx = np.sort(np.asarray(idx), axis=1)
    assert (idx // 4 == np.arange(8)).all()  # one expert a share ...
    assert (np.ptp(idx % 4, axis=1) == 0).all()  # ... of one direction
    assert np.allclose(np.asarray(weights), 0.125)


def test_scopes_counters_and_the_record_are_read_from_the_rewriters_programs():
    """The decode program of a small rewriter, compiled: its text holds ops
    under each of the language model's named scopes; the counters say the
    snapshot engaged and count the passes; the record's readers count what
    the passes' rows chose."""
    import jax

    from benchmark.families import sdar_sdxl as fam
    from benchmark.harness import lm_readers as L
    from benchmark.harness import sdar_readers as R
    from benchmark.reference import sdar_sdxl as ref
    from distrifuser_tpu.models import sdar as lm
    from distrifuser_tpu.pipelines import (
        PromptRewriter,
        RewriteSpec,
        SimpleTokenizer,
    )

    config = bench_run.merged(published(), published()["rehearse"])
    family = fam.Family(config)
    cfg = family.lm_config
    assert (cfg.num_experts, cfg.n_local_experts) == (32, 4)
    rewriter = PromptRewriter(
        cfg, lm.init_sdar_params(jax.random.PRNGKey(0), cfg),
        RewriteSpec(**config["rewrite"]), [SimpleTokenizer(1000)])
    out = rewriter(["a red fox"])
    assert out[0].shape == (1, 77)
    scopes = set(L.scope_of_instruction(rewriter.decode_program_text())
                 .values())
    for name in ("lm.attn.proj", "lm.attn", "lm.moe.router",
                 "lm.moe.experts", "lm.head", "lm.sdar.unmask"):
        assert any(f"/{name}/" in s for s in scopes), name
    family.rewriter = rewriter
    ctx = {"bench": types.SimpleNamespace(family=family, peaks=None)}
    rw = config["rewrite"]
    prompt = rw["instruction_tokens"] + rw["user_tokens"]
    total = prompt + rw["new_tokens"]
    blocks = rw["new_tokens"] // 4
    assert R.kv_cache_mb(ctx) * 1e6 == 3 * 2 * 2 * total * 16 * 4
    assert R.passes_per_token(ctx) == 1.25
    c = R._counters(ctx)
    assert c["tokens_reused"] == rewriter._prefix_len == 40
    assert (c["tokens_prefilled"], c["tokens_decoded"]) == (
        prompt, rw["new_tokens"])
    assert (c["denoise_passes"], c["commit_passes"]) == (4 * blocks, blocks)
    assert c["expert_assignments"] == (prompt + 5 * blocks * 4) * 3 * 8
    served = rewriter.served[-1]
    record = {k: np.asarray(v) for k, v in served.experts[1].items()}
    assert record["denoise_experts"].shape == (blocks, 4, 4, 3, 8)
    assert record["experts"].shape == (3, total, 8)
    assert record["fixed_in_pass"].shape == (rw["new_tokens"],)
    held, distinct = R._held_of_passes(ctx)
    assert held.shape == (5 * blocks * 3, 4, 8)
    assert R.moe_local_per_pass(ctx) == held.sum() / (5 * blocks * 3)
    # the calls fetch an expert a held assignment, not a distinct expert
    assert c["experts_fetched"] == held.sum() >= distinct.sum()
    assert R.experts_fetched_per_pass(ctx) == R.moe_local_per_pass(ctx)
    assert (distinct <= held.sum(axis=(1, 2))).all()
    in_prefill = (record["experts"][:, :prompt] < 4).sum()
    assert c["expert_assignments_held"] == in_prefill + held.sum()
    assert np.array_equal(served.prompt_ids,
                          ref.prompt_ids(config, "a red fox"))
    # without a trace the timed readers read nothing
    assert R.pass_ms(ctx) is None and R.decode_roofline(ctx) is None


@pytest.mark.parametrize("name", LIMITS)
def test_every_limit_is_written_with_its_reason(name):
    limits = bench_run.load_json("limits", CELL + ".json")
    for section in (limits, limits["rehearse"]):
        assert section[name]["limit"] > 0 and len(section[name]["why"]) > 20
    assert set(limits) == set(LIMITS) | {"rehearse"}
    assert "readings" in limits[name] and len(limits[name]["what"]) > 40


def test_the_reference_shares_nothing_with_the_programs_ops():
    """Plain float32 `jax.numpy`: the reference's source names no module of
    `distrifuser_tpu`, no cache and no kernel route."""
    with open(os.path.join(BENCH, "reference", "sdar_sdxl.py")) as f:
        source = f.read()
    body = source.split('"""', 2)[2]  # past the module's docstring
    code = "\n".join(line.split("#")[0] for line in body.splitlines())
    assert "import distrifuser_tpu" not in code
    assert "from distrifuser_tpu" not in code
    for word in ("pallas", "ragged_dot", "bfloat16", "dynamic_update_slice",
                 "lax.map"):
        assert word not in code, word
