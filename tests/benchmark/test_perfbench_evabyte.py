"""The byte-level rewrite cell's own pieces: the arithmetic of the cut, the
traffic it reuses, the control its logit limits must catch, and the readers
of its decode program (the manifest, reference and rehearsal tests take the
cell in as one more case of their parametrised tests)."""

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

CELL = "evabyte-sdxl-1024-rewrite"
CONFIG = "evabyte-sdxl-rewrite"
LIMITS = ["lm_logit_rel_rmse_median", "lm_logit_rel_rmse_late",
          "lm_logit_rel_rmse_worst", "image_rel_rmse"]


def published():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_the_cut_is_the_one_stated():
    from benchmark.families import evabyte_sdxl as fam

    config = published()
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 32,
                                   "parameters": 6_488_330_240}
    assert config["held"]["num_hidden_layers"] == config[
        "num_hidden_layers"] == 16
    assert "2 chips as pipeline stages" in config["deployment"]
    # every width as published
    for key, value in {
            "hidden_size": 4096, "num_attention_heads": 32,
            "num_key_value_heads": 32, "intermediate_size": 11008,
            "window_size": 2048, "chunk_size": 16, "vocab_size": 320,
            "num_pred_heads": 8, "rope_theta": 100000,
            "rms_norm_eps": 1e-5, "attention_class": "eva"}.items():
        assert config[key] == value, key
    # ... and the counts from the program's own shapes
    from distrifuser_tpu.models.evabyte import param_shapes

    def count(cfg):
        return fam._leaf_count(param_shapes(fam.Family(cfg).lm_config))

    held = fam.Family(config)
    assert count(config) == config["held"]["parameters"] == 3_250_065_408
    whole = count(dict(config, num_hidden_layers=32))
    assert whole == config["published"]["parameters"] == 6_488_330_240
    assert whole - count(config) == 16 * 202_391_552
    # a decode step: 16 layers' weights, ~20 MB a layer of ring and table
    step = held.decode_step_bytes()
    assert step["weights"] == 16 * 202_391_552 * 2
    assert 19e6 <= (step["ring"] + step["summary_table"]) / 16 <= 21e6
    assert round(step["head_and_embedding"] / 1e6) == 21
    assert 6.80e9 <= step["total"] <= 6.84e9, step
    assert sum(v for k, v in step.items() if k != "total") == step["total"]
    assert held.step_cost(1024, 1024)["flops"] < 7e12  # one UNet row
    # the rewrite ends 1792 positions into window 1 and rolls once
    rw = config["rewrite"]
    prompt = rw["instruction_tokens"] + rw["user_tokens"]
    assert (prompt, prompt % 16, prompt - 2048) == (3840, 0, 1792)
    assert prompt + 256 == 2 * 2048 and rw["new_tokens"] == 512
    assert rw["prompt_tokens"] == 4 * 75


def test_the_traffic_file_is_the_rewrite_cells_unchanged():
    with open(os.path.join(BENCH, "traffic", "solo-1024-rewrite.json"),
              "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()
    assert digest == ("8afea56392986303422c2191c508191f"
                      "1cf6a7664eed7a8a3ebb13ff8bc98a61"), digest
    cells = {c["name"]: c for c in manifest()["workloads"]}
    assert cells[CELL]["traffic"] == cells["nemotron-sdxl-1024-rewrite"][
        "traffic"] == "solo-1024-rewrite"
    assert cells[CELL]["chips"] == 1 and cells[CELL]["config"] == CONFIG


def test_the_group_rule_is_the_same_number_on_the_host_and_the_device():
    import jax.numpy as jnp

    from benchmark.reference import evabyte_sdxl as ref
    from distrifuser_tpu import pipelines as P

    assert (ref.GROUP_BYTES, ref.GROUP_BASE) == (P.GROUP_BYTES, P.GROUP_BASE)
    ids = np.random.default_rng(0).integers(0, 320, 300).astype(np.int32)
    ids[:8] = [319] * 4 + [0] * 4
    want = ref.group_ids(ids, 49408)
    assert want[0] == (319 * (331**3 + 331**2 + 331 + 1)) % 49406
    got = P.byte_group_ids(jnp.asarray(ids), 49406)
    assert got.dtype == jnp.int32 and np.array_equal(np.asarray(got), want)


def test_a_residual_stream_in_bfloat16_is_not_correct(capsys):
    """The control of the logit limits at a size a test holds: the cell as
    committed but for `fp32_skip_add` false, the residual stream a precision
    below the float32 the configuration states.  The run goes through, the
    median logit reading fails its limit - one of the cell's limits, not
    each - and `correct` is false."""
    spec = bench_run.resolve_cell(CELL, rehearse=True)
    spec["config"] = bench_run.merged(spec["config"],
                                      {"fp32_skip_add": False})
    args = argparse.Namespace(workload=CELL, seed=12, seconds=1.0, trace=0,
                              rehearse=True)
    capsys.readouterr()
    assert bench_run.run(args, spec) == 0
    out = capsys.readouterr().out
    last = json.loads(out.splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0
    line = next(ln for ln in out.splitlines() if ln.startswith("lm logits"))
    assert "x 2560 columns" in line  # all eight blocks of the head
    assert dict(re.findall(r"(lm_\w+) value=\S+ limit=\S+ (\w+)", line)) == {
        "lm_logit_rel_rmse_median": "FAILED", "lm_logit_rel_rmse_late": "ok",
        "lm_logit_rel_rmse_worst": "ok"}
    failed = re.search(r"checks: \d+ made, failed: (.*)", out).group(1)
    assert re.fullmatch(r"\['image_rel_rmse\[request \d+\]'\]", failed), failed


def test_readers_find_nothing_without_this_rewriter():
    from benchmark.harness import eva_readers as E

    for rewriter in (None, types.SimpleNamespace(  # a model of another kind
            lm=types.SimpleNamespace(counters=("tokens_prefilled",)),
            served=[object()])):
        bench = types.SimpleNamespace(
            family=types.SimpleNamespace(rewriter=rewriter),
            traced=[{"ok": True}])
        ctx = {"bench": bench, "trace": {"devices": {}}}
        assert E.scopes_ms_per_byte(ctx, ["lm.eva.attn"]) is None
        assert E.decode_roofline(ctx) is None
        assert E.state_mb(ctx) is None
    # ... and nothing of a family with no rewriter at all
    ctx = {"bench": types.SimpleNamespace(family=object(), traced=[]),
           "trace": None}
    assert E.decode_roofline(ctx) is None and E.state_mb(ctx) is None


def test_scopes_are_read_from_the_compiled_decode_programs_text():
    """The decode program of a small rewriter, compiled: its text holds ops
    under each of the language model's named scopes, and the counter the
    state reader reads is the state's size."""
    import jax

    from benchmark.harness import eva_readers as E
    from benchmark.harness import lm_readers as R
    from distrifuser_tpu.models import evabyte as lm
    from distrifuser_tpu.pipelines import (
        PromptRewriter,
        RewriteSpec,
        SimpleTokenizer,
    )

    config = bench_run.merged(published(), published()["rehearse"])
    cfg = lm.evabyte_config_from_json(config)
    rewriter = PromptRewriter(
        cfg, lm.init_evabyte_params(jax.random.PRNGKey(0), cfg),
        RewriteSpec(**config["rewrite"]), [SimpleTokenizer(1000)])
    out = rewriter(["a red fox"])
    assert out[0].shape == (1, 77)
    scopes = set(R.scope_of_instruction(rewriter.decode_program_text())
                 .values())
    for name in ("lm.eva.proj", "lm.eva.pool", "lm.eva.attn", "lm.mlp",
                 "lm.head"):
        assert any(f"/{name}/" in s for s in scopes), name
    bench = types.SimpleNamespace(
        family=types.SimpleNamespace(rewriter=rewriter))
    window, chunk = cfg.window_size, cfg.chunk_size
    total = sum(config["rewrite"][k] for k in (
        "instruction_tokens", "user_tokens", "new_tokens"))
    assert E.state_mb({"bench": bench}) * 1e6 == cfg.num_hidden_layers * (
        2 * 4 * cfg.hidden_size * (window + -(-total // chunk)))
    counters = E._counters({"bench": bench})
    assert counters["summaries_written"] == total // chunk
    assert counters["windows_rolled"] == total // window == 3
    # the served ids are what the group rule makes of the last bytes
    served = rewriter.served[-1]
    from benchmark.reference import evabyte_sdxl as ref

    n = config["rewrite"]["prompt_tokens"]
    assert np.array_equal(
        np.asarray(out[0])[0, 1:1 + n // 4],
        ref.group_ids(np.asarray(served.new_ids)[-n:], 1000))
    assert np.array_equal(served.prompt_ids,
                          ref.prompt_ids(config, "a red fox"))


@pytest.mark.parametrize("name", LIMITS)
def test_every_limit_is_written_with_its_reason(name):
    limits = bench_run.load_json("limits", CELL + ".json")
    for section in (limits, limits["rehearse"]):
        assert section[name]["limit"] > 0 and len(section[name]["why"]) > 20
    assert limits["rehearse"][name]["limit"] <= limits[name]["limit"]
    assert set(limits) == set(LIMITS) | {"rehearse"}
