"""The readers of what a block-diffusion decode loop does with its KV caches
(benchmark/harness/sdar_cache_readers.py) on a hand-written program and
hand-written counters, what they do with a program that has nothing to read
(the parent of PR 43, a cell without such a rewrite stage), and the two
metrics' place in the manifest."""

import importlib
import types

import pytest
from _util import BENCH, manifest  # noqa: F401  (repo root on sys.path)

import run as bench_run
from benchmark.harness import sdar_cache_readers as C

CELL = "sdar-sdxl-1024-rewrite"
METRICS = ["sdar_cache_staged_mb_per_block", "sdar_kv_rows_per_sweep"]
CACHE = "bf16[2,48,128]{2,1,0:T(8,128)(2,1)%s}"


def trace(name, staged, extra=0):
    """One trace of a one-layer stack round its two caches [2, 48, 128]:
    the rows written in place, or - ``staged`` - the key cache brought into
    VMEM whole for the write and sent back."""
    hbm, vmem = CACHE % "", CACHE % "S(1)"
    lines = [f"%{name} (carry: (s32[], {hbm}, {hbm})) -> (s32[], {hbm}) {{"]
    if staged:
        lines += [
            f"  %copy-start.1 = ({vmem}, {hbm}, u32[]{{:S(2)}}) "
            "copy-start(%k)",
            f"  %copy-done.1 = {vmem} copy-done(%copy-start.1)",
            f"  %dus.1 = {vmem} dynamic-update-slice(%copy-done.1, %rows, "
            "%zero, %i, %zero)",
            f"  %copy-start.2 = ({hbm}, {vmem}, u32[]{{:S(2)}}) "
            "copy-start(%dus.1)",
            f"  %copy-done.2 = {hbm} copy-done(%copy-start.2)"]
    else:
        lines += [f"  %dus.1 = {hbm} dynamic-update-slice(%k, %rows, %zero, "
                  "%i, %zero)"]
    lines += [f"  %dus.2 = {hbm} dynamic-update-slice(%v, %rows, %zero, %i, "
              "%zero)"]
    lines += [f"  %pad.{n} = s32[] add(%i, %i)" for n in range(extra)]
    return "\n".join(lines + ["}"])


def program(denoise, shared, alone=False):
    """The decode program's shape: an inner loop whose body is a denoise
    pass, an outer one whose body holds a conditional between the shared
    sweep (the longer branch) and the last commit pass."""
    return "\n\n".join([
        "HloModule jit_rewrite_decode",
        trace("denoise_body", denoise), trace("shared_sweep", shared, 8),
        trace("last_commit", alone),
        "%block_body (carry: s32[]) -> s32[] {\n"
        "  %while.1 = (s32[]) while(%init), condition=%c1, "
        "body=%denoise_body\n"
        "  %conditional.1 = (s32[]) conditional(%pred, %a, %b), "
        "branch_computations={%last_commit, %shared_sweep}\n}",
        "ENTRY %main (x: s32[]) -> s32[] {\n"
        "  %while.2 = (s32[]) while(%x), condition=%c2, body=%block_body\n}",
    ]) + "\n"


def ctx(text=None, counters=None, names=None, **config):
    spec = types.SimpleNamespace(instruction_tokens=24, user_tokens=8,
                                 new_tokens=16)
    config = types.SimpleNamespace(**{
        "num_key_value_heads": 2, "head_dim": 128, "num_hidden_layers": 1,
        "denoising_steps": 4, **config})
    served = [types.SimpleNamespace(counters=counters)] if counters else []
    rewriter = types.SimpleNamespace(
        spec=spec, config=config, decode_program_text=lambda: text,
        lm=types.SimpleNamespace(counters=names or ()), served=served)
    family = types.SimpleNamespace(rewriter=rewriter)
    return {"bench": types.SimpleNamespace(family=family)}


ONE_WAY = 2 * 48 * 128 * 2 / 1e6  # a cache of [2, 48, 128] bfloat16


@pytest.mark.parametrize("text, config, expected", [
    pytest.param(program(False, False), {}, 0.0, id="every_row_in_place"),
    pytest.param(program(True, False), {}, 3 * 2 * ONE_WAY,
                 id="the_denoise_pass_stages_a_cache"),
    pytest.param(program(True, True), {}, 4 * 2 * ONE_WAY, id="both_do"),
    pytest.param(program(False, False, True), {}, 0.0,
                 id="the_last_commit_pass_is_not_a_blocks"),
    pytest.param(program(True, True), {"denoising_steps": 2},
                 2 * 2 * ONE_WAY, id="two_denoise_passes_a_block"),
    pytest.param(program(True, True), {"head_dim": 64}, None,
                 id="caches_of_another_shape"),
    pytest.param(program(True, True), {"denoising_steps": None}, None,
                 id="a_loop_that_decodes_a_token_a_trip"),
    pytest.param(None, {}, None, id="no_program_text"),
])
def test_staged_mb_of_a_blocks_sweeps(text, config, expected):
    assert C.cache_staged_mb_per_block(ctx(text, **config)) == expected


NAMES = ("tokens_prefilled", "tokens_reused", "tokens_decoded",
         "denoise_passes", "commit_passes", "expert_assignments",
         "expert_assignments_held", "experts_fetched", "kv_cache_bytes",
         "stack_sweeps")


@pytest.mark.parametrize("names, counters, expected", [
    pytest.param(NAMES + ("kv_rows_fetched",),
                 [32, 24, 16, 16, 4, 0, 0, 0, 0, 17, 17 * 3 * 40], 40.0,
                 id="the_kernels_route"),
    pytest.param(NAMES + ("kv_rows_fetched",),
                 [32, 24, 16, 16, 4, 0, 0, 0, 0, 17, 0], 0.0,
                 id="the_xla_route"),
    pytest.param(NAMES, [32, 24, 16, 16, 4, 0, 0, 0, 0, 17], None,
                 id="the_parents_counters"),
    pytest.param(NAMES + ("kv_rows_fetched",), None, None,
                 id="nothing_served_yet"),
    pytest.param(("tokens_prefilled", "tokens_decoded", "cache_rows_fetched"),
                 [32, 16, 640], None, id="a_model_of_another_kind"),
])
def test_rows_a_sweep_and_layer_from_the_programs_counter(names, counters,
                                                          expected):
    got = C.kv_rows_per_sweep(ctx(counters=counters, names=names,
                                  num_hidden_layers=3))
    assert got == expected


def test_a_cell_without_a_rewrite_stage_has_nothing_to_read():
    for family in (types.SimpleNamespace(), types.SimpleNamespace(
            rewriter=None)):
        bare = {"bench": types.SimpleNamespace(family=family)}
        assert C.cache_staged_mb_per_block(bare) is None
        assert C.kv_rows_per_sweep(bare) is None


@pytest.mark.parametrize("name", METRICS)
def test_a_new_metric_is_appended_and_names_its_reader(name):
    """In the manifest in this order among themselves (a later PR appends
    after them), each listing the one cell whose program has something to
    read."""
    per_layer = manifest()["per_layer"]
    assert [p["name"] for p in per_layer if p["name"] in METRICS] == METRICS
    entry = next(p for p in per_layer if p["name"] == name)
    spec = bench_run.load_json("layer_metrics", name + ".json")
    for key in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert spec[key] == entry[key], key
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["workloads"] == [CELL] and entry["moves"] == "image_s"
    assert entry["source"] == "program_counter" and entry["layer"] == "ops"
    module, func = spec["reader"].split(":")
    assert module == "harness.sdar_cache_readers"
    assert callable(getattr(importlib.import_module(f"benchmark.{module}"),
                            func))
    assert len(spec["what"]) > 60
