"""The eight per-layer metrics of the collectives layer and of the loop's two
phases (PR 36): each reader on a hand-written trace and HLO text, the
identity of the two phase times with `step_ms`, and the four-chip cell's CPU
rehearsal with all eight in its last line.

The cell `sdxl-1024-patch4` is not in `BENCHMARK.json` yet (PERF.md section
7: its limit lacks the control's reading on four chips).  What its PR will
add - the manifest's entries, the traffic, the limits and the eight metric
files - is kept ready under `cells/sdxl-1024-patch4/`, laid out as
`benchmark/` is, and tried here the way `test_perfbench_rehearsal.py` tries
its own sketch of the cell: copied over a copy of the benchmark."""

import json
import os
import shutil
import types

import pytest
from _util import BENCH, run_in_copy

from benchmark.harness import exchange_readers as X
from benchmark.harness import loop_readers as L
from benchmark.harness import readers as R

CELL = "sdxl-1024-patch4"
STAGED = os.path.join(os.path.dirname(__file__), "cells", CELL)
ENTRIES = json.load(open(os.path.join(STAGED, "manifest.json")))
NEW = ("collective_exposed_share", "sync_step_ms", "stale_step_ms",
       "halo_ms_per_step", "stale_kv_ms_per_step", "gn_stats_ms_per_step",
       "exchange_mb_per_step", "inline_collectives_per_step")

P = "jit(loop)/shard_map/while/body/closed_call"
# A compiled loop of two phases as its text names it: a synchronous body
# whose gather feeds a dot; a displaced body with a permute pair, two
# gathers that reach only the carry, the output gather and the CFG combine;
# an instruction the compiler made itself (no op_name); a transpose hoisted
# out of the displaced loop, which keeps the loop's op_name.
TEXT = f'''
HloModule jit_loop

%sync_body (c: (s32[], f32[8])) -> (s32[], f32[8]) {{
  %c = (s32[], f32[8]{{0}}) parameter(0)
  %i.1 = s32[] get-tuple-element(%c), index=0
  %fusion.1 = f32[8]{{0}} fusion(%c), kind=kLoop, calls=%f1, metadata={{op_name="{P}/phase_sync/down_0/conv/conv_general_dilated"}}
  %all-gather.1 = f32[2,8]{{1,0}} all-gather(%fusion.1), channel_id=1, dimensions={{0}}, metadata={{op_name="{P}/phase_sync/down_0/stale_kv/all_gather"}}
  %dot.1 = f32[8]{{0}} dot(%all-gather.1, %fusion.1), metadata={{op_name="{P}/phase_sync/down_0/attn/dot_general"}}
  %copy.1 = f32[8]{{0}} copy(%dot.1)
  ROOT %tuple.1 = (s32[], f32[8]{{0}}) tuple(%i.1, %copy.1)
}}

%stale_body (c: (s32[], f32[8], f32[4], f32[2,8], f32[2,2])) -> (s32[], f32[8], f32[4], f32[2,8], f32[2,2]) {{
  %c2 = (s32[], f32[8]{{0}}, f32[4]{{0}}, f32[2,8]{{1,0}}, f32[2,2]{{1,0}}) parameter(0)
  %i.2 = s32[] get-tuple-element(%c2), index=0
  %fusion.2 = f32[8]{{0}} fusion(%c2), kind=kLoop, calls=%f2, metadata={{op_name="{P}/phase_stale/down_0/conv/conv_general_dilated"}}
  %rows.2 = f32[4]{{0}} slice(%fusion.2), slice={{[0:4]}}
  %collective-permute-start.1 = (f32[4]{{0}}, f32[4]{{0}}, u32[], u32[]) collective-permute-start(%rows.2), channel_id=2, source_target_pairs={{{{0,1}}}}, metadata={{op_name="{P}/phase_stale/down_0/halo/halo/ppermute"}}
  %all-gather.2 = f32[2,8]{{1,0}} all-gather(%fusion.2), channel_id=3, dimensions={{0}}, metadata={{op_name="{P}/phase_stale/down_0/stale_kv/all_gather"}}
  %m.2 = f32[2]{{0}} slice(%fusion.2), slice={{[0:2]}}
  %all-gather.3 = f32[2,2]{{1,0}} all-gather(%m.2), channel_id=4, dimensions={{0}}, metadata={{op_name="{P}/phase_stale/down_0/groupnorm/gn_stats/all_gather"}}
  %collective-permute-done.1 = f32[4]{{0}} collective-permute-done(%collective-permute-start.1), metadata={{op_name="{P}/phase_stale/down_0/halo/halo/ppermute"}}
  %all-gather.4 = f32[2,8]{{1,0}} all-gather(%fusion.2), channel_id=5, dimensions={{0}}, metadata={{op_name="{P}/phase_stale/out_gather/all_gather"}}
  %all-gather.5 = f32[2,2,8]{{2,1,0}} all-gather(%all-gather.4), channel_id=6, dimensions={{0}}, metadata={{op_name="{P}/phase_stale/cfg_combine/all_gather"}}
  %add.1 = f32[8]{{0}} fusion(%all-gather.5), kind=kLoop, calls=%f3, metadata={{op_name="{P}/phase_stale/cfg_combine/add"}}
  ROOT %tuple.2 = (s32[], f32[8]{{0}}, f32[4]{{0}}, f32[2,8]{{1,0}}, f32[2,2]{{1,0}}) tuple(%i.2, %add.1, %collective-permute-done.1, %all-gather.2, %all-gather.3)
}}

%f3 (p: f32[2,2,8]) -> f32[8] {{
  %p = f32[2,2,8]{{2,1,0}} parameter(0)
  ROOT %reduce.3 = f32[8]{{0}} reduce(%p, %zero), dimensions={{0,1}}, to_apply=%sum
}}

ENTRY %main (x: f32[8]) -> f32[8] {{
  %x = f32[8]{{0}} parameter(0)
  %transpose.9 = f32[8,8]{{0,1}} transpose(%w), dimensions={{1,0}}, metadata={{op_name="{P}/phase_stale/down_0/linear/transpose"}}
  %while.1 = (s32[], f32[8]{{0}}) while(%init), condition=%cond.1, body=%sync_body
  %while.2 = (s32[], f32[8]{{0}}, f32[4]{{0}}, f32[2,8]{{1,0}}, f32[2,2]{{1,0}}) while(%seed), condition=%cond.2, body=%stale_body
  ROOT %out = f32[8]{{0}} get-tuple-element(%while.2), index=1
}}
'''
STALE_BYTES = {"halo": 16, "stale_kv": 64, "gn_stats": 16, "out_gather": 64,
               "cfg_combine": 128}

# ns within a step, as a TPU trace names the ops (instruction + result shape)
SYNC_STEP = [("fusion.1 f32[8]", 0, 50), ("all-gather.1 f32[2,8]", 50, 20),
             ("dot.1 f32[8]", 70, 20), ("copy.1 f32[8]", 90, 5)]


def stale_step(wait):
    return [("fusion.2 f32[8]", 0, 100),
            ("collective-permute-start.1 (tuple)", 100, 4),
            ("all-gather.2 f32[2,8]", 104, 30),
            ("all-gather.3 f32[2,2]", 134, 2),
            ("collective-permute-done.1 f32[4]", 136, wait),
            ("all-gather.4 f32[2,8]", 150, 10),
            ("all-gather.5 f32[2,2,8]", 160, 20),
            ("add.1 f32[8]", 180, 8)]


def device(t0, wait):
    """One execution of jit_loop from t0: the hoisted transpose, two
    synchronous steps of 100 ns, two displaced steps of 240 ns: 634 ns."""
    from benchmark.harness import trace_reduce as T

    ops = [("transpose.9 f32[8,8]", t0, 3), ("while.1", t0 + 5, 195),
           ("while.2", t0 + 205, 430)]
    for k in (0, 1):
        ops += [(n, t0 + 5 + 100 * k + s, d) for n, s, d in SYNC_STEP]
        ops += [(n, t0 + 205 + 240 * k + s, d) for n, s, d in stale_step(wait)]
    return {"ops": T.leaf_ops(ops),
            "modules": [("jit_loop(7)", t0, 634), ("jit_decode(3)", t0 + 700, 50)]}


def hand_ctx(text=TEXT):
    fam = types.SimpleNamespace(DENOISE_MODULES=("loop",))
    bench = types.SimpleNamespace(family_module=fam, steps=4, chips=2,
                                  peaks=None)
    trace = {"devices": {0: device(1000, wait=6), 1: device(1010, wait=12)},
             "host": []}
    plan = {"steps": {"sync": 2, "stale": 2, "shallow": 0},
            "bytes_per_step": {"sync": 96, "stale": 96}}
    return {"trace": trace, "bench": bench,
            "compiled_loop": {"text": text, "plan": plan},
            "loop_scopes": L.instruction_scopes(text)}


# sync: first op 5, last ends 5 + 100 + 95 = 200 -> 195 ns over 2 steps;
# stale: first op 205, last ends 205 + 240 + 188 = 633 -> 428 ns over 2
EXPECTED = {
    "sync_step_ms": 195 / 2 / 1e6,
    "stale_step_ms": 428 / 2 / 1e6,
    "halo_ms_per_step": (4 + 12) / 1e6,  # the chip that waits longer
    "stale_kv_ms_per_step": 30 / 1e6,
    "gn_stats_ms_per_step": 2 / 1e6,
    "exchange_mb_per_step": sum(STALE_BYTES.values()) / 1e6,
    "inline_collectives_per_step": 2.0,
    # per chip: 2 x 20 (sync) + 2 x (4 + 30 + 2 + wait + 10 + 20) of
    # collectives with nothing beside them, over the window 1000 .. 1643
    "collective_exposed_share": 100.0 * (40 + 2 * (66 + 12)) / 643,
}


def read(name, ctx):
    spec = json.load(open(os.path.join(STAGED, "layer_metrics", name + ".json")))
    module, func = spec["reader"].split(":")
    reader = getattr({"harness.exchange_readers": X, "harness.readers": R}[
        module], func)
    return reader(ctx, **spec.get("params", {}))


@pytest.mark.parametrize("name", NEW)
def test_each_metric_on_a_hand_written_trace_and_text(name, capsys):
    assert read(name, hand_ctx()) == pytest.approx(EXPECTED[name], rel=1e-9)
    out = capsys.readouterr().out
    if name == "halo_ms_per_step":  # issue and wait printed apart
        assert "issue (-start) 0.00000" in out and "wait (-done) 0.00001" in out
    if name == "exchange_mb_per_step":  # the model's figure and the ratio
        assert "['stale'] = 96 B" in out and "compiled / model = 3.0" in out


def test_the_two_phases_add_up_to_the_step(capsys):
    """sync x its steps + stale x its steps comes to steps x `step_ms`
    within 2%: the phases' spans hold the bubbles between their ops, and what
    the program does outside its two loops is all that is missing."""
    ctx = hand_ctx()
    phases = 2 * read("sync_step_ms", ctx) + 2 * read("stale_step_ms", ctx)
    whole = 4 * R.step_ms(ctx)
    assert whole == pytest.approx(634 / 1e6)
    assert phases == pytest.approx(whole, rel=0.02) and phases < whole


def test_what_the_compiler_hoisted_out_of_a_loop_is_in_no_phase():
    phases = X.phase_of_instruction(TEXT)
    assert "transpose.9" not in phases and "while.2" not in phases
    assert phases["copy.1"] == "phase_sync"  # the compiler's own, by its body
    assert phases["rows.2"] == phases["fusion.2"] == "phase_stale"


@pytest.mark.parametrize("name", NEW[1:])
def test_a_program_without_the_scopes_gives_nothing_to_read(name):
    """The parent of PR 36: the same loop, no phase in any op_name."""
    text = TEXT.replace("/phase_sync", "").replace("/phase_stale", "")
    assert read(name, hand_ctx(text)) is None


def test_no_trace_no_device_metric():
    ctx = dict(hand_ctx(), trace=None)
    for name in NEW[:6]:
        assert read(name, ctx) is None
    assert read("inline_collectives_per_step", ctx) == 2.0


def add_the_cell(m, b):
    """The staged files beside the benchmark's, the staged entries after the
    manifest's: nothing that is there is touched, and what is there already
    (once the cell's own PR has landed) is left as it is."""
    for folder in ("traffic", "limits", "layer_metrics"):
        for name in os.listdir(os.path.join(STAGED, folder)):
            if not (b / folder / name).exists():
                shutil.copy(os.path.join(STAGED, folder, name), b / folder)
    for kind, entries in ENTRIES.items():
        have = {e["name"] for e in m[kind]}
        m[kind] += [e for e in entries if e["name"] not in have]


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """The cell's traced CPU rehearsal on four virtual devices, once."""
    proc, last = run_in_copy(
        tmp_path_factory.mktemp("patch4"), add_the_cell,
        ["--workload", CELL, "--seed", "9", "--seconds", "1", "--trace", "1",
         "--rehearse"], devices=4)
    assert proc.returncode == 0 and last, (proc.stdout + proc.stderr)[-3000:]
    return last, proc.stdout


def test_the_cells_rehearsal_reads_all_eight(rehearsed):
    last, out = rehearsed
    assert last["correct"] is True
    assert last["device"]["count"] == 4
    assert "mesh {'dp': 1, 'cfg': 2, 'sp': 2}" in out
    metrics = last["metrics"]
    assert set(NEW) <= set(metrics), sorted(metrics)
    listed = {m["name"]: m for m in ENTRIES["per_layer"]}
    for name in NEW:
        assert metrics[name]["unit"] == listed[name]["unit"]
        assert metrics[name]["value"] > 0
    assert metrics["inline_collectives_per_step"]["value"] == 2.0
    # the compiled loop's bytes are the program's model plus the two inline
    # gathers (65,536 + 131,072 B at the rehearsal's 512 x 512)
    assert "['stale'] = 2493056 B; compiled / model = 1.0788" in out
    assert metrics["exchange_mb_per_step"]["value"] == pytest.approx(2.689664)
    for kind in ("halo", "stale_kv", "gn_stats"):
        assert metrics[f"{kind}_ms_per_step"]["value"] < \
            metrics["stale_step_ms"]["value"]


def test_the_rehearsals_phases_add_up_to_its_step(rehearsed):
    """The rehearsal runs steps 0-1 synchronous and 2-3 displaced; a CPU's
    thread pool is no chip, so the identity is held loosely here and to 2%
    on the hand-written trace."""
    metrics = rehearsed[0]["metrics"]
    phases = 2 * metrics["sync_step_ms"]["value"] \
        + 2 * metrics["stale_step_ms"]["value"]
    assert phases == pytest.approx(4 * metrics["step_ms"]["value"], rel=0.15)


def test_the_staged_cell_is_the_issues():
    """What ISSUE 36 specified, held on the staged files; where an entry
    stands in the manifest once it is added is the manifest's own tests'."""
    (cell,) = ENTRIES["workloads"]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "sdxl-base-1.0", "solo-1024-patch4", 4)
    assert len(cell["why"]) <= 200
    solo = json.load(open(os.path.join(BENCH, "traffic", "solo-1024.json")))
    mine = json.load(open(os.path.join(STAGED, "traffic",
                                       "solo-1024-patch4.json")))
    assert mine["distri"] == {"mode": "corrected_async_gn", "warmup_steps": 4,
                              "parallelism": "patch", "vae_sp": True}
    for key in ("arrivals", "request", "tail", "serve", "trace"):
        assert mine[key] == solo[key], key
    assert [e["name"] for e in ENTRIES["per_layer"]] == list(NEW)
    for entry in ENTRIES["per_layer"]:
        spec = json.load(open(os.path.join(
            STAGED, "layer_metrics", entry["name"] + ".json")))
        assert {k: spec[k] for k in entry} == entry
        assert entry["workloads"] == [CELL] and entry["moves"] == "image_s"
    limits = json.load(open(os.path.join(STAGED, "limits", CELL + ".json")))
    assert limits["rehearse"]["image_rel_rmse"]["limit"] <= 0.05
