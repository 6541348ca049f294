"""`groupnorm_ms_per_step` / `conv_ms_per_step` (PR 30): the reader of the
denoise loop's ops by named scope on a hand-written trace and HLO text, and
in the CPU rehearsal of the cells that list it."""

import json
import os
import types

import jax
import pytest
from _util import BENCH, manifest, rehearse

from benchmark.harness import loop_readers as R

NEW = ("groupnorm_ms_per_step", "conv_ms_per_step")
UNET_CELLS = ["sdxl-1024-solo", "nemotron-sdxl-1024-rewrite"]

# a compiled loop as its text names it: a norm's fusion and the select of
# its rows, a conv with a moment riding out as a second output, a copy the
# compiler made itself, a fused computation's inside
TEXT = '''
HloModule jit_loop

%fused_computation.3 (p: bf16[2,8]) -> bf16[2,8] {
  %multiply.9 = bf16[2,8]{1,0} multiply(%p, %p), metadata={op_name="jit(loop)/while/body/up_2/groupnorm/mul"}
}

ENTRY %main {
  %fusion.3 = bf16[2,8]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(loop)/while/body/up_2/groupnorm/mul" source_file="n.py"}
  %select.5 = bf16[2,8]{1,0} select(%m, %fusion.3, %fusion.3), metadata={op_name="jit(loop)/while/body/up_2/groupnorm/select_n"}
  %convolution_fusion.1 = (bf16[2,8]{1,0}, f32[2]{0}) fusion(%b), kind=kOutput, calls=%c, metadata={op_name="jit(loop)/while/body/up_2/conv/conv_general_dilated"}
  %copy.7 = bf16[2,8]{0,1} copy(%convolution_fusion.1)
  ROOT %dot.2 = bf16[2,8]{1,0} dot(%copy.7, %w), metadata={op_name="jit(loop)/while/body/up_2/linear/dot_general"}
}
'''

# One device, ns.  jit_loop runs twice (100..500, 700..1100) under a `while`
# container, jit_decode once, its own norm outside the loop.
#   fusion.3 [110,150) [710,750)   select.5 [150,160) [750,760)
#   convolution_fusion.1 [200,400) [800,1000)   copy.7 [400,420) [1000,1020)
#   dot.2 [430,480) [1030,1080)    decode: fusion.3 [1200,1300)
def hand_trace(extra=()):
    from benchmark.harness import trace_reduce as T

    ops = [("while.1", 100, 400), ("while.1", 700, 400),
           ("fusion.3 bf16[2,8]", 1200, 100)]
    for t0 in (100, 700):
        ops += [("fusion.3 bf16[2,8]", t0 + 10, 40),
                ("select.5 bf16[2,8]", t0 + 50, 10),
                ("convolution_fusion.1 (tuple)", t0 + 100, 200),
                ("copy.7 bf16[2,8]", t0 + 300, 20),
                ("dot.2 bf16[2,8]", t0 + 330, 50)]
    return {"devices": {0: {
        "ops": T.leaf_ops(ops + list(extra)),
        "modules": [("jit_loop(17)", 100, 400), ("jit_loop(17)", 700, 400),
                    ("jit_decode(3)", 1200, 250)]}}, "host": []}


def hand_ctx(trace, scopes):
    fam = types.SimpleNamespace(DENOISE_MODULES=("loop",))
    bench = types.SimpleNamespace(family_module=fam, steps=2, chips=1,
                                  peaks=None)
    return {"trace": trace, "bench": bench, "loop_scopes": scopes}


def test_the_text_names_every_instruction_and_the_scope_of_most():
    scopes = R.instruction_scopes(TEXT)
    assert scopes["fusion.3"].endswith("/up_2/groupnorm/mul")
    # as a TPU trace names it, with its result shape; as a CPU trace does
    assert scopes["fusion.3 bf16[2,8]"] == scopes["fusion.3"]
    assert scopes["convolution_fusion.1 (tuple)"].endswith("/conv/conv_general_dilated")
    assert scopes["dot.2 bf16[2,8]"] == scopes["dot.2"]  # ROOT
    assert "fusion.3 bf16[4,4]" not in scopes
    assert scopes["select.5"].endswith("/groupnorm/select_n")
    assert scopes["convolution_fusion.1"].endswith("/conv/conv_general_dilated")
    assert scopes["copy.7"] == ""  # the compiler's own: named, no scope
    assert scopes["dot.2"].endswith("/linear/dot_general")
    assert "no_such_instruction" not in scopes


def test_a_scopes_ops_inside_the_loop_by_hand():
    ctx = hand_ctx(hand_trace(), R.instruction_scopes(TEXT))
    # two images of two steps: (40 + 10) ns of norm a loop, 200 of conv; the
    # decode program's fusion.3 carries the same name and lies outside
    assert R.scope_ms_per_step(ctx, "groupnorm") == pytest.approx(50e-6 / 2)
    assert R.scope_ms_per_step(ctx, "conv") == pytest.approx(200e-6 / 2)
    assert R.scope_ms_per_step(ctx, "linear") == pytest.approx(50e-6 / 2)
    assert R.scope_ms_per_step(ctx, "layernorm") is None  # nothing under it
    assert R.scope_ms_per_step(ctx, "norm") is None  # a whole scope, no prefix


def test_a_text_that_is_not_the_traced_loops_reads_nothing(capsys):
    # 320 of each loop's 320 + 40 busy ns are instructions of the text: 89%.
    # Another program's fusion.3 is not this one's: the shape says so
    strangers = [("fusion.3 bf16[4,4]", t0 + 60, 40) for t0 in (100, 700)]
    ctx = hand_ctx(hand_trace(strangers), R.instruction_scopes(TEXT))
    assert R.scope_ms_per_step(ctx, "groupnorm") is None
    assert "not the served program" in capsys.readouterr().out
    # and with all but a twentieth named it reads
    few = [("fusion.999 bf16[4]", t0 + 60, 10) for t0 in (100, 700)]
    ctx = hand_ctx(hand_trace(few), R.instruction_scopes(TEXT))
    assert R.scope_ms_per_step(ctx, "groupnorm") == pytest.approx(50e-6 / 2)


def test_no_trace_no_loop_no_unet_reads_nothing():
    scopes = R.instruction_scopes(TEXT)
    assert R.scope_ms_per_step(hand_ctx(None, scopes), "groupnorm") is None
    other = hand_trace()
    other["devices"][0]["modules"] = [("jit_decode(3)", 1200, 250)]
    assert R.scope_ms_per_step(hand_ctx(other, scopes), "groupnorm") is None
    # a family that serves no UNet (PixArt's): no program text is asked for
    bench = types.SimpleNamespace(
        family_module=types.SimpleNamespace(DENOISE_MODULES=("loop",)),
        family=object(), weights={"dit": {}}, steps=2, chips=1, peaks=None)
    ctx = {"trace": hand_trace(), "bench": bench}
    assert R.scope_ms_per_step(ctx, "groupnorm") is None
    assert ctx["loop_scopes"] is None


def test_a_compiled_programs_own_text_carries_the_scope():
    from distrifuser_tpu.ops import group_norm

    def f(x):
        with jax.named_scope("up_2"):
            return group_norm(None, x, groups=2)

    text = jax.jit(f).lower(jax.numpy.ones((2, 4, 4, 8))).compile().as_text()
    scopes = R.instruction_scopes(text)
    assert any("/up_2/groupnorm/" in s for s in scopes.values())


def test_the_manifest_lists_the_two_metrics_for_the_unet_cells_only():
    per_layer = {m["name"]: m for m in manifest()["per_layer"]}
    for name in NEW:
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        listed = per_layer[name]
        assert listed["workloads"] == UNET_CELLS == spec["workloads"]
        assert {k: spec[k] for k in listed} == listed
        assert spec["reader"] == "harness.loop_readers:scope_ms_per_step"
        assert listed["layer"] == "ops" and listed["moves"] == "image_s"
    assert per_layer[NEW[0]]["source"] == "device_trace"


@pytest.mark.parametrize("cell", UNET_CELLS)
def test_rehearsal_reads_both_scopes_in_the_unet_cells(capsys, cell):
    code, last, out = rehearse(capsys, cell, 1, seed=2300000777,
                               extra=["--rehearse"])
    assert code == 0 and last["correct"] is True, out[-3000:]
    step = last["metrics"]["step_ms"]["value"]
    for name in NEW:
        value = last["metrics"][name]
        assert value["unit"] == "ms" and 0.0 < value["value"] < step, out[-3000:]


def test_rehearsal_of_the_dit_cell_leaves_them_out(capsys):
    import run as bench_run

    spec = bench_run.resolve_cell("pixart-1024-solo", rehearse=True)
    assert not set(NEW) & {m["name"] for m in spec["per_layer"]}
