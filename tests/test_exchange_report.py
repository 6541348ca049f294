"""The collectives layer as the compiled program states it (PR 36): every
collective of the patch loop named by exchange and by phase, the overlap
analysis as counters (`DenoiseRunner.exchange_report`), its bytes against the
program's own model (`comm_volume_report`), and TPU-style text read as well
as CPU text.
"""

import jax
import pytest

from distrifuser_tpu import DistriConfig
from distrifuser_tpu.models import unet as unet_mod
from distrifuser_tpu.parallel.runner import DenoiseRunner
from distrifuser_tpu.schedulers import get_scheduler
from distrifuser_tpu.utils import overlap

# the byte model's layer kinds -> the exchanges' scopes
MODEL_KIND = {"attn": "stale_kv", "conv2d": "halo", "gn": "gn_stats"}
DESIGNED_INLINE = {"out_gather", "cfg_combine"}


def _runner(devices, **distri):
    ucfg = unet_mod.tiny_config(sdxl=True)
    params = unet_mod.init_unet_params(jax.random.PRNGKey(0), ucfg)
    cfg = DistriConfig(devices=devices, height=128, width=128,
                       warmup_steps=1, **distri)
    return DenoiseRunner(cfg, ucfg, params, get_scheduler("ddim"))


@pytest.fixture(scope="module")
def patch4(devices8):
    """The displaced patch loop over dp1 x cfg2 x sp2, compiled: steps 0-1
    synchronous, 2-4 displaced."""
    runner = _runner(devices8[:4], parallelism="patch",
                     mode="corrected_async_gn")
    assert dict(runner.cfg.mesh.shape) == {"dp": 1, "cfg": 2, "sp": 2}
    return runner, runner.compiled_hlo(5, text_len=7)


def test_every_collective_of_the_loop_has_a_kind_and_a_phase(patch4):
    _, text = patch4
    reports = overlap.analyze_loop_collectives(text)
    assert len(reports) == 2  # the synchronous loop and the displaced scan
    seen = set()
    for report in reports:
        assert set(report.collectives) == (
            set(report.deferred) | set(report.inline))
        for name, c in report.collectives.items():
            assert c.kind in overlap.EXCHANGE_KINDS, (name, c)
            assert c.phase in overlap.PHASES, (name, c)
            assert c.nbytes > 0 and c.inline == (name in report.inline)
            seen.add((c.phase, c.kind))
        # one body, one phase
        assert len({c.phase for c in report.collectives.values()}) == 1
    assert seen == {(p, k) for p in overlap.PHASES for k in (
        "halo", "stale_kv", "gn_stats", "out_gather", "cfg_combine")}


def test_the_displaced_body_has_exactly_the_designed_inline_pair(patch4):
    runner, text = patch4
    report = runner.exchange_report(text)
    stale, sync = report["phase_stale"], report["phase_sync"]
    assert {k for k, row in stale.items() if row["inline"]} == DESIGNED_INLINE
    assert all(stale[k]["inline"] == stale[k]["collectives"] == 1
               for k in DESIGNED_INLINE)
    # the negative control: a synchronous step computes with every exchange
    assert all(row["inline"] == row["collectives"] for row in sync.values())
    # through cheap elementwise arithmetic alone the CFG combine does reach
    # only the carry (the scheduler's update): the strict reading is the one
    # the counters give
    lenient = overlap.analyze_loop_collectives(text, elementwise_carry=True)
    assert any(c.kind == "cfg_combine" and c.phase == "phase_stale"
               and not c.inline for r in lenient
               for c in r.collectives.values())


def test_the_compiled_bytes_are_the_models_bytes(patch4):
    """Per phase and kind, what the compiled program's collectives move is
    what `comm_volume_report` counts from the carry's shapes; the model
    leaves out the two inline gathers, which carry no state."""
    runner, text = patch4
    compiled = runner.exchange_report(text)
    model = runner.comm_volume_report(per_phase=True)["bytes"]
    assert set(model) == {"sync", "stale"}
    for phase, kinds in model.items():
        rows = compiled[f"phase_{phase}"]
        assert {MODEL_KIND[k] for k in kinds} | DESIGNED_INLINE == set(rows)
        for kind, nbytes in kinds.items():
            assert rows[MODEL_KIND[kind]]["bytes"] == nbytes, (phase, kind)
    lat = runner.cfg.latent_height * runner.cfg.latent_width * 4 * 4
    assert compiled["phase_stale"]["out_gather"]["bytes"] == lat
    assert compiled["phase_stale"]["cfg_combine"]["bytes"] == 2 * lat


def test_a_one_chip_loop_carries_the_synchronous_phase_only(devices8):
    text = _runner(devices8[:1]).compiled_hlo(3, text_len=7)
    assert "/phase_sync/" in text and "phase_stale" not in text
    assert DenoiseRunner.exchange_report(text) == {}


# What a TPU compiler makes of the exchanges: an async pair, a permute pair
# whose concatenate became pad + maximum, a generic async wrapper, one
# all-gather split into start / overlapped compute / done fusions (one
# channel_id), layouts with parentheses of their own.
TPU_TEXT = '''
HloModule jit_loop, is_scheduled=true

%wrapped_rs (p: f32[8,128]) -> f32[4,128] {
  %p = f32[8,128]{1,0:T(8,128)} parameter(0)
  ROOT %reduce-scatter.1 = f32[4,128]{1,0:T(8,128)} reduce-scatter(%p), channel_id=7, replica_groups={{0,1}}, dimensions={0}, to_apply=%add
}

%fused_start (p0: bf16[512,256]) -> (bf16[512,256], bf16[1024,256], u32[]) {
  %p0 = bf16[512,256]{1,0:T(8,128)(2,1)} parameter(0)
  %all-gather.9 = bf16[1024,256]{1,0:T(8,128)(2,1)} all-gather(%p0), channel_id=9, replica_groups={{0,1},{2,3}}, dimensions={0}
  ROOT %custom-call.1 = (bf16[512,256]{1,0}, bf16[1024,256]{1,0}, u32[]) custom-call(%p0, %all-gather.9), custom_call_target="AsyncCollectiveStart"
}

%fused_middle (p0: bf16[512,256], p1: bf16[64,64]) -> bf16[64,64] {
  %p0 = bf16[512,256]{1,0} parameter(0)
  %p1 = bf16[64,64]{1,0} parameter(1)
  %all-gather.9b = bf16[1024,256]{1,0:T(8,128)(2,1)} all-gather(%p0), channel_id=9, replica_groups={{0,1},{2,3}}, dimensions={0}
  ROOT %dot.5 = bf16[64,64]{1,0} dot(%p1, %p1)
}

%fused_done (p0: bf16[512,256], p1: bf16[1024,256]) -> bf16[1024,256] {
  %p0 = bf16[512,256]{1,0} parameter(0)
  %p1 = bf16[1024,256]{1,0} parameter(1)
  %all-gather.9c = bf16[1024,256]{1,0:T(8,128)(2,1)} all-gather(%p0), channel_id=9, replica_groups={{0,1},{2,3}}, dimensions={0}
  ROOT %custom-call.2 = bf16[1024,256]{1,0:T(8,128)(2,1)S(1)} custom-call(%p0, %p1, %all-gather.9c), custom_call_target="AsyncCollectiveDone"
}

%pad_max (a: bf16[1,4,8], b: bf16[1,4,8]) -> bf16[2,4,8] {
  %a = bf16[1,4,8]{2,1,0} parameter(0)
  %b = bf16[1,4,8]{2,1,0} parameter(1)
  %c = bf16[]{:T(256)} constant(-inf)
  %pad.1 = bf16[2,4,8]{2,1,0} pad(%a, %c), padding=0_1x0_0x0_0
  %pad.2 = bf16[2,4,8]{2,1,0} pad(%b, %c), padding=1_0x0_0x0_0
  ROOT %maximum.1 = bf16[2,4,8]{2,1,0} maximum(%pad.1, %pad.2)
}

%body (carry: (s32[], bf16[2,4,8], bf16[2,16,32], f32[4,128], bf16[64,64])) -> (s32[], bf16[2,4,8], bf16[2,16,32], f32[4,128], bf16[64,64]) {
  %carry = (s32[], bf16[2,4,8]{2,1,0}, bf16[2,16,32]{2,1,0}, f32[4,128]{1,0}, bf16[64,64]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%carry), index=0
  %rows = bf16[1,4,8]{2,1,0:T(8,128)(2,1)S(1)} slice(%carry), slice={[0:1], [0:4], [0:8]}
  %collective-permute-start.3 = (bf16[1,4,8]{2,1,0:T(8,128)(2,1)S(1)}, bf16[1,4,8]{2,1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%rows), channel_id=1, source_target_pairs={{1,0},{3,2}}, metadata={op_name="jit(loop)/shard_map/while/body/closed_call/phase_stale/down_0/halo/halo/ppermute" stack_frame_id=9}
  %collective-permute-start.4 = (bf16[1,4,8]{2,1,0:T(8,128)(2,1)S(1)}, bf16[1,4,8]{2,1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%rows), channel_id=2, source_target_pairs={{0,1},{2,3}}, metadata={op_name="jit(loop)/shard_map/while/body/closed_call/phase_stale/down_0/halo/halo/ppermute" stack_frame_id=9}
  %kv = bf16[16,32]{1,0:T(8,128)(2,1)} fusion(%carry), kind=kOutput, calls=%proj
  %all-gather-start.5 = (bf16[16,32]{1,0:T(8,128)(2,1)}, bf16[2,16,32]{2,1,0:T(8,128)(2,1)}) all-gather-start(%kv), channel_id=3, replica_groups={{0,1},{2,3}}, dimensions={0}, metadata={op_name="jit(loop)/shard_map/while/body/closed_call/phase_stale/down_1/stale_kv/all_gather"}
  %collective-permute-done.3 = bf16[1,4,8]{2,1,0:T(8,128)(2,1)S(1)} collective-permute-done(%collective-permute-start.3), metadata={op_name="jit(loop)/shard_map/while/body/closed_call/phase_stale/down_0/halo/halo/ppermute"}
  %collective-permute-done.4 = bf16[1,4,8]{2,1,0:T(8,128)(2,1)S(1)} collective-permute-done(%collective-permute-start.4), metadata={op_name="jit(loop)/shard_map/while/body/closed_call/phase_stale/down_0/halo/halo/ppermute"}
  %halos = bf16[2,4,8]{2,1,0:T(8,128)(2,1)} fusion(%collective-permute-done.3, %collective-permute-done.4), kind=kLoop, calls=%pad_max, metadata={op_name="jit(loop)/shard_map/while/body/closed_call/phase_stale/down_0/halo/concatenate"}
  %all-gather-done.5 = bf16[2,16,32]{2,1,0:T(8,128)(2,1)} all-gather-done(%all-gather-start.5), metadata={op_name="jit(loop)/shard_map/while/body/closed_call/phase_stale/down_1/stale_kv/all_gather"}
  %moments = f32[8,128]{1,0:T(8,128)} fusion(%carry), kind=kLoop, calls=%moments_of
  %reduce-scatter-start.1 = ((f32[8,128]{1,0:T(8,128)}), f32[4,128]{1,0:T(8,128)}, u32[]) async-start(%moments), calls=%wrapped_rs, metadata={op_name="jit(loop)/shard_map/while/body/closed_call/phase_stale/mid/groupnorm/gn_stats/psum_scatter"}
  %reduce-scatter-done.1 = f32[4,128]{1,0:T(8,128)} async-done(%reduce-scatter-start.1)
  %q = bf16[64,64]{1,0} get-tuple-element(%carry), index=4
  %async-collective-start.2 = (bf16[512,256]{1,0:T(8,128)(2,1)}, bf16[1024,256]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}) fusion(%kv), kind=kCustom, calls=%fused_start
  %gte.1 = bf16[512,256]{1,0} get-tuple-element(%async-collective-start.2), index=0
  %overlapped = bf16[64,64]{1,0} fusion(%gte.1, %q), kind=kCustom, calls=%fused_middle, metadata={op_name="jit(loop)/shard_map/while/body/closed_call/phase_stale/down_1/linear/dot_general"}
  %gte.2 = bf16[1024,256]{1,0} get-tuple-element(%async-collective-start.2), index=1
  %async-collective-done.2 = bf16[1024,256]{1,0:T(8,128)(2,1)S(1)} fusion(%gte.1, %gte.2), kind=kCustom, calls=%fused_done, metadata={op_name="jit(loop)/shard_map/while/body/closed_call/phase_stale/out_gather/all_gather"}
  %attended = bf16[64,64]{1,0} dot(%async-collective-done.2, %overlapped)
  ROOT %tuple.9 = (s32[], bf16[2,4,8]{2,1,0}, bf16[2,16,32]{2,1,0}, f32[4,128]{1,0}, bf16[64,64]{1,0}) tuple(%i, %halos, %all-gather-done.5, %reduce-scatter-done.1, %attended)
}

ENTRY %main (x: s32[]) -> s32[] {
  %x = s32[] parameter(0)
  %while.1 = (s32[], bf16[2,4,8]{2,1,0}, bf16[2,16,32]{2,1,0}, f32[4,128]{1,0}, bf16[64,64]{1,0}) while(%init), condition=%cond, body=%body
  ROOT %r = s32[] get-tuple-element(%while.1), index=0
}
'''


def test_tpu_style_text_is_parsed_and_every_exchange_counted_once():
    (report,) = overlap.analyze_loop_collectives(TPU_TEXT)
    got = {name: (c.opcode, c.kind, c.phase, c.nbytes, c.inline)
           for name, c in report.collectives.items()}
    stale = "phase_stale"
    assert got == {
        # a -start / -done pair once, at the start; a permute: the rows sent
        "collective-permute-start.3": (
            "collective-permute-start", "halo", stale, 1 * 4 * 8 * 2, False),
        "collective-permute-start.4": (
            "collective-permute-start", "halo", stale, 1 * 4 * 8 * 2, False),
        # the gathered buffer, not the operand beside it in the tuple
        "all-gather-start.5": (
            "all-gather-start", "stale_kv", stale, 2 * 16 * 32 * 2, False),
        # the generic wrapper is the collective it calls
        "reduce-scatter-start.1": (
            "reduce-scatter", "gn_stats", stale, 4 * 128 * 4, False),
        # three fusions, one channel: once, at the done, which a dot reads
        "async-collective-done.2": (
            "all-gather", "out_gather", stale, 1024 * 256 * 2, True),
    }
    assert report.n_deferred == 4 and report.n_inline == 1
    assert DenoiseRunner.exchange_report(TPU_TEXT) == {stale: {
        "halo": {"collectives": 2, "inline": 0, "bytes": 128},
        "stale_kv": {"collectives": 1, "inline": 0, "bytes": 2048},
        "gn_stats": {"collectives": 1, "inline": 0, "bytes": 2048},
        "out_gather": {"collectives": 1, "inline": 1, "bytes": 524288},
    }}


PAD_MAX = TPU_TEXT[TPU_TEXT.index("%pad_max ("):TPU_TEXT.index("%body (")]


@pytest.mark.parametrize("what, pad_max, inline", [
    ("as the compiler writes it", PAD_MAX, False),
    ("through the float-normalisation pass's converts", PAD_MAX.replace(
        "maximum(%pad.1, %pad.2)", "maximum(%w.1, %pad.2)").replace(
        "  ROOT", "  %w.1 = f32[2,4,8]{2,1,0} convert(%pad.1)\n  ROOT"), False),
    # a fusion is data movement by what it holds, not by the name it kept
    ("a gathered value scaled inside it", PAD_MAX.replace(
        "pad(%b, %c)", "pad(%s.1, %c)").replace(
        "  %pad.2", "  %s.1 = bf16[1,4,8]{2,1,0} multiply(%b, %b)\n  %pad.2"),
     True),
    ("padded with another value", PAD_MAX.replace(
        "constant(-inf)", "constant(0)"), True),
    ("a maximum against a live value", PAD_MAX.replace(
        "maximum(%pad.1, %pad.2)", "maximum(%pad.1, %live)").replace(
        "  ROOT", "  %live = bf16[2,4,8]{2,1,0} broadcast(%c), dimensions={}\n"
        "  ROOT"), True),
])
def test_a_concatenate_named_fusion_is_data_movement_only_as_pad_and_maximum(
        what, pad_max, inline):
    assert pad_max != PAD_MAX or not inline, what
    text = TPU_TEXT.replace(PAD_MAX, pad_max)
    (report,) = overlap.analyze_loop_collectives(text)
    halos = {c.inline for c in report.collectives.values() if c.kind == "halo"}
    assert halos == {inline}, what
    others = {n: c.inline for n, c in report.collectives.items()
              if c.kind != "halo"}
    assert others == {"all-gather-start.5": False,
                      "reduce-scatter-start.1": False,
                      "async-collective-done.2": True}


def test_the_printed_report_has_kind_phase_and_bytes():
    """What `python -m distrifuser_tpu.utils.overlap <file>` prints."""
    out = overlap.format_report(overlap.analyze_loop_collectives(TPU_TEXT))
    assert "4 deferred / 1 inline" in out
    rows = [ln.split() for ln in out.splitlines()
            if ln.strip().startswith("phase_stale")]
    assert ["phase_stale", "halo", "collective-permute-start", "2", "0",
            "128"] in rows
    assert ["phase_stale", "out_gather", "all-gather", "1", "1",
            "524288"] in rows


@pytest.mark.parametrize("line, opcode, nbytes", [
    ("%a.1 = f32[2,2,1,8]{3,2,1,0} all-gather(%m), dimensions={0}",
     "all-gather", 2 * 2 * 8 * 4),
    ("%a.2 = f32[4,1,32]{2,1,0:T(1,128)S(1)} all-gather(%m), channel_id=2",
     "all-gather", 4 * 32 * 4),
    ("%s.3 = (bf16[8,4]{1,0:T(8,128)(2,1)}, bf16[16,4]{1,0:T(8,128)(2,1)}) "
     "all-gather-start(%x)", "all-gather-start", 16 * 4 * 2),
    ("%s.4 = ((bf16[8,4]{1,0}, f32[2]{0}), (bf16[16,4]{1,0}, f32[4]{0})) "
     "all-gather-start(%x, %y)", "all-gather-start", 16 * 4 * 2 + 4 * 4),
    ("%p.5 = (bf16[1,1,128,320]{3,2,1,0:T(8,128)(2,1)S(1)}, "
     "bf16[1,1,128,320]{3,2,1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}, "
     "u32[]{:S(2)}) collective-permute-start(%r)",
     "collective-permute-start", 128 * 320 * 2),
    ("%r.6 = (f32[4]{0}, s8[3,3]{1,0}) all-reduce(%a, %b), to_apply=%add",
     "all-reduce", 4 * 4 + 9),
])
def test_an_instructions_opcode_and_wire_bytes(line, opcode, nbytes):
    result, rest = overlap._split_result(line)
    assert rest.startswith(opcode + "(") and overlap._opcode(line) == opcode
    assert overlap._wire_bytes(opcode, result) == nbytes


# A decode loop's body as a TPU compiler leaves it around two layers' caches
# of 64 rows: the first layer's `c` comes into VMEM whole, has its row
# written there and goes back, its 64-wide `k_pe` comes in two slices (joined
# by a bitcast) and goes back in one copy; the second layer's two rows are
# written in place; a weight prefetch of another shape stands beside them.
STAGED_TEXT = '''
HloModule jit_rewrite_decode, is_scheduled=true

%write_row (p0: bf16[64,64], p1: bf16[1,64], p2: s32[]) -> bf16[64,64] {
  %p0 = bf16[64,64]{1,0:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[1,64]{1,0:T(2,128)(2,1)} parameter(1)
  %p2 = s32[]{:T(128)} parameter(2)
  %zero = s32[]{:T(128)} constant(0)
  ROOT %dynamic-update-slice.9 = bf16[64,64]{1,0:T(8,128)(2,1)} dynamic-update-slice(%p0, %p1, %p2, %zero)
}

%body (carry: (s32[], bf16[64,512], bf16[64,64], bf16[64,512], bf16[64,64])) -> (s32[], bf16[64,512], bf16[64,64], bf16[64,512], bf16[64,64]) {
  %carry = (s32[]{:T(128)}, bf16[64,512]{1,0:T(8,128)(2,1)}, bf16[64,64]{1,0:T(8,128)(2,1)}, bf16[64,512]{1,0:T(8,128)(2,1)}, bf16[64,64]{1,0:T(8,128)(2,1)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%carry), index=0
  %zero = s32[]{:T(128)} constant(0)
  %c0 = bf16[64,512]{1,0:T(8,128)(2,1)} get-tuple-element(%carry), index=1
  %k0 = bf16[64,64]{1,0:T(8,128)(2,1)} get-tuple-element(%carry), index=2
  %c1 = bf16[64,512]{1,0:T(8,128)(2,1)} get-tuple-element(%carry), index=3
  %k1 = bf16[64,64]{1,0:T(8,128)(2,1)} get-tuple-element(%carry), index=4
  %copy-start.1 = (bf16[64,512]{1,0:T(8,128)(2,1)S(1)}, bf16[64,512]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%c0)
  %slice-start.1 = ((bf16[64,64]{1,0:T(8,128)(2,1)}), bf16[32,64]{1,0:T(8,128)(2,1)S(1)}, s32[]{:S(2)}) slice-start(%k0), slice={[0:32], [0:64]}
  %slice-start.2 = ((bf16[64,64]{1,0:T(8,128)(2,1)}), bf16[32,64]{1,0:T(8,128)(2,1)S(1)}, s32[]{:S(2)}) slice-start(%k0), slice={[32:64], [0:64]}
  %copy-start.7 = (bf16[2048]{0:T(1024)(128)(2,1)S(1)}, bf16[2048]{0:T(1024)(128)(2,1)}, u32[]{:S(2)}) copy-start(%weight)
  %copy-done.1 = bf16[64,512]{1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.1)
  %slice-done.1 = bf16[32,64]{1,0:T(8,128)(2,1)S(1)} slice-done(%slice-start.1)
  %slice-done.2 = bf16[32,64]{1,0:T(8,128)(2,1)S(1)} slice-done(%slice-start.2)
  %joined = bf16[64,64]{1,0:T(8,128)(2,1)S(1)} custom-call(%slice-done.1, %slice-done.2), custom_call_target="ConcatBitcast"
  %dynamic_update_slice.1 = bf16[64,512]{1,0:T(8,128)(2,1)S(1)} dynamic-update-slice(%copy-done.1, %c_row, %i, %zero), metadata={op_name="jit(rewrite_decode)/while/body/closed_call/lm.mla.attn/dynamic_update_slice"}
  %dynamic_update_slice.2 = bf16[64,64]{1,0:T(8,128)(2,1)S(1)} dynamic-update-slice(%joined, %k_row, %i, %zero)
  %latent_cache_attention.1 = (bf16[32,512]{1,0:T(8,128)(2,1)}, s32[1]{0:T(128)S(6)}) custom-call(%i, %q_lat, %q_pe, %dynamic_update_slice.1, %dynamic_update_slice.2), custom_call_target="tpu_custom_call"
  %copy-start.2 = (bf16[64,512]{1,0:T(8,128)(2,1)}, bf16[64,512]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) copy-start(%dynamic_update_slice.1)
  %copy-start.3 = (bf16[64,64]{1,0:T(8,128)(2,1)}, bf16[64,64]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) copy-start(%dynamic_update_slice.2)
  %copy-done.2 = bf16[64,512]{1,0:T(8,128)(2,1)} copy-done(%copy-start.2)
  %copy-done.3 = bf16[64,64]{1,0:T(8,128)(2,1)} copy-done(%copy-start.3)
  %dynamic_update_slice.3 = bf16[64,512]{1,0:T(8,128)(2,1)} dynamic-update-slice(%c1, %c_row, %i, %zero)
  %row_fusion.4 = bf16[64,64]{1,0:T(8,128)(2,1)} fusion(%k1, %k_row, %i), kind=kLoop, calls=%write_row
  %latent_cache_attention.2 = (bf16[32,512]{1,0:T(8,128)(2,1)}, s32[1]{0:T(128)S(6)}) custom-call(%i, %q_lat, %q_pe, %dynamic_update_slice.3, %row_fusion.4), custom_call_target="tpu_custom_call"
  ROOT %tuple.9 = (s32[]{:T(128)}, bf16[64,512]{1,0:T(8,128)(2,1)}, bf16[64,64]{1,0:T(8,128)(2,1)}, bf16[64,512]{1,0:T(8,128)(2,1)}, bf16[64,64]{1,0:T(8,128)(2,1)}) tuple(%i, %copy-done.2, %copy-done.3, %dynamic_update_slice.3, %row_fusion.4)
}

ENTRY %main (x: s32[]) -> s32[] {
  %x = s32[] parameter(0)
  %while.1 = (s32[], bf16[64,512]{1,0}, bf16[64,64]{1,0}, bf16[64,512]{1,0}, bf16[64,64]{1,0}) while(%init), condition=%cond, body=%body
  ROOT %r = s32[] get-tuple-element(%while.1), index=0
}
'''


def test_a_staged_cache_is_counted_and_one_written_in_place_is_not():
    c, k_pe = 64 * 512 * 2, 64 * 128 * 2  # a 64-wide row moves as 128 lanes
    assert overlap.cache_staging(STAGED_TEXT, 64) == {
        "staged_bytes": 2 * c + 2 * k_pe, "staged_copies": 5,
        "writes": 4, "writes_outside_hbm": 2}
    # by shape, where the state's leading axes are not its rows alone
    assert overlap.cache_staging(STAGED_TEXT, shapes=[(64, 512)]) == {
        "staged_bytes": 2 * c, "staged_copies": 2,
        "writes": 2, "writes_outside_hbm": 1}
    # another length's caches, and a program with no loop: nothing to count
    nothing = {"staged_bytes": 0, "staged_copies": 0, "writes": 0,
               "writes_outside_hbm": 0}
    assert overlap.cache_staging(STAGED_TEXT, 8704) == nothing
    in_place = STAGED_TEXT.replace("S(1)", "")
    assert overlap.cache_staging(in_place, 64) == {
        **nothing, "writes": 4}
    assert overlap.cache_staging(
        STAGED_TEXT.replace("body=%body", ""), 64) == nothing
