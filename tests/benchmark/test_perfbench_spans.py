"""The readers of the program's own spans and stage clocks
(benchmark/harness/span_readers.py) on hand-written traces and windows, and
what they do with a program that has neither (the parent of PR 24)."""

import types

import pytest
from _util import BENCH, manifest  # noqa: F401  (repo root on sys.path)

from benchmark.harness import span_readers as S

NEW = ["dispatch_ms", "host_post_ms", "execute_unattributed_ms",
       "execute_excess_ms", "host_gap_ms", "programs_per_image",
       "pre_denoise_ms", "post_denoise_ms"]


def sp(name, start, end, thread=0, **stats):
    return {"name": name, "start": start, "end": end, "thread": thread,
            "stats": stats}


# One request on two chips, times in ns.  The scheduler's thread 0 holds the
# batch and its hand-off, the watchdog's worker thread 1 the executor:
#   batch [0, 1000)   handoff [20, 960)      complete [960, 990)
#   run [40, 950)     dispatch [50, 300): latents [60, 100) encode [100, 160)
#                     denoise [160, 200) decode [200, 300)
#                     wait_device [300, 800) to_host [800, 880)
#                     post [880, 950)
SPANS = sorted([
    sp("distri.serve.batch", 0, 1000, n=1),
    sp("distri.serve.handoff", 20, 960),
    sp("distri.serve.complete", 960, 990),
    sp("distri.exec.run", 40, 950, 1),
    sp("distri.pipe.dispatch", 50, 300, 1),
    sp("distri.pipe.latents", 60, 100, 1),
    sp("distri.pipe.encode", 100, 160, 1),
    sp("distri.pipe.denoise", 160, 200, 1),
    sp("distri.pipe.decode", 200, 300, 1),
    sp("distri.pipe.wait_device", 300, 800, 1),
    sp("distri.pipe.to_host", 800, 880, 1),
    sp("distri.pipe.post", 880, 950, 1),
], key=lambda s: (s["start"], -s["end"]))


def device(ops, modules):
    return {"ops": sorted(ops, key=lambda e: e[1]), "modules": modules}


# chip 0: latents glue [70, 90), encoder [110, 150), loop [210, 700) in two
#   ops with a bubble [400, 450), decode [720, 790): idle inside the batch =
#   1000 - (20 + 40 + 440 + 70) = 430, of which inside wait_device:
#   [400, 450) + [700, 720) + [790, 800) = 80 -> host gap 350
# chip 1: the same but its encoder starts late [140, 180) and its loop
#   [260, 700) has no bubble: idle 1000 - (20 + 40 + 440 + 70) = 430,
#   inside wait_device [700, 720) + [790, 800) = 30 -> host gap 400 (worst)
TRACE = {"devices": {
    0: device([("normal", 70, 20), ("fusion.e", 110, 40),
               ("fusion.a", 210, 190), ("fusion.b", 450, 250),
               ("conv.d", 720, 70)],
              [("jit__normal(1)", 70, 20), ("jit__lambda(2)", 110, 40),
               ("jit_loop(3)", 210, 490), ("jit__lambda(4)", 720, 70)]),
    1: device([("normal", 70, 20), ("fusion.e", 140, 40),
               ("fusion.a", 260, 440), ("conv.d", 720, 70)],
              [("jit__normal(1)", 70, 20), ("jit__lambda(2)", 140, 40),
               ("jit_loop(3)", 260, 440), ("jit__lambda(4)", 720, 70),
               ("jit_add(5)", 795, 0)]),
}, "host": []}


class Fam:
    DENOISE_MODULES = ("loop",)


def context(trace=TRACE, spans=SPANS, traced=1):
    bench = types.SimpleNamespace(
        family_module=Fam, traced=[{"ok": True}] * traced, trace_dir=None)
    ctx = {"trace": trace, "bench": bench, "results": []}
    if spans is not None:
        ctx["_program_spans"] = spans
    return ctx


def test_innermost_segment_crosses_threads():
    seg = S.innermost_segments(SPANS)
    at = {t: next(n for lo, hi, n in seg if lo <= t < hi)
          for t in (10, 30, 45, 55, 70, 250, 500, 900, 955, 970, 995)}
    assert at == {10: "distri.serve.batch", 30: "distri.serve.handoff",
                  45: "distri.exec.run", 55: "distri.pipe.dispatch",
                  70: "distri.pipe.latents", 250: "distri.pipe.decode",
                  500: "distri.pipe.wait_device", 900: "distri.pipe.post",
                  955: "distri.serve.handoff", 970: "distri.serve.complete",
                  995: "distri.serve.batch"}
    assert all(a[1] <= b[0] for a, b in zip(seg, seg[1:]))
    idle = S.idle_by_segment([(0, 70), (90, 110), (400, 450)], seg)
    assert idle["distri.pipe.latents"] == 20  # [60, 70) + [90, 100)
    assert idle["distri.pipe.wait_device"] == 50
    assert sum(idle.values()) == 140


def test_host_gap_is_idle_outside_wait_device_on_the_worst_chip(capsys):
    assert S.host_gap_ms(context()) == pytest.approx(400e-6)
    said = capsys.readouterr().out
    assert "device 1" in said and "93.0% of it under a span" in said
    # the same gaps on one chip only: its own number
    one = {"devices": {0: TRACE["devices"][0]}, "host": []}
    assert S.host_gap_ms(context(one)) == pytest.approx(350e-6)
    # two traced images share the idle time
    assert S.host_gap_ms(context(traced=2)) == pytest.approx(200e-6)


def test_busy_time_before_and_after_the_denoise_program():
    ctx = context()
    # chip 0: 20 + 40 before its loop, 70 after; chip 1 the same
    assert S.pre_denoise_ms(ctx) == pytest.approx(60e-6)
    assert S.post_denoise_ms(ctx) == pytest.approx(70e-6)
    # the worst chip has five module executions, one of them an empty glue op
    assert S.programs_per_image(ctx) == 5.0
    # glue between two denoise chunks is in neither
    two = {"devices": {0: device(
        [("fusion.e", 110, 40), ("fusion.a", 210, 90), ("add", 310, 30),
         ("fusion.a", 350, 250), ("conv.d", 720, 70)],
        [("jit__lambda(2)", 110, 40), ("jit_loop(3)", 210, 90),
         ("jit_add(9)", 310, 30), ("jit_loop(3)", 350, 250),
         ("jit__lambda(4)", 720, 70)])}, "host": []}
    assert S.pre_denoise_ms(context(two)) == pytest.approx(40e-6)
    assert S.post_denoise_ms(context(two)) == pytest.approx(70e-6)


def test_a_program_without_spans_or_clocks_gives_nothing_to_read():
    """The parent of PR 24 under this PR's benchmark files: no metric raises,
    the span- and clock-based ones are left out, the device-only ones read
    the one traced request's whole window."""
    old = types.SimpleNamespace(execute_s=2.0, queue_wait_s=0.1)
    ctx = dict(context(spans=[]), results=[old, old])
    assert S.stage_ms(ctx, ["dispatch"]) is None
    assert S.execute_unattributed_ms(ctx) is None
    assert S.execute_excess_ms(ctx) is None
    assert S.host_gap_ms(ctx) is None
    assert S.programs_per_image(ctx) == 5.0
    assert S.pre_denoise_ms(ctx) == pytest.approx(60e-6)
    # two traced requests and no span to tell them apart: nothing
    assert S.pre_denoise_ms(dict(ctx, bench=context(traced=2)["bench"])) \
        is None
    # a run that was not traced
    off = {"trace": None, "results": [], "bench": context()["bench"]}
    for read in (S.host_gap_ms, S.programs_per_image, S.pre_denoise_ms,
                 S.post_denoise_ms, S.execute_excess_ms):
        assert read(off) is None


def result(i, execute_s, **stage_s):
    return types.SimpleNamespace(request_id=i, execute_s=execute_s,
                                 stage_s=stage_s)


def test_a_stalled_request_gets_a_stage(capsys):
    """Nine quiet requests and one whose copy to the host stalled for 3 s."""
    quiet = dict(dispatch=0.012, device_wait=2.4, to_host=0.008, post=0.004)
    window = [result(i, 2.4245 + 1e-4 * (i % 3), **quiet) for i in range(9)]
    window.insert(4, result(9, 5.4246, **dict(quiet, to_host=3.008)))
    ctx = {"results": window}
    assert S.stage_ms(ctx, ["dispatch"]) == pytest.approx(12.0)
    assert S.stage_ms(ctx, ["to_host", "post"]) == pytest.approx(12.0)
    assert S.execute_unattributed_ms(ctx) == pytest.approx(0.6, abs=0.11)
    assert S.execute_excess_ms(ctx) == pytest.approx(3000.0, abs=0.2)
    said = capsys.readouterr().out
    assert "request 9" in said and "to_host 3008.000 / 8.000" in said
    # the medians do not see it
    assert S.stage_ms(ctx, ["to_host"]) == pytest.approx(8.0)


def test_step_mode_begin_is_outside_execute():
    r = result(1, 1.0, begin=0.3, steps=0.6, finish=0.1)
    assert S.execute_unattributed_ms({"results": [r]}) == pytest.approx(300.0)
    assert S.stage_ms({"results": [r]}, ["dispatch"]) is None


@pytest.mark.parametrize("name", NEW)
def test_new_metric_is_appended_with_no_workloads_key(name):
    m = manifest()
    listed = [e["name"] for e in m["per_layer"]]
    assert listed[-len(NEW):] == NEW
    entry = m["per_layer"][listed.index(name)]
    assert "workloads" not in entry and entry["moves"] == "image_s"
