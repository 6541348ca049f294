"""Per-layer metric readers of the collectives layer and of the denoise
loop's two phases (PR 36): the device time of a synchronous and of a
displaced step, the device time of each exchange's collective instructions,
and what the compiled loop itself says it puts on the wire.

The program names its phases (`phase_sync`, `phase_stale`: the scopes
`parallel/runner.py _make_step` puts a step under) and its exchanges
(`halo`, `stale_kv`, `gn_stats`, `out_gather`, `cfg_combine`); a trace names
a device op by its instruction.  Which scope an instruction came from, and
which instructions are collectives, is read from the compiled loop's own HLO
text: `DenoiseRunner.compiled_hlo` of a runner built as `Bench.build`
builds the served one (the served program out of JAX's compile cache, not a
second compile: `_abstract_inputs` states the served call's argument
attributes), through `utils/overlap.py`, which also classifies each
collective as carry-only or inline.

A program without the scopes - the parent of PR 36 (found by its
`utils/overlap.py` lacking `exchange_report`, before anything is compiled), a family without a
UNet, a loop of one phase where a displaced step is asked for - gives these
readers nothing to read: they return None and the line leaves the metric out.
"""

import collections
import re

from . import loop_readers as L
from .readers import _denoise

SYNC, STALE = "phase_sync", "phase_stale"
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def compiled_loop(ctx):
    """{"text", "plan"} of the served denoise loop, made once a run: its
    compiled HLO text and the program's own byte model for the cell's steps
    (what `comm_plan` hands out: the steps of each phase, and
    `comm_volume_report`'s bytes per step of each); None where there is no
    UNet loop to compile.  Leaves `loop_readers`' scopes of the same text in
    the context, so a cell that lists readers of both compiles once."""
    if "compiled_loop" not in ctx:
        ctx["compiled_loop"] = _compiled_loop(ctx["bench"])
        if ctx["compiled_loop"] and "loop_scopes" not in ctx:
            ctx["loop_scopes"] = L.instruction_scopes(
                ctx["compiled_loop"]["text"])
    return ctx["compiled_loop"]


def _compiled_loop(bench):
    """The runner as `loop_readers._loop_scopes` builds it (this module may
    not edit that one to share the lines)."""
    family = bench.family
    if not (hasattr(family, "unet_config") and "unet" in bench.weights):
        return None
    from distrifuser_tpu.utils import overlap

    if not hasattr(overlap, "exchange_report"):
        # a program from before the phases were named: nothing to read, and
        # no reason to compile its loop a second time to find that out
        return None
    from distrifuser_tpu import DistriConfig
    from distrifuser_tpu.parallel.runner import make_runner
    from distrifuser_tpu.parallel.stepcache import phase_step_counts
    from distrifuser_tpu.schedulers import get_scheduler

    from benchmark.families._common import scheduler_kwargs

    serve = bench.traffic.get("serve", {})
    dcfg = DistriConfig(
        devices=bench.devices, height=bench.height, width=bench.width,
        do_classifier_free_guidance=bench.guidance > 1.0,
        batch_size=int(serve.get("program_batch_rows", 1)),
        **bench.traffic.get("distri", {}))
    runner = make_runner(
        dcfg, family.unet_config, bench.weights["unet"],
        get_scheduler(bench.scheduler, **scheduler_kwargs(bench.config)))
    text = runner.compiled_hlo(
        bench.steps, text_len=bench.config["tokenizer"]["model_max_length"])
    model = runner.comm_volume_report(per_phase=True)  # as comm_plan asks
    per_step = {phase: sum(kinds.values())
                for phase, kinds in model.get("bytes", {}).items()}
    interval = dcfg.step_cache_interval if dcfg.step_cache_enabled else 1
    return {"text": text, "plan": {
        "steps": phase_step_counts(bench.steps, dcfg.warmup_steps, interval),
        "bytes_per_step": per_step}}


def _phase_steps(ctx):
    """{phase scope: steps of an image that run under it}."""
    loop, steps = compiled_loop(ctx), ctx["bench"].steps
    if f"/{STALE}/" not in loop["text"]:  # a loop of one phase
        return {SYNC: steps, STALE: 0}
    sync = loop["plan"]["steps"]["sync"]
    return {SYNC: sync, STALE: steps - sync}


def _loop_ops(ctx):
    """Per device: (images traced, [ops of each traced loop execution]),
    filed once a run; None without a trace, and where the text does not name
    the traced loop (another program than the served one)."""
    if "loop_ops" not in ctx:
        ctx["loop_ops"] = _find_loop_ops(ctx)
    return ctx["loop_ops"]


def _find_loop_ops(ctx):
    d = _denoise(ctx) if ctx.get("trace") is not None else None
    if not d or not compiled_loop(ctx):
        return None
    scopes = L.loop_scopes(ctx)
    out = []
    for dev, (n, _, runs) in zip(ctx["trace"]["devices"].values(), d):
        per_run = [[] for _ in runs]
        named = busy = 0
        i = 0
        for name, start, dur in dev["ops"]:  # sorted by start, like runs
            while i < len(runs) and start >= runs[i][1]:
                i += 1
            if i == len(runs):
                break
            if start >= runs[i][0]:
                per_run[i].append((name, start, dur))
                busy += dur
                named += dur * (name in scopes)
        if named < L.NAMED_SHARE * busy:
            print(f"[exchange_readers] the compiled loop's text names "
                  f"{named / 1e6:.3f} of {busy / 1e6:.3f} ms of the loop's "
                  f"traced ops: not the served program", flush=True)
            return None
        out.append((n, per_run))
    return out


_CALLED = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation)"
    r"=%?([\w.\-]+)|(?:branch_computations|called_computations)=\{([^}]*)\}")
_BODY = re.compile(r"body=%?([\w.\-]+)")


def phase_of_instruction(text):
    """{instruction name: phase scope} of the instructions that run INSIDE a
    while loop of a compiled program: those of every computation a `while`
    body reaches.  An instruction is of the phase its own op_name names, one
    the compiler made (no op_name) of the phase most of its computation is.
    What the compiler hoisted out of a loop keeps the loop's op_name and runs
    once, before it: it is in no computation a body reaches, and left out."""
    from distrifuser_tpu.utils.overlap import parse_computations

    blocks = parse_computations(text)
    todo, reached = list(set(_BODY.findall(text))), set()
    while todo:
        comp = todo.pop()
        if comp in reached or comp not in blocks:
            continue
        reached.add(comp)
        for line in blocks[comp]:
            for one, many in _CALLED.findall(line):
                todo += [one] if one else [
                    c.strip().lstrip("%") for c in many.split(",")]
    out = {}
    for comp in reached:
        mine = {}
        for line in blocks[comp]:
            m = L._NAMED.match(line)
            if m:
                op = _OP_NAME.search(line)
                parts = op.group(1).split("/") if op else ()
                mine[m.group(1)] = next(
                    (p for p in (SYNC, STALE) if p in parts), "")
        tally = collections.Counter(p for p in mine.values() if p)
        default = tally.most_common(1)[0][0] if tally else ""
        out.update({name: p or default for name, p in mine.items()})
    return out


def phase_step_ms(ctx, phase):
    """Device ms per step of the ops of the loop's ``phase``: from the first
    to the last of them in each execution of the program, the bubbles
    between them included as `step_ms` includes them, over the steps that
    run under the phase; worst chip.  Prints the busy ms (the ops' own
    durations summed) beside it."""
    per_dev = _loop_ops(ctx)
    steps = _phase_steps(ctx)[phase] if per_dev else 0
    if not steps:
        return None
    if "phase_of_instruction" not in ctx:
        ctx["phase_of_instruction"] = phase_of_instruction(
            compiled_loop(ctx)["text"])
    phases = ctx["phase_of_instruction"]
    worst = busy_worst = 0.0
    for n, per_run in per_dev:
        span = busy = 0
        for ops in per_run:
            mine = [(s, s + d) for name, s, d in ops
                    if phases.get(name.split(" ")[0]) == phase]
            if mine:
                span += max(e for _, e in mine) - min(s for s, _ in mine)
                busy += sum(e - s for s, e in mine)
        worst = max(worst, span / 1e6 / n / steps)
        busy_worst = max(busy_worst, busy / 1e6 / n / steps)
    print(f"[exchange_readers] {phase}: {worst:.4f} ms a step over {steps} "
          f"steps an image, {busy_worst:.4f} ms of it inside ops",
          flush=True)
    return worst or None


def _collectives(ctx):
    """{instruction name: (`overlap.Collective`, "issue" | "wait" |
    "whole")} of the compiled loop's while bodies.  An async pair is two
    device ops, the start (issue) and the done (wait); the analysis names
    one half and the other is found by its name."""
    if "loop_collectives" in ctx:
        return ctx["loop_collectives"]
    from distrifuser_tpu.utils.overlap import analyze_loop_collectives

    out = {}
    for report in analyze_loop_collectives(compiled_loop(ctx)["text"]):
        for name, c in report.collectives.items():
            if "-start" in name:
                out[name] = (c, "issue")
                out[name.replace("-start", "-done", 1)] = (c, "wait")
            elif "-done" in name:
                out[name] = (c, "wait")
                out[name.replace("-done", "-start", 1)] = (c, "issue")
            else:
                out[name] = (c, "whole")
    ctx["loop_collectives"] = out
    return out


def exchange_ms_per_step(ctx, scope):
    """Summed device ms, per DISPLACED step, of the collective instructions
    of the `phase_stale` body that came from the ``scope`` exchange; worst
    chip.  For an async pair the start's time is the issue and the done's
    the wait: both are in the sum, and printed apart."""
    per_dev = _loop_ops(ctx)
    steps = _phase_steps(ctx)[STALE] if per_dev else 0
    if not steps:
        return None
    wanted = {name: half for name, (c, half) in _collectives(ctx).items()
              if c.phase == STALE and c.kind == scope}
    if not wanted:
        return None
    worst = None
    for n, per_run in per_dev:
        halves = {"issue": 0, "wait": 0, "whole": 0}
        for ops in per_run:
            for name, _, dur in ops:
                half = wanted.get(name.split(" ")[0])
                if half:
                    halves[half] += dur
        ms = {k: v / 1e6 / n / steps for k, v in halves.items()}
        if worst is None or sum(ms.values()) > sum(worst.values()):
            worst = ms
    print(f"[exchange_readers] {scope}: {len(wanted)} device ops a displaced "
          f"step; ms a step: issue (-start) {worst['issue']:.5f}, wait "
          f"(-done) {worst['wait']:.5f}, synchronous {worst['whole']:.5f}",
          flush=True)
    return sum(worst.values()) or None


def _stale_report(ctx):
    """`utils.overlap.exchange_report` of the compiled loop's `phase_stale`
    body: {kind: {"collectives", "inline", "bytes"}}."""
    loop = compiled_loop(ctx)
    if not loop:
        return None
    if "exchange_report" not in ctx:
        from distrifuser_tpu.utils.overlap import exchange_report

        ctx["exchange_report"] = exchange_report(loop["text"])
    return ctx["exchange_report"].get(STALE)


def exchange_mb_per_step(ctx):
    """MB per device and displaced step that the COMPILED loop puts on the
    wire (its collective instructions' shapes, gathered-buffer convention);
    prints the program's own model, `comm_plan`, beside it."""
    stale = _stale_report(ctx)
    if not stale:
        return None
    nbytes = sum(row["bytes"] for row in stale.values())
    plan = compiled_loop(ctx)["plan"]["bytes_per_step"].get("stale", 0)
    by_kind = {kind: row["bytes"] for kind, row in sorted(stale.items())}
    print(f"[exchange_readers] compiled phase_stale body: {nbytes} B a "
          f"device and step {by_kind}; comm_plan({ctx['bench'].steps})"
          f"['bytes_per_step']['stale'] = {plan} B; compiled / model = "
          f"{nbytes / plan if plan else float('nan'):.6f} (the model leaves "
          "the output gather and the CFG combine out)", flush=True)
    return nbytes / 1e6 or None


def inline_collectives_per_step(ctx):
    """Collective instructions of the `phase_stale` body whose value this
    iteration computes with (the design: the output gather and the CFG
    combine); every other one reaches only the carry."""
    stale = _stale_report(ctx)
    if not stale:
        return None
    inline = {kind: row["inline"] for kind, row in stale.items()
              if row["inline"]}
    print(f"[exchange_readers] inline collectives of the phase_stale body: "
          f"{inline} of {sum(r['collectives'] for r in stale.values())}",
          flush=True)
    return float(sum(inline.values()))
