"""Per-layer metric reader of the UNet's denoise loop by named scope (PR 30):
the device time of the `jit(loop)` instructions that came from one
`jax.named_scope` (`groupnorm`, `conv`), per denoise step.

The trace names a device op by its instruction; which scope an instruction
came from is read from the compiled loop's own HLO text, as
`lm_readers.scope_ms_per_token` does for the decode program.  The server is
gone when the readers run, so the text comes from a runner of the program's
own (`parallel.runner.make_runner` over the run's weights, configured as
`Bench.build` configures the served one) through `DenoiseRunner.compiled_hlo`:
the served program from JAX's compile cache on the chip, a compile of
seconds in the CPU rehearsal.

A family without a UNet gives the reader nothing to read, and so does a loop
whose traced ops the text does not name (another program than the served
one): it returns None and the line leaves the metric out.
"""

import re

from . import trace_reduce as T
from .lm_readers import scope_of_instruction
from .readers import _denoise

# any instruction of a compiled program's HLO text, scoped or not
_NAMED = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")

# the share of the loop's traced device time that instructions of the text
# must cover before a scope's share of it is believed
NAMED_SHARE = 0.9


def loop_scopes(ctx):
    """`instruction_scopes` of the compiled denoise loop, made once a run;
    None where the family serves no UNet."""
    if "loop_scopes" not in ctx:
        ctx["loop_scopes"] = _loop_scopes(ctx["bench"])
    return ctx["loop_scopes"]


def _loop_scopes(bench):
    family = bench.family
    if not (hasattr(family, "unet_config") and "unet" in bench.weights):
        return None
    from distrifuser_tpu import DistriConfig
    from distrifuser_tpu.parallel.runner import make_runner
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
    return instruction_scopes(runner.compiled_hlo(
        bench.steps, text_len=bench.config["tokenizer"]["model_max_length"]))


def instruction_scopes(hlo_text):
    """{instruction: op_name, "" where the compiler named no scope} of a
    compiled program's HLO text.  An instruction is keyed as a trace names
    a device op: by `trace_reduce.short_name`, its name and result shape (a
    TPU trace; another program's `fusion.7` of another shape is then not
    this one's), and by its bare name (a CPU trace)."""
    scopes = scope_of_instruction(hlo_text)
    out = {}
    for line in hlo_text.splitlines():
        m = _NAMED.match(line)
        if m:
            op_name = scopes.get(m.group(1), "")
            out[m.group(1)] = op_name
            out[T.short_name(line.strip().removeprefix("ROOT "))] = op_name
    return out


def scope_ms_per_step(ctx, scope):
    """Summed device ms, per denoise step, of the loop's ops whose op_name
    lies under ``scope``; mean over the chips."""
    d = _denoise(ctx)
    if not d:
        return None
    scopes = loop_scopes(ctx)
    if not scopes:
        return None
    tag = f"/{scope}/"
    per_dev = []
    for dev, (n, _, runs) in zip(ctx["trace"]["devices"].values(), d):
        loop = T.union(runs)

        def inside(pred):
            return T.total(T.intersection(T.op_intervals(dev, pred), loop))

        named, busy = inside(scopes.__contains__), inside(None)
        if named < NAMED_SHARE * busy:
            print(f"[loop_readers] the compiled loop's text names "
                  f"{named / 1e6:.3f} of {busy / 1e6:.3f} ms of the loop's "
                  f"traced ops: not the served program", flush=True)
            return None
        hit = inside(lambda name: tag in scopes.get(name, ""))
        per_dev.append(hit / 1e6 / n / ctx["bench"].steps)
    value = sum(per_dev) / len(per_dev)
    return value or None
