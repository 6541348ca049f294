"""Per-layer metric readers of the rewrite stage (PR 27): the language
model's two programs in the device trace, by XLA module name; the ops of one
named scope inside the decode program; the counters its loop carries.

A program without a rewriter - every other family, and the parent of PR 27 -
gives these readers nothing to read: they return None and the line leaves
the metric out.
"""

import re

from . import trace_reduce as T
from .peaks import PEAKS
from .span_readers import _images

# an instruction of a compiled program's HLO text and the op_name its
# metadata carries (the named scopes it came from)
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name=\"([^\"]*)\"")


def _rewriter(ctx):
    return getattr(ctx["bench"].family, "rewriter", None)


def _module_runs(ctx, which):
    """Per device, the traced executions [(start, end)] of the rewrite
    stage's ``which`` program ("prefill" / "decode")."""
    if ctx.get("trace") is None or _rewriter(ctx) is None or not _images(ctx):
        return None
    fam = ctx["bench"].family_module
    name = {"prefill": fam.PREFILL_MODULE, "decode": fam.DECODE_MODULE}[which]
    runs = [T.module_events(dev, {name})
            for dev in ctx["trace"]["devices"].values()]
    return runs if all(runs) else None


def module_ms(ctx, module, per_token=False):
    """Device ms per traced image of one of the stage's programs (the
    slowest chip), over the decoded tokens where ``per_token``."""
    runs = _module_runs(ctx, module)
    if not runs:
        return None
    ms = max(T.total(r) for r in runs) / 1e6 / _images(ctx)
    return ms / _rewriter(ctx).spec.new_tokens if per_token else ms


def scope_of_instruction(hlo_text):
    """{instruction name: op_name} of a compiled program's HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def scope_ms_per_token(ctx, scope):
    """Summed device ms, per decoded token, of the decode program's ops
    whose op_name holds ``scope``.  The trace names a device op by its
    instruction; which scope an instruction came from is read from the
    compiled program's own HLO text (`PromptRewriter.decode_program_text`),
    the same on the chip and in the CPU rehearsal."""
    runs = _module_runs(ctx, "decode")
    if not runs:
        return None
    text = _rewriter(ctx).decode_program_text()
    if not text:
        return None
    scopes = scope_of_instruction(text)
    tag = f"/{scope}/"

    def in_scope(op_name):
        return tag in scopes.get(op_name.split(" ")[0].lstrip("%"), "")

    worst = 0.0
    for dev, decode in zip(ctx["trace"]["devices"].values(), runs):
        hits = T.intersection(T.op_intervals(dev, in_scope), T.union(decode))
        worst = max(worst, T.total(hits))
    if not worst:
        return None
    return worst / 1e6 / _images(ctx) / _rewriter(ctx).spec.new_tokens


def _counters(ctx):
    """The newest served request's `COUNTERS`, as a dict of ints."""
    import numpy as np

    from distrifuser_tpu.models.nemotron_h import COUNTERS

    rewriter = _rewriter(ctx)
    if rewriter is None or not rewriter.served:
        return None
    return dict(zip(COUNTERS, np.asarray(rewriter.served[-1].counters
                                         ).tolist()))


def moe_local_per_token(ctx):
    """Expert assignments that fell on experts held here, per token and E
    layer, over the request's prefill and decode."""
    c = _counters(ctx)
    if not c or not c["expert_assignments"]:
        return None
    top_k = _rewriter(ctx).config.num_experts_per_tok
    return float(c["expert_assignments_held"] * top_k
                 / c["expert_assignments"])


def decode_roofline(ctx):
    """The least time the chip could take for one decode step - the bytes it
    must move over the HBM bandwidth: at batch 1 the step is bandwidth-bound
    by two orders of magnitude - over the time a step took."""
    ms = module_ms(ctx, "decode", per_token=True)
    if ms is None:
        return None
    bench = ctx["bench"]
    # the CPU rehearsal has no chip: it reads its CPU's step against the one
    # chip of the table, a number that means nothing and is never reported
    peaks = bench.peaks or PEAKS["TPU v5 lite"]
    nbytes = bench.family.decode_step_bytes(moe_local_per_token(ctx))["total"]
    return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / (ms / 1e3)
