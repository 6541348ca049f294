"""Per-layer metric readers of a byte-level rewrite stage whose attention is
EVA (PR 31): several named scopes of the decode program summed, the decode
step against the bytes it must move, the decode state the loop holds.

They read the counters a rewriter's language model carries
(`PromptRewriter.lm.counters`) by name.  A program without such a rewriter -
every other family, and the parent of PR 31 - gives them nothing to read:
they return None and the line leaves the metric out.
"""

from . import lm_readers as R
from .peaks import PEAKS


def _counters(ctx):
    """The newest served request's counters as a dict of ints, if the
    resident language model is one that holds a ring and a summary table."""
    import numpy as np

    rewriter = R._rewriter(ctx)
    names = getattr(getattr(rewriter, "lm", None), "counters", ())
    if "state_bytes" not in names or not rewriter.served:
        return None
    return dict(zip(names, np.asarray(rewriter.served[-1].counters).tolist()))


def scopes_ms_per_byte(ctx, scopes):
    """Summed device ms, per decoded byte, of the decode program's ops from
    any of the named ``scopes`` (disjoint: an op comes from one)."""
    if _counters(ctx) is None:
        return None
    parts = [R.scope_ms_per_token(ctx, scope) for scope in scopes]
    return sum(p for p in parts if p is not None) if any(
        p is not None for p in parts) else None


def decode_roofline(ctx):
    """The least time the chip could take for one decode step - the bytes it
    must move (`families/evabyte_sdxl.py decode_step_bytes`) over the HBM
    bandwidth: at batch 1 the step is bandwidth-bound by two orders of
    magnitude - over the time a step took."""
    if _counters(ctx) is None:
        return None
    ms = R.module_ms(ctx, "decode", per_token=True)
    if ms is None:
        return None
    bench = ctx["bench"]
    # the CPU rehearsal has no chip: it reads its CPU's step against the one
    # chip of the table, a number that means nothing and is never reported
    peaks = bench.peaks or PEAKS["TPU v5 lite"]
    nbytes = bench.family.decode_step_bytes()["total"]
    return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / (ms / 1e3)


def state_mb(ctx):
    """MB of decode state the loop holds (every layer's ring and summary
    table), as the program counted it."""
    c = _counters(ctx)
    return None if c is None else c["state_bytes"] / 1e6
