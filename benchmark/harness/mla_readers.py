"""Per-layer metric readers of a rewrite stage whose language model holds a
latent-attention cache and sparse experts (PR 34): the counters its decode
loop carries, by name, and the decode step against the bytes it must move.

They read the counters a rewriter's language model carries
(`PromptRewriter.lm.counters`) by name.  A program without such a rewriter -
every other family, and the parent of PR 34 - gives them nothing to read:
they return None and the line leaves the metric out.  The times of the
stage's programs and of the named scopes inside its decode program are
`lm_readers`' `module_ms` and `scope_ms_per_token`, as they are, and the
cache's size is `eva_readers.state_mb` (any model's `state_bytes`).
"""

from . import lm_readers as R
from .peaks import PEAKS

NEEDS = {"expert_assignments", "expert_assignments_held", "tokens_reused"}


def _counters(ctx):
    """The newest served request's counters as a dict of ints, if the
    resident language model carries the ones these readers need."""
    import numpy as np

    rewriter = R._rewriter(ctx)
    names = getattr(getattr(rewriter, "lm", None), "counters", ())
    if not NEEDS <= set(names) or not rewriter.served:
        return None
    return dict(zip(names, np.asarray(rewriter.served[-1].counters).tolist()))


def moe_local_per_token(ctx):
    """Expert assignments that fell on experts held here, per token and
    expert layer, over everything the request's state covers (a snapshot's
    tokens too) and its decoded tokens."""
    c = _counters(ctx)
    if not c or not c["expert_assignments"]:
        return None
    top_k = R._rewriter(ctx).config.num_experts_per_tok
    return float(c["expert_assignments_held"] * top_k
                 / c["expert_assignments"])


def _decode_held_per_token(ctx):
    """The same count over the newest request's DECODED positions alone,
    from the record of the experts every position chose that the decode
    program hands back whole: a step's time follows its own tokens' load,
    not the prompt's."""
    import numpy as np

    rewriter = R._rewriter(ctx)
    served = rewriter.served[-1]
    cfg = rewriter.config
    chosen = np.asarray(served.experts[1])[:, len(served.prompt_ids):]
    local = chosen - cfg.first_local_expert
    held = (local >= 0) & (local < cfg.n_local_experts)
    return float(held.sum() / (chosen.shape[0] * chosen.shape[1]))


def decode_roofline(ctx):
    """The least time the chip could take for one decode step - the bytes it
    must move (`families/deepseek_v3_sdxl.py decode_step_bytes`, the routed
    experts by the run's own record of the decoded tokens' choices) over the
    HBM bandwidth: at batch 1 the step is bandwidth-bound by two orders of
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
    nbytes = bench.family.decode_step_bytes(
        _decode_held_per_token(ctx))["total"]
    return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / (ms / 1e3)
