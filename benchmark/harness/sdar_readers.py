"""Per-layer metric readers of a rewrite stage whose language model decodes
by diffusion over blocks (PR 41): a trip of its decode loop is a block of B
ids fixed in T denoise passes and committed by one more, so the program's
time is read over its PASSES, not only over its ids.

They read the counters a rewriter's language model carries
(`PromptRewriter.lm.counters`) by name, and the record its decode program
hands back of the experts every pass's rows chose.  A program without such a
rewriter - every other family, and the parent of PR 41 - gives them nothing
to read: they return None and the line leaves the metric out.  The times of
the stage's programs and of the named scopes inside its decode program are
`lm_readers`' `module_ms` and `scope_ms_per_token`, as they are.
"""

from . import lm_readers as R
from .peaks import PEAKS

NEEDS = {"denoise_passes", "commit_passes", "experts_fetched",
         "kv_cache_bytes", "tokens_decoded"}


def _counters(ctx):
    """The newest served request's counters as a dict of ints, if the
    resident language model counts its passes."""
    import numpy as np

    rewriter = R._rewriter(ctx)
    names = getattr(getattr(rewriter, "lm", None), "counters", ())
    if not NEEDS <= set(names) or not rewriter.served:
        return None
    return dict(zip(names, np.asarray(rewriter.served[-1].counters).tolist()))


def _passes(counters):
    return counters["denoise_passes"] + counters["commit_passes"]


def passes_per_token(ctx):
    """Trips of the stack a decoded id: (T + 1) / B."""
    c = _counters(ctx)
    if not c or not c["tokens_decoded"]:
        return None
    return _passes(c) / c["tokens_decoded"]


def pass_ms(ctx):
    """Device ms of the decode program per traced image, over its passes."""
    c = _counters(ctx)
    ms = R.module_ms(ctx, "decode") if c else None
    return None if ms is None or not _passes(c) else ms / _passes(c)


def kv_cache_mb(ctx):
    """MB of keys and values the loop holds, as the program counted them."""
    c = _counters(ctx)
    return None if c is None else c["kv_cache_bytes"] / 1e6


def experts_fetched_per_pass(ctx):
    """Expert weight blocks the decode passes' expert calls fetched, per
    pass and layer, from the program's counter (one a held assignment: an
    expert two rows of a pass chose is fetched twice)."""
    c = _counters(ctx)
    if not c or not _passes(c):
        return None
    layers = R._rewriter(ctx).config.num_hidden_layers
    return c["experts_fetched"] / (_passes(c) * layers)


def _held_of_passes(ctx):
    """From the newest request's record: whether each (pass, layer) call's
    rows chose each expert held here -> bool [calls, rows, top_k] (held)
    and the distinct held experts of each call [calls]."""
    import numpy as np

    rewriter = R._rewriter(ctx)
    served, cfg = rewriter.served[-1], rewriter.config
    record = served.experts[1]
    chosen = np.asarray(record["denoise_experts"])  # [blocks, T, B, L, k]
    blocks, steps, size, layers, top_k = chosen.shape
    calls = np.moveaxis(chosen, 3, 2).reshape(-1, size, top_k)
    if _counters(ctx)["commit_passes"]:
        start = len(served.prompt_ids)
        committed = np.asarray(record["experts"])[
            :, start:start + blocks * size]  # [L, blocks * B, k]
        calls = np.concatenate([calls, committed.reshape(-1, size, top_k)])
    local = calls - cfg.first_local_expert
    held = (local >= 0) & (local < cfg.n_local_experts)
    distinct = np.asarray([len(np.unique(c[h])) for c, h in zip(local, held)])
    return held, distinct


def moe_local_per_pass(ctx):
    """Expert assignments of a pass's B rows that fell on experts held
    here, per pass and layer, over the newest request's decode passes."""
    if _counters(ctx) is None:
        return None
    held, _ = _held_of_passes(ctx)
    return float(held.sum() / len(held))


def decode_roofline(ctx):
    """The least time the chip could take for the decode program - the
    bytes a pass must move (`families/sdar_sdxl.py decode_step_bytes`, the
    experts by the DISTINCT held ones each pass's rows chose, from the run's
    own record) times the passes counted, over the HBM bandwidth: a pass of
    B rows is bandwidth-bound by an order of magnitude - over the program's
    device time."""
    c = _counters(ctx)
    if c is None:
        return None
    ms = R.module_ms(ctx, "decode")
    if ms is None:
        return None
    bench = ctx["bench"]
    # the CPU rehearsal has no chip: it reads its CPU's program against the
    # one chip of the table, a number that means nothing and is never reported
    peaks = bench.peaks or PEAKS["TPU v5 lite"]
    _, distinct = _held_of_passes(ctx)
    nbytes = bench.family.decode_step_bytes(float(distinct.mean()))["total"]
    return 100.0 * nbytes * _passes(c) / peaks["hbm_bytes_per_s"] / (ms / 1e3)
