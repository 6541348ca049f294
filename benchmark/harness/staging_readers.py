"""Per-layer metric reader of where a rewrite stage's decode loop keeps the
caches it carries (PR 40): the bytes a decoded token moves between memory
spaces in whole-cache copies the compiler put round a row's write, counted
from the SERVED decode program's own compiled text.

The count is the program's (`distrifuser_tpu.utils.overlap.cache_staging`).
A program without it - the parent of PR 40 - or without a rewriter gives the
reader nothing to read: it returns None and the line leaves the metric out.
"""

from . import lm_readers as R


def cache_staged_mb_per_token(ctx):
    """MB a decoded token moves between HBM and VMEM in asynchronous copies
    of cache-shaped arrays (as many rows as the stage's longest sequence) in
    the decode loop's body, lane padding counted: 0 where every row is
    written into its cache in place.  Prints the whole count.  None where
    the loop writes no row into such an array (another kind of state)."""
    rewriter = R._rewriter(ctx)
    if rewriter is None:
        return None
    try:
        from distrifuser_tpu.utils.overlap import cache_staging
    except ImportError:
        return None
    text = rewriter.decode_program_text()
    if not text:
        return None
    spec = rewriter.spec
    rows = spec.instruction_tokens + spec.user_tokens + spec.new_tokens
    staging = cache_staging(text, rows)
    print(f"[staging_readers] caches of {rows} rows in the decode loop's "
          f"body: {staging}", flush=True)
    return staging["staged_bytes"] / 1e6 if staging["writes"] else None
