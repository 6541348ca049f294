"""Per-layer metric reader of where a rewrite stage's greedy decode loop
keeps KV caches of THREE axes (PR 45): `staging_readers
.cache_staged_mb_per_token` knows a cache by its rows alone ([rows, C]);
a cache that holds `kv_pack` KV heads side by side in a row
([KV heads / pack, rows, pack * head dim]) is named by its whole shape.

The count is the program's (`distrifuser_tpu.utils.overlap.cache_staging`).
A program without such a cache - every other family, and the parent of PR
45 - gives the reader nothing to read: it returns None and the line leaves
the metric out.
"""

from . import lm_readers as R


def cache_staged_mb_per_token(ctx):
    """MB a decoded token moves between HBM and VMEM in asynchronous copies
    of whole KV caches (keys or values of one attention layer, the rows of
    the stage's longest sequence) in the body of the SERVED decode program's
    loop, lane padding counted: 0 where every row is written into its cache
    in place.  Prints the whole count.  None where the resident language
    model holds no such cache or the loop writes no row into one."""
    rewriter = R._rewriter(ctx)
    cfg = getattr(rewriter, "config", None)
    pack = getattr(cfg, "kv_pack", None)
    if not pack:
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
    shape = (cfg.num_key_value_heads // pack, rows, pack * cfg.head_dim)
    staging = cache_staging(text, shapes=[shape])
    print(f"[lfm2_readers] caches {shape} in the decode loop's body: "
          f"{staging}", flush=True)
    return staging["staged_bytes"] / 1e6 if staging["writes"] else None
