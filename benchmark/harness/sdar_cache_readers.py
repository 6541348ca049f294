"""Per-layer metric readers of what a block-diffusion decode loop does with
the KV caches it carries (PR 43): how much of them the SERVED decode
program's sweeps move through VMEM whole round the rows' writes, counted
from its compiled text, and how many cache rows the sweeps' attention
fetched, from the program's own counter.

`staging_readers.cache_staged_mb_per_token` cannot read this program: its
caches have three axes ([KV heads, rows, head dim]), and the stack is traced
three times - the inner loop's body (a denoise pass alone) and a
conditional's two branches (the sweep a commit pass shares with the next
block's first denoise pass; the last block's commit pass alone).  A program
without such a loop or without the counter - every other family, the parent
of PR 43 for the counter - gives the readers nothing to read: they return
None and the line leaves the metric out.
"""

import functools
import re

from . import lm_readers as R
from . import sdar_readers as S


def _computations(text):
    """{name: its text} of a compiled module's computations."""
    return {m.group(1): c for c in re.split(
        r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", text)
        if (m := re.match(r"(?:ENTRY )?%([\w.\-]+) \(", c))}


def cache_staged_mb_per_block(ctx):
    """MB a decoded BLOCK moves between HBM and VMEM in asynchronous copies
    of whole KV caches ([KV heads, rows, head dim], the rows of the stage's
    longest sequence) in the traces of the stack a block's sweeps run: the
    denoise pass's loop body x (denoising steps - 1) + the shared sweep's
    branch; 0 where every row is written into its cache in place.  Prints
    each trace's count.  None where the program has no such traces."""
    rewriter = R._rewriter(ctx)
    cfg = getattr(rewriter, "config", None)
    steps = getattr(cfg, "denoising_steps", None)
    if not steps:
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
    shape = (cfg.num_key_value_heads, rows, cfg.head_dim)
    comps = _computations(text)

    @functools.lru_cache(maxsize=None)
    def staging(name):
        # (`cache_staging` reads loop bodies: each trace is handed to it as
        # one, with the fusions it calls - a row's write may be one's root)
        called = re.findall(r"calls=%?([\w.\-]+)", comps[name])
        return cache_staging("\n".join(
            [f"%w = () while(), body=%{name}", comps[name]]
            + [comps[c] for c in called if c in comps]) + "\n",
            shapes=[shape])

    def writes_every_cache(names):
        return [n for n in sorted(set(names)) if n in comps and staging(n)[
            "writes"] == 2 * cfg.num_hidden_layers]

    try:
        bodies = writes_every_cache(re.findall(r"body=%?([\w.\-]+)", text))
        branches = writes_every_cache(
            n.strip().lstrip("%") for found in re.findall(
                r"branch_computations=\{([^}]*)\}", text)
            for n in found.split(","))
    except TypeError:  # a `cache_staging` that takes no shapes
        return None
    if len(bodies) != 1 or len(branches) != 2:
        return None
    # the shared sweep holds two blocks' expert calls and the head: the
    # longer of the two branches
    shared = max(branches, key=lambda n: comps[n].count("\n"))
    staged = {"denoise": staging(bodies[0]), "shared": staging(shared)}
    print(f"[sdar_cache_readers] caches {shape} in a block's sweeps, "
          f"{steps - 1} x denoise + shared: {staged}", flush=True)
    return ((steps - 1) * staged["denoise"]["staged_bytes"]
            + staged["shared"]["staged_bytes"]) / 1e6


def kv_rows_per_sweep(ctx):
    """Cache rows of each KV head the sweeps' attention fetched, per sweep
    of the stack and layer, from the program's `kv_rows_fetched` counter:
    the rows in view where the single-pass kernel runs, 0 where the XLA form
    does.  None where the program has no such counter."""
    c = S._counters(ctx)
    if not c or "kv_rows_fetched" not in c or not c.get("stack_sweeps"):
        return None
    layers = R._rewriter(ctx).config.num_hidden_layers
    return c["kv_rows_fetched"] / (c["stack_sweeps"] * layers)
