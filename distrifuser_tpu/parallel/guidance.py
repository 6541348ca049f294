"""Classifier-free-guidance branch handling shared by all runners.

Three CFG modes exist framework-wide (reference semantics, utils.py:68-96 +
the world_size==1 batch-fold path in the model forwards):

* ``cfg_split``   — the ``cfg`` mesh axis holds one branch per device group;
* folded          — no split axis, both branches ride the batch dim (2B);
* none            — guidance off, single branch.

`DenoiseRunner` (displaced patch / tensor) and `PipeFusionRunner` (DiT
pipeline) must agree on branch order (0 = unconditional, reference rank
layout utils.py:98-104) and on the combine formula, so the logic lives here
once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.config import CFG_AXIS, DistriConfig
from .collectives import all_gather


def branch_select(cfg: DistriConfig, enc, added=None):
    """Pick this device's CFG branch of branch-major inputs ``[2, B, ...]``
    (cfg_split), fold branches into the batch dim (single-group CFG), or
    drop the conditional branch (guidance off).

    Returns (my_enc, my_added, batch_mult): ``batch_mult`` is how many
    branch-copies of the latent batch ride the model's batch dim.
    """
    if cfg.cfg_split:
        br = lax.axis_index(CFG_AXIS)
        my_enc = jnp.take(enc, br, axis=0)
        my_added = (
            {k: jnp.take(v, br, axis=0) for k, v in added.items()}
            if added is not None
            else None
        )
        return my_enc, my_added, 1
    if cfg.do_classifier_free_guidance:
        my_enc = enc.reshape(-1, *enc.shape[2:])
        my_added = (
            {k: v.reshape(-1, *v.shape[2:]) for k, v in added.items()}
            if added is not None
            else None
        )
        return my_enc, my_added, enc.shape[0]
    my_added = {k: v[0] for k, v in added.items()} if added is not None else None
    return enc[0], my_added, 1


def _per_row_gs(gs, ref):
    """A [B]-shaped guidance vector (packed cohort rows, each request its
    own scale) broadcasts over the per-sample trailing dims; the scalar
    path is untouched — byte-identical programs for solo dispatch."""
    gs = jnp.asarray(gs)
    if gs.ndim == 0:
        return gs
    return gs.reshape(gs.shape + (1,) * (jnp.ndim(ref) - 1))


@jax.named_scope("cfg_combine")
def combine_guidance(cfg: DistriConfig, out, gs, batch):
    """Guided output from per-branch model output (full latent or chunk):
    ``u + gs * (c - u)`` with branches gathered over the cfg axis
    (cfg_split), unfolded from the batch dim (folded), or passed through.
    ``gs`` is a scalar, or [B] for packed cohort rows (one scale per
    batch row)."""
    if cfg.cfg_split:
        both = all_gather(out, CFG_AXIS)  # [2, B, ...]
        u, c = both[0], both[1]
        return u + _per_row_gs(gs, u) * (c - u)
    if cfg.do_classifier_free_guidance:
        u, c = out[:batch], out[batch:]
        return u + _per_row_gs(gs, u) * (c - u)
    return out
