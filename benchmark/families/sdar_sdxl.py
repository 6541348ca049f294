"""Family `sdar_sdxl`: an SDAR-style language model (grouped-query attention
with per-head norms, softmax-routed gated-SiLU experts, generation by
diffusion over blocks: SDAR-30B-A3B-Chat) as the think-then-rewrite stage in
front of SDXL, through `DistriSDXLPipeline` with a `PromptRewriter`
resident.

The image side is `unet_sdxl`'s, inherited: config objects, weights from the
seed, the UNet's analytic FLOPs (one row a step: the cell's sampler runs
without guidance).  Added here: the language model's configuration from the
published keys at the top level of the benchmark's configuration dict, its
weights made on the device leaf by leaf with every router made
share-symmetric (the router has no selection bias to balance as the sibling
families balance theirs: `share_symmetric_router`), and the bytes one PASS
of the decode program must move, for `sdar_decode_roofline`.

The pipeline with its rewriter resident, and what that rewriter served last
(`latest_served()`, for the reference), are `families/deepseek_v3_sdxl.py`'s:
nothing there names a model.
"""

import functools

from . import _common as F
from .deepseek_v3_sdxl import DECODE_MODULE, PREFILL_MODULE  # noqa: F401
from .deepseek_v3_sdxl import Family as LatentFamily
from .deepseek_v3_sdxl import LM_STREAM, latest_served  # noqa: F401
from .nemotron_h_sdxl import _leaf_count
from .unet_sdxl import DENOISE_MODULES, PIPELINE_KIND, TABLE_STD  # noqa: F401
from .unet_sdxl import Family as SDXLFamily

REFERENCE = "sdar_sdxl"


class Family(LatentFamily):
    """`families/deepseek_v3_sdxl.py Family` - its pipeline with the
    rewriter resident, its record of what was served (`latest_served`), the
    UNet's one-row step cost - with this language model's configuration,
    weights and decode-pass bytes."""

    def __init__(self, config: dict):
        from distrifuser_tpu.models import sdar as lm
        from distrifuser_tpu.pipelines import RewriteSpec

        SDXLFamily.__init__(self, config)
        self.lm_config = lm.sdar_config_from_json(config)
        self.rewrite = RewriteSpec(**config["rewrite"])
        self.rewriter = None

    def init_weights(self, seed: int, dtype, mesh) -> dict:
        lm = init_lm_on_device(self.lm_config, F.seed_key(seed, LM_STREAM),
                               dtype, mesh)
        return dict(SDXLFamily.init_weights(self, seed, dtype, mesh), lm=lm)

    def decode_step_bytes(self, distinct_per_pass=None, itemsize=2) -> dict:
        """What one PASS of the decode program (a block's B rows through
        the stack) cannot avoid moving, from shapes, as a mean over the
        program's passes - T denoise passes and one commit pass a block:
        every layer's weights outside its experts once (attention, norms,
        router); of the held experts the DISTINCT ones the pass's rows chose
        (``distinct_per_pass`` a layer, from the run's own record; else the
        expectation held * (1 - (1 - top_k / width)^B)), each once, so that
        fetching an expert once for all rows can never read above 100%; of
        every layer's keys and values the rows 0 .. end of the block read,
        as a mean over the blocks, and in a commit pass the block's B rows
        written; final norm and head in the denoise passes only; B embedding
        rows."""
        from distrifuser_tpu.models import sdar as lm

        cfg, rewrite = self.lm_config, self.rewrite
        size, steps = cfg.block_length, cfg.denoising_steps
        if distinct_per_pass is None:
            miss = 1.0 - cfg.num_experts_per_tok / cfg.num_experts
            distinct_per_pass = cfg.n_local_experts * (1.0 - miss ** size)
        shapes = lm.param_shapes(cfg)
        weights = experts = 0
        for layer in shapes["layers"]:
            ffn = dict(layer["ffn"])
            one = _leaf_count(ffn.pop("experts")) / cfg.n_local_experts
            experts += distinct_per_pass * one * itemsize
            weights += _leaf_count(dict(layer, ffn=ffn)) * itemsize
        start = rewrite.instruction_tokens + rewrite.user_tokens
        # the block at t0 reads rows 0 .. t0 + B - 1: a mean over the blocks
        rows = start + (rewrite.new_tokens - size) / 2 + size
        row_bytes = 2 * cfg.num_key_value_heads * cfg.head_dim * itemsize
        commit_share = 1.0 / (steps + 1)
        cache = cfg.num_hidden_layers * row_bytes * (
            rows + size * commit_share)
        head = (_leaf_count(shapes["head"]) + _leaf_count(
            shapes["final_norm"])) * itemsize * (1.0 - commit_share) \
            + size * cfg.hidden_size * itemsize
        return {"weights": weights, "routed_experts": experts,
                "kv_cache": cache, "head_and_embedding": head,
                "total": weights + experts + cache + head}


def init_lm_on_device(cfg, key, dtype, mesh):
    """The language model's tree (`models.sdar.param_shapes`), each leaf
    made on the mesh, replicated, in the served dtype, by the program's
    `init_leaf` rule for its name: one small jitted generator per distinct
    (name, shape), as `init_on_device` does for the diffusion trees - but
    every ROUTER kernel, which is one seeded [d, held] block repeated for
    each share: `share_symmetric_router`."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from distrifuser_tpu.models import sdar as lm

    replicated = NamedSharding(mesh, PartitionSpec())
    leaves, treedef = lm.named_leaves(cfg)
    keys = jax.device_put(jax.random.split(key, len(leaves)), replicated)

    @functools.lru_cache(maxsize=None)
    def generator(name, shape):
        return jax.jit(
            lambda ks, i: lm.init_leaf(ks[i], name, shape, cfg, dtype),
            out_shardings=replicated)

    params = jax.tree_util.tree_unflatten(treedef, [
        generator(name, tuple(shape))(keys, i)
        for i, (name, shape) in enumerate(leaves)])
    symmetric = jax.jit(
        lambda kernel: share_symmetric_router(kernel, cfg.n_local_experts),
        out_shardings=replicated)
    for layer in params["layers"]:
        layer["ffn"]["router"] = {
            "kernel": symmetric(layer["ffn"]["router"]["kernel"])}
    return params


def share_symmetric_router(kernel, held: int):
    """A seeded router kernel [d, width] -> its first ``held`` columns
    repeated for every share of ``held`` experts: each of the ``held``
    directions the router scores is then served by ONE expert of every
    share, a row's ``top_k = width / held`` experts are the best
    direction's one a share, and every row of every pass loads every share
    alike - ``top_k * held / width`` = 1 assignment here, whatever the seed.

    This router has no selection bias to fit (the sibling cells fit theirs
    so that a seed's routing does not move `image_s`), and a seeded softmax
    router left as drawn is the worst case of it: the rows of a request
    reach a layer's router nearly as one vector, choose nearly the same 8
    experts of 128 through all 640 passes, and how many of THOSE lie among
    the 16 held is one draw a layer and seed - 0.68 to 1.33 expert blocks a
    pass and layer over three seeds on the chip, 8% of a pass's time (my
    chip runs, PR 41), where the cell may spread by half a percent.  A
    deployment balances its chips' load; this is that balance exact, by
    construction.  The arithmetic is the published router's, untouched
    (softmax over all 128, the 8 largest - ties within the best direction
    all taken -, weights p / sum of the chosen p = 1/8 each); the experts'
    own weights stay independent draws."""
    import jax.numpy as jnp

    return jnp.tile(kernel[:, :held], (1, kernel.shape[1] // held))
