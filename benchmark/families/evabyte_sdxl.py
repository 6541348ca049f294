"""Family `evabyte_sdxl`: the byte-level EvaByte language model as the
think-then-rewrite stage in front of SDXL, through `DistriSDXLPipeline` with
a `PromptRewriter` resident.

The image side is `unet_sdxl`'s, inherited: config objects, weights from the
seed, the UNet's analytic FLOPs (one row a step: the cell's sampler runs
without guidance).  Added here: the language model's configuration from the
published keys at the top level of the benchmark's configuration dict, its
weights made on the device leaf by leaf under the program's own initialiser
rules, and the bytes one decode step must read, for
`evabyte_decode_roofline`.

What the rewriter of the pipeline built last served stays reachable after
the server has stopped: `Family.rewriter` for the metric readers, and
`latest_served()` - its record of the last requests' served bytes and
logits, what `correct` compares, and nothing that holds weights - for the
reference, which is handed only weights and a request.
"""

import functools

import numpy as np

from . import _common as F
from .nemotron_h_sdxl import _leaf_count
from .unet_sdxl import DENOISE_MODULES, PIPELINE_KIND, TABLE_STD  # noqa: F401
from .unet_sdxl import Family as SDXLFamily
from .unet_sdxl import unet_step_cost

REFERENCE = "evabyte_sdxl"
# XLA module names of the rewrite stage's two programs in the device trace
PREFILL_MODULE, DECODE_MODULE = "rewrite_prefill", "rewrite_decode"
LM_STREAM = 7  # the seed's stream for the language model's weights

_LATEST = {"served": ()}


def latest_served():
    """The `ServedRewrite` records, oldest first, of the rewriter this
    process built last."""
    return _LATEST["served"]


class Family(SDXLFamily):
    def __init__(self, config: dict):
        from distrifuser_tpu.models import evabyte as lm
        from distrifuser_tpu.pipelines import RewriteSpec

        super().__init__(config)
        self.lm_config = lm.evabyte_config_from_json(config)
        self.rewrite = RewriteSpec(**config["rewrite"])
        self.rewriter = None

    def init_weights(self, seed: int, dtype, mesh) -> dict:
        lm = init_lm_on_device(self.lm_config, F.seed_key(seed, LM_STREAM),
                               dtype, mesh)
        return dict(super().init_weights(seed, dtype, mesh), lm=lm)

    def build_pipeline(self, distri_config, weights, scheduler):
        from distrifuser_tpu.pipelines import DistriSDXLPipeline
        from distrifuser_tpu.schedulers import get_scheduler

        sched = get_scheduler(scheduler, **F.scheduler_kwargs(self.config))
        pipe = DistriSDXLPipeline.from_params(
            distri_config, self.unet_config, weights["unet"], self.vae_config,
            weights["vae"], self.text_configs, weights["text"],
            scheduler=sched,
            rewriter=(self.lm_config, weights["lm"], self.rewrite))
        self.rewriter = pipe.rewriter
        _LATEST["served"] = pipe.rewriter.served
        return pipe

    def step_cost(self, height: int, width: int, cfg_rows: int = 1) -> dict:
        """One UNet row a step: the sampler runs without guidance."""
        return unet_step_cost(
            self.config["unet"], height // 8, width // 8, cfg_rows,
            text_len=self.config["tokenizer"]["model_max_length"])

    def decode_step_bytes(self, itemsize=2) -> dict:
        """What one greedy decode step cannot avoid moving, from shapes:
        every layer's weights once, the final norm, the head of eight
        predictions and one embedding row; of the decode state the rows a
        step may see - ring rows 0 .. t % window and the summary rows of
        earlier windows, K and V - as a mean over the decoded positions t
        (the middle position of each side of a window's roll, weighted by
        the side's length).  What the step writes (one ring row, a summary
        row every chunk_size steps) is under a thousandth and left out."""
        from distrifuser_tpu.models import evabyte as lm

        cfg, rewrite = self.lm_config, self.rewrite
        shapes = lm.param_shapes(cfg)
        weights = _leaf_count(shapes["layers"]) * itemsize
        head = (_leaf_count(shapes["head"]) + _leaf_count(shapes["final_norm"])
                + cfg.hidden_size) * itemsize
        start = rewrite.instruction_tokens + rewrite.user_tokens
        t = np.arange(start, start + rewrite.new_tokens)
        row = 2 * cfg.hidden_size * itemsize  # one position's K and V
        ring = float(np.mean(t % cfg.window_size + 1)) * row
        table = float(np.mean(t // cfg.window_size)) * (
            cfg.window_size // cfg.chunk_size) * row
        layers = cfg.num_hidden_layers
        return {"weights": weights, "ring": layers * ring,
                "summary_table": layers * table, "head_and_embedding": head,
                "total": weights + layers * (ring + table) + head}


def init_lm_on_device(cfg, key, dtype, mesh):
    """The language model's tree (`models.evabyte.param_shapes`), each leaf
    made on the mesh, replicated, in the served dtype, by the program's
    `init_leaf` rule for its name: one small jitted generator per distinct
    (name, shape), as `init_on_device` does for the diffusion trees."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from distrifuser_tpu.models import evabyte as lm

    replicated = NamedSharding(mesh, PartitionSpec())
    leaves, treedef = lm.named_leaves(cfg)
    keys = jax.device_put(jax.random.split(key, len(leaves)), replicated)

    @functools.lru_cache(maxsize=None)
    def generator(name, shape):
        return jax.jit(
            lambda ks, i: lm.init_leaf(ks[i], name, shape, cfg, dtype),
            out_shardings=replicated)

    return jax.tree_util.tree_unflatten(treedef, [
        generator(name, tuple(shape))(keys, i)
        for i, (name, shape) in enumerate(leaves)])
