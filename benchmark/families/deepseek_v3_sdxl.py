"""Family `deepseek_v3_sdxl`: a DeepSeek-V3-style language model (latent
attention, gated-SiLU routed and shared experts: Kanana-2-30B-A3B) as the
think-then-rewrite stage in front of SDXL, through `DistriSDXLPipeline` with
a `PromptRewriter` resident.

The image side is `unet_sdxl`'s, inherited: config objects, weights from the
seed, the UNet's analytic FLOPs (one row a step: the cell's sampler runs
without guidance).  Added here: the language model's configuration from the
published keys at the top level of the benchmark's configuration dict, its
weights made on the device leaf by leaf with the routers' selection bias
balanced as training leaves it - over the rewriter's own instruction, the
context every request of the cell is served in -, and the bytes one decode
step must read, for `kanana_decode_roofline`.

What the rewriter of the pipeline built last served stays reachable after
the server has stopped: `Family.rewriter` for the metric readers, and
`latest_served()` - its record of the last requests' served ids, logits and
routing, what `correct` compares, and nothing that holds weights - for the
reference, which is handed only weights and a request.
"""

import functools

from . import _common as F
from .nemotron_h_sdxl import _leaf_count
from .unet_sdxl import DENOISE_MODULES, PIPELINE_KIND, TABLE_STD  # noqa: F401
from .unet_sdxl import Family as SDXLFamily
from .unet_sdxl import unet_step_cost

REFERENCE = "deepseek_v3_sdxl"
# XLA module names of the rewrite stage's two request programs in the device
# trace (the third, `rewrite_prefix`, runs once, inside set-up)
PREFILL_MODULE, DECODE_MODULE = "rewrite_prefill", "rewrite_decode"
LM_STREAM = 7  # the seed's stream for the language model's weights

_LATEST = {"served": ()}


def latest_served():
    """The `ServedRewrite` records, oldest first, of the rewriter this
    process built last."""
    return _LATEST["served"]


class Family(SDXLFamily):
    def __init__(self, config: dict):
        from distrifuser_tpu.models import deepseek_v3 as lm
        from distrifuser_tpu.pipelines import RewriteSpec

        super().__init__(config)
        self.lm_config = lm.deepseek_v3_config_from_json(config)
        self.rewrite = RewriteSpec(**config["rewrite"])
        self.rewriter = None

    def init_weights(self, seed: int, dtype, mesh) -> dict:
        # the language model first: balancing its routers runs a prefill
        lm = init_lm_on_device(self.lm_config, F.seed_key(seed, LM_STREAM),
                               dtype, mesh, self.rewrite)
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

    def decode_step_bytes(self, held_per_token=None, itemsize=2) -> dict:
        """What one greedy decode step cannot avoid moving, from shapes:
        every layer's weights outside its routed experts once (attention,
        norms, router and bias, shared experts, the dense layer's MLP); of
        the routed experts those the token chose among the ones held here
        (``held_per_token`` a layer, the router's expectation top_k * held /
        width unless the run's counters give it); of the latent cache the
        rows 0 .. t read and one written, 576 numbers a row and layer, as a
        mean over the decoded positions t; the final norm, the head and one
        embedding row."""
        from distrifuser_tpu.models import deepseek_v3 as lm

        cfg, rewrite = self.lm_config, self.rewrite
        if held_per_token is None:
            held_per_token = (cfg.num_experts_per_tok * cfg.n_local_experts
                              / cfg.n_routed_experts)
        shapes = lm.param_shapes(cfg)
        weights = experts = 0
        for layer in shapes["layers"]:
            ffn = dict(layer["ffn"])
            if "experts" in ffn:
                one = _leaf_count(ffn.pop("experts")) / cfg.n_local_experts
                experts += held_per_token * one * itemsize
            weights += (_leaf_count(dict(layer, ffn=ffn))) * itemsize
        start = rewrite.instruction_tokens + rewrite.user_tokens
        # at position t the step writes row t, then reads rows 0 .. t
        rows = start + (rewrite.new_tokens - 1) / 2 + 1 + 1
        cache = (cfg.num_hidden_layers * rows * itemsize
                 * (cfg.kv_lora_rank + cfg.qk_rope_head_dim))
        head = (_leaf_count(shapes["head"]) + _leaf_count(shapes["final_norm"])
                + cfg.hidden_size) * itemsize
        return {"weights": weights, "routed_experts": experts,
                "latent_cache": cache, "head_and_embedding": head,
                "total": weights + experts + cache + head}


def init_lm_on_device(cfg, key, dtype, mesh, rewrite):
    """The language model's tree (`models.deepseek_v3.param_shapes`), each
    leaf made on the mesh, replicated, in the served dtype, by the program's
    `init_leaf` rule for its name: one small jitted generator per distinct
    (name, shape), as `init_on_device` does for the diffusion trees; then
    the routers balanced over the instruction of ``rewrite``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from distrifuser_tpu.models import deepseek_v3 as lm
    from distrifuser_tpu.pipelines import PromptRewriter

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
    # the routers' selection bias as load balancing leaves it.  The
    # calibration sequence is the rewriter's own instruction (its whole
    # prefill blocks: what the snapshot covers), not ids of the seed's own:
    # with an embedding of N(0, 0.02^2) the first layer's attention leaves
    # every position of a sequence a common part as large as its token's own,
    # so a router balanced on ANOTHER sequence loads this chip's 16 experts
    # 0.66-0.98 a token and layer from seed to seed (2048 random ids, nine
    # seeds on the chip, PR 34) where a trained router's long-run load is
    # top_k * held / width = 0.75 - and a decode step's time follows the load
    ids = PromptRewriter(cfg, None, rewrite, ()).instruction
    ids = ids[:len(ids) // cfg.prefill_block * cfg.prefill_block or None]
    biases = iter(lm.balanced_selection_bias(params, cfg, ids))
    for layer in params["layers"]:
        if "router" in layer["ffn"]:
            layer["ffn"]["e_score_correction_bias"] = next(biases)
    return params
