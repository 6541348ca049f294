"""Family `kimi_linear_sdxl`: a Kimi-Linear language model (delta-rule linear
attention with a per-channel gate, three such layers to one NoPE
latent-attention layer, gated-SiLU routed and shared experts:
Kimi-Linear-48B-A3B) as the think-then-rewrite stage in front of SDXL,
through `DistriSDXLPipeline` with a `PromptRewriter` resident.

The image side is `unet_sdxl`'s, inherited: config objects, weights from the
seed, the UNet's analytic FLOPs (one row a step: the cell's sampler runs
without guidance).  Added here: the language model's configuration from the
published keys at the top level of the benchmark's configuration dict, its
weights made on the device leaf by leaf with the routers' selection bias
balanced as training leaves it - over the rewriter's own instruction, the
context every request of the cell is served in -, and the bytes one decode
step must move, for `kimi_decode_roofline`.

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

REFERENCE = "kimi_linear_sdxl"


class Family(LatentFamily):
    """`families/deepseek_v3_sdxl.py Family` - its pipeline with the
    rewriter resident, its record of what was served (`latest_served`), the
    UNet's one-row step cost - with this language model's configuration,
    weights and decode-step bytes."""

    def __init__(self, config: dict):
        from distrifuser_tpu.models import kimi_linear as lm
        from distrifuser_tpu.pipelines import RewriteSpec

        SDXLFamily.__init__(self, config)
        self.lm_config = lm.kimi_linear_config_from_json(config)
        self.rewrite = RewriteSpec(**config["rewrite"])
        self.rewriter = None

    def init_weights(self, seed: int, dtype, mesh) -> dict:
        # the language model first: balancing its routers runs a prefill
        lm = init_lm_on_device(self.lm_config, F.seed_key(seed, LM_STREAM),
                               dtype, mesh, self.rewrite)
        return dict(SDXLFamily.init_weights(self, seed, dtype, mesh), lm=lm)

    def decode_step_bytes(self, held_per_token=None, itemsize=2) -> dict:
        """What one greedy decode step cannot avoid moving, from shapes:
        every layer's weights outside its routed experts once (both kinds
        of mixer, norms, router and bias, shared expert, the dense layer's
        MLP); of the routed experts those the token chose among the ones
        held here (``held_per_token`` a layer, the router's expectation
        top_k * held / width unless the run's record gives it); every KDA
        layer's matrix state read once and written once in its own dtype,
        and its convolution's tail likewise; of the latent caches the rows
        0 .. t read and one written, 576 numbers a row and full layer, as a
        mean over the decoded positions t; the final norm, the head and one
        embedding row."""
        import numpy as np

        from distrifuser_tpu.models import kimi_linear as lm

        cfg, rewrite = self.lm_config, self.rewrite
        if held_per_token is None:
            held_per_token = (cfg.num_experts_per_token * cfg.n_local_experts
                              / cfg.num_experts)
        shapes = lm.param_shapes(cfg)
        weights = experts = 0
        for layer in shapes["layers"]:
            ffn = dict(layer["ffn"])
            if "experts" in ffn:
                one = _leaf_count(ffn.pop("experts")) / cfg.n_local_experts
                experts += held_per_token * one * itemsize
            weights += (_leaf_count(dict(layer, ffn=ffn))) * itemsize
        n_kda = cfg.kinds.count("kda")
        wide = cfg.kda_num_heads * cfg.kda_head_dim
        state = 2 * n_kda * (
            wide * cfg.kda_head_dim * np.dtype(cfg.state_dtype).itemsize
            + (cfg.short_conv_kernel_size - 1) * 3 * wide * itemsize)
        start = rewrite.instruction_tokens + rewrite.user_tokens
        # at position t the step writes row t, then reads rows 0 .. t
        rows = start + (rewrite.new_tokens - 1) / 2 + 1 + 1
        cache = (cfg.kinds.count("mla") * rows * itemsize
                 * (cfg.kv_lora_rank + cfg.qk_rope_head_dim))
        head = (_leaf_count(shapes["head"]) + _leaf_count(shapes["final_norm"])
                + cfg.hidden_size) * itemsize
        return {"weights": weights, "routed_experts": experts,
                "kda_state": state, "latent_cache": cache,
                "head_and_embedding": head,
                "total": weights + experts + state + cache + head}


def init_lm_on_device(cfg, key, dtype, mesh, rewrite):
    """The language model's tree (`models.kimi_linear.param_shapes`), each
    leaf made on the mesh, replicated, in the served dtype, by the program's
    `init_leaf` rule for its name: one small jitted generator per distinct
    (name, shape), as `init_on_device` does for the diffusion trees; then
    the routers balanced over the instruction of ``rewrite`` (its whole
    prefill blocks, what the snapshot covers: `families/deepseek_v3_sdxl.py
    init_lm_on_device` has why it is that sequence)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from distrifuser_tpu.models import kimi_linear as lm
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
    ids = PromptRewriter(cfg, None, rewrite, ()).instruction
    ids = ids[:len(ids) // cfg.prefill_block * cfg.prefill_block or None]
    biases = iter(lm.balanced_selection_bias(params, cfg, ids))
    for layer in params["layers"]:
        if "router" in layer["ffn"]:
            layer["ffn"]["e_score_correction_bias"] = next(biases)
    return params
