"""Family `lfm2_sdxl`: an LFM2 language model (a gated short convolution as
the token mixer in three layers of four, grouped-query attention with heads
of 64 in the fourth, sigmoid-routed gated-SiLU experts behind two dense
layers, a head tied to the embedding: LFM2-24B-A2B) as the
think-then-rewrite stage in front of SDXL, through `DistriSDXLPipeline` with
a `PromptRewriter` resident.

The image side is `unet_sdxl`'s, inherited: config objects, weights from the
seed, the UNet's analytic FLOPs (one row a step: the cell's sampler runs
without guidance).  Added here: the language model's configuration from the
published keys at the top level of the benchmark's configuration dict, its
weights made on the device leaf by leaf with every router share-symmetric
(`init_lm_on_device`: each token loads this chip with exactly one expert a
layer, whatever the seed), and the bytes one decode step must move, for
`lfm2_decode_roofline`.

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

REFERENCE = "lfm2_sdxl"
LANES = 128  # a row of a cache in HBM is whole tiles of this many lanes


class Family(LatentFamily):
    """`families/deepseek_v3_sdxl.py Family` - its pipeline with the
    rewriter resident, its record of what was served (`latest_served`), the
    UNet's one-row step cost - with this language model's configuration,
    weights and decode-step bytes."""

    def __init__(self, config: dict):
        from distrifuser_tpu.models import lfm2 as lm
        from distrifuser_tpu.pipelines import RewriteSpec

        SDXLFamily.__init__(self, config)
        self.lm_config = lm.lfm2_config_from_json(config)
        self.rewrite = RewriteSpec(**config["rewrite"])
        self.rewriter = None

    def init_weights(self, seed: int, dtype, mesh) -> dict:
        lm = init_lm_on_device(self.lm_config, F.seed_key(seed, LM_STREAM),
                               dtype, mesh)
        return dict(SDXLFamily.init_weights(self, seed, dtype, mesh), lm=lm)

    def decode_step_bytes(self, held_per_token=None, itemsize=2) -> dict:
        """What one greedy decode step cannot avoid moving, from shapes:
        every layer's weights outside its routed experts once (both kinds
        of mixer, norms, router and bias, the two dense layers' MLPs); of
        the routed experts those the token chose among the ones held here
        (``held_per_token`` a layer, the router's expectation top_k * held /
        width unless the run's record gives it); every conv layer's tail
        read once and written once; of the KV caches the rows 0 .. t read
        and one written an attention layer, keys and values, AT THE BYTES A
        ROW IS HELD WITH (`kv_pack` heads a row, to whole tiles of 128
        lanes), as a mean over the decoded positions t; the final norm, the
        tied matrix once as the head, and one embedding row."""
        from distrifuser_tpu.models import lfm2 as lm

        cfg, rewrite = self.lm_config, self.rewrite
        if held_per_token is None:
            held_per_token = (cfg.num_experts_per_tok * cfg.n_local_experts
                              / cfg.num_experts)
        shapes = lm.param_shapes(cfg)
        weights = experts = 0
        for layer in shapes["layers"]:
            ffn = dict(layer["ffn"])
            if "experts" in ffn:
                one = _leaf_count(ffn.pop("experts")) / cfg.n_local_experts
                experts += held_per_token * one * itemsize
            weights += (_leaf_count(dict(layer, ffn=ffn))) * itemsize
        tails = (2 * cfg.kinds.count("conv") * (cfg.conv_L_cache - 1)
                 * cfg.hidden_size * itemsize)
        start = rewrite.instruction_tokens + rewrite.user_tokens
        # at position t the step writes row t, then reads rows 0 .. t
        rows = start + (rewrite.new_tokens - 1) / 2 + 1 + 1
        row_bytes = (cfg.num_key_value_heads // cfg.kv_pack * itemsize
                     * -(-cfg.kv_pack * cfg.head_dim // LANES) * LANES)
        cache = cfg.kinds.count("full_attention") * rows * 2 * row_bytes
        head = (_leaf_count(shapes["embed"]) + _leaf_count(
            shapes["final_norm"]) + cfg.hidden_size) * itemsize
        return {"weights": weights, "routed_experts": experts,
                "conv_tails": tails, "kv_cache": cache,
                "head_and_embedding": head,
                "total": weights + experts + tails + cache + head}


def init_lm_on_device(cfg, key, dtype, mesh):
    """The language model's tree (`models.lfm2.param_shapes`), each leaf
    made on the mesh, replicated, in the served dtype, by the program's
    `init_leaf` rule for its name: one small jitted generator per distinct
    (name, shape), as `init_on_device` does for the diffusion trees; then
    every router made SHARE-SYMMETRIC (`families/sdar_sdxl.py
    share_symmetric_router`: one seeded block of ``n_local_experts`` columns
    repeated for every share, and the selection bias with it): the router's
    ``top_k`` is its width over the experts held, so a token's experts are
    the best direction's one a share and every token loads this chip with
    exactly one assignment a layer, whatever the seed.  The sibling sigmoid
    routers are balanced by fitting the bias over the instruction; here
    that held the load over the PROMPT at 1.00 and left the decoded tokens'
    - which reach a router nearly as one vector - at 0.93-1.07 from seed to
    seed, and the experts are a quarter of a step's bytes: `image_s` spread
    by 1.7% over six seeds where the cell may spread by half a percent (my
    chip runs, PR 45)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from distrifuser_tpu.models import lfm2 as lm

    from .sdar_sdxl import share_symmetric_router

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
    held, shares = cfg.n_local_experts, cfg.num_experts // cfg.n_local_experts
    if cfg.num_experts_per_tok != shares:
        raise ValueError("a share-symmetric router chooses one expert a "
                         "share: top_k = num_experts / held")
    symmetric = jax.jit(
        lambda kernel, bias: (share_symmetric_router(kernel, held),
                              jnp.tile(bias[:held], shares)),
        out_shardings=replicated)
    for layer in params["layers"]:
        ffn = layer["ffn"]
        if "router" in ffn:
            ffn["router"]["kernel"], ffn["expert_bias"] = symmetric(
                ffn["router"]["kernel"], ffn["expert_bias"])
    return params
