"""Family `nemotron_h_sdxl`: a Nemotron-H language model as the
think-then-rewrite stage in front of SDXL, through `DistriSDXLPipeline` with
a `PromptRewriter` resident.

The image side is `unet_sdxl`'s, inherited: config objects, weights from the
seed, the UNet's analytic FLOPs (one row a step: the cell's sampler runs
without guidance).  Added here: the language model's configuration from the
published keys at the top level of the benchmark's configuration dict, its
weights made on the device leaf by leaf with the routers' selection bias
balanced as training leaves it, and the bytes one decode step must read, for
`lm_decode_roofline`.

What the rewriter of the pipeline built last served stays reachable after
the server has stopped: `Family.rewriter` for the metric readers, and
`latest_served()` - its record of the last requests' served ids, logits and
routing, what `correct` compares, and nothing that holds weights - for the
reference, which is handed only weights and a request.
"""

import functools

from . import _common as F
from .unet_sdxl import DENOISE_MODULES, PIPELINE_KIND, TABLE_STD  # noqa: F401
from .unet_sdxl import Family as SDXLFamily
from .unet_sdxl import unet_step_cost

REFERENCE = "nemotron_h_sdxl"
# XLA module names of the rewrite stage's two programs in the device trace
PREFILL_MODULE, DECODE_MODULE = "rewrite_prefill", "rewrite_decode"
LM_STREAM = 7  # the seed's stream for the language model's weights
BALANCE_TOKENS = 2048  # the calibration sequence of the routers' balancing

_LATEST = {"served": ()}


def latest_served():
    """The `ServedRewrite` records, oldest first, of the rewriter this
    process built last."""
    return _LATEST["served"]


class Family(SDXLFamily):
    def __init__(self, config: dict):
        from distrifuser_tpu.models import nemotron_h as lm
        from distrifuser_tpu.pipelines import RewriteSpec

        super().__init__(config)
        self.lm_config = lm.nemotron_h_config_from_json(config)
        self.rewrite = RewriteSpec(**config["rewrite"])
        self.rewriter = None

    def init_weights(self, seed: int, dtype, mesh) -> dict:
        # the language model first: balancing its routers runs a prefill
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

    def decode_step_bytes(self, held_per_token=None, itemsize=2) -> dict:
        """What one greedy decode step cannot avoid moving, from shapes:
        every mixer's weights once (of an E layer's experts only the ones
        the token chose among those held: ``held_per_token`` a layer, the
        router's expectation top_k * held / width unless the run's counters
        give it), each M layer's float32 state read and written, the KV
        cache as far as the middle decoded position, one embedding row and
        the head."""
        from distrifuser_tpu.models import nemotron_h as lm

        cfg, rewrite = self.lm_config, self.rewrite
        if held_per_token is None:
            held_per_token = (cfg.num_experts_per_tok * cfg.n_local_experts
                              / cfg.n_routed_experts)
        weights = state = cache = 0
        prompt = rewrite.instruction_tokens + rewrite.user_tokens
        for kind, layer in zip(cfg.pattern, lm.param_shapes(cfg)["layers"]):
            mixer = dict(layer["mixer"])
            if kind == "E":
                one = _leaf_count(mixer.pop("experts")) / cfg.n_local_experts
                weights += held_per_token * one * itemsize
            weights += (_leaf_count(mixer)
                        + _leaf_count(layer["norm"])) * itemsize
            if kind == "M":
                state += 2 * 4 * (cfg.mamba_num_heads * cfg.mamba_head_dim
                                  * cfg.ssm_state_size)
            if kind == "*":
                cache += (2 * itemsize * cfg.num_key_value_heads
                          * cfg.head_dim * (prompt + rewrite.new_tokens / 2))
        head = (cfg.hidden_size * cfg.vocab_size
                + 2 * cfg.hidden_size) * itemsize
        return {"weights": weights, "state": state, "kv_cache": cache,
                "head_and_embedding": head,
                "total": weights + state + cache + head}


def init_lm_on_device(cfg, key, dtype, mesh):
    """The language model's tree (`models.nemotron_h.param_shapes`), each
    leaf made on the mesh, replicated, in the served dtype, by the program's
    `init_leaf` rule for its name: one small jitted generator per distinct
    (name, shape), as `init_on_device` does for the diffusion trees."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from distrifuser_tpu.models import nemotron_h as lm

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
    # the routers' selection bias as load balancing leaves it, from a
    # calibration sequence of the seed's own: a random router would load
    # this chip's experts by +-4% from seed to seed, a trained one does not
    ids = jax.random.randint(jax.random.fold_in(key, len(leaves)),
                             (BALANCE_TOKENS,), 0, cfg.vocab_size)
    biases = iter(lm.balanced_selection_bias(params, cfg, ids))
    for kind, layer in zip(cfg.pattern, params["layers"]):
        if kind == "E":
            layer["mixer"]["e_score_correction_bias"] = next(biases)
    return params


def _leaf_count(tree) -> int:
    import math

    if isinstance(tree, tuple):
        return math.prod(tree)
    if isinstance(tree, dict):
        tree = tree.values()
    return sum(_leaf_count(v) for v in tree)
