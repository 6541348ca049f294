"""Family `dit_pixart`: PixArt-alpha through `DistriPixArtPipeline`.

Same duties as `unet_sdxl`: config objects from the published keys, weights
on the device from the seed, a pipeline for the serve plane, and the analytic
work of one guided DiT step.
"""

from . import _common as F

REFERENCE = "dit_pixart"
PIPELINE_KIND = "DistriPixArtPipeline"
DENOISE_MODULES = ("loop",)


class Family:
    def __init__(self, config: dict):
        from distrifuser_tpu.models import dit as dit_mod
        from distrifuser_tpu.models import t5 as t5_mod
        from distrifuser_tpu.models import vae as vae_mod

        self.config = config
        self.dit_config = dit_mod.dit_config_from_json(config["transformer"])
        self.vae_config = vae_mod.vae_config_from_json(config["vae"])
        self.t5_config = t5_mod.t5_config_from_json(config["text_encoder"])
        self.in_channels = self.dit_config.in_channels

    def init_weights(self, seed: int, dtype, mesh) -> dict:
        from distrifuser_tpu.models import dit as dit_mod
        from distrifuser_tpu.models import t5 as t5_mod
        from distrifuser_tpu.models import vae as vae_mod

        hidden = self.dit_config.hidden_size
        t5 = self.t5_config
        # T5 folds attention's 1/sqrt(d_kv) into the query initialiser
        # (Mesh-TF / transformers `_init_weights`): with 1/sqrt(fan_in)
        # there instead, logits have a deviation of 8, the softmax is all
        # but one-hot and a bf16 encoder is 0.6 away from a float32 one
        # (my chip run, PR 23)
        tables = {"shared": 1.0,
                  "relative_attention_bias": t5.d_model ** -0.5,
                  "attn/q/kernel": (t5.d_model * t5.d_kv) ** -0.5,
                  "scale_shift_table": hidden ** -0.5,
                  "final_table": hidden ** -0.5}

        def init(fn, cfg, stream):
            return F.init_on_device(lambda k: fn(k, cfg), F.seed_key(seed, stream),
                                    dtype, mesh, tables)

        return {
            "dit": init(dit_mod.init_dit_params, self.dit_config, 0),
            "vae": init(vae_mod.init_vae_params, self.vae_config, 1),
            "t5": init(t5_mod.init_t5_params, self.t5_config, 2),
        }

    def build_pipeline(self, distri_config, weights, scheduler):
        from distrifuser_tpu.pipelines import DistriPixArtPipeline
        from distrifuser_tpu.schedulers import get_scheduler

        sched = get_scheduler(scheduler, **F.scheduler_kwargs(self.config))
        return DistriPixArtPipeline.from_params(
            distri_config, self.dit_config, weights["dit"], self.vae_config,
            weights["vae"], self.t5_config, weights["t5"], scheduler=sched)

    def step_cost(self, height: int, width: int, cfg_rows: int = 2) -> dict:
        t = self.config["transformer"]
        ps = t["patch_size"]
        n = (height // 8 // ps) * (width // 8 // ps)
        heads, d = t["num_attention_heads"], t["attention_head_dim"]
        c = heads * d
        rows, depth = cfg_rows, t["num_layers"]
        text = self.config["tokenizer"]["model_max_length"]
        per = 2 * rows * n * c * c * 4            # self q, kv, out
        per += 2 * rows * n * c * c * 2           # cross q, out
        per += 2 * rows * n * c * 4 * c * 2       # MLP
        sa, _ = F.attention_cost(rows, n, n, heads, d)
        ca, _ = F.attention_cost(rows, n, text, heads, d)
        flops = depth * (per + sa + ca)
        flops += 2 * rows * n * (ps * ps * t["in_channels"]) * c * 2  # in, out
        return {"flops": flops,
                "self_attention": [(depth, rows, n, n, heads, d)]}
