"""Family `unet_sdxl`: SDXL through `DistriSDXLPipeline`.

Builds the program's config objects from the benchmark's configuration dict
(the published config.json keys), makes the weights on the device from the
seed, hands a pipeline to the serve plane, and keeps the analytic FLOP /
byte arithmetic for the UNet step.
"""

from . import _common as F

REFERENCE = "unet_sdxl"
PIPELINE_KIND = "DistriSDXLPipeline"
# XLA module names (jit(<fn>)) of the denoise programs in the device trace
DENOISE_MODULES = ("loop",)
# embedding tables: CLIP's initialiser ranges
TABLE_STD = {"token_embedding": 0.02, "position_embedding": 0.01}


class Family:
    def __init__(self, config: dict):
        from distrifuser_tpu.models import clip as clip_mod
        from distrifuser_tpu.models import unet as unet_mod
        from distrifuser_tpu.models import vae as vae_mod

        self.config = config
        self.unet_config = unet_mod.unet_config_from_json(config["unet"])
        self.vae_config = vae_mod.vae_config_from_json(config["vae"])
        self.text_configs = [
            clip_mod.clip_config_from_json(config[k])
            for k in ("text_encoder", "text_encoder_2")]
        self.in_channels = self.unet_config.in_channels

    def init_weights(self, seed: int, dtype, mesh) -> dict:
        from distrifuser_tpu.models import clip as clip_mod
        from distrifuser_tpu.models import unet as unet_mod
        from distrifuser_tpu.models import vae as vae_mod

        def init(fn, cfg, stream):
            return F.init_on_device(lambda k: fn(k, cfg), F.seed_key(seed, stream),
                                    dtype, mesh, TABLE_STD)

        return {
            "unet": init(unet_mod.init_unet_params, self.unet_config, 0),
            "vae": init(vae_mod.init_vae_params, self.vae_config, 1),
            "text": [init(clip_mod.init_clip_params, tc, 2 + i)
                     for i, tc in enumerate(self.text_configs)],
        }

    def build_pipeline(self, distri_config, weights, scheduler):
        from distrifuser_tpu.pipelines import DistriSDXLPipeline
        from distrifuser_tpu.schedulers import get_scheduler

        sched = get_scheduler(scheduler, **F.scheduler_kwargs(self.config))
        return DistriSDXLPipeline.from_params(
            distri_config, self.unet_config, weights["unet"], self.vae_config,
            weights["vae"], self.text_configs, weights["text"], scheduler=sched)

    # -- analytic work per denoise step (CFG folded: two UNet rows) ---------

    def step_cost(self, height: int, width: int, cfg_rows: int = 2) -> dict:
        """FLOPs of one guided step, and the attention calls in it as
        (count, batch, Lq, Lk, heads, head_dim)."""
        u = self.config["unet"]
        return unet_step_cost(u, height // 8, width // 8, cfg_rows,
                              text_len=self.config["tokenizer"]["model_max_length"])


def unet_step_cost(u, lat_h, lat_w, rows, text_len):
    """Walk UNet2DConditionModel on shapes: 2*MACs of every conv and linear
    plus the attention matmuls.  Norms, activations and the scheduler are
    left out (under 1% at SDXL's widths)."""
    ch = u["block_out_channels"]
    heads = u["attention_head_dim"]
    tl = u["transformer_layers_per_block"]
    cross = u["cross_attention_dim"]
    temb = ch[0] * 4
    flops = 0
    attn = []  # (count, batch, lq, lk, heads, head_dim)

    def conv(h, w, cin, cout, k=3):
        return 2 * rows * h * w * cin * cout * k * k

    def resnet(h, w, cin, cout):
        f = conv(h, w, cin, cout) + conv(h, w, cout, cout)
        f += 2 * rows * temb * cout
        if cin != cout:
            f += conv(h, w, cin, cout, 1)
        return f

    def transformer(h, w, c, n_heads, layers):
        n = h * w
        f = 2 * (2 * rows * n * c * c)  # proj_in, proj_out
        per = 2 * rows * n * c * c * 4  # attn1 q, kv (2c), out
        per += 2 * rows * n * c * c * 2  # cross q, out (text K/V: once per image)
        per += 2 * rows * n * c * 8 * c + 2 * rows * n * 4 * c * c  # GEGLU ff
        d = c // n_heads
        sa, _ = F.attention_cost(rows, n, n, n_heads, d)
        ca, _ = F.attention_cost(rows, n, text_len, n_heads, d)
        attn.append((layers, rows, n, n, n_heads, d))
        return f + layers * (per + sa + ca)

    h, w = lat_h, lat_w
    flops += conv(h, w, u["in_channels"], ch[0])
    flops += 2 * rows * (ch[0] * temb + temb * temb)
    flops += 2 * rows * (u["projection_class_embeddings_input_dim"] * temb
                         + temb * temb)
    skips = [ch[0]]
    c = ch[0]
    n_down = len(ch)
    for i, btype in enumerate(u["down_block_types"]):
        for _ in range(u["layers_per_block"]):
            flops += resnet(h, w, c, ch[i])
            c = ch[i]
            if btype == "CrossAttnDownBlock2D":
                flops += transformer(h, w, c, heads[i], tl[i])
            skips.append(c)
        if i < n_down - 1:
            h, w = h // 2, w // 2
            flops += conv(h, w, c, c)
            skips.append(c)
    flops += 2 * resnet(h, w, c, c) + transformer(h, w, c, heads[-1], tl[-1])
    for i, btype in enumerate(u["up_block_types"]):
        out = ch[n_down - 1 - i]
        for _ in range(u["layers_per_block"] + 1):
            flops += resnet(h, w, c + skips.pop(), out)
            c = out
            if btype == "CrossAttnUpBlock2D":
                flops += transformer(h, w, c, heads[n_down - 1 - i],
                                     tl[n_down - 1 - i])
        if i < n_down - 1:
            h, w = h * 2, w * 2
            flops += conv(h, w, c, c)
    flops += conv(h, w, c, u["out_channels"])
    return {"flops": flops, "self_attention": attn}
