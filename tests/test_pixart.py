"""PixArt model path: converter, caption masking, micro-conditioning,
pipeline.

The real checkpoints cannot live on this box (zero egress), so the proof
layers are: (1) numerical equivalence of the two nontrivial converter moves
(patch-embed conv -> linear, learned-sigma head slice) against torch/numpy
references; (2) a full synthetic diffusers-format state dict flowing through
convert_pixart_state_dict into a working forward; (3) exactness oracles for
the caption mask (== truncation) and the size-condition fold (== explicit
add); (4) the DistriPixArtPipeline surface end-to-end on tiny models,
including from_pretrained over a synthetic snapshot directory.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distrifuser_tpu import DistriConfig
from distrifuser_tpu.models import dit as dit_mod
from distrifuser_tpu.models import t5 as t5_mod
from distrifuser_tpu.models.vae import init_vae_params, tiny_vae_config
from distrifuser_tpu.models.weights import convert_pixart_state_dict
from distrifuser_tpu.pipelines import DistriPixArtPipeline
from distrifuser_tpu.schedulers import get_scheduler

torch = pytest.importorskip("torch")


PIXART_JSON = {
    "num_attention_heads": 4, "attention_head_dim": 16, "num_layers": 2,
    "in_channels": 4, "out_channels": 8, "patch_size": 2, "sample_size": 16,
    "caption_channels": 32,
}


def synthetic_pixart_sd(seed=0, depth=2, hidden=64, cap=32, ps=2, in_ch=4):
    """Random state dict in the diffusers PixArtTransformer2DModel layout."""
    r = np.random.RandomState(seed)
    f32 = lambda *s: (r.randn(*s) * 0.05).astype(np.float32)
    sd = {
        "pos_embed.proj.weight": f32(hidden, in_ch, ps, ps),
        "pos_embed.proj.bias": f32(hidden),
        "adaln_single.emb.timestep_embedder.linear_1.weight": f32(hidden, 256),
        "adaln_single.emb.timestep_embedder.linear_1.bias": f32(hidden),
        "adaln_single.emb.timestep_embedder.linear_2.weight": f32(hidden, hidden),
        "adaln_single.emb.timestep_embedder.linear_2.bias": f32(hidden),
        "adaln_single.linear.weight": f32(6 * hidden, hidden),
        "adaln_single.linear.bias": f32(6 * hidden),
        "caption_projection.linear_1.weight": f32(hidden, cap),
        "caption_projection.linear_1.bias": f32(hidden),
        "caption_projection.linear_2.weight": f32(hidden, hidden),
        "caption_projection.linear_2.bias": f32(hidden),
        "scale_shift_table": f32(2, hidden),
        "proj_out.weight": f32(ps * ps * 2 * in_ch, hidden),
        "proj_out.bias": f32(ps * ps * 2 * in_ch),
    }
    for i in range(depth):
        b = f"transformer_blocks.{i}"
        sd[f"{b}.scale_shift_table"] = f32(6, hidden)
        for attn in ("attn1", "attn2"):
            for proj in ("to_q", "to_k", "to_v"):
                sd[f"{b}.{attn}.{proj}.weight"] = f32(hidden, hidden)
                sd[f"{b}.{attn}.{proj}.bias"] = f32(hidden)
            sd[f"{b}.{attn}.to_out.0.weight"] = f32(hidden, hidden)
            sd[f"{b}.{attn}.to_out.0.bias"] = f32(hidden)
        sd[f"{b}.ff.net.0.proj.weight"] = f32(4 * hidden, hidden)
        sd[f"{b}.ff.net.0.proj.bias"] = f32(4 * hidden)
        sd[f"{b}.ff.net.2.weight"] = f32(hidden, 4 * hidden)
        sd[f"{b}.ff.net.2.bias"] = f32(hidden)
    return sd


def test_patch_embed_conv_equivalence():
    """Converted proj_in linear over patchify == the original strided conv."""
    sd = synthetic_pixart_sd()
    cfg = dit_mod.dit_config_from_json(PIXART_JSON)
    params = convert_pixart_state_dict(sd)
    x = np.random.RandomState(1).randn(2, 16, 16, 4).astype(np.float32)

    from distrifuser_tpu.ops.linear import linear

    ours = np.asarray(linear(params["proj_in"], dit_mod.patchify(cfg, jnp.asarray(x))))

    with torch.no_grad():
        ref = torch.nn.functional.conv2d(
            torch.tensor(x).permute(0, 3, 1, 2),
            torch.tensor(sd["pos_embed.proj.weight"]),
            torch.tensor(sd["pos_embed.proj.bias"]),
            stride=2,
        )  # [B, hidden, 8, 8]
    ref = ref.permute(0, 2, 3, 1).reshape(2, 64, 64).numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-5, atol=1e-5)


def test_learned_sigma_slice_equivalence():
    """Converted final_out == diffusers proj_out + unpatchify + eps slice."""
    sd = synthetic_pixart_sd()
    cfg = dit_mod.dit_config_from_json(PIXART_JSON)
    params = convert_pixart_state_dict(sd)
    h = np.random.RandomState(2).randn(1, 64, 64).astype(np.float32)

    from distrifuser_tpu.ops.linear import linear

    tokens = np.asarray(linear(params["final_out"], jnp.asarray(h)))
    ours = np.asarray(dit_mod.unpatchify(cfg, jnp.asarray(tokens), 4))

    # diffusers path: full 2C head, nhwpqc->nchpwq unpatchify, keep eps rows
    full = h @ sd["proj_out.weight"].T + sd["proj_out.bias"]  # [1, 64, 32]
    full = full.reshape(1, 8, 8, 2, 2, 8)
    ref = np.einsum("nhwpqc->nchpwq", full).reshape(1, 8, 16, 16)[:, :4]
    np.testing.assert_allclose(ours, ref.transpose(0, 2, 3, 1), rtol=1e-5, atol=1e-5)


def test_converted_forward_runs():
    sd = synthetic_pixart_sd()
    cfg = dit_mod.dit_config_from_json(PIXART_JSON)
    assert cfg.caption_dim == 32 and cfg.mlp_ratio == 4
    assert not cfg.use_additional_conditions  # sample_size 16 != 128
    params = convert_pixart_state_dict(sd)
    x = jnp.ones((1, 16, 16, 4))
    enc = jnp.ones((1, 9, 32))
    out = dit_mod.dit_forward(params, cfg, x, jnp.asarray(500.0), enc)
    assert out.shape == (1, 16, 16, 4)
    assert np.isfinite(np.asarray(out)).all()


def test_caption_mask_equals_truncation():
    """Masking padded caption tokens == feeding only the real tokens."""
    cfg = dit_mod.tiny_dit_config(depth=4)
    params = dit_mod.init_dit_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 4))
    enc = jax.random.normal(jax.random.PRNGKey(2), (2, 12, cfg.caption_dim))
    mask = jnp.concatenate([jnp.ones((2, 7)), jnp.zeros((2, 5))], axis=1)
    t = jnp.asarray(300.0)

    masked = dit_mod.dit_forward(params, cfg, x, t, enc, cap_mask=mask)
    truncated = dit_mod.dit_forward(params, cfg, x, t, enc[:, :7])
    np.testing.assert_allclose(
        np.asarray(masked), np.asarray(truncated), rtol=2e-5, atol=2e-5
    )


def test_runner_caption_mask_equals_truncation():
    """The displaced runner honors cap_mask (same oracle, 4-dev mesh)."""
    from distrifuser_tpu.parallel.dit_sp import DiTDenoiseRunner

    dcfg = dit_mod.tiny_dit_config(depth=4)
    params = dit_mod.init_dit_params(jax.random.PRNGKey(0), dcfg)
    cfg = DistriConfig(
        devices=jax.devices()[:4], height=128, width=128, warmup_steps=1,
        do_classifier_free_guidance=False, split_batch=False, dtype=jnp.float32,
    )
    lat = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 16, 4))
    enc = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 12, dcfg.caption_dim))
    mask = jnp.concatenate([jnp.ones((1, 1, 8)), jnp.zeros((1, 1, 4))], axis=2)

    r1 = DiTDenoiseRunner(cfg, dcfg, params, get_scheduler("ddim"))
    out_masked = r1.generate(lat, enc, guidance_scale=1.0,
                             num_inference_steps=3, cap_mask=mask)
    r2 = DiTDenoiseRunner(cfg, dcfg, params, get_scheduler("ddim"))
    out_trunc = r2.generate(lat, enc[:, :, :8], guidance_scale=1.0,
                            num_inference_steps=3)
    np.testing.assert_allclose(
        np.asarray(out_masked), np.asarray(out_trunc), rtol=2e-5, atol=2e-5
    )


def test_fold_size_condition_exact():
    """Folding the micro-conditioning into t_fc2.bias == explicit addition."""
    cfg = dit_mod.DiTConfig(
        sample_size=16, patch_size=2, hidden_size=66, depth=2, num_heads=6,
        mlp_ratio=2, caption_dim=32, use_additional_conditions=True,
    )
    params = dit_mod.init_dit_params(jax.random.PRNGKey(0), cfg)
    folded = dit_mod.fold_size_condition(params, cfg, 1024.0, 1024.0)
    t = jnp.asarray(123.0)
    explicit = dit_mod.t_embed(params, cfg, t) + dit_mod.size_condition_embed(
        params, cfg, 1024.0, 1024.0
    )
    np.testing.assert_allclose(
        np.asarray(dit_mod.t_embed(folded, cfg, t)), np.asarray(explicit),
        rtol=1e-6, atol=1e-6,
    )
    # flag off or embedders absent -> identity
    cfg_off = dit_mod.tiny_dit_config()
    p_off = dit_mod.init_dit_params(jax.random.PRNGKey(1), cfg_off)
    assert dit_mod.fold_size_condition(p_off, cfg_off, 128.0, 128.0) is p_off


def _tiny_pixart_stack(n_dev, parallelism="patch"):
    dcfg = dit_mod.tiny_dit_config(depth=4)
    t5cfg = t5_mod.tiny_t5_config()
    # caption width must match the t5 d_model for the real-encoder path
    dcfg = dit_mod.DiTConfig(
        sample_size=16, patch_size=2, hidden_size=64, depth=4, num_heads=4,
        mlp_ratio=2, caption_dim=t5cfg.d_model,
    )
    cfg = DistriConfig(
        devices=jax.devices()[:n_dev], height=128, width=128, warmup_steps=1,
        parallelism=parallelism, dtype=jnp.float32,
    )
    vcfg = tiny_vae_config()
    pipe = DistriPixArtPipeline.from_params(
        cfg, dcfg,
        dit_mod.init_dit_params(jax.random.PRNGKey(0), dcfg),
        vcfg, init_vae_params(jax.random.PRNGKey(1), vcfg),
        t5_config=t5cfg,
        t5_params=t5_mod.init_t5_params(jax.random.PRNGKey(2), t5cfg),
    )
    return pipe, cfg


@pytest.mark.parametrize("parallelism", ["patch", "pipefusion"])
def test_pixart_pipeline_generates(parallelism):
    pipe, cfg = _tiny_pixart_stack(4, parallelism)
    out = pipe(prompt="a tpu etching an image", num_inference_steps=3,
               guidance_scale=3.0, output_type="np")
    assert len(out.images) == 1
    # tiny VAE has 2 levels -> 2x upsample of the 16x16 latent
    assert out.images[0].shape == (32, 32, 3)
    assert np.isfinite(out.images[0]).all()


def test_pixart_pipeline_latent_repeatable():
    pipe, cfg = _tiny_pixart_stack(4)
    a = pipe(prompt="x", num_inference_steps=2, output_type="latent", seed=7)
    b = pipe(prompt="x", num_inference_steps=2, output_type="latent", seed=7)
    np.testing.assert_array_equal(np.asarray(a.images[0]), np.asarray(b.images[0]))


def test_pixart_from_pretrained_synthetic_snapshot(tmp_path):
    """from_pretrained over a synthetic diffusers-layout snapshot: config
    discovery, safetensors loading, conversion, and generation all engage —
    the only thing synthetic is the weight values."""
    from safetensors.numpy import save_file

    root = tmp_path / "snap"
    (root / "transformer").mkdir(parents=True)
    (root / "vae").mkdir()
    (root / "text_encoder").mkdir()
    (root / "scheduler").mkdir()

    with open(root / "transformer" / "config.json", "w") as f:
        json.dump(PIXART_JSON, f)
    save_file(synthetic_pixart_sd(),
              str(root / "transformer" / "diffusion_pytorch_model.safetensors"))

    t5cfg = t5_mod.tiny_t5_config()
    import transformers

    hf = transformers.T5EncoderModel(transformers.T5Config(
        vocab_size=t5cfg.vocab_size, d_model=t5cfg.d_model, d_kv=t5cfg.d_kv,
        d_ff=t5cfg.d_ff, num_layers=t5cfg.num_layers,
        num_heads=t5cfg.num_heads, feed_forward_proj="gated-gelu",
        dropout_rate=0.0,
    ))
    save_file({k: v.numpy() for k, v in hf.state_dict().items()},
              str(root / "text_encoder" / "model.safetensors"))
    with open(root / "text_encoder" / "config.json", "w") as f:
        json.dump({"d_model": t5cfg.d_model, "d_kv": t5cfg.d_kv,
                   "d_ff": t5cfg.d_ff, "num_layers": t5cfg.num_layers,
                   "num_heads": t5cfg.num_heads,
                   "vocab_size": t5cfg.vocab_size,
                   "feed_forward_proj": "gated-gelu"}, f)

    # VAE: dump a tiny diffusers-format state dict by inverting our param
    # tree (the same inversion the converter-roundtrip suite uses)
    from test_weights_roundtrip import invert_tree

    vcfg = tiny_vae_config()
    vparams = init_vae_params(jax.random.PRNGKey(1), vcfg)
    vsd = {}
    invert_tree(jax.tree.map(np.asarray, vparams), "", vsd)
    save_file(vsd, str(root / "vae" / "diffusion_pytorch_model.safetensors"))
    with open(root / "vae" / "config.json", "w") as f:
        json.dump({"block_out_channels": [16, 32], "layers_per_block": 1,
                   "norm_num_groups": 8, "scaling_factor": 0.18215}, f)

    cfg = DistriConfig(
        devices=jax.devices()[:4], height=128, width=128, warmup_steps=1,
        dtype=jnp.float32,
    )
    pipe = DistriPixArtPipeline.from_pretrained(cfg, str(root), scheduler="ddim")
    assert pipe.dit_config.caption_dim == t5cfg.d_model == 32
    out = pipe(prompt="snapshot smoke", num_inference_steps=2,
               output_type="latent")
    assert np.asarray(out.images[0]).shape == (16, 16, 4)
    assert np.isfinite(np.asarray(out.images[0])).all()


def test_pos_embed_interpolation_scale():
    """Coordinate scaling follows diffusers PatchEmbed: at native size the
    coords are arange/interpolation_scale, so the 1024-class table must
    equal a plain table evaluated at halved coordinates."""
    base = dit_mod.DiTConfig(sample_size=16, hidden_size=64, depth=1,
                             num_heads=4, caption_dim=32)
    scaled = dit_mod.DiTConfig(sample_size=16, hidden_size=64, depth=1,
                               num_heads=4, caption_dim=32,
                               interpolation_scale=2.0, pos_embed_base_size=8)

    t_scaled = np.asarray(dit_mod.pos_embed_table(scaled))
    # manual: coords arange(8)/(8/8)/2 = arange(8)/2
    dim = 32
    om = 1.0 / (10000.0 ** (np.arange(dim // 2) / (dim // 2)))
    coords = np.arange(8) / 2.0
    ax = np.concatenate([np.sin(coords[:, None] * om),
                         np.cos(coords[:, None] * om)], axis=-1)
    row = np.repeat(ax, 8, axis=0)
    col = np.tile(ax, (8, 1))
    np.testing.assert_allclose(t_scaled, np.concatenate([col, row], axis=-1),
                               rtol=1e-6, atol=1e-6)
    # default config unchanged (identity scaling)
    t_base = np.asarray(dit_mod.pos_embed_table(base))
    assert not np.allclose(t_base, t_scaled)

    # from_json wires the diffusers rule: 1024-class -> scale 2, base 64
    cfg = dit_mod.dit_config_from_json({"sample_size": 128})
    assert cfg.interpolation_scale == 2.0 and cfg.pos_embed_base_size == 64
    cfg512 = dit_mod.dit_config_from_json({"sample_size": 64})
    assert cfg512.interpolation_scale == 1.0


def _diffusers_2d_sincos(embed_dim, grid_size, interpolation_scale=1.0,
                         base_size=None):
    """Oracle transcribing diffusers get_2d_sincos_pos_embed structurally:
    np.meshgrid(grid_w, grid_h) puts the WIDTH coordinate in grid[0], and the
    first half of the channel dim is built from grid[0]."""
    base_size = base_size or grid_size
    coords = (np.arange(grid_size, dtype=np.float32)
              / (grid_size / base_size) / interpolation_scale)
    grid = np.stack(np.meshgrid(coords, coords), axis=0)  # [2(w,h), side, side]
    grid = grid.reshape(2, -1)

    def _1d(dim, pos):
        omega = 1.0 / 10000.0 ** (np.arange(dim // 2, dtype=np.float64)
                                  / (dim / 2.0))
        out = np.einsum("m,d->md", pos, omega)
        return np.concatenate([np.sin(out), np.cos(out)], axis=1)

    return np.concatenate(
        [_1d(embed_dim // 2, grid[0]), _1d(embed_dim // 2, grid[1])], axis=1
    )


def test_pos_embed_matches_diffusers_channel_order():
    """Column/width embedding occupies the FIRST channel half (row-first diagonally transposes the table for converted checkpoints).

    Pinned both against a structurally independent meshgrid oracle and
    against hardcoded sin/cos spot values, so a shared re-implementation of
    the wrong order cannot pass."""
    cfg = dit_mod.DiTConfig(sample_size=8, hidden_size=8, depth=1,
                            num_heads=2, caption_dim=8)
    table = np.asarray(dit_mod.pos_embed_table(cfg))  # [16, 8], side 4
    oracle = _diffusers_2d_sincos(8, 4)
    np.testing.assert_allclose(table, oracle, rtol=1e-6, atol=1e-6)

    # hidden 8 -> per-axis dim 4, omega = [1, 0.01]
    # token 1 = (row 0, col 1): first half encodes col=1, second half col=0
    np.testing.assert_allclose(
        table[1], [np.sin(1.0), np.sin(0.01), np.cos(1.0), np.cos(0.01),
                   0.0, 0.0, 1.0, 1.0], rtol=1e-6, atol=1e-6)
    # token 4 = (row 1, col 0): halves swap relative to token 1
    np.testing.assert_allclose(
        table[4], [0.0, 0.0, 1.0, 1.0,
                   np.sin(1.0), np.sin(0.01), np.cos(1.0), np.cos(0.01)],
        rtol=1e-6, atol=1e-6)

    # scaling path agrees with the oracle too
    cfg_s = dit_mod.DiTConfig(sample_size=8, hidden_size=8, depth=1,
                              num_heads=2, caption_dim=8,
                              interpolation_scale=2.0, pos_embed_base_size=2)
    np.testing.assert_allclose(
        np.asarray(dit_mod.pos_embed_table(cfg_s)),
        _diffusers_2d_sincos(8, 4, interpolation_scale=2.0, base_size=2),
        rtol=1e-6, atol=1e-6)


def test_pixart_pipeline_callback():
    """Pipeline-level per-step callback on the displaced-patch DiT runner
    (compiled mode); PipeFusion rejects callbacks loudly before any work."""
    pipe, cfg = _tiny_pixart_stack(4)
    seen = []
    out = pipe(prompt="a fox", num_inference_steps=3, output_type="latent",
               seed=2, callback=lambda i, t, x: seen.append((i, float(t),
                                                             x.shape)))
    assert [i for i, _, _ in seen] == [0, 1, 2]
    ts = [t for _, t, _ in seen]
    assert ts == sorted(ts, reverse=True)
    assert all(s == (1, cfg.latent_height, cfg.latent_width, 4)
               for _, _, s in seen)
    assert np.isfinite(np.asarray(out.images[0])).all()

    pipe_pf, _ = _tiny_pixart_stack(4, "pipefusion")
    with pytest.raises(ValueError, match="token"):
        pipe_pf(prompt="a fox", num_inference_steps=2, output_type="latent",
                callback=lambda i, t, x: None)


# CPU-compile-heavy module: the fake 8-device mesh compiles full
# multi-device denoise loops, minutes per test on the tier-1 CPU runner.
# Runs with `-m slow` and on real-hardware rounds.
pytestmark = pytest.mark.slow
