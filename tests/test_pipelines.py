"""Pipeline-level tests with tiny random-weight models on the fake mesh."""

import jax
import numpy as np
import pytest

from distrifuser_tpu import DistriConfig
from distrifuser_tpu.models.clip import init_clip_params, tiny_clip_config
from distrifuser_tpu.models.unet import init_unet_params, tiny_config
from distrifuser_tpu.models.vae import init_vae_params, tiny_vae_config
from distrifuser_tpu.pipelines import (
    DistriSDPipeline,
    DistriSDXLPipeline,
    SimpleTokenizer,
)


def build_sdxl_pipeline(devices, n_dev, **cfg_kw):
    cfg_kw.setdefault("height", 128)
    cfg_kw.setdefault("width", 128)
    cfg_kw.setdefault("warmup_steps", 1)
    dcfg = DistriConfig(devices=devices[:n_dev], **cfg_kw)
    # SDXL-shaped tiny stack: the two encoders' hidden widths concat to the
    # UNet cross_attention_dim (16+16=32); pooled embeds use encoder 2's
    # projection, which must match ucfg's text_embeds width (32)
    from distrifuser_tpu.models.clip import CLIPTextConfig

    tc1 = tiny_clip_config(hidden=16)
    tc2 = CLIPTextConfig(
        vocab_size=1000, hidden_size=16, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=32, projection_dim=32,
    )
    ucfg = tiny_config(cross_attention_dim=32, sdxl=True)
    vcfg = tiny_vae_config()
    pipe = DistriSDXLPipeline.from_params(
        dcfg,
        ucfg,
        init_unet_params(jax.random.PRNGKey(0), ucfg),
        vcfg,
        init_vae_params(jax.random.PRNGKey(1), vcfg),
        [tc1, tc2],
        [
            init_clip_params(jax.random.PRNGKey(2), tc1),
            init_clip_params(jax.random.PRNGKey(3), tc2),
        ],
    )
    return pipe, dcfg


def build_sd_pipeline(devices, n_dev, **cfg_kw):
    cfg_kw.setdefault("height", 128)
    cfg_kw.setdefault("width", 128)
    cfg_kw.setdefault("warmup_steps", 1)
    dcfg = DistriConfig(devices=devices[:n_dev], **cfg_kw)
    tc = tiny_clip_config(hidden=32)
    ucfg = tiny_config(cross_attention_dim=32, sdxl=False)
    vcfg = tiny_vae_config()
    pipe = DistriSDPipeline.from_params(
        dcfg, ucfg,
        init_unet_params(jax.random.PRNGKey(0), ucfg),
        vcfg, init_vae_params(jax.random.PRNGKey(1), vcfg),
        [tc], [init_clip_params(jax.random.PRNGKey(2), tc)],
    )
    return pipe, dcfg


def test_sdxl_pipeline_generates_pil(devices8):
    pipe, _ = build_sdxl_pipeline(devices8, 8)
    out = pipe("a photo of an astronaut riding a horse", num_inference_steps=3, seed=7)
    img = out.images[0]
    # tiny VAE has 2 blocks -> one 2x upsample: 16x16 latent -> 32x32 pixels
    assert img.size == (32, 32)
    arr = np.asarray(img)
    assert arr.dtype == np.uint8 and arr.shape == (32, 32, 3)


def test_sdxl_deterministic_per_seed(devices8):
    pipe, _ = build_sdxl_pipeline(devices8, 4)
    a = pipe("a corgi", num_inference_steps=2, seed=1, output_type="np").images[0]
    b = pipe("a corgi", num_inference_steps=2, seed=1, output_type="np").images[0]
    c = pipe("a corgi", num_inference_steps=2, seed=2, output_type="np").images[0]
    np.testing.assert_array_equal(a, b)
    assert np.abs(a - c).max() > 0


def test_sdxl_multi_device_matches_single(devices8):
    """Pipeline-level golden test (the reference's §4 protocol as a unit test)."""
    pipe1, _ = build_sdxl_pipeline(devices8, 1)
    pipe8, _ = build_sdxl_pipeline(devices8, 8, mode="full_sync")
    kw = dict(num_inference_steps=3, seed=11, output_type="np")
    img1 = pipe1("a lighthouse at dusk", **kw).images[0]
    img8 = pipe8("a lighthouse at dusk", **kw).images[0]
    # uint8-scale agreement: PSNR > 30 dB (the reference's quality bar)
    mse = float(np.mean((img1 - img8) ** 2))
    psnr = 10 * np.log10(1.0 / max(mse, 1e-12))
    assert psnr > 30, f"PSNR {psnr:.1f} dB"


def test_sd_pipeline_latent_output(devices8):
    pipe, dcfg = build_sd_pipeline(devices8, 4)
    out = pipe("a cat", num_inference_steps=2, seed=3, output_type="latent")
    assert len(out.images) == 1  # one entry per image, like 'np'/'pil'
    lat = out.images[0]
    assert lat.shape == (dcfg.latent_height, dcfg.latent_width, 4)
    assert np.isfinite(lat).all()


def test_pipeline_rejects_runtime_size(devices8):
    pipe, _ = build_sd_pipeline(devices8, 2)
    with pytest.raises(ValueError, match="fixed in DistriConfig"):
        pipe("a cat", height=512)


def test_guidance_forced_off_without_cfg(devices8):
    pipe, _ = build_sd_pipeline(devices8, 4, do_classifier_free_guidance=False)
    out = pipe("a cat", num_inference_steps=2, guidance_scale=9.0, output_type="latent")
    assert np.isfinite(out.images[0]).all()


def test_batch_of_prompts(devices8):
    pipe, dcfg = build_sd_pipeline(devices8, 4, batch_size=2)
    out = pipe(["a cat", "a dog"], num_inference_steps=2, output_type="latent")
    assert len(out.images) == 2
    lat = np.stack(out.images)
    assert lat.shape == (2, dcfg.latent_height, dcfg.latent_width, 4)
    assert np.isfinite(lat).all()
    # fewer prompts than batch_size: padded internally, one image back
    one = pipe("just one", num_inference_steps=2, output_type="latent")
    assert len(one.images) == 1


def test_prompt_chunking_matches_manual_chunks(devices8):
    """3 prompts through a batch_size=2 pipeline == the two manual chunk
    calls with the same per-image initial noise (arbitrary
    prompt counts chunk instead of asserting)."""
    pipe, _ = build_sd_pipeline(devices8, 2, batch_size=2)
    lats = np.asarray(jax.random.normal(jax.random.PRNGKey(9), (3, 16, 16, 4)))
    kw = dict(num_inference_steps=2, output_type="latent")
    all3 = pipe(["a cat", "a dog", "a bird"], latents=lats, **kw).images
    assert len(all3) == 3
    first2 = pipe(["a cat", "a dog"], latents=lats[:2], **kw).images
    # the tail chunk pads internally; hand it the padded latents explicitly
    last1 = pipe(["a bird", "a bird"], latents=np.concatenate(
        [lats[2:], lats[2:]]), **kw).images
    np.testing.assert_array_equal(np.stack(all3[:2]), np.stack(first2))
    np.testing.assert_array_equal(all3[2], last1[0])


def test_chunked_decode_and_empty_prompts(devices8):
    """The decode path handles totals that are not a batch_size multiple
    (chunked VAE decode), and an empty prompt list fails with a clear
    message."""
    pipe, _ = build_sd_pipeline(devices8, 2, batch_size=2)
    out = pipe(["a cat", "a dog", "a bird"], num_inference_steps=2,
               output_type="np")
    assert len(out.images) == 3
    assert all(np.isfinite(im).all() for im in out.images)
    with pytest.raises(AssertionError, match="at least one prompt"):
        pipe([], num_inference_steps=2)


def test_num_images_per_prompt(devices8):
    """num_images_per_prompt expands prompt-major (diffusers order): the
    expanded call equals an explicit repeated-prompt call on the same
    latents."""
    pipe, _ = build_sd_pipeline(devices8, 2, batch_size=2)
    lats = np.asarray(jax.random.normal(jax.random.PRNGKey(4), (4, 16, 16, 4)))
    kw = dict(num_inference_steps=2, output_type="latent")
    expanded = pipe(["a cat", "a dog"], num_images_per_prompt=2,
                    latents=lats, **kw).images
    explicit = pipe(["a cat", "a cat", "a dog", "a dog"],
                    latents=lats, **kw).images
    assert len(expanded) == 4
    np.testing.assert_array_equal(np.stack(expanded), np.stack(explicit))
    # different noise per image of the same prompt
    assert np.abs(expanded[0] - expanded[1]).max() > 0


def test_sdxl_batch_prompts(devices8):
    pipe, dcfg = build_sdxl_pipeline(devices8, 4, batch_size=2)
    out = pipe(
        ["a red fox", "a blue bird"],
        negative_prompt=["blurry", "low quality"],
        num_inference_steps=2,
        output_type="latent",
    )
    assert len(out.images) == 2
    lat = np.stack(out.images)
    assert lat.shape == (2, dcfg.latent_height, dcfg.latent_width, 4)
    assert np.isfinite(lat).all()


def test_img2img_wiring_matches_manual_latents(devices8):
    """strength=1.0 img2img == text2img fed the manually noised encode of
    the same image (pins the encode -> add_noise -> generate wiring), and a
    partial strength runs fewer steps from a closer start."""
    import jax.numpy as jnp

    from distrifuser_tpu.models import vae as vae_mod

    pipe, dcfg = build_sd_pipeline(devices8, 2)
    rng = np.random.RandomState(7)
    im = rng.rand(32, 32, 3).astype(np.float32)  # [0,1], decoder-sized
    kw = dict(num_inference_steps=4, output_type="latent", seed=11)

    out_i2i = pipe("a cabin", image=im, strength=1.0, **kw).images[0]

    init = pipe._encode_image(
        pipe.vae_params, jnp.asarray((im * 2 - 1)[None])
    ) * pipe.vae_config.scaling_factor
    pipe.scheduler.set_timesteps(4)
    noise = jax.random.normal(jax.random.PRNGKey(11), init.shape, jnp.float32)
    manual = pipe.scheduler.add_noise(init, noise, 0)
    out_manual = pipe("a cabin", latents=np.asarray(manual), **kw).images[0]
    np.testing.assert_array_equal(out_i2i, out_manual)

    # partial strength: still finite, and output differs (fewer steps, start
    # closer to the init image)
    out_half = pipe("a cabin", image=im, strength=0.5, **kw).images[0]
    assert np.isfinite(out_half).all()
    assert np.abs(out_half - out_i2i).max() > 0
    with pytest.raises(AssertionError, match="not both"):
        pipe("a cabin", image=im, latents=np.asarray(manual), **kw)


def test_img2img_low_strength_stays_closer_to_init(devices8):
    """Lower strength must reconstruct the init latent more closely — the
    user-visible img2img contract."""
    import jax.numpy as jnp

    from distrifuser_tpu.models import vae as vae_mod

    pipe, _ = build_sd_pipeline(devices8, 1)
    rng = np.random.RandomState(8)
    im = rng.rand(32, 32, 3).astype(np.float32)
    init = np.asarray(vae_mod.encode(
        pipe.vae_params, pipe.vae_config, jnp.asarray((im * 2 - 1)[None])
    ) * pipe.vae_config.scaling_factor)
    kw = dict(num_inference_steps=8, output_type="latent", seed=3)
    d = {}
    for s in (0.25, 1.0):
        out = pipe("a cabin", image=im, strength=s, **kw).images[0]
        d[s] = float(np.abs(out - init[0]).mean())
    assert d[0.25] < d[1.0], d


def test_sdxl_micro_conditioning_kwargs(devices8):
    """original_size / crops / target_size flow into the SDXL time_ids
    (diffusers kwargs the reference forwards): explicit defaults equal the
    implicit ones bitwise; a different original_size changes the output."""
    pipe, dcfg = build_sdxl_pipeline(devices8, 2)
    kw = dict(num_inference_steps=2, output_type="latent", seed=5)
    base = pipe("a fox", **kw).images[0]
    explicit = pipe("a fox", original_size=(dcfg.height, dcfg.width),
                    crops_coords_top_left=(0, 0),
                    target_size=(dcfg.height, dcfg.width), **kw).images[0]
    np.testing.assert_array_equal(base, explicit)
    shifted = pipe("a fox", original_size=(4 * dcfg.height, 4 * dcfg.width),
                   crops_coords_top_left=(64, 64), **kw).images[0]
    assert np.abs(shifted - base).max() > 0
    # 6-id base layout (diffusers 0.24.0 gating): a LONE negative size is
    # ignored — the uncond branch reuses the positive add_time_ids unless
    # BOTH negative_original_size AND negative_target_size are passed
    lone = pipe("a fox", negative_original_size=(4 * dcfg.height,
                                                 4 * dcfg.width),
                **kw).images[0]
    np.testing.assert_array_equal(base, lone)
    # with both given, the negative set reaches ONLY the uncond branch:
    # values equal to the positive defaults are a bitwise no-op, an
    # asymmetric negative size changes the output
    sym = pipe("a fox", negative_original_size=(dcfg.height, dcfg.width),
               negative_target_size=(dcfg.height, dcfg.width),
               **kw).images[0]
    np.testing.assert_array_equal(base, sym)
    asym = pipe("a fox", negative_original_size=(4 * dcfg.height,
                                                 4 * dcfg.width),
                negative_target_size=(dcfg.height, dcfg.width),
                **kw).images[0]
    assert np.abs(asym - base).max() > 0
    # custom positive crops are REUSED by the uncond branch when the
    # negative set is inactive; activating it resets uncond crops to (0, 0)
    # unless negative_crops_coords_top_left overrides them
    crop = pipe("a fox", crops_coords_top_left=(32, 32), **kw).images[0]
    crop_reused = pipe("a fox", crops_coords_top_left=(32, 32),
                       negative_original_size=(dcfg.height, dcfg.width),
                       negative_target_size=(dcfg.height, dcfg.width),
                       negative_crops_coords_top_left=(32, 32),
                       **kw).images[0]
    np.testing.assert_array_equal(crop, crop_reused)
    crop_zeroed = pipe("a fox", crops_coords_top_left=(32, 32),
                       negative_original_size=(dcfg.height, dcfg.width),
                       negative_target_size=(dcfg.height, dcfg.width),
                       **kw).images[0]
    assert np.abs(crop_zeroed - crop).max() > 0


def test_refiner_layout_aesthetic_score(devices8):
    """5-id refiner-style UNet: aesthetic_score conditions the positive
    branch, negative_aesthetic_score (diffusers default 2.5) the uncond
    branch — so the branches differ by default and equalizing the scores
    changes the output."""
    import dataclasses

    from distrifuser_tpu.models.clip import CLIPTextConfig, init_clip_params
    from distrifuser_tpu.models.unet import init_unet_params, tiny_config
    from distrifuser_tpu.models.vae import init_vae_params, tiny_vae_config
    from distrifuser_tpu.pipelines import DistriSDXLPipeline

    from distrifuser_tpu import DistriConfig
    from distrifuser_tpu.models.clip import tiny_clip_config

    dcfg = DistriConfig(devices=devices8[:2], height=128, width=128,
                        warmup_steps=1)
    tc1 = tiny_clip_config(hidden=16)
    tc2 = CLIPTextConfig(vocab_size=1000, hidden_size=16, num_hidden_layers=2,
                         num_attention_heads=4, intermediate_size=32,
                         projection_dim=32)
    base_ucfg = tiny_config(cross_attention_dim=32, sdxl=True)
    # pooled(32) + 5 * addition_time_embed_dim(8) = 72: the refiner layout
    ucfg = dataclasses.replace(base_ucfg,
                               projection_class_embeddings_input_dim=72)
    pipe = DistriSDXLPipeline.from_params(
        dcfg, ucfg, init_unet_params(jax.random.PRNGKey(0), ucfg),
        tiny_vae_config(),
        init_vae_params(jax.random.PRNGKey(1), tiny_vae_config()),
        [tc1, tc2],
        [init_clip_params(jax.random.PRNGKey(2), tc1),
         init_clip_params(jax.random.PRNGKey(3), tc2)],
    )
    kw = dict(num_inference_steps=2, output_type="latent", seed=5)
    default = pipe("a fox", **kw).images[0]  # scores 6.0 vs 2.5
    equalized = pipe("a fox", negative_aesthetic_score=6.0, **kw).images[0]
    assert np.abs(default - equalized).max() > 0
    repeat = pipe("a fox", **kw).images[0]
    np.testing.assert_array_equal(default, repeat)


def test_denoising_split_equals_full_run(devices8):
    """Base+refiner split protocol: a run stopped at denoising_end plus a
    second run resumed at the same denoising_start must equal the
    uninterrupted run (single device: one-phase loop, so the handoff cannot
    change warmup semantics)."""
    pipe, dcfg = build_sd_pipeline(devices8, 1)
    noise = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (1, 16, 16, 4)))
    kw = dict(num_inference_steps=6, output_type="latent")
    full = pipe("a canyon", latents=noise, **kw).images[0]
    mid = pipe("a canyon", latents=noise, denoising_end=0.5, **kw).images[0]
    assert np.abs(mid - full).max() > 0  # actually stopped early
    resumed = pipe("a canyon", latents=mid[None], denoising_start=0.5,
                   **kw).images[0]
    # bitwise equality does not survive XLA compiling the three loop
    # programs separately (float re-association); 1e-4 on O(30) latents
    # is ~1e-5 relative
    np.testing.assert_allclose(resumed, full, atol=1e-4)
    with pytest.raises(AssertionError, match="mid-trajectory"):
        pipe("a canyon", denoising_start=0.5, **kw)


def test_simple_tokenizer_shapes():
    tok = SimpleTokenizer()
    ids = tok(["hello world", ""])
    assert ids.shape == (2, 77)
    assert ids[0, 0] == tok.bos
    assert (ids[1] == tok.eos).sum() >= 76


def test_rectangular_image(devices8):
    pipe, dcfg = build_sd_pipeline(devices8, 4, height=192, width=128)
    out = pipe("a waterfall", num_inference_steps=2, output_type="latent")
    assert len(out.images) == 1
    lat = out.images[0]
    assert lat.shape == (24, 16, 4)
    assert np.isfinite(lat).all()


def test_caller_supplied_latents(devices8):
    pipe, dcfg = build_sd_pipeline(devices8, 2)
    lat0 = np.asarray(
        jax.random.normal(jax.random.PRNGKey(0), (1, 16, 16, 4))
    )
    a = pipe("a pier", num_inference_steps=2, latents=lat0, output_type="np").images[0]
    b = pipe("a pier", num_inference_steps=2, latents=lat0, output_type="np").images[0]
    np.testing.assert_array_equal(a, b)
    with pytest.raises(AssertionError):
        pipe("a pier", num_inference_steps=2, latents=lat0[:, :8])


def test_weightless_tokenizer_flag_on_output(devices8):
    """Hash-tokenizer runs carry the warning ON the artifact: the PipelineOutput says it must not be quality-judged; a
    real-tokenizer pipeline emits a clean output."""
    pipe, _ = build_sdxl_pipeline(devices8, 1)
    out = pipe("a fox", num_inference_steps=1, output_type="latent", seed=0)
    assert out.weightless_tokenizer
    assert "SimpleTokenizer" in out.warning

    class _FakeRealTok:
        model_max_length = 77

        def __call__(self, texts, max_length=77, **kw):
            return {"input_ids": np.zeros((len(texts), max_length), np.int64)}

    pipe.tokenizers = [_FakeRealTok(), _FakeRealTok()]
    out2 = pipe("a fox", num_inference_steps=1, output_type="latent", seed=0)
    assert not out2.weightless_tokenizer and out2.warning is None


def test_host_image_buffers_come_back_only_when_every_view_is_gone():
    """`_HostImages`: an image a caller still holds - or any slice of it -
    is never written again; once the last view is dropped the same memory
    is handed out for the next image."""
    import gc

    from distrifuser_tpu.pipelines import _HostImages

    pool = _HostImages()
    first = pool.take((2, 4, 4, 3))
    first[...] = 1.0
    where = first.__array_interface__["data"][0]
    kept = first[1]  # what a caller keeps of a batch
    del first
    gc.collect()
    second = pool.take((2, 4, 4, 3))
    assert second.__array_interface__["data"][0] != where
    second[...] = 2.0
    assert float(kept.min()) == 1.0 == float(kept.max())
    del kept, second
    gc.collect()
    # both buffers are back: the next two images are written where the
    # first two were
    again = [pool.take((2, 4, 4, 3)) for _ in range(2)]
    assert all(a.flags.writeable and a.dtype == np.float32 for a in again)
    assert where in {a.__array_interface__["data"][0] for a in again}
    assert pool.take((1, 2, 2, 3)).shape == (1, 2, 2, 3)  # another size
