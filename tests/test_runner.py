"""End-to-end denoise-loop tests on the fake 8-device mesh.

The reference's correctness story is golden-output comparison between N-device
and 1-device runs (SURVEY.md §4); these tests make it a unit test: the
full_sync N-device generation must closely match the single-device one, the
displaced modes must stay close at small step counts, and all parallelism /
scheduler / CFG combinations must produce finite latents.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distrifuser_tpu import DistriConfig
from distrifuser_tpu.models.unet import init_unet_params, tiny_config
from distrifuser_tpu.parallel.runner import DenoiseRunner
from distrifuser_tpu.schedulers import get_scheduler


def make_runner(devices, n_dev, *, parallelism="patch", mode="corrected_async_gn",
                scheduler="ddim", do_cfg=True, split_scheme="row",
                height=128, width=128, warmup=1):
    cfg = DistriConfig(
        devices=devices[:n_dev],
        height=height,
        width=width,
        do_classifier_free_guidance=do_cfg,
        warmup_steps=warmup,
        mode=mode,
        parallelism=parallelism,
        split_scheme=split_scheme,
        use_cuda_graph=True,
    )
    ucfg = tiny_config(sdxl=False)
    params = init_unet_params(jax.random.PRNGKey(0), ucfg)
    sched = get_scheduler(scheduler)
    return DenoiseRunner(cfg, ucfg, params, sched), cfg, ucfg


def make_inputs(cfg, ucfg, key=42, l_text=7):
    k = jax.random.PRNGKey(key)
    b = cfg.batch_size
    lat = jax.random.normal(k, (b, cfg.latent_height, cfg.latent_width, ucfg.in_channels))
    n_br = 2 if cfg.do_classifier_free_guidance else 1
    enc = jax.random.normal(
        jax.random.fold_in(k, 1), (n_br, b, l_text, ucfg.cross_attention_dim)
    )
    return lat, enc


def test_single_device_loop_runs():
    runner, cfg, ucfg = make_runner(jax.devices()[:1], 1)
    lat, enc = make_inputs(cfg, ucfg)
    out = runner.generate(lat, enc, num_inference_steps=4, guidance_scale=5.0)
    assert out.shape == lat.shape
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("mode", ["full_sync", "corrected_async_gn"])
def test_multi_device_matches_single_device(devices8, mode):
    """The golden oracle: 8-device (cfg 2 x sp 4) vs single device."""
    runner1, cfg1, ucfg = make_runner(devices8, 1, mode=mode)
    runner8, cfg8, _ = make_runner(devices8, 8, mode=mode)
    lat, enc = make_inputs(cfg1, ucfg)
    steps = 6
    out1 = np.asarray(runner1.generate(lat, enc, num_inference_steps=steps))
    out8 = np.asarray(runner8.generate(lat, enc, num_inference_steps=steps))
    assert np.isfinite(out8).all()
    # full_sync is near-exact (GroupNorm Bessel-vs-biased + reduction order);
    # displaced modes drift slightly through stale activations
    tol = 0.05 if mode == "full_sync" else 0.35
    err = np.abs(out8 - out1).max() / (np.abs(out1).max() + 1e-6)
    assert err < tol, f"relative deviation {err} exceeds {tol} for {mode}"


@pytest.mark.parametrize("mode", ["stale_gn", "separate_gn", "sync_gn", "no_sync"])
def test_all_sync_modes_finite(devices8, mode):
    runner, cfg, ucfg = make_runner(devices8, 4, mode=mode)
    lat, enc = make_inputs(cfg, ucfg)
    out = runner.generate(lat, enc, num_inference_steps=4)
    assert out.shape == lat.shape
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("split_scheme", ["row", "col", "alternate"])
def test_naive_patch_schemes(devices8, split_scheme):
    runner, cfg, ucfg = make_runner(
        devices8, 4, parallelism="naive_patch", split_scheme=split_scheme
    )
    lat, enc = make_inputs(cfg, ucfg)
    out = runner.generate(lat, enc, num_inference_steps=3)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("scheduler", ["euler", "dpm-solver"])
def test_other_schedulers_through_loop(devices8, scheduler):
    runner, cfg, ucfg = make_runner(devices8, 4, scheduler=scheduler)
    lat, enc = make_inputs(cfg, ucfg)
    lat = lat * runner.scheduler.set_timesteps(4).init_noise_sigma
    out = runner.generate(lat, enc, num_inference_steps=4)
    assert np.isfinite(np.asarray(out)).all()


def test_no_cfg_path(devices8):
    runner, cfg, ucfg = make_runner(devices8, 4, do_cfg=False)
    assert cfg.n_device_per_batch == 4
    lat, enc = make_inputs(cfg, ucfg)
    out = runner.generate(lat, enc, num_inference_steps=3, guidance_scale=1.0)
    assert np.isfinite(np.asarray(out)).all()


def test_geometry_validation(devices8):
    with pytest.raises(ValueError, match="divisible"):
        make_runner(devices8, 8, height=96, width=96)  # latent 12 rows, sp=4, depth 1


def test_comm_volume_report(devices8):
    runner, cfg, ucfg = make_runner(devices8, 4)
    report = runner.comm_volume_report()
    # patch mode tracks exactly the three layer families the reference
    # accounts for (utils.py:152-158): conv halos, attention KV, GN moments
    assert set(report) == {"conv2d", "attn", "gn"}
    assert report["attn"] > report["gn"]
    # single device: no comm, empty report
    runner1, _, _ = make_runner(devices8, 1)
    assert runner1.comm_volume_report() == {}


def test_patch_mode_bf16_end_to_end(devices8):
    """bf16 model dtype through the patch-parallel path (the real-chip
    configuration): the text-KV cache is computed
    outside unet_forward and must apply the same model-dtype entry cast —
    fp32 prompt embeds once upcast the whole residual stream after the
    first cross-attention (caught via comm_volume_report tracing)."""
    import jax.numpy as jnp
    import numpy as np

    from distrifuser_tpu.models import unet as unet_mod
    from distrifuser_tpu.schedulers import get_scheduler

    cfg = DistriConfig(devices=devices8, height=256, width=256,
                       warmup_steps=1, parallelism="patch",
                       dtype=jnp.bfloat16, use_cuda_graph=False)
    ucfg = unet_mod.tiny_config(sdxl=True)
    params = unet_mod.init_unet_params(jax.random.PRNGKey(0), ucfg, cfg.dtype)
    runner = DenoiseRunner(cfg, ucfg, params, get_scheduler("ddim"))
    report = runner.comm_volume_report()
    assert set(report) == {"conv2d", "attn", "gn"}
    lat = jax.random.normal(jax.random.PRNGKey(1),
                            (1, 32, 32, ucfg.in_channels), jnp.float32)
    # fp32 prompt embeds on purpose: the KV cache must cast, not upcast
    enc = jax.random.normal(jax.random.PRNGKey(2),
                            (2, 1, 77, ucfg.cross_attention_dim), jnp.float32)
    emb = (ucfg.projection_class_embeddings_input_dim
           - 6 * ucfg.addition_time_embed_dim)
    added = {"text_embeds": jnp.zeros((2, 1, emb), jnp.float32),
             "time_ids": jnp.zeros((2, 1, 6), jnp.float32)}
    out = runner.generate(lat, enc, guidance_scale=5.0,
                          num_inference_steps=3, added_cond=added)
    assert np.isfinite(np.asarray(out, np.float32)).all()


def test_bf16_denoise_psnr_vs_fp32():
    """The real-chip dtype (bf16) must stay faithful to fp32 through a full
    multi-step denoise — the weight-free analog of the reference's PSNR
    quality gate (README.md:121-144; BASELINE north star is >=30 dB).
    Measured ~52 dB at 8 steps on the tiny SDXL config; 40 dB leaves margin
    for platform variation while still far above the quality bar."""
    import jax.numpy as jnp
    import numpy as np

    from distrifuser_tpu.models import unet as unet_mod
    from distrifuser_tpu.schedulers import get_scheduler

    ucfg = unet_mod.tiny_config(sdxl=True)
    outs = {}
    for name, dt in [("fp32", jnp.float32), ("bf16", jnp.bfloat16)]:
        cfg = DistriConfig(devices=jax.devices()[:1], height=256, width=256,
                           warmup_steps=1, parallelism="patch", dtype=dt,
                           use_cuda_graph=False)
        params = unet_mod.init_unet_params(jax.random.PRNGKey(0), ucfg, dt)
        r = DenoiseRunner(cfg, ucfg, params, get_scheduler("ddim"))
        lat = jax.random.normal(jax.random.PRNGKey(1),
                                (1, 32, 32, ucfg.in_channels), jnp.float32)
        enc = jax.random.normal(jax.random.PRNGKey(2),
                                (2, 1, 77, ucfg.cross_attention_dim),
                                jnp.float32)
        emb = (ucfg.projection_class_embeddings_input_dim
               - 6 * ucfg.addition_time_embed_dim)
        added = {"text_embeds": jnp.zeros((2, 1, emb), jnp.float32),
                 "time_ids": jnp.zeros((2, 1, 6), jnp.float32)}
        outs[name] = np.asarray(
            r.generate(lat, enc, guidance_scale=5.0, num_inference_steps=8,
                       added_cond=added), np.float32)
    a, b = outs["fp32"], outs["bf16"]
    mse = float(np.mean((a - b) ** 2))
    rng = float(a.max() - a.min())
    psnr = 10 * np.log10(rng ** 2 / mse)
    assert psnr >= 40.0, f"bf16 denoise deviates from fp32: {psnr:.1f} dB"


def test_compiled_handle_is_cached_and_observable():
    """The serve layer's contract: compiled_handle returns the SAME object
    for a repeated signature (no request-path retrace) and cache_info
    reports builds/entries."""
    runner, cfg, ucfg = make_runner(jax.devices("cpu"), 1)
    assert runner.cache_info() == {"entries": [], "builds": 0}
    h1 = runner.compiled_handle(3)
    h2 = runner.compiled_handle(3)
    assert h1 is h2
    assert runner.cache_info()["builds"] == 1
    runner.compiled_handle(4)
    info = runner.cache_info()
    assert info["builds"] == 2 and len(info["entries"]) == 2
    # generate() dispatches to the prepared handle, not a fresh build
    runner.prepare(3)
    lat, enc = make_inputs(cfg, ucfg)
    out = runner.generate(lat, enc, num_inference_steps=3)
    assert np.isfinite(np.asarray(out)).all()
    assert runner.cache_info()["builds"] == 2


# CPU-compile-heavy module: the fake 8-device mesh compiles full
# multi-device denoise loops, minutes per test on the tier-1 CPU runner.
# Runs with `-m slow` and on real-hardware rounds.
pytestmark = pytest.mark.slow
