"""Per-step (use_cuda_graph=False parity) mode vs the fused compiled loop."""

import jax
import numpy as np
import pytest

from distrifuser_tpu import DistriConfig
from distrifuser_tpu.models.unet import init_unet_params, tiny_config
from distrifuser_tpu.parallel.runner import make_runner
from distrifuser_tpu.schedulers import get_scheduler


def build(devices, n, **kw):
    cfg = DistriConfig(devices=devices[:n], height=128, width=128,
                       warmup_steps=1, **kw)
    ucfg = tiny_config()
    params = init_unet_params(jax.random.PRNGKey(0), ucfg)
    return make_runner(cfg, ucfg, params, get_scheduler("ddim")), cfg, ucfg


def inputs(cfg, ucfg):
    k = jax.random.PRNGKey(9)
    lat = jax.random.normal(k, (1, cfg.latent_height, cfg.latent_width, 4))
    n_br = 2 if cfg.do_classifier_free_guidance else 1
    enc = jax.random.normal(jax.random.fold_in(k, 1), (n_br, 1, 7, ucfg.cross_attention_dim))
    return lat, enc


@pytest.mark.parametrize("kw", [
    {},  # displaced patch, gather
    {"attn_impl": "ring"},
    {"parallelism": "naive_patch", "split_scheme": "alternate"},
    {"parallelism": "tensor"},
])
def test_stepwise_matches_fused(devices8, kw):
    fused, cfg, ucfg = build(devices8, 8, use_cuda_graph=True, **kw)
    stepw, cfg2, _ = build(devices8, 8, use_cuda_graph=False, **kw)
    lat, enc = inputs(cfg, ucfg)
    a = np.asarray(fused.generate(lat, enc, num_inference_steps=4))
    b = np.asarray(stepw.generate(lat, enc, num_inference_steps=4))
    np.testing.assert_allclose(a, b, atol=2e-4)


def test_stepwise_single_device():
    stepw, cfg, ucfg = build(jax.devices()[:1], 1, use_cuda_graph=False)
    lat, enc = inputs(cfg, ucfg)
    out = stepw.generate(lat, enc, num_inference_steps=3)
    assert np.isfinite(np.asarray(out)).all()


def test_stepwise_with_dp(devices8):
    """Per-step mode with the 3-axis mesh: state lays out over (dp,cfg,sp)."""
    stepw, cfg, ucfg = build(devices8, 8, use_cuda_graph=False,
                             dp_degree=2, batch_size=2)
    fused, _, _ = build(devices8, 8, use_cuda_graph=True,
                        dp_degree=2, batch_size=2)

    k = jax.random.PRNGKey(5)
    lat = jax.random.normal(k, (2, 16, 16, 4))
    enc = jax.random.normal(jax.random.fold_in(k, 1), (2, 2, 7, ucfg.cross_attention_dim))
    a = np.asarray(stepw.generate(lat, enc, num_inference_steps=4))
    b = np.asarray(fused.generate(lat, enc, num_inference_steps=4))
    np.testing.assert_allclose(a, b, atol=2e-4)


def test_start_step_stepwise_matches_fused(devices8):
    """img2img entry (start_step > 0): the fused loop's fori/scan offsets
    must replay the per-step schedule exactly — warmup counted from the
    first executed step."""
    fused, cfg, ucfg = build(devices8, 4, use_cuda_graph=True)
    stepw, _, _ = build(devices8, 4, use_cuda_graph=False)
    lat, enc = inputs(cfg, ucfg)
    for start in (2, 5):
        a = np.asarray(fused.generate(lat, enc, num_inference_steps=6,
                                      start_step=start))
        b = np.asarray(stepw.generate(lat, enc, num_inference_steps=6,
                                      start_step=start))
        np.testing.assert_allclose(a, b, atol=2e-4)
    # full run still differs from a tail run (the offset actually engages)
    full = np.asarray(fused.generate(lat, enc, num_inference_steps=6))
    tail = np.asarray(fused.generate(lat, enc, num_inference_steps=6,
                                     start_step=5))
    assert np.abs(full - tail).max() > 0
    with pytest.raises(AssertionError):
        fused.generate(lat, enc, num_inference_steps=4, start_step=4)


def test_stepwise_callback(devices8):
    """callback(step, timestep, latents) — the diffusers legacy signature —
    fires once per executed step from the host loop."""
    stepw, cfg, ucfg = build(devices8, 2, use_cuda_graph=False)
    lat, enc = inputs(cfg, ucfg)
    seen = []
    out = stepw.generate(
        lat, enc, num_inference_steps=4,
        callback=lambda i, t, x: seen.append((i, int(t), x.shape)))
    assert [i for i, _, _ in seen] == [0, 1, 2, 3]
    ts = [t for _, t, _ in seen]
    assert ts == sorted(ts, reverse=True) and ts[-1] >= 0  # descending sched
    assert all(s == np.asarray(out).shape for _, _, s in seen)


def test_fused_callback_matches_stepwise(devices8):
    """Callback with use_cuda_graph=True: the compiled
    loop fires the diffusers legacy callback via io_callback with the SAME
    count, order, timesteps, and latents as the host loop — in both the
    fused and hybrid configs (a callback routes hybrid through the same
    compiled-callback program)."""
    stepw, cfg, ucfg = build(devices8, 2, use_cuda_graph=False)
    fused, _, _ = build(devices8, 2, use_cuda_graph=True)
    hybrid, _, _ = build(devices8, 2, use_cuda_graph=True, hybrid_loop=True)
    lat, enc = inputs(cfg, ucfg)

    def run(runner, **kw):
        seen = []
        out = runner.generate(
            lat, enc, num_inference_steps=5,
            callback=lambda i, t, x: seen.append(
                (int(i), float(t), np.array(x, copy=True))),
            **kw,
        )
        return seen, np.asarray(out)

    s_seen, s_out = run(stepw)
    assert [i for i, _, _ in s_seen] == [0, 1, 2, 3, 4]
    for name, runner in (("fused", fused), ("hybrid", hybrid)):
        f_seen, f_out = run(runner)
        assert [i for i, _, _ in f_seen] == [i for i, _, _ in s_seen], name
        assert [t for _, t, _ in f_seen] == [t for _, t, _ in s_seen], name
        for (_, _, xa), (_, _, xb) in zip(f_seen, s_seen):
            np.testing.assert_allclose(xa, xb, atol=2e-4)
        np.testing.assert_allclose(f_out, s_out, atol=2e-4)
        # the last callback sees exactly the returned latents
        np.testing.assert_allclose(f_seen[-1][2], f_out, atol=0)

    # img2img entry: the compiled-callback loop honors start_step
    s2, _ = run(stepw, start_step=2)
    f2, _ = run(fused, start_step=2)
    assert [i for i, _, _ in f2] == [i for i, _, _ in s2] == [2, 3, 4]


def test_hybrid_matches_fused(devices8):
    """Hybrid loop (per-step sync warmup + fused stale-only scan) must equal
    the fully fused loop — it is the compile-time-resilient execution of the
    same program."""
    fused, cfg, ucfg = build(devices8, 8, use_cuda_graph=True)
    hybrid, _, _ = build(devices8, 8, use_cuda_graph=True, hybrid_loop=True)
    lat, enc = inputs(cfg, ucfg)
    a = np.asarray(fused.generate(lat, enc, num_inference_steps=5))
    b = np.asarray(hybrid.generate(lat, enc, num_inference_steps=5))
    np.testing.assert_allclose(a, b, atol=2e-4)
    # all-sync short runs take the pure stepwise path inside hybrid
    a2 = np.asarray(fused.generate(lat, enc, num_inference_steps=2))
    b2 = np.asarray(hybrid.generate(lat, enc, num_inference_steps=2))
    np.testing.assert_allclose(a2, b2, atol=2e-4)


# CPU-compile-heavy module: the fake 8-device mesh compiles full
# multi-device denoise loops, minutes per test on the tier-1 CPU runner.
# Runs with `-m slow` and on real-hardware rounds.
pytestmark = pytest.mark.slow
