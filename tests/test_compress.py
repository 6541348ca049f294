"""Quantized stale-refresh exchange (parallel/compress.py, comm_compress):
round-trip error bounds, stale-phase parity on all three model families at
pinned tolerances, warmup bit-exactness, fused-vs-stepwise equality,
carry-pytree identity across the sync/stale/shallow bodies, byte-accurate
comm accounting, the serve key surface, and (slow) the HLO proof that the
quantize/dequantize converts stay on the deferred path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distrifuser_tpu import DistriConfig
from distrifuser_tpu.models import dit as dit_mod
from distrifuser_tpu.models import mmdit as mm
from distrifuser_tpu.models.unet import init_unet_params, tiny_config
from distrifuser_tpu.parallel import compress
from distrifuser_tpu.parallel.dit_sp import DiTDenoiseRunner
from distrifuser_tpu.parallel.mmdit_sp import MMDiTDenoiseRunner
from distrifuser_tpu.parallel.runner import DenoiseRunner
from distrifuser_tpu.schedulers import get_scheduler
from jax import shard_map

MODES = ["int8", "int8_residual"] + (["fp8"] if compress.fp8_supported()
                                     else [])


# ---------------------------------------------------------------------------
# quantizer round trips
# ---------------------------------------------------------------------------


def test_int8_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 64)) * 3.0
    q, s = compress.quantize(x, "int8")
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert s.shape == x.shape[:-1]  # one fp32 scale per tile
    back = compress.dequantize(q, s, x.dtype)
    # symmetric rounding: |err| <= scale/2 per tile, scale = amax/127
    amax = np.abs(np.asarray(x)).max(axis=-1)
    bound = amax / 127.0 / 2.0 + 1e-7
    err = np.abs(np.asarray(back) - np.asarray(x)).max(axis=-1)
    assert (err <= bound).all(), (err / amax).max()


@pytest.mark.skipif(not compress.fp8_supported(), reason="no float8_e4m3fn")
def test_fp8_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64)) * 3.0
    q, s = compress.quantize(x, "fp8")
    assert q.dtype == compress.fp8_dtype()
    back = np.asarray(compress.dequantize(q, s, x.dtype))
    xn = np.asarray(x)
    # e4m3 keeps ~3 mantissa bits: per-element relative error <= 2^-3 of
    # the magnitude, plus the subnormal floor near the tile scale
    amax = np.abs(xn).max(axis=-1, keepdims=True)
    bound = np.abs(xn) * 2.0**-3 + amax / 448.0
    assert (np.abs(back - xn) <= bound).all()


def test_quantize_preserves_exact_zeros():
    """Edge-device halos are exact zeros (image-border padding); the
    quantizer must keep them exact, including all-zero tiles."""
    x = jnp.zeros((2, 3, 8))
    for mode in MODES:
        q, s = compress.quantize(x, mode)
        assert not np.asarray(compress.dequantize(q, s, x.dtype)).any()
        assert np.isfinite(np.asarray(s)).all()


def test_wire_nbytes():
    # fp32 tensor, 8-wide tiles: 4 bytes/elem -> 1 byte/elem + 4/8 scale
    assert compress.wire_nbytes((2, 4, 8), 4, "none") == 256
    assert compress.wire_nbytes((2, 4, 8), 4, "int8") == 64 + 8 * 4
    assert compress.wire_nbytes((2, 4, 8), 2, "none") == 128
    # quantized wire cost is itemsize-independent (payload is 1 byte)
    assert compress.wire_nbytes((2, 4, 8), 2, "fp8") == \
        compress.wire_nbytes((2, 4, 8), 4, "int8_residual")


# ---------------------------------------------------------------------------
# config / runner validation
# ---------------------------------------------------------------------------


def test_config_validation():
    kw = dict(devices=jax.devices()[:1], height=128, width=128)
    with pytest.raises(ValueError, match="comm_compress"):
        DistriConfig(comm_compress="int4", **kw)
    with pytest.raises(ValueError, match="stale refresh"):
        DistriConfig(comm_compress="int8", parallelism="naive_patch", **kw)
    with pytest.raises(ValueError, match="stale refresh"):
        DistriConfig(comm_compress="int8", parallelism="tensor", **kw)
    # DiT: only the gather layout has a refresh collective to compress
    dcfg = dit_mod.tiny_dit_config()
    dparams = dit_mod.init_dit_params(jax.random.PRNGKey(0), dcfg)
    for impl in ("ring", "ulysses"):
        cfg = DistriConfig(devices=jax.devices()[:2],
                           height=dcfg.sample_size * 8,
                           width=dcfg.sample_size * 8, split_batch=False,
                           comm_compress="int8", attn_impl=impl)
        with pytest.raises(ValueError, match="refresh collective"):
            DiTDenoiseRunner(cfg, dcfg, dparams, get_scheduler("ddim"))
    mcfg = mm.tiny_mmdit_config()
    mparams = mm.init_mmdit_params(jax.random.PRNGKey(0), mcfg)
    cfg = DistriConfig(devices=jax.devices()[:2],
                       height=mcfg.sample_size * 8,
                       width=mcfg.sample_size * 8, split_batch=False,
                       comm_compress="int8", attn_impl="ring")
    with pytest.raises(ValueError, match="refresh collective"):
        MMDiTDenoiseRunner(cfg, mcfg, mparams, get_scheduler("flow-euler"))


# ---------------------------------------------------------------------------
# UNet: parity, warmup exactness, stepwise/batched equality
# (2-device displaced meshes keep the tier-1 compile budget small; the
# 8-device variants run in the slow block)
# ---------------------------------------------------------------------------


def _unet_runner(n, **kw):
    # split_batch=False folds CFG into the batch dim, so BOTH devices of
    # the 2-dev mesh are sp peers and the refresh exchange actually exists
    # (a 2-dev cfg-split mesh is sp=1: nothing to compress)
    kw.setdefault("warmup_steps", 1)
    kw.setdefault("split_batch", False)
    cfg = DistriConfig(devices=jax.devices()[:n], height=128, width=128,
                       parallelism="patch", **kw)
    ucfg = tiny_config(sdxl=False)
    params = init_unet_params(jax.random.PRNGKey(0), ucfg)
    return DenoiseRunner(cfg, ucfg, params, get_scheduler("ddim")), cfg, ucfg


def _unet_inputs(cfg, ucfg):
    k = jax.random.PRNGKey(42)
    lat = jax.random.normal(
        k, (1, cfg.latent_height, cfg.latent_width, ucfg.in_channels))
    enc = jax.random.normal(
        jax.random.fold_in(k, 1), (2, 1, 7, ucfg.cross_attention_dim))
    return lat, enc


# Pinned stale-parity tolerances (relative max vs the uncompressed run),
# measured on the tiny config at 4-device cfg2xsp2, 6 steps: int8 9.6e-4,
# fp8 2.9e-3, int8_residual 5.9e-4 (the closed-loop delta coder is the
# tightest, as designed).  Margins ~5-10x for platform variation; all far
# below the 0.35 displaced-mode gate in test_runner.py.
UNET_TOL = {"int8": 0.01, "fp8": 0.03, "int8_residual": 0.005}


def test_unet_stale_parity():
    """One baseline compile, every mode checked against it (a parametrized
    split would recompile the uncompressed program per case — minutes of
    tier-1 budget for no extra coverage)."""
    r_off, cfg, ucfg = _unet_runner(2)
    lat, enc = _unet_inputs(cfg, ucfg)
    a = np.asarray(r_off.generate(lat, enc, num_inference_steps=5))
    for mode in MODES:
        r_on, _, _ = _unet_runner(2, comm_compress=mode)
        b = np.asarray(r_on.generate(lat, enc, num_inference_steps=5))
        assert np.isfinite(b).all()
        rel = np.abs(a - b).max() / (np.abs(a).max() + 1e-6)
        assert rel < UNET_TOL[mode], f"{mode} drift {rel}"
        assert rel > 0, f"{mode} bit-identical: compression dead?"


def test_unet_warmup_bit_exact():
    """A run that never leaves warmup is bit-identical with compression on:
    sync exchanges never compress."""
    r_off, cfg, ucfg = _unet_runner(2, warmup_steps=4)
    r_on, _, _ = _unet_runner(2, warmup_steps=4,
                              comm_compress="int8_residual")
    lat, enc = _unet_inputs(cfg, ucfg)
    a = np.asarray(r_off.generate(lat, enc, num_inference_steps=3))
    b = np.asarray(r_on.generate(lat, enc, num_inference_steps=3))
    np.testing.assert_array_equal(a, b)


def test_unet_stepwise_and_batched_match_fused():
    """The host-driven stepwise loop and the comm_batch flat exchange must
    reproduce the fused compressed program exactly — the quantize/exchange/
    dequantize round trip is the same computation in all three."""
    r_f, cfg, ucfg = _unet_runner(2, comm_compress="int8_residual")
    r_sw, _, _ = _unet_runner(2, comm_compress="int8_residual",
                              use_cuda_graph=False)
    r_bc, _, _ = _unet_runner(2, comm_compress="int8_residual",
                              comm_batch=True)
    lat, enc = _unet_inputs(cfg, ucfg)
    a = np.asarray(r_f.generate(lat, enc, num_inference_steps=5))
    b = np.asarray(r_sw.generate(lat, enc, num_inference_steps=5))
    c = np.asarray(r_bc.generate(lat, enc, num_inference_steps=5))
    np.testing.assert_allclose(a, b, atol=2e-4)
    np.testing.assert_allclose(a, c, atol=2e-4)


@pytest.mark.slow
def test_unet_stepcache_composition():
    """Compression composes with the full/shallow cadence: finite output,
    stepwise replay equality, and the shallow phase's refresh bytes stay
    strictly below the full stale phase's.  Slow: the cadence program
    carries three step bodies (sync + full + shallow) — the most expensive
    compile in this module, and the byte assertion below also runs
    compile-free in test_bytes_report_* for tier-1."""
    kw = dict(comm_compress="int8", step_cache_interval=2,
              step_cache_depth=1)
    r_on, cfg, ucfg = _unet_runner(2, **kw)
    r_sw, _, _ = _unet_runner(2, use_cuda_graph=False, **kw)
    lat, enc = _unet_inputs(cfg, ucfg)
    a = np.asarray(r_on.generate(lat, enc, num_inference_steps=6))
    b = np.asarray(r_sw.generate(lat, enc, num_inference_steps=6))
    assert np.isfinite(a).all()
    np.testing.assert_allclose(a, b, atol=2e-4)
    rep = r_on.comm_volume_report(per_phase=True)
    assert sum(rep["bytes"]["shallow"].values()) < sum(
        rep["bytes"]["stale"].values())


# ---------------------------------------------------------------------------
# carry-pytree identity across sync / stale / shallow bodies
# ---------------------------------------------------------------------------


def _state_struct(runner, step, pstate_in):
    """eval_shape one step body's emitted patch state through the same
    shard_map harness the comm report uses."""
    cfg = runner.cfg
    runner.scheduler.set_timesteps(4)
    lat, enc, added, gs = runner._abstract_inputs(per_group=True)
    has_state = pstate_in is not None

    def one_step(params, latents, enc, added, gs, *maybe_state):
        my_enc, my_added, _ = runner._branch_inputs(enc, added)
        from distrifuser_tpu.models.unet import precompute_text_kv

        text_kv = precompute_text_kv(params, my_enc)
        sstate = runner.scheduler.init_state(latents.shape)
        _, pout, _ = step(
            params, 1, latents.astype(jnp.float32),
            maybe_state[0] if has_state else None, sstate,
            my_enc, my_added, text_kv, gs,
        )
        return pout

    args = (runner.params, lat, enc, added, gs)
    specs = (runner.param_specs, P(), P(), P(), P())
    if has_state:
        args += (pstate_in,)
        specs += (P(),)
    return jax.eval_shape(
        lambda *a: shard_map(one_step, mesh=cfg.mesh, in_specs=specs,
                             out_specs=P(), check_vma=False)(*a),
        *args,
    )


@pytest.mark.parametrize("mode", ["int8", "int8_residual"])
def test_carry_pytree_identity(mode):
    """The sync-seeded carry must be structurally identical (names, shapes,
    dtypes) to what the stale and shallow bodies return — a lax.scan carry
    cannot change structure, and residual mode's own-rows entries must be
    present in every phase."""
    from distrifuser_tpu.parallel.context import OWN_SUFFIX
    from distrifuser_tpu.parallel.runner import PHASE_STALE, PHASE_SYNC

    r, _, _ = _unet_runner(2, comm_compress=mode, step_cache_interval=2,
                           step_cache_depth=1)
    sync = _state_struct(r, r._make_step(PHASE_SYNC), None)
    stale = _state_struct(r, r._make_step(PHASE_STALE), sync)
    shallow = _state_struct(r, r._make_step(PHASE_STALE, shallow=True), sync)

    def desc(tree):
        return {k: (v.shape, str(v.dtype)) for k, v in tree.items()}

    assert desc(sync) == desc(stale) == desc(shallow)
    has_own = any(k.endswith(OWN_SUFFIX) for k in sync)
    assert has_own == (mode == "int8_residual")


# ---------------------------------------------------------------------------
# byte-accurate comm accounting (eval_shape only: no compiles, so the
# acceptance-criterion mesh runs in tier-1)
# ---------------------------------------------------------------------------


def test_bytes_report_int8_reduction(devices8):
    """Acceptance: >= 1.9x stale-phase refresh BYTE reduction at int8 on
    the tiny config, with warmup/sync traffic byte-identical to "none"."""
    def rep(mode):
        cfg = DistriConfig(devices=devices8, height=128, width=128,
                           warmup_steps=1, parallelism="patch",
                           comm_compress=mode)
        ucfg = tiny_config(sdxl=False)
        params = init_unet_params(jax.random.PRNGKey(0), ucfg)
        r = DenoiseRunner(cfg, ucfg, params, get_scheduler("ddim"))
        return r.comm_volume_report(per_phase=True)

    off, on = rep("none"), rep("int8")
    assert off["bytes"]["sync"] == on["bytes"]["sync"]
    # element counts are mode-independent (the carry stays full precision)
    assert off["phases"] == on["phases"]
    s_off = sum(off["bytes"]["stale"].values())
    s_on = sum(on["bytes"]["stale"].values())
    assert s_off / s_on >= 1.9, (off["bytes"]["stale"], on["bytes"]["stale"])
    # the compressed kinds individually shrink; gn stays full precision
    for kind in ("attn", "conv2d"):
        assert on["bytes"]["stale"][kind] < off["bytes"]["stale"][kind]
    assert on["bytes"]["stale"]["gn"] == off["bytes"]["stale"]["gn"]


def test_bytes_report_shallow_below_stale(devices8):
    """Step-cache composition, compile-free half: under the cadence the
    shallow phase's fresh refresh bytes stay strictly below the full stale
    phase's (the numeric-equality half runs in the slow
    test_unet_stepcache_composition)."""
    cfg = DistriConfig(devices=devices8, height=128, width=128,
                       warmup_steps=1, parallelism="patch",
                       comm_compress="int8", step_cache_interval=2,
                       step_cache_depth=1)
    ucfg = tiny_config(sdxl=False)
    params = init_unet_params(jax.random.PRNGKey(0), ucfg)
    r = DenoiseRunner(cfg, ucfg, params, get_scheduler("ddim"))
    rep = r.comm_volume_report(per_phase=True)
    assert sum(rep["bytes"]["shallow"].values()) < sum(
        rep["bytes"]["stale"].values())


def test_bytes_report_residual_own_rows_are_wire_free(devices8):
    cfg = DistriConfig(devices=devices8, height=128, width=128,
                       warmup_steps=1, parallelism="patch",
                       comm_compress="int8_residual")
    ucfg = tiny_config(sdxl=False)
    params = init_unet_params(jax.random.PRNGKey(0), ucfg)
    r = DenoiseRunner(cfg, ucfg, params, get_scheduler("ddim"))
    rep = r.comm_volume_report(per_phase=True)
    # own-rows ride the carry (elements > 0) but never the wire (bytes == 0)
    assert rep["phases"]["stale"].get("local", 0) > 0
    assert rep["bytes"]["stale"].get("local", 1) == 0
    assert rep["bytes"]["sync"].get("local", 1) == 0


def test_dit_mmdit_closed_form_bytes():
    dcfg = dit_mod.tiny_dit_config()
    dparams = dit_mod.init_dit_params(jax.random.PRNGKey(0), dcfg)

    def dit_rep(mode):
        cfg = DistriConfig(devices=jax.devices()[:2],
                           height=dcfg.sample_size * 8,
                           width=dcfg.sample_size * 8, split_batch=False,
                           comm_compress=mode)
        return DiTDenoiseRunner(cfg, dcfg, dparams,
                                get_scheduler("ddim")).comm_report()

    off, on = dit_rep("none"), dit_rep("int8")
    assert on["sync_step_collective_bytes"] == off["sync_step_collective_bytes"]
    assert off["per_step_collective_bytes"] / on["per_step_collective_bytes"] \
        >= 1.9
    mcfg = mm.tiny_mmdit_config()
    mparams = mm.init_mmdit_params(jax.random.PRNGKey(0), mcfg)

    def mm_rep(mode):
        cfg = DistriConfig(devices=jax.devices()[:2],
                           height=mcfg.sample_size * 8,
                           width=mcfg.sample_size * 8, split_batch=False,
                           comm_compress=mode)
        return MMDiTDenoiseRunner(cfg, mcfg, mparams,
                                  get_scheduler("flow-euler")).comm_report()

    off, on = mm_rep("none"), mm_rep("int8_residual")
    assert off["per_step_collective_bytes"] / on["per_step_collective_bytes"] \
        >= 1.9


def test_phase_step_counts():
    from distrifuser_tpu.parallel.stepcache import phase_step_counts

    assert phase_step_counts(10, 1, 1) == {"sync": 2, "stale": 8,
                                           "shallow": 0}
    assert phase_step_counts(10, 1, 2) == {"sync": 2, "stale": 4,
                                           "shallow": 4}
    assert phase_step_counts(2, 4, 2) == {"sync": 2, "stale": 0,
                                          "shallow": 0}
    assert phase_step_counts(0, 1, 2) == {"sync": 0, "stale": 0,
                                          "shallow": 0}


# ---------------------------------------------------------------------------
# DiT / MMDiT stale parity
# ---------------------------------------------------------------------------


# Measured at 4-device, 6 steps: DiT int8 1.1e-5 / fp8 5.4e-5 / residual
# 2.3e-6; MMDiT int8 1.9e-5 / residual 2.2e-6.  The transformer KV payload
# is far less error-sensitive than the UNet's halo rows (attention softmax
# averages the perturbation); pin at ~20x margin.
DIT_TOL = {"int8": 1e-3, "fp8": 2e-3, "int8_residual": 5e-4}


def test_dit_stale_parity():
    dcfg = dit_mod.tiny_dit_config()
    params = dit_mod.init_dit_params(jax.random.PRNGKey(0), dcfg)
    k = jax.random.PRNGKey(3)
    lat = jax.random.normal(
        k, (1, dcfg.sample_size, dcfg.sample_size, dcfg.in_channels))
    enc = jax.random.normal(
        jax.random.fold_in(k, 1), (2, 1, 8, dcfg.caption_dim))

    def mk(**kw):
        cfg = DistriConfig(devices=jax.devices()[:2],
                           height=dcfg.sample_size * 8,
                           width=dcfg.sample_size * 8, warmup_steps=1,
                           split_batch=False, **kw)
        return DiTDenoiseRunner(cfg, dcfg, params, get_scheduler("ddim"))

    a = np.asarray(mk().generate(lat, enc, num_inference_steps=5))
    for mode in ("int8", "int8_residual"):
        b = np.asarray(mk(comm_compress=mode).generate(
            lat, enc, num_inference_steps=5))
        assert np.isfinite(b).all()
        rel = np.abs(a - b).max() / (np.abs(a).max() + 1e-6)
        assert 0 < rel < DIT_TOL[mode], f"DiT {mode} drift {rel}"


def test_mmdit_stale_parity():
    mcfg = mm.tiny_mmdit_config()
    params = mm.init_mmdit_params(jax.random.PRNGKey(0), mcfg)
    k = jax.random.PRNGKey(7)
    lat = jax.random.normal(
        k, (1, mcfg.sample_size, mcfg.sample_size, mcfg.in_channels))
    enc = jax.random.normal(
        jax.random.fold_in(k, 1), (2, 1, 5, mcfg.joint_attention_dim))
    pooled = jax.random.normal(
        jax.random.fold_in(k, 2), (2, 1, mcfg.pooled_projection_dim))

    def mk(**kw):
        cfg = DistriConfig(devices=jax.devices()[:2],
                           height=mcfg.sample_size * 8,
                           width=mcfg.sample_size * 8, warmup_steps=1,
                           split_batch=False, **kw)
        return MMDiTDenoiseRunner(cfg, mcfg, params,
                                  get_scheduler("flow-euler"))

    a = np.asarray(mk().generate(lat, enc, pooled, num_inference_steps=5))
    b = np.asarray(mk(comm_compress="int8_residual").generate(
        lat, enc, pooled, num_inference_steps=5))
    assert np.isfinite(b).all()
    rel = np.abs(a - b).max() / (np.abs(a).max() + 1e-6)
    assert 0 < rel < DIT_TOL["int8_residual"], f"MMDiT drift {rel}"


# ---------------------------------------------------------------------------
# serve surfaces
# ---------------------------------------------------------------------------


def test_serve_exec_key_comm_compress():
    from distrifuser_tpu.serve.cache import ExecKey
    from distrifuser_tpu.utils.config import ServeConfig

    base = dict(model_id="m", scheduler="ddim", height=512, width=512,
                steps=8, cfg=True, mesh_plan="dp1.cfg1.sp1")
    k_off = ExecKey(**base)
    k_on = ExecKey(**base, comm_compress="int8")
    # two requests differing only in compression must not share an executor
    assert k_off != k_on
    assert ":int8" in k_on.short() and ":int8" not in k_off.short()
    with pytest.raises(ValueError, match="comm_compress"):
        ExecKey(**base, comm_compress="lz4")
    with pytest.raises(ValueError, match="comm_compress"):
        ServeConfig(comm_compress="lz4")
    cfg = ServeConfig(comm_compress="int8_residual")
    assert cfg.comm_compress == "int8_residual"


def test_serve_server_threads_comm_compress():
    from distrifuser_tpu.serve.server import InferenceServer
    from distrifuser_tpu.serve.testing import FakeExecutorFactory
    from distrifuser_tpu.utils.config import ServeConfig

    cfg = ServeConfig(comm_compress="int8", warmup_buckets=((512, 512, 4),))
    srv = InferenceServer(FakeExecutorFactory(batch_size=2), cfg,
                          model_id="m")
    keys = srv._warmup_keys()
    assert keys and all(k.comm_compress == "int8" for k in keys)


def test_apply_key_policy_forces_compress_off():
    from distrifuser_tpu.serve.cache import ExecKey
    from distrifuser_tpu.serve.executors import apply_key_policy

    class _Pipe:
        def __init__(self, dcfg):
            self.distri_config = dcfg

    dcfg = DistriConfig(devices=jax.devices()[:1], height=128, width=128,
                        comm_compress="int8")
    pipe = _Pipe(dcfg)
    key = ExecKey(model_id="m", scheduler="ddim", height=128, width=128,
                  steps=4, cfg=True, mesh_plan="dp1.cfg1.sp1")
    apply_key_policy(pipe, key)
    assert dcfg.comm_compress == "none"


def test_comm_plan_raises_without_byte_model():
    """A runner with no byte-modeled comm report must make comm_plan
    RAISE, not hand back a confident-looking empty plan (the PipeFusion
    carve-out used to return total_bytes=None silently; every first-party
    runner now carries a byte model, so reaching the fallback is a bug in
    the runner, not a condition to paper over)."""
    import types

    from distrifuser_tpu.pipelines import _GenerationMixin

    class Shell(_GenerationMixin):
        def __init__(self):
            self.distri_config = types.SimpleNamespace(
                comm_compress="none", warmup_steps=1,
                step_cache_interval=1, step_cache_depth=0,
                step_cache_enabled=False)
            self.runner = object()  # neither comm_volume_report nor comm_report

    with pytest.raises(ValueError, match="byte-model"):
        Shell().comm_plan(4)


def test_pipeline_comm_plan(devices8):
    from test_pipelines import build_sd_pipeline

    pipe, _ = build_sd_pipeline(devices8, 2, comm_compress="int8",
                                warmup_steps=1, split_batch=False)
    plan = pipe.comm_plan(6)
    assert plan["comm_compress"] == "int8"
    assert plan["steps"] == {"sync": 2, "stale": 4, "shallow": 0}
    assert plan["bytes_per_step"]["stale"] < plan["bytes_per_step"]["sync"]
    assert plan["total_bytes"] == (
        2 * plan["bytes_per_step"]["sync"] + 4 * plan["bytes_per_step"]["stale"]
    )


# ---------------------------------------------------------------------------
# HLO: the quantize/dequantize converts stay on the deferred path
# (8-device compiles: minutes on the tier-1 CPU runner -> slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_hlo_compressed_refresh_stays_deferred(devices8):
    """The compressed stale body must keep every refresh collective off the
    inline (serializing) path: payload + scale exchanges classify deferred
    or deferred_compute (carry-only through the dequantize's elementwise
    convert/multiply/add chain, utils/overlap.py elementwise_carry), the
    inline set stays exactly the uncompressed program's (the per-step
    output gather), and the collective COUNT doubles (payload + scale per
    refresh) — proof the scales ride their own exchange rather than
    widening the payload."""
    from distrifuser_tpu.models import unet as unet_mod
    from distrifuser_tpu.utils.overlap import analyze_loop_collectives

    ucfg = unet_mod.tiny_config(sdxl=False)
    params = unet_mod.init_unet_params(jax.random.PRNGKey(0), ucfg)
    depth = len(ucfg.block_out_channels) - 1

    def hlo(**kw):
        cfg = DistriConfig(
            devices=devices8, height=8 * 8 * (1 << depth) * 2, width=128,
            warmup_steps=1, parallelism="patch", mode="separate_gn", **kw,
        )
        runner = DenoiseRunner(cfg, ucfg, params, get_scheduler("ddim"))
        lat = jnp.zeros(
            (1, cfg.latent_height, cfg.latent_width, ucfg.in_channels))
        enc = jnp.zeros((2, 1, 7, ucfg.cross_attention_dim))
        fn = runner._build(6)
        return fn.lower(params, lat, enc, None, 5.0).compile().as_text()

    def pick_stale(reports):
        assert reports, "no while-loop collectives found"
        return max(reports, key=lambda r: r.n_deferred + r.n_deferred_compute)

    def count(rep, prefix, *buckets):
        return sum(1 for b in buckets
                   for op in getattr(rep, b).values() if op.startswith(prefix))

    off = pick_stale(analyze_loop_collectives(hlo(), elementwise_carry=True))
    on = pick_stale(analyze_loop_collectives(
        hlo(comm_compress="int8_residual"), elementwise_carry=True))

    # nothing new serializes: the inline opcode multiset is unchanged
    assert sorted(on.inline.values()) == sorted(off.inline.values()), (
        on.inline, off.inline)
    # the dequantize chains exist and classify deferred-compute, not inline
    assert on.n_deferred_compute > 0, (on.deferred, on.inline)
    # in the uncompressed body the refresh collectives are exactly the
    # pure-data-movement `deferred` set; compressed, each becomes a payload
    # + scale PAIR riding the dequant chain (deferred_compute), while any
    # carry-only-through-arithmetic collective the baseline already had
    # (off.deferred_compute) is not refresh traffic and stays single
    for prefix in ("all-gather", "collective-permute"):
        n_refresh_off = count(off, prefix, "deferred")
        n_other_off = count(off, prefix, "deferred_compute")
        n_on = count(on, prefix, "deferred", "deferred_compute")
        assert n_refresh_off > 0 or prefix == "all-gather", prefix
        assert n_on == 2 * n_refresh_off + n_other_off, (
            prefix, n_on, n_refresh_off, n_other_off)


@pytest.mark.slow
def test_unet_multi_device_parity_8dev(devices8):
    """Displaced 8-device (cfg 2 x sp 4) parity at the pinned tolerances,
    all modes, against the uncompressed run."""
    r_off, cfg, ucfg = _unet_runner(8)
    lat, enc = _unet_inputs(cfg, ucfg)
    a = np.asarray(r_off.generate(lat, enc, num_inference_steps=6))
    for mode in MODES:
        r_on, _, _ = _unet_runner(8, comm_compress=mode)
        b = np.asarray(r_on.generate(lat, enc, num_inference_steps=6))
        assert np.isfinite(b).all()
        rel = np.abs(a - b).max() / (np.abs(a).max() + 1e-6)
        assert 0 < rel < UNET_TOL[mode], f"{mode} 8-dev drift {rel}"


@pytest.mark.slow
def test_residual_drift_does_not_accumulate():
    """Closed-loop DPCM regression: the int8_residual delta is taken
    against the RECONSTRUCTED previous value on both the gather path
    (stale-buffer slot) and the halo path (own-rows predictor carry,
    context._halo_record) — so per-step quantization errors cancel
    instead of random-walking.  A 24-step run (22 stale) must drift no
    more than a handful of times the 6-step run; the open-loop bug this
    pins (raw rows as predictor) grew linearly with step count."""
    r_off, cfg, ucfg = _unet_runner(4)
    r_res, _, _ = _unet_runner(4, comm_compress="int8_residual")
    lat, enc = _unet_inputs(cfg, ucfg)

    def drift(steps):
        a = np.asarray(r_off.generate(lat, enc, num_inference_steps=steps))
        b = np.asarray(r_res.generate(lat, enc, num_inference_steps=steps))
        return np.abs(a - b).max() / (np.abs(a).max() + 1e-6)

    d6, d24 = drift(6), drift(24)
    # measured: 4.4e-4 at 6 steps, 3.3e-4 at 24 — flat.  3x leaves noise
    # margin while an accumulating coder (~4x more stale steps) fails.
    assert d24 < 3 * d6 + 1e-5, (d6, d24)
