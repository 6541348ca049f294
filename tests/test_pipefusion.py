"""PipeFusion patch-pipeline tests.

The oracle here is a *sequential* single-device implementation of the exact
PipeFusion schedule (items processed in submission order, per-block KV
caches committed as each item flows through the whole stack, scheduler
updates applied with the pipeline's P-tick delay).  Equivalence of the
mesh-parallel runner against this oracle pins the displaced semantics; the
warmup-only path is additionally pinned against a plain dense scheduler
loop, which the pipeline must reproduce exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distrifuser_tpu.models import dit as dit_mod
from distrifuser_tpu.parallel.pipefusion import PipeFusionRunner
from distrifuser_tpu.schedulers import get_scheduler
from distrifuser_tpu.utils.config import DistriConfig


def make_model(depth=8, seed=0):
    dcfg = dit_mod.tiny_dit_config(depth=depth)
    params = dit_mod.init_dit_params(jax.random.PRNGKey(seed), dcfg)
    return dcfg, params


def make_inputs(dcfg, batch=1, text_len=8, seed=1):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    lat = jax.random.normal(
        k1, (batch, dcfg.sample_size, dcfg.sample_size, dcfg.in_channels),
        jnp.float32,
    )
    enc = jax.random.normal(k2, (2, batch, text_len, dcfg.caption_dim), jnp.float32)
    return lat, enc


# ---------------------------------------------------------------------------
# oracle
# ---------------------------------------------------------------------------


def _stack_state(sched, n_patch, batch, chunk, dim):
    return jax.vmap(lambda _: sched.init_state((batch, chunk, dim)))(
        jnp.arange(n_patch)
    )


def _tree_at(tree, i):
    return jax.tree.map(lambda l: l[i], tree)


def _tree_set(tree, sub, i):
    return jax.tree.map(
        lambda l, s: l.at[i].set(jnp.asarray(s, l.dtype)), tree, sub
    )


def oracle_generate(params, dcfg, sched, latents, enc, gs, num_steps,
                    warmup_steps, n_stage, n_patch, do_cfg=True):
    """Sequential reference implementation of the PipeFusion schedule."""
    sched.set_timesteps(num_steps)
    ts = sched.timesteps()
    x = dit_mod.patchify(dcfg, latents.astype(jnp.float32))  # [B, N, D]
    batch, n_tok, d_in = x.shape
    chunk = n_tok // n_patch
    n_sync = min(warmup_steps + 1, num_steps)
    hid = dcfg.hidden_size
    pos = dit_mod.pos_embed_table(dcfg, jnp.float32)
    branches = (0, 1) if do_cfg else (0,)

    cap_kv = {
        br: dit_mod.precompute_caption_kv(params, dcfg, enc[br])
        for br in branches
    }
    cache = {
        br: [
            (jnp.zeros((batch, n_tok, hid)), jnp.zeros((batch, n_tok, hid)))
            for _ in range(dcfg.depth)
        ]
        for br in branches
    }
    sstate = _stack_state(sched, n_patch, batch, chunk, d_in)

    def run_rows(br, tokens, s, offset):
        """Embed + all blocks + final for a token range, committing caches."""
        temb = dit_mod.t_embed(params, dcfg, ts[s])
        c6 = dit_mod.adaln_table(params, dcfg, temb)
        pos_rows = lax_slice(pos, offset, tokens.shape[1])
        h = dit_mod.embed_tokens(params, dcfg, tokens, pos_rows)
        for l in range(dcfg.depth):
            bp = _tree_at(params["blocks"], l)
            h, (k, v) = dit_mod.dit_block(
                bp, dcfg, h, c6, cap_kv[br][l],
                self_kv=cache[br][l], patch_start=offset,
            )
            ck, cv = cache[br][l]
            cache[br][l] = (
                jax.lax.dynamic_update_slice(ck, k, (0, offset, 0)),
                jax.lax.dynamic_update_slice(cv, v, (0, offset, 0)),
            )
        return dit_mod.final_layer(params, dcfg, h, temb)

    def lax_slice(arr, off, n):
        return jax.lax.dynamic_slice_in_dim(arr, off, n, axis=0)

    def combine(eps_by_branch):
        if not do_cfg:
            return eps_by_branch[0]
        u, c = eps_by_branch[0], eps_by_branch[1]
        return u + gs * (c - u)

    def sched_rows(x, sstate, guided, m, s):
        rows = x[:, m * chunk:(m + 1) * chunk]
        st = _tree_at(sstate, m)
        new_rows, new_st = sched.step(rows, guided.astype(jnp.float32), s, st)
        x = x.at[:, m * chunk:(m + 1) * chunk].set(
            jnp.asarray(new_rows, x.dtype)
        )
        return x, _tree_set(sstate, new_st, m)

    # warmup: full-sequence, fresh, exact
    for s in range(n_sync):
        x_in = sched.scale_model_input(x, s)
        eps = {br: run_rows(br, x_in, s, 0) for br in branches}
        guided = combine(eps)
        for m in range(n_patch):
            x, sstate = sched_rows(
                x, sstate, guided[:, m * chunk:(m + 1) * chunk], m, s
            )

    # steady state: items with the pipeline's P-tick scheduler delay
    n_items = (num_steps - n_sync) * n_patch
    pending = {}
    for q in range(n_items):
        arr = q - n_stage
        if arr >= 0:
            s_a = n_sync + arr // n_patch
            m_a = arr % n_patch
            x, sstate = sched_rows(x, sstate, pending.pop(arr), m_a, s_a)
        s_q = n_sync + q // n_patch
        m_q = q % n_patch
        x_in = sched.scale_model_input(
            x[:, m_q * chunk:(m_q + 1) * chunk], s_q
        )
        eps = {br: run_rows(br, x_in, s_q, m_q * chunk) for br in branches}
        pending[q] = combine(eps)
    for q in sorted(pending):
        s_a = n_sync + q // n_patch
        m_a = q % n_patch
        x, sstate = sched_rows(x, sstate, pending[q], m_a, s_a)

    return dit_mod.unpatchify(dcfg, x, dcfg.in_channels)


def dense_loop(params, dcfg, sched, latents, enc, gs, num_steps, do_cfg=True):
    """Plain full-sequence scheduler loop (no pipeline, no staleness)."""
    sched.set_timesteps(num_steps)
    ts = sched.timesteps()
    x = latents.astype(jnp.float32)
    sstate = sched.init_state(x.shape)
    for s in range(num_steps):
        x_in = sched.scale_model_input(x, s)
        eps_u = dit_mod.dit_forward(params, dcfg, x_in, ts[s], enc[0])
        if do_cfg:
            eps_c = dit_mod.dit_forward(params, dcfg, x_in, ts[s], enc[1])
            guided = eps_u + gs * (eps_c - eps_u)
        else:
            guided = eps_u
        x, sstate = sched.step(x, guided, s, sstate)
    return x


# ---------------------------------------------------------------------------
# tests
# ---------------------------------------------------------------------------


def pipe_config(n_dev, do_cfg, **kw):
    return DistriConfig(
        devices=jax.devices()[:n_dev],
        height=128, width=128,
        do_classifier_free_guidance=do_cfg,
        split_batch=do_cfg,
        parallelism="patch",  # runner ignores; mesh geometry is what matters
        **kw,
    )


def test_warmup_only_matches_dense_loop():
    """All-sync pipeline (warmup covers every step) == dense scheduler loop."""
    dcfg, params = make_model()
    lat, enc = make_inputs(dcfg)
    cfg = pipe_config(4, do_cfg=False, warmup_steps=9)
    runner = PipeFusionRunner(cfg, dcfg, params, get_scheduler("ddim"))
    out = runner.generate(lat, enc, guidance_scale=1.0, num_inference_steps=3)
    ref = dense_loop(params, dcfg, get_scheduler("ddim"), lat, enc, 1.0, 3,
                     do_cfg=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("scheduler", ["ddim", "dpm-solver"])
def test_displaced_matches_oracle(scheduler):
    dcfg, params = make_model()
    lat, enc = make_inputs(dcfg)
    cfg = pipe_config(4, do_cfg=False, warmup_steps=1)
    runner = PipeFusionRunner(cfg, dcfg, params, get_scheduler(scheduler))
    out = runner.generate(lat, enc, guidance_scale=1.0, num_inference_steps=6)
    ref = oracle_generate(
        params, dcfg, get_scheduler(scheduler), lat, enc, 1.0, 6,
        warmup_steps=1, n_stage=4, n_patch=4, do_cfg=False,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_cfg_split_composes():
    """cfg axis (2) x pipeline stages (4) == oracle with guided combine."""
    dcfg, params = make_model()
    lat, enc = make_inputs(dcfg)
    cfg = pipe_config(8, do_cfg=True, warmup_steps=1)
    assert cfg.cfg_split and cfg.n_device_per_batch == 4
    runner = PipeFusionRunner(cfg, dcfg, params, get_scheduler("ddim"))
    out = runner.generate(lat, enc, guidance_scale=3.5, num_inference_steps=5)
    ref = oracle_generate(
        params, dcfg, get_scheduler("ddim"), lat, enc, 3.5, 5,
        warmup_steps=1, n_stage=4, n_patch=4, do_cfg=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_cfg_folded_single_stageline():
    """No cfg split (folded batch CFG) with a 2-stage pipeline."""
    dcfg, params = make_model()
    lat, enc = make_inputs(dcfg)
    cfg2 = DistriConfig(
        devices=jax.devices()[:2], height=128, width=128,
        do_classifier_free_guidance=True, split_batch=False, warmup_steps=1,
    )
    assert not cfg2.cfg_split and cfg2.n_device_per_batch == 2
    runner = PipeFusionRunner(cfg2, dcfg, params, get_scheduler("ddim"))
    out = runner.generate(lat, enc, guidance_scale=3.5, num_inference_steps=4)
    ref = oracle_generate(
        params, dcfg, get_scheduler("ddim"), lat, enc, 3.5, 4,
        warmup_steps=1, n_stage=2, n_patch=2, do_cfg=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_more_patches_than_stages():
    """M = 2P streams fine and still matches the oracle."""
    dcfg, params = make_model()
    lat, enc = make_inputs(dcfg)
    cfg = pipe_config(2, do_cfg=False, warmup_steps=0)
    runner = PipeFusionRunner(cfg, dcfg, params, get_scheduler("ddim"),
                              pipe_patches=4)
    out = runner.generate(lat, enc, guidance_scale=1.0, num_inference_steps=4)
    ref = oracle_generate(
        params, dcfg, get_scheduler("ddim"), lat, enc, 1.0, 4,
        warmup_steps=0, n_stage=2, n_patch=4, do_cfg=False,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_dp_composes():
    """dp(2) x cfg(2) x pipe(2) on 8 devices: each image group must match
    the single-group run of its own batch element."""
    dcfg, params = make_model()
    lat, enc = make_inputs(dcfg, batch=2)
    cfg = DistriConfig(
        devices=jax.devices()[:8], height=128, width=128,
        do_classifier_free_guidance=True, split_batch=True,
        warmup_steps=1, dp_degree=2, batch_size=2,
    )
    assert cfg.dp_degree == 2 and cfg.n_device_per_batch == 2
    runner = PipeFusionRunner(cfg, dcfg, params, get_scheduler("ddim"))
    out = np.asarray(
        runner.generate(lat, enc, guidance_scale=3.0, num_inference_steps=4)
    )
    for i in range(2):
        ref = oracle_generate(
            params, dcfg, get_scheduler("ddim"),
            lat[i:i + 1], enc[:, i:i + 1], 3.0, 4,
            warmup_steps=1, n_stage=2, n_patch=2, do_cfg=True,
        )
        np.testing.assert_allclose(out[i:i + 1], np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


def test_comm_report():
    """Static accounting invariants of the pipeline layout report."""
    dcfg, params = make_model()
    cfg = pipe_config(4, do_cfg=False, warmup_steps=1)
    runner = PipeFusionRunner(cfg, dcfg, params, get_scheduler("ddim"))
    rep = runner.comm_report()
    total = sum(int(np.prod(np.shape(l))) for l in jax.tree.leaves(params))
    assert rep["params_replicated_equiv"] == total
    shared = sum(
        int(np.prod(np.shape(l)))
        for k, v in params.items() if k != "blocks"
        for l in jax.tree.leaves(v)
    )
    # 4 stages x depth 8 -> each device holds shared + 2 blocks
    assert rep["params_per_device"] == shared + (total - shared) // 4
    assert rep["ring_payload_elems_per_tick"] == dcfg.num_tokens // 4 * dcfg.hidden_size
    assert rep["kv_cache_elems_per_device"] == 2 * 2 * dcfg.num_tokens * dcfg.hidden_size


def test_geometry_validation():
    dcfg, params = make_model(depth=6)  # 6 % 4 != 0
    cfg = pipe_config(4, do_cfg=False)
    with pytest.raises(ValueError, match="depth"):
        PipeFusionRunner(cfg, dcfg, params, get_scheduler("ddim"))
    dcfg8, params8 = make_model(depth=8)
    with pytest.raises(ValueError, match="pipe_patches"):
        PipeFusionRunner(pipe_config(4, do_cfg=False), dcfg8, params8,
                         get_scheduler("ddim"), pipe_patches=2)
    with pytest.raises(ValueError, match="sample_size"):
        PipeFusionRunner(
            DistriConfig(devices=jax.devices()[:4], height=256, width=256,
                         do_classifier_free_guidance=False, split_batch=False),
            dcfg8, params8, get_scheduler("ddim"),
        )


def test_full_sync_mode_runs_every_step_exact():
    """mode='full_sync': the displaced schedule must never
    engage — every step runs as the exact mega-patch, matching the dense
    loop even when warmup_steps alone would hand off after one step."""
    dcfg, params = make_model()
    lat, enc = make_inputs(dcfg)
    cfg = pipe_config(4, do_cfg=False, warmup_steps=1, mode="full_sync")
    runner = PipeFusionRunner(cfg, dcfg, params, get_scheduler("ddim"))
    out = runner.generate(lat, enc, guidance_scale=1.0, num_inference_steps=5)
    ref = dense_loop(params, dcfg, get_scheduler("ddim"), lat, enc, 1.0, 5,
                     do_cfg=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_inapplicable_knobs_rejected():
    """no_sync and --no_cuda_graph have no pipeline semantics: loud errors
    beat silently ignoring the request."""
    dcfg, params = make_model()
    with pytest.raises(ValueError, match="no_sync"):
        PipeFusionRunner(pipe_config(4, do_cfg=False, mode="no_sync"),
                         dcfg, params, get_scheduler("ddim"))
    with pytest.raises(ValueError, match="use_cuda_graph"):
        PipeFusionRunner(pipe_config(4, do_cfg=False, use_cuda_graph=False),
                         dcfg, params, get_scheduler("ddim"))


@pytest.mark.parametrize("sched", ["ddim", "dpm-solver"])
def test_hybrid_matches_fused(sched):
    """cfg.hybrid_loop (warmup + steady phases as two one-body programs,
    carry across the jit boundary) must equal the fused loop — incl. the
    per-patch DPM scheduler state crossing the boundary."""
    dcfg, params = make_model()
    lat, enc = make_inputs(dcfg)
    from distrifuser_tpu.parallel.pipefusion import PipeFusionRunner
    from distrifuser_tpu.utils.config import DistriConfig as _DC

    def build(**kw):
        cfg = _DC(devices=jax.devices()[:4], height=128, width=128,
                  warmup_steps=1, **kw)
        return PipeFusionRunner(cfg, dcfg, params, get_scheduler(sched))

    a = np.asarray(build().generate(lat, enc, guidance_scale=4.0,
                                    num_inference_steps=5))
    b = np.asarray(build(hybrid_loop=True).generate(
        lat, enc, guidance_scale=4.0, num_inference_steps=5))
    np.testing.assert_allclose(a, b, atol=2e-4)


# ---------------------------------------------------------------------------
# first-class knob composition (PR 7, ROADMAP item 2): step cache, wire
# compression, quantized weights, and the serve-side pipeline_off rung
# ---------------------------------------------------------------------------


def knob_config(n_dev=2, **kw):
    """2-stage default (the cheapest real pipeline on the CPU runner)."""
    kw.setdefault("warmup_steps", 1)
    return DistriConfig(
        devices=jax.devices()[:n_dev], height=128, width=128,
        do_classifier_free_guidance=False, split_batch=False,
        parallelism="pipefusion", **kw,
    )


def knob_generate(dcfg, params, steps=6, **kw):
    runner = PipeFusionRunner(knob_config(**kw), dcfg, params,
                              get_scheduler("ddim"))
    lat, enc = make_inputs(dcfg)
    return np.asarray(
        runner.generate(lat, enc, guidance_scale=1.0,
                        num_inference_steps=steps)
    )


def test_step_cache_skips_deep_stages_with_pinned_parity():
    """interval=2 x depth=1 (depth counts PIPELINE STAGES): the deep
    stage's pass-through branch must stay within the pinned drift of the
    cadence-off baseline (measured 1.2e-2 on this seed/config)."""
    dcfg, params = make_model(depth=4)
    base = knob_generate(dcfg, params)
    cached = knob_generate(dcfg, params, step_cache_interval=2,
                           step_cache_depth=1)
    assert np.abs(cached - base).max() <= 3e-2
    assert np.isfinite(cached).all()
    # depth must leave stage 0 running: >= stages rejects at construction
    with pytest.raises(ValueError, match="STAGES"):
        PipeFusionRunner(
            knob_config(step_cache_interval=2, step_cache_depth=2),
            dcfg, params, get_scheduler("ddim"),
        )


def test_compressed_hops_parity_pinned():
    """int8 / closed-loop int8_residual ring hops vs the uncompressed
    pipeline: pinned tolerances (measured 1.3e-2 / 4e-3), and the
    residual coder must beat plain int8 — its whole point."""
    dcfg, params = make_model(depth=4)
    base = knob_generate(dcfg, params)
    d_int8 = np.abs(knob_generate(dcfg, params, comm_compress="int8")
                    - base).max()
    d_res = np.abs(
        knob_generate(dcfg, params, comm_compress="int8_residual") - base
    ).max()
    assert d_int8 <= 3e-2
    assert d_res <= 1.2e-2
    assert d_res < d_int8


def test_compressed_warmup_only_bit_identical():
    """Warmup mega-patch hops never compress: a run that never leaves
    warmup is bit-identical with every knob on."""
    dcfg, params = make_model(depth=4)
    base = knob_generate(dcfg, params, steps=3, warmup_steps=9)
    knobs = knob_generate(dcfg, params, steps=3, warmup_steps=9,
                          comm_compress="int8_residual",
                          step_cache_interval=2, step_cache_depth=1)
    np.testing.assert_array_equal(base, knobs)


def test_weight_quant_stage_local_slices():
    """int8-quantized stacked block tree through the depth split: the
    per-(block, out-channel) scales slice along depth exactly like dense
    leaves, with pinned parity vs the dense pipeline."""
    from distrifuser_tpu.models.weights import quantize_params

    dcfg, params = make_model(depth=4)
    base = knob_generate(dcfg, params)
    quant = knob_generate(dcfg, quantize_params(params, "int8"),
                          weight_quant="int8")
    assert np.abs(quant - base).max() <= 6e-2
    assert np.isfinite(quant).all()


def test_all_knobs_acceptance_config():
    """The ISSUE-7 acceptance point: comm_compress='int8_residual' x
    step cache (2x1) x weight_quant='int8' constructs and generates on a
    2-device CPU mesh with pinned parity vs the all-knobs-off baseline."""
    from distrifuser_tpu.models.weights import quantize_params

    dcfg, params = make_model(depth=4)
    base = knob_generate(dcfg, params)
    allk = knob_generate(
        dcfg, quantize_params(params, "int8"), weight_quant="int8",
        comm_compress="int8_residual", step_cache_interval=2,
        step_cache_depth=1,
    )
    assert np.abs(allk - base).max() <= 8e-2
    assert np.isfinite(allk).all()


def test_hybrid_composes_with_compression():
    """The hybrid two-program split must equal the fused loop with the
    residual coder on — the predictor carries cross the jit boundary."""
    dcfg, params = make_model(depth=4)
    fused = knob_generate(dcfg, params, comm_compress="int8_residual")
    hybrid = knob_generate(dcfg, params, comm_compress="int8_residual",
                           hybrid_loop=True)
    np.testing.assert_allclose(fused, hybrid, atol=2e-4)


def test_comm_report_closed_form_bytes():
    """The byte model pipelines.comm_plan consumes: per-hop and per-step
    arithmetic, compression-aware, warmup always full precision."""
    dcfg, params = make_model(depth=4)
    n_tok, hid = dcfg.num_tokens, dcfg.hidden_size
    raw = PipeFusionRunner(knob_config(), dcfg, params,
                           get_scheduler("ddim"))
    rep = raw.comm_report()
    chunk = n_tok // 2
    assert rep["per_hop_bytes"] == chunk * hid * 4  # fp32 chunk
    assert rep["per_step_collective_bytes"] == 2 * rep["per_hop_bytes"]
    assert rep["sync_step_collective_bytes"] == 2 * n_tok * hid * 4
    assert rep["per_step_cfg_gather_bytes"] == 0  # no cfg axis here
    comp = PipeFusionRunner(knob_config(comm_compress="int8"), dcfg,
                            params, get_scheduler("ddim"))
    crep = comp.comm_report()
    assert crep["per_hop_bytes"] == chunk * hid + chunk * 4  # payload+scales
    # warmup hops never compress: sync bytes identical across modes
    assert crep["sync_step_collective_bytes"] == rep["sync_step_collective_bytes"]
    sc = PipeFusionRunner(
        knob_config(step_cache_interval=2, step_cache_depth=1), dcfg,
        params, get_scheduler("ddim"),
    ).comm_report()
    # hops persist on shallow steps: the report must say bytes are equal,
    # never imply a wire saving the schedule does not deliver
    assert (sc["step_cache"]["shallow_per_step_collective_elems"]
            == sc["per_step_collective_elems"])


def test_serve_pipeline_off_rebuilds_bit_identical_to_patch():
    """End-to-end serve acceptance: a pipefusion bucket OOM-injected at
    execute falls down the pipeline_off rung and its rebuilt executor is
    the patch bucket's — images bit-identical to a server that was
    patch-parallel all along."""
    from distrifuser_tpu.models.vae import init_vae_params, tiny_vae_config
    from distrifuser_tpu.pipelines import DistriPixArtPipeline
    from distrifuser_tpu.serve import InferenceServer, ServeConfig
    from distrifuser_tpu.serve.executors import pipeline_executor_factory
    from distrifuser_tpu.serve.faults import FaultPlan, FaultRule
    from distrifuser_tpu.utils.config import ResilienceConfig

    dcfg, params = make_model(depth=4)
    vcfg = tiny_vae_config()
    vparams = init_vae_params(jax.random.PRNGKey(1), vcfg)

    def build(key):
        cfg = DistriConfig(
            devices=jax.devices()[:2], height=key.height, width=key.width,
            do_classifier_free_guidance=key.cfg, split_batch=False,
            warmup_steps=1, parallelism=key.parallelism,
            pipe_patches=key.pipe_patches or None,
            batch_size=1,
        )
        return DistriPixArtPipeline.from_params(cfg, dcfg, params, vcfg,
                                                vparams)

    def serve_images(parallelism, fault_plan=None):
        config = ServeConfig(
            buckets=((128, 128),), default_steps=3, max_batch_size=1,
            batch_window_s=0.0, parallelism=parallelism,
            resilience=ResilienceConfig(
                max_retries=2, backoff_base_s=0.001, backoff_max_s=0.002,
                backoff_jitter=0.0, watchdog_timeout_s=0.0,
            ),
        )
        server = InferenceServer(
            pipeline_executor_factory(build), config, model_id="pixart",
            scheduler="ddim", mesh_plan="dp1.cfg1.sp2",
            fault_plan=fault_plan,
        )
        with server:
            res = server.submit("a fox", height=128, width=128,
                                guidance_scale=1.0, seed=3).result(timeout=600)
            snap = server.metrics_snapshot()
        return res, snap

    plan = FaultPlan([FaultRule(site="execute", kind="oom", p=1.0,
                                key_substr=":pf")])
    degraded, dsnap = serve_images("pipefusion", fault_plan=plan)
    assert degraded.degradations == ("pipeline_off",)
    fresh, _ = serve_images("patch")
    np.testing.assert_array_equal(np.asarray(degraded.output),
                                  np.asarray(fresh.output))
    assert dsnap["requests"]["degraded_pipeline_off"] == 1


# CPU-compile-heavy module: the fake 8-device mesh compiles full
# multi-device denoise loops, minutes per test on the tier-1 CPU runner.
# Runs with `-m slow` and on real-hardware rounds.
pytestmark = pytest.mark.slow
