"""Staged serving pipeline (serve/staging.py + pipelines.prepare_stages):
staged-vs-monolithic bit-identity on all three model families, the
max_inflight_batches residency cap, cancel/deadline/stop propagation,
one-terminal-failure breaker semantics, the staging_off degradation rung,
executor-cache pinning under eviction, and the serve_bench --stages
artifact contract."""

import threading
import time

import numpy as np
import pytest

from distrifuser_tpu.serve import (
    CircuitOpenError,
    DeadlineExceededError,
    ExecKey,
    ExecuteFailedError,
    ExecutorCache,
    InferenceServer,
    ResilienceConfig,
    ServeConfig,
    ServerClosedError,
)
from distrifuser_tpu.serve.testing import (
    FakeExecutorFactory,
    StagedFakeExecutorFactory,
    fake_image,
)
from distrifuser_tpu.utils.metrics import GapTracker


def serve_config(**kw):
    kw.setdefault("max_queue_depth", 32)
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("batch_window_s", 0.05)
    kw.setdefault("buckets", ((512, 512),))
    kw.setdefault("default_steps", 4)
    kw.setdefault("pipeline_stages", True)
    return ServeConfig(**kw)


def wait_until(pred, timeout=10.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


# --------------------------------------------------------------------------
# GapTracker
# --------------------------------------------------------------------------


def test_gap_tracker_math():
    g = GapTracker()
    assert g.snapshot()["gap_fraction"] == 0.0
    g.begin(0.0)
    g.end(1.0)
    g.begin(3.0)
    g.end(4.0)
    snap = g.snapshot()
    assert snap["intervals"] == 2
    assert snap["busy_s"] == pytest.approx(2.0)
    assert snap["span_s"] == pytest.approx(4.0)
    assert snap["gap_fraction"] == pytest.approx(0.5)
    with pytest.raises(AssertionError):
        g.end(5.0)  # unbalanced


# --------------------------------------------------------------------------
# config / key plumbing
# --------------------------------------------------------------------------


def test_serve_config_validates_max_inflight():
    with pytest.raises(ValueError, match="max_inflight_batches"):
        ServeConfig(max_inflight_batches=0)
    assert ServeConfig(pipeline_stages=True).max_inflight_batches == 2
    assert ServeConfig().pipeline_stages is False  # off by default


def test_staged_keys_compose_with_step_cache_and_compress():
    """pipeline_stages changes dispatch, never compile identity: the
    cadence/compression knobs reach the built ExecKeys exactly as on a
    monolithic server."""
    factory = StagedFakeExecutorFactory(batch_size=4)
    config = serve_config(step_cache_interval=2, step_cache_depth=1,
                          comm_compress="int8")
    with InferenceServer(factory, config) as server:
        server.submit("p", height=512, width=512).result(timeout=30)
    (key,) = factory.built
    assert key.step_cache_interval == 2 and key.step_cache_depth == 1
    assert key.comm_compress == "int8"
    snap = server.metrics_snapshot()
    assert snap["config"]["pipeline_stages"] is True
    assert snap["step_cache"]["steps_shallow"] > 0  # shallow share flows


# --------------------------------------------------------------------------
# staged server over fakes: identity, overlap, residency
# --------------------------------------------------------------------------


def test_staged_server_matches_monolithic_fake():
    """Same submissions through a staged and a monolithic server resolve
    to bit-identical outputs — pipelining changes WHEN stages run, never
    what they compute."""
    results = {}
    for staged in (False, True):
        factory = StagedFakeExecutorFactory(batch_size=4, step_time_s=0.002,
                                            encode_s=0.002, decode_s=0.002)
        config = serve_config(pipeline_stages=staged)
        with InferenceServer(factory, config) as server:
            futs = [server.submit(f"p{i}", height=512, width=512, seed=i)
                    for i in range(6)]
            results[staged] = [f.result(timeout=30) for f in futs]
    for a, b in zip(results[False], results[True]):
        np.testing.assert_array_equal(a.output, b.output)
    expected = fake_image("p0", 0, ExecKey(
        model_id="model", scheduler="ddim", height=512, width=512,
        steps=4, cfg=True, mesh_plan="dp1.cfg1.sp1"))
    np.testing.assert_array_equal(results[True][0].output, expected)


def test_staged_metrics_schema_and_gap():
    factory = StagedFakeExecutorFactory(batch_size=1, step_time_s=0.005,
                                        encode_s=0.005, decode_s=0.005)
    config = serve_config(max_batch_size=1, batch_window_s=0.0)
    with InferenceServer(factory, config) as server:
        futs = [server.submit(f"p{i}", height=512, width=512)
                for i in range(6)]
        for f in futs:
            f.result(timeout=30)
        snap = server.metrics_snapshot()
    staging = snap["staging"]
    assert staging["max_inflight_batches"] == 2
    assert staging["completed"] == staging["submitted"] == len(futs)
    for s in ("encode", "denoise", "decode"):
        assert staging["stages"][s]["service"]["count"] == len(futs)
        assert staging["stages"][s]["queue_wait"]["count"] == len(futs)
    gap = staging["denoise_gap"]
    assert gap["intervals"] == len(futs)
    assert 0.0 <= gap["gap_fraction"] <= 1.0
    import json

    json.dumps(snap)  # JSON-serializable end to end


def test_max_inflight_bound_is_enforced():
    """No more than max_inflight_batches batches hold buffers at once:
    asserted via the pipeline's semaphore accounting AND the fakes'
    independent encode-entry/decode-exit tracker."""
    factory = StagedFakeExecutorFactory(batch_size=1, encode_s=0.02,
                                        denoise_s=0.02, decode_s=0.02)
    config = serve_config(max_batch_size=1, batch_window_s=0.0,
                          max_inflight_batches=2)
    with InferenceServer(factory, config) as server:
        futs = [server.submit(f"p{i}", height=512, width=512)
                for i in range(10)]
        for f in futs:
            f.result(timeout=30)
    snap = server.metrics_snapshot()["staging"]
    assert factory.tracker.peak <= 2
    assert snap["peak_inflight"] <= 2
    # the pipeline actually pipelined: two batches were resident at once
    assert snap["peak_inflight"] == 2
    assert factory.tracker.current == 0  # everything drained


def test_staged_throughput_beats_monolithic():
    """The point of the tentpole: with stage times e/d/v, monolithic costs
    ~(e+d+v) per batch while staged steady-state costs ~max(e,d,v)."""
    wall = {}
    for staged in (False, True):
        factory = StagedFakeExecutorFactory(batch_size=1, encode_s=0.02,
                                            denoise_s=0.03, decode_s=0.02)
        config = serve_config(max_batch_size=1, batch_window_s=0.0,
                              pipeline_stages=staged)
        with InferenceServer(factory, config) as server:
            t0 = time.monotonic()
            futs = [server.submit(f"p{i}", height=512, width=512)
                    for i in range(12)]
            for f in futs:
                f.result(timeout=30)
            wall[staged] = time.monotonic() - t0
    # 12 batches: serial ~0.84s, staged ~0.36s + ramp; generous margin for
    # slow CI — anything under ~0.75x serial proves overlap happened
    assert wall[True] < wall[False] * 0.75, wall


# --------------------------------------------------------------------------
# failure semantics: one terminal failure, breaker, staging_off rung
# --------------------------------------------------------------------------


def test_stage_failure_is_one_terminal_dispatch_failure():
    """A stage failure fails the batch once (typed), feeds the breaker as
    ONE terminal failure, and the breaker trips at its threshold."""
    factory = StagedFakeExecutorFactory(batch_size=4, fail_stage="denoise",
                                        fail_times=1)
    config = serve_config(
        resilience=ResilienceConfig(breaker_failure_threshold=1,
                                    breaker_cooldown_s=60.0),
    )
    with InferenceServer(factory, config) as server:
        bad = server.submit("p", height=512, width=512)
        with pytest.raises(ExecuteFailedError, match="staged denoise"):
            bad.result(timeout=30)
        # circuit tripped by the single terminal failure: next dispatch
        # sheds fast (the drain runs at dispatch time)
        shed = server.submit("p2", height=512, width=512)
        with pytest.raises(CircuitOpenError):
            shed.result(timeout=30)
    snap = server.metrics_snapshot()
    assert snap["requests"]["failed_execute"] == 1
    assert snap["requests"]["shed_circuit_open"] == 1


def test_oom_in_stage_forces_staging_off():
    """The degradation ladder's staging_off rung: an OOM-shaped stage
    failure turns pipelining off for the key; the NEXT dispatch runs
    monolithically (same executor, __call__ path) and succeeds."""
    factory = StagedFakeExecutorFactory(
        batch_size=4, fail_stage="denoise", fail_times=1,
        fail_exc=RuntimeError("RESOURCE_EXHAUSTED: injected staged OOM"),
    )
    with InferenceServer(factory, serve_config()) as server:
        bad = server.submit("p", height=512, width=512)
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
            bad.result(timeout=30)
        ok = server.submit("p2", height=512, width=512).result(timeout=30)
        assert ok.output is not None
        assert "staging_off" in ok.degradations
        health = server.health()
    (ex,) = factory.executors
    # denoise stage ran exactly once (the failed staged batch); the
    # recovery went through the monolithic __call__
    assert ex.stage_calls["denoise"] == 1
    assert ex.batch_sizes == [1]
    assert server.counters.get("degraded_staging_off") == 1
    degr = health["degradations"]
    assert any("staging_off" in d["rungs"] for d in degr.values())


def test_watchdog_timeout_defers_unpin_until_abandoned_stage_drains():
    """A stage hanging past the watchdog fails its batch fast — but the
    abandoned worker thread is STILL running the executor, so the pin
    must only drop once that thread drains (the evict-while-running
    hazard the pinning exists for)."""
    from distrifuser_tpu.serve import WatchdogTimeoutError

    factory = StagedFakeExecutorFactory(batch_size=4, denoise_s=1.0)
    config = serve_config(
        resilience=ResilienceConfig(watchdog_timeout_s=0.15,
                                    breaker_failure_threshold=100),
    )
    with InferenceServer(factory, config) as server:
        fut = server.submit("p", height=512, width=512)
        with pytest.raises(WatchdogTimeoutError):
            fut.result(timeout=30)
        (ex,) = factory.executors
        # the abandoned denoise thread (sleeping ~1s) still holds the
        # executor: the pin is deferred, not dropped
        assert server.cache.pin_count(ex) == 1
        assert wait_until(lambda: server.cache.pin_count(ex) == 0,
                          timeout=10)


def test_staged_server_respects_execute_fault_plan():
    """Chaos composition: the server's "execute"-site FaultPlan fires at
    the staged denoise stage, so chaos runs exercise staged failure
    handling instead of silently skipping injection."""
    from distrifuser_tpu.serve import FaultPlan, FaultRule

    plan = FaultPlan([FaultRule(site="execute", kind="execute_error",
                                at_calls=(0,))])
    factory = StagedFakeExecutorFactory(batch_size=4)
    with InferenceServer(factory, serve_config(), fault_plan=plan) as server:
        bad = server.submit("p", height=512, width=512)
        with pytest.raises(ExecuteFailedError):
            bad.result(timeout=30)
        # the rule fired once; the next staged dispatch is clean
        ok = server.submit("p2", height=512, width=512).result(timeout=30)
    assert ok.output is not None
    assert plan.fired() == {"execute/execute_error": 1}


def test_stage_tracker_balances_on_injected_failure():
    """The residency probe must not leak entries when a stage fails —
    fault-injected runs still assert the inflight cap meaningfully."""
    factory = StagedFakeExecutorFactory(batch_size=4, fail_stage="denoise",
                                        fail_times=1)
    with InferenceServer(factory, serve_config()) as server:
        bad = server.submit("p", height=512, width=512)
        with pytest.raises(ExecuteFailedError):
            bad.result(timeout=30)
        server.submit("p2", height=512, width=512).result(timeout=30)
    assert factory.tracker.current == 0


def test_staging_off_rung_requires_staged_server():
    """On a monolithic server the rung is never applicable — OOMs walk the
    ladder exactly as before this PR."""
    from distrifuser_tpu.serve.resilience import (
        RUNG_STAGING_OFF,
        DegradationLadder,
        KeyResilience,
        CircuitBreaker,
    )

    key = ExecKey(model_id="m", scheduler="ddim", height=512, width=512,
                  steps=4, cfg=True, mesh_plan="dp1.cfg1.sp1")
    st = KeyResilience(breaker=CircuitBreaker(3, 1.0))
    mono = DegradationLadder(ResilienceConfig(), staging=False)
    staged = DegradationLadder(ResilienceConfig(), staging=True)
    assert mono.next_rung(st, "compile", key, 1) != RUNG_STAGING_OFF
    assert staged.next_rung(st, "compile", key, 1) == RUNG_STAGING_OFF
    # the rung is dispatch-mode only: it never changes the key
    assert staged.apply(key, [RUNG_STAGING_OFF]) == key
    off = DegradationLadder(ResilienceConfig(allow_staging_off=False),
                            staging=True)
    assert off.next_rung(st, "compile", key, 1) != RUNG_STAGING_OFF


# --------------------------------------------------------------------------
# cancel / deadline / stop propagation
# --------------------------------------------------------------------------


def test_cancel_mid_stage_drops_batch():
    """A batch whose every future was cancelled while a stage ran is
    dropped at the next stage boundary — no denoise time spent on it."""
    factory = StagedFakeExecutorFactory(batch_size=4, encode_s=0.3)
    config = serve_config(batch_window_s=0.0)
    with InferenceServer(factory, config) as server:
        fut = server.submit("doomed", height=512, width=512)
        # let the scheduler dispatch it into the encode stage, then cancel
        assert wait_until(lambda: len(factory.executors) == 1
                          and factory.executors[0].stage_calls["encode"] == 1)
        assert fut.cancel()
        assert wait_until(
            lambda: server.counters.get("staged_cancelled") == 1)
        ok = server.submit("live", height=512, width=512).result(timeout=30)
    assert ok.output is not None
    assert factory.executors[0].stage_calls["denoise"] == 1  # only "live"


def test_deadline_lapsing_before_denoise_rejects():
    """All riders expired before the denoise stage: the mesh stage is a
    scheduling point, so the batch is rejected (typed), never denoised."""
    factory = StagedFakeExecutorFactory(batch_size=4, encode_s=0.5)
    config = serve_config(batch_window_s=0.0)
    with InferenceServer(factory, config) as server:
        fut = server.submit("late", height=512, width=512, ttl_s=0.2)
        with pytest.raises(DeadlineExceededError, match="before the "
                           "denoise"):
            fut.result(timeout=30)
    assert factory.executors[0].stage_calls["denoise"] == 0
    assert server.counters.get("staged_expired") == 1
    assert server.counters.get("rejected_deadline") == 1


def test_staged_stop_drains_deterministically():
    """stop() resolves EVERY staged future: completed batches keep their
    results, batches still inside the pipeline fail with
    ServerClosedError, and nothing is left pending."""
    factory = StagedFakeExecutorFactory(batch_size=1, denoise_s=0.2)
    config = serve_config(max_batch_size=1, batch_window_s=0.0,
                          max_inflight_batches=2)
    server = InferenceServer(factory, config).start(warmup=False)
    futs = [server.submit(f"p{i}", height=512, width=512) for i in range(6)]
    # stop once at least one batch is through and several are still
    # queued/mid-pipeline (event-driven: a fixed sleep is flaky on a
    # loaded CI box)
    assert wait_until(lambda: any(f.done() for f in futs), timeout=20)
    server.stop(timeout=10.0)
    assert all(f.done() for f in futs), "stop() left futures unresolved"
    outcomes = {"ok": 0, "closed": 0}
    for f in futs:
        try:
            r = f.result(timeout=0)
            assert r.output is not None
            outcomes["ok"] += 1
        except ServerClosedError:
            outcomes["closed"] += 1
    assert outcomes["ok"] >= 1 and outcomes["closed"] >= 1, outcomes
    snap = server.metrics_snapshot()["staging"]
    assert snap["inflight"] == 0


def test_plain_executor_falls_back_to_monolithic():
    """A staged server over executors WITHOUT stage programs serves
    monolithically (no crash, no staged metrics) — staging is an
    optimization, never a new executor requirement."""
    factory = FakeExecutorFactory(batch_size=4)
    with InferenceServer(factory, serve_config()) as server:
        r = server.submit("p", height=512, width=512).result(timeout=30)
    assert r.output is not None
    snap = server.metrics_snapshot()
    assert snap["staging"]["submitted"] == 0
    assert snap["requests"]["completed"] == 1


# --------------------------------------------------------------------------
# ExecutorCache pinning
# --------------------------------------------------------------------------


def key_for(h, w, steps=4):
    return ExecKey(model_id="m", scheduler="ddim", height=h, width=w,
                   steps=steps, cfg=True, mesh_plan="dp1.cfg1.sp1")


def test_cache_pin_skips_lru_eviction():
    """The evict-while-inflight race: LRU pressure must never victimize a
    pinned executor — it stays resident (capacity temporarily exceeded)
    and becomes evictable again only after the last unpin."""
    evicted = []
    cache = ExecutorCache(lambda k: object(), capacity=1,
                          on_evict=lambda k, e: evicted.append(k))
    k1, k2, k3 = key_for(512, 512), key_for(768, 768), key_for(1024, 1024)
    ex1, _ = cache.get(k1, pin=True)
    cache.get(k2)  # capacity 1: k1 is the LRU victim — but it is pinned
    assert k1 in cache and k2 in cache  # over capacity, never freed
    assert evicted == []
    assert cache.stats()["pinned"] == 1
    cache.unpin(ex1)
    assert cache.pin_count(ex1) == 0
    cache.get(k3)  # next pressure event: the now-unpinned k1 (oldest) goes
    assert k1 not in cache
    assert k1 in evicted
    assert cache.stats()["deferred_evictions"] == 0


def test_cache_pin_refcounts_and_invalidate():
    evicted = []
    cache = ExecutorCache(lambda k: object(), capacity=4,
                          on_evict=lambda k, e: evicted.append((k, e)))
    k = key_for(512, 512)
    ex, _ = cache.get(k, pin=True)
    ex_again, hit = cache.get(k, pin=True)
    assert hit and ex_again is ex and cache.pin_count(ex) == 2
    # invalidate (the degradation path's poisoned-program eviction) while
    # two staged batches still hold the executor
    assert cache.invalidate(k)
    assert k not in cache
    assert evicted == []
    cache.unpin(ex)
    assert evicted == []  # one batch still inflight
    cache.unpin(ex)
    assert evicted == [(k, ex)]
    # a rebuilt key gets a FRESH executor while the old one was pinned
    ex2, hit2 = cache.get(k)
    assert not hit2 and ex2 is not ex


def test_cache_unpinned_behavior_unchanged():
    """pin=False (the monolithic path) is exactly the old cache: immediate
    on_evict at capacity."""
    evicted = []
    cache = ExecutorCache(lambda k: f"exec-{k.height}", capacity=2,
                          on_evict=lambda k, e: evicted.append(k))
    k1, k2, k3 = key_for(512, 512), key_for(768, 768), key_for(1024, 1024)
    cache.get(k1), cache.get(k2), cache.get(k3)
    assert evicted == [k1]
    assert cache.stats()["deferred_evictions"] == 0
    assert cache.stats()["pinned"] == 0


# --------------------------------------------------------------------------
# real pipelines: staged == monolithic, bit for bit, on all three families
# --------------------------------------------------------------------------


def build_pixart_pipeline(devices, n_dev, **cfg_kw):
    import jax

    from distrifuser_tpu import DistriConfig
    from distrifuser_tpu.models import dit as dit_mod
    from distrifuser_tpu.models.vae import init_vae_params, tiny_vae_config
    from distrifuser_tpu.pipelines import DistriPixArtPipeline

    dcfg = dit_mod.tiny_dit_config()
    cfg_kw.setdefault("height", dcfg.sample_size * 8)
    cfg_kw.setdefault("width", dcfg.sample_size * 8)
    cfg_kw.setdefault("warmup_steps", 1)
    dist = DistriConfig(devices=devices[:n_dev], **cfg_kw)
    return DistriPixArtPipeline.from_params(
        dist, dcfg, dit_mod.init_dit_params(jax.random.PRNGKey(0), dcfg),
        tiny_vae_config(),
        init_vae_params(jax.random.PRNGKey(1), tiny_vae_config()),
        scheduler="ddim",
    )


def staged_run(ex, prompts, negs, gs, seeds):
    """Drive the executor's three-stage contract by hand — exactly what
    the StagePipeline workers do."""
    work = ex.encode_stage(prompts, negs, seeds)
    work = ex.denoise_stage(work, gs)
    return ex.decode_stage(work)


def assert_staged_identical(pipe, steps=2, prompts=("a cat", "a dog")):
    from distrifuser_tpu.serve.executors import PipelineExecutor

    ex = PipelineExecutor(pipe, steps=steps)
    prompts = list(prompts)
    negs = [""] * len(prompts)
    seeds = list(range(3, 3 + len(prompts)))
    mono = ex(prompts, negs, 5.0, seeds)
    staged = staged_run(ex, prompts, negs, 5.0, seeds)
    assert len(mono) == len(staged) == len(prompts)
    for a, b in zip(mono, staged):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_executor_staged_matches_monolithic_unet(devices8):
    from test_pipelines import build_sd_pipeline

    pipe, _ = build_sd_pipeline(devices8, 1, batch_size=2)
    assert_staged_identical(pipe)


def test_executor_staged_matches_monolithic_dit(devices8):
    pipe = build_pixart_pipeline(devices8, 1, batch_size=2)
    assert_staged_identical(pipe)


def test_executor_staged_matches_monolithic_mmdit(devices8):
    from test_sd3_pipeline import build_sd3_pipeline

    pipe, _ = build_sd3_pipeline(devices8, 1, batch_size=2)
    assert_staged_identical(pipe)


def test_executor_staged_composes_with_step_cache(devices8):
    """prepare_stages under the temporal step-cache cadence: the staged
    denoise program carries the cadence (shallow steps and all) and stays
    bit-identical to the monolithic dispatch."""
    from test_pipelines import build_sd_pipeline

    pipe, _ = build_sd_pipeline(devices8, 1, batch_size=2,
                                step_cache_interval=2, step_cache_depth=1)
    from distrifuser_tpu.serve.executors import PipelineExecutor

    ex = PipelineExecutor(pipe, steps=4)
    assert ex.shallow_steps > 0
    mono = ex(["a cat"], [""], 5.0, [7])
    staged = staged_run(ex, ["a cat"], [""], 5.0, [7])
    np.testing.assert_array_equal(np.asarray(mono[0]), np.asarray(staged[0]))


@pytest.mark.parametrize("seeds", [
    [3, 9, 12345],
    [0], [1], [2**31 - 1],
    # the server's validation is `int(seed)`: the largest it lets through is
    # the largest `PRNGKey` takes
    [2**63 - 1], [-1],
    [0, 1, 2**31 - 1, 2**31, 2**32 + 5, 2**63 - 1, -2**63],
], ids=lambda seeds: "-".join(map(str, seeds)))
def test_draw_latents_vmapped_parity(devices8, seeds):
    """One cached program (`pipelines.seeded_latents`) draws every request's
    noise, bit-identical to the per-seed loop - `PRNGKey` + `normal` + an
    eager multiply - for every seed `PRNGKey` takes; a second call traces
    nothing, and the dispatch path does not mutate shared scheduler
    state."""
    import jax
    import jax.numpy as jnp

    from test_pipelines import build_sd_pipeline
    from distrifuser_tpu import pipelines
    from distrifuser_tpu.schedulers import get_scheduler
    from distrifuser_tpu.serve.executors import PipelineExecutor

    pipe, dcfg = build_sd_pipeline(devices8, 1, batch_size=2)
    # a sigma that is not 1: the scale is a multiply of its own
    pipe.scheduler = get_scheduler("euler").set_timesteps(2)
    assert pipe.scheduler.init_noise_sigma > 1.5
    ex = PipelineExecutor(pipe, steps=2)
    got = np.asarray(ex._draw_latents(seeds))
    shape = (1, dcfg.latent_height, dcfg.latent_width,
             pipe.unet_config.in_channels)
    ref = jnp.concatenate([
        jax.random.normal(jax.random.PRNGKey(s), shape, jnp.float32)
        for s in seeds
    ], axis=0) * pipe.scheduler.init_noise_sigma
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got.view(np.uint32),
                                  np.asarray(ref).view(np.uint32))
    # the pipelines' own draw, one key for the whole batch, is the same
    # program at another rank
    whole = pipelines.seeded_latents(seeds[0], (2,) + shape[1:],
                                     pipe.scheduler.init_noise_sigma)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(
        jax.random.normal(jax.random.PRNGKey(seeds[0]), (2,) + shape[1:],
                          jnp.float32) * pipe.scheduler.init_noise_sigma))

    def boom(*a, **kw):  # noqa: ANN002
        raise AssertionError("_draw_latents must not touch the scheduler")

    pipe.scheduler.set_timesteps = boom
    traced = pipelines._seeded_latents._cache_size()
    np.testing.assert_array_equal(np.asarray(ex._draw_latents(seeds)), got)
    assert pipelines._seeded_latents._cache_size() == traced


def test_server_staged_real_pipeline_matches_monolithic(devices8):
    """Full stack on the tiny SD config: the same submissions through a
    staged and a monolithic server produce bit-identical images, and the
    staged run reports per-stage metrics."""
    from test_pipelines import build_sd_pipeline
    from distrifuser_tpu.serve.executors import pipeline_executor_factory

    def build(key: ExecKey):
        pipe, _ = build_sd_pipeline(
            devices8, 1, height=key.height, width=key.width, batch_size=2,
            do_classifier_free_guidance=key.cfg,
        )
        return pipe

    results = {}
    snaps = {}
    for staged in (False, True):
        config = ServeConfig(
            max_queue_depth=8, max_batch_size=2, batch_window_s=0.2,
            buckets=((128, 128),), default_steps=2, cache_capacity=2,
            pipeline_stages=staged,
        )
        factory = pipeline_executor_factory(build)
        with InferenceServer(factory, config, model_id="tiny-sd",
                             scheduler="ddim",
                             mesh_plan="dp1.cfg1.sp1") as server:
            futs = [server.submit(p, height=128, width=128, seed=s)
                    for p, s in (("a cat", 1), ("a dog", 2), ("a fox", 3))]
            results[staged] = [f.result(timeout=600) for f in futs]
        snaps[staged] = server.metrics_snapshot()
    for a, b in zip(results[False], results[True]):
        np.testing.assert_array_equal(np.asarray(a.output),
                                      np.asarray(b.output))
    staging = snaps[True]["staging"]
    assert staging["completed"] >= 2
    assert staging["stages"]["denoise"]["service"]["count"] >= 2
    assert snaps[False]["staging"] is None


# --------------------------------------------------------------------------
# serve_bench --stages artifact
# --------------------------------------------------------------------------


def test_serve_bench_stages_artifact(tmp_path):
    import json
    import sys

    sys.path.insert(0, "scripts")
    import serve_bench

    out = tmp_path / "staged.json"
    rc = serve_bench.main([
        "--dry-run", "--stages", "--mode", "closed", "--requests", "8",
        "--concurrency", "4", "--steps", "2", "--fake_build_s", "0",
        "--fake_step_s", "0.002", "--fake_encode_s", "0.004",
        "--fake_decode_s", "0.004", "--out", str(out),
    ])
    assert rc == 0
    art = json.loads(out.read_text())
    assert art["bench"]["staged_compare"] is True
    assert art["monolithic"]["load"]["completed"] == 8
    assert art["staged"]["load"]["completed"] == 8
    assert art["throughput_ratio"] > 0
    staging = art["staged"]["metrics"]["staging"]
    for s in ("encode", "denoise", "decode"):
        assert staging["stages"][s]["service"]["count"] > 0
    assert 0.0 <= art["denoise_gap_fraction"] <= 1.0
    assert art["staged"]["metrics"]["config"]["pipeline_stages"] is True
    assert art["monolithic"]["metrics"]["config"]["pipeline_stages"] is False
