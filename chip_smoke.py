#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process that touches JAX itself and starts no other.  It drives the
normal path once — `DistriSDXLPipeline.from_params` inside a
`build_pipeline(key)` handed to `pipeline_executor_factory`, served by
`InferenceServer` — at SDXL's published widths with seeded random bf16
weights, on however many local chips it finds (4 chips -> DistriConfig()'s
default dp1 x cfg2 x sp2 mesh), and exits non-zero on the first thing that is
wrong.  Depth is cut (`SMOKE_DEPTH`), widths are not: every channel count,
head count, head dim, sequence length and attention shape is the published
one, so every kernel and collective sees its real shape, while the UNet's
transformer stacks and the bigG text tower repeat fewer times — one full-depth
SDXL program takes minutes to compile on the chip machine and the whole check
has 1200 s.  Legs, in order:

  device    platform must be "tpu"; versions and the compile cache in use
  kernels   every Pallas kernel a default or table route can reach, compiled
            (never interpreted) at the shapes the models use, against
            `_sdpa_xla` / a plain matmul / the grouped expert matmul under
            a written tolerance
  serve     >= 3 requests + one repeated seed at 1024^2 / 50-step DDIM / CFG
            through a whole-batch server, then through a step-batching
            server with a request joining while another is mid-denoise
  chips     (count > 1) weights resident on every mesh device, all-chip
            full_sync against one chip over real ICI, mesh device order
  report    one JSON line with the facts the next issue starts from, then
            the verdict as the LAST stdout line: exactly
            {"ok": true, "device": {"platform", "kind", "count"}}

Without a chip it fails (exit 3) and prints no result.  `--rehearse` — an
explicit flag, never a default — runs the same legs at the tiny configs on
the CPU with the kernels in interpret mode, so this file can be debugged
before chip time is spent; its JSON says `"rehearsal": true` and carries no
timing, because a CPU time is not a device metric.

The smoke loads no checkpoint and no vocabulary: weights are seeded random
and the tokenizer is the weightless `SimpleTokenizer`, so neither native
library (`distrifuser_tpu/native/*.so`) is built or loaded.
"""

import argparse
import contextlib
import dataclasses
import functools
import json
import statistics
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

T0 = time.time()


class SmokeFailure(Exception):
    """One thing was wrong; the message names the leg and the fact."""


def say(msg: str) -> None:
    print(f"[{time.time() - T0:7.1f}s] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileLog:
    """Every backend compile (or persistent-cache load) JAX performs, by
    program name, plus the persistent cache's own hit/miss events."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.compiles = []  # (fun_name, seconds)
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == self._COMPILE:
            name = str(kw.get("fun_name", "?"))
            self.compiles.append((name, seconds))
            if seconds >= 5.0:
                say(f"compile: {name} took {seconds:.1f}s")

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self) -> int:
        return len(self.compiles)

    def since(self, mark: int):
        return self.compiles[mark:]


# ---------------------------------------------------------------------------
# leg: device
# ---------------------------------------------------------------------------


def leg_device(cache_dir: str) -> dict:
    import jaxlib

    dev = jax.devices()[0]
    facts = {"platform": dev.platform, "kind": dev.device_kind,
             "count": len(jax.devices())}
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    say(f"device: {facts} jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu_version} default_backend={jax.default_backend()} "
        f"compile_cache={cache_dir}")
    return {"device": facts,
            "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                         "libtpu": libtpu_version},
            "compile_cache_dir": cache_dir}


# ---------------------------------------------------------------------------
# leg: kernels
# ---------------------------------------------------------------------------

# Flash kernels against _sdpa_xla, bf16 in and out.  One bf16 ulp at |x|<=1
# is 2^-8 = 3.9e-3; a tile-ordered online softmax and the unfused softmax
# round P and the output at different points, so they agree to a few ulps:
# 1e-3..4e-3 measured on one v5e (PR 21), 2e-2 leaves room and still catches
# a wrong mask or a dropped tile (errors of order 1e-1 and up).
FLASH_ATOL = 2e-2
# The gather mat-vec kernel against the grouped matmul, bf16 weights and
# float32 accumulation on both sides: the sums run in another order, so a
# hidden value near a bf16 rounding boundary can land one ulp (2^-8) apart;
# a dropped expert or f-tile is an error of the order of the output itself.
# Relative to the largest output.
MOE_RTOL = 5e-3


def leg_kernels(rehearse: bool) -> dict:
    from jax.experimental.pallas import tpu as pltpu

    from distrifuser_tpu.ops import sdpa_routing
    from distrifuser_tpu.ops.attention import _sdpa_xla, sdpa
    from distrifuser_tpu.ops.flash_attention import (
        flash_sdpa,
        padded_flash_sdpa,
        upstream_flash_sdpa,
    )

    dtype = jnp.float32 if rehearse else jnp.bfloat16
    # rehearsal: the same calls, with Pallas forced into TPU interpret mode
    interpret = (pltpu.force_tpu_interpret_mode if rehearse
                 else contextlib.nullcontext)

    def qkv(b, lq, lk, c):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        return (jax.random.normal(ks[0], (b, lq, c), dtype),
                jax.random.normal(ks[1], (b, lk, c), dtype),
                jax.random.normal(ks[2], (b, lk, c), dtype))

    @functools.partial(jax.jit, static_argnames="heads")
    def reference(q, k, v, heads):
        b, lq, c = q.shape
        lk, d = k.shape[1], c // heads
        return _sdpa_xla(q.reshape(b, lq, heads, d), k.reshape(b, lk, heads, d),
                         v.reshape(b, lk, heads, d), 1.0 / d**0.5
                         ).reshape(b, lq, c)

    done = []

    def flash_case(name, fn, b, lq, lk, heads, d):
        q, k, v = qkv(b, lq, lk, heads * d)
        with interpret():
            out = jax.block_until_ready(fn(q, k, v))
        ref = reference(q, k, v, heads=heads)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        check(out.shape == q.shape and bool(jnp.isfinite(out).all()),
              f"kernels: {name}: output not finite or wrong shape")
        check(err <= FLASH_ATOL,
              f"kernels: {name}: max |kernel - _sdpa_xla| = {err:.3g} "
              f"> {FLASH_ATOL}")
        say(f"kernels: {name}: compiled, max abs err {err:.3g}")
        done.append(name)
        return out, (q, k, v)

    if rehearse:
        # (name, L, heads) stand-ins; d = 64 like the real shapes
        levels = [("level1", 256, 2), ("level2", 256, 4)]
        tiles = (128, 128)
        padded_len, padded_heads = 330, 2
    else:
        # SDXL 1024^2: level 1 is 64x64 tokens x 10 heads, level 2 is 32x32
        # x 20 heads, d = 64; CFG batch 2 on one chip
        levels = [("level1", 4096, 10), ("level2", 1024, 20)]
        tiles = None  # the routing table's
        padded_len, padded_heads = 4250, 24  # SD3 joint 4096 + 154

    kernels = {"inrepo": flash_sdpa, "upstream": upstream_flash_sdpa}
    for level, length, heads in levels:
        if tiles is None:
            route = sdpa_routing.route(length, length, heads * 64, heads,
                                       jax.devices()[0].platform)
            check(route.impl in kernels,
                  f"kernels: table route for L={length} d=64 is {route}, "
                  "expected a flash kernel")
            impl, bq, bk = route.impl, route.block_q, route.block_k
        else:
            impl, (bq, bk) = "inrepo", tiles
        kern = functools.partial(kernels[impl], heads=heads, block_k=bk)
        name = f"{impl}_flash {level} L={length} h={heads} d=64 {bq}x{bk}"
        out, (q, k, v) = flash_case(name, functools.partial(kern, block_q=bq),
                                    2, length, length, heads, 64)
        if not rehearse:
            # the routed entry the models call reaches THIS kernel with THESE
            # tiles: same jitted program, so the bits are equal; any other
            # implementation behind sdpa() would differ
            routed = jax.block_until_ready(sdpa(q, k, v, heads=heads))
            check(bool(jnp.array_equal(routed, out)),
                  f"kernels: sdpa() at L={length} h={heads} did not run the "
                  f"table's {impl} route (bits differ from the direct call)")
        # the four-chip local shape: sp=2 halves the queries, KV is gathered
        # (sdpa cuts the tiles to what divides the local length)
        flash_case(f"{name} local Lq={length // 2}",
                   functools.partial(kern, block_q=min(bq, length // 2)),
                   1, length // 2, length, heads, 64)

    # the kernel the table sends longer sequences to, at the tiles it had here
    length, heads = levels[0][1], levels[0][2]
    ubq, ubk = tiles or (256, 1024)
    flash_case(f"upstream_flash L={length} h={heads} d=64 {ubq}x{ubk}",
               lambda q, k, v: upstream_flash_sdpa(
                   q, k, v, heads=heads, block_q=ubq, block_k=ubk),
               2, length, length, heads, 64)
    flash_case(f"padded_flash L={padded_len} h={padded_heads} d=64 "
               "(upstream, segment ids)",
               lambda q, k, v: padded_flash_sdpa(q, k, v, heads=padded_heads),
               2, padded_len, padded_len, padded_heads, 64)

    # the decode step's routed experts, from their ids: one token's 22
    # chosen experts, the held ones all over the held range, against the
    # grouped matmul that three copies of the token (66 rows) reach
    from distrifuser_tpu.ops import moe

    if rehearse:
        latent, inter, held_experts, moe_tile = 256, 384, 8, 128
    else:
        # Nemotron-3-Super: moe_latent_size, moe_intermediate_size, one
        # chip's share of eight; None: the kernel's own tile
        latent, inter, held_experts, moe_tile = 1024, 2688, 64, None
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    token = jax.random.normal(ks[0], (1, latent), dtype)
    w1 = (jax.random.normal(ks[1], (held_experts, latent, inter), dtype)
          * latent ** -0.5).astype(dtype)
    w2 = (jax.random.normal(ks[2], (held_experts, inter, latent), dtype)
          * inter ** -0.5).astype(dtype)
    last = held_experts - 1
    chosen = jnp.asarray([[0, last, last // 2, 1] + list(
        range(held_experts + 3, held_experts + 21))], jnp.int32)
    shares = jax.random.uniform(ks[3], chosen.shape, jnp.float32, 0.05, 0.5)
    with interpret():
        got, n = jax.block_until_ready(moe.gather_expert_sum(
            token, chosen, shares, w1, w2, first_expert=0, tile=moe_tile))
    want, n_want = moe.local_expert_sum(
        jnp.tile(token, (3, 1)), jnp.tile(chosen, (3, 1)),
        jnp.tile(shares, (3, 1)), w1, w2, first_expert=0)
    scale = float(jnp.max(jnp.abs(want[0])))
    err = float(jnp.max(jnp.abs(got[0] - want[0])))
    name = (f"expert_gather_matvec d={latent} f={inter} "
            f"{held_experts} held experts")
    check(bool(jnp.isfinite(got).all()) and scale > 0,
          f"kernels: {name}: output not finite")
    check(int(n) == 4 and int(n_want) == 12,
          f"kernels: {name}: fetched {int(n)} expert blocks for 4 held "
          f"assignments (the grouped path counted {int(n_want)} for 12)")
    check(err <= MOE_RTOL * scale,
          f"kernels: {name}: max |kernel - grouped matmul| = {err:.3g} > "
          f"{MOE_RTOL} x {scale:.3g}")
    say(f"kernels: {name}: compiled, max abs err {err:.3g} "
        f"(largest output {scale:.3g})")
    done.append(name)

    return {"kernels_compiled": done, "kernels_interpreted": bool(rehearse)}


# ---------------------------------------------------------------------------
# leg: serve
# ---------------------------------------------------------------------------

# Published: transformer_layers_per_block (1, 2, 10) and 32 bigG layers.  At
# full depth the first fused-loop request spent ~230 s compiling on one v5e's
# 13-core host, and at (1, 2, 2) the four-chip run still compiled eleven
# UNet-sized programs for ~90-190 s each, 1208 s in all (PR 21).
SMOKE_DEPTH = {"unet_transformer_layers_per_block": (1, 1, 1),
               "open_clip_bigg_layers": 8}


def build_weights(base_cfg, rehearse: bool):
    """SDXL as published (or the tiny stand-in), seeded random weights in the
    config's dtype, placed ONCE on the mesh: every pipeline built from them —
    one per ExecKey — shares these buffers (the runners' own placement is
    then a no-op), so two keys never mean two UNet copies."""
    from distrifuser_tpu.models import clip as clip_mod
    from distrifuser_tpu.models import unet as unet_mod
    from distrifuser_tpu.models import vae as vae_mod
    from distrifuser_tpu.models.weights import params_nbytes

    if rehearse:
        ucfg = unet_mod.tiny_config(sdxl=True)
        vcfg = vae_mod.tiny_vae_config()
        tcs = [clip_mod.tiny_clip_config(hidden=16),
               clip_mod.CLIPTextConfig(
                   vocab_size=1000, hidden_size=16, num_hidden_layers=2,
                   num_attention_heads=4, intermediate_size=32,
                   projection_dim=32)]
    else:
        ucfg = dataclasses.replace(
            unet_mod.sdxl_config(), transformer_layers_per_block=SMOKE_DEPTH[
                "unet_transformer_layers_per_block"])
        vcfg = vae_mod.sdxl_vae_config()
        tcs = [clip_mod.clip_vit_l_config(),
               dataclasses.replace(
                   clip_mod.open_clip_bigg_config(),
                   num_hidden_layers=SMOKE_DEPTH["open_clip_bigg_layers"])]
    dt = base_cfg.dtype
    key = jax.random.PRNGKey
    weights = {
        "unet": base_cfg.place(unet_mod.init_unet_params(key(0), ucfg, dt)),
        "vae": base_cfg.place(vae_mod.init_vae_params(key(1), vcfg, dt)),
        "text": [base_cfg.place(clip_mod.init_clip_params(key(2 + i), tc, dt))
                 for i, tc in enumerate(tcs)],
    }
    nbytes = {"unet": params_nbytes(weights["unet"]),
              "vae": params_nbytes(weights["vae"]),
              "text": sum(params_nbytes(p) for p in weights["text"])}
    return (ucfg, vcfg, tcs), weights, nbytes


def wait_for(predicate, what: str, timeout_s: float) -> None:
    deadline = time.time() + timeout_s
    while not predicate():
        check(time.time() < deadline, f"serve: timed out waiting for {what}")
        time.sleep(0.02)


def check_image(img, size: int, what: str) -> None:
    check(img.shape == (size, size, 3),
          f"serve: {what}: image shape {img.shape}, wanted {(size, size, 3)}")
    check(bool(np.isfinite(img).all()), f"serve: {what}: image not finite")
    check(float(img.std()) > 1e-4, f"serve: {what}: image is constant")


def check_server(server, results, want_key: str, n_keys: int,
                 what: str) -> None:
    """No rung, no retry, no open breaker, the asked-for exec_mode, and one
    executor build per distinct key."""
    health = server.health()
    check(health["status"] == "ok" and not health["degradations"]
          and not health["open_circuits"],
          f"serve: {what}: health is {health['status']}: degradations="
          f"{health['degradations']} open_circuits={health['open_circuits']} "
          f"last_errors={health.get('last_errors')}")
    bad = {k: v for k, v in health["requests"].items()
           if v and k not in ("submitted", "completed")}
    check(not bad, f"serve: {what}: health counters {bad} "
          f"last_errors={health.get('last_errors')}")
    counters = server.metrics_snapshot()["requests"]
    check(not counters.get("warmup_build_failures"),
          f"serve: {what}: warm-up build failed: {health.get('last_errors')}")
    for r in results:
        check(r.retries == 0 and not r.degradations,
              f"serve: {what}: request {r.request_id} took retries="
              f"{r.retries} degradations={r.degradations}")
        check(r.exec_key == want_key,
              f"serve: {what}: request {r.request_id} executed at "
              f"{r.exec_key!r}, asked for {want_key!r}")
    check(server.cache.misses == n_keys,
          f"serve: {what}: {server.cache.misses} executor builds for "
          f"{n_keys} distinct key(s)")


def leg_serve(rehearse: bool, compiles: CompileLog):
    """(facts, (base config, UNet config, the shared weights))."""
    from distrifuser_tpu import DistriConfig
    from distrifuser_tpu.pipelines import DistriSDXLPipeline, SimpleTokenizer
    from distrifuser_tpu.serve import (
        ExecKey,
        InferenceServer,
        ServeConfig,
        pipeline_executor_factory,
    )
    from distrifuser_tpu.utils.config import StepBatchConfig

    size, steps, join_at = (128, 16, 6) if rehearse else (1024, 50, 10)
    base_cfg = DistriConfig(height=size, width=size)
    check(rehearse or base_cfg.dtype == jnp.bfloat16,
          f"serve: DistriConfig.dtype is {base_cfg.dtype}, not bfloat16")
    say(f"serve: mesh {dict(base_cfg.mesh.shape)} ({base_cfg.mesh_plan}) "
        f"dtype {jnp.dtype(base_cfg.dtype).name}; building "
        f"{'tiny' if rehearse else 'SDXL'} weights from seeds")
    (ucfg, vcfg, tcs), weights, nbytes = build_weights(base_cfg, rehearse)
    say(f"serve: weights placed: {nbytes} bytes")

    built, pipelines = [], []

    def build_pipeline(key):
        built.append(key.short())
        dcfg = DistriConfig(
            height=key.height, width=key.width,
            do_classifier_free_guidance=key.cfg,
            # the compiled batch width: one image for the whole-batch server,
            # two rows for the slot pool so a joining request PACKS with the
            # one mid-denoise (parallel/rowpack.py)
            batch_size=2 if key.exec_mode == "step" else 1,
        )
        pipe = DistriSDXLPipeline.from_params(
            dcfg, ucfg, weights["unet"], vcfg, weights["vae"], tcs,
            weights["text"], scheduler=key.scheduler)
        check(all(isinstance(t, SimpleTokenizer) for t in pipe.tokenizers),
              "serve: expected the weightless SimpleTokenizer")
        pipelines.append(pipe)
        return pipe

    factory = pipeline_executor_factory(build_pipeline)
    common = dict(model_id="sdxl-random", scheduler="ddim",
                  mesh_plan=base_cfg.mesh_plan)

    def serve_config(**kw):
        return ServeConfig(buckets=((size, size),),
                           warmup_buckets=((size, size, steps),),
                           default_steps=steps, **kw)

    def want_key(exec_mode):
        return ExecKey(model_id=common["model_id"], scheduler="ddim",
                       height=size, width=size, steps=steps, cfg=True,
                       mesh_plan=base_cfg.mesh_plan,
                       exec_mode=exec_mode).short()

    def submit(server, seed):
        return server.submit(f"a photo of a corgi #{seed}", height=size,
                             width=size, seed=seed, guidance_scale=5.0)

    facts = {"image_size": size, "steps": steps, "weights_bytes": nbytes,
             "depth": "tiny" if rehearse else SMOKE_DEPTH}
    # the VAE upsamples once per level after the first (x8 for SDXL's four)
    out_px = size // 8 * 2 ** (len(vcfg.block_out_channels) - 1)

    # -- whole-batch (fused loop) server -----------------------------------
    # The warm bucket is built at start(): the factory compiles every program
    # with a throwaway request, outside the dispatch watchdog.  Every request
    # below must find them compiled.
    seeds = [11, 12, 13, 11]
    mark, t0 = compiles.mark(), time.time()
    with InferenceServer(factory, serve_config(max_batch_size=1),
                         **common) as fused:
        build_s = time.time() - t0
        cold = compiles.since(mark)
        say(f"serve: fused: server up, {len(cold)} compiles at build")
        mark, t0 = compiles.mark(), time.time()
        results = [submit(fused, seed).result(timeout=600) for seed in seeds]
        window_s = time.time() - t0
        recompiles, glue = split_recompiles(compiles.since(mark))
        check_server(fused, results, want_key("fused"), 1, "fused")
    images = [np.asarray(r.output) for r in results]
    for seed, img in zip(seeds, images):
        check_image(img, out_px, f"fused seed {seed}")
    check(not recompiles,
          f"serve: fused: compiled on the request path: {recompiles}")
    check(np.array_equal(images[0], images[3]),
          "serve: fused: the repeated seed is not byte-identical")
    check(not np.array_equal(images[0], images[1]),
          "serve: fused: distinct seeds gave the same image")
    facts["fused"] = {
        "requests": len(results),
        "compiles_at_build": len(cold),
        "recompiles_on_request_path": len(recompiles),
        "glue_op_compiles_on_request_path": glue,
        "repeated_seed_byte_identical": True,
    }
    if not rehearse:  # a CPU time is not a device metric
        facts["fused"].update({
            "server_start_s": round(build_s, 1),
            "cold_compile_s": by_program(cold),
            "first_request_e2e_s": round(results[0].e2e_s, 2),
            "warm_s_per_image": round(statistics.median(
                r.execute_s for r in results), 3),
            "warm_window_s_four_images": round(window_s, 2),
        })
    say(f"serve: fused: {facts['fused']}")

    # -- step-batching server: a request joins one that is mid-denoise ------
    step_cfg = serve_config(step_batching=StepBatchConfig(enabled=True,
                                                          slots=2))
    mark, t0 = compiles.mark(), time.time()
    with InferenceServer(factory, step_cfg, **common) as stepped:
        build_s = time.time() - t0
        cold = compiles.since(mark)
        say(f"serve: step: server up, {len(cold)} compiles at build")

        def overlapped(first_seed, joining_seed):
            """Submit one request, wait until it is ``join_at`` steps in,
            submit the second: (results, the step the first was at)."""
            f1 = submit(stepped, first_seed)

            def mid_denoise():
                s = stepped.metrics_snapshot()["step_batching"]
                return (s["occupied"] == 1
                        and s["remaining_steps_total"] <= steps - join_at)

            wait_for(mid_denoise, f"seed {first_seed} to reach step {join_at}",
                     600)
            at = steps - stepped.metrics_snapshot()[
                "step_batching"]["remaining_steps_total"]
            f2 = submit(stepped, joining_seed)
            return [f1.result(timeout=600), f2.result(timeout=600)], at

        mark, t0 = compiles.mark(), time.time()
        (ra, rb), joined_first = overlapped(seeds[0], seeds[1])
        (rc, ra2), joined_at = overlapped(seeds[2], seeds[3])
        window_s = time.time() - t0
        recompiles, glue = split_recompiles(compiles.since(mark))
        results = [ra, rb, rc, ra2]
        check_server(stepped, results, want_key("step"), 1, "step")
        snap = stepped.metrics_snapshot()
    check(0 < joined_first < steps and 0 < joined_at < steps,
          f"serve: step: the joins happened at steps {joined_first} and "
          f"{joined_at}, not mid-denoise")
    images_step = [np.asarray(r.output) for r in results]
    for seed, img in zip(seeds, images_step):
        check_image(img, out_px, f"step seed {seed}")
    check(not recompiles,
          f"serve: step: compiled on the request path: {recompiles}")
    # seed 11 ran first-and-joined-by-12, then joining-13-mid-denoise: same
    # bytes, or batch rows are not independent on this device
    check(np.array_equal(images_step[0], images_step[3]),
          "serve: step: the repeated seed is not byte-identical across "
          "cohort positions")
    sb, reqs = snap["step_batching"], snap["requests"]
    check(sb["joins"] == 4 and sb["preempts"] == 0,
          f"serve: step: joins={sb['joins']} preempts={sb['preempts']}")
    dispatches = reqs.get("stepbatch_dispatches", 0)
    check(dispatches < 4 * steps,
          f"serve: step: {dispatches} dispatches for {4 * steps} request-"
          "steps: overlapping requests never packed into one call")
    facts["step"] = {
        "requests": len(results),
        "compiles_at_build": len(cold),
        "joined_at_steps": [joined_first, joined_at],
        "dispatches": dispatches, "request_steps": 4 * steps,
        "recompiles_on_request_path": len(recompiles),
        "glue_op_compiles_on_request_path": glue,
        "repeated_seed_byte_identical": True,
        # a fact, not a check: the fused scan and the per-step programs are
        # different XLA programs
        "max_abs_vs_fused_same_seed":
            float(np.abs(images_step[0] - images[0]).max()),
    }
    if not rehearse:
        facts["step"].update({
            "server_start_s": round(build_s, 1),
            "cold_compile_s": by_program(cold),
            "warm_window_s_two_overlapped_pairs": round(window_s, 2),
            "e2e_s": [round(r.e2e_s, 2) for r in results],
        })
    say(f"serve: step: {facts['step']}")
    check(built == [want_key("fused"), want_key("step")],
          f"serve: build_pipeline ran for {built}")
    # two keys, one set of weights: both runners read the buffers placed
    # above (device_put onto the sharding an array already has is a no-op)
    shared = buffer_pointers(weights["unet"])
    for tag, pipe in zip(built, pipelines):
        check(buffer_pointers(pipe.runner.params) == shared,
              f"serve: the {tag} executor holds its own copy of the UNet")
    facts["executors_built"] = built
    return facts, (base_cfg, ucfg, weights)


def buffer_pointers(tree):
    """Device buffer address of every shard of every leaf."""
    return [s.data.unsafe_buffer_pointer()
            for leaf in jax.tree.leaves(tree)
            for s in leaf.addressable_shards]


# A compile on the request path that takes this long is a model program
# (denoise, encode, decode: 25 s and up at SDXL's widths on the chip machine)
# being built again.  Below it are eager glue ops — rowpack's pack/extract, a
# stack of PRNG keys — which compile in well under a second the first time a
# cohort pattern is seen; which patterns occur depends on when the joining
# request lands, so they are counted and reported, not judged.
RECOMPILE_FLOOR_S = 3.0


def split_recompiles(events):
    """(model-program recompiles, number of small glue-op compiles)."""
    big = [(n, round(s, 2)) for n, s in events if s >= RECOMPILE_FLOOR_S]
    return big, len(events) - len(big)


def by_program(events, floor_s: float = 1.0):
    """{program: seconds} for the compiles that took a second or more
    (summed over programs sharing a name)."""
    out = {}
    for name, secs in events:
        if secs >= floor_s:
            out[name] = round(out.get(name, 0.0) + secs, 1)
    return out


def on_first_device(tree):
    """The first device's shard of every (replicated) leaf, in place:
    `Shard.data` aliases the buffer, so this is a one-chip view of weights
    that live on the whole mesh, not a second copy."""
    dev0 = jax.devices()[0]
    return jax.tree.map(
        lambda leaf: next(s.data for s in leaf.addressable_shards
                          if s.device == dev0), tree)


def unet_step_flops(facts: dict, ucfg, weights, dtype) -> float:
    """FLOPs XLA counts for one CFG-folded forward of the served (depth-cut)
    UNet — bench.py's analytic count is the full-depth model's.  Counted on
    ONE device: jitted over the mesh-replicated tree the program would be a
    multi-device one, and Mosaic kernels outside shard_map refuse to be
    partitioned."""
    from bench import xla_step_flops

    return xla_step_flops(on_first_device(weights["unet"]), ucfg,
                          facts["image_size"], dtype)


def check_floor(facts: dict, step_flops: float) -> dict:
    """A warm time below the roofline floor is a broken clock, not a fast
    chip: the UNet's FLOPs times the steps, over the published peak of every
    chip present."""
    from distrifuser_tpu.utils.env import device_peaks

    peaks = device_peaks()
    n = len(jax.devices())
    floor = step_flops * facts["steps"] / (peaks.bf16_tflops * 1e12 * n)
    fused = facts["fused"]["warm_s_per_image"]
    check(fused >= floor, f"report: fused {fused}s per image is below the "
          f"roofline floor {floor:.2f}s")
    step_window = facts["step"]["warm_window_s_two_overlapped_pairs"]
    check(step_window >= 4 * floor,
          f"report: step mode made four images in {step_window}s, below the "
          f"roofline floor {4 * floor:.2f}s")
    return {"roofline_floor_s_per_image": round(floor, 3),
            "peak_bf16_tflops_per_chip": peaks.bf16_tflops}


# ---------------------------------------------------------------------------
# leg: several chips
# ---------------------------------------------------------------------------

# all-chip full_sync against one chip, bf16 latents after a handful of steps.
# full_sync is not bit-equal across device counts by design (per-patch
# GroupNorm moments are combined with a local Bessel correction, and bf16
# reductions over gathered halves re-associate); the repo's own bar for
# "the same image" is PSNR > 30 dB (tests, README), applied here to latents
# over the one-chip latents' own range.
FULL_SYNC_MIN_PSNR_DB = 30.0
# every mesh device holds the replicated weights and its share of the
# activations; chip 0 also holds what eager host-side code leaves on the
# default device (seeds, noise, the last image) — megabytes against
# gigabytes of weights
HBM_MAX_OVER_MIN = 1.5


def leg_chips(rehearse: bool, base_cfg, ucfg, weights) -> dict:
    from distrifuser_tpu import DistriConfig
    from distrifuser_tpu.parallel.runner import make_runner
    from distrifuser_tpu.schedulers import get_scheduler
    from distrifuser_tpu.utils.metrics import psnr

    mesh = base_cfg.mesh
    mesh_devices = set(mesh.devices.flat)
    # (c) printed, not judged: the mesh takes jax.devices() as listed
    order = [{"id": d.id, "coords": list(getattr(d, "coords", ()) or ()),
              "mesh_index": list(map(int, idx))}
             for idx, d in np.ndenumerate(mesh.devices)]
    say(f"chips: mesh {dict(mesh.shape)} device order: {order}")

    # (a) every leaf of every served tree lives on every mesh device
    for name, tree in (("unet", weights["unet"]), ("vae", weights["vae"]),
                       ("text", weights["text"])):
        for leaf in jax.tree.leaves(tree):
            check(leaf.sharding.device_set == mesh_devices,
                  f"chips: a {name} weight lives on "
                  f"{sorted(d.id for d in leaf.sharding.device_set)}, not "
                  "on the whole mesh")
    facts = {"mesh": dict(mesh.shape), "device_order": order}
    stats = [d.memory_stats() for d in jax.devices()]
    if all(s is not None for s in stats):
        in_use = [int(s["bytes_in_use"]) for s in stats]
        say(f"chips: bytes_in_use per device: {in_use}")
        check(max(in_use) <= HBM_MAX_OVER_MIN * min(in_use),
              f"chips: HBM in use per device {in_use}: max/min > "
              f"{HBM_MAX_OVER_MIN}")
        facts["bytes_in_use_per_device"] = in_use

    # (b) all-chip full_sync vs one chip, same seed, same process.  The
    # one-chip runner reads chip 0's shard of the replicated weights in place
    # (on_first_device): no second UNet copy on a 16 GB chip.
    size, n_steps = (128, 6) if rehearse else (1024, 6)
    lat_hw = size // 8
    k = jax.random.PRNGKey(7)
    lat = jax.random.normal(k, (1, lat_hw, lat_hw, ucfg.in_channels),
                            jnp.float32)
    enc = jax.random.normal(jax.random.fold_in(k, 1),
                            (2, 1, 77, ucfg.cross_attention_dim),
                            base_cfg.dtype)
    emb = (ucfg.projection_class_embeddings_input_dim
           - 6 * ucfg.addition_time_embed_dim)
    added = {
        "text_embeds": jax.random.normal(jax.random.fold_in(k, 2),
                                         (2, 1, emb), base_cfg.dtype),
        "time_ids": jnp.tile(jnp.asarray([size, size, 0, 0, size, size],
                                         jnp.float32)[None, None], (2, 1, 1)),
    }
    outs = {}
    for tag, devices, params in (
            ("all", None, weights["unet"]),
            ("one", jax.devices()[:1],
             on_first_device(weights["unet"]))):
        cfg = DistriConfig(devices=devices, height=size, width=size,
                           mode="full_sync")
        runner = make_runner(cfg, ucfg, params, get_scheduler("ddim"))
        outs[tag] = np.asarray(runner.generate(
            lat, enc, guidance_scale=5.0, num_inference_steps=n_steps,
            added_cond=added), np.float32)
        check(bool(np.isfinite(outs[tag]).all()),
              f"chips: full_sync on {tag} chip(s): latents not finite")
    rng = float(outs["one"].max() - outs["one"].min())
    db = float(psnr(outs["all"], outs["one"], data_range=rng))
    max_abs = float(np.abs(outs["all"] - outs["one"]).max())
    say(f"chips: full_sync {len(mesh_devices)} chips vs 1 chip after "
        f"{n_steps} steps: PSNR {db:.1f} dB, max abs {max_abs:.3g} over a "
        f"latent range of {rng:.3g}")
    check(db >= FULL_SYNC_MIN_PSNR_DB,
          f"chips: full_sync on all chips vs one chip: PSNR {db:.1f} dB < "
          f"{FULL_SYNC_MIN_PSNR_DB}")
    facts["full_sync_vs_one_chip"] = {"steps": n_steps,
                                      "psnr_db": round(db, 1),
                                      "max_abs": max_abs}
    return facts


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU debug mode: tiny configs, kernels interpreted, "
                    "no timing in the result; never a default")
    args = ap.parse_args()

    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse:
        if platform != "cpu":
            print(f"chip_smoke: --rehearse is the CPU debug mode; this "
                  f"process sees platform {platform!r} — run it without the "
                  "flag", file=sys.stderr)
            return 2
    elif platform != "tpu":
        print(f"chip_smoke: no accelerator: jax.devices()[0].platform is "
              f"{platform!r}.  This check only means something on a TPU; "
              "--rehearse debugs it on the CPU.", file=sys.stderr)
        return 3

    from distrifuser_tpu import native
    from distrifuser_tpu.utils.env import setup_compile_cache

    compiles = CompileLog()
    report = {"ok": False, "rehearsal": bool(args.rehearse)}
    try:
        report.update(leg_device(setup_compile_cache()))
        report.update(leg_kernels(args.rehearse))
        serve_facts, (base_cfg, ucfg, weights) = leg_serve(args.rehearse,
                                                           compiles)
        report["serve"] = serve_facts
        if len(devices) > 1:
            report["chips"] = leg_chips(args.rehearse, base_cfg, ucfg,
                                        weights)
        step_flops = unet_step_flops(serve_facts, ucfg, weights,
                                     base_cfg.dtype)
        report["serve"]["unet_tflop_per_step_xla"] = round(step_flops / 1e12,
                                                           4)
        if not args.rehearse:
            report["serve"].update(check_floor(serve_facts, step_flops))
            report["peak_hbm_bytes_per_device"] = [
                int(d.memory_stats()["peak_bytes_in_use"]) for d in devices]
            report["hbm_bytes_limit"] = int(
                devices[0].memory_stats()["bytes_limit"])
        check(native._lib is None and native._bpe_lib is None,
              "report: a native library was loaded; the smoke must not "
              "depend on distrifuser_tpu/native/*.so")
        say("loader: none (seeded random weights); tokenizer: "
            "SimpleTokenizer (weightless); native libraries: not loaded")
    except SmokeFailure as exc:
        print(f"chip_smoke FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    except Exception:
        traceback.print_exc()
        print("chip_smoke FAILED: a leg raised (traceback above)",
              file=sys.stderr, flush=True)
        return 1
    if not args.rehearse:
        report["compile_s_by_program"] = by_program(compiles.compiles)
        report["wall_s"] = round(time.time() - T0, 1)
    report.update({
        "ok": True,
        "compile_cache": {"hits": compiles.cache_hits,
                          "misses": compiles.cache_misses},
        # every way the program could have run something else was a check
        # above (health, retries, degradations, executed exec_mode, routed
        # kernel bits): reaching this line means none was taken
        "fallbacks_taken": [],
        "claim": None,
    })
    print(json.dumps(report), flush=True)
    # the verdict the driver parses: these two keys and nothing else, last
    print(json.dumps({"ok": True, "device": report["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
