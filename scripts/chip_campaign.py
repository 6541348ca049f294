"""Serialized real-chip measurement campaign.

One process holds the chip and runs every measurement phase in sequence (a
chip belongs to one process at a time, and phases share compiles).  Each
phase prints one JSON line (flushed); a phase that raises is reported on its
line, later phases still run so a partial run yields data, and the script
exits non-zero at the end if any phase failed.  A wall-clock deadline bounds
the whole campaign; remaining phases emit explicit "skipped" lines.  Needs a
TPU: exits 3 at start on any other platform.

Phases (cheap compiles first):
  attn       XLA vs in-repo Pallas vs upstream flash at SDXL shapes
  tune       (block_q, block_k) sweep for the in-repo kernel
  b1024_step 50-step stepwise latency @1024 (small programs)
  b1024      50-step fused latency @1024, default routing
  b1024_xla  same with DISTRIFUSER_TPU_FLASH=0 (the A/B round 2 never got)
  b2048      50-step fused latency @2048
  trace      jax.profiler trace of a short run -> chiprun_out/trace

Usage (through the chip tool; the log comes back under chiprun_out/):
  python scripts/chip_campaign.py --phases attn,gemm > chiprun_out/campaign.log
"""

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

START = time.time()
FAILED = []  # phases that raised; a non-empty list is a non-zero exit


def emit(phase: str, **kv):
    print(json.dumps({"phase": phase, "t": round(time.time() - START, 1), **kv}),
          flush=True)


def failed(phase: str, exc: Exception) -> str:
    """Record a failed phase (or sub-measurement) and return its log tag."""
    FAILED.append(phase)
    return f"failed:{type(exc).__name__}: {str(exc)[:200]}"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--phases", type=str,
                    default="attn,tune,gemm,b1024_step,b1024,b1024_xla,b2048,"
                            "b2048_ring,b1024_fp32,trace")
    ap.add_argument("--deadline_s", type=float, default=9000.0,
                    help="total wall-clock budget; later phases skip")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--test_times", type=int, default=3)
    args = ap.parse_args()
    phases = args.phases.split(",")

    import jax
    import jax.numpy as jnp

    from distrifuser_tpu.utils.env import setup_compile_cache

    cache_dir = setup_compile_cache()
    t0 = time.time()
    dev = jax.devices()[0]
    emit("init", ok=dev.platform == "tpu", platform=dev.platform,
         device_kind=dev.device_kind, compile_cache=cache_dir,
         init_s=round(time.time() - t0, 1))
    if dev.platform != "tpu":
        # a campaign on any other platform would bake CPU timings into the
        # routing tables under a chip's name
        sys.exit(3)

    import numpy as np

    def left():
        return args.deadline_s - (time.time() - START)

    def timed(step, x0, *extras, iters=20, reps=3):
        """Seconds per application of ``step(x, *extras) -> same-shape-x``.

        Chains ``iters`` applications inside ONE jit via fori_loop and
        reduces the final value to a SCALAR, then np.asarray's it: the
        scalar data-depends on every iteration (fori_loop carries cannot be
        dead-code-eliminated), so compute is forced, while the host
        transfer is 4 bytes — nothing to subtract, and one dispatch per
        measurement instead of ``iters``.

        ``extras`` (the K/V tensors) MUST be jit arguments, not closures:
        closed-over arrays are baked into the HLO as literal constants
        (~300 MB of program at L=57600).
        """
        chain = jax.jit(lambda x, *es: jnp.sum(jax.lax.fori_loop(
            0, iters, lambda i, y: step(y, *es), x)).astype(jnp.float32))
        np.asarray(chain(x0, *extras))  # compile + settle
        vals = []
        for _ in range(reps):
            t0 = time.perf_counter()
            np.asarray(chain(x0, *extras))
            vals.append(time.perf_counter() - t0)
        return statistics.median(vals) / iters

    # ---------------- attn: impl comparison at SDXL shapes ----------------
    if "attn" in phases and left() > 600:
        from distrifuser_tpu.ops.attention import _sdpa_xla
        from distrifuser_tpu.ops.flash_attention import (
            flash_sdpa, upstream_flash_sdpa,
        )

        shapes = [  # (L, C, heads) — SDXL levels at 1024/2048/3840 px,
            (4096, 640, 10), (1024, 1280, 20),
            (16384, 640, 10), (4096, 1280, 20),
            (57600, 640, 10),
            (4096, 1152, 16),  # PixArt-XL 1024px self-attn (head_dim 72)
            (4096, 1536, 24),  # SD3-medium 1024px image tokens (head_dim
                               # 64; the joint seq adds ~154 ctx tokens and
                               # routes XLA — this probes the aligned core)
        ]
        for (L, C, H) in shapes:
            if left() < 300:
                emit("attn", L=L, skipped="deadline")
                continue
            d = C // H
            ks = jax.random.split(jax.random.PRNGKey(0), 3)
            q = jax.random.normal(ks[0], (2, L, C), jnp.bfloat16)
            k = jax.random.normal(ks[1], (2, L, C), jnp.bfloat16)
            v = jax.random.normal(ks[2], (2, L, C), jnp.bfloat16)

            # each impl as an (x, k, v) -> x-shaped map so timed() can chain
            # iterations by data dependency; k/v ride as jit args (never
            # closures — see timed() on constant bloat)
            def xla_path(x, kk, vv):
                return _sdpa_xla(
                    x.reshape(2, L, H, d), kk.reshape(2, L, H, d),
                    vv.reshape(2, L, H, d), 1.0 / d**0.5,
                ).reshape(2, L, C)

            res = {}
            for name, fn in [
                ("xla", xla_path),
                ("inrepo",
                 lambda x, kk, vv: flash_sdpa(x, kk, vv, heads=H)),
                ("upstream",
                 lambda x, kk, vv: upstream_flash_sdpa(x, kk, vv, heads=H)),
            ]:
                try:
                    res[name] = round(timed(fn, q, k, v) * 1e3, 3)
                except Exception as e:
                    res[name] = failed(f"attn:{name}:L={L}", e)
            emit("attn", L=L, heads=H, head_dim=d, batch=2, ms=res)

    # ---------------- tune: flash-kernel tile sweeps -----------------------
    if "tune" in phases and left() > 600:
        from distrifuser_tpu.ops.flash_attention import (
            flash_sdpa, upstream_flash_sdpa,
        )

        sweeps = [  # (phase name, kernel, tile grid)
            ("tune", flash_sdpa,
             [(bq, bk) for bq in (128, 256, 512)
              for bk in (128, 256, 512, 1024)]),
            ("tune_upstream", upstream_flash_sdpa,
             [(bq, bk) for bq in (256, 512, 1024)
              for bk in (256, 512, 1024, 2048)]),
        ]
        # 57600 = 2^8 * 225: only tiles <= 256 divide it, so the grids'
        # small corner is what makes the 3840px level-1 shape sweepable
        for (L, C, H) in [(4096, 640, 10), (16384, 640, 10),
                          (57600, 640, 10)]:
            if left() < 300:
                emit("tune", L=L, skipped="deadline")
                continue
            ks = jax.random.split(jax.random.PRNGKey(0), 3)
            q = jax.random.normal(ks[0], (2, L, C), jnp.bfloat16)
            k = jax.random.normal(ks[1], (2, L, C), jnp.bfloat16)
            v = jax.random.normal(ks[2], (2, L, C), jnp.bfloat16)
            for phase_name, kernel, grid in sweeps:
                res = {}
                for bq, bk in grid:
                    if L % bq or L % bk:
                        continue
                    try:
                        res[f"{bq}x{bk}"] = round(timed(
                            lambda x, kk, vv, bq=bq, bk=bk, kern=kernel: kern(
                                x, kk, vv, heads=H, block_q=bq, block_k=bk),
                            q, k, v, iters=10,
                        ) * 1e3, 3)
                    except Exception as e:
                        res[f"{bq}x{bk}"] = failed(
                            f"{phase_name}:{bq}x{bk}:L={L}", e)
                emit(phase_name, L=L, heads=H, head_dim=C // H, batch=2,
                     ms=res)

    # ---------------- gemm: quantized-compute impl comparison --------------
    if "gemm" in phases and left() > 600:
        from distrifuser_tpu.ops.linear import _quantized_matmul
        from distrifuser_tpu.parallel.compress import (fp8_supported,
                                                       quantize_weight)

        # (M, K, N): token-count x reduction x output dims of the hot
        # quantized matmuls — SDXL level-0/1 attention + MLP projections
        # at 1024px, SD3-medium image-stream projections, 2048px level 1.
        gemm_shapes = [
            (1024, 1280, 5120), (4096, 640, 2560), (4096, 640, 640),
            (4096, 1536, 6144), (16384, 640, 2560),
        ]
        gemm_iters = 20
        gemm_modes = ["int8"] + (["fp8"] if fp8_supported() else [])
        for (M, K, N) in gemm_shapes:
            if left() < 300:
                emit("gemm", M=M, skipped="deadline")
                continue
            x = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.bfloat16)
            w1 = np.asarray(jax.random.normal(
                jax.random.PRNGKey(1), (K, N), jnp.bfloat16))
            w2 = np.asarray(jax.random.normal(
                jax.random.PRNGKey(2), (N, K), jnp.bfloat16))
            for mode in gemm_modes:
                # chain by PAIRS of matmuls ([M,K]@[K,N] then [M,N]@[N,K]
                # back to x's shape) so timed() can data-depend iterations;
                # ms is per PAIR — only the impl ordering matters, and it
                # is shared by every column
                res = {}
                for impl in ("dequant", "dot", "pallas"):
                    q1 = quantize_weight(jnp.asarray(w1), mode, compute=impl)
                    q2 = quantize_weight(jnp.asarray(w2), mode, compute=impl)

                    def pair(xx, a, b):
                        return _quantized_matmul(
                            _quantized_matmul(xx, a), b).astype(xx.dtype)

                    try:
                        res[impl] = round(timed(pair, x, q1, q2,
                                                iters=gemm_iters) * 1e3, 3)
                    except Exception as e:
                        res[impl] = failed(f"gemm:{mode}:{impl}:M={M}", e)
                emit("gemm", M=M, K=K, N=N, mode=mode,
                     backend=dev.platform, ms=res)
            # pallas tile sweep (int8 only: the tile optimum is about the
            # accumulator walk, not the payload dtype)
            res = {}
            q1 = quantize_weight(jnp.asarray(w1), "int8", compute="pallas")
            q2 = quantize_weight(jnp.asarray(w2), "int8", compute="pallas")
            for bm, bn, bk in [(128, 256, 512), (256, 256, 512),
                               (256, 512, 512), (512, 256, 1024)]:
                os.environ["DISTRIFUSER_TPU_GEMM"] = "pallas"
                os.environ["DISTRIFUSER_TPU_GEMM_BM"] = str(bm)
                os.environ["DISTRIFUSER_TPU_GEMM_BN"] = str(bn)
                os.environ["DISTRIFUSER_TPU_GEMM_BK"] = str(bk)
                jax.clear_caches()  # env routing is trace-time
                try:
                    res[f"{bm}x{bn}x{bk}"] = round(timed(
                        lambda xx, a, b: _quantized_matmul(
                            _quantized_matmul(xx, a), b).astype(xx.dtype),
                        x, q1, q2, iters=min(gemm_iters, 10),
                    ) * 1e3, 3)
                except Exception as e:
                    res[f"{bm}x{bn}x{bk}"] = failed(
                        f"gemm_tune:{bm}x{bn}x{bk}:M={M}", e)
            for var in ("DISTRIFUSER_TPU_GEMM", "DISTRIFUSER_TPU_GEMM_BM",
                        "DISTRIFUSER_TPU_GEMM_BN", "DISTRIFUSER_TPU_GEMM_BK"):
                os.environ.pop(var, None)
            jax.clear_caches()
            emit("gemm_tune", M=M, K=K, N=N, mode="int8",
                 backend=dev.platform, ms=res)

    # ---------------- full-model latencies --------------------------------
    def bench_unet(size, stepwise, label, flash_env=None, attn_impl="gather",
                   dtype=None):
        if flash_env is not None:
            os.environ["DISTRIFUSER_TPU_FLASH"] = flash_env
        elif "DISTRIFUSER_TPU_FLASH" in os.environ:
            del os.environ["DISTRIFUSER_TPU_FLASH"]
        from distrifuser_tpu import DistriConfig
        from distrifuser_tpu.models import unet as unet_mod
        from distrifuser_tpu.parallel.runner import make_runner
        from distrifuser_tpu.schedulers import get_scheduler

        ucfg = unet_mod.sdxl_config()
        cfg = DistriConfig(devices=jax.devices()[:1], height=size, width=size,
                           warmup_steps=4, parallelism="patch",
                           attn_impl=attn_impl, dtype=dtype,
                           use_cuda_graph=not stepwise)
        emit(label + "_cfg", dtype=str(jnp.dtype(cfg.dtype).name))
        params = unet_mod.init_unet_params(jax.random.PRNGKey(0), ucfg, cfg.dtype)
        runner = make_runner(cfg, ucfg, params, get_scheduler("ddim"))
        lat = jax.random.normal(jax.random.PRNGKey(1),
                                (1, size // 8, size // 8, ucfg.in_channels),
                                jnp.float32)
        enc = jax.random.normal(jax.random.PRNGKey(2),
                                (2, 1, 77, ucfg.cross_attention_dim), cfg.dtype)
        emb = (ucfg.projection_class_embeddings_input_dim
               - 6 * ucfg.addition_time_embed_dim)
        added = {"text_embeds": jnp.zeros((2, 1, emb), cfg.dtype),
                 "time_ids": jnp.tile(jnp.asarray(
                     [size, size, 0, 0, size, size], jnp.float32)[None, None],
                     (2, 1, 1))}

        def run():
            out = runner.generate(lat, enc, guidance_scale=5.0,
                                  num_inference_steps=args.steps,
                                  added_cond=added)
            return jax.block_until_ready(out)

        tc0 = time.time()
        run()  # warmup/compile
        compile_s = round(time.time() - tc0, 1)
        times = [0.0] * args.test_times
        for i in range(args.test_times):
            t = time.perf_counter()
            run()
            times[i] = time.perf_counter() - t
        med = statistics.median(times)
        # vs_a100 only where the workload matches the baseline config: 1024px
        # in the default (bf16) dtype — the fp32 ablation exists to quantify
        # the dtype delta, not to compare against the A100 number
        comparable = size == 1024 and dtype is None
        emit(label, size=size, steps=args.steps, s=round(med, 4),
             compile_s=compile_s,
             vs_a100=round(6.6 * args.steps / 50 / med, 3) if comparable else None)
        return med

    # b2048 vs b2048_ring: the gather-vs-ring layout A/B at the north-star
    # resolution — the analytic HBM table says ring is what fits 3840²; this
    # measures its latency cost at 2048².  b1024_fp32 quantifies the dtype
    # delta on otherwise identical programs.
    for label, size, stepwise, flash, impl, dt in [
        ("b1024_step", 1024, True, None, "gather", None),
        ("b1024", 1024, False, None, "gather", None),
        ("b1024_xla", 1024, False, "0", "gather", None),
        ("b2048", 2048, False, None, "gather", None),
        ("b2048_ring", 2048, False, None, "ring", None),
        ("b1024_fp32", 1024, False, None, "gather", jnp.float32),
        # opt-in (not in the default phase list): the reference's showcase
        # resolution, single-chip — viable since the (64,16) flash route
        # (256x256 tiles, the only power-of-2 divisor class of 57600)
        ("b3840", 3840, False, None, "gather", None),
    ]:
        if label not in phases:
            continue
        if left() < 900:
            emit(label, skipped="deadline")
            continue
        try:
            bench_unet(size, stepwise, label, flash, impl, dt)
        except Exception as e:
            emit(label, ok=False, error=failed(label, e))
        finally:
            # drop every live executable + its device scratch between
            # phases: keeping b1024_step's ~50 per-step programs alive OOMs
            # every later phase (HBM holds one 2.6B-param model + one
            # program set, not two)
            import gc
            jax.clear_caches()
            gc.collect()

    # ---------------- trace: profiler capture ------------------------------
    if "trace" in phases and left() > 300:
        try:
            trace_dir = os.path.join(REPO, "chiprun_out", "trace")
            os.makedirs(trace_dir, exist_ok=True)
            from distrifuser_tpu import DistriConfig
            from distrifuser_tpu.models import unet as unet_mod
            from distrifuser_tpu.parallel.runner import make_runner
            from distrifuser_tpu.schedulers import get_scheduler

            ucfg = unet_mod.sdxl_config()
            cfg = DistriConfig(devices=jax.devices()[:1], height=1024,
                               width=1024, warmup_steps=1, parallelism="patch")
            params = unet_mod.init_unet_params(jax.random.PRNGKey(0), ucfg,
                                               cfg.dtype)
            runner = make_runner(cfg, ucfg, params, get_scheduler("ddim"))
            lat = jnp.zeros((1, 128, 128, ucfg.in_channels), jnp.float32)
            enc = jnp.zeros((2, 1, 77, ucfg.cross_attention_dim), cfg.dtype)
            emb = (ucfg.projection_class_embeddings_input_dim
                   - 6 * ucfg.addition_time_embed_dim)
            added = {"text_embeds": jnp.zeros((2, 1, emb), cfg.dtype),
                     "time_ids": jnp.zeros((2, 1, 6), jnp.float32)}

            def short():
                return runner.generate(lat, enc, guidance_scale=5.0,
                                       num_inference_steps=4, added_cond=added)

            jax.block_until_ready(short())  # compile outside the trace
            # perfetto json.gz alongside the xplane pb: stdlib-parseable by
            # scripts/analyze_trace.py
            with jax.profiler.trace(trace_dir, create_perfetto_trace=True):
                jax.block_until_ready(short())
            emit("trace", ok=True, dir=trace_dir)
        except Exception as e:
            emit("trace", ok=False, error=failed("trace", e))

    emit("done", total_s=round(time.time() - START, 1), failed=FAILED)
    if FAILED:
        sys.exit(1)


if __name__ == "__main__":
    main()
