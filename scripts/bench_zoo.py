"""Single-chip latency for the beyond-reference model families.

The 2026-07 campaign benched the reference-parity SDXL UNet; this probe
takes the same campaign-style JSON lines for the round-5
additions at their family-native sampling defaults, random weights (latency
is weight-independent):

  * SD3-medium MMDiT (2B), 1024^2, 28-step flow-euler, CFG 7.0
  * PixArt-XL DiT, 1024^2, 20-step DDIM(-like), CFG 4.5

Timing discipline matches bench.py: block_until_ready on the final latents,
compile outside the timed window.  A family that raises is reported on its
line and makes the script exit non-zero at the end.  Needs a TPU.

Usage (through the chip tool; one process holds the chip):
    python scripts/bench_zoo.py [--steps_sd3 28] [--steps_pixart 20]
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

START = time.time()
FAILED = []  # families that raised; a non-empty list is a non-zero exit


def emit(phase, **kv):
    from common import BENCH_SCHEMA_VERSION

    print(json.dumps({"schema": BENCH_SCHEMA_VERSION, "phase": phase,
                      "t": round(time.time() - START, 1), **kv}),
          flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps_sd3", type=int, default=28)
    ap.add_argument("--steps_pixart", type=int, default=20)
    ap.add_argument("--test_times", type=int, default=2)
    ap.add_argument("--families", type=str, default="sd3,pixart")
    args = ap.parse_args()

    import gc

    import jax
    import jax.numpy as jnp

    from distrifuser_tpu import DistriConfig
    from distrifuser_tpu.schedulers import get_scheduler
    from distrifuser_tpu.utils.env import setup_compile_cache

    cache_dir = setup_compile_cache()
    dev = jax.devices()[0]
    emit("init", ok=dev.platform == "tpu", platform=dev.platform,
         device_kind=dev.device_kind, compile_cache=cache_dir)
    if dev.platform != "tpu":
        sys.exit(3)  # a family latency is a chip number or nothing

    families = set(args.families.split(","))
    unknown = families - {"sd3", "pixart"}
    if unknown:
        # hard error: a typo must not silently burn chip time producing an
        # empty JSON stream
        sys.exit(f"unknown --families {sorted(unknown)}; "
                 "choose from sd3,pixart")

    def run_family(label, build):
        try:
            runner, gen = build()
            tc0 = time.time()
            jax.block_until_ready(gen())  # compile + execute
            compile_s = round(time.time() - tc0, 1)
            times = []
            for _ in range(args.test_times):
                t0 = time.perf_counter()
                jax.block_until_ready(gen())
                times.append(time.perf_counter() - t0)
            emit(label, s=round(statistics.median(times), 4),
                 compile_s=compile_s)
        except Exception as e:
            FAILED.append(label)
            emit(label, ok=False, error=f"{type(e).__name__}: {str(e)[:200]}")
        finally:
            jax.clear_caches()
            gc.collect()

    if "sd3" in families:
        def build_sd3():
            from distrifuser_tpu.models import mmdit as mmdit_mod
            from distrifuser_tpu.parallel.mmdit_sp import MMDiTDenoiseRunner

            mcfg = mmdit_mod.sd3_config(128)  # 1024^2
            cfg = DistriConfig(devices=jax.devices()[:1], height=1024,
                               width=1024, warmup_steps=4,
                               parallelism="patch")
            emit("zoo_sd3_cfg", dtype=str(jnp.dtype(cfg.dtype).name),
                 steps=args.steps_sd3)
            params = mmdit_mod.init_mmdit_params(
                jax.random.PRNGKey(0), mcfg, cfg.dtype)
            runner = MMDiTDenoiseRunner(cfg, mcfg, params,
                                        get_scheduler("flow-euler"))
            lat = jax.random.normal(
                jax.random.PRNGKey(1), (1, 128, 128, mcfg.in_channels),
                jnp.float32)
            enc = jax.random.normal(
                jax.random.PRNGKey(2), (2, 1, 154, mcfg.joint_attention_dim),
                cfg.dtype)
            pooled = jax.random.normal(
                jax.random.PRNGKey(3), (2, 1, mcfg.pooled_projection_dim),
                cfg.dtype)

            def gen():
                return runner.generate(lat, enc, pooled, guidance_scale=7.0,
                                       num_inference_steps=args.steps_sd3)
            return runner, gen

        run_family("zoo_sd3_1024", build_sd3)

    if "pixart" in families:
        def build_pixart():
            from distrifuser_tpu.models import dit as dit_mod
            from distrifuser_tpu.parallel.dit_sp import DiTDenoiseRunner

            dcfg = dit_mod.pixart_config(128)  # 1024^2
            cfg = DistriConfig(devices=jax.devices()[:1], height=1024,
                               width=1024, warmup_steps=4,
                               parallelism="patch")
            emit("zoo_pixart_cfg", dtype=str(jnp.dtype(cfg.dtype).name),
                 steps=args.steps_pixart)
            params = dit_mod.init_dit_params(
                jax.random.PRNGKey(0), dcfg, cfg.dtype)
            runner = DiTDenoiseRunner(cfg, dcfg, params,
                                      get_scheduler("ddim"))
            lat = jax.random.normal(
                jax.random.PRNGKey(1), (1, 128, 128, dcfg.in_channels),
                jnp.float32)
            enc = jax.random.normal(
                jax.random.PRNGKey(2), (2, 1, 120, dcfg.caption_dim),
                cfg.dtype)

            def gen():
                return runner.generate(lat, enc, guidance_scale=4.5,
                                       num_inference_steps=args.steps_pixart)
            return runner, gen

        run_family("zoo_pixart_1024", build_pixart)

    emit("done", total_s=round(time.time() - START, 1), failed=FAILED)
    if FAILED:
        sys.exit(1)


if __name__ == "__main__":
    main()
