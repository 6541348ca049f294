"""Headline benchmark: SDXL 50-step UNet denoise latency on real hardware.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Protocol mirrors the reference's benchmark mode
(/root/reference/scripts/run_sdxl.py:124-153): untimed warmup (includes
compilation), timed runs, median reported, VAE decode excluded
(--output_type latent equivalent).  The full real-architecture SDXL UNet runs
with random bf16 weights — latency is weight-value-independent.

vs_baseline: the reference's single-A100 SDXL 1024x1024 50-step DDIM latency
(PyTorch 2.2, fp16, CFG batch 2) is ~6.6 s/image (DistriFusion paper,
arXiv 2402.19481, Table 4's 1-GPU column; README.md:30 hardware).
vs_baseline = 6.6 / measured_seconds, i.e. >1 means faster than the
reference's single-GPU baseline at the same workload shape.

Wall-clock discipline: the whole run operates under ONE total budget counted
from process start.  The fast-to-compile stepwise mode runs first and its
result is held as ``best``; the fused 50-step loop is attempted only if
enough budget remains, and the watchdog prints ``best`` (rc 0) instead of a
timeout line whenever a real number exists.  Whatever happens, a parseable
JSON line is emitted before the budget expires.

No chip, no number: without an accelerator the run fails (exit 3) unless
``--preset tiny`` was asked for, which is a CPU contract check for
tests/test_bench_contract.py and never a device metric.  A mode that raises
is a non-zero exit, not a line on stderr.
"""

import argparse
import json
import os
import statistics
import sys
import threading
import time

A100_SDXL_1024_50STEP_S = 6.6

# Result holder the watchdog can flush: {"metric", "value", "unit",
# "vs_baseline"} once any mode has produced a real median.
_BEST = {}
_PRINT_LOCK = threading.Lock()
_PRINTED = threading.Event()


def analytic_step_flops(px: int) -> float:
    """Analytic FLOPs for one CFG-folded SDXL denoise step.

    13.12 TFLOP is the scan-corrected cost_analysis number at 1024^2
    (2026-07 roofline) — exact at 1024.  Elsewhere it is a LOWER bound (the
    floor check needs that direction): quadratic scaling above 1024
    under-counts attention's quartic term; below 1024 quadratic would
    OVER-count it, so scale quartically there — under everything, over
    nothing.
    """
    ratio = px / 1024
    return 13.12e12 * (ratio ** 2 if ratio >= 1.0 else ratio ** 4)


def xla_step_flops(params, ucfg, size: int, dtype, b: int = 1) -> float:
    """FLOPs XLA counts for one CFG-folded UNet forward (batch 2b) at
    ``size`` px: the COMPILED program's cost_analysis —
    Lowered.cost_analysis() is None on this runtime (PR 21, one v5e).
    Kernels without a cost estimate count as zero, so this too is a lower
    bound.  chip_smoke.py uses it for the floor of its depth-cut model."""
    import jax
    import jax.numpy as jnp

    from distrifuser_tpu.models import unet as unet_mod

    sample = jnp.zeros((2 * b, size // 8, size // 8, ucfg.in_channels), dtype)
    enc = jnp.zeros((2 * b, 77, ucfg.cross_attention_dim), dtype)
    added = None
    if ucfg.addition_embed_type == "text_time":
        ed = (ucfg.projection_class_embeddings_input_dim
              - 6 * ucfg.addition_time_embed_dim)
        added = {"text_embeds": jnp.zeros((2 * b, ed), dtype),
                 "time_ids": jnp.zeros((2 * b, 6), jnp.float32)}
    fn = jax.jit(lambda p, s, e: unet_mod.unet_forward(
        p, ucfg, s, jnp.asarray([500.0] * (2 * b)), e, added_cond=added))
    return float(fn.lower(params, sample, enc).compile()
                 .cost_analysis()["flops"])


def _emit(result: dict) -> None:
    """Print the one JSON line exactly once, even if the watchdog races the
    main thread at the deadline boundary."""
    with _PRINT_LOCK:
        if not _PRINTED.is_set():
            _PRINTED.set()
            print(json.dumps(result), flush=True)


def _arm_watchdog(deadline: float):
    """Fire at ``deadline`` (absolute epoch seconds): flush the best real
    result if one exists (rc 0), else emit the explicit timeout line (rc 2).

    One absolute deadline covers every hazard — backend-init hang, a slow
    compile — because the line is printed BEFORE an outer timeout can strike
    (rc=124 with nothing parseable on stdout).  Returns a disarm callback.
    """
    _disarmed = threading.Event()

    def fire():
        if _disarmed.wait(max(1.0, deadline - time.time())):
            return
        if _PRINTED.is_set():
            # main thread already printed its result but had not disarmed
            # yet (forced modes have no _BEST) — that run succeeded
            os._exit(0)
        if _BEST:
            _emit(_BEST)
            print("bench watchdog: budget expired, flushing best recorded "
                  "result", file=sys.stderr, flush=True)
            os._exit(0)
        _emit({
            "metric": "bench_watchdog_timeout",
            "value": -1.0,
            "unit": "s",
            "vs_baseline": 0.0,
        })
        print("bench watchdog: budget expired with no recorded result "
              "(TPU runtime hang?)", file=sys.stderr, flush=True)
        os._exit(2)

    threading.Thread(target=fire, daemon=True).start()
    return _disarmed.set


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--image_size", type=int, default=1024)
    parser.add_argument("--steps", type=int, default=50)
    parser.add_argument("--test_times", type=int, default=3)
    parser.add_argument("--preset", type=str, default="sdxl",
                        choices=["sdxl", "tiny"],
                        help="sdxl needs an accelerator; tiny is the CPU "
                        "contract check and must be asked for")
    parser.add_argument("--mode", type=str, default="auto",
                        choices=["auto", "fused", "stepwise"],
                        help="auto: stepwise first (records a number in "
                        "minutes), then the fused loop if budget remains; "
                        "fused/stepwise force a single mode.  (The hybrid "
                        "loop is a multi-chip feature — DistriConfig"
                        "(hybrid_loop=True) — and cannot engage on this "
                        "bench's single-chip config, where the fused "
                        "program already carries one UNet body.)")
    # Total wall clock from process start: the budget bounds the SUM of
    # attempts, not each one.
    parser.add_argument("--total_budget_s", type=float, default=1500.0)
    # Only start the fused attempt if at least this much budget remains;
    # below it, the stepwise number is the round's result.
    parser.add_argument("--fused_min_budget_s", type=float, default=420.0)
    # Quantized mode: weight_quant holds the kernels low-precision,
    # quant_compute routes their matmuls (DistriConfig semantics).  The
    # MFU line then carries a "mode" tag ("int8-auto", ...) so quantized
    # and bf16 runs land side by side in the bench trajectory — ROADMAP
    # item 5 gates on MFU/latency, not byte ratios.  MFU stays computed
    # against the bf16-equivalent FLOP count and bf16 peak, so a value
    # above the bf16 run's is exactly the compute-path win.
    parser.add_argument("--weight_quant", type=str, default="none",
                        choices=["none", "int8", "fp8"])
    parser.add_argument("--quant_compute", type=str, default="auto",
                        choices=["off", "auto", "dot"])
    args = parser.parse_args()
    deadline = time.time() + args.total_budget_s - 90.0  # margin before driver
    disarm_watchdog = _arm_watchdog(deadline)

    def remaining():
        return deadline - time.time()

    import jax
    import jax.numpy as jnp

    from distrifuser_tpu import DistriConfig
    from distrifuser_tpu.models import unet as unet_mod
    from distrifuser_tpu.parallel.runner import make_runner
    from distrifuser_tpu.schedulers import get_scheduler
    from distrifuser_tpu.utils.env import device_peaks, setup_compile_cache

    # persistent compilation cache: a repeated bench run skips the SDXL
    # compiles
    cache_dir = setup_compile_cache()
    devices = jax.devices()
    on_tpu = devices[0].platform != "cpu"
    preset = args.preset
    # provenance on stderr: an earlier round of chip numbers had silently run
    # fp32 — the effective platform/dtype is visible in every bench log
    print(f"bench provenance: platform={devices[0].platform} "
          f"device_kind={devices[0].device_kind!r} jax={jax.__version__} "
          f"compile_cache={cache_dir}", file=sys.stderr, flush=True)
    if preset == "sdxl" and not on_tpu:
        _emit({
            "metric": "bench_no_accelerator",
            "value": -1.0,
            "unit": "s",
            "vs_baseline": 0.0,
        })
        print("bench: no accelerator found (platform="
              f"{devices[0].platform}); the sdxl preset measures the chip "
              "and never the CPU — pass --preset tiny for the CPU contract "
              "check", file=sys.stderr, flush=True)
        sys.exit(3)
    # bf16 peak for the MFU line and the roofline floor: one table keyed by
    # device_kind (utils/env.py); a chip that is not in it is an error
    peak_flops = (device_peaks(devices[0].device_kind).bf16_tflops * 1e12
                  if on_tpu else None)
    if preset == "sdxl":
        ucfg = unet_mod.sdxl_config()
        size = args.image_size
        metric = f"sdxl_unet_{args.steps}step_{size}px_latency"
    else:
        ucfg = unet_mod.tiny_config(sdxl=True)
        size = 256
        metric = f"tiny_unet_{args.steps}step_{size}px_latency"

    dtype_cfg = DistriConfig(
        devices=devices[:1], height=size, width=size, warmup_steps=4,
        parallelism="patch",
    )
    dtype = dtype_cfg.dtype
    print(f"bench provenance: model dtype={jnp.dtype(dtype).name}",
          file=sys.stderr, flush=True)
    params = unet_mod.init_unet_params(jax.random.PRNGKey(0), ucfg, dtype)
    if args.weight_quant != "none":
        from distrifuser_tpu.models.weights import quantize_params

        params = quantize_params(params, args.weight_quant,
                                 compute=args.quant_compute)
        print(f"bench provenance: weight_quant={args.weight_quant} "
              f"quant_compute={args.quant_compute}",
              file=sys.stderr, flush=True)
    quant_tag = ("bf16" if args.weight_quant == "none"
                 else f"{args.weight_quant}-{args.quant_compute}")
    if args.weight_quant != "none":
        # a quantized run is a different trajectory than the bf16
        # headline — never let the two alias one metric name
        metric = f"{metric}_{quant_tag}"
    scheduler = get_scheduler("ddim")

    b = 1
    lat = jax.random.normal(
        jax.random.PRNGKey(1), (b, size // 8, size // 8, ucfg.in_channels), jnp.float32
    )
    enc = jax.random.normal(
        jax.random.PRNGKey(2), (2, b, 77, ucfg.cross_attention_dim), dtype
    )
    added = None
    if ucfg.addition_embed_type == "text_time":
        emb_dim = ucfg.projection_class_embeddings_input_dim - 6 * ucfg.addition_time_embed_dim
        added = {
            "text_embeds": jnp.zeros((2, b, emb_dim), dtype),
            "time_ids": jnp.tile(
                jnp.asarray([size, size, 0, 0, size, size], jnp.float32)[None, None],
                (2, b, 1),
            ),
        }

    def build_run(mode: str):
        cfg = DistriConfig(
            devices=devices[:1],  # single-chip headline number
            height=size,
            width=size,
            warmup_steps=4,
            parallelism="patch",
            use_cuda_graph=mode != "stepwise",
            weight_quant=args.weight_quant,
            quant_compute=args.quant_compute,
        )
        runner = make_runner(cfg, ucfg, params, scheduler)

        def run():
            out = runner.generate(
                lat, enc, guidance_scale=5.0, num_inference_steps=args.steps,
                added_cond=added,
            )
            # block_until_ready waits for the device on this runtime (PR 21,
            # one v5e: a 50-call explicit-tile Pallas chain read 66.4 ms
            # blocked vs 69.9 ms with a forced host transfer); the roofline
            # floor in measure() stays as the guard against an async escape
            return jax.block_until_ready(out)

        return run

    def warmup(mode: str):
        run = build_run(mode)
        t0 = time.time()
        run()  # compile + execute
        print(f"warmup (compile+run, mode={mode}): "
              f"{time.time() - t0:.1f}s", file=sys.stderr, flush=True)
        return run

    def _print_mfu(gen_seconds: float) -> None:
        """Emit an MFU line alongside the latency: XLA's
        own cost_analysis FLOPs for one folded-CFG UNet forward x steps,
        against the chip's bf16 peak.  vs_baseline is the fraction of the
        45% sustained-MFU assumption the roofline projection
        (scripts/project_scaling.py) rests on."""
        if preset != "sdxl" or not on_tpu or gen_seconds <= 0:
            return
        try:
            flops = xla_step_flops(params, ucfg, size, dtype, b)
            print(f"mfu: cost_analysis {flops / 1e12:.2f} TFLOP/step "
                  f"(analytic {analytic_step_flops(size) / 1e12:.2f})",
                  file=sys.stderr, flush=True)
            mfu = flops * args.steps / gen_seconds / peak_flops
            print(json.dumps({
                "metric": "mfu_vs_bf16_peak",
                "value": round(mfu, 4),
                "unit": "fraction",
                "vs_baseline": round(mfu / 0.45, 3),
                # which arithmetic produced it: "bf16", or
                # "<weight_quant>-<quant_compute>" — both modes report
                # against the SAME bf16-equivalent FLOP count and bf16
                # peak, so quantized > bf16 reads directly as the
                # compute-path speedup (ROADMAP item 5's gate)
                "mode": quant_tag,
            }), flush=True)
        except Exception as e:  # never let the MFU extra sink the bench
            print(f"mfu line skipped: {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)

    # Physical floor for one generation: per-step FLOPs (lower bound, see
    # analytic_step_flops) at 100% of bf16 peak.  A measurement below
    # this is a broken measurement (async escape), never a fast chip —
    # refuse to record it.
    def _plausibility_floor_s() -> float:
        if preset != "sdxl":
            return 0.0
        return analytic_step_flops(size) * args.steps / peak_flops

    def measure(mode: str) -> dict:
        run = warmup(mode)
        times = []
        for _ in range(args.test_times):
            t0 = time.perf_counter()
            run()
            times.append(time.perf_counter() - t0)
        val = statistics.median(times)
        floor = _plausibility_floor_s()
        if on_tpu and val < floor:
            raise RuntimeError(
                f"implausible {mode} measurement {val:.4f}s < roofline floor "
                f"{floor:.2f}s (100% bf16 peak) — async-dispatch escape, "
                "not recording")
        # baseline scaled to the actual step count (it is per-50-step-gen)
        vs = (
            (A100_SDXL_1024_50STEP_S * args.steps / 50) / val
            if preset == "sdxl" and size == 1024
            else 0.0
        )
        return {
            "metric": metric + ("" if mode == "fused" else f"_{mode}"),
            "value": round(val, 4),
            "unit": "s",
            "vs_baseline": round(vs, 3),
        }

    try:
        if args.mode != "auto":
            r = measure(args.mode)
            # record BEFORE the MFU extra: if the watchdog fires during the
            # MFU lowering, it flushes this real number instead of rc=2
            _BEST.update(r)
            _print_mfu(r["value"])
            _emit(r)
        else:
            # auto: fast path first so SOMETHING real is on record, then the
            # fused loop if the remaining budget can plausibly absorb its
            # compile.  The single-chip fused program carries ONE UNet body
            # (the is_sp one-phase collapse in runner._device_loop), so there
            # is no separate hybrid rung here — hybrid pays off multi-chip,
            # where the scripts' --hybrid_loop flag (DistriConfig.hybrid_loop)
            # selects it; bench.py's --mode only covers auto/fused/stepwise.
            # A mode that raises fails the run (the handler below emits the
            # explicit failure line and re-raises).
            _BEST.update(measure("stepwise"))
            print(f"stepwise result recorded: {_BEST} "
                  f"({remaining():.0f}s budget left)", file=sys.stderr,
                  flush=True)
            if remaining() > args.fused_min_budget_s:
                r = measure("fused")
                if 0 < r["value"] < _BEST["value"]:
                    # plain update (same four keys): no instant where the
                    # watchdog could observe an empty _BEST
                    _BEST.update(r)
            else:
                print("skipping fused attempt: insufficient budget",
                      file=sys.stderr, flush=True)
            # one MFU line for whichever mode won, before the final emit
            _print_mfu(_BEST["value"])
            _emit(_BEST)
    except Exception as e:
        # the one-parseable-line contract holds even for unexpected errors
        # (OOM, runner bug): emit an explicit failure line, then re-raise so
        # the traceback still reaches stderr
        _emit({
            "metric": "bench_exception",
            "value": -1.0,
            "unit": "s",
            "vs_baseline": 0.0,
        })
        print(f"bench failed: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        raise
    disarm_watchdog()


if __name__ == "__main__":
    main()
