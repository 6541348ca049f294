"""Micro-benchmark: attention implementations at SDXL self-attention shapes.

Decides the sdpa routing policy with data (the
flash path is *measured*, not assumed): XLA einsum+softmax vs the in-repo
Pallas kernel (ops/flash_attention.py) vs jax.experimental's tuned TPU flash
kernel, at the (B*2 CFG, L, C, heads) shapes the SDXL UNet actually runs at
1024/2048 px plus the 3840 px level-1 long-context shape (57600 tokens; the
3840 px level-2 shape, 14400 tokens, is not 128-aligned and always takes
the XLA path, so it is not a routing decision).

Prints one JSON line per (shape, impl): {"impl", "L", "heads", "ms"}.

On-chip runs should go through scripts/chip_campaign.py (one process, all
phases serialized); its attn/tune lines feed scripts/update_sdpa_table.py,
which bakes the winners into the checked-in routing table
(ops/sdpa_routing.py).
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp


def timed(fn, *args, iters=20):
    fn(*args).block_until_ready()  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args()
    dtype = jnp.dtype(args.dtype)

    from distrifuser_tpu.ops.attention import _sdpa_xla
    from distrifuser_tpu.ops.flash_attention import flash_sdpa

    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as upstream_flash,
        )
    except ImportError:  # pragma: no cover
        upstream_flash = None

    # (L, C, heads) per SDXL attention level at [1024, 2048] px (CFG batch 2)
    shapes = [
        (4096, 640, 10),    # 1024px level-1
        (1024, 1280, 20),   # 1024px level-2
        (16384, 640, 10),   # 2048px level-1
        (4096, 1280, 20),   # 2048px level-2
        (57600, 640, 10),   # 3840px level-1 (ring/long-context regime)
    ]
    b = 2
    for (L, C, H) in shapes:
        d = C // H
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (b, L, C), dtype)
        k = jax.random.normal(key, (b, L, C), dtype)
        v = jax.random.normal(key, (b, L, C), dtype)

        def xla_path(q, k, v):
            qh = q.reshape(b, L, H, d)
            kh = k.reshape(b, L, H, d)
            vh = v.reshape(b, L, H, d)
            return _sdpa_xla(qh, kh, vh, 1.0 / d**0.5).reshape(b, L, C)

        results = {"xla": timed(jax.jit(xla_path), q, k, v, iters=args.iters)}
        try:
            results["pallas_inrepo"] = timed(
                jax.jit(lambda q, k, v: flash_sdpa(q, k, v, heads=H)),
                q, k, v, iters=args.iters,
            )
        except Exception as e:  # noqa: BLE001
            results["pallas_inrepo"] = f"failed: {type(e).__name__}"
        if upstream_flash is not None:
            def up(q, k, v):
                qh = q.reshape(b, L, H, d).transpose(0, 2, 1, 3)
                kh = k.reshape(b, L, H, d).transpose(0, 2, 1, 3)
                vh = v.reshape(b, L, H, d).transpose(0, 2, 1, 3)
                o = upstream_flash(qh, kh, vh, causal=False,
                                   sm_scale=1.0 / d**0.5)
                return o.transpose(0, 2, 1, 3).reshape(b, L, C)
            try:
                results["pallas_upstream"] = timed(
                    jax.jit(up), q, k, v, iters=args.iters
                )
            except Exception as e:  # noqa: BLE001
                results["pallas_upstream"] = f"failed: {type(e).__name__}"

        from common import emit_bench_line

        for impl, ms in results.items():
            emit_bench_line({
                "impl": impl, "L": L, "heads": H,
                "ms": round(ms, 3) if isinstance(ms, float) else ms,
            })


if __name__ == "__main__":
    main()
