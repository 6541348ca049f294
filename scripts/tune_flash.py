"""Flash-attention block-size sweep on the attached TPU.

Times `flash_sdpa` over the SDXL self-attention shapes (the two transformer
resolutions at a given image size, CFG batch 2) for a grid of (block_q,
block_k) tile sizes, against the XLA softmax path as baseline.  Prints the
best tiles per shape.  To apply them: prefer checking the winners into the
measured routing table — run the sweep through scripts/chip_campaign.py and
feed the log to scripts/update_sdpa_table.py (ops/sdpa_routing.py).  The
DISTRIFUSER_TPU_FLASH_BQ/BK env vars remain as a session-local override
(ops/attention.py reads both; setting either also selects the in-repo
kernel, since the tiles target it).

The reference gets its fused attention pre-tuned inside cuDNN/Flash
(modules/pp/attn.py:87,153); on TPU tile choice is ours to make, and the MXU
sweet spot depends on head_dim / VMEM budget, so measure, don't guess.

Usage (through the chip tool; exits non-zero without a TPU — a tile sweep in
interpret mode times the interpreter):
  python scripts/tune_flash.py --image_size 1024 --repeats 20
"""

import argparse
import itertools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def sdxl_attention_shapes(image_size: int):
    """(name, B, L, heads, head_dim) for SDXL self-attention at this size.

    SDXL runs transformers at latent/2 (640ch, 10 heads) and latent/4
    (1280ch, 20 heads); latent = image/8.  CFG batch 2.
    """
    lat = image_size // 8
    return [
        (f"down1 {lat//2}x{lat//2}", 2, (lat // 2) ** 2, 10, 64),
        (f"mid   {lat//4}x{lat//4}", 2, (lat // 4) ** 2, 20, 64),
    ]


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--image_size", type=int, default=1024)
    parser.add_argument("--repeats", type=int, default=20)
    parser.add_argument("--blocks", type=int, nargs="*",
                        default=[128, 256, 512, 1024])
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from distrifuser_tpu.ops.attention import _sdpa_xla
    from distrifuser_tpu.ops.flash_attention import flash_sdpa

    if jax.devices()[0].platform != "tpu":
        sys.exit(f"tune_flash needs a TPU, found {jax.devices()[0].platform}")
    dtype = jnp.bfloat16

    def bench(fn, *xs):
        fn(*xs).block_until_ready()  # compile
        t0 = time.perf_counter()
        for _ in range(args.repeats):
            out = fn(*xs)
        out.block_until_ready()
        return (time.perf_counter() - t0) / args.repeats

    for name, b, l, heads, d in sdxl_attention_shapes(args.image_size):
        c = heads * d
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, l, c), dtype)
        k = jax.random.normal(ks[1], (b, l, c), dtype)
        v = jax.random.normal(ks[2], (b, l, c), dtype)

        def xla_path(q, k, v):
            qh = q.reshape(b, l, heads, d)
            return _sdpa_xla(
                qh, k.reshape(b, l, heads, d), v.reshape(b, l, heads, d),
                1.0 / d**0.5,
            ).reshape(b, l, c)

        t_xla = bench(jax.jit(xla_path), q, k, v)
        print(f"{name}: L={l} H={heads} | XLA softmax {t_xla*1e3:.3f} ms")

        best = None
        for bq, bk in itertools.product(args.blocks, args.blocks):
            if l % bq or l % bk:
                continue
            try:
                t = bench(
                    lambda q, k, v: flash_sdpa(
                        q, k, v, heads=heads, block_q=bq, block_k=bk,
                    ),
                    q, k, v,
                )
            except Exception as e:
                print(f"  bq={bq:4d} bk={bk:4d}: FAILED {type(e).__name__}")
                continue
            mark = ""
            if best is None or t < best[0]:
                best, mark = (t, bq, bk), "  <- best"
            print(f"  bq={bq:4d} bk={bk:4d}: {t*1e3:.3f} ms "
                  f"({t_xla/t:.2f}x vs XLA){mark}")
        if best:
            print(f"  BEST: DISTRIFUSER_TPU_FLASH_BQ={best[1]} "
                  f"DISTRIFUSER_TPU_FLASH_BK={best[2]} "
                  f"({best[0]*1e3:.3f} ms, {t_xla/best[0]:.2f}x vs XLA)")


if __name__ == "__main__":
    main()
