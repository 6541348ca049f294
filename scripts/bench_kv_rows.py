#!/usr/bin/env python3
"""How to hold a KV cache of 64-wide heads, timed alone on the chip: a decode
loop's step over 5 attention layers - one row written into each layer's
cache, then one query row of 32 heads over 8 KV heads of 64 against the rows
written so far, positions 8192 onward of 8704 - for each layout of the
cache and each route `models/lfm2.py` could take (PR 45):

    apart64-xla      k, v [8, 8704, 64] apart (a row padded to 128 lanes in
                     HBM), `ops/attention.py gqa_sdpa_by_query_block`: what
                     `ops/gqa_cache.py cache_attention` gives a head of 64
    fused128-xla     k | v side by side in ONE row [8, 8704, 128], the
                     queries [q | 0], the values' half of the sum kept; XLA
    pair128-xla      two KV heads a row, k and v [4, 8704, 128] apart, the
                     queries widened with zeros (`models/lfm2.py`); XLA
    pair128-kernel   the same through `streamed_gqa_attention`

and prints, one JSON line a layout: us a step and layer, the bytes the
caches take with every row padded to whole tiles of 128 lanes, and what the
loop's body stages of them through VMEM (`utils/overlap.py cache_staging`,
which reads a copy's padded size off the compiled text: 17.8 MB for an
8.9 MB array of 64-wide rows).  Then `ops/moe.py gather_expert_sum` at
LFM2's expert (d 2048, f 1536, one held assignment a call) for each f-tile
that divides f.

    chiprun -- python3 scripts/bench_kv_rows.py
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from distrifuser_tpu.models import lfm2  # noqa: E402
from distrifuser_tpu.ops import moe  # noqa: E402
from distrifuser_tpu.ops.attention import gqa_sdpa_by_query_block  # noqa: E402
from distrifuser_tpu.ops.gqa_cache import streamed_gqa_attention  # noqa: E402
from distrifuser_tpu.utils.overlap import cache_staging  # noqa: E402

LAYERS, HQ, HKV, D, ROWS, START, STEPS = 5, 32, 8, 64, 8704, 8192, 256
BF16 = jnp.bfloat16


def xla(q, k, v, pos):
    return gqa_sdpa_by_query_block(q, k, v, q_positions=pos[None])


def kernel(q, k, v, pos):
    return streamed_gqa_attention(q, k, v, pos[None])[0]


def apart(route):
    """(the caches' shapes, a layer's step)."""
    def step(cache, q, k, v, pos):
        cache = [lax.dynamic_update_slice_in_dim(c, r.swapaxes(0, 1), pos, 1)
                 for c, r in zip(cache, (k, v))]
        return cache, route(q, *cache, pos)
    return [(HKV, ROWS, D)] * 2, step


def fused(route):
    def step(cache, q, k, v, pos):
        row = jnp.concatenate([k, v], axis=-1).swapaxes(0, 1)
        kv = lax.dynamic_update_slice_in_dim(cache[0], row, pos, 1)
        wide = jnp.concatenate([q, jnp.zeros_like(q)], axis=-1)
        wide = (wide.astype(jnp.float32) * 2 ** 0.5).astype(q.dtype)
        return [kv], route(wide, kv, kv, pos)[..., D:]
    return [(HKV, ROWS, 2 * D)], step


def paired(route):
    def step(cache, q, k, v, pos):
        cache = [lax.dynamic_update_slice_in_dim(c, lfm2.pack_rows(r, 2),
                                                 pos, 1)
                 for c, r in zip(cache, (k, v))]
        wide = lfm2.widen_queries(
            (q.astype(jnp.float32) * 2 ** 0.5).astype(q.dtype), 2, HQ // HKV)
        return cache, lfm2.own_slots(route(wide, *cache, pos), 2, HQ // HKV)
    return [(HKV // 2, ROWS, 2 * D)] * 2, step


LAYOUTS = {"apart64-xla": apart(xla), "fused128-xla": fused(xla),
           "pair128-xla": paired(xla), "pair128-kernel": paired(kernel)}


def time_layout(name, shapes, step):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (1, HQ, D), BF16)
    k, v = (jax.random.normal(key, (1, HKV, D), BF16) for key in keys[1:])
    caches = [[jnp.zeros(s, BF16) for s in shapes] for _ in range(LAYERS)]

    def loop(caches):
        def body(i, carry):
            caches, acc = carry
            out = []
            for cache in caches:
                cache, a = step(cache, q, k, v, START + i)
                acc = acc + a.astype(jnp.float32).sum()
                out.append(cache)
            return out, acc
        return lax.fori_loop(0, STEPS, body, (caches, jnp.zeros(())))

    run = jax.jit(loop, donate_argnums=0)
    compiled = run.lower(caches).compile()
    caches, _ = jax.block_until_ready(compiled(caches))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        caches, _ = jax.block_until_ready(compiled(caches))
        times.append(time.perf_counter() - t0)
    staging = cache_staging(compiled.as_text(), shapes=set(shapes))
    print(json.dumps({
        "layout": name, "caches": [list(s) for s in shapes],
        "us_per_step_and_layer": round(
            1e6 * min(times) / STEPS / LAYERS, 2),
        # a row in HBM is whole tiles of 128 lanes (what a copy of the
        # array moves, as `cache_staging` reads it off the compiled text)
        "cache_mb_as_held": LAYERS * sum(
            s[0] * s[1] * -(-s[2] // 128) * 128 * 2 for s in shapes) / 1e6,
        "cache_mb_unpadded": LAYERS * 2 * HKV * ROWS * D * 2 / 1e6,
        "staged_mb_per_step": staging["staged_bytes"] / 1e6,
        "writes": staging["writes"]}), flush=True)


def time_expert_tiles(d=2048, f=1536, experts=16, calls=18):
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(keys[0], (1, d), BF16)
    w1 = jax.random.normal(keys[1], (experts, d, 2 * f), BF16) / d ** 0.5
    w2 = jax.random.normal(keys[2], (experts, f, d), BF16) / f ** 0.5
    weights = jnp.full((1, 4), 0.25)
    for tile in (None, 1536, 768, 512, 384, 256):
        def loop(x):
            def body(i, x):
                for layer in range(calls):
                    # one of the four chosen experts is held: expert i + layer
                    idx = jnp.stack([(i + layer) % experts, 17, 18, 19])[None]
                    out, _ = moe.gather_expert_sum(
                        x, idx.astype(jnp.int32), weights, w1, w2,
                        first_expert=0, activation="silu", tile=tile)
                    x = x + (0.01 * out).astype(x.dtype)
                return x
            return lax.fori_loop(0, 64, body, x)

        run = jax.jit(loop)
        jax.block_until_ready(run(x))
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(run(x))
            times.append(time.perf_counter() - t0)
        print(json.dumps({
            "gather_expert_sum": {"d": d, "f": f, "tile": tile or "default"},
            "us_per_call": round(1e6 * min(times) / 64 / calls, 2),
            "expert_mb": 3 * d * f * 2 / 1e6}), flush=True)


if __name__ == "__main__":
    if jax.devices()[0].platform != "tpu":
        sys.exit("bench_kv_rows.py: no accelerator")
    for name, (shapes, step) in LAYOUTS.items():
        time_layout(name, shapes, step)
    time_expert_tiles()
