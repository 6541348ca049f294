#!/usr/bin/env python3
"""Read the numbers the latent-attention rewrite cell's logit limits are set
from.

    python3 benchmark/calibrate_kanana.py --variants sound,float8_e4m3fn \
        --seeds 6 [--first-seed N] [--out FILE] [--forms]

For each variant (the latent cache's dtype: "sound" is the configuration as
committed, the cache in the served bfloat16; "float8_e4m3fn" is the control,
the cache a precision below) and each seed: the language
model's weights from the seed, the program's own `PromptRewriter` - the
three programs the cell's path runs, at the timed sizes, without the
diffusion side - over one prompt, and the served logits against the float32
reference as `Reference.generate` compares them.  Also the host-clock time
of a warm full prefill, of the prefill that enters the snapshot, and of the
whole rewrite.  ``--forms`` times one layer's attention core for the 128
entering rows in both forms (absorbed against the cache; the cache expanded
to per-head keys and values, then materialised).  One JSON line per
reading; `benchmark/limits/` records the readings a limit was set from.  Not
part of a benchmark run.
"""

import argparse
import json
import os
import sys
import time

import run as bench_run  # benchmark/run.py, beside this file

sys.path.insert(0, bench_run.ROOT)
CONFIG = "kanana-2-30b-sdxl-rewrite"


def time_entering_forms(cfg, rows, cached, dtype, emit):
    """One layer's attention core for ``rows`` queries entering a cache of
    ``cached`` + ``rows`` positions, both forms, warm, on this device."""
    import jax
    import jax.numpy as jnp

    from distrifuser_tpu.ops import mla

    h, c_dim, r = cfg.num_attention_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dn, dv, s = cfg.qk_nope_head_dim, cfg.v_head_dim, cached + rows
    k = iter(jax.random.split(jax.random.PRNGKey(0), 8))
    q_nope = jax.random.normal(next(k), (rows, h, dn), dtype)
    q_pe = jax.random.normal(next(k), (rows, h, r), dtype)
    c = jax.random.normal(next(k), (s, c_dim), dtype)
    k_pe = jax.random.normal(next(k), (s, r), dtype)
    k_up = (jax.random.normal(next(k), (h, dn, c_dim)) / c_dim ** 0.5).astype(dtype)
    v_up = (jax.random.normal(next(k), (h, c_dim, dv)) / c_dim ** 0.5).astype(dtype)
    positions = cached + jnp.arange(rows)

    @jax.jit
    def absorbed(q_nope, q_pe, c, k_pe, k_up, v_up):
        q_lat = jnp.einsum("thd,hdc->thc", q_nope, k_up)
        out = mla.absorbed_attention(q_lat, q_pe, c, k_pe,
                                     q_positions=positions,
                                     scale=cfg.softmax_scale)
        return jnp.einsum("thc,hcd->thd", out, v_up)

    @jax.jit
    def expanded(q_nope, q_pe, c, k_pe, k_up, v_up):
        k_nope = jnp.einsum("sc,hdc->shd", c, k_up)
        v = jnp.einsum("sc,hcd->shd", c, v_up)
        logits = (jnp.einsum("thd,shd->hts", q_nope, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("thr,sr->hts", q_pe, k_pe,
                               preferred_element_type=jnp.float32)
                  ) * cfg.softmax_scale
        seen = jnp.arange(s)[None, :] <= positions[:, None]
        w = jax.nn.softmax(jnp.where(seen[None], logits, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", w.astype(v.dtype), v)

    args = (q_nope, q_pe, c, k_pe, k_up, v_up)
    out = {}
    for name, fn in (("absorbed", absorbed), ("expanded", expanded)):
        got = jax.block_until_ready(fn(*args))
        t0 = time.time()
        for _ in range(20):
            got = fn(*args)
        jax.block_until_ready(got)
        out[name + "_ms"] = round((time.time() - t0) / 20 * 1e3, 4)
        out[name] = got
    diff = jnp.abs(out.pop("absorbed").astype(jnp.float32)
                   - out.pop("expanded").astype(jnp.float32)).max()
    emit({"forms_of_one_layers_entering_attention": out, "rows": rows,
          "cache_rows": s, "max_abs_difference": float(diff)})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--variants", default="sound,float8_e4m3fn")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_400_000_001)
    ap.add_argument("--out", help="append each reading to this file too")
    ap.add_argument("--forms", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    base = bench_run.load_json("configs", CONFIG + ".json")
    if args.rehearse:
        base = bench_run.merged(base, base["rehearse"])
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from benchmark.families import deepseek_v3_sdxl as fam
    from benchmark.harness.traffic import request_pool
    from benchmark.reference import deepseek_v3_sdxl as ref
    from distrifuser_tpu.pipelines import PromptRewriter, SimpleTokenizer

    if not args.rehearse:
        if jax.devices()[0].platform != "tpu":
            print("calibrate_kanana.py: no accelerator", file=sys.stderr)
            return bench_run.EXIT_NO_CHIP
        bench_run.setup_compile_cache()
    dtype = jnp.dtype(base["dtype"])
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("x",))
    toks = [SimpleTokenizer(base[k]["vocab_size"])
            for k in ("text_encoder", "text_encoder_2")]
    traffic = bench_run.load_json("traffic", "solo-1024-rewrite.json")

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")

    for variant in args.variants.split(","):
        config = dict(base)
        if variant != "sound":
            config["cache_dtype"] = variant
        family = fam.Family(config)
        reference = ref.Reference(config, 0, 0)
        if args.forms:
            rw = family.rewrite
            block = family.lm_config.prefill_block
            prompt_len = rw.instruction_tokens + rw.user_tokens
            rows = prompt_len - (prompt_len - 1) // block * block
            time_entering_forms(family.lm_config, rows, prompt_len - rows,
                                dtype, emit)
        for k in range(args.seeds):
            seed = args.first_seed + 7919 * k
            t0 = time.time()
            weights = fam.init_lm_on_device(
                family.lm_config, fam.F.seed_key(seed, fam.LM_STREAM), dtype,
                mesh, family.rewrite)
            jax.block_until_ready(weights)
            rewriter = PromptRewriter(family.lm_config, weights,
                                      family.rewrite, toks)
            prompt = request_pool(traffic, seed)[0]["prompt"]
            jax.block_until_ready(rewriter([prompt]))  # compiles, snapshots
            ids = rewriter.lm_ids(prompt)
            t1 = time.time()
            out = jax.block_until_ready(rewriter._prefill(weights, ids))
            t2 = time.time()
            del out
            out = jax.block_until_ready(rewriter._prefill(
                weights, ids[rewriter._prefix_len:], rewriter.snapshot()))
            t3 = time.time()
            del out
            jax.block_until_ready(rewriter([prompt]))
            t4 = time.time()
            served = rewriter.served[-1]
            with jax.default_matmul_precision("highest"):
                checks, agree = reference.compare_logits(
                    weights, ref.prompt_ids(config, prompt), served)
            counters = dict(zip(rewriter.lm.counters,
                                np.asarray(served.counters).tolist()))
            emit({"variant": variant, "seed": seed,
                  **{name: value for name, value, _, _ in checks},
                  "median_by_quarter": [
                      float(np.median(q)) for q in np.array_split(
                          reference.position_errors, 4)],
                  "argmax_agree": agree, "counters": counters,
                  "full_prefill_s": round(t2 - t1, 5),
                  "entering_prefill_s": round(t3 - t2, 5),
                  "rewrite_s": round(t4 - t3, 5),
                  "setup_s": round(t1 - t0, 1),
                  "reference_s": round(time.time() - t4, 1)})
            rewriter.drop_snapshot()
            del weights, rewriter, served
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
