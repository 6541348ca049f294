#!/usr/bin/env python3
"""Read the numbers the convolution-attention rewrite cell's logit limits are
set from.

    python3 benchmark/calibrate_lfm2.py \
        --variants sound,cache_float8_e4m3fn,tails_not_carried \
        --seeds 6 [--first-seed N] [--out FILE]

For each variant - "sound" is the configuration as committed: the KV caches
in the served bfloat16, every conv layer's tail carried into the suffix and
into decoding; "cache_float8_e4m3fn" keeps the caches (`cache_dtype`) a
precision below; "tails_not_carried" (`carry_conv_tails` false) enters the
snapshot and the prefill's state with zero tails, what a wrong
`prefill_from` would compute - and each seed: the language model's weights
from the seed, the program's own `PromptRewriter` - the three programs the
cell's path runs, at the timed sizes, without the diffusion side - over one
prompt, and the served logits against the float32 reference as
`Reference.generate` compares them, each beside its limit.  Also the
host-clock time of a warm prefill that enters the snapshot and of the whole
rewrite.  One JSON line per reading; `benchmark/limits/` records the
readings a limit was set from.  Not part of a benchmark run
(`calibrate_kimi.py` with this cell's names).
"""

import argparse
import json
import os
import sys
import time

import run as bench_run  # benchmark/run.py, beside this file

sys.path.insert(0, bench_run.ROOT)
CONFIG = "lfm2-24b-a2b-sdxl-rewrite"
VARIANTS = {"sound": {}, "cache_float8_e4m3fn": {"cache_dtype": "float8_e4m3fn"},
            "tails_not_carried": {"carry_conv_tails": False}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--variants", default=",".join(VARIANTS))
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=4_500_000_001)
    ap.add_argument("--out", help="append each reading to this file too")
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

    from benchmark.families import lfm2_sdxl as fam
    from benchmark.harness.traffic import request_pool
    from benchmark.reference import lfm2_sdxl as ref
    from distrifuser_tpu.pipelines import PromptRewriter, SimpleTokenizer

    if not args.rehearse:
        if jax.devices()[0].platform != "tpu":
            print("calibrate_lfm2.py: no accelerator", file=sys.stderr)
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
        config = dict(base, **VARIANTS[variant])
        family = fam.Family(config)
        reference = ref.Reference(config, 0, 0)
        for k in range(args.seeds):
            seed = args.first_seed + 7919 * k
            t0 = time.time()
            weights = fam.init_lm_on_device(
                family.lm_config, fam.F.seed_key(seed, fam.LM_STREAM), dtype,
                mesh)
            jax.block_until_ready(weights)
            rewriter = PromptRewriter(family.lm_config, weights,
                                      family.rewrite, toks)
            prompt = request_pool(traffic, seed)[0]["prompt"]
            jax.block_until_ready(rewriter([prompt]))  # compiles, snapshots
            ids = rewriter.lm_ids(prompt)
            t1 = time.time()
            out = jax.block_until_ready(rewriter._prefill(
                weights, ids[rewriter._prefix_len:], rewriter.snapshot()))
            t2 = time.time()
            del out
            jax.block_until_ready(rewriter([prompt]))
            t3 = time.time()
            served = rewriter.served[-1]
            with jax.default_matmul_precision("highest"):
                checks, agree = reference.compare_logits(
                    weights, ref.prompt_ids(config, prompt), served)
            counters = dict(zip(rewriter.lm.counters,
                                np.asarray(served.counters).tolist()))
            emit({"variant": variant, "seed": seed,
                  **{name: value for name, value, _, _ in checks},
                  "failed": [name for name, _, _, ok in checks if not ok],
                  "median_by_quarter": [
                      float(np.median(q)) for q in np.array_split(
                          reference.position_errors, 4)],
                  "argmax_agree": agree, "counters": counters,
                  "entering_prefill_s": round(t2 - t1, 5),
                  "rewrite_s": round(t3 - t2, 5),
                  "setup_s": round(t1 - t0, 1),
                  "reference_s": round(time.time() - t3, 1)})
            rewriter.drop_snapshot()
            del weights, rewriter, served
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
