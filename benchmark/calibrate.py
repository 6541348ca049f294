#!/usr/bin/env python3
"""Read the numbers a cell's correctness limit is set from, in one process.

    python3 benchmark/calibrate.py --workload <cell> --variants none,int8,fp8 \
        --seeds 3 --seconds 8 [--first-seed N] [--out FILE]

For each variant and each seed: weights from the seed, the cell's server, a
short window at the cell's own load, and `image_rel_rmse` of the last
finished request's served image against the float32 reference, as run.py
reads it.  "none" is the cell as committed (the sound reading).  "int8" and
"fp8" are the controls: the SAME server and timed path with the program's
own lower-precision path switched on (`weight_quant`, and the `quant_compute`
it defaults to), the precision below the bf16 the configuration states.
Prints one JSON line per reading and a summary; `benchmark/limits/<cell>.json`
records the readings a limit was set from.  Not part of a benchmark run.
"""

import argparse
import gc
import json
import os
import sys
import time

import run as bench_run  # benchmark/run.py, beside this file

sys.path.insert(0, bench_run.ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variants", default="none,int8,fp8")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", help="append each reading to this file too")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    spec = bench_run.resolve_cell(args.workload, args.rehearse)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np

    from benchmark.harness.compile_log import CompileLog

    if not args.rehearse:
        if jax.devices()[0].platform != "tpu":
            print("calibrate.py: no accelerator", file=sys.stderr)
            return bench_run.EXIT_NO_CHIP
        bench_run.setup_compile_cache()
    compiles = CompileLog()

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")

    rows = []
    reference = None
    for variant in args.variants.split(","):
        quant = {} if variant == "none" else {"weight_quant": variant}
        v_spec = dict(spec, traffic=bench_run.merged(
            spec["traffic"], {"distri": quant, "serve": quant}))
        for k in range(args.seeds):
            seed = args.first_seed + 7919 * k
            run_args = argparse.Namespace(
                seed=seed, seconds=args.seconds, trace=0,
                rehearse=args.rehearse, workload=args.workload)
            t0 = time.time()
            row = {"variant": variant, "seed": seed}
            b = None
            try:
                b = bench_run.Bench(run_args, v_spec)
                b.peaks = None
                b.build()
                ok = [r for r in b.measure(compiles) if r["ok"]]
                last = ok[-1]
                assert b.images.last_index == last["index"]
                image = b.images.last
                row.update(
                    requests=len(ok), request=last["index"],
                    served_s=[round(r["done"] - r["due"], 4) for r in ok],
                    key=last["result"].exec_key,
                    build_window_s=round(time.time() - t0, 1))
                b.server.stop()
                b.server = None
                gc.collect()
                ref_mod, weights, req = b.reference_inputs(last["index"])
                if reference is None:
                    reference = ref_mod.Reference(b.config, b.height, b.width)
                t1 = time.time()
                with jax.default_device(b.devices[0]):
                    ref = reference.generate(weights, req)
                row.update(image_rel_rmse=bench_run.rel_rmse(image, ref),
                           max_abs=float(np.abs(image - ref).max()),
                           ref_std=float(ref.std()),
                           reference_s=round(time.time() - t1, 1))
                del weights, image, ref
            except Exception as exc:  # a control that crashes has failed
                row["error"] = f"{type(exc).__name__}: {exc}"[:400]
                if b is not None and getattr(b, "server", None) is not None:
                    b.server.stop()
            del b
            rows.append(row)
            emit(row)
            gc.collect()
    summary = {"workload": args.workload}
    for variant in args.variants.split(","):
        vals = [r["image_rel_rmse"] for r in rows
                if r["variant"] == variant and "image_rel_rmse" in r]
        if vals:
            summary[variant] = {"seeds": len(vals), "min": min(vals),
                                "median": float(np.median(vals)),
                                "max": max(vals)}
    emit(summary)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
