#!/usr/bin/env python3
"""The benchmark's command: one cell, once, in a new process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

build -> warm up -> measure -> check -> print.  The cell is looked up by name
in BENCHMARK.json; its configuration (`benchmark/configs/<config>.json`), its
traffic mix (`benchmark/traffic/<traffic>.json`), its correctness limits
(`benchmark/limits/<cell>.json`), its family builder and plain reference
(`benchmark/families/<family>.py`, `benchmark/reference/<family>.py`) and the
per-layer metric readers (`benchmark/layer_metrics/<metric>.json`) are all
found by those names: a later cell, configuration or metric is new files plus
appended manifest entries, and no edit here.

The LAST stdout line is one JSON object with exactly the keys `correct`,
`attempted`, `failed`, `metrics`, `device` (and `breakdown` in a traced run).
Without a TPU whose `device_kind` is in `harness/peaks.py`, or with fewer
chips than the cell asks for, it exits non-zero and prints no result;
`--rehearse` is the explicit CPU debug mode (tiny sizes, `"platform": "cpu"`,
never a device number).
"""

import time

T0 = time.time()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
EXIT_NO_CHIP = 3
EXIT_BROKEN = 4


def say(msg: str) -> None:
    print(f"[{time.time() - T0:7.1f}s] {msg}", flush=True)


def load_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def merged(base: dict, over: dict) -> dict:
    """`base` with `over` laid on top, nested dicts merged key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def resolve_cell(name: str, rehearse: bool) -> dict:
    """Everything the cell names, as data."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json "
                         f"(have: {sorted(cells)})")
    cell = cells[name]
    config = load_json("configs", cell["config"] + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    limits = load_json("limits", name + ".json")
    if rehearse:
        config = merged(config, config.get("rehearse", {}))
        traffic = merged(traffic, traffic.get("rehearse", {}))
        limits = merged(limits, limits.get("rehearse", {}))

    def reports(metric):
        return name in metric.get("workloads", [name])

    return {
        "cell": cell, "config": config, "traffic": traffic, "limits": limits,
        "end_to_end": [m for m in manifest["end_to_end"] if reports(m)],
        "per_layer": [m for m in manifest["per_layer"] if reports(m)],
    }


def setup_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout (the path is
    part of the key), unless the machine's owner set one."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env_dir:
        env_dir = os.path.join(BENCH_DIR, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", env_dir)
    # every program, the small eager ones too: a run after the first compiles
    # nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return env_dir


def rel_rmse(image, ref) -> float:
    """RMS difference over the reference image's own standard deviation."""
    import numpy as np

    return float(np.sqrt(np.mean(np.square(image - ref)))
                 / max(float(ref.std()), 1e-12))


def on_first_device(tree, device):
    """`device`'s shard of every replicated leaf, in place (`Shard.data`
    aliases the buffer): a one-chip view for the reference, not a copy."""
    import jax

    return jax.tree.map(
        lambda leaf: next(s.data for s in leaf.addressable_shards
                          if s.device == device), tree)


class GcPauses:
    """Seconds of every garbage collection inside the block: the interpreter
    stops every thread, the server's too, for as long as one lasts."""

    def __enter__(self):
        self.seconds, self._t0 = [], None
        gc.callbacks.append(self._note)
        return self

    def _note(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.seconds.append((time.perf_counter() - self._t0,
                                 info["generation"]))

    def __exit__(self, *exc):
        gc.callbacks.remove(self._note)


class Bench:
    """One run of one cell."""

    def __init__(self, args, spec):
        import jax

        self.args, self.spec = args, spec
        self.cell, self.config = spec["cell"], spec["config"]
        self.traffic, self.limits = spec["traffic"], spec["limits"]
        self.chips = int(self.cell["chips"])
        self.devices = jax.devices()[:self.chips]
        req = self.traffic["request"]
        self.height, self.width = req["height"], req["width"]
        sampler = self.config["sampler"]
        self.steps, self.guidance = sampler["steps"], sampler["guidance_scale"]
        self.scheduler = sampler["scheduler"]
        fam = importlib.import_module(
            f"benchmark.families.{self.config['family']}")
        self.family_module = fam
        self.family = fam.Family(self.config)
        self.checks = []  # (name, value, limit, ok)

    # -- set-up -------------------------------------------------------------

    def build(self):
        import jax
        import jax.numpy as jnp
        from distrifuser_tpu import DistriConfig
        from distrifuser_tpu.serve import (
            InferenceServer,
            ServeConfig,
            pipeline_executor_factory,
        )
        from distrifuser_tpu.utils.config import StepBatchConfig

        from benchmark.families._common import tree_nbytes
        from benchmark.harness.images import ImageChecks
        from benchmark.harness.traffic import request_pool

        distri_kw = dict(self.traffic.get("distri", {}))
        base = DistriConfig(devices=self.devices, height=self.height,
                            width=self.width, **distri_kw)
        want = jnp.dtype(self.config["dtype"])
        if not self.args.rehearse and jnp.dtype(base.dtype) != want:
            raise RuntimeError(f"the program would serve {base.dtype}, the "
                               f"configuration states {want}")
        say(f"mesh {dict(base.mesh.shape)} ({base.mesh_plan}) dtype "
            f"{jnp.dtype(base.dtype).name}; weights from seed {self.args.seed}")
        self.weights = self.family.init_weights(self.args.seed, base.dtype,
                                                base.mesh)
        jax.block_until_ready(self.weights)
        say(f"weights on device: {tree_nbytes(self.weights) / 1e9:.2f} GB")

        serve_kw = dict(self.traffic.get("serve", {}))
        step_mode = serve_kw.pop("step_batching", None)
        rows = int(serve_kw.pop("program_batch_rows", 1))

        def build_pipeline(key):
            dcfg = DistriConfig(
                devices=self.devices, height=key.height, width=key.width,
                do_classifier_free_guidance=key.cfg, batch_size=rows,
                **distri_kw)
            return self.family.build_pipeline(dcfg, self.weights,
                                              key.scheduler)

        if step_mode:
            serve_kw["step_batching"] = StepBatchConfig(enabled=True,
                                                        **step_mode)
        size = (self.height, self.width)
        cfg = ServeConfig(buckets=(size,), warmup_buckets=(size + (self.steps,),),
                          default_steps=self.steps, **serve_kw)
        # start() builds the warm bucket: the factory compiles every program
        # of the cell's one shape with a throwaway request, off the request
        # path
        self.server = InferenceServer(
            pipeline_executor_factory(build_pipeline), cfg,
            model_id=self.cell["config"], scheduler=self.scheduler,
            mesh_plan=base.mesh_plan)
        self.server.start()
        self.pool = request_pool(self.traffic, self.args.seed)
        # the VAE upsamples once per level after the first (x8 as published)
        up = 2 ** (len(self.config["vae"]["block_out_channels"]) - 1)
        self.images = ImageChecks(
            (self.height // 8 * up, self.width // 8 * up, 3))

    def submit(self, index: int):
        req = self.pool[index % len(self.pool)]
        return self.server.submit(
            req["prompt"], height=self.height, width=self.width,
            negative_prompt=req["negative_prompt"],
            guidance_scale=self.guidance, seed=req["seed"])

    # -- the window -----------------------------------------------------------

    def measure(self, compiles):
        """The window, with the profiler off; then, in a traced run, the
        same mix once more under the profiler for `trace.seconds` (0: one
        request a caller).  Stopping the profiler takes as long as a window
        where a request is half a million device ops, so it is kept out of
        the window: every number of the requests' own clocks is read from a
        window like a `--trace 0` run's."""
        import jax

        from benchmark.harness.loadgen import LoadGen

        def keep_the_checks(record, n_done):
            if record["ok"]:  # look at the image and let go of it
                result = record["result"]
                self.images.put(record["index"], result.output)
                result.output = None

        gen = LoadGen(self.submit, self.traffic["arrivals"], self.args.seconds,
                      self.args.seed, on_done=keep_the_checks)
        mark = compiles.mark()
        with GcPauses() as pauses:
            records = gen.run()
        self.window_compiles = compiles.since(mark)
        self.gc_pauses = pauses.seconds
        self.images.close()

        self.trace_dir, self.traced = None, []
        if self.args.trace:
            def let_go(record, n_done):
                if record["ok"]:
                    record["result"].output = None

            self.trace_dir = os.path.join(BENCH_DIR, "out", "trace",
                                          self.cell["name"])
            sample = LoadGen(
                self.submit, self.traffic["arrivals"],
                float(self.traffic.get("trace", {}).get("seconds", 0.0)),
                self.args.seed, on_done=let_go, first_index=len(records))
            mark = compiles.mark()
            jax.profiler.start_trace(self.trace_dir)
            try:
                self.traced = sample.run()
            finally:
                jax.profiler.stop_trace()
            self.window_compiles += compiles.since(mark)
            say(f"traced after the window: {len(self.traced)} requests, "
                f"{[round(r['done'] - r['due'], 4) for r in self.traced]} s "
                "under the profiler")
        return records

    # -- correctness ----------------------------------------------------------

    def check(self, name, value, limit, ok):
        self.checks.append((name, value, limit, bool(ok)))
        say(f"check {name}: value={value} limit={limit} "
            f"{'ok' if ok else 'FAILED'}")

    def check_window(self, records, latency_median):
        from benchmark.harness.compile_log import split_model_compiles

        ok = [r for r in records if r["ok"]]
        for r in records:
            if not r["ok"]:
                say(f"request {r['index']} failed: {r.get('error')}")
        self.check("failed_requests", len(records) - len(ok), 0,
                   len(ok) == len(records) and ok)
        big, glue = split_model_compiles(self.window_compiles)
        say(f"compiles in the window: {len(big)} model-program {big}, "
            f"{glue} small eager ops")
        self.check("model_compiles_in_window", len(big), 0, not big)
        results = [r["result"] for r in ok + self.traced if r["ok"]]
        self.check("retries_and_degradations",
                   sum(r.retries + len(r.degradations) for r in results), 0,
                   all(r.retries == 0 and not r.degradations for r in results))
        health = self.server.health()
        self.check("server_health", health["status"], "ok",
                   health["status"] == "ok" and not health["open_circuits"])
        self.check("images_finite_sized_not_constant", len(self.images.bad),
                   0, not self.images.bad)
        for index, what in self.images.bad:
            say(f"image of request {index}: {what}")
        # the pool repeats: the same (prompt, seed) gives the same bytes,
        # different ones differ
        differ = self.images.repeats_that_differ(len(self.pool))
        self.check("repeated_request_differs", len(differ), 0, not differ)
        same = self.images.distinct_that_agree(len(self.pool))
        self.check("distinct_requests_identical", len(same), 0, not same)
        bad_traced = [r["index"] for r in self.traced if not r["ok"]]
        self.check("failed_requests_under_the_profiler", len(bad_traced), 0,
                   not bad_traced)
        # a warm image faster than the model's FLOPs at the published peak is
        # a broken clock, not a fast chip
        if not self.args.rehearse:
            floor = self.roofline_floor_s()
            self.check("image_s_over_roofline_floor", latency_median,
                       f">={floor:.4f}", latency_median >= floor)

    def roofline_floor_s(self) -> float:
        cost = self.family.step_cost(self.height, self.width)
        return cost["flops"] * self.steps / (
            self.peaks["bf16_flops"] * self.chips)

    def reference_inputs(self, index):
        """(the reference module, one-device weights, request `index` as the
        reference takes it)."""
        ref_mod = importlib.import_module(
            f"benchmark.reference.{self.family_module.REFERENCE}")
        weights = self.weights
        if self.chips > 1:
            weights = on_first_device(weights, self.devices[0])
        request = dict(self.pool[index % len(self.pool)], steps=self.steps,
                       guidance_scale=self.guidance)
        return ref_mod, weights, request

    def check_reference(self):
        """The plain float32 reference over the last request the window
        finished (every request of a mix is as long) against the served
        image: RMS difference over the reference image's own standard
        deviation."""
        import jax

        index = self.images.last_index
        if index is None:
            return
        lim = self.limits["image_rel_rmse"]["limit"]
        t0 = time.time()
        ref_mod, weights, request = self.reference_inputs(index)
        reference = ref_mod.Reference(self.config, self.height, self.width)
        with jax.default_device(self.devices[0]):
            ref = reference.generate(weights, request)
        err = rel_rmse(self.images.last, ref)
        self.check(f"image_rel_rmse[request {index}]", err, lim, err <= lim)
        say(f"reference: request {index} in {time.time() - t0:.1f}s "
            "(not in setup_s, not in the window)")


def run(args, spec) -> int:
    import jax

    from benchmark.harness import measure as M
    from benchmark.harness.compile_log import CompileLog
    from benchmark.harness.peaks import peaks_for

    devices = jax.devices()
    dev0 = devices[0]
    chips = int(spec["cell"]["chips"])
    if args.rehearse:
        if dev0.platform != "cpu":
            print(f"run.py: --rehearse is the CPU debug mode; this process "
                  f"sees {dev0.platform!r}", file=sys.stderr)
            return 2
    elif dev0.platform != "tpu":
        print(f"run.py: no accelerator: jax.devices()[0].platform is "
              f"{dev0.platform!r}; the benchmark measures on a TPU only "
              "(--rehearse debugs it on the CPU)", file=sys.stderr)
        return EXIT_NO_CHIP
    if len(devices) < chips:
        print(f"run.py: workload {args.workload} needs {chips} chips, this "
              f"machine has {len(devices)}", file=sys.stderr)
        return EXIT_NO_CHIP

    # the CPU rehearsal keeps no cache: nothing of it is ever measured
    cache_dir = None if args.rehearse else setup_compile_cache()
    compiles = CompileLog()
    bench = Bench(args, spec)
    bench.peaks = None if args.rehearse else peaks_for(dev0.device_kind)
    say(f"device {dev0.platform} {dev0.device_kind} x{len(devices)} "
        f"(cell uses {chips}); compile cache {cache_dir}")
    bench.build()
    setup_s = time.time() - T0
    cold = compiles.since(0)
    say(f"ready in {setup_s:.1f}s: {len(cold)} compiles "
        f"({sum(s for _, s in cold):.1f}s), persistent cache "
        f"{compiles.cache_hits} hits / {compiles.cache_misses} misses; "
        f"slowest {sorted(cold, key=lambda c: -c[1])[:4]}")

    records = bench.measure(compiles)
    ok = [r for r in records if r["ok"]]
    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                      for d in bench.devices)
    lat = M.latencies(records)
    tail_kind = bench.traffic.get("tail", "max")
    numbers = {"setup_s": setup_s}
    if lat:
        numbers.update(image_s=statistics.median(lat),
                       image_tail_s=M.tail(lat, tail_kind),
                       images_per_s=M.completed_rate(records))
    late = M.lateness_ms(records)
    say(f"window: {len(records)} requests, {len(ok)} ok; image_s median "
        f"{numbers.get('image_s')} tail({tail_kind}) "
        f"{numbers.get('image_tail_s')} (sample supports "
        f"{M.auto_tail(len(lat))}); generator late by median {late[0]:.3f} ms "
        f"max {late[1]:.3f} ms")
    longest = max(bench.gc_pauses, default=(0.0, None))
    say(f"garbage collections in the window: {len(bench.gc_pauses)}, "
        f"{sum(p for p, _ in bench.gc_pauses):.4f}s in all, longest "
        f"{longest[0]:.4f}s (generation {longest[1]})")
    for r in ok:
        res = r["result"]
        say(f"  request {r['index']}: due->done {r['done'] - r['due']:.4f}s "
            f"queue {res.queue_wait_s:.4f}s execute {res.execute_s:.4f}s "
            f"e2e {res.e2e_s:.4f}s key {res.exec_key}")

    bench.check_window(records, numbers.get("image_s", 0.0))
    say(f"memory_peak_bytes (fullest chip, before the reference runs): "
        f"{memory_peak}")

    trace = None
    if args.trace and bench.trace_dir:
        from benchmark.harness import trace_reduce as T

        trace = T.load_xplane(T.find_xplane(bench.trace_dir))
        with open(os.path.join(BENCH_DIR, "out",
                               f"trace_{args.workload}.json"), "w") as f:
            json.dump(T.describe(trace), f, indent=1, default=dict)

    # the program's state goes before the reference runs: memory_peak_bytes
    # above stays the program's, and the float32 activations fit
    bench.server.stop()
    bench.server = None
    gc.collect()
    bench.check_reference()

    ctx = {"records": records, "results": [r["result"] for r in ok],
           "trace": trace, "bench": bench, "numbers": numbers,
           "memory_peak_bytes": memory_peak}
    listed = spec["per_layer"] if args.trace else spec["end_to_end"]
    metrics = {}
    for m in listed:
        value = numbers.get(m["name"])
        if args.trace:
            value = read_layer_metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    for name, val in metrics.items():
        if (name.endswith("_roofline") or "util" in name or "mfu" in name) \
                and val["unit"] == "%":
            bench.check(f"{name}_at_most_100", val["value"], 100.0,
                        val["value"] <= 100.0)

    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    line = {"correct": all(c[3] for c in bench.checks),
            "attempted": len(records), "failed": len(records) - len(ok),
            "metrics": metrics, "device": device}
    if trace is not None:
        from benchmark.harness import trace_reduce as T

        busy = T.busy_summary(trace)
        device.update(busy_s=busy["busy_s"], window_s=busy["window_s"])
        line["breakdown"] = T.breakdown(trace)
    failed = [c[0] for c in bench.checks if not c[3]]
    say(f"checks: {len(bench.checks)} made, failed: {failed or 'none'}")
    print(json.dumps(line), flush=True)
    return 0


def read_layer_metric(name: str, ctx: dict):
    """`layer_metrics/<name>.json` names a reader `module:function` under
    benchmark/ and its parameters; a reader with nothing to read returns None
    and the metric is left out of the line."""
    spec = load_json("layer_metrics", name + ".json")
    module, func = spec["reader"].split(":")
    reader = getattr(importlib.import_module(f"benchmark.{module}"), func)
    value = reader(ctx, **spec.get("params", {}))
    say(f"layer metric {name}: {value}")
    return value


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU debug mode: tiny sizes, never a device number")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    spec = resolve_cell(args.workload, args.rehearse)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        n = int(spec["cell"]["chips"])
        if n > 1 and "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={n}")
    os.makedirs(os.path.join(BENCH_DIR, "out"), exist_ok=True)
    try:
        import distrifuser_tpu  # noqa: F401  the system under test
    except ImportError as exc:
        print(f"run.py: the system under test is not here: {exc}",
              file=sys.stderr)
        return EXIT_BROKEN
    try:
        return run(args, spec)
    except Exception:
        traceback.print_exc()
        print("run.py FAILED before a result (traceback above)",
              file=sys.stderr, flush=True)
        return 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # the serve plane's daemon threads must not hold the exit
