"""Per-layer metrics that read what the program itself records (PR 24):

* the stage clocks on every `ServeResult` (`stage_s`, always on), over the
  window's requests - `dispatch_ms`, `host_post_ms`,
  `execute_unattributed_ms`, `execute_excess_ms`;
* its `distri.*` spans, which `jax.profiler.TraceAnnotation` writes into the
  traced run's `.xplane.pb` on the same clock as the device ops -
  `host_gap_ms`;
* the device trace cut by request rather than by kernel -
  `programs_per_image`, `pre_denoise_ms`, `post_denoise_ms`.

A program without the clocks or the spans (the parent of PR 24) gives a
reader nothing to read: it returns None and the line leaves the metric out.
`trace_reduce.load_xplane` keeps the benchmark's own `bench.` spans only, so
this file has its own small loader of the program's; the interval arithmetic
is `trace_reduce`'s.
"""

import collections
import statistics

from . import trace_reduce as T

SPAN_PREFIX = "distri."
BATCH, WAIT = "distri.serve.batch", "distri.pipe.wait_device"
# step mode runs `begin` before it admits the request: inside queue_wait_s,
# not execute_s
OUTSIDE_EXECUTE = ("begin",)


def _say(msg):
    print(f"[span_readers] {msg}", flush=True)


# -- stage clocks -------------------------------------------------------------


def _clocked(ctx):
    """The window's results that carry stage clocks."""
    return [r for r in ctx["results"] if getattr(r, "stage_s", None)]


def _stage_ms(result, stages):
    return 1e3 * sum(result.stage_s.get(k, 0.0) for k in stages)


def stage_ms(ctx, stages):
    """Median over the window of the summed stage clocks named in `stages`
    (a server kind keeps only its own keys: whole-batch `dispatch`,
    step mode `begin`, staged `encode` all name the host's part before the
    denoise program can start)."""
    rs = [r for r in _clocked(ctx) if any(k in r.stage_s for k in stages)]
    if not rs:
        return None
    return statistics.median(_stage_ms(r, stages) for r in rs)


def _unattributed_ms(result):
    inside = [k for k in result.stage_s if k not in OUTSIDE_EXECUTE]
    return 1e3 * result.execute_s - _stage_ms(result, inside)


def execute_unattributed_ms(ctx):
    """execute_s less the stage clocks inside it: on a whole-batch server
    the per-dispatch watchdog thread's start and the scheduler's wake-up."""
    rs = _clocked(ctx)
    return statistics.median(_unattributed_ms(r) for r in rs) if rs else None


def execute_excess_ms(ctx):
    """The window's longest execute_s over its median, and which stage of
    that request held the excess."""
    rs = _clocked(ctx)
    if not rs:
        return None
    median = statistics.median(r.execute_s for r in rs)
    worst = max(rs, key=lambda r: r.execute_s)
    keys = list(worst.stage_s)
    medians = {k: statistics.median(_stage_ms(r, [k]) for r in rs)
               for k in keys}
    _say(f"execute_excess: request {worst.request_id} execute_s "
         f"{worst.execute_s:.4f} against the window's median {median:.4f} "
         f"over {len(rs)} requests; stage ms of that request / window "
         f"medians: " + ", ".join(
             f"{k} {_stage_ms(worst, [k]):.3f} / {medians[k]:.3f}"
             for k in keys)
         + f", unattributed {_unattributed_ms(worst):.3f} / "
         f"{statistics.median(_unattributed_ms(r) for r in rs):.3f}")
    return 1e3 * (worst.execute_s - median)


# -- the program's spans in the device trace ---------------------------------


def load_spans(path: str):
    """The `distri.` host events of an .xplane.pb:
    [{"name", "start", "end" (ns), "thread" (line index), "stats"}], by
    start."""
    import jax

    spans = []
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for thread, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append({
                        "name": e.name, "start": e.start_ns,
                        "end": e.start_ns + e.duration_ns, "thread": thread,
                        "stats": dict(e.stats)})
    return sorted(spans, key=lambda s: (s["start"], -s["end"]))


def spans_of(ctx):
    """The traced run's program spans, loaded once; None without a trace,
    [] where the program enters none."""
    if ctx.get("trace") is None:
        return None
    if "_program_spans" not in ctx:
        ctx["_program_spans"] = load_spans(
            T.find_xplane(ctx["bench"].trace_dir))
    return ctx["_program_spans"]


def innermost_segments(spans):
    """[(start, end, name)], disjoint and sorted: each stretch of time under
    the span that started last among those holding it - across threads, so a
    worker's `distri.pipe.*` wins over the scheduler's `distri.serve.handoff`
    that waits for it."""
    cuts = sorted({t for s in spans for t in (s["start"], s["end"])})
    out, live, i = [], [], 0
    for lo, hi in zip(cuts, cuts[1:]):
        while i < len(spans) and spans[i]["start"] <= lo:
            live.append(spans[i])
            i += 1
        live = [s for s in live if s["end"] > lo]
        if live:
            inner = max(live, key=lambda s: (s["start"], -s["end"]))
            if out and out[-1][2] == inner["name"] and out[-1][1] == lo:
                out[-1] = (out[-1][0], hi, inner["name"])
            else:
                out.append((lo, hi, inner["name"]))
    return out


def idle_by_segment(idle, segments):
    """ns of the sorted disjoint `idle` intervals under each segment's name,
    in one pass over both."""
    by_name, i, j = collections.defaultdict(float), 0, 0
    while i < len(idle) and j < len(segments):
        lo = max(idle[i][0], segments[j][0])
        hi = min(idle[i][1], segments[j][1])
        if hi > lo:
            by_name[segments[j][2]] += hi - lo
        if idle[i][1] < segments[j][1]:
            i += 1
        else:
            j += 1
    return by_name


def _requests(ctx, spans):
    """[(start, end)] of each traced dispatch, by `distri.serve.batch`; the
    whole traced window where the program has no spans and one request was
    traced."""
    batches = [(s["start"], s["end"]) for s in spans or ()
               if s["name"] == BATCH]
    if batches:
        return batches
    if len(ctx["bench"].traced) == 1:
        return [T.window(ctx["trace"])]
    return []


def _images(ctx):
    return sum(1 for r in ctx["bench"].traced if r["ok"])


def host_gap_ms(ctx):
    """Device idle ms per traced image, worst chip, inside the traced
    dispatches' `distri.serve.batch` spans but outside every
    `distri.pipe.wait_device`: the device waiting for the host, as against
    bubbles inside a program the host is merely waiting for."""
    spans = spans_of(ctx)
    if not spans or not _images(ctx):
        return None
    batches = T.union((s["start"], s["end"]) for s in spans
                      if s["name"] == BATCH)
    if not batches:
        return None
    segments = innermost_segments(spans)
    worst = None
    for ordinal, dev in ctx["trace"]["devices"].items():
        busy = T.op_intervals(dev)
        idle = [g for lo, hi in batches for g in T.gaps(busy, lo, hi)]
        by_name = idle_by_segment(idle, segments)
        total = sum(by_name.values())
        gap = total - by_name.get(WAIT, 0.0)
        if worst is None or gap > worst[0]:
            worst = (gap, ordinal, by_name, total)
    gap, ordinal, by_name, total = worst
    named = total - by_name.get(BATCH, 0.0)
    _say(f"host_gap: device {ordinal}: {total / 1e6:.3f} ms idle inside "
         f"{len(batches)} {BATCH} spans, {100 * named / max(total, 1):.1f}% "
         "of it under a span inside the batch's; by innermost span (ms): "
         + ", ".join(f"{n} {v / 1e6:.3f}" for n, v in sorted(
             by_name.items(), key=lambda kv: -kv[1])))
    return gap / 1e6 / _images(ctx)


def programs_per_image(ctx):
    """XLA module executions (one per dispatched program: the three model
    programs and the eager glue around them) per traced image, worst chip."""
    if ctx.get("trace") is None or not _images(ctx):
        return None
    counts = [len(dev["modules"]) for dev in ctx["trace"]["devices"].values()]
    return max(counts) / _images(ctx) if counts and max(counts) else None


def _around_denoise(ctx):
    """Per device (pre_ns, post_ns): device busy time inside the traced
    dispatches before the first and after the last execution of a denoise
    module there.  The encoders and the VAE decode are all `jit(<lambda>)`,
    so they are told apart by order, not by name."""
    if ctx.get("trace") is None or not _images(ctx):
        return None
    requests = _requests(ctx, spans_of(ctx))
    if not requests:
        return None
    names = set(ctx["bench"].family_module.DENOISE_MODULES)
    out = []
    for dev in ctx["trace"]["devices"].values():
        busy = T.op_intervals(dev)
        pre = post = 0.0
        for lo, hi in requests:
            runs = T.clip(T.module_events(dev, names), lo, hi)
            if not runs:
                return None
            pre += T.total(T.clip(busy, lo, min(s for s, _ in runs)))
            post += T.total(T.clip(busy, max(e for _, e in runs), hi))
        out.append((pre, post))
    return out or None


def pre_denoise_ms(ctx):
    """Encoders, latents and glue: device busy ms per image before the
    request's first denoise program, worst chip."""
    d = _around_denoise(ctx)
    return max(pre for pre, _ in d) / 1e6 / _images(ctx) if d else None


def post_denoise_ms(ctx):
    """VAE decode and glue: device busy ms per image after the request's
    last denoise program, worst chip."""
    d = _around_denoise(ctx)
    return max(post for _, post in d) / 1e6 / _images(ctx) if d else None
