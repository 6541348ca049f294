"""The per-layer metric readers that `layer_metrics/<metric>.json` name.

Each takes the run's context - request records, the served results, the
reduced device trace, the numbers already computed - and returns one float,
or None when there is nothing to read (no trace, no matching kernel, one
chip and no collective): the harness then leaves the metric out.
"""

import re
import statistics

from . import measure as M
from . import trace_reduce as T


def loadgen_late_ms(ctx):
    return M.lateness_ms(ctx["records"])[0] if ctx["records"] else None


def queue_wait_ms(ctx):
    rs = ctx["results"]
    return statistics.median(r.queue_wait_s for r in rs) * 1e3 if rs else None


def serve_overhead_ms(ctx):
    """Server time that is neither waiting in the queue nor executing."""
    rs = ctx["results"]
    if not rs:
        return None
    return statistics.median(
        r.e2e_s - r.execute_s - r.queue_wait_s for r in rs) * 1e3


def image_tail_s(ctx):
    """The cell's stated tail of due -> image on the host, over the window
    (the profiler runs after it has closed)."""
    lat = M.latencies(ctx["records"])
    return M.tail(lat, ctx["bench"].traffic.get("tail", "max")) if lat else None


def images_per_s(ctx):
    """Completed images over first-due -> last-done, whole cell."""
    return M.completed_rate(ctx["records"]) or None


def peak_hbm_gb(ctx):
    return ctx["memory_peak_bytes"] / 1e9 or None


# -- device trace -------------------------------------------------------------


def _denoise(ctx):
    """Per device: (denoise executions fully traced, their device seconds)."""
    trace, bench = ctx["trace"], ctx["bench"]
    if trace is None:
        return None
    names = set(bench.family_module.DENOISE_MODULES)
    per_dev = []
    for dev in trace["devices"].values():
        runs = T.module_events(dev, names)
        if runs:
            per_dev.append((len(runs), T.total(runs) / 1e9, runs))
    return per_dev or None


def step_ms(ctx):
    """Denoise program's device time per image over its steps, mean over
    the chips."""
    d = _denoise(ctx)
    if not d:
        return None
    return statistics.mean(s / n for n, s, _ in d) / ctx["bench"].steps * 1e3


def nondenoise_ms(ctx):
    """Device busy time per image outside the denoise program: encoders,
    VAE decode, glue."""
    d = _denoise(ctx)
    if not d:
        return None
    lo, hi = T.window(ctx["trace"])
    out = []
    for dev, (n, _, runs) in zip(ctx["trace"]["devices"].values(), d):
        busy = T.clip(T.op_intervals(dev), lo, hi)
        inside = T.total(T.intersection(busy, T.union(runs)))
        out.append((T.total(busy) - inside) / 1e6 / n)
    return statistics.mean(out)


def step_flop_util(ctx):
    """The family's analytic FLOPs per guided step over step_ms at the bf16
    peak of the chips used: an end-to-end utilisation of the denoise
    program, not a kernel's roofline share."""
    ms, bench = step_ms(ctx), ctx["bench"]
    if ms is None or bench.peaks is None:
        return None
    cost = bench.family.step_cost(bench.height, bench.width)
    return 100.0 * cost["flops"] / (ms / 1e3) / (
        bench.peaks["bf16_flops"] * bench.chips)


def _kernel_seconds_per_step(ctx, patterns):
    d = _denoise(ctx)
    if not d:
        return None
    rx = re.compile("|".join(patterns), re.I)
    per_dev = []
    for dev, (n, _, runs) in zip(ctx["trace"]["devices"].values(), d):
        hits = T.intersection(T.op_intervals(dev, rx.search), T.union(runs))
        if hits:
            per_dev.append(T.total(hits) / 1e9 / n / ctx["bench"].steps)
    return statistics.mean(per_dev) if per_dev else None


def kernel_ms_per_step(ctx, patterns):
    """Summed device time of the ops whose name matches, per denoise step."""
    s = _kernel_seconds_per_step(ctx, patterns)
    return None if s is None else s * 1e3


def attention_roofline(ctx, patterns):
    """The least time the chip could take for the step's self-attention
    calls - the larger of FLOPs over peak and bytes over bandwidth, from the
    cell's shapes - over the time its kernels took.  On several chips the
    step's attention work is shared by them."""
    s, bench = _kernel_seconds_per_step(ctx, patterns), ctx["bench"]
    if s is None or bench.peaks is None:
        return None
    from benchmark.families._common import attention_cost

    least = 0.0
    for count, b, lq, lk, heads, d in bench.family.step_cost(
            bench.height, bench.width)["self_attention"]:
        flops, nbytes = attention_cost(b, lq, lk, heads, d)
        least += count * max(flops / bench.peaks["bf16_flops"],
                             nbytes / bench.peaks["hbm_bytes_per_s"])
    return 100.0 * least / bench.chips / s


def collective_exposed_share(ctx):
    """Time a collective runs while no other op does on that device, over
    the traced window; worst device.  None on one chip."""
    trace = ctx["trace"]
    if trace is None or ctx["bench"].chips < 2:
        return None
    lo, hi = T.window(trace)
    worst = None
    for dev in trace["devices"].values():
        coll = T.op_intervals(dev, T.COLLECTIVE.match)
        if not coll:
            continue
        compute = T.op_intervals(dev, lambda n: not T.COLLECTIVE.match(n))
        hidden = T.total(T.intersection(coll, compute))
        share = 100.0 * (T.total(coll) - hidden) / (hi - lo)
        worst = share if worst is None else max(worst, share)
    return worst


def device_idle_share(ctx):
    if ctx["trace"] is None:
        return None
    return 100.0 * T.busy_summary(ctx["trace"])["idle_share_worst"]
