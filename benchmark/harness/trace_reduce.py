"""From a profiler trace (.xplane.pb) to device numbers.

`load_xplane` normalises what `jax.profiler.ProfileData` reads into plain
tuples, so every reduction below is arithmetic on

    {"devices": {ordinal: {"ops": [(name, start_ns, dur_ns)],
                           "modules": [(name, start_ns, dur_ns)]}},   ops: leaves only
     "host": [(name, start_ns, dur_ns)]}

and can be checked on a hand-written trace.  On a TPU the device planes are
`/device:TPU:<n>` with one line of XLA ops and one of XLA modules (a module
event spans one execution of one jitted program).  The CPU backend has no
device plane: its XLA thunks are host events carrying `hlo_op`,
`hlo_module`, `device_ordinal` and `run_id` stats, which the loader files
under the same structure so the CPU rehearsal exercises every reader.
The interval arithmetic (`union`, `intersection`) is copied from
scripts/analyze_trace.py.
"""

import collections
import glob
import os
import re

COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|all-to-all|collective-permute|reduce-scatter|"
    r"collective-broadcast|ragged-all-to-all|"
    r"ppermute|all_gather|all_to_all|psum|pmax|pmin)", re.I)
# XLA ops that only wait or mark time on the device: not work
_NOT_WORK = re.compile(r"^(end: |ThreadpoolListener|\$)")
HOST_SPAN_PREFIX = "bench."


# -- interval arithmetic ------------------------------------------------------


def union(intervals):
    """Merge (start, end) pairs into a sorted list of disjoint intervals."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def intersection(a, b):
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy, lo, hi):
    """The idle intervals of [lo, hi) given sorted disjoint busy ones."""
    out, at = [], lo
    for s, e in clip(busy, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


# -- loading ------------------------------------------------------------------


def short_name(name: str) -> str:
    """The TPU profiler names a device op by its whole HLO line,
    `%fusion.7 = bf16[64,16,9,1280]{...} fusion(...operands...)`.  Keep the
    instruction's own name and result shape: `fusion.7 bf16[64,16,9,1280]`.
    Matching a kernel by name must not match the ops that merely consume it."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    shape = "(tuple)" if rest.startswith("(") else (
        re.match(r"[\w\[\],]+", rest) or [""])[0]
    return f"{head.lstrip('%')} {shape}".strip()


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_xplane(path: str) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices = collections.defaultdict(lambda: {"ops": [], "modules": []})
    host, cpu_runs = [], {}
    for plane in data.planes:
        m = re.match(r"^/device:TPU:(\d+)", plane.name)
        if m:
            dev = devices[int(m.group(1))]
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    dev[key].extend((short_name(e.name), e.start_ns,
                                     e.duration_ns) for e in line.events)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        host.append((e.name, e.start_ns, e.duration_ns))
                    elif line.name.startswith("tf_XLA") and e.duration_ns > 0 \
                            and not _NOT_WORK.match(e.name):
                        stats = dict(e.stats)
                        if "hlo_op" not in stats:
                            continue
                        ordinal = int(stats.get("device_ordinal", 0))
                        devices[ordinal]["ops"].append(
                            (e.name, e.start_ns, e.duration_ns))
                        run = (ordinal, stats.get("hlo_module", "?"),
                               stats.get("run_id", 0))
                        lo, hi = cpu_runs.get(run, (e.start_ns, 0))
                        cpu_runs[run] = (min(lo, e.start_ns),
                                         max(hi, e.start_ns + e.duration_ns))
    for (ordinal, module, _), (lo, hi) in cpu_runs.items():
        devices[ordinal]["modules"].append((module, lo, hi - lo))
    for dev in devices.values():
        dev["ops"] = leaf_ops(dev["ops"])
        dev["modules"].sort(key=lambda e: e[1])
    return {"devices": dict(devices), "host": sorted(host, key=lambda e: e[1])}


def describe(trace: dict, top: int = 25) -> dict:
    """What is in a trace, for looking at one by hand."""
    out = {"host_spans": collections.Counter(n for n, _, _ in trace["host"])}
    for ordinal, dev in trace["devices"].items():
        by_name = collections.defaultdict(float)
        for name, _, dur in dev["ops"]:
            by_name[name] += dur
        out[f"device{ordinal}"] = {
            "n_ops": len(dev["ops"]),
            "modules": collections.Counter(n for n, _, _ in dev["modules"]),
            "top_ops_ms": [(n, round(d / 1e6, 3)) for n, d in sorted(
                by_name.items(), key=lambda kv: -kv[1])[:top]],
        }
    return out


# -- reductions ---------------------------------------------------------------


def module_base(name: str) -> str:
    """`jit_loop(1234)` / `jit_loop` -> `loop`."""
    name = re.sub(r"\(\d+\)$", "", name.strip())
    return re.sub(r"^(jit|pjit|pmap)_", "", name)


def window(trace: dict):
    """[lo, hi) ns: from the first device op to the end of the last."""
    starts = [d["ops"][0][1] for d in trace["devices"].values() if d["ops"]]
    ends = [max(s + dur for _, s, dur in d["ops"])
            for d in trace["devices"].values() if d["ops"]]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def op_intervals(dev, predicate=None):
    return union((s, s + d) for n, s, d in dev["ops"]
                 if predicate is None or predicate(n))


def leaf_ops(ops):
    """Drop the events that contain another event: a `while` or a `call`
    spans its whole body, gaps and all, and is not itself work."""
    out, stack = [], []  # stack of [event, has_child]
    for ev in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0][1] + stack[-1][0][2] <= ev[1]:
            top, has_child = stack.pop()
            if not has_child:
                out.append(top)
        if stack and ev[1] + ev[2] <= stack[-1][0][1] + stack[-1][0][2]:
            stack[-1][1] = True  # nested, not merely overlapping
        stack.append([ev, False])
    out.extend(ev for ev, has_child in stack if not has_child)
    return sorted(out, key=lambda e: e[1])


def module_events(dev, names):
    """Executions of the jitted programs whose base name is in `names`."""
    return [(s, s + d) for n, s, d in dev["modules"] if module_base(n) in names]


def busy_summary(trace: dict) -> dict:
    """busy_s averaged over devices, window_s, worst-device idle share."""
    lo, hi = window(trace)
    busy = {o: total(clip(op_intervals(d), lo, hi))
            for o, d in trace["devices"].items()}
    span = hi - lo
    return {"busy_s": sum(busy.values()) / len(busy) / 1e9,
            "window_s": span / 1e9,
            "idle_share_worst": max(1.0 - b / span for b in busy.values()),
            "worst_device": max(busy, key=lambda o: 1.0 - busy[o] / span)}


def breakdown(trace: dict, top: int = 10) -> dict:
    """Top device ops by summed time (worst device) and the longest idle gaps
    there, each labelled by the benchmark's host span it falls in."""
    lo, hi = window(trace)
    worst = busy_summary(trace)["worst_device"]
    dev = trace["devices"][worst]
    by_name = collections.defaultdict(float)
    for name, _, dur in dev["ops"]:
        by_name[name] += dur
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    spans = trace["host"]
    by_label = collections.defaultdict(float)
    for s, e in sorted(gaps(op_intervals(dev), lo, hi),
                       key=lambda g: g[0] - g[1])[:100]:
        mid = (s + e) / 2
        label = next((n for n, hs, hd in spans if hs <= mid < hs + hd),
                     "outside_benchmark_spans")
        by_label[label] += e - s
    idle = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, d / 1e9] for n, d in ops],
            "idle_gaps": [[n, d / 1e9] for n, d in idle]}
