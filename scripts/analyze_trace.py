"""Overlap evidence from a real-chip profiler trace.

`utils/overlap.py` proves 63/65 refresh collectives are *deferrable* from
HLO structure; this script closes the loop with runtime evidence: did the TPU scheduler actually hide the collectives behind
compute — the reference's async-NCCL behavior
(/root/reference/distrifuser/utils.py:170-190) — or did they serialize?

Input: a jax.profiler trace directory captured with
``create_perfetto_trace=True``.  The
perfetto artifact is Chrome-trace JSON (gzip), parseable with stdlib — no
tensorboard needed.

Method: complete ("ph" == "X") events are grouped into lanes by
(pid, tid); event names matching the XLA collective opcodes
(all-gather / all-reduce / collective-permute / reduce-scatter /
all-to-all, incl. their -start/-done async halves) form the collective
interval set, everything else on device lanes the compute set.  Host lanes
(python/runtime threads) are dropped by keeping only lanes that contain at
least one XLA-looking op.  Reported: per-set busy time (interval union) and
the intersection of collective time with compute time — the overlapped
fraction.  A collective is "hidden" exactly where its interval co-runs with
compute, so ``overlapped_frac`` near 1.0 is the async-NCCL analog; near 0.0
means the collectives serialize the step.

Usage:
    python scripts/analyze_trace.py chiprun_out/trace [--json]
"""

import argparse
import glob
import gzip
import json
import os
import re
import sys

_COLLECTIVE = re.compile(
    r"all-gather|all-reduce|collective-permute|reduce-scatter|all-to-all"
    r"|psum|ppermute", re.I,
)
# ops that look like device compute (XLA emits these names into the trace)
_XLA_OP = re.compile(
    r"fusion|convolution|dot|copy|%|\.\d+$|all-gather|all-reduce"
    r"|collective-permute|reduce-scatter|all-to-all|dynamic-slice|transpose",
    re.I,
)


def find_perfetto(path: str):
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(
        os.path.join(path, "**", "*.json.gz"), recursive=True))
    named = [h for h in hits if "perfetto" in os.path.basename(h)]
    hits = named or hits
    return hits[-1] if hits else None


def load_events(path: str):
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        doc = json.load(f)
    return doc["traceEvents"] if isinstance(doc, dict) else doc


def union(intervals):
    """Total covered time of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def intersection(a, b):
    """Covered time common to two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if s < e:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _device_lanes(events):
    """Complete events grouped into (pid, tid) lanes, host/python lanes
    dropped (a lane must contain at least one XLA-looking op)."""
    lanes = {}
    for ev in events:
        if ev.get("ph") != "X" or "dur" not in ev:
            continue
        lanes.setdefault((ev.get("pid"), ev.get("tid")), []).append(ev)
    return {key: evs for key, evs in lanes.items()
            if any(_XLA_OP.search(e.get("name", "")) for e in evs)}


def analyze(events):
    """Per-device (per-pid) overlap: a TPU trace carries one pid per
    device with separate compute/async lanes; a collective is hidden where
    its interval co-runs with compute *of the same device*.  A CPU trace
    has a single pid, so the analysis degrades to global — fine for the
    scheduling-level question (did XLA execute the async-start/done pairs
    concurrently with compute at all)."""
    per_pid = {}  # pid -> {"coll": [...], "comp": [...]}
    coll_names = {}
    n_coll = 0
    for (pid, _tid), evs in _device_lanes(events).items():
        slot = per_pid.setdefault(pid, {"coll": [], "comp": []})
        for e in evs:
            iv = (e["ts"], e["ts"] + e["dur"])
            name = e.get("name", "")
            m = _COLLECTIVE.search(name)
            if m:
                slot["coll"].append(iv)
                n_coll += 1
                coll_names[m.group(0).lower()] = (
                    coll_names.get(m.group(0).lower(), 0) + 1)
            else:
                slot["comp"].append(iv)

    coll_busy = comp_busy = overlapped = 0.0
    for slot in per_pid.values():
        coll_busy += union(slot["coll"])
        comp_busy += union(slot["comp"])
        overlapped += intersection(merged(slot["coll"]), merged(slot["comp"]))
    return {
        "n_devices": len(per_pid),
        "n_collective_events": n_coll,
        "collective_kinds": coll_names,
        "collective_busy_us": round(coll_busy, 1),
        "compute_busy_us": round(comp_busy, 1),
        "overlapped_us": round(overlapped, 1),
        "overlapped_frac": round(overlapped / coll_busy, 4) if coll_busy else None,
        "exposed_us": round(coll_busy - overlapped, 1),
    }


def top_ops(events, n):
    """Total device-lane time by op name — where does the step actually go?

    XLA fusion names keep their `fusion.N` identity, so a single hot fused
    region is visible as itself rather than smeared into one 'fusion'
    bucket."""
    totals = {}
    counts = {}
    grand = 0.0
    for evs in _device_lanes(events).values():
        for e in evs:
            name = e.get("name", "")
            totals[name] = totals.get(name, 0.0) + e["dur"]
            counts[name] = counts.get(name, 0) + 1
            grand += e["dur"]
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [{"name": k, "total_us": round(v, 1), "calls": counts[k],
             "share": round(v / grand, 4) if grand else 0.0}
            for k, v in ranked], grand


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("trace", help="trace dir or perfetto json(.gz)")
    ap.add_argument("--json", action="store_true", help="JSON line only")
    ap.add_argument("--top", type=int, default=0,
                    help="also print the N ops with the largest total "
                         "device time")
    args = ap.parse_args()

    path = find_perfetto(args.trace)
    if path is None:
        print(f"no perfetto json(.gz) under {args.trace}", file=sys.stderr)
        return 2
    events = load_events(path)
    rep = analyze(events)
    if args.top:
        ranked, grand = top_ops(events, args.top)
        if args.json:
            # ONE object on one line (the documented --json contract):
            # top_ops rides inside the overlap report
            rep = {**rep, "top_ops": ranked,
                   "device_total_us": round(grand, 1)}
        else:
            print(f"top {len(ranked)} ops by total device time "
                  f"(of {grand / 1e3:.1f} ms):")
            for r in ranked:
                print(f"  {r['share'] * 100:5.1f}%  {r['total_us'] / 1e3:8.2f} ms"
                      f"  x{r['calls']:<5} {r['name'][:80]}")
            print()
    if args.json:
        print(json.dumps(rep))
        return 0
    print(f"trace: {path}")
    for k, v in rep.items():
        print(f"  {k}: {v}")
    if rep["n_collective_events"] == 0:
        print("  (no collectives found — single-device trace?)")
    elif rep["overlapped_frac"] is not None:
        verdict = ("hidden behind compute (async-NCCL analog confirmed)"
                   if rep["overlapped_frac"] > 0.7 else
                   "partially exposed" if rep["overlapped_frac"] > 0.3 else
                   "serializing the step")
        print(f"  => collectives are {verdict}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
