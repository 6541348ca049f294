"""Regenerate the checked-in SDPA routing table from chip-campaign output.

Reads the JSON lines scripts/chip_campaign.py emits ("attn" lines compare
xla / in-repo Pallas / upstream flash per shape; "tune" lines sweep the
in-repo kernel's tiles), picks the measured winner per (head_dim, log2 L)
bucket, and rewrites the MEASURED_ROUTES block of
distrifuser_tpu/ops/sdpa_routing.py in place.

Usage:
    python scripts/update_sdpa_table.py --log chiprun_out/campaign.log \
        [--label "v5e 2026-07-29"] [--dry_run]

Re-running on a newer log overwrites the previous table; the table file is
committed so the routing decision is reviewable data, not an env var.
"""

import argparse
import json
import math
import os
import re
import sys

TABLE_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "distrifuser_tpu", "ops", "sdpa_routing.py",
)
BEGIN = "# --- BEGIN MEASURED_ROUTES (generated by scripts/update_sdpa_table.py) ---"
END = "# --- END MEASURED_ROUTES ---"


_BENCH_ATTENTION_IMPLS = {
    "xla": "xla", "pallas_inrepo": "inrepo", "pallas_upstream": "upstream",
}


def parse_log(path):
    """Accept both producers: chip_campaign.py attn/tune/tune_upstream lines
    (one record per shape with an {impl: ms} dict) and bench_attention.py
    lines (one record per shape+impl) — the latter are grouped into the same
    form.  Tune records carry their target impl ("inrepo" for the legacy
    "tune" phase name)."""
    attn, tune = [], []
    flat = {}  # (L, heads) -> {"phase": "attn", ..., "ms": {...}}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(rec.get("ms"), dict):
                if rec.get("phase") == "attn":
                    attn.append(rec)
                elif rec.get("phase") == "tune":
                    tune.append({**rec, "impl": "inrepo"})
                elif rec.get("phase") == "tune_upstream":
                    tune.append({**rec, "impl": "upstream"})
            elif rec.get("impl") in _BENCH_ATTENTION_IMPLS and "L" in rec:
                key = (rec["L"], rec.get("heads"))
                grouped = flat.setdefault(
                    key, {"phase": "attn", "L": rec["L"],
                          "heads": rec.get("heads"), "ms": {}}
                )
                grouped["ms"][_BENCH_ATTENTION_IMPLS[rec["impl"]]] = rec["ms"]
    attn.extend(flat.values())
    return attn, tune


_BF16_PEAK_FLOPS = 197e12  # v5e chip peak; v4/v5p are lower, so this floor
                           # is conservative on every current TPU


def _roofline_floor_ms(rec):
    """Physical latency lower bound for one SDPA call of this record's shape:
    QK + PV matmul FLOPs at 100% of bf16 peak.  Campaign r5 produced
    upstream-flash tune entries of 0.02 ms at L=16384 (350x ABOVE peak —
    async-dispatch timing escapes at block_q >= 512 there, not real kernel
    latencies); any timing below this floor is a broken measurement, not a
    fast kernel, and must never reach the routing table."""
    L = rec.get("L", 0)
    heads = rec.get("heads", 1) or 1
    d = rec.get("head_dim", 64) or 64
    # chip_campaign.py emits `batch` in attn/tune records (B=2, the CFG
    # pair); a record without it gets the conservative batch=1 floor
    # rather than silently assuming the campaign's batching.  Legacy
    # (pre-batch-field) logs therefore get a floor 2x looser than their
    # true B=2 measurement — acceptable: the guard exists for the ~350x
    # timing escapes, and re-running the campaign re-tightens it.
    batch = rec.get("batch", 1) or 1
    flops = 4.0 * batch * heads * float(L) * float(L) * d
    return flops / _BF16_PEAK_FLOPS * 1e3


def _sane(rec, timings):
    floor = _roofline_floor_ms(rec)
    kept = {k: v for k, v in timings.items() if v >= floor}
    dropped = {k: v for k, v in timings.items() if v < floor}
    if dropped:
        print(f"dropping sub-roofline timings at L={rec.get('L')} "
              f"heads={rec.get('heads')} (floor {floor:.3f} ms): {dropped}",
              file=sys.stderr)
    return kept


def build_routes(attn, tune):
    """{(head_dim, bucket): (impl, bq, bk, comment)} — later lines win, so a
    rerun campaign supersedes an earlier partial one in the same log."""
    # keyed by (L, head_dim, heads, impl) — tiles tuned for one head_dim or
    # kernel must not leak onto another's route, and a tuned
    # time measured at one head COUNT must not be compared against another
    # head count's attn record (campaign r5: folding an h=10 sweep into the
    # h=24 SD3 record flipped the route to a kernel that loses at both)
    best_tiles = {}
    for rec in tune:
        timings = _sane(rec, {k: v for k, v in rec["ms"].items()
                              if isinstance(v, (int, float))})
        if timings:
            tile, ms = min(timings.items(), key=lambda kv: kv[1])
            bq, bk = (int(x) for x in tile.split("x"))
            key = (rec["L"], rec.get("head_dim", 64), rec.get("heads"),
                   rec.get("impl", "inrepo"))
            best_tiles[key] = (bq, bk, ms)

    routes = {}
    # per route slot, the tile sweep the FINAL (last-wins) owner consumed —
    # a sweep consumed by an overwritten record did not govern anything
    slot_consumed = {}
    for rec in attn:
        timings = _sane(rec, {k: v for k, v in rec["ms"].items()
                              if isinstance(v, (int, float))})
        if not timings:
            continue
        lk = rec["L"]
        # head_dim emitted since round 3; SDXL is d=64 at every level, which
        # older campaign logs relied on implicitly
        d = rec.get("head_dim", 64)
        heads = rec.get("heads")

        def tuned_for(cand):
            # only sweeps measured at THIS record's head count may fold in
            # (apples to apples); a missing heads field on either side —
            # pre-r5 logs — is a wildcard so old logs keep their tiles
            if heads is not None:
                return (best_tiles.get((lk, d, heads, cand))
                        or best_tiles.get((lk, d, None, cand)))
            matches = [v for k, v in best_tiles.items()
                       if k[0] == lk and k[1] == d and k[3] == cand]
            return min(matches, key=lambda t: t[2]) if matches else None

        impl, ms = min(timings.items(), key=lambda kv: kv[1])
        # a tuned sweep can beat the default-tile attn comparison: consider
        # each impl at its best tuned time before declaring the winner
        for cand in timings:
            t = tuned_for(cand)
            if t and t[2] < ms:
                impl, ms = cand, t[2]
        bq = bk = None
        slot = (d, round(math.log2(lk)))
        tuned = tuned_for(impl)
        # attach tiles only when the tuned config actually beats (or ties)
        # the winner's default-tile time — a slower sweep best must not pin
        # slower-than-default tiles with a comment claiming the faster time
        if tuned and tuned[2] <= ms:
            bq, bk = tuned[0], tuned[1]
            ms = tuned[2]
            slot_consumed[slot] = (lk, d, impl)
        else:
            slot_consumed[slot] = None
        losers = ", ".join(f"{k}={v}" for k, v in sorted(timings.items())
                           if k != impl)
        hdr = f"L={lk}" + (f" h={heads}" if heads is not None else "")
        # the campaign measures the CFG pair (batch=2); record which batch
        # governed the decision so a re-bake from differently-batched logs
        # is visible in the table diff
        if rec.get("batch") is not None:
            hdr += f" b={rec['batch']}"
        comment = f"{hdr}: {impl}={ms}ms vs {losers}"
        routes[slot] = (impl, bq, bk, comment)
    # surface sweeps that did not govern any FINAL route — time was spent
    # tuning a configuration the route decision cannot use (campaign r5:
    # L=4096 sweeps run at h=10 while the last, route-owning attn record at
    # that bucket is h=24)
    consumed = {v for v in slot_consumed.values() if v}
    for (skl, skd, skh, ski) in best_tiles:
        if (skl, skd, ski) not in consumed:
            print(f"note: tuned sweep at L={skl} d={skd} h={skh} "
                  f"impl={ski} did not govern any route (head-count "
                  "mismatch with the route-owning attn record, its impl "
                  "lost, or its best time did not beat default tiles)",
                  file=sys.stderr)
    return routes


def render_block(routes, label):
    lines = [BEGIN,
             f"# provenance: {label}",
             f'MEASURED_PROVENANCE = "{label}"',
             "MEASURED_ROUTES: dict = {"]
    for (d, bucket), (impl, bq, bk, comment) in sorted(routes.items()):
        args = f'"{impl}"' if bq is None else f'"{impl}", {bq}, {bk}'
        lines.append(f"    ({d}, {bucket}): Route({args}),  # {comment}")
    lines += ["}", END]
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--log", required=True)
    ap.add_argument("--label", default=None,
                    help="provenance string; defaults to the log filename")
    ap.add_argument("--dry_run", action="store_true")
    args = ap.parse_args()

    attn, tune = parse_log(args.log)
    routes = build_routes(attn, tune)
    if not routes:
        print(f"no usable attn measurements in {args.log}; table unchanged")
        return 1

    label = args.label or f"measured from {os.path.basename(args.log)}"
    block = render_block(routes, label)
    src = open(TABLE_PATH).read()
    pattern = re.escape(BEGIN) + r".*?" + re.escape(END)
    new_src, n = re.subn(pattern, lambda _m: block, src, flags=re.DOTALL)
    if n != 1:
        print(f"marker block not found in {TABLE_PATH}", file=sys.stderr)
        return 2
    if args.dry_run:
        print(block)
        return 0
    with open(TABLE_PATH, "w") as f:
        f.write(new_src)
    print(f"wrote {len(routes)} routes to {TABLE_PATH}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
