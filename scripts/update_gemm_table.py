"""Regenerate the checked-in GEMM routing table from chip-campaign output.

The quantized-compute sibling of scripts/update_sdpa_table.py: reads the
JSON lines scripts/chip_campaign.py emits ("gemm" lines compare dequant /
int8-fp8 dot / Pallas per (M, K, N, mode); "gemm_tune" lines sweep the
Pallas kernel's tiles), picks the measured winner per (mode, log2 M)
bucket, and rewrites the MEASURED_GEMM_ROUTES block of
distrifuser_tpu/ops/gemm_routing.py in place.

The table records the BACKEND the measurements were taken on
(gemm_routing.MEASURED_BACKEND); resolve() only consults the table when
that matches the running platform, so a CPU structural run can never pin
dequant fleet-wide on TPU (and a TPU bake never misroutes CPU tests).

Usage:
    python scripts/update_gemm_table.py --log chiprun_out/campaign.log \
        [--label "v5e 2026-08-04"] [--dry_run]
"""

import argparse
import json
import math
import os
import re
import sys

TABLE_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "distrifuser_tpu", "ops", "gemm_routing.py",
)
BEGIN = ("# --- BEGIN MEASURED_GEMM_ROUTES "
         "(generated by scripts/update_gemm_table.py) ---")
END = "# --- END MEASURED_GEMM_ROUTES ---"

_BF16_PEAK_FLOPS = 197e12  # v5e bf16 peak; int8 runs the MXU at 2x this


def parse_log(path):
    gemm, tune = [], []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if not isinstance(rec.get("ms"), dict):
                continue
            if rec.get("phase") == "gemm":
                gemm.append(rec)
            elif rec.get("phase") == "gemm_tune":
                tune.append(rec)
    return gemm, tune


def _roofline_floor_ms(rec):
    """Latency lower bound for one timed PAIR of this record's GEMMs at
    the int8 MXU rate (2x bf16 peak) — the same broken-measurement guard
    as update_sdpa_table (campaign r5's 350x async-dispatch escapes must
    never reach a routing table)."""
    m = rec.get("M", 0)
    k = rec.get("K", 1)
    n = rec.get("N", 1)
    flops = 2 * 2.0 * m * k * n  # two matmuls per chained pair
    return flops / (2 * _BF16_PEAK_FLOPS) * 1e3


def _sane(rec, timings):
    floor = _roofline_floor_ms(rec)
    kept = {k: v for k, v in timings.items()
            if isinstance(v, (int, float)) and v >= floor}
    dropped = {k: v for k, v in timings.items()
               if isinstance(v, (int, float)) and v < floor}
    if dropped:
        print(f"dropping sub-roofline timings at M={rec.get('M')} "
              f"K={rec.get('K')} N={rec.get('N')} (floor {floor:.3f} ms): "
              f"{dropped}", file=sys.stderr)
    return kept


def build_routes(gemm, tune):
    """{(mode, bucket): (impl, bm, bn, bk, comment)}; later lines win, and
    the single backend of the log is returned alongside (mixed-backend
    logs refuse — the table is gated on one platform)."""
    backends = {r.get("backend") for r in gemm + tune if r.get("backend")}
    if len(backends) > 1:
        raise SystemExit(f"log mixes backends {sorted(backends)}: bake one "
                         "platform per table")
    backend = backends.pop() if backends else ""

    best_tiles = {}
    for rec in tune:
        timings = _sane(rec, rec["ms"])
        if timings:
            tile, ms = min(timings.items(), key=lambda kv: kv[1])
            bm, bn, bk = (int(x) for x in tile.split("x"))
            best_tiles[(rec["M"], rec.get("mode", "int8"))] = (bm, bn, bk, ms)

    routes = {}
    for rec in gemm:
        timings = _sane(rec, rec["ms"])
        if not timings:
            continue
        mode = rec.get("mode", "int8")
        m, k, n = rec["M"], rec.get("K"), rec.get("N")
        impl, ms = min(timings.items(), key=lambda kv: kv[1])
        tuned = best_tiles.get((m, mode))
        if tuned and tuned[3] < ms:
            impl, ms = "pallas", tuned[3]
        bm = bn = bk = None
        if impl == "pallas" and tuned and tuned[3] <= ms:
            bm, bn, bk, ms = tuned
        losers = ", ".join(f"{kk}={vv}" for kk, vv in sorted(timings.items())
                           if kk != impl)
        comment = f"M={m} K={k} N={n}: {impl}={ms}ms vs {losers}"
        routes[(mode, round(math.log2(max(m, 1))))] = (
            impl, bm, bn, bk, comment)
    return routes, backend


def render_block(routes, label, backend):
    lines = [BEGIN,
             f"# provenance: {label}",
             f'MEASURED_PROVENANCE = "{label}"',
             f'MEASURED_BACKEND = "{backend}"',
             "MEASURED_ROUTES: dict = {"]
    for (mode, bucket), (impl, bm, bn, bk, comment) in sorted(routes.items()):
        args = f'"{impl}"'
        if bm is not None:
            args += f", {bm}, {bn}, {bk}"
        lines.append(f'    ("{mode}", {bucket}): GemmRoute({args}),'
                     f"  # {comment}")
    lines += ["}", END]
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--log", required=True)
    ap.add_argument("--label", default=None,
                    help="provenance string; defaults to the log filename")
    ap.add_argument("--dry_run", action="store_true")
    args = ap.parse_args()

    gemm, tune = parse_log(args.log)
    routes, backend = build_routes(gemm, tune)
    if not routes:
        print(f"no usable gemm measurements in {args.log}; table unchanged")
        return 1

    label = args.label or f"measured from {os.path.basename(args.log)}"
    block = render_block(routes, label, backend)
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
    print(f"wrote {len(routes)} routes to {TABLE_PATH} "
          f"(backend={backend!r})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
