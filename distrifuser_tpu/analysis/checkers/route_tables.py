"""The attention route table: well formed, and every row with its origin.

`ops/sdpa_routing.py TABLE` is measurement DATA committed as code: per head
dim, inclusive ranges of kv_len, each with the kernel and tiles that won
there and where that was measured.  A hand-edit that overlaps two ranges,
names a kernel that does not exist, writes a tile no length of its range
could have run, or drops the origin would silently turn "reviewable
measurement" into "unexplained magic constant".  Invariants as checks,
suppressions as reviewed data — the pattern the rest of distrilint
generalizes.
"""

from __future__ import annotations

from typing import List

from ..core import CheckContext, Finding

NAME = "route-tables"
DESCRIPTION = ("the sdpa route table parses; ranges disjoint and ascending, "
               "kernels known, tiles fit, every row with its origin")

SDPA_PATH = "distrifuser_tpu/ops/sdpa_routing.py"
TABLE_IMPLS = ("xla", "inrepo", "upstream")


def _finding(message: str, identity: str) -> Finding:
    return Finding(checker=NAME, path=SDPA_PATH, line=0, message=message,
                   identity=identity)


def check_tables() -> List[Finding]:
    """The table checks, import-based (the table must parse to be checked
    at all — an ImportError IS the finding)."""
    findings: List[Finding] = []
    try:
        from ...ops import sdpa_routing
    except Exception as exc:
        return [_finding(f"route table failed to import: {exc}",
                         "import-error")]

    for d, rows in sdpa_routing.TABLE.items():
        if not (isinstance(d, int) and d > 0 and isinstance(rows, tuple)):
            findings.append(_finding(
                f"sdpa table key malformed: {d!r} (a head dim -> a tuple "
                "of rows)", f"sdpa:key:{d!r}"))
            continue
        last_hi = 0
        for row in rows:
            where = f"{d!r}:{tuple(row)[:2]!r}"
            if not (isinstance(row, sdpa_routing.Row)
                    and isinstance(row.kv_lo, int)
                    and isinstance(row.kv_hi, int)
                    and 0 < row.kv_lo <= row.kv_hi):
                findings.append(_finding(
                    f"sdpa row malformed at head dim {d}: {row!r}",
                    f"sdpa:key:{where}"))
                continue
            if row.kv_lo <= last_hi:
                findings.append(_finding(
                    f"sdpa rows of head dim {d} overlap or descend at "
                    f"{row.kv_lo}..{row.kv_hi} (previous row ends at "
                    f"{last_hi})", f"sdpa:order:{where}"))
            last_hi = max(last_hi, row.kv_hi)
            route = row.route
            if not isinstance(route, sdpa_routing.Route) or (
                    route.impl not in TABLE_IMPLS) or route.kernel is not None:
                findings.append(_finding(
                    f"sdpa route value malformed: {where} -> {route!r}",
                    f"sdpa:value:{where}"))
                continue
            # a tile is a power of two from the 128-lane minimum up to its
            # range's longest length.  sdpa fits tiles to each call, but a
            # row whose tile fits no length of its range records a
            # measurement that cannot have been made.
            for tile in (route.block_q, route.block_k):
                if tile is not None and not (
                        isinstance(tile, int) and 128 <= tile <= row.kv_hi
                        and tile & (tile - 1) == 0):
                    findings.append(_finding(
                        f"sdpa row {where}: tile {tile!r} is not a power "
                        f"of two in [128, {row.kv_hi}]",
                        f"sdpa:tile:{where}"))
            if not (isinstance(row.origin, str) and row.origin.strip()):
                findings.append(_finding(
                    f"sdpa row {where}: no origin (the ledger line or the "
                    "measurement its verdict came from)",
                    f"sdpa:origin:{where}"))
    return findings


def run(ctx: CheckContext) -> List[Finding]:
    return check_tables()
