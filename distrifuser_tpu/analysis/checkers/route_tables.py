"""Measured routing tables: provenance + parse (the original lint).

The sdpa/gemm routing tables are measurement DATA committed as code
(regenerated by scripts/update_sdpa_table.py / update_gemm_table.py from
chip-campaign logs).  A hand-edit that drops the provenance line,
desyncs it from the generated block's comment, or malforms a key would
silently turn "reviewable measurement" into "unexplained magic
constant".  This checker is `scripts/lint_route_tables.py` folded into
the framework — the script remains as a thin shim so existing workflows
and tests/test_routing_tables.py keep one behavior — and is the pattern
the rest of distrilint generalizes: invariants as checks, suppressions
as reviewed data.
"""

from __future__ import annotations

import re
from typing import List, Optional

from ..core import CheckContext, Finding

NAME = "route-tables"
DESCRIPTION = ("sdpa + gemm measured tables parse and carry provenance "
               "synced to their generated blocks")

SDPA_PATH = "distrifuser_tpu/ops/sdpa_routing.py"
GEMM_PATH = "distrifuser_tpu/ops/gemm_routing.py"


def _finding(path: str, message: str, identity: str) -> Finding:
    return Finding(checker=NAME, path=path, line=0, message=message,
                   identity=identity)


def _block_provenance(src: str, begin: str, end: str) -> Optional[str]:
    m = re.search(re.escape(begin) + r"(.*?)" + re.escape(end), src,
                  flags=re.DOTALL)
    if not m:
        return None
    p = re.search(r"^# provenance: (.+)$", m.group(1), flags=re.MULTILINE)
    return p.group(1).strip() if p else ""


def check_tables() -> List[Finding]:
    """The table checks, import-based (tables must parse to be checked
    at all — an ImportError IS the finding)."""
    findings: List[Finding] = []
    try:
        from ...ops import gemm_routing, sdpa_routing
    except Exception as exc:
        return [_finding(SDPA_PATH,
                         f"routing tables failed to import: {exc}",
                         "import-error")]

    def check_provenance(mod, path: str, begin: str, end: str, tag: str):
        with open(mod.__file__) as f:
            src = f.read()
        comment = _block_provenance(src, begin, end)
        if comment is None:
            findings.append(_finding(
                path, f"{tag}: generated block markers missing",
                f"{tag}:markers"))
        elif not comment:
            findings.append(_finding(
                path, f"{tag}: no '# provenance:' line in the generated "
                "block", f"{tag}:comment-missing"))
        prov = getattr(mod, "MEASURED_PROVENANCE", None)
        if not (isinstance(prov, str) and prov.strip()):
            findings.append(_finding(
                path, f"{tag}: MEASURED_PROVENANCE missing/empty",
                f"{tag}:provenance-missing"))
        elif comment and comment != prov:
            findings.append(_finding(
                path, f"{tag}: provenance comment {comment!r} != "
                f"MEASURED_PROVENANCE {prov!r}", f"{tag}:desync"))

    check_provenance(sdpa_routing, SDPA_PATH,
                     "# --- BEGIN MEASURED_ROUTES",
                     "# --- END MEASURED_ROUTES ---", "sdpa")
    for table in ("MEASURED_ROUTES", "MODEL_VALIDATED_OVERRIDES"):
        for key, route in getattr(sdpa_routing, table).items():
            if not (isinstance(key, tuple) and len(key) == 2
                    and all(isinstance(x, int) for x in key)):
                findings.append(_finding(
                    SDPA_PATH, f"sdpa route key malformed: {key!r}",
                    f"sdpa:key:{key!r}"))
                continue
            if not isinstance(route, sdpa_routing.Route) or (
                    route.impl not in ("xla", "inrepo", "upstream")):
                findings.append(_finding(
                    SDPA_PATH, f"sdpa route value malformed: {key!r} -> "
                    f"{route!r}", f"sdpa:value:{key!r}"))
                continue
            # a tile is a power of two from the 128-lane minimum up to its
            # bucket's length, so it divides that length.  sdpa fits tiles
            # to each call, but an entry that does not fit its own bucket
            # records a measurement that cannot have been made.
            for tile in (route.block_q, route.block_k):
                if tile is not None and not (
                        isinstance(tile, int) and 128 <= tile <= 2 ** key[1]
                        and tile & (tile - 1) == 0):
                    findings.append(_finding(
                        SDPA_PATH, f"sdpa {table} {key!r}: tile {tile!r} is "
                        f"not a power of two in [128, 2^{key[1]}]",
                        f"sdpa:tile:{table}:{key!r}"))

    check_provenance(gemm_routing, GEMM_PATH,
                     "# --- BEGIN MEASURED_GEMM_ROUTES",
                     "# --- END MEASURED_GEMM_ROUTES ---", "gemm")
    backend = getattr(gemm_routing, "MEASURED_BACKEND", None)
    if not isinstance(backend, str):
        findings.append(_finding(
            GEMM_PATH, "gemm: MEASURED_BACKEND missing",
            "gemm:backend-missing"))
    if gemm_routing.MEASURED_ROUTES and not backend:
        findings.append(_finding(
            GEMM_PATH, "gemm table has routes but no MEASURED_BACKEND — "
            "unscoped measurements would govern every platform",
            "gemm:backend-unscoped"))
    for key, route in gemm_routing.MEASURED_ROUTES.items():
        if not (isinstance(key, tuple) and len(key) == 2
                and isinstance(key[0], str) and key[0] in ("int8", "fp8")
                and isinstance(key[1], int)):
            findings.append(_finding(
                GEMM_PATH, f"gemm route key malformed: {key!r}",
                f"gemm:key:{key!r}"))
        if not isinstance(route, gemm_routing.GemmRoute) or (
                route.impl not in gemm_routing.GEMM_IMPLS):
            findings.append(_finding(
                GEMM_PATH, f"gemm route value malformed: {key!r} -> "
                f"{route!r}", f"gemm:value:{key!r}"))
    return findings


def run(ctx: CheckContext) -> List[Finding]:
    return check_tables()
