"""The attention route table (ops/sdpa_routing.py TABLE) is reviewable DATA:
it must parse on import, its ranges must not overlap, and every row carries
the origin of its verdict — the `route-tables` checker of distrilint, run
here under pytest so a local `pytest tests/` catches a bad row before
`python -m distrifuser_tpu.analysis --strict` does."""

import pytest

from distrifuser_tpu.analysis.checkers import route_tables
from distrifuser_tpu.ops import sdpa_routing
from distrifuser_tpu.ops.sdpa_routing import Route, Row


def test_route_tables_lint_clean():
    assert route_tables.check_tables() == []


@pytest.mark.parametrize("bad,ident", [
    ({72: (Row(2944, 5760, Route("inrepo", 1000, 512), "t"),)}, "sdpa:tile:"),
    ({72: (Row(2944, 5760, Route("inrepo", 1024, 64), "t"),)}, "sdpa:tile:"),
    ({64: (Row(768, 1408, Route("inrepo", 2048, 512), "t"),)}, "sdpa:tile:"),
    ({72: (Row(2944, 5760, Route("mosaic", 256, 512), "t"),)}, "sdpa:value:"),
    ({72: (Row(2944, 5760, Route("padded", kernel="upstream"), "t"),)},
     "sdpa:value:"),
    ({72: (Row(2944, "5760", Route("inrepo", 256, 512), "t"),)}, "sdpa:key:"),
    ({72: (Row(5760, 2944, Route("inrepo", 256, 512), "t"),)}, "sdpa:key:"),
    ({"72": (Row(2944, 5760, Route("inrepo", 256, 512), "t"),)}, "sdpa:key:"),
    ({72: (Row(2944, 5760, Route("inrepo", 256, 512), ""),)}, "sdpa:origin:"),
], ids=["tile-not-pow2", "tile-under-128", "tile-over-range", "impl-unknown",
        "padded-is-no-row", "bound-not-int", "range-reversed",
        "head-dim-not-int", "origin-empty"])
def test_override_table_is_linted_like_the_measured_one(monkeypatch, bad,
                                                        ident):
    """The one table is what routes the benchmark's cells: a malformed key,
    an unknown impl, a tile that no length of its range could have run or a
    row without its origin is a finding."""
    monkeypatch.setattr(sdpa_routing, "TABLE", bad)
    found = route_tables.check_tables()
    assert found and all(f.identity.startswith(ident) for f in found), found
