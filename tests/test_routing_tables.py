"""Measured routing tables (sdpa + gemm) are reviewable DATA: they must
parse on import and carry provenance — the lint scripts/lint_route_tables.py
enforces in CI, run here under pytest so a local `pytest tests/` catches a
bad bake before the workflow does."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_route_tables_lint_clean():
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import lint_route_tables
    finally:
        sys.path.pop(0)
    assert lint_route_tables.check_tables() == []


def test_lint_script_runs_as_tooling():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint_route_tables.py")],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr
    assert "clean" in proc.stdout


def test_gemm_table_backend_declared_when_measured():
    from distrifuser_tpu.ops import gemm_routing

    if gemm_routing.MEASURED_ROUTES:
        assert gemm_routing.MEASURED_BACKEND in ("cpu", "tpu", "gpu")
    # provenance is never empty, measured or not
    assert gemm_routing.MEASURED_PROVENANCE.strip()


def test_override_table_is_linted_like_the_measured_one(monkeypatch):
    """MODEL_VALIDATED_OVERRIDES is what routes the benchmark's cells: a
    malformed key, an unknown impl or a tile that cannot divide its bucket's
    length is a finding there too."""
    from distrifuser_tpu.analysis.checkers import route_tables
    from distrifuser_tpu.ops import sdpa_routing
    from distrifuser_tpu.ops.sdpa_routing import Route

    assert route_tables.check_tables() == []
    for bad, ident in [
        ({(72, 12): Route("inrepo", 1000, 512)}, "sdpa:tile:"),
        ({(72, 12): Route("inrepo", 1024, 64)}, "sdpa:tile:"),
        ({(64, 10): Route("inrepo", 2048, 512)}, "sdpa:tile:"),
        ({(72, 12): Route("mosaic", 256, 512)}, "sdpa:value:"),
        ({(72, "12"): Route("inrepo", 256, 512)}, "sdpa:key:"),
    ]:
        monkeypatch.setattr(sdpa_routing, "MODEL_VALIDATED_OVERRIDES", bad)
        found = route_tables.check_tables()
        assert any(f.identity.startswith(ident) for f in found), (bad, found)
