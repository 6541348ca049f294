"""bench.py's one-parseable-line contract.

bench.py guarantees exactly one JSON result line within its total
wall-clock budget (a real latency, or an explicit failure metric) and a
meaningful exit code.  These run the real script on the CPU with the tiny
preset, which has to be asked for: without a chip the default preset fails.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _run(args, timeout):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # single CPU device is fine and faster
    return subprocess.run(
        [sys.executable, BENCH, *args], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=REPO,
    )


def _parse_result(stdout):
    lines = [l for l in stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, f"expected exactly one JSON line, got: {stdout!r}"
    rec = json.loads(lines[0])
    assert set(rec) == {"metric", "value", "unit", "vs_baseline"}
    return rec


def test_normal_run_emits_real_latency():
    r = _run(["--preset", "tiny", "--steps", "2", "--test_times", "1"],
             timeout=420)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = _parse_result(r.stdout)
    assert rec["value"] > 0 and rec["unit"] == "s"
    assert "provenance" in r.stderr  # platform/dtype always logged


def test_expired_budget_still_emits_parseable_line():
    """Budget already spent at start: the watchdog must print the explicit
    timeout metric (never silence) and exit 2."""
    r = _run(["--preset", "tiny", "--steps", "2", "--test_times", "1",
              "--total_budget_s", "91"], timeout=300)
    assert r.returncode == 2, (r.returncode, r.stderr[-500:])
    rec = _parse_result(r.stdout)
    assert rec["metric"] == "bench_watchdog_timeout"
    assert rec["value"] == -1.0


def test_no_chip_and_no_tiny_preset_fails():
    """No accelerator and no --preset tiny: an explicit failure line and a
    non-zero exit — never a CPU number under the headline metric's name."""
    r = _run(["--steps", "2", "--test_times", "1"], timeout=120)
    assert r.returncode == 3, (r.returncode, r.stderr[-500:])
    rec = _parse_result(r.stdout)
    assert rec["metric"] == "bench_no_accelerator" and rec["value"] == -1.0
