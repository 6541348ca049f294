"""chip_smoke.py's contract, as far as a CPU can check it: without a chip it
fails and prints no result; `--rehearse` — asked for, never a default — runs
every leg at the tiny configs, says so in its report line, and ends with
the two-key verdict line."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, timeout):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # one CPU device, and XLA's cheapest CPU codegen: the rehearsal compiles
    # a few hundred tiny programs and runs each for milliseconds
    env["XLA_FLAGS"] = ("--xla_backend_optimization_level=0 "
                        "--xla_llvm_disable_expensive_passes=true")
    return subprocess.run([sys.executable, SMOKE, *args], capture_output=True,
                          text=True, timeout=timeout, env=env, cwd=REPO)


def test_without_the_flag_a_cpu_only_box_fails_and_prints_no_result():
    r = _run([], timeout=120)
    assert r.returncode not in (0, None), r.stderr[-500:]
    assert not [l for l in r.stdout.splitlines() if l.startswith("{")]
    assert "no accelerator" in r.stderr


def test_rehearse_runs_every_leg_and_says_it_is_a_rehearsal():
    r = _run(["--rehearse"], timeout=600)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    lines = [l for l in r.stdout.splitlines() if l.startswith("{")]
    # the report, then the verdict the driver parses as the last line:
    # exactly "ok" and "device" {"platform", "kind", "count"}
    assert len(lines) == 2 and lines[1] == r.stdout.splitlines()[-1]
    rec, verdict = map(json.loads, lines)
    assert verdict == {"ok": True, "device": rec["device"]}
    assert list(verdict["device"]) == ["platform", "kind", "count"]
    assert rec["ok"] is True and rec["rehearsal"] is True
    assert rec["claim"] is None and list(rec)[-1] == "claim"
    assert rec["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert rec["kernels_interpreted"] is True
    # two levels x (whole, local Lq), upstream, padded, the expert gather
    assert len(rec["kernels_compiled"]) == 7
    assert all("flash" in k or k.startswith("expert_gather_matvec")
               for k in rec["kernels_compiled"])
    for mode in ("fused", "step"):
        assert rec["serve"][mode]["requests"] == 4
        assert rec["serve"][mode]["compiles_at_build"] > 0
        assert rec["serve"][mode]["recompiles_on_request_path"] == 0
        assert rec["serve"][mode]["repeated_seed_byte_identical"] is True
    # a CPU time is never written under the name of a device metric
    assert "warm_s_per_image" not in rec["serve"]["fused"]
    assert "wall_s" not in rec and "compile_s_by_program" not in rec
