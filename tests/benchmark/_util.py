"""Shared by the benchmark's tests: where things are, and one in-process run."""

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def rehearse(capsys, workload, trace, seed=5, seconds=1.0, extra=()):
    """run.py --rehearse in this process -> (exit code, last-line dict or
    None, all of stdout)."""
    import run as bench_run

    capsys.readouterr()
    code = bench_run.main(["--workload", workload, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace),
                           *extra])
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.strip()]
    last = None
    if lines and lines[-1].startswith("{"):
        last = json.loads(lines[-1])
    return code, last, out


def run_in_copy(tmp_path, manifest_edit, args, devices=None, timeout=900):
    """Copy the benchmark beside an edited BENCHMARK.json into `tmp_path`
    and run its run.py there in a new process: how a later PR's added files
    and appended entries are tried without editing a file that is there.
    -> (CompletedProcess, last-line dict or None)."""
    import shutil
    import subprocess

    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", ".jax_cache",
                                                  "__pycache__"))
    m = manifest()
    manifest_edit(m, tmp_path / "benchmark")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if devices:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"), *args],
        cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=timeout)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    last = json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None
    return proc, last
