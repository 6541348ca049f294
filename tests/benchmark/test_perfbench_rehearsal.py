"""`run.py --rehearse` on the CPU for every cell: the last line's contract,
failure (not fallback) without the flag, and a broken timed path reported as
not correct."""

import argparse
import json
import re

import pytest
from _util import NAME, UNIT, manifest, rehearse, run_in_copy

M = manifest()
CELLS = [c["name"] for c in M["workloads"]]
# what a CPU trace and CPU clocks can yield; utilisation against a chip's
# peak, Mosaic kernel times and HBM readings exist only on the chip
CPU_READABLE = {"loadgen_late_ms", "queue_wait_ms", "serve_overhead_ms",
                "image_tail_s", "images_per_s", "nondenoise_ms", "step_ms",
                "device_idle_share"}
# read only on the chip: a share of its peaks, Mosaic kernels, its memory
CHIP_ONLY = {"step_flop_util", "attn_ms_per_step", "attn_roofline",
             "peak_hbm_gb"}


def listed(kind, cell):
    return {m["name"]: m for m in M[kind]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contracted_last_line(capsys, cell, trace):
    code, last, out = rehearse(capsys, cell, trace, extra=["--rehearse"])
    assert code == 0, out[-3000:]
    want = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(last) == (want | {"breakdown"} if trace else want)
    assert last["correct"] is True, out[-3000:]
    assert last["attempted"] >= 1 and last["failed"] == 0
    dev = last["device"]
    assert dev["platform"] == "cpu" and {"kind", "count",
                                         "memory_peak_bytes"} <= set(dev)
    names = listed("per_layer" if trace else "end_to_end", cell)
    for name, val in last["metrics"].items():
        assert name in names and NAME.match(name)
        assert val["unit"] == names[name]["unit"] and UNIT.match(val["unit"])
        assert isinstance(val["value"], float)
    if trace:
        assert dev["busy_s"] > 0 and dev["window_s"] >= dev["busy_s"]
        # a listed metric that a traced run leaves out is refused by the check
        assert set(names) - CHIP_ONLY <= set(last["metrics"])
        assert CPU_READABLE <= set(last["metrics"])
        for part in ("device_ops", "idle_gaps"):
            assert 1 <= len(last["breakdown"][part]) <= 10
    else:
        assert set(last["metrics"]) == set(names)
        assert all(v["value"] > 0 for v in last["metrics"].values())


def test_without_the_flag_there_is_no_cpu_fallback(capsys):
    code, last, out = rehearse(capsys, CELLS[0], 0)
    assert code == 3 and last is None and "{" not in out


PATCH4 = {"name": "sdxl-1024-patch4", "config": "sdxl-base-1.0",
          "traffic": "solo-1024-patch4", "chips": 4,
          "why": "cell 1's traffic over dp1 x cfg2 x sp2"}
PATCH4_DISTRI = {"mode": "corrected_async_gn", "warmup_steps": 4,
                 "parallelism": "patch", "vae_sp": True}
COLLECTIVES = {"name": "collective_exposed_share", "unit": "%",
               "better": "lower", "source": "device_trace",
               "layer": "collectives", "moves": "image_s",
               "workloads": [PATCH4["name"]]}


def test_the_four_chip_cell_runs_on_four_virtual_devices(tmp_path):
    """`sdxl-1024-patch4` (PERF.md section 7: not in the tree yet) added the
    way its own PR will, as files and appended entries: displaced patch
    parallelism over dp1 x cfg2 x sp2 against the one-device float32
    reference, and the collectives read from the trace."""

    def add(m, b):
        traffic = json.loads((b / "traffic" / "solo-1024.json").read_text())
        traffic["distri"] = PATCH4_DISTRI
        (b / "traffic" / "solo-1024-patch4.json").write_text(
            json.dumps(traffic))
        # displaced patches are not the one-device computation: per-patch
        # GroupNorm moments and one-step-stale halos read 0.002 at this size
        (b / "limits" / "sdxl-1024-patch4.json").write_text(json.dumps(
            {"image_rel_rmse": {"limit": 0.05}}))
        (b / "layer_metrics" / "collective_exposed_share.json").write_text(
            json.dumps(dict(
                COLLECTIVES,
                reader="harness.readers:collective_exposed_share")))
        m["workloads"].append(PATCH4)
        m["per_layer"].append(COLLECTIVES)

    proc, last = run_in_copy(tmp_path, add, [
        "--workload", PATCH4["name"], "--seed", "6", "--seconds", "1",
        "--trace", "1", "--rehearse"], devices=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert last["correct"] is True, proc.stdout[-3000:]
    assert last["device"]["count"] == 4
    assert CPU_READABLE <= set(last["metrics"])
    assert 0.0 <= last["metrics"]["collective_exposed_share"]["value"] <= 100.0


@pytest.mark.parametrize("cell", [CELLS[0], CELLS[1]])
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch, cell):
    """The rest of a run with the timed path broken underneath: the VAE
    decode tail every served image passes through shifts its output."""
    from distrifuser_tpu import pipelines

    real = pipelines._GenerationMixin._decode_to_np

    def shifted(self, latent):
        img = real(self, latent)
        return (img * 0.9 + 0.05).astype(img.dtype)

    monkeypatch.setattr(pipelines._GenerationMixin, "_decode_to_np", shifted)
    code, last, out = rehearse(capsys, cell, 0, seed=8, extra=["--rehearse"])
    assert code == 0, out[-3000:]
    assert last["correct"] is False
    assert "image_rel_rmse" in out and "FAILED" in out


@pytest.mark.parametrize("quant", ["int8", "fp8"])
@pytest.mark.parametrize("cell", CELLS)
def test_the_programs_lower_precision_path_is_not_correct(capsys, cell, quant):
    """The control of PERF.md section 2 at a size a test holds: the cell as
    committed but for the program's own `weight_quant` switched on in its
    server (as calibrate.py does on the chip), the precision below the one
    the configuration states.  The whole run goes through and
    `image_rel_rmse`, nothing else, fails it."""
    import run as bench_run

    spec = bench_run.resolve_cell(cell, rehearse=True)
    low = {"weight_quant": quant}
    spec["traffic"] = bench_run.merged(spec["traffic"],
                                       {"distri": low, "serve": low})
    args = argparse.Namespace(workload=cell, seed=11, seconds=1.0, trace=0,
                              rehearse=True)
    capsys.readouterr()
    assert bench_run.run(args, spec) == 0
    out = capsys.readouterr().out
    last = json.loads(out.splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0
    assert f":wq-{quant}" in out  # the key the server says it ran
    failed = re.search(r"checks: \d+ made, failed: (.*)", out).group(1)
    assert re.fullmatch(r"\['image_rel_rmse\[request \d+\]'\]", failed), failed
