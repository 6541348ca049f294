"""BENCHMARK.json against the files it names, and the promise that a later
cell, configuration or per-layer metric is new files plus appended entries."""

import importlib
import json
import os
import shutil

import pytest
from _util import BENCH, NAME, ROOT, UNIT, manifest, run_in_copy

M = manifest()


def test_keys_and_names():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in M[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    for m in M["end_to_end"] + M["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in M["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in M["end_to_end"]}
    assert len(json.dumps(M)) < 64 * 1024


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda c: c["name"])
def test_cell_files_are_found_by_name(cell):
    configs = {c["name"]: c for c in M["configs"]}
    cfg_entry = configs[cell["config"]]
    assert cfg_entry["file"] == f"benchmark/configs/{cell['config']}.json"
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    assert config["reduced"] == cfg_entry["reduced"]
    assert config["source"] == cfg_entry["source"]
    for part in ("traffic/" + cell["traffic"], "limits/" + cell["name"]):
        assert os.path.exists(os.path.join(BENCH, part + ".json")), part
    family = importlib.import_module(f"benchmark.families.{config['family']}")
    reference = importlib.import_module(
        f"benchmark.reference.{family.REFERENCE}")
    assert hasattr(family, "Family") and hasattr(reference, "Reference")
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200


def test_every_config_is_used_and_four_chip_cells_are_capped():
    used = {c["config"] for c in M["workloads"]}
    assert used == {c["name"] for c in M["configs"]}
    pairs = [(c["config"], c["traffic"]) for c in M["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(c["chips"] == 4 for c in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 4)


@pytest.mark.parametrize("metric", M["per_layer"], ids=lambda m: m["name"])
def test_layer_metric_has_a_reader_and_moves_a_reported_metric(metric):
    with open(os.path.join(BENCH, "layer_metrics",
                           metric["name"] + ".json")) as f:
        spec = json.load(f)
    for key in ("layer", "unit", "better", "source", "moves"):
        assert spec[key] == metric[key], key
    assert spec.get("workloads") == metric.get("workloads")
    module, func = spec["reader"].split(":")
    assert callable(getattr(importlib.import_module(f"benchmark.{module}"),
                            func))
    e2e = {m["name"]: m for m in M["end_to_end"]}
    moved = e2e[metric["moves"]]
    cells = [c["name"] for c in M["workloads"]]
    for cell in metric.get("workloads", cells):
        assert cell in cells
        assert cell in moved.get("workloads", cells)


def test_a_cell_added_as_files_only_is_picked_up(tmp_path):
    """ADD a configuration, a traffic mix, a limits file and a per-layer
    metric, APPEND their manifest entries, and run the new cell: no file
    that was there is edited."""

    def add(m, b):
        config = json.loads((b / "configs" / "sdxl-base-1.0.json").read_text())
        config["name"] = "fixture-unet"
        (b / "configs" / "fixture-unet.json").write_text(json.dumps(config))
        traffic = json.loads((b / "traffic" / "solo-1024.json").read_text())
        traffic["arrivals"] = {"kind": "open", "rate_per_s": 8.0,
                               "jitter": 0.5}
        # the step-mode server of `sdxl-1024-stepserve` (PERF.md section 7)
        traffic["serve"] = {"step_batching": {"slots": 2},
                            "program_batch_rows": 2}
        (b / "traffic" / "fixture-open.json").write_text(json.dumps(traffic))
        shutil.copy(b / "limits" / "sdxl-1024-solo.json",
                    b / "limits" / "fixture-cell.json")
        spec = json.loads(
            (b / "layer_metrics" / "queue_wait_ms.json").read_text())
        spec.update(name="fixture_queue_ms", workloads=["fixture-cell"])
        (b / "layer_metrics" / "fixture_queue_ms.json").write_text(
            json.dumps(spec))
        m["configs"].append({
            "name": "fixture-unet", "source": config["source"],
            "file": "benchmark/configs/fixture-unet.json", "reduced": [],
            "why": "fixture"})
        m["workloads"].append({
            "name": "fixture-cell", "config": "fixture-unet",
            "traffic": "fixture-open", "chips": 1, "why": "fixture"})
        m["per_layer"].append({
            "name": "fixture_queue_ms", "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "serve plane",
            "moves": "image_s", "workloads": ["fixture-cell"]})

    proc, last = run_in_copy(tmp_path, add, [
        "--workload", "fixture-cell", "--seed", "9", "--seconds", "1",
        "--trace", "1", "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert last["correct"] is True and last["failed"] == 0
    assert "fixture_queue_ms" in last["metrics"]
    assert "collective_exposed_share" not in last["metrics"]
