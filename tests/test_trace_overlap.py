"""Runtime overlap evidence (scheduling level).

utils/overlap.py proves the refresh collectives are *structurally*
deferrable; these tests add runtime evidence one level up: a profiler trace
of the real displaced-patch program on the 8-device mesh, run through
scripts/analyze_trace.py, shows XLA actually executing the collectives
concurrently with compute (the reference's async-NCCL behavior,
utils.py:170-190).  CPU scheduling is not TPU scheduling — the TPU-silicon
version of this number comes from the chip campaign's trace phase — but a
serializing schedule would show up here too, so the test pins a floor.
"""

import glob
import gzip
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts"))

import analyze_trace  # noqa: E402


def test_interval_math():
    assert analyze_trace.union([(0, 10), (5, 15), (20, 30)]) == 25
    assert analyze_trace.merged([(0, 5), (3, 8), (10, 12)]) == [[0, 8], [10, 12]]
    assert analyze_trace.intersection([[0, 10]], [[5, 20]]) == 5
    assert analyze_trace.intersection([[0, 1]], [[2, 3]]) == 0


def test_analyze_synthetic_trace():
    """Two device pids; collectives half-hidden on one, fully on the other."""
    evs = [
        # device 1: fusion 0-100, all-gather 50-150 -> 50 of 100 overlapped
        {"ph": "X", "pid": 1, "tid": 1, "name": "fusion.1", "ts": 0, "dur": 100},
        {"ph": "X", "pid": 1, "tid": 2, "name": "all-gather-start.3", "ts": 50,
         "dur": 100},
        # device 2: fusion 0-100, collective-permute 10-60 -> fully overlapped
        {"ph": "X", "pid": 2, "tid": 1, "name": "fusion.9", "ts": 0, "dur": 100},
        {"ph": "X", "pid": 2, "tid": 2, "name": "collective-permute.2",
         "ts": 10, "dur": 50},
        # host lane: ignored (no XLA-looking names)
        {"ph": "X", "pid": 9, "tid": 9, "name": "HostPython", "ts": 0,
         "dur": 1000},
    ]
    rep = analyze_trace.analyze(evs)
    assert rep["n_devices"] == 2
    assert rep["n_collective_events"] == 2
    assert rep["collective_busy_us"] == 150.0
    assert rep["overlapped_us"] == 100.0
    assert rep["exposed_us"] == 50.0
    assert rep["collective_kinds"] == {"all-gather": 1, "collective-permute": 1}

def _tiny_patch_runner(devices8, **cfg_overrides):
    """Tiny-SDXL displaced-patch runner + its generate inputs (shared by the
    trace tests below — one place for the 8-patch geometry and the
    added-cond embed math)."""
    from distrifuser_tpu import DistriConfig
    from distrifuser_tpu.models import unet as unet_mod
    from distrifuser_tpu.parallel.runner import make_runner
    from distrifuser_tpu.schedulers import get_scheduler

    ucfg = unet_mod.tiny_config(sdxl=True)
    depth = len(ucfg.block_out_channels) - 1
    cfg = DistriConfig(devices=devices8, height=8 * 16 * (1 << depth),
                       width=128, warmup_steps=1, parallelism="patch",
                       **cfg_overrides)
    params = unet_mod.init_unet_params(jax.random.PRNGKey(0), ucfg)
    runner = make_runner(cfg, ucfg, params, get_scheduler("ddim"))
    lat = jnp.zeros((1, cfg.latent_height, cfg.latent_width, ucfg.in_channels))
    enc = jnp.zeros((2, 1, 7, ucfg.cross_attention_dim))
    emb = (ucfg.projection_class_embeddings_input_dim
           - 6 * ucfg.addition_time_embed_dim)
    added = {"text_embeds": jnp.zeros((2, 1, emb)),
             "time_ids": jnp.zeros((2, 1, 6))}

    def gen(steps):
        return runner.generate(lat, enc, guidance_scale=5.0,
                               num_inference_steps=steps, added_cond=added)

    return gen


@pytest.mark.slow
def test_comm_batch_reduces_collective_launches(devices8, tmp_path):
    """comm_batch=True must show up in the runtime trace as fewer collective
    launch events per generation (the reference's comm_checkpoint rationale,
    utils.py:181-190: bound launch overhead by batching the refresh
    exchanges).  Bitwise carry equivalence is pinned elsewhere
    (tests/test_comm_batch.py); this checks the launch-count claim itself."""
    counts = {}
    for batch in (False, True):
        gen = _tiny_patch_runner(devices8, comm_batch=batch)
        jax.block_until_ready(gen(4))
        d = tmp_path / f"trace_{batch}"
        with jax.profiler.trace(str(d), create_perfetto_trace=True):
            jax.block_until_ready(gen(4))
        rep = analyze_trace.analyze(
            analyze_trace.load_events(analyze_trace.find_perfetto(str(d))))
        counts[batch] = rep["n_collective_events"]
    assert counts[True] < counts[False], counts


@pytest.mark.slow
def test_real_runner_trace_overlap(devices8, tmp_path):
    """Trace the real displaced-patch generation (tiny SDXL config, 8-dev
    mesh) and require the analyzer to find its collectives executing
    concurrently with compute."""
    gen = _tiny_patch_runner(devices8)
    jax.block_until_ready(gen(3))  # compile outside the trace
    with jax.profiler.trace(str(tmp_path), create_perfetto_trace=True):
        jax.block_until_ready(gen(3))

    path = analyze_trace.find_perfetto(str(tmp_path))
    assert path is not None and "perfetto" in os.path.basename(path)
    rep = analyze_trace.analyze(analyze_trace.load_events(path))
    # the displaced-patch program has halo ppermutes + KV all-gathers
    assert rep["n_collective_events"] > 0, rep
    assert rep["collective_busy_us"] > 0
    # scheduling-level floor: XLA must not fully serialize the collectives
    assert rep["overlapped_frac"] is not None
    assert rep["overlapped_frac"] > 0.3, rep
