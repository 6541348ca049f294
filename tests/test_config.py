"""DistriConfig / mesh bootstrap tests.

Checks the rank-topology parity with the reference
(/root/reference/distrifuser/utils.py:68-109): CFG split halves the patch
axis, batch_idx/split_idx mapping, power-of-2 assertion, and latent geometry.
"""

import jax
import pytest

from distrifuser_tpu import DistriConfig
from distrifuser_tpu.utils.config import CFG_AXIS, DP_AXIS, SP_AXIS


def make_config(devices, **kw):
    kw.setdefault("use_cuda_graph", False)
    return DistriConfig(devices=devices, **kw)


def test_cfg_split_topology(devices8):
    cfg = make_config(devices8)
    assert cfg.world_size == 8
    assert cfg.n_device_per_batch == 4
    assert cfg.mesh.shape == {DP_AXIS: 1, CFG_AXIS: 2, SP_AXIS: 4}
    # reference utils.py:98-109: ranks [0, n) are CFG branch 0, [n, 2n) branch 1
    assert [cfg.batch_idx(r) for r in range(8)] == [0, 0, 0, 0, 1, 1, 1, 1]
    assert [cfg.split_idx(r) for r in range(8)] == [0, 1, 2, 3, 0, 1, 2, 3]
    # mesh device order matches that rank layout
    flat = list(cfg.mesh.devices.flat)
    assert flat == list(devices8)


def test_no_cfg_split(devices8):
    cfg = make_config(devices8, do_classifier_free_guidance=False)
    assert cfg.n_device_per_batch == 8
    assert cfg.mesh.shape == {DP_AXIS: 1, CFG_AXIS: 1, SP_AXIS: 8}
    assert cfg.batch_idx(5) == 0

    cfg2 = make_config(devices8, split_batch=False)
    assert cfg2.n_device_per_batch == 8


def test_single_device():
    cfg = make_config([jax.devices()[0]])
    assert cfg.world_size == 1
    assert cfg.n_device_per_batch == 1
    assert cfg.mesh.shape == {DP_AXIS: 1, CFG_AXIS: 1, SP_AXIS: 1}


def test_power_of_two_asserted(devices8):
    with pytest.raises(AssertionError):
        make_config(devices8[:3])


def test_validation(devices8):
    with pytest.raises(ValueError):
        make_config(devices8, mode="bogus")
    with pytest.raises(ValueError):
        make_config(devices8, parallelism="bogus")
    with pytest.raises(ValueError):
        make_config(devices8, split_scheme="bogus")
    with pytest.raises(ValueError):
        make_config(devices8, height=1001)  # not a multiple of 8


def test_latent_geometry(devices8):
    cfg = make_config(devices8, height=1024, width=1024)
    assert cfg.latent_height == 128 and cfg.latent_width == 128
    assert cfg.patch_height() == 32  # 128 rows / 4 sp devices
    assert cfg.patch_height(scale=4) == 8


def test_tpu_backend_defaults_to_bfloat16(monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = DistriConfig(devices=jax.devices()[:1], use_cuda_graph=False)
    assert cfg.dtype == jnp.bfloat16


def test_default_backend_propagates_backend_init_error(monkeypatch):
    """A TPU that fails to initialise must not read as "cpu" (that turned
    the default dtype float32): the error reaches the caller."""
    from distrifuser_tpu.utils import env

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", boom)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        env.default_backend()
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        DistriConfig(devices=jax.devices()[:1], use_cuda_graph=False)


def test_pipefusion_accepts_first_class_knobs(devices8):
    """PR 7 (ROADMAP item 2): the knobs DistriConfig used to reject for
    parallelism='pipefusion' — comm_compress, weight_quant, the step
    cache, and the new pipe_patches — all construct; the step cache still
    pairs its knobs, and weight_quant still rejects tensor parallelism."""
    cfg = DistriConfig(
        devices=devices8[:2], height=128, width=128,
        parallelism="pipefusion", comm_compress="int8_residual",
        step_cache_interval=2, step_cache_depth=1, weight_quant="int8",
        pipe_patches=4, use_cuda_graph=True,
    )
    assert cfg.step_cache_enabled and cfg.pipe_patches == 4


def test_pipe_patches_validation(devices8):
    with pytest.raises(ValueError, match="pipe_patches"):
        make_config(devices8[:2], pipe_patches=2)  # patch parallelism
    with pytest.raises(ValueError, match="pipe_patches"):
        make_config(devices8[:2], parallelism="pipefusion", pipe_patches=0)
