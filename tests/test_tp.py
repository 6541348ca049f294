"""Tensor parallelism vs dense oracle.

The reference's TP path was never testable (its CFG gather crashes,
distri_sdxl_unet_tp.py:160 — SURVEY.md §2.6); here TP is exact math, so the
oracle is strict: an n-way TP UNet forward must match the dense forward, with
non-divisible head counts (zero-padded shards) covered explicitly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from distrifuser_tpu import DistriConfig
from distrifuser_tpu.models.unet import (
    DenseDispatch,
    UNetConfig,
    init_unet_params,
    tiny_config,
    unet_forward,
)
from distrifuser_tpu.models.unet_tp import (
    TPDispatch,
    head_dim_table,
    prepare_tp_params,
    tp_attention,
    _shard_attn,
)
from distrifuser_tpu.ops.attention import attention
from distrifuser_tpu.parallel.runner import make_runner
from distrifuser_tpu.schedulers import get_scheduler
from distrifuser_tpu.utils.config import SP_AXIS


def sp_mesh(devices, n):
    return Mesh(np.array(devices[:n]).reshape(n), axis_names=(SP_AXIS,))


@pytest.mark.parametrize("heads,n", [(4, 4), (5, 4), (2, 8)])
def test_tp_attention_matches_dense_with_head_padding(devices8, heads, n):
    c = heads * 8  # head_dim 8
    mesh = sp_mesh(devices8, n)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    p = {
        "to_q": {"kernel": jax.random.normal(keys[0], (c, c)) * 0.3},
        "to_kv": {"kernel": jax.random.normal(keys[1], (c, 2 * c)) * 0.3},
        "to_out": {
            "kernel": jax.random.normal(keys[2], (c, c)) * 0.3,
            "bias": jax.random.normal(keys[3], (c,)) * 0.1,
        },
    }
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, c))
    dense = attention(p, x, heads=heads)

    tp_p, spec = _shard_attn(p, heads, n)
    y = jax.jit(
        shard_map(
            lambda pp, xx: tp_attention(pp, xx, head_dim=c // heads),
            mesh=mesh,
            in_specs=(spec, P()),
            out_specs=P(),
            check_vma=False,
        )
    )(tp_p, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense), atol=1e-4)


@pytest.mark.parametrize("n", [2, 4])
def test_tp_unet_matches_dense(devices8, n):
    ucfg = tiny_config(sdxl=False)
    params = init_unet_params(jax.random.PRNGKey(0), ucfg)
    mesh = sp_mesh(devices8, n)
    key = jax.random.PRNGKey(1)
    sample = jax.random.normal(key, (1, 16, 16, ucfg.in_channels))
    enc = jax.random.normal(jax.random.fold_in(key, 1), (1, 7, ucfg.cross_attention_dim))
    t = jnp.array([3.0])

    dense = unet_forward(params, ucfg, sample, t, enc, dispatch=DenseDispatch())

    tp_params, specs = prepare_tp_params(params, ucfg, n)
    head_dims = head_dim_table(ucfg)

    def fwd(pp, s, e):
        d = TPDispatch(n, head_dims)
        return unet_forward(pp, ucfg, s, t, e, dispatch=d)

    y = jax.jit(
        shard_map(
            fwd, mesh=mesh, in_specs=(specs, P(), P()), out_specs=P(), check_vma=False
        )
    )(tp_params, sample, enc)
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense), atol=2e-3)


def test_tp_runner_end_to_end(devices8):
    cfg = DistriConfig(
        devices=devices8[:4],
        height=128,
        width=128,
        parallelism="tensor",
        warmup_steps=1,
    )
    ucfg = tiny_config()
    params = init_unet_params(jax.random.PRNGKey(0), ucfg)
    runner = make_runner(cfg, ucfg, params, get_scheduler("ddim"))
    lat = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 16, 4))
    enc = jax.random.normal(jax.random.PRNGKey(3), (2, 1, 7, ucfg.cross_attention_dim))
    out = runner.generate(lat, enc, num_inference_steps=3)
    assert np.isfinite(np.asarray(out)).all()

    # oracle: single-device run of the same generation
    cfg1 = DistriConfig(
        devices=devices8[:1], height=128, width=128, parallelism="tensor",
        warmup_steps=1,
    )
    runner1 = make_runner(cfg1, ucfg, params, get_scheduler("ddim"))
    out1 = runner1.generate(lat, enc, num_inference_steps=3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out1), atol=2e-2)


def test_head_dim_table_covers_all_attn():
    ucfg = tiny_config()
    table = head_dim_table(ucfg)
    params = init_unet_params(jax.random.PRNGKey(0), ucfg)
    # every attn in the tree must be in the table
    names = []

    def walk(tree, path):
        if isinstance(tree, dict):
            for k, v in tree.items():
                if k in ("attn1", "attn2"):
                    names.append(f"{path}.{k}")
                elif isinstance(v, (dict, list)):
                    walk(v, f"{path}.{k}" if path else k)
        else:
            for i, v in enumerate(tree):
                walk(v, f"{path}.{i}")

    walk(params, "")
    assert set(names) == set(table)


# CPU-compile-heavy module: the fake 8-device mesh compiles full
# multi-device denoise loops, minutes per test on the tier-1 CPU runner.
# Runs with `-m slow` and on real-hardware rounds.
pytestmark = pytest.mark.slow
