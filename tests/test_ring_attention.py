"""Ring attention vs the gather-layout oracle (sync and displaced phases)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from distrifuser_tpu import DistriConfig
from distrifuser_tpu.models.unet import init_unet_params, tiny_config
from distrifuser_tpu.ops.attention import attention
from distrifuser_tpu.ops.ring_attention import ring_self_attention
from distrifuser_tpu.parallel.context import PHASE_STALE, PHASE_SYNC, PatchContext
from distrifuser_tpu.parallel.runner import DenoiseRunner
from distrifuser_tpu.schedulers import get_scheduler
from distrifuser_tpu.utils.config import SP_AXIS


def sp_mesh(devices, n):
    return Mesh(np.array(devices[:n]).reshape(n), axis_names=(SP_AXIS,))


def attn_params(key, c):
    keys = jax.random.split(key, 4)
    return {
        "to_q": {"kernel": jax.random.normal(keys[0], (c, c)) * 0.3},
        "to_kv": {"kernel": jax.random.normal(keys[1], (c, 2 * c)) * 0.3},
        "to_out": {
            "kernel": jax.random.normal(keys[2], (c, c)) * 0.3,
            "bias": jax.random.normal(keys[3], (c,)) * 0.1,
        },
    }


@pytest.mark.parametrize("n,heads", [(2, 2), (4, 1), (8, 4)])
def test_ring_sync_matches_dense(devices8, n, heads):
    c = heads * 8
    b, l = 2, 6
    mesh = sp_mesh(devices8, n)
    p = attn_params(jax.random.PRNGKey(0), c)
    x = jax.random.normal(jax.random.PRNGKey(1), (b, l * n, c))
    dense = attention(p, x, heads=heads)

    def f(xl):
        ctx = PatchContext(n=n, mode="full_sync", phase=PHASE_SYNC, attn_impl="ring")
        return ring_self_attention(p, xl, ctx, "attn", heads=heads)

    y = jax.jit(
        shard_map(f, mesh=mesh, in_specs=P(None, SP_AXIS), out_specs=P(None, SP_AXIS))
    )(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense), atol=2e-4)


def test_ring_stale_matches_gather_stale(devices8):
    """Displaced phase: ring must reproduce the gather layout's stale output
    with an O(L/n) state (own chunk only)."""
    from distrifuser_tpu.ops.attention import patch_self_attention

    n, heads, b, l = 4, 2, 1, 4
    c = heads * 8
    mesh = sp_mesh(devices8, n)
    p = attn_params(jax.random.PRNGKey(2), c)
    x1 = jax.random.normal(jax.random.PRNGKey(3), (b, l * n, c))
    x2 = jax.random.normal(jax.random.PRNGKey(4), (b, l * n, c))

    def run(fn_name, impl):
        def sync(xl):
            ctx = PatchContext(n=n, mode="corrected_async_gn", phase=PHASE_SYNC,
                               attn_impl=impl)
            fn = ring_self_attention if impl == "ring" else patch_self_attention
            y = fn(p, xl, ctx, "attn", heads=heads)
            return y, ctx.state_out["attn"]

        y1, st = jax.jit(
            shard_map(sync, mesh=mesh, in_specs=P(None, SP_AXIS),
                      out_specs=(P(None, SP_AXIS), P(SP_AXIS)) if impl == "ring"
                      else (P(None, SP_AXIS), P()), check_vma=False)
        )(x1)

        def stale(xl, st):
            ctx = PatchContext(n=n, mode="corrected_async_gn", phase=PHASE_STALE,
                               attn_impl=impl, state_in={"attn": st})
            fn = ring_self_attention if impl == "ring" else patch_self_attention
            return fn(p, xl, ctx, "attn", heads=heads)

        st_spec = P(SP_AXIS) if impl == "ring" else P()
        y2 = jax.jit(
            shard_map(stale, mesh=mesh, in_specs=(P(None, SP_AXIS), st_spec),
                      out_specs=P(None, SP_AXIS), check_vma=False)
        )(x2, st)
        return np.asarray(y2), st

    y_ring, st_ring = run("ring", "ring")
    y_gather, st_gather = run("gather", "gather")
    np.testing.assert_allclose(y_ring, y_gather, atol=2e-4)
    # ring state is sharded over sp (per-device = global/n); gather state is
    # the full gathered KV replicated on every device -> n x more memory
    ring_per_device = st_ring.size // n
    gather_per_device = st_gather.size
    assert gather_per_device == n * ring_per_device


def test_ring_end_to_end_runner(devices8):
    ucfg = tiny_config()
    params = init_unet_params(jax.random.PRNGKey(0), ucfg)
    out = {}
    for impl in ("gather", "ring"):
        cfg = DistriConfig(
            devices=devices8, height=128, width=128, warmup_steps=1,
            attn_impl=impl,
        )
        runner = DenoiseRunner(cfg, ucfg, params, get_scheduler("ddim"))
        lat = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 16, 4))
        enc = jax.random.normal(jax.random.PRNGKey(2), (2, 1, 7, ucfg.cross_attention_dim))
        out[impl] = np.asarray(runner.generate(lat, enc, num_inference_steps=4))
    np.testing.assert_allclose(out["ring"], out["gather"], atol=1e-3)


def test_ring_no_sync_mode_traces(devices8):
    """Regression: ring + no_sync must keep the scan carry structure stable
    (no attn-only state emission in the steady state)."""
    ucfg = tiny_config()
    params = init_unet_params(jax.random.PRNGKey(0), ucfg)
    cfg = DistriConfig(
        devices=devices8, height=128, width=128, warmup_steps=1,
        mode="no_sync", attn_impl="ring",
    )
    runner = DenoiseRunner(cfg, ucfg, params, get_scheduler("ddim"))
    lat = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 16, 4))
    enc = jax.random.normal(jax.random.PRNGKey(2), (2, 1, 7, ucfg.cross_attention_dim))
    out = runner.generate(lat, enc, num_inference_steps=4)
    assert np.isfinite(np.asarray(out)).all()


def test_attn_impl_validation(devices8):
    with pytest.raises(ValueError, match="attn_impl"):
        DistriConfig(devices=devices8, attn_impl="bogus")


# CPU-compile-heavy module: the fake 8-device mesh compiles full
# multi-device denoise loops, minutes per test on the tier-1 CPU runner.
# Runs with `-m slow` and on real-hardware rounds.
pytestmark = pytest.mark.slow
