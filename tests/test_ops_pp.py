"""Patch-parallel ops vs their dense oracles on the fake 8-device mesh.

The tests the reference never had (SURVEY.md §4): each distributed op, run
under shard_map in sync phase, must reproduce the dense op on the full image
exactly (up to reduction order); stale-phase semantics are checked against
hand-computed displaced values.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from distrifuser_tpu.ops import (
    attention,
    conv2d,
    group_norm,
    patch_conv2d,
    patch_self_attention,
    patch_group_norm,
    sliced_conv2d,
)
from distrifuser_tpu.parallel.context import PHASE_STALE, PHASE_SYNC, PatchContext
from distrifuser_tpu.utils.config import SP_AXIS


def sp_mesh(devices, n):
    return Mesh(np.array(devices[:n]).reshape(n), axis_names=(SP_AXIS,))


def conv_params(key, kh, kw, cin, cout):
    k1, k2 = jax.random.split(key)
    return {
        "kernel": jax.random.normal(k1, (kh, kw, cin, cout), jnp.float32) * 0.2,
        "bias": jax.random.normal(k2, (cout,), jnp.float32) * 0.1,
    }


def run_patch_op(mesh, fn, x, state=None, n=None, mode="corrected_async_gn", phase=PHASE_SYNC):
    """Run `fn(x_local, ctx) -> y_local` under shard_map, returning (y, state_out)."""
    n = n or mesh.shape[SP_AXIS]

    def wrapped(xl, st):
        ctx = PatchContext(n=n, mode=mode, phase=phase, state_in=st)
        y = fn(xl, ctx)
        return y, ctx.state_out

    state_specs = None if state is None else jax.tree.map(lambda _: P(), state)
    return jax.jit(
        shard_map(
            wrapped,
            mesh=mesh,
            in_specs=(P(None, SP_AXIS), state_specs),
            out_specs=(P(None, SP_AXIS), jax.tree.map(lambda _: P(), state)
                       if state is not None else P()),
            check_vma=False,
        )
    )(x, state)


@pytest.mark.parametrize("n,stride,k", [(4, 1, 3), (4, 2, 3), (2, 1, 5), (8, 2, 3)])
def test_halo_conv_sync_matches_dense(devices8, n, stride, k):
    mesh = sp_mesh(devices8, n)
    key = jax.random.PRNGKey(0)
    b, h, w, cin, cout = 2, 16 * n // 2 * stride, 12, 3, 5
    # ensure h divisible by stride*n
    h = stride * n * 4
    x = jax.random.normal(key, (b, h, w, cin))
    p = conv_params(jax.random.PRNGKey(1), k, k, cin, cout)
    dense = conv2d(p, x, stride=stride)

    def fn(xl, ctx):
        return patch_conv2d(p, xl, ctx, "conv", stride=stride)

    def wrapped(xl):
        ctx = PatchContext(n=n, mode="full_sync", phase=PHASE_SYNC)
        return fn(xl, ctx)

    y = jax.jit(
        shard_map(wrapped, mesh=mesh, in_specs=P(None, SP_AXIS), out_specs=P(None, SP_AXIS))
    )(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense), atol=1e-5)


@pytest.mark.parametrize("stride", [1, 2])
def test_sliced_conv_matches_dense(devices8, stride):
    n = 4
    mesh = sp_mesh(devices8, n)
    b, h, w, cin, cout = 1, stride * n * 4, 10, 4, 6
    x = jax.random.normal(jax.random.PRNGKey(2), (b, h, w, cin))
    p = conv_params(jax.random.PRNGKey(3), 3, 3, cin, cout)
    dense = conv2d(p, x, stride=stride)

    def wrapped(xf):
        ctx = PatchContext(n=n, mode="full_sync", phase=PHASE_SYNC)
        return sliced_conv2d(p, xf, ctx, stride=stride)

    y = jax.jit(
        shard_map(
            wrapped, mesh=mesh, in_specs=P(), out_specs=P(None, SP_AXIS), check_vma=False
        )
    )(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense), atol=1e-5)


def test_halo_conv_stale_uses_previous_step(devices8):
    """Displaced semantics: step 2's conv must see step 1's neighbor rows."""
    n = 4
    mesh = sp_mesh(devices8, n)
    b, h, w, c = 1, 4 * n, 6, 2
    x1 = jax.random.normal(jax.random.PRNGKey(4), (b, h, w, c))
    x2 = jax.random.normal(jax.random.PRNGKey(5), (b, h, w, c))
    p = conv_params(jax.random.PRNGKey(6), 3, 3, c, c)

    def fn(xl, ctx):
        return patch_conv2d(p, xl, ctx, "conv")

    y1, state = run_patch_op(mesh, fn, x1, phase=PHASE_SYNC)
    y2, _ = run_patch_op(mesh, fn, x2, state=state, phase=PHASE_STALE)

    # Dense oracle for the stale step: each patch row-block convolved with
    # x2's interior but x1's rows at the patch boundaries.
    hp = h // n
    x2n, x1n = np.asarray(x2), np.asarray(x1)
    got = np.asarray(y2)
    for i in range(n):
        lo, hi = i * hp, (i + 1) * hp
        top = x1n[:, lo - 1 : lo] if i > 0 else np.zeros((b, 1, w, c), np.float32)
        bottom = x1n[:, hi : hi + 1] if i < n - 1 else np.zeros((b, 1, w, c), np.float32)
        padded = np.concatenate([top, x2n[:, lo:hi], bottom], axis=1)
        want = np.asarray(
            conv2d(p, jnp.asarray(padded), stride=1, padding=(0, 1))
        )
        np.testing.assert_allclose(got[:, lo:hi], want, atol=1e-5)


@pytest.mark.parametrize("mode", ["full_sync", "sync_gn", "stale_gn", "corrected_async_gn", "separate_gn", "no_sync"])
def test_group_norm_sync_phase_matches_global_moments(devices8, mode):
    """In the sync (warmup) phase every mode must use global moments + local-ne
    Bessel (groupnorm.py:45-47,74-91)."""
    n, b, h, w, c, g = 4, 2, 8, 6, 8, 4
    mesh = sp_mesh(devices8, n)
    x = jax.random.normal(jax.random.PRNGKey(7), (b, h * n, w, c)) * 2 + 1
    p = {
        "scale": jax.random.normal(jax.random.PRNGKey(8), (c,)) + 1,
        "bias": jax.random.normal(jax.random.PRNGKey(9), (c,)),
    }

    def fn(xl, ctx):
        return patch_group_norm(p, xl, ctx, "gn", groups=g)

    y, _ = run_patch_op(mesh, fn, x, mode=mode, phase=PHASE_SYNC)

    # dense oracle: global moments, Bessel with local ne
    xn = np.asarray(x, np.float64).reshape(b, n * h, w, g, c // g)
    mean = xn.mean(axis=(1, 2, 4), keepdims=True)
    var = (xn**2).mean(axis=(1, 2, 4), keepdims=True) - mean**2
    ne = (c // g) * h * w
    var = var * ne / (ne - 1)
    want = (xn - mean) / np.sqrt(var + 1e-5)
    want = want.reshape(b, n * h, w, c) * np.asarray(p["scale"]) + np.asarray(p["bias"])
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-4)


def test_group_norm_separate_steady_is_local(devices8):
    n, b, h, w, c, g = 4, 1, 6, 4, 4, 2
    mesh = sp_mesh(devices8, n)
    x = jax.random.normal(jax.random.PRNGKey(10), (b, h * n, w, c))
    p = {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}

    def fn(xl, ctx):
        return patch_group_norm(p, xl, ctx, "gn", groups=g)

    y, _ = run_patch_op(mesh, fn, x, mode="separate_gn", phase=PHASE_STALE)
    # oracle: plain (biased) GN applied per local patch
    want = np.concatenate(
        [
            np.asarray(group_norm(p, x[:, i * h : (i + 1) * h], groups=g))
            for i in range(n)
        ],
        axis=1,
    )
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-4)


def test_group_norm_stale_modes_displaced_semantics(devices8):
    """stale_gn: mean = (stale peers + fresh self)/n; corrected_async_gn adds the
    un-normalized correction and clamps negative variance to local."""
    n, b, h, w, c, g = 2, 1, 4, 4, 4, 2
    mesh = sp_mesh(devices8, n)
    x1 = jax.random.normal(jax.random.PRNGKey(11), (b, h * n, w, c))
    x2 = jax.random.normal(jax.random.PRNGKey(12), (b, h * n, w, c)) * 1.5

    p = None  # no affine

    def fn(xl, ctx):
        return patch_group_norm(p, xl, ctx, "gn", groups=g)

    def moments(xp):  # [2, B, G] for one patch
        xg = np.asarray(xp, np.float64).reshape(b, h, w, g, c // g)
        return np.stack([xg.mean(axis=(1, 2, 4)), (xg**2).mean(axis=(1, 2, 4))])

    for mode in ["stale_gn", "corrected_async_gn"]:
        _, state = run_patch_op(mesh, fn, x1, mode=mode, phase=PHASE_SYNC)
        y2, state2 = run_patch_op(mesh, fn, x2, state=state, mode=mode, phase=PHASE_STALE)

        ne = (c // g) * h * w
        got = np.asarray(y2)
        for i in range(n):
            m_fresh = moments(np.asarray(x2)[:, i * h : (i + 1) * h])
            stale_all = [moments(np.asarray(x1)[:, j * h : (j + 1) * h]) for j in range(n)]
            if mode == "stale_gn":
                full = (sum(stale_all) - stale_all[i] + m_fresh) / n
            else:
                full = sum(stale_all) / n + (m_fresh - stale_all[i])
            var = full[1] - full[0] ** 2
            if mode == "corrected_async_gn":
                lvar = m_fresh[1] - m_fresh[0] ** 2
                var = np.where(var < 0, lvar, var)
            var = var * ne / (ne - 1)
            xg = np.asarray(x2, np.float64)[:, i * h : (i + 1) * h].reshape(
                b, h, w, g, c // g
            )
            want = (xg - full[0][:, None, None, :, None]) / np.sqrt(
                var[:, None, None, :, None] + 1e-5
            )
            np.testing.assert_allclose(
                got[:, i * h : (i + 1) * h],
                want.reshape(b, h, w, c),
                atol=1e-4,
            )
        # refreshed state must hold x2's gathered moments
        want_state = np.stack([moments(np.asarray(x2)[:, j * h : (j + 1) * h]) for j in range(n)])
        np.testing.assert_allclose(np.asarray(state2["gn"]), want_state, atol=1e-5)


def test_patch_attention_sync_matches_dense(devices8):
    n, b, l, c, heads = 4, 2, 6, 8, 2
    mesh = sp_mesh(devices8, n)
    x = jax.random.normal(jax.random.PRNGKey(13), (b, l * n, c))
    keys = jax.random.split(jax.random.PRNGKey(14), 4)
    p = {
        "to_q": {"kernel": jax.random.normal(keys[0], (c, c)) * 0.3},
        "to_kv": {"kernel": jax.random.normal(keys[1], (c, 2 * c)) * 0.3},
        "to_out": {
            "kernel": jax.random.normal(keys[2], (c, c)) * 0.3,
            "bias": jax.random.normal(keys[3], (c,)) * 0.1,
        },
    }
    dense = attention(p, x, heads=heads)

    def wrapped(xl):
        ctx = PatchContext(n=n, mode="full_sync", phase=PHASE_SYNC)
        return patch_self_attention(p, xl, ctx, "attn", heads=heads)

    y = jax.jit(
        shard_map(wrapped, mesh=mesh, in_specs=P(None, SP_AXIS), out_specs=P(None, SP_AXIS))
    )(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense), atol=1e-4)


def test_patch_attention_stale_kv(devices8):
    """Steady state: KV = fresh local slot + stale peer slots (attn.py:135-138)."""
    n, b, l, c, heads = 2, 1, 4, 4, 1
    mesh = sp_mesh(devices8, n)
    x1 = jax.random.normal(jax.random.PRNGKey(15), (b, l * n, c))
    x2 = jax.random.normal(jax.random.PRNGKey(16), (b, l * n, c))
    keys = jax.random.split(jax.random.PRNGKey(17), 3)
    p = {
        "to_q": {"kernel": jax.random.normal(keys[0], (c, c)) * 0.4},
        "to_kv": {"kernel": jax.random.normal(keys[1], (c, 2 * c)) * 0.4},
        "to_out": {"kernel": jax.random.normal(keys[2], (c, c)) * 0.4},
    }

    def fn(xl, ctx):
        return patch_self_attention(p, xl, ctx, "attn", heads=heads)

    def run(x, state, phase):
        def wrapped(xl, st):
            ctx = PatchContext(n=n, mode="corrected_async_gn", phase=phase, state_in=st)
            y = fn(xl, ctx)
            return y, ctx.state_out

        return jax.jit(
            shard_map(
                wrapped,
                mesh=mesh,
                in_specs=(P(None, SP_AXIS), None if state is None else jax.tree.map(lambda _: P(), state)),
                out_specs=(P(None, SP_AXIS), jax.tree.map(lambda _: P(), state) if state is not None else P()),
                check_vma=False,
            )
        )(x, state)

    _, state = run(x1, None, PHASE_SYNC)
    y2, state2 = run(x2, state, PHASE_STALE)

    # oracle: per patch i, kv rows of x2 for patch i, x1 for others
    from distrifuser_tpu.ops.linear import linear as jlin
    from distrifuser_tpu.ops.attention import sdpa as jsdpa, split_kv

    kv1 = np.asarray(jlin(p["to_kv"], x1))
    kv2 = np.asarray(jlin(p["to_kv"], x2))
    q2 = jlin(p["to_q"], x2)
    got = np.asarray(y2)
    for i in range(n):
        kv_mix = kv1.copy()
        kv_mix[:, i * l : (i + 1) * l] = kv2[:, i * l : (i + 1) * l]
        k, v = split_kv(jnp.asarray(kv_mix))
        out = jsdpa(q2[:, i * l : (i + 1) * l], k, v, heads=heads)
        want = np.asarray(jlin(p["to_out"], out))
        np.testing.assert_allclose(got[:, i * l : (i + 1) * l], want, atol=1e-4)
    # refreshed state holds x2's gathered kv
    want_state = np.stack([kv2[:, j * l : (j + 1) * l] for j in range(n)])
    np.testing.assert_allclose(np.asarray(state2["attn"]), want_state, atol=1e-5)


# CPU-compile-heavy module: the fake 8-device mesh compiles full
# multi-device denoise loops, minutes per test on the tier-1 CPU runner.
# Runs with `-m slow` and on real-hardware rounds.
pytestmark = pytest.mark.slow
