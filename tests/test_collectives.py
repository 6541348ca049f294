"""Collective helpers under shard_map on the fake 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from distrifuser_tpu.parallel import collectives as col
from distrifuser_tpu.utils.config import SP_AXIS


def sp_mesh(devices, n):
    return Mesh(np.array(devices[:n]).reshape(n), axis_names=(SP_AXIS,))


def test_halo_exchange_matches_neighbors(devices8):
    n, b, h, w, c, halo = 4, 1, 6, 5, 3, 2
    mesh = sp_mesh(devices8, n)
    x = jnp.arange(b * n * h * w * c, dtype=jnp.float32).reshape(b, n * h, w, c)

    def f(xl):
        fp, fn = col.halo_exchange(xl, halo, n)
        return fp, fn

    fp, fn = jax.jit(
        shard_map(f, mesh=mesh, in_specs=P(None, SP_AXIS), out_specs=P(None, SP_AXIS))
    )(x)
    fp = np.asarray(fp).reshape(n, b, halo, w, c)  # concat over sp gave n*halo rows
    fn = np.asarray(fn).reshape(n, b, halo, w, c)
    xg = np.asarray(x).reshape(b, n, h, w, c).transpose(1, 0, 2, 3, 4)
    for i in range(n):
        want_prev = xg[i - 1][:, -halo:] if i > 0 else np.zeros_like(fp[i])
        want_next = xg[i + 1][:, :halo] if i < n - 1 else np.zeros_like(fn[i])
        np.testing.assert_array_equal(fp[i], want_prev)
        np.testing.assert_array_equal(fn[i], want_next)


def test_gather_rows_roundtrip(devices8):
    n = 8
    mesh = sp_mesh(devices8, n)
    x = jnp.arange(2 * 16 * 3 * 2, dtype=jnp.float32).reshape(2, 16, 3, 2)

    out = jax.jit(
        shard_map(
            lambda xl: col.gather_rows(xl),
            mesh=mesh,
            in_specs=P(None, SP_AXIS),
            out_specs=P(None, None),  # replicated full tensor
            check_vma=False,
        )
    )(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_gather_cols_roundtrip(devices8):
    n = 4
    mesh = sp_mesh(devices8, n)
    x = jnp.arange(1 * 6 * 8 * 2, dtype=jnp.float32).reshape(1, 6, 8, 2)
    out = jax.jit(
        shard_map(
            lambda xl: col.gather_cols(xl),
            mesh=mesh,
            in_specs=P(None, None, SP_AXIS),
            out_specs=P(None, None, None),
            check_vma=False,
        )
    )(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_all_gather_seq(devices8):
    n = 4
    mesh = sp_mesh(devices8, n)
    x = jnp.arange(2 * 12 * 3, dtype=jnp.float32).reshape(2, 12, 3)
    out = jax.jit(
        shard_map(
            lambda xl: col.all_gather_seq(xl),
            mesh=mesh,
            in_specs=P(None, SP_AXIS, None),
            out_specs=P(None, None, None),
            check_vma=False,
        )
    )(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_psum_mean(devices8):
    n = 8
    mesh = sp_mesh(devices8, n)
    x = jnp.arange(n, dtype=jnp.float32).reshape(n, 1)
    out = jax.jit(
        shard_map(
            lambda xl: col.psum_mean(xl),
            mesh=mesh,
            in_specs=P(SP_AXIS, None),
            out_specs=P(SP_AXIS, None),
        )
    )(x)
    np.testing.assert_allclose(np.asarray(out), np.full((n, 1), np.mean(range(n))))


def test_ring_perm_covers_every_peer_once():
    n = 4
    perm = col.ring_perm(n)
    assert perm == [(0, 1), (1, 2), (2, 3), (3, 0)]
    # n-1 hops deliver device r-h mod n to rank r, every peer exactly once
    for r in range(n):
        seen = set()
        src = r
        for _ in range(n - 1):
            src = (src - 1) % n
            seen.add(src)
        assert seen == set(range(n)) - {r}


def test_ring_shift_rotates_one_hop(devices8):
    n = 4
    mesh = sp_mesh(devices8, n)
    x = jnp.arange(n, dtype=jnp.float32).reshape(n, 1)
    out = jax.jit(
        shard_map(
            lambda xl: col.ring_shift(xl, n),
            mesh=mesh,
            in_specs=P(SP_AXIS, None),
            out_specs=P(SP_AXIS, None),
        )
    )(x)
    # rank r receives rank r-1's value (wrap at 0)
    np.testing.assert_array_equal(
        np.asarray(out).ravel(), np.array([3.0, 0.0, 1.0, 2.0])
    )


def test_pipelined_ring_pass_permute_is_deferred(devices8):
    """FastUSP-style overlap, checked structurally: the software-pipelined
    ring_pass issues hop i+1's ppermute before merging hop i's arrival, so
    in the compiled while body the collective-permute's value reaches ONLY
    the loop carry — utils/overlap.py classifies it deferred
    (overlappable), where the serial ring's permute (consumed by the same
    iteration's score matmuls) classified inline."""
    from distrifuser_tpu.ops.ring_attention import ring_pass
    from distrifuser_tpu.utils.overlap import analyze_loop_collectives

    n, b, L, c, heads = 4, 1, 256, 64, 4
    mesh = sp_mesh(devices8, n)
    q = jnp.zeros((b, L, c))
    kv = jnp.zeros((b, L, 2 * c))
    sm = shard_map(
        lambda ql, kvl: ring_pass(ql, kvl, kvl, n, SP_AXIS, heads=heads),
        mesh=mesh,
        in_specs=(P(None, SP_AXIS), P(None, SP_AXIS)),
        out_specs=P(None, None, SP_AXIS),
    )
    hlo = jax.jit(sm).lower(q, kv).compile().as_text()
    reports = analyze_loop_collectives(hlo)
    assert reports, "ring fori_loop produced no while-body collectives"
    ring = max(reports, key=lambda r: r.n_deferred)
    assert "collective-permute" in ring.deferred.values(), (
        f"pipelined ring hop not carry-only: {ring.inline}"
    )
    assert ring.n_inline == 0, (
        f"ring while body serializes a collective against compute: "
        f"{ring.inline}"
    )
