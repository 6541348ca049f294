"""Dense GroupNorm at more than one row (PR 30): every row by its own moments,
each taken by a reduction that runs through the batch axis as well, and at one
row the parent's function itself."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from distrifuser_tpu.ops import group_norm

GROUPS = 32
# the SDXL UNet's C / 32 (320, 640, 960, 1280, 1920, 2560 channels); H * W =
# 35 is a multiple of no tile
PER_GROUP = (10, 20, 30, 40, 60, 80)
H, W = 7, 5


def parent_group_norm(p, x, *, groups, eps=1e-5):
    """`group_norm` as PR 29 left it: every row's moments in one set of
    reductions over [b, h, w, groups, c / groups], the variance centred."""
    b, h, w, c = x.shape
    xg = x.reshape(b, h, w, groups, c // groups).astype(jnp.float32)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = jnp.square(xg - mean).mean(axis=(1, 2, 4), keepdims=True)
    y = (xg - mean) * lax.rsqrt(var + eps)
    y = y.reshape(b, h, w, c).astype(x.dtype)
    if p is not None:
        y = y * p["scale"] + p["bias"]
    return y


def case(rows, c, dtype, seed=0):
    kx, ks, kb = jax.random.split(jax.random.PRNGKey(seed), 3)
    # rows of different scale and offset: a moment taken over two rows shows
    x = jax.random.normal(kx, (rows, H, W, c)) * jnp.arange(
        1, rows + 1).reshape(rows, 1, 1, 1) + jnp.arange(rows).reshape(
            rows, 1, 1, 1)
    p = {"scale": (jax.random.normal(ks, (c,)) + 1).astype(dtype),
         "bias": jax.random.normal(kb, (c,)).astype(dtype)}
    return p, x.astype(dtype)


def bf16_ulp(a):
    """One unit in the last place of bfloat16 (8 significant bits) at |a|."""
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(a), 2.0 ** -126))) - 7)


@pytest.mark.parametrize("per_group", PER_GROUP)
@pytest.mark.parametrize("rows", [2, 3, 4])
def test_float32_rows_are_the_parents_row_by_row(rows, per_group):
    p, x = case(rows, GROUPS * per_group, jnp.float32,
                seed=rows * 100 + per_group)
    got = group_norm(p, x, groups=GROUPS)
    assert got.shape == x.shape and got.dtype == x.dtype
    for i in range(rows):
        want = parent_group_norm(p, x[i:i + 1], groups=GROUPS)
        np.testing.assert_allclose(np.asarray(got[i:i + 1]), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("per_group", PER_GROUP)
@pytest.mark.parametrize("rows", [2, 3, 4])
def test_bfloat16_rows_are_the_parents_to_one_ulp(rows, per_group):
    # before the affine, whose own bfloat16 roundings would widen the ulp
    x = case(rows, GROUPS * per_group, jnp.bfloat16,
             seed=rows * 100 + per_group)[1]
    got = group_norm(None, x, groups=GROUPS)
    assert got.shape == x.shape and got.dtype == x.dtype
    for i in range(rows):
        want = np.asarray(parent_group_norm(
            None, x[i:i + 1], groups=GROUPS).astype(jnp.float32))
        have = np.asarray(got[i:i + 1].astype(jnp.float32))
        assert np.abs(want).max() <= 8
        assert (np.abs(have - want) <= bf16_ulp(want)).all()


@pytest.mark.parametrize("eps", [1e-5, 1e-6])
def test_one_row_is_the_parents_jaxpr(eps):
    p, x = case(1, 320, jnp.bfloat16)
    ours = jax.make_jaxpr(lambda p, x: group_norm(p, x, groups=GROUPS,
                                                  eps=eps))(p, x)
    theirs = jax.make_jaxpr(lambda p, x: parent_group_norm(
        p, x, groups=GROUPS, eps=eps))(p, x)
    assert str(ours) == str(theirs)


@pytest.mark.parametrize("rows", [2, 4])
def test_nothing_runs_along_part_of_the_batch_axis(rows):
    """The rule itself, in the traced program: every reduction of the norm
    runs through the batch axis, no moment is broadcast along it, and the
    rows are neither sliced apart nor concatenated."""
    p, x = case(rows, 640, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda p, x: group_norm(p, x, groups=GROUPS))(p, x)
    names = [eqn.primitive.name for eqn in jaxpr.eqns]
    sums = [eqn.params["axes"] for eqn in jaxpr.eqns
            if eqn.primitive.name == "reduce_sum"]
    assert len(sums) == 2 * rows and all(0 in axes for axes in sums), sums
    assert not {"slice", "concatenate", "dynamic_slice"} & set(names), names
    # a float32 operand that varies along the batch axis is the activation
    # itself: no [rows, 1, 1, groups, 1] moment is there to be broadcast
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if v.aval.dtype == jnp.float32 and v.aval.shape[:1] == (rows,):
                assert v.aval.shape[1:3] == (H, W), eqn


def test_a_constant_row_has_no_negative_variance():
    """E[x^2] - mean^2 of a constant rounds to either side of zero: floored,
    the row comes out as its bias and not as NaN."""
    c = 320
    x = jnp.stack([jnp.full((H, W, c), 1000.25), jnp.full((H, W, c), -3.1),
                   jax.random.normal(jax.random.PRNGKey(0), (H, W, c))])
    p = {"scale": jnp.full((c,), 2.0), "bias": jnp.arange(c, dtype=jnp.float32)}
    got = np.asarray(group_norm(p, x, groups=GROUPS, eps=1e-6))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[:2], np.broadcast_to(
        np.arange(c, dtype=np.float32), (2, H, W, c)), atol=0.5)
    np.testing.assert_allclose(
        got[2:], np.asarray(parent_group_norm(p, x[2:], groups=GROUPS,
                                              eps=1e-6)), rtol=1e-5, atol=1e-5)


def test_a_mean_of_ten_deviations_holds():
    """Where the one-read variance loses digits: at a mean of 10 to 20
    standard deviations E[x^2] - mean^2 keeps four to five of float32's seven
    and the result holds to 2e-3, half of what bfloat16 resolves."""
    p, x = case(2, 640, jnp.float32, seed=7)
    x = x + 20.0 * jnp.arange(1, 3).reshape(2, 1, 1, 1)
    got = group_norm(p, x, groups=GROUPS)
    for i in range(2):
        np.testing.assert_allclose(
            np.asarray(got[i:i + 1]),
            np.asarray(parent_group_norm(p, x[i:i + 1], groups=GROUPS)),
            rtol=2e-3, atol=2e-3)


def test_without_an_affine_and_with_no_row():
    x = case(3, 320, jnp.float32)[1]
    got = group_norm(None, x, groups=GROUPS)
    for i in range(3):
        np.testing.assert_allclose(
            np.asarray(got[i:i + 1]),
            np.asarray(group_norm(None, x[i:i + 1], groups=GROUPS)),
            rtol=1e-5, atol=1e-5)
    assert group_norm(None, x[:0], groups=GROUPS).shape == (0, H, W, 320)
