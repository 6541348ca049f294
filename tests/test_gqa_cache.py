"""`ops/gqa_cache.py`: the single-pass grouped-query kernel a decode sweep's
few rows take against a whole KV cache, interpreted on the CPU, against the
XLA form it stands in for (`ops/attention.py gqa_sdpa_by_query_block`) and
against a plain float32 softmax; and `cache_attention`, which routes a call
by its shape."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distrifuser_tpu.ops import gqa_cache
from distrifuser_tpu.ops.attention import gqa_sdpa_by_query_block

# 2 KV heads of 4 query heads each, a cache of 4 blocks of 16 rows
HKV, GROUP, D, MAX_LEN, BLOCK = 2, 4, 16, 64, 16


def operands(t, seed=0, max_len=MAX_LEN, d=D, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (t, HKV * GROUP, d), dtype),
            jax.random.normal(ks[1], (HKV, max_len, d), dtype),
            jax.random.normal(ks[2], (HKV, max_len, d), dtype))


def plain_softmax(q, k, v, limits):
    """float32, one query head at a time over its KV head's rows in view."""
    q, k, v = (np.asarray(a, np.float32) for a in (q, k, v))
    out = np.zeros(q.shape, np.float32)
    for i, limit in enumerate(limits):
        for h in range(q.shape[1]):
            keys, values = (a[h // GROUP, :limit + 1] for a in (k, v))
            s = keys @ q[i, h] / np.sqrt(q.shape[-1])
            w = np.exp(s - s.max())
            out[i, h] = (w / w.sum()) @ values
    return out


def interpreted(q, k, v, limits, **kw):
    out, rows = gqa_cache.streamed_gqa_attention(
        q, k, v, jnp.asarray(limits, jnp.int32), block_rows=BLOCK,
        interpret=True, **kw)
    # the interpreter's callbacks run JAX ops on another thread: nothing
    # else is dispatched before they are done
    return jax.block_until_ready(out), int(rows)


def fetched(limits, block=BLOCK, sub=BLOCK):
    """Rows `streamed_gqa_attention` fetches of each KV head: whole blocks
    up to the furthest limit, the last one ``sub`` rows a copy."""
    rows = max(limits) + 1
    whole = rows // block * block
    return whole + -(-(rows - whole) // sub) * sub


@pytest.mark.parametrize("limits", [
    pytest.param([35] * 4, id="one_pass_mid_block"),
    pytest.param([35] * 4 + [39] * 4, id="two_passes_two_limits"),
    pytest.param([47] * 4, id="limit_on_a_blocks_last_row"),
    pytest.param([48] * 4, id="limit_on_a_blocks_first_row"),
    pytest.param([47] * 4 + [51] * 4, id="a_block_ends_between_the_limits"),
    pytest.param([63] * 4, id="the_whole_cache"),
    pytest.param([3] * 4, id="inside_the_first_block"),
    pytest.param([60] * 4 + [3] * 4, id="limits_in_any_order_blocks_apart"),
    pytest.param([20, 9, 41, 33], id="a_limit_a_row"),
    pytest.param([63] * 4 + [67] * 4, id="a_limit_past_the_caches_last_row"),
])
def test_the_kernel_is_the_xla_form_over_the_rows_in_view(limits):
    """Every row beyond the furthest limit holds NaN - never written, never
    fetched: none reaches the result; the rows fetched are those the
    furthest limit implies."""
    q, k, v = operands(len(limits), seed=len(limits) + limits[0])
    lim = jnp.asarray(limits, jnp.int32)
    want = gqa_sdpa_by_query_block(q, k, v, q_positions=lim)
    beyond = jnp.arange(MAX_LEN)[None, :, None] > max(limits)
    out, rows = interpreted(q, jnp.where(beyond, jnp.nan, k),
                            jnp.where(beyond, jnp.nan, v), limits)
    assert out.shape == q.shape and out.dtype == q.dtype
    np.testing.assert_allclose(out, want, atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(out, plain_softmax(q, k, v, limits),
                               atol=2e-6, rtol=2e-6)
    assert rows == fetched([min(limit, MAX_LEN - 1) for limit in limits])


@pytest.mark.parametrize("limits, rows", [
    ([200] * 4, 256), ([255] * 4, 256), ([256] * 4, 384),
    ([256] * 4 + [260] * 4, 384), ([127] * 4, 128)])
def test_the_last_block_comes_a_copy_of_128_rows_at_a_time(limits, rows):
    """Blocks of 256 rows, as `_block_rows` cuts a cache: the last one only
    as far as the furthest limit reaches, 128 rows a copy."""
    q, k, v = operands(len(limits), seed=3, max_len=512)
    lim = jnp.asarray(limits, jnp.int32)
    beyond = jnp.arange(512)[None, :, None] > max(limits)
    out, got = gqa_cache.streamed_gqa_attention(
        q, jnp.where(beyond, jnp.nan, k), jnp.where(beyond, jnp.nan, v), lim,
        block_rows=256, interpret=True)
    jax.block_until_ready(out)
    np.testing.assert_allclose(
        out, gqa_sdpa_by_query_block(q, k, v, q_positions=lim), atol=2e-6,
        rtol=2e-6)
    assert int(got) == rows == fetched(limits, 256, 128)


@pytest.mark.parametrize("cache_dtype", ["float8_e4m3fn", "bfloat16"])
def test_a_cache_a_precision_below_is_read_in_the_queries_dtype(cache_dtype):
    """`cfg.cache_dtype`: the rows are widened after they arrive - the
    result is the XLA form's over the same rounded rows."""
    limits = [35] * 4 + [39] * 4
    q, k, v = operands(8, seed=7)
    k, v = k.astype(cache_dtype), v.astype(cache_dtype)
    want = gqa_sdpa_by_query_block(q, k, v,
                                   q_positions=jnp.asarray(limits, jnp.int32))
    out, _ = interpreted(q, k, v, limits)
    assert out.dtype == q.dtype == jnp.float32
    np.testing.assert_allclose(out, want, atol=2e-6, rtol=2e-6)
    # ... and not what the unrounded rows give
    assert np.abs(out - plain_softmax(*operands(8, seed=7), limits)).max() \
        > 1e-3


def test_blocks_that_do_not_divide_the_cache_are_refused():
    q, k, v = operands(4)
    with pytest.raises(ValueError, match="do not divide"):
        gqa_cache.streamed_gqa_attention(
            q, k, v, jnp.full((4,), 9, jnp.int32), block_rows=24,
            interpret=True)
    with pytest.raises(ValueError, match="do not divide"):  # no block at all
        gqa_cache.streamed_gqa_attention(
            q, k[:, :60], v[:, :60], jnp.full((4,), 9, jnp.int32))
    with pytest.raises(ValueError, match="query heads"):
        gqa_cache.streamed_gqa_attention(
            q[:, :7], k, v, jnp.full((4,), 9, jnp.int32), block_rows=BLOCK)


class _Device:
    def __init__(self, platform):
        self.platform = platform


@pytest.mark.parametrize("case, platform, t, rows, d, visible, kernel", [
    ("a_decode_pass", "tpu", 4, 1024, 128, 1024, True),
    ("two_passes_sharing_a_sweep", "tpu", 8, 1024, 128, 1024, True),
    ("a_prompt_over_its_own_keys", "tpu", 256, 256, 128, None, False),
    ("a_suffix_entering_a_snapshot", "tpu", 128, 1024, 128, 896, False),
    ("a_cache_no_block_divides", "tpu", 4, 1000, 128, 1000, False),
    ("heads_of_half_a_lane_row", "tpu", 4, 1024, 64, 1024, False),
    ("a_cpu", "cpu", 4, 1024, 128, 1024, False),
])
def test_a_call_is_routed_by_what_it_can_see(monkeypatch, case, platform, t,
                                             rows, d, visible, kernel):
    """Few query rows against a cache whole blocks divide, heads of whole
    lanes, a TPU: the kernel (here a stand-in that records the call - the
    real one needs the chip).  Everything else is the XLA form over the
    first ``visible`` rows, and reports no rows fetched."""
    q, k, v = operands(t, max_len=rows, d=d)
    # the last t positions of what is visible, by blocks of 4
    limits = ((visible or t) - t + jnp.arange(t)) // 4 * 4 + 3
    calls = []

    def stand_in(q, k, v, limits):
        calls.append((q.shape, k.shape, limits.shape))
        return gqa_sdpa_by_query_block(q, k, v, q_positions=limits), \
            jnp.asarray(rows, jnp.int32)

    monkeypatch.setattr(gqa_cache, "streamed_gqa_attention", stand_in)
    monkeypatch.setattr(jax, "devices", lambda *a: [_Device(platform)])
    out, fetched_rows = gqa_cache.cache_attention(q, k, v, limits=limits,
                                                  visible=visible)
    # the kernel is handed the WHOLE cache, never a slice
    assert calls == ([((t, HKV * GROUP, d), (HKV, rows, d), (t,))]
                     if kernel else [])
    assert int(fetched_rows) == (rows if kernel else 0)
    np.testing.assert_allclose(out, gqa_sdpa_by_query_block(
        q, k[:, :visible], v[:, :visible], q_positions=limits), atol=1e-6)


def test_the_default_block_is_the_largest_that_divides_the_cache():
    assert gqa_cache._block_rows(8704) == 256  # 2^9 x 17
    assert gqa_cache._block_rows(1280) == 256
    assert gqa_cache._block_rows(128 * 9) == 128
    assert gqa_cache._block_rows(128 * 7) == 128
    assert gqa_cache._block_rows(1000) == 0
