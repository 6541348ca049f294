"""`ops/moe.py`'s router under both scorings and its gather kernel with
several rows that share experts; `ops/attention.py`'s grouped-query
attention by query block against the whole-logits form under both
visibility rules."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from distrifuser_tpu.ops import moe
from distrifuser_tpu.ops.attention import (
    causal_gqa_sdpa,
    gqa_sdpa_by_query_block,
)

F32 = jnp.float32


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1.0), (
        np.abs(a - b).max(), np.abs(b).max())


def router_inputs(dtype, t=24, d=64, e=32):
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    return (jax.random.normal(keys[0], (t, d)).astype(dtype),
            (jax.random.normal(keys[1], (d, e)) * 0.2).astype(dtype),
            (jax.random.normal(keys[2], (e,)) * 0.02).astype(dtype))


def test_softmax_scoring_is_the_plain_formula():
    u, w, _ = router_inputs(F32)
    idx, weights = moe.route(u, w, top_k=8, scoring="softmax")
    logits = np.asarray(u, np.float64) @ np.asarray(w, np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.argsort(-p, axis=-1)[:, :8]
    assert idx.dtype == jnp.int32 and weights.dtype == F32
    assert np.array_equal(np.asarray(idx), want)
    chosen = np.take_along_axis(p, want, axis=-1)
    close(weights, chosen / chosen.sum(-1, keepdims=True), tol=1e-5)
    close(np.asarray(weights).sum(-1), np.ones(24), tol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_sigmoid_callers_get_what_they_got_to_the_bit(dtype):
    """The three sigmoid models call `route(u, W, bias, top_k=, scale=)`:
    the formula as it stood before the scoring became an argument."""
    u, w, bias = router_inputs(dtype)

    def before(u, router_kernel, score_bias, *, top_k, scale):
        logits = jnp.dot(u.astype(F32), router_kernel.astype(F32),
                         precision=lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        _, idx = lax.top_k(s + score_bias.astype(F32), top_k)
        chosen = jnp.take_along_axis(s, idx, axis=-1)
        weights = scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
        return idx.astype(jnp.int32), weights

    got = moe.route(u, w, bias, top_k=6, scale=2.448)
    want = before(u, w, bias, top_k=6, scale=2.448)
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_a_scoring_the_router_does_not_know_is_refused():
    u, w, _ = router_inputs(F32)
    with pytest.raises(ValueError, match="sigmoid or softmax"):
        moe.route(u, w, top_k=2, scoring="tanh")


def _gated_dense_loop(x, idx, weights, w1, w2, first):
    """Every held expert over every row, weighted by the router's weight or
    zero: bf16 into the matmuls, float32 accumulation, as the kernels."""
    out = jnp.zeros((x.shape[0], w2.shape[-1]), F32)
    for e in range(w1.shape[0]):
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1)
        gate, up = jnp.split(jnp.dot(x, w1[e], preferred_element_type=F32),
                             2, axis=-1)
        hidden = (gate * jax.nn.sigmoid(gate) * up).astype(x.dtype)
        out = out + w_e[:, None] * jnp.dot(hidden, w2[e],
                                           preferred_element_type=F32)
    return out


@pytest.mark.parametrize("tile", [None, 128], ids=["whole", "tiles"])
def test_gather_kernel_with_four_rows_that_share_experts(tile):
    """A decode PASS's call: 4 rows x 8 slots = 32 (under
    `MIN_GROUPED_ROWS`), rows that choose the same held expert.  The kernel
    (interpreted here) gives `local_expert_sum`'s grouped form and a dense
    loop, and counts the assignments on held experts alike - which is also
    what it fetches: an expert per held assignment, a shared one again."""
    t, k, d, f, e_local, first, e_all = 4, 8, 256, 256, 8, 8, 32
    keys = jax.random.split(jax.random.PRNGKey(13), 4)
    x = jax.random.normal(keys[0], (t, d), jnp.bfloat16)
    w1 = (jax.random.normal(keys[1], (e_local, d, 2 * f)) * d ** -0.5
          ).astype(jnp.bfloat16)
    w2 = (jax.random.normal(keys[2], (e_local, f, d)) * f ** -0.5).astype(
        jnp.bfloat16)
    # held: 8 .. 15.  Expert 9 is chosen by all four rows, 15 by two, 8 and
    # 12 by one each; a row without any other held expert; the range's
    # neighbours
    idx = jnp.asarray([[9, 0, 15, 1, 2, 3, 7, 16],
                       [4, 9, 5, 6, 8, 17, 18, 19],
                       [20, 21, 22, 9, 15, 12, 23, 24],
                       [25, 26, 27, 28, 29, 30, 31, 9]], jnp.int32)
    assert idx.max() < e_all and t * k < moe.MIN_GROUPED_ROWS
    weights = jax.random.uniform(keys[3], (t, k), F32, 0.05, 0.5)
    # (the interpreter's callbacks run JAX ops of their own: wait for them
    # before this thread dispatches more)
    got, held = jax.block_until_ready(moe.gather_expert_sum(
        x, idx, weights, w1, w2, first_expert=first, activation="silu",
        tile=tile, interpret=True))
    grouped, n_grouped = moe.local_expert_sum(
        x, idx, weights, w1, w2, first_expert=first, activation="silu")
    assert got.dtype == F32 and got.shape == (t, d)
    assert int(held) == int(n_grouped) == 8  # of 4 distinct experts
    close(got, grouped, tol=1e-2)  # hidden rounds to bf16 before W2
    close(got, _gated_dense_loop(x, idx, weights, w1, w2, first), tol=1e-2)


def _qkv(t, s, hq=8, hkv=2, d=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (t, hq, d)),
            jax.random.normal(keys[1], (s, hkv, d)),
            jax.random.normal(keys[2], (s, hkv, d)))


RULES = {"causal": lambda pos: pos,
         "by_blocks_of_4": lambda pos: pos // 4 * 4 + 3}
# (queries, keys, the first query's position, queries a block)
CALLS = {"prompt": (24, 24, 0, 8), "suffix_into_a_cache": (8, 40, 16, 4),
         "decode_pass": (4, 40, 20, 32)}


@pytest.mark.parametrize("rule", RULES)
@pytest.mark.parametrize("call", CALLS)
def test_attention_by_query_block_is_the_whole_logits_form(call, rule):
    t, s, first, block = CALLS[call]
    q, k, v = _qkv(t, s)
    # rows past the last visible one: a cache not written yet, never read
    seen = int(RULES[rule](jnp.asarray(first + t - 1))) + 1
    k = k.at[seen:].set(jnp.nan)
    v = v.at[seen:].set(jnp.nan)
    positions = RULES[rule](first + jnp.arange(t))
    want = causal_gqa_sdpa(q, jnp.nan_to_num(k), jnp.nan_to_num(v),
                           q_positions=positions)
    got = gqa_sdpa_by_query_block(
        q, jnp.nan_to_num(k).swapaxes(0, 1), jnp.nan_to_num(v).swapaxes(0, 1),
        q_positions=positions, block=block)
    close(got, want, tol=1e-5)
    assert not np.isnan(np.asarray(got)).any()


def test_attention_by_query_block_builds_no_whole_logits():
    """No array with the query length beside the key length among its
    dims, and a cache a precision below is read in the queries' dtype."""
    q, k, v = _qkv(64, 64)
    k, v = k.swapaxes(0, 1), v.swapaxes(0, 1)
    text = jax.jit(lambda q, k, v: gqa_sdpa_by_query_block(
        q, k, v, q_positions=jnp.arange(64), block=8)).lower(
            q, k, v).as_text()
    assert "x64x64x" not in text and "8x64x" in text
    low = gqa_sdpa_by_query_block(
        q, k.astype(jnp.float8_e4m3fn), v.astype(jnp.float8_e4m3fn),
        q_positions=jnp.arange(64))
    assert low.dtype == q.dtype
    close(low, gqa_sdpa_by_query_block(q, k, v, q_positions=jnp.arange(64)),
          tol=0.2)
    with pytest.raises(ValueError, match="query heads over"):
        gqa_sdpa_by_query_block(q[:, :7], k, v, q_positions=jnp.arange(64))
