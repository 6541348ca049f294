"""A decode step's EVA attention as one pass over the rows in view
(`ops/eva.py streamed_decode_attention`, interpreted here) against the plain
form `decode_attention` and a dense float64 softmax; what it fetches; the
route `step_attention` takes; the model decoding across a roll on both
routes."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from distrifuser_tpu.models import evabyte as lm  # noqa: E402
from distrifuser_tpu.ops import eva  # noqa: E402
from test_evabyte import CFG, C, W  # noqa: E402  the small model, float32


def close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1.0), (
        np.abs(a - b).max(), np.abs(b).max())


# (window, chunk, heads, head_dim, dtype, position).  A window of 512 in
# blocks of 128 rows, 128 summary rows a window, room for three windows: the
# positions where a count can slip.  Then the cell's own shape.
SMALL = (512, 4, 4, 16, "float32")
CELL = (2048, 16, 32, 128, "bfloat16")
KERNEL_CASES = {
    "at_zero": SMALL + (0,),
    "one_short_of_a_block": SMALL + (126,),
    "on_a_blocks_last_row": SMALL + (127,),
    "first_row_of_the_second_block": SMALL + (128,),
    "on_the_second_blocks_last_row": SMALL + (255,),
    "first_row_of_the_third_block": SMALL + (256,),
    "window_minus_one": SMALL + (511,),
    "first_position_after_a_roll": SMALL + (512,),
    "in_the_second_window": SMALL + (512 + 300,),
    "last_row_of_the_second_window": SMALL + (1023,),
    "in_the_third_window": SMALL + (1024 + 129,),
    "the_cell_before_its_roll": CELL + (4000,),
    "the_cell_after_its_roll": CELL + (4200,),
}
# what the rows the query may not see hold: after a roll the previous
# window's keys and values (finite, plausible, LARGE here so that a slip
# shows), and what never-fetched VMEM may hold
FILLS = {"garbage": 3e4, "nan": float("nan")}


def state_and_query(window, chunk, h, d, dtype, position, fill):
    """q [H, D]; ring and table, K and V, random where the query at
    ``position`` may see them and ``fill`` everywhere else."""
    rows = 3 * window // chunk
    k = iter(jax.random.split(jax.random.PRNGKey(position), 5))
    q = jax.random.normal(next(k), (h, d)).astype(dtype)
    in_ring = (jnp.arange(window) <= position % window)[:, None, None]
    in_table = (jnp.arange(rows) < position // window * (window // chunk)
                )[:, None, None]
    state = [jnp.where(seen, jax.random.normal(next(k), seen.shape[:1]
                                               + (h, d)), fill).astype(dtype)
             for seen in (in_ring, in_ring, in_table, in_table)]
    return q, state


def rows_in_view(position, window, chunk, block):
    """Ring rows up to the position's, to a whole block, and the summary
    rows of earlier windows."""
    return (-(-(position % window + 1) // block) * block
            + position // window * (window // chunk))


@pytest.mark.parametrize("fill", FILLS)
@pytest.mark.parametrize("case", KERNEL_CASES)
def test_streamed_kernel_against_the_plain_form_and_a_dense_softmax(case,
                                                                    fill):
    """`streamed_decode_attention` (interpreted here) is `decode_attention`
    and a dense float64 softmax over the rows in view; what the rows out of
    view hold - the previous window's rows, NaN - never reaches the result:
    it is the result over zeros there, bit for bit; it says how many rows it
    fetched."""
    window, chunk, h, d, dtype, position = KERNEL_CASES[case]
    q, clean = state_and_query(window, chunk, h, d, dtype, position, 0.0)
    _, dirty = state_and_query(window, chunk, h, d, dtype, position,
                               FILLS[fill])
    assert not all(np.array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))
                   for a, b in zip(clean, dirty))
    kw = dict(position=position, window=window, chunk=chunk)
    # (the interpreted kernel's callbacks run JAX ops of their own: wait for
    # them before this thread dispatches more)
    got, rows = jax.block_until_ready(eva.streamed_decode_attention(
        q, *dirty, interpret=True, **kw))
    assert got.shape == q.shape and got.dtype == q.dtype
    assert int(rows) == rows_in_view(position, window, chunk, 128)
    over_zeros, _ = jax.block_until_ready(eva.streamed_decode_attention(
        q, *clean, interpret=True, **kw))
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(over_zeros, np.float32))
    tol = 1e-6 if dtype == "float32" else 2e-2  # weights rounded to bf16
    close(got, eva.decode_attention(q, *clean, **kw), tol)
    at, n = position % window + 1, position // window * (window // chunk)
    keys = np.concatenate([np.asarray(clean[0], np.float64)[:at],
                           np.asarray(clean[2], np.float64)[:n]])
    values = np.concatenate([np.asarray(clean[1], np.float64)[:at],
                             np.asarray(clean[3], np.float64)[:n]])
    logits = np.einsum("hd,shd->hs", np.asarray(q, np.float64), keys
                       ) / np.sqrt(d)
    w = np.exp(logits - logits.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    close(got, np.einsum("hs,shd->hd", w, values), tol)


def test_the_rows_fetched_over_the_cells_positions_are_the_issues_count():
    """The cell decodes positions 3840-4351 across the roll at 4096: in
    blocks of 128 rows a layer's kernel fetches 655,360 rows of the 512 x
    2320 the plain form reads - 10,485,760 over 16 layers, 0.55."""
    window, chunk, positions = 2048, 16, range(3840, 4352)
    q, state = state_and_query(window, chunk, 1, 8, "float32", 0, 1.0)
    state = state[:2] + [a[:272] for a in state[2:]]
    fetched = []
    for position in positions:
        fetched.append(int(jax.block_until_ready(
            eva.streamed_decode_attention(
                q, *state, position=jnp.asarray(position), window=window,
                chunk=chunk, interpret=True))[1]))
    assert fetched == [rows_in_view(p, window, chunk, 128) for p in positions]
    assert sum(fetched) == 655_360 == 10_485_760 // 16
    exact = sum(p % window + 1 + p // window * 128 for p in positions)
    assert (16 * exact, 16 * 512 * (2048 + 272)) == (9_965_568, 19_005_440)


def test_a_ring_no_block_divides_is_refused():
    q, state = state_and_query(96, 4, 4, 16, "float32", 5, 0.0)
    with pytest.raises(ValueError, match="do not divide"):
        eva.streamed_decode_attention(q, *state, position=5, window=96,
                                      chunk=4, interpret=True)
    with pytest.raises(ValueError, match="do not divide"):
        eva.streamed_decode_attention(q, *state, position=5, window=96,
                                      chunk=4, block_rows=36, interpret=True)
    with pytest.raises(ValueError, match="do not divide"):  # 24 summary rows
        eva.streamed_decode_attention(q, *state, position=5, window=96,
                                      chunk=4, block_rows=48, interpret=True)
    with pytest.raises(ValueError, match="do not divide"):  # not the ring
        eva.streamed_decode_attention(q, state[0][:48], *state[1:],
                                      position=5, window=96, chunk=4,
                                      block_rows=24, interpret=True)


class _Chip:
    platform = "tpu"


# (window, chunk, head_dim): what `step_attention` sees of a call's shape
XLA_SHAPES = {
    "the_cells_shape_off_the_tpu": (2048, 16, 128, False),
    "a_window_no_block_divides": (2000, 16, 128, True),
    "summary_rows_no_block_divides": (2048, 32, 128, True),
    "heads_of_half_a_lane_row": (2048, 16, 64, True),
}


@pytest.mark.parametrize("case", XLA_SHAPES)
def test_the_routed_entry_takes_the_plain_form(case, monkeypatch):
    """Off the TPU every call, and on one a shape the kernel's blocks do not
    divide: `decode_attention` itself, and every row held counted as read."""
    window, chunk, d, on_tpu = XLA_SHAPES[case]
    q, state = state_and_query(window, chunk, 2, d, "float32", window + 70,
                               0.0)
    if on_tpu:
        monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Chip()])
    monkeypatch.setattr(eva, "streamed_decode_attention", None)  # not called
    kw = dict(position=window + 70, window=window, chunk=chunk)
    out, rows = eva.step_attention(q, *state, **kw)
    assert rows == window + state[2].shape[0] and isinstance(rows, int)
    assert np.array_equal(np.asarray(out),
                          np.asarray(eva.decode_attention(q, *state, **kw)))


def test_the_cells_shape_on_a_tpu_takes_the_kernel(monkeypatch):
    q, state = state_and_query(2048, 16, 2, 128, "float32", 0, 0.0)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Chip()])
    taken = []
    monkeypatch.setattr(eva, "streamed_decode_attention",
                        lambda *a, **kw: taken.append(kw) or ("out", "rows"))
    assert eva.step_attention(q, *state, position=3, window=2048,
                              chunk=16) == ("out", "rows")
    assert taken == [dict(position=3, window=2048, chunk=16)]


# -- through the model --------------------------------------------------------

# ring and summary rows a block of the interpreted kernel (a window of 32
# adds 8 summary rows): the decoded positions cross blocks and a window
BLOCK = 8


def kernel_interpreted(q, *state, **kw):
    return eva.streamed_decode_attention(q, *state, block_rows=BLOCK,
                                         interpret=True, **kw)


def test_the_lowered_step_off_the_tpu_is_the_plain_forms(monkeypatch):
    """On the CPU `attention_step` lowers to the text it lowers to with
    `decode_attention` called directly, as the tree before the kernel did:
    the routed entry adds no operation (the rows it counts are a Python
    number)."""
    params = lm.init_evabyte_params(jax.random.PRNGKey(3), CFG)["layers"][0]
    state = lm.empty_state(CFG, 3 * W, jnp.float32)
    x = jnp.ones((1, CFG.hidden_size))

    def lowered():
        return jax.jit(lambda p, x, st, at: lm.attention_step(
            p["attn"], CFG, x, st, at)[:2]).lower(
                params, x, state, jnp.int32(W + 3)).as_text()

    routed = lowered()
    monkeypatch.setattr(eva, "step_attention", lambda *a, **kw: (
        eva.decode_attention(*a, **kw), 0))
    assert routed == lowered()
    assert "custom_call" not in routed


def test_the_counters_keep_their_places_and_the_new_one_is_last():
    assert lm.COUNTERS[:6] == (
        "bytes_prefilled", "bytes_decoded", "summaries_written",
        "windows_rolled", "state_bytes", "bytes_reused")
    assert lm.COUNTERS[6:] == ("state_rows_read",)
    assert CFG.language_model().counters == lm.COUNTERS


def test_decoding_across_a_roll_is_the_same_on_both_routes(monkeypatch):
    """36 bytes from position 24 on, through the rest of window 0 and past
    the roll at 32: the kernel's route (interpreted) gives the plain
    route's ids and logits, the same state, and counts the rows in view
    where the plain route counts every row held."""
    params = lm.init_evabyte_params(jax.random.PRNGKey(3), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(7), (W - 2 * C,),
                                CFG.byte_offset, CFG.vocab_size)
    new, layers = 36, CFG.num_hidden_layers
    total = len(prompt) + new

    def served():
        # (the interpreted kernel's callbacks run JAX ops of their own: wait
        # for them before this thread dispatches more)
        return jax.block_until_ready(jax.jit(
            lambda p, ids: lm.generate(p, CFG, ids, new))(params, prompt))

    plain = served()
    monkeypatch.setattr(eva, "step_attention", kernel_interpreted)
    kernel = served()
    assert np.array_equal(np.asarray(plain[0]), np.asarray(kernel[0]))
    close(kernel[1], plain[1], 2e-5)
    for a, b in zip(jax.tree.leaves(kernel[3]), jax.tree.leaves(plain[3]),
                    strict=True):
        close(a, b, 2e-5)
    steps = range(len(prompt), total)
    assert any(p % W == 0 for p in steps)  # it did roll
    counters = [dict(zip(lm.COUNTERS, np.asarray(c).tolist()))
                for c in (plain[2], kernel[2])]
    assert counters[0].pop("state_rows_read") == layers * new * (
        W + -(-total // C))
    assert counters[1].pop("state_rows_read") == layers * sum(
        rows_in_view(p, W, C, BLOCK) for p in steps)
    assert counters[0] == counters[1]
