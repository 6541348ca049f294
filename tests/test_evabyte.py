"""EvaByte at a small size, float32, seeded weights: prefill, and prefill
then decode through ring and summary table across a chunk and a window
boundary, against the plain reference's full forward (`benchmark/reference`)
by the logits of all eight head blocks; the attention op against its own
definition; the state and the counters the decode loop carries."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import evabyte_sdxl as ref  # noqa: E402
from distrifuser_tpu.models import evabyte as lm  # noqa: E402
from distrifuser_tpu.ops import eva  # noqa: E402

# the published keys, small: window 32, chunk 4, 3 layers
JSON = {
    "attention_class": "eva", "num_hidden_layers": 3, "vocab_size": 320,
    "byte_offset": 64, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 96, "window_size": 32,
    "chunk_size": 4, "num_pred_heads": 8, "rms_norm_eps": 1e-5,
    "rope_theta": 100000, "fp32_skip_add": True,
}
CFG = lm.evabyte_config_from_json(JSON)
W, C = CFG.window_size, CFG.chunk_size


@pytest.fixture(scope="module")
def params():
    p = lm.init_evabyte_params(jax.random.PRNGKey(3), CFG)
    # norm offsets away from their initial zero: 1 + w is not 1
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 2 * len(p["layers"])
                                 + 1))
    for lp in p["layers"]:
        for name in ("attn_norm", "mlp_norm"):
            lp[name]["scale"] = 0.1 * jax.random.normal(
                next(keys), lp[name]["scale"].shape)
    p["final_norm"]["scale"] = 0.1 * jax.random.normal(
        next(keys), p["final_norm"]["scale"].shape)
    return p


def byte_ids(n, seed=5):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), CFG.byte_offset, CFG.vocab_size))


def close(a, b, tol=2e-5):
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1.0), (
        np.abs(a - b).max(), np.abs(b).max())


def reference_logits(params, ids, first=0):
    with jax.default_matmul_precision("highest"):
        return ref.LanguageModel(JSON).logits(params, ids, first=first)


def plain_causal(q, k, v):
    """softmax(q k^T / sqrt(d)) v over [T, H, D], causal."""
    t, _, d = q.shape
    logits = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(d)
    logits = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], logits,
                       -jnp.inf)
    return jnp.einsum("hts,shd->thd", jax.nn.softmax(logits, -1), v)


def qkv(t, seed=0, h=4, d=16):
    return (jax.random.normal(k, (t, h, d))
            for k in jax.random.split(jax.random.PRNGKey(seed), 3))


def from_zero(q, k, v, phi, mu, *, chunk, block):
    """EVA attention of a whole prompt: the entering form, nothing before."""
    ks, vs = eva.chunk_summaries(k, v, phi, mu, chunk=chunk)
    ring, table = jnp.zeros((W,) + k.shape[1:]), jnp.zeros_like(ks)
    return eva.prefill_attention(q, k, v, ks, vs, ring, ring, table, table,
                                 position=0, window=W, chunk=chunk,
                                 block=block)[0]


def test_the_parameters_are_the_published_count():
    full = lm.evabyte_config_from_json({"num_attention_heads": 32})
    count = sum(int(np.prod(shape)) for _, shape in lm.named_leaves(full)[0])
    assert count == 6_488_330_240
    layer = lm.param_shapes(full)["layers"][0]
    assert sum(int(np.prod(s)) for s in jax.tree.leaves(
        layer, is_leaf=lambda x: isinstance(x, tuple))) == 202_391_552


def test_prefill_logits_against_the_reference_over_two_and_a_half_windows(
        params):
    ids = byte_ids(2 * W + W // 2)
    # every position's logits, all eight blocks: the stack's output through
    # the program's head
    empty = [lm.empty_state(CFG, len(ids), jnp.float32)] * len(
        params["layers"])
    h, *_ = lm._forward(params, CFG, jnp.asarray(ids), empty, 0,
                        lm.attention_prefill)
    got = lm.head(params, CFG, h)
    assert got.shape == (len(ids), 8 * CFG.vocab_size)
    close(got, reference_logits(params, ids), tol=5e-5)
    last, *_ = lm.prefill(params, CFG, jnp.asarray(ids), max_len=len(ids))
    assert last.dtype == jnp.float32
    close(last, got[-1], tol=1e-6)


def test_prefill_then_decode_across_a_chunk_and_a_window_boundary(params):
    """A prompt that ends inside window 0, six chunks in; decoding runs
    through the rest of the window (a summary every 4 bytes), rolls it, and
    goes on into window 1 - at every decoded position the logits the byte
    was chosen from are the reference's over the same ids."""
    prompt, new = byte_ids(W - 2 * C, seed=7), 3 * C + 2
    new_ids, chosen_from, counters, state = jax.jit(
        lambda p, ids: lm.generate(p, CFG, ids, new))(params,
                                                      jnp.asarray(prompt))
    new_ids = np.asarray(new_ids)
    assert np.array_equal(
        new_ids, np.asarray(chosen_from)[:, :CFG.vocab_size].argmax(1))
    ids = np.concatenate([prompt, new_ids[:-1]])
    close(chosen_from, reference_logits(params, ids, first=len(prompt) - 1),
          tol=5e-5)
    # the state: a ring of one window, a table of a row a chunk
    total = len(prompt) + new
    assert len(state) == CFG.num_hidden_layers
    for st in state:
        assert st["k"].shape == st["v"].shape == (W, 4, 16)
        assert st["ks"].shape == st["vs"].shape == (-(-total // C), 4, 16)
    nbytes = lm.params_nbytes(state)
    assert nbytes == 3 * 2 * 4 * 4 * 16 * (W + -(-total // C))
    assert dict(zip(lm.COUNTERS, np.asarray(counters).tolist())) == {
        "bytes_prefilled": len(prompt), "bytes_decoded": new,
        "summaries_written": total // C, "windows_rolled": total // W,
        "state_bytes": nbytes, "bytes_reused": 0,
        # off the TPU a step reads every row the state holds
        "state_rows_read": 3 * new * (W + -(-total // C))}
    assert total // W == 1 and len(prompt) // W == 0  # it did roll
    # a chunk that is not complete leaves its row of the table alone
    assert total % C and not np.asarray(state[0]["ks"][total // C]).any()
    assert np.asarray(state[0]["ks"][total // C - 1]).any()


@pytest.mark.parametrize("position,t", [(W + 2, C), (W, C + 1)])
def test_an_entering_prefill_of_broken_chunks_is_refused(params, position,
                                                         t):
    """(A position without a state and a state without room: every model's,
    `tests/test_language_models.py`.)"""
    _, state, counters, _ = lm.prefill(params, CFG, jnp.asarray(byte_ids(W)),
                                       max_len=2 * W + 2 * C)
    with pytest.raises(ValueError, match="whole chunks"):
        lm.prefill(params, CFG, jnp.asarray(byte_ids(t)),
                   max_len=2 * W + 2 * C, state=state, counters=counters,
                   position=position)


@pytest.mark.parametrize("position,t,match", [
    (W + 2, C, "whole chunks"), (W, C + 1, "whole chunks"),
    (W, 2 * C, "do not reach")])
def test_the_attention_op_refuses_broken_chunks_and_a_short_table(
        position, t, match):
    q, k, v = qkv(t)
    ring, table = jnp.zeros((W, 4, 16)), jnp.zeros((W // C + 1, 4, 16))
    with pytest.raises(ValueError, match=match):
        eva.prefill_attention(q, k, v, table[:t // C], table[:t // C], ring,
                              ring, table, table, position=position,
                              window=W, chunk=C)


def test_within_one_window_eva_is_plain_causal_attention():
    q, k, v = qkv(W)
    phi, mu = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 16))
    got = from_zero(q, k, v, phi, mu, chunk=C, block=8)
    close(got, plain_causal(q, k, v))


def test_with_chunks_of_one_and_no_offset_eva_is_plain_causal_attention():
    """chunk_size 1, mu 0: a summary IS its position, so the window and the
    summaries of all before it are every earlier position."""
    q, k, v = qkv(2 * W + 5, seed=2)
    phi = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
    ks, vs = eva.chunk_summaries(k, v, phi, jnp.zeros((4, 16)), chunk=1)
    close(ks, k), close(vs, v)
    got = from_zero(q, k, v, phi, jnp.zeros((4, 16)), chunk=1, block=16)
    close(got, plain_causal(q, k, v))
    # and the one-row form, the ring and the table as a decode step has them
    t = 2 * W + 4
    ring = jnp.zeros((W, 4, 16)).at[:t % W + 1].set(k[t - t % W:t + 1])
    ring_v = jnp.zeros((W, 4, 16)).at[:t % W + 1].set(v[t - t % W:t + 1])
    row = eva.decode_attention(q[t], ring, ring_v, ks, vs, position=t,
                               window=W, chunk=1)
    close(row, plain_causal(q[:t + 1], k[:t + 1], v[:t + 1])[t])


def test_a_residual_stream_in_bfloat16_is_another_result(params):
    """`fp32_skip_add` false, the benchmark's control: the stream rounded to
    bfloat16 after every layer's sum, a precision below the stated one."""
    import dataclasses

    ids = jnp.asarray(byte_ids(W + 2 * C))
    want = reference_logits(params, np.asarray(ids))[-1]
    err = {}
    for keep in (True, False):
        cfg = dataclasses.replace(CFG, fp32_skip_add=keep)
        got, *_ = lm.prefill(params, cfg, ids, max_len=len(ids))
        err[keep] = float(jnp.sqrt(jnp.mean(jnp.square(got - want)))
                          / jnp.std(want))
    assert err[True] < 1e-4 and err[False] > 1e-3, err


def test_what_is_not_built_is_refused():
    for key, value in (("attention_class", "softmax"), ("fp32_ln", True),
                       ("num_key_value_heads", 2)):
        with pytest.raises(ValueError, match="built"):
            lm.evabyte_config_from_json(dict(JSON, **{key: value}))
    with pytest.raises(ValueError, match="straddles"):
        lm.EvaByteConfig(window_size=30, chunk_size=4)
