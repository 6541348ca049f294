"""Nemotron-H at a small size, float32, seeded weights: each mixer and the
whole stack against the plain reference (`benchmark/reference`), the chunked
scan against its own recurrence (from zero and entering a state), prefill then decode through both kinds of
state against the full forward, and the expert shares adding up."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import nemotron_h_sdxl as ref  # noqa: E402
from distrifuser_tpu.models import nemotron_h as lm  # noqa: E402
from distrifuser_tpu.ops import moe, ssm  # noqa: E402
from distrifuser_tpu.ops.attention import causal_gqa_sdpa  # noqa: E402

# the published keys, small: 64 experts in the router, 8 chips, 8 held
JSON = {
    "hybrid_override_pattern": "MEMEM*E", "num_hidden_layers": 7,
    "vocab_size": 256, "hidden_size": 64, "norm_eps": 1e-5,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "n_routed_experts": 8, "expert_parallel": {"chips": 8, "index": 3},
    "num_experts_per_tok": 6, "moe_latent_size": 32,
    "moe_intermediate_size": 48, "moe_shared_expert_intermediate_size": 64,
    "routed_scaling_factor": 5.0,
}
CFG = lm.nemotron_h_config_from_json(JSON)
SHAPE = ref.lm_shape(JSON)
T = 24


@pytest.fixture(scope="module")
def params():
    return lm.init_nemotron_h_params(jax.random.PRNGKey(3), CFG)


@pytest.fixture(scope="module")
def hidden():
    return jax.random.normal(jax.random.PRNGKey(4), (T, CFG.hidden_size))


def layer(params, kind):
    return params["layers"][CFG.pattern.index(kind)]["mixer"]


def close(a, b, tol=2e-5):
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1.0), (
        np.abs(a - b).max(), np.abs(b).max())


def test_config_from_the_published_keys():
    assert CFG.pattern == "MEMEM*E"
    assert (CFG.n_routed_experts, CFG.n_local_experts,
            CFG.first_local_expert) == (64, 8, 24)
    assert CFG.conv_dim == 4 * 8 + 2 * 2 * 16
    with pytest.raises(ValueError, match="pattern"):
        lm.NemotronHConfig(pattern="MXE")
    with pytest.raises(ValueError, match="shorter"):
        lm.nemotron_h_config_from_json(dict(JSON, num_hidden_layers=9))


@pytest.mark.parametrize("kind", ["M", "*", "E"])
def test_each_mixer_against_the_reference(params, hidden, kind):
    p = layer(params, kind)
    want = (ref.MIXERS[kind](p, SHAPE, hidden) if kind != "E"
            else ref.experts(p, SHAPE, hidden)[0])
    if kind == "M":
        got, _ = lm.mamba_prefill(p, CFG, hidden)
    elif kind == "*":
        got, _ = lm.attention_layer(
            p, CFG, hidden, lm.empty_cache(CFG, T, jnp.float32), 0)
    else:
        got, held, idx = lm.moe_layer(p, CFG, hidden)
        assert 0 < int(held) < T * CFG.num_experts_per_tok
        # teacher-forced over the program's own choice: the same layer, and
        # a choice the reference would have made itself has no slack
        forced, slack = ref.experts(p, SHAPE, hidden, served=idx)
        close(forced, want)
        assert float(slack) == 0.0
        wrong = idx.at[0, 0].set((idx[0, 1] + 1) % 64)
        assert float(ref.experts(p, SHAPE, hidden, served=wrong)[1]) > 0
        twice = idx.at[0, 0].set(idx[0, 1])
        assert np.isinf(float(ref.experts(p, SHAPE, hidden, served=twice)[1]))
    close(got, want)


def scan_inputs(h=4, p=8, g=2, n=16):
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(keys[0], (T, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (T, h)) - 2.0)
    a = -jnp.exp(jax.random.uniform(keys[2], (h,), minval=0.0, maxval=2.5))
    b = jax.random.normal(keys[3], (T, g, n))
    c = jax.random.normal(keys[4], (T, g, n))
    return x, dt, a, b, c


def test_chunked_scan_is_its_own_recurrence():
    x, dt, a, b, c = scan_inputs()
    h, p, n = *x.shape[1:], b.shape[-1]
    y, last = ssm.ssd_chunked(x, dt, a, b, c, chunk=8)
    state = jnp.zeros((h, p, n))
    for t in range(T):
        y_t, state = ssm.ssd_step(state, x[t], dt[t], a, b[t], c[t])
        close(y[t], y_t)
    close(last, state)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        ssm.ssd_chunked(x[:T - 1], dt[:T - 1], a, b[:T - 1], c[:T - 1],
                        chunk=8)


@pytest.mark.parametrize("cut", [8, 16])
def test_chunked_scan_enters_a_state(cut):
    """The rows after a chunk boundary through the state the rows before it
    left: the recurrence from that state row by row, and - the cut being
    where the scan carries one state anyway - bit for bit the whole
    sequence from zero; a state of another dtype is read as float32."""
    x, dt, a, b, c = scan_inputs()
    whole, last = ssm.ssd_chunked(x, dt, a, b, c, chunk=8)
    before, state = ssm.ssd_chunked(x[:cut], dt[:cut], a, b[:cut], c[:cut],
                                    chunk=8)
    rest = (x[cut:], dt[cut:], a, b[cut:], c[cut:])
    after, entered = ssm.ssd_chunked(*rest, chunk=8, state=state)
    assert np.array_equal(jnp.concatenate([before, after]), whole)
    assert np.array_equal(entered, last) and entered.dtype == jnp.float32
    stepped = state
    for t in range(cut, T):
        y_t, stepped = ssm.ssd_step(stepped, x[t], dt[t], a, b[t], c[t])
        close(after[t - cut], y_t)
    close(entered, stepped)
    # the state is entered, not ignored; None is the zero state
    from_zero, _ = ssm.ssd_chunked(*rest, chunk=8)
    assert float(jnp.abs(from_zero - after).max()) > 1e-2
    zeros, _ = ssm.ssd_chunked(*rest, chunk=8, state=jnp.zeros_like(state))
    assert np.array_equal(zeros, from_zero)
    rounded, _ = ssm.ssd_chunked(*rest, chunk=8,
                                 state=state.astype(jnp.bfloat16))
    assert rounded.dtype == jnp.float32
    close(rounded, after, tol=2e-2)


def test_mamba_prefill_enters_the_state_and_the_convolutions_tail(
        params, hidden):
    p = layer(params, "M")
    whole, last = lm.mamba_prefill(p, CFG, hidden)
    _, state = lm.mamba_prefill(p, CFG, hidden[:16])
    out, entered = lm.mamba_prefill(p, CFG, hidden[16:], state)
    close(out, whole[16:])
    for name in ("ssm", "conv"):
        close(entered[name], last[name])
    # either half of the state left out shows in the rows after the cut
    for name in ("ssm", "conv"):
        without = dict(state, **{name: jnp.zeros_like(state[name])})
        wrong, _ = lm.mamba_prefill(p, CFG, hidden[16:], without)
        assert float(jnp.abs(wrong - whole[16:]).max()) > 1e-4


def test_mamba_prefill_then_steps_is_one_long_prefill(params, hidden):
    p = layer(params, "M")
    whole, _ = lm.mamba_prefill(p, CFG, hidden)
    out, state = lm.mamba_prefill(p, CFG, hidden[:16])
    close(out, whole[:16])
    assert state["ssm"].dtype == jnp.float32
    for t in range(16, T):
        out, state = lm.mamba_step(p, CFG, hidden[t:t + 1], state)
        close(out[0], whole[t])


def test_causal_gqa_reads_only_the_rows_written():
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(keys[0], (5, 4, 16))
    k = jax.random.normal(keys[1], (5, 2, 16))
    v = jax.random.normal(keys[2], (5, 2, 16))
    whole = causal_gqa_sdpa(q, k, v, q_positions=jnp.arange(5))
    # a cache with rows beyond the query's position filled with rubbish
    junk = jnp.full((3, 2, 16), 1e9)
    row = causal_gqa_sdpa(q[2:3], jnp.concatenate([k[:3], junk]),
                          jnp.concatenate([v[:3], junk]),
                          q_positions=jnp.asarray([2]))
    close(row[0], whole[2])
    with pytest.raises(ValueError, match="KV heads"):
        causal_gqa_sdpa(q[:, :3], k, v, q_positions=jnp.arange(5))


def test_prefill_then_decode_against_the_references_full_forward(params):
    """Logits, not tokens: every decoded position's logits, through the SSM
    state, the convolution tail and the KV cache, against one full forward
    of the reference over prompt + decoded ids."""
    prompt = np.random.default_rng(0).integers(0, 256, 16).astype(np.int32)
    new_ids, logits, counters, chosen = jax.jit(
        lambda p, i: lm.generate(p, CFG, i, 8))(params, prompt)
    ids = np.concatenate([prompt, np.asarray(new_ids)[:-1]])
    model = ref.LanguageModel(JSON)
    want, _ = model.logits(params, ids, first=15)
    close(logits, want)
    assert chosen.shape == (3, 23, CFG.num_experts_per_tok)
    forced, slack = model.logits(params, ids, first=15,
                                 served_experts=np.asarray(chosen))
    close(forced, want)
    assert slack <= 1e-6
    assert np.array_equal(np.asarray(new_ids), np.asarray(want).argmax(1))
    c = dict(zip(lm.COUNTERS, np.asarray(counters).tolist()))
    n_e = CFG.pattern.count("E")
    assert c["tokens_prefilled"] == 16 and c["tokens_decoded"] == 8
    assert c["expert_assignments"] == 24 * n_e * CFG.num_experts_per_tok
    assert 0 < c["expert_assignments_held"] < c["expert_assignments"]


def test_the_shares_add_up(params, hidden):
    """Eight chips' shares of an E layer - each routes over all 64 experts
    and computes its own 8 - with the shared expert counted once, are the
    uncut layer as the reference computes it with all 64 held."""
    cfg = lm.NemotronHConfig(**{
        **{f.name: getattr(CFG, f.name)
           for f in CFG.__dataclass_fields__.values()},
        "n_local_experts": 64, "first_local_expert": 0})
    full = lm.init_nemotron_h_params(jax.random.PRNGKey(5), cfg)
    p = full["layers"][cfg.pattern.index("E")]["mixer"]
    want, _ = ref.experts(p, dict(SHAPE, first_expert=0, held=64), hidden)
    sh = p["shared"]
    shared = jnp.square(jax.nn.relu(hidden @ sh["fc1"]["kernel"])) \
        @ sh["fc2"]["kernel"]
    total, held = shared, 0
    for chip in range(8):
        share = dict(p, experts={k: w[8 * chip:8 * chip + 8]
                                 for k, w in p["experts"].items()})
        cfg_i = lm.nemotron_h_config_from_json(
            dict(JSON, expert_parallel={"chips": 8, "index": chip}))
        out, n, _ = lm.moe_layer(share, cfg_i, hidden)
        total = total + (out - shared)
        held += int(n)
    close(total, want)
    assert held == T * CFG.num_experts_per_tok  # every assignment, once


def test_route_weights_are_normalised_over_all_the_chosen(params, hidden):
    p = layer(params, "E")
    idx, w = moe.route(hidden, p["router"]["kernel"],
                       p["e_score_correction_bias"], top_k=6, scale=5.0)
    assert idx.shape == (T, 6) and len(set(np.asarray(idx[0]).tolist())) == 6
    close(w.sum(-1), np.full(T, 5.0))
    # a token whose chosen experts all lie elsewhere gets nothing from here
    out, n = moe.local_expert_sum(
        hidden[:, :32], idx, w, p["experts"]["w1"], p["experts"]["w2"],
        first_expert=1000)
    assert int(n) == 0 and float(jnp.abs(out).max()) == 0.0


def test_balanced_selection_bias_evens_the_load(params):
    """The published balancing rule run to its fixed point: over the
    calibration sequence every expert is chosen about equally often, where
    the random router's own load is uneven by tens of per cent."""
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 256, 512),
                      jnp.int32)
    biases = lm.balanced_selection_bias(params, CFG, ids)
    assert len(biases) == CFG.pattern.count("E")
    assert all(b.shape == (CFG.n_routed_experts,) for b in biases)

    def first_layer_load(bias):
        i = CFG.pattern.index("E")
        x = params["embed"][ids]
        for kind, lp in zip(CFG.pattern[:i], params["layers"][:i]):
            u = lm.rms_norm(lp["norm"]["scale"], x, CFG.norm_eps)
            x = x + lm.mamba_prefill(lp["mixer"], CFG, u)[0]
        lp = params["layers"][i]
        idx, _ = moe.route(lm.rms_norm(lp["norm"]["scale"], x, CFG.norm_eps),
                           lp["mixer"]["router"]["kernel"], bias,
                           top_k=CFG.num_experts_per_tok, scale=5.0)
        return np.bincount(np.asarray(idx).reshape(-1), minlength=64)

    share = 512 * CFG.num_experts_per_tok / 64
    uneven = first_layer_load(jnp.zeros(64))
    even = first_layer_load(biases[0])
    assert np.abs(uneven - share).max() > 0.3 * share
    assert np.abs(even - share).max() < 0.1 * share


def _chosen(held_ids, first, e_local, k=22, total=64, seed=0):
    """One token's k distinct expert ids: ``held_ids`` and, for the rest,
    experts outside [first, first + e_local), shuffled."""
    rng = np.random.default_rng(seed)
    outside = [e for e in range(total)
               if not first <= e < first + e_local and e not in held_ids]
    rest = rng.permutation(outside)[:k - len(held_ids)]
    return rng.permutation(np.concatenate(
        [np.asarray(held_ids, np.int64), rest])).astype(np.int32)


# (first_expert, per token the held experts among its 22 chosen); 16 held
GATHER_CASES = {
    "t1_held0": (0, [[]]),
    "t1_held1": (0, [[5]]),
    "t1_held3": (0, [[2, 9, 14]]),
    "t1_held8": (0, [[0, 3, 4, 7, 8, 11, 12, 15]]),
    "t2_44_rows": (0, [[1, 6, 13], [6, 10]]),
    "first_expert_24": (24, [[25, 30, 38]]),
    "both_ends_of_the_held_range": (24, [[24, 39]]),
}


@pytest.mark.parametrize("case", GATHER_CASES)
def test_gather_kernel_against_the_grouped_path_and_a_dense_loop(case):
    """`gather_expert_sum` (the decode-shaped call on a TPU; interpreted
    here) is `local_expert_sum`'s grouped matmul and a dense loop over the
    held experts, in bf16 with float32 accumulation, and counts alike."""
    first, held_ids = GATHER_CASES[case]
    d, f, e_local, k = 256, 384, 16, 22
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    t = len(held_ids)
    x = jax.random.normal(keys[0], (t, d), jnp.bfloat16)
    w1 = (jax.random.normal(keys[1], (e_local, d, f)) * d ** -0.5).astype(
        jnp.bfloat16)
    w2 = (jax.random.normal(keys[2], (e_local, f, d)) * f ** -0.5).astype(
        jnp.bfloat16)
    # the experts just outside the held range are among the chosen too
    idx = jnp.asarray(np.stack([_chosen(h, first, e_local, seed=i)
                                for i, h in enumerate(held_ids)]))
    assert t * k < moe.MIN_GROUPED_ROWS and all(
        len(set(row)) == k for row in np.asarray(idx).tolist())
    weights = jax.random.uniform(keys[3], (t, k), jnp.float32, 0.05, 0.5)

    # (the interpreter's callbacks run JAX ops of their own: wait for them
    # before this thread dispatches more)
    got, n = jax.block_until_ready(moe.gather_expert_sum(
        x, idx, weights, w1, w2, first_expert=first, tile=128,
        interpret=True))
    grouped, n_grouped = moe.local_expert_sum(x, idx, weights, w1, w2,
                                              first_expert=first)
    dense = np.zeros((t, d), np.float32)
    for ti in range(t):
        for e, w in zip(np.asarray(idx[ti]), np.asarray(weights[ti])):
            if first <= e < first + e_local:
                hidden = jnp.dot(x[ti], w1[e - first],
                                 preferred_element_type=jnp.float32)
                hidden = jnp.square(jax.nn.relu(hidden)).astype(x.dtype)
                dense[ti] += w * np.asarray(jnp.dot(
                    hidden, w2[e - first],
                    preferred_element_type=jnp.float32))
    assert got.dtype == jnp.float32
    assert int(n) == int(n_grouped) == sum(map(len, held_ids))
    close(got, grouped)
    close(got, dense)
