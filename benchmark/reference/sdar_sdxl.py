"""Plain float32 reference of the block-diffusion think-then-rewrite cell:
the SDAR-style language model's full forward (SDAR-30B-A3B-Chat's published
keys) over what the program served, then few-step SDXL from the ids it
ended on.

The language model, as its published description has it (and each departure
in the configuration's `assumed`): with h = RMSNorm(x), eps 1e-6, per row r
at position pos_r

    q_r = rope(RMSNorm_128(h_r W_q -> 32 heads of 128; q_norm), pos_r)
    k_r = rope(RMSNorm_128(h_r W_k ->  4 heads of 128; k_norm), pos_r)
    v_r = h_r W_v -> 4 heads of 128
    rope: the pair (x[i], x[i + 64]) turned by pos * theta^(-2 i / 128)
    a_r = sum_s softmax_s(q_r . k_s / sqrt(128) | r sees s) v_s
        8 query heads a KV head (each KV head REPEATED for its 8);
    x <- x + concat_heads(a) W_o

then u = RMSNorm(x); p = softmax(u W_g) over ALL experts, the 8 largest
chosen, w_e = p_e / sum of the chosen p; x <- x + sum over the chosen
experts HELD HERE of w_e (silu(u G_e) * u U_e) D_e - a DENSE loop, every
held expert over every row, weighted by the router's weight or zero.  Final
RMSNorm, head.

**Who sees whom** is the block rule (B = block_length): position i sees
position j iff j // B <= i // B.  Generation denoises a block of B MASK ids
in T passes and commits it by one more; the reference recomputes all of it,
teacher-forced on what was served, in ONE forward with no cache: the rows
are the final sequence (prompt + served ids: the COMMITTED rows, whose keys
and values are what later blocks see) and, for every denoise pass of every
block, B more rows - a VIEW: the block as it stood in that pass, MASK where
an id was fixed in that pass or later.  A view's rows see the committed
rows of earlier blocks and their own view; nobody sees a view.  One mask
over the concatenation, the queries in blocks only so that
[heads, queries, rows] fits.  It is given the same share of the model as
the program - the experts held, the slice of the vocabulary - and the same
parameter tree (an expert's gate and up-projection one fused kernel
[gate | up]; the same parameters).  It imports nothing of `distrifuser_tpu`.

What decides `correct`: the served logits of every id - from the pass that
fixed it - against the reference's view of that pass
(`reference/nemotron_h_sdxl.py logit_readings`); `lm_router_slack_worst`:
how far below the reference's own 8th largest p the lowest expert the
program chose lies, over committed rows and views (the expert layers are
computed over the served choice); `lm_unmask_slack_worst`: in every view,
how far below the reference's most confident masked position (in log
probability) the position the program fixed lies, and how far below the
reference's largest logit there the served id's logit lies (0: the same
position, the same id); then the image from the served ids.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import _common as C
from ._common import F32, f32, silu
from .nemotron_h_sdxl import Reference as RewriteReference
from .nemotron_h_sdxl import load_limits, logit_readings
from .unet_sdxl import clip_text, unet

QUERY_BLOCK = 256  # queries a block of the reference's attention


# -- the language model -------------------------------------------------------


def lm_shape(config):
    """The sizes the reference needs, from the configuration's keys."""
    ep = config.get("expert_parallel", {"chips": 1, "index": 0})
    held = config["num_experts"]
    return {
        "eps": config["rms_norm_eps"], "theta": config["rope_theta"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "first_expert": held * ep["index"], "held": held,
        "top_k": config["num_experts_per_tok"],
        "block": config["block_length"], "steps": config["denoising_steps"],
        "mask_id": config["vocab_size"] - 1,
    }


def prompt_ids(config, prompt):
    """The language model's prompt: the instruction drawn from its seed over
    the ids a text can hold (every id of the held vocabulary but the last,
    the MASK id), then the caller's words through the word hash over the
    same ids, cut or repeated."""
    import zlib

    rw = config["rewrite"]
    vocab = config["vocab_size"] - 1
    rng = np.random.default_rng(rw["instruction_seed"])
    instruction = rng.integers(0, vocab, rw["instruction_tokens"])
    words = [zlib.crc32(w.encode()) % vocab
             for w in prompt.lower().split()] or [0]
    n = rw["user_tokens"]
    user = (words * -(-n // len(words)))[:n]
    return np.concatenate([instruction, user]).astype(np.int32)


def rms_norm(scale, x, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * f32(scale)


def rotary(x, positions, theta):
    """x [R, H, D] at ``positions`` [R]: the pair (x[i], x[i + D/2]) turned
    by position * theta^(-2 i / D)."""
    half = x.shape[-1] // 2
    angle = f32(positions)[:, None, None] * theta ** (
        -jnp.arange(half, dtype=F32) / half)
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * jnp.cos(angle) - hi * jnp.sin(angle),
                            hi * jnp.cos(angle) + lo * jnp.sin(angle)], -1)


def attention(p, s, x, rows):
    """x [R, d] -> [R, d]; ``rows`` = (positions, block, view) [R] each:
    row r sees row q iff q is a committed row (view 0) of an earlier block,
    or a row of r's own block in r's own view."""
    positions, block, view = rows
    r, h, kv, d = x.shape[0], s["heads"], s["kv_heads"], s["head_dim"]
    q = rotary(rms_norm(p["q_norm"]["scale"],
                        (x @ f32(p["q"]["kernel"])).reshape(r, h, d),
                        s["eps"]), positions, s["theta"])
    k = rotary(rms_norm(p["k_norm"]["scale"],
                        (x @ f32(p["k"]["kernel"])).reshape(r, kv, d),
                        s["eps"]), positions, s["theta"])
    v = (x @ f32(p["v"]["kernel"])).reshape(r, kv, d)
    k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
    out = []
    for lo in range(0, r, QUERY_BLOCK):
        hi = min(r, lo + QUERY_BLOCK)
        logits = jnp.einsum("thd,shd->hts", q[lo:hi], k) / np.sqrt(d)
        sees = ((view[None, :] == 0) & (block[None, :] < block[lo:hi, None])
                ) | ((view[None, :] == view[lo:hi, None])
                     & (block[None, :] == block[lo:hi, None]))
        w = jax.nn.softmax(jnp.where(sees[None], logits, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hts,shd->thd", w, v))
    return jnp.concatenate(out).reshape(r, -1) @ f32(p["o_proj"]["kernel"])


def experts(p, s, u, served=None):
    """Router over all experts; of the chosen, those held here computed by a
    dense loop.  -> (out [R, d], router slack).  ``served`` [R, top_k]: the
    experts the program chose - the comparison is teacher-forced over them,
    as `reference/nemotron_h_sdxl.py experts` has it: the reference's own
    float32 probabilities decide whether the served choice was a sound one
    (the slack), and the layer is then computed over the served choice with
    the reference's probabilities for weights."""
    prob = jax.nn.softmax(u @ f32(p["router"]["kernel"]), axis=-1)
    kth, idx = jax.lax.top_k(prob, s["top_k"])
    slack = jnp.zeros(())
    if served is not None:
        idx = jnp.sort(served, axis=-1)
        valid = jnp.all(idx[:, 1:] > idx[:, :-1]) & (idx.min() >= 0) & (
            idx.max() < prob.shape[-1])
        idx = jnp.clip(idx, 0, prob.shape[-1] - 1)
        lowest = jnp.take_along_axis(prob, idx, axis=-1).min(-1)
        slack = jnp.where(valid, jnp.max(kth[:, -1] - lowest), jnp.inf)
    chosen = jnp.take_along_axis(prob, idx, axis=-1)
    weights = chosen / chosen.sum(-1, keepdims=True)

    def one(total, expert):
        e, w1, w2 = expert
        w_e = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)  # [R]
        gate, up = jnp.split(u @ f32(w1), 2, axis=-1)
        return total + w_e[:, None] * ((silu(gate) * up) @ f32(w2)), None

    ids = s["first_expert"] + jnp.arange(s["held"])
    routed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                             (ids, p["experts"]["w1"], p["experts"]["w2"]))
    return routed, slack


class LanguageModel:
    """The full forward over any rows under the block rule, attention and
    the expert layer one jitted piece each (a layer's float32 temporaries at
    ten thousand rows lie beside the served weights: the halves are compiled
    apart so that they fit)."""

    def __init__(self, config):
        self.shape = s = lm_shape(config)
        self._attn = jax.jit(lambda lp, x, rows: x + attention(
            lp["attn"], s, rms_norm(lp["attn_norm"]["scale"], x, s["eps"]),
            rows))

        def expert_layer(lp, x, served):
            out, slack = experts(
                lp["ffn"], s, rms_norm(lp["ffn_norm"]["scale"], x, s["eps"]),
                served)
            return x + out, slack

        self._experts = jax.jit(expert_layer)
        self._head = jax.jit(lambda p, x: rms_norm(
            p["final_norm"]["scale"], x, s["eps"]) @ f32(p["head"]["kernel"]))

    def hidden(self, params, ids, positions, view, served_experts=None):
        """Rows ``ids`` [R] at ``positions``, ``view`` [R] 0 for a committed
        row -> (the last layer's output [R, d], the worst router slack).
        ``served_experts`` [layers, R, top_k]: the routing the program
        chose, see `experts`."""
        positions = jnp.asarray(positions)
        rows = (positions, positions // self.shape["block"],
                jnp.asarray(view))
        x = f32(params["embed"][jnp.asarray(ids)])
        slack = 0.0
        for i, lp in enumerate(params["layers"]):
            x = self._attn(lp, x, rows)
            x, worst = self._experts(
                lp, x, None if served_experts is None
                else jnp.asarray(served_experts[i]))
            slack = max(slack, float(worst))
        return x, slack

    def logits(self, params, ids):
        """A sequence from position 0, all of it committed -> the logits AT
        every position [T, V] (a masked position's predict that position)."""
        x, _ = self.hidden(params, ids, np.arange(len(ids)),
                           np.zeros(len(ids), np.int32))
        return self._head(params, x)


def views_of(shape, prompt_len, new_ids, fixed_in_pass):
    """The denoise passes' inputs from what was served: new_ids [N] and the
    pass that fixed each [N] -> (ids [blocks, T, B]: MASK where fixed in
    that pass or later; positions [blocks, T, B]; whether the record is one
    the procedure can leave: every pass of every block fixed B / T ids)."""
    size, steps = shape["block"], shape["steps"]
    ids = np.asarray(new_ids).reshape(-1, 1, size)
    fixed = np.asarray(fixed_in_pass).reshape(-1, 1, size)
    passes = np.arange(steps)[None, :, None]
    views = np.where(fixed < passes, ids, shape["mask_id"])
    positions = prompt_len + np.arange(ids.size).reshape(-1, 1, size)
    valid = np.all((fixed == passes).sum(-1) == size // steps)
    return (views.astype(np.int32),
            np.broadcast_to(positions, views.shape), bool(valid))


def unmask_slack(shape, view_logits, new_ids, fixed_in_pass):
    """view_logits [blocks, T, B, V] (the reference's) -> the worst, over
    the views, of: the reference's (B / T)-th largest log confidence among
    the view's masked positions minus the lowest of the positions the
    program fixed there; and, at those, the reference's largest logit (the
    MASK id's left out) minus the served id's."""
    size, steps = shape["block"], shape["steps"]
    per = size // steps
    logits = np.array(view_logits, np.float64)
    logits[..., shape["mask_id"]] = -np.inf
    top = logits.max(-1)
    confidence = -np.log(np.exp(logits - top[..., None]).sum(-1))
    ids = np.asarray(new_ids).reshape(-1, 1, size)
    fixed = np.asarray(fixed_in_pass).reshape(-1, 1, size)
    passes = np.arange(steps)[None, :, None]
    masked, here = fixed >= passes, fixed == passes
    kth = np.sort(np.where(masked, confidence, -np.inf), axis=-1)[..., -per]
    lowest = np.where(here, confidence, np.inf).min(-1)
    of_id = np.take_along_axis(
        logits, np.broadcast_to(ids, fixed.shape[:1] + (steps, size))[
            ..., None], axis=-1)[..., 0]
    below = np.where(here, top - of_id, 0.0).max(-1)
    return float(max((kth - lowest).max(), below.max()))


def compare_served(lm, lm_weights, prompt, new_ids, served_logits, record):
    """One teacher-forced forward over the committed rows (prompt + served
    ids) and every denoise pass's view - and, in the expert layers, over the
    served choice of experts of all of them (``record``: what the decode
    program hands back beside ids and logits) - against the served logits of
    every id, from the pass that fixed it -> ({reading: value}, the share of
    ids whose largest logit agrees, the per-id errors)."""
    shape = lm.shape
    new_ids = np.asarray(new_ids)
    record = {k: np.asarray(v) for k, v in record.items()}
    fixed_in = record["fixed_in_pass"]
    n_rows = len(prompt) + len(new_ids)
    views, positions, valid = views_of(shape, len(prompt), new_ids, fixed_in)
    blocks, steps, size = views.shape
    ids = np.concatenate([prompt, new_ids, views.reshape(-1)])
    view = np.concatenate([
        np.zeros(n_rows, np.int32),
        1 + np.repeat(np.arange(blocks * steps, dtype=np.int32), size)])
    # [blocks, T, B, layers, top_k] -> [layers, blocks * T * B, top_k]
    of_views = np.moveaxis(record["denoise_experts"], 3, 0)
    routing = np.concatenate([
        record["experts"][:, :n_rows],
        of_views.reshape(of_views.shape[0], -1, of_views.shape[-1])], axis=1)
    x, slack = lm.hidden(
        lm_weights, ids,
        np.concatenate([np.arange(n_rows), positions.reshape(-1)]), view,
        served_experts=routing)
    view_logits = np.asarray(lm._head(lm_weights, x[n_rows:])).reshape(
        blocks, steps, size, -1)
    # an id's reference: its row of the view of the pass that fixed it
    at = np.arange(len(new_ids))
    reference = view_logits[at // size, np.clip(fixed_in, 0, steps - 1),
                            at % size]
    readings, agree, errors = logit_readings(served_logits, reference)
    readings["lm_router_slack_worst"] = slack
    readings["lm_unmask_slack_worst"] = unmask_slack(
        shape, view_logits, new_ids, fixed_in) if valid else float("inf")
    return readings, agree, errors


# -- prompt -> image ----------------------------------------------------------


class Reference(RewriteReference):
    """`reference/nemotron_h_sdxl.py Reference` with this language model:
    the printed comparison and the image from the served ids are its own."""

    def __init__(self, config, height, width):
        self.config, self.h, self.w = config, height, width
        self.lm = LanguageModel(config)
        self.limits = load_limits(config)
        self._clip = [
            jax.jit(lambda p, ids, c=c: clip_text(p, c, ids))
            for c in (config["text_encoder"], config["text_encoder_2"])]
        self._unet = jax.jit(
            lambda p, x, t, enc, te, tid: unet(p, config["unet"], x, t,
                                               enc, te, tid))
        self._decode = jax.jit(lambda p, z: C.vae_decode(p, config["vae"], z))

    def served_rewrite(self, request):
        """The served rewrite of this request, from the family module."""
        from benchmark.families.deepseek_v3_sdxl import latest_served

        want = prompt_ids(self.config, request["prompt"])
        for served in reversed(latest_served()):
            if np.array_equal(served.prompt_ids, want):
                return want, served
        raise LookupError(
            "the program kept no served rewrite whose prompt ids are the "
            "reference's own for this request")

    def compare_logits(self, lm_weights, prompt, served):
        """`compare_served` over one served rewrite -> [(name, value,
        limit, ok)], and the share of ids whose largest logit agrees."""
        readings, agree, self.position_errors = compare_served(
            self.lm, lm_weights, prompt, served.new_ids, served.logits,
            served.experts[1])
        return [(name, value, self.limits[name]["limit"],
                 bool(value <= self.limits[name]["limit"]))
                for name, value in readings.items()], agree
