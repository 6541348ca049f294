"""Plain float32 reference of the think-then-rewrite cell: the Nemotron-H
language model's full forward, then few-step SDXL from the ids it ended on.

The language model, as its published description has it (and each departure
in the configuration's `assumed`): every layer x <- x + mixer(RMSNorm(x)),
the mixer a Mamba-2 block (``M``: the selective scan as the SEQUENTIAL
recurrence, one token after another), causal grouped-query attention (``*``:
no position embedding, keys and values repeated per query head, no cache)
or latent sparse experts (``E``: sigmoid router in float32, the 22 largest
of s + bias, weights 5 s_i / sum of the chosen s, then a DENSE loop over the
experts held - every held expert over every token, weighted by the router's
weight or zero; teacher-forced over the program's choice of experts where it
is given one, see `experts`).  It is given the same share of the model as the program:
the experts held, the slice of the vocabulary.  No kernels, no chunking, no
state carried between calls.  It imports nothing of `distrifuser_tpu`.

What decides `correct` (run.py's one hook is `generate`): the request's
served ids and the served logits of every decoded position are taken from
the family module, which kept them; the reference runs ONE teacher-forced
forward over prompt + served ids and compares logits position by position -
not tokens: with seeded weights the largest logit changes on rounding.  It
prints every comparison with its limit on one line, goes on to the image
from the served ids (two CLIP towers, 4-step Euler without guidance, VAE
decode: `unet_sdxl`'s pieces), and hands back an image of NaNs if a logit
limit failed, so the harness's `image_rel_rmse` check fails and `correct` is
false.
"""

import json
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from . import _common as C
from ._common import F32, f32, silu
from .unet_sdxl import clip_text, unet

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the language model -------------------------------------------------------


def lm_shape(config):
    """The sizes the reference needs, from the configuration's keys."""
    ep = config.get("expert_parallel", {"chips": 1, "index": 0})
    start = config.get("layer_offset", 0)
    held = config["n_routed_experts"]
    return {
        "pattern": config["hybrid_override_pattern"][
            start:start + config["num_hidden_layers"]],
        "eps": config["norm_eps"],
        "heads": config["mamba_num_heads"], "head_dim": config["mamba_head_dim"],
        "groups": config["n_groups"], "state": config["ssm_state_size"],
        "q_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "attn_dim": config["head_dim"],
        "first_expert": held * ep["index"], "held": held,
        "top_k": config["num_experts_per_tok"],
        "scale": config["routed_scaling_factor"],
    }


def rms_norm(scale, x, eps, groups=1):
    shape = x.shape
    x = x.reshape(shape[:-1] + (groups, shape[-1] // groups))
    x = x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return x.reshape(shape) * f32(scale)


def mamba(p, s, u):
    """u [T, D] -> [T, D]: in-projection, causal depthwise convolution,
    the recurrence S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,
    y_t = S_t C_t + D x_t token by token, gate, grouped norm, out."""
    t = u.shape[0]
    h, pd, g, n = s["heads"], s["head_dim"], s["groups"], s["state"]
    di, gn = h * pd, g * n
    z, xbc, dt = jnp.split(u @ f32(p["in_proj"]["kernel"]),
                           [di, 2 * di + 2 * gn], axis=-1)
    w = f32(p["conv"]["kernel"])
    k = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), F32), xbc])
    xbc = silu(f32(p["conv"]["bias"])
               + sum(padded[i:i + t] * w[i] for i in range(k)))
    x, b, c = jnp.split(xbc, [di, di + gn], axis=-1)
    x = x.reshape(t, h, pd)
    b = jnp.repeat(b.reshape(t, g, n), h // g, axis=1)  # [T, H, N]
    c = jnp.repeat(c.reshape(t, g, n), h // g, axis=1)
    dt = jax.nn.softplus(dt + f32(p["dt_bias"]))  # [T, H]
    a = -jnp.exp(f32(p["A_log"]))

    def token(state, inputs):
        x_t, b_t, c_t, dt_t = inputs
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((h, pd, n), F32), (x, b, c, dt))
    y = y + f32(p["D"])[None, :, None] * x
    y = y.reshape(t, di) * silu(z)
    y = rms_norm(p["norm"]["scale"], y, s["eps"], groups=g)
    return y @ f32(p["out_proj"]["kernel"])


def gqa(p, s, u):
    """Causal attention, each KV head repeated for its query heads."""
    t = u.shape[0]
    hq, hkv, d = s["q_heads"], s["kv_heads"], s["attn_dim"]
    q, k, v = jnp.split(u @ f32(p["qkv"]["kernel"]),
                        [hq * d, (hq + hkv) * d], axis=-1)
    k = jnp.repeat(k.reshape(1, t, hkv, d), hq // hkv, axis=2)
    v = jnp.repeat(v.reshape(1, t, hkv, d), hq // hkv, axis=2)
    causal = jnp.triu(jnp.full((t, t), -jnp.inf, F32), k=1)[None, None]
    out = C.attention(q[None], k.reshape(1, t, hq * d),
                      v.reshape(1, t, hq * d), hq, causal)
    return out[0] @ f32(p["o_proj"]["kernel"])


def experts(p, s, u, served=None):
    """Router over all experts; of the chosen, those held here computed by
    a dense loop (every held expert over every token); shared expert.
    -> (out [T, D], router slack).

    ``served`` [T, top_k]: the experts the program chose.  Which 22 scores
    are the largest changes on rounding, as the largest logit does, so the
    comparison is teacher-forced here too: the reference's own float32
    scores decide whether the served choice was a sound one - the slack is
    how far below the reference's own 22nd largest s + bias the lowest
    served choice lies, 0 for the same set, infinite for a repeated or
    unknown expert - and the layer is then computed over the served choice,
    with the reference's scores for weights."""
    scores = jax.nn.sigmoid(u @ f32(p["router"]["kernel"]))
    select = scores + f32(p["e_score_correction_bias"])
    kth, idx = jax.lax.top_k(select, s["top_k"])
    slack = jnp.zeros(())
    if served is not None:
        idx = jnp.sort(served, axis=-1)
        valid = jnp.all(idx[:, 1:] > idx[:, :-1]) & (idx.min() >= 0) & (
            idx.max() < scores.shape[-1])
        idx = jnp.clip(idx, 0, scores.shape[-1] - 1)
        lowest = jnp.take_along_axis(select, idx, axis=-1).min(-1)
        slack = jnp.where(valid, jnp.max(kth[:, -1] - lowest), jnp.inf)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = s["scale"] * chosen / chosen.sum(-1, keepdims=True)
    latent = u @ f32(p["down"]["kernel"])

    def one(total, expert):
        e, w1, w2 = expert
        w_e = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)  # [T]
        out = jnp.square(jax.nn.relu(latent @ f32(w1))) @ f32(w2)
        return total + w_e[:, None] * out, None

    ids = s["first_expert"] + jnp.arange(s["held"])
    routed, _ = jax.lax.scan(one, jnp.zeros_like(latent),
                             (ids, p["experts"]["w1"], p["experts"]["w2"]))
    sh = p["shared"]
    shared = jnp.square(jax.nn.relu(u @ f32(sh["fc1"]["kernel"]))) \
        @ f32(sh["fc2"]["kernel"])
    return routed @ f32(p["up"]["kernel"]) + shared, slack


MIXERS = {"M": mamba, "*": gqa}


class LanguageModel:
    """The full forward, one jitted piece per kind of layer."""

    def __init__(self, config):
        self.shape = s = lm_shape(config)

        def normed(lp, x):
            return rms_norm(lp["norm"]["scale"], x, s["eps"])

        self._layer = {
            kind: jax.jit(lambda lp, x, fn=fn: x + fn(lp["mixer"], s,
                                                     normed(lp, x)))
            for kind, fn in MIXERS.items()}

        def expert_layer(lp, x, served):
            out, slack = experts(lp["mixer"], s, normed(lp, x), served)
            return x + out, slack

        self._experts = jax.jit(expert_layer)
        self._head = jax.jit(lambda p, x: rms_norm(
            p["final_norm"]["scale"], x, s["eps"]) @ f32(p["head"]["kernel"]))

    def hidden(self, params, ids, served_experts=None):
        """ids [T] -> (the last layer's output [T, D], the worst router
        slack over the E layers).  ``served_experts`` [E layers, T, top_k]:
        the routing the program chose, see `experts`."""
        x = f32(params["embed"][jnp.asarray(ids)])
        slack, e = 0.0, 0
        for kind, lp in zip(self.shape["pattern"], params["layers"]):
            if kind == "E":
                x, worst = self._experts(
                    lp, x, None if served_experts is None
                    else jnp.asarray(served_experts[e]))
                slack, e = max(slack, float(worst)), e + 1
            else:
                x = self._layer[kind](lp, x)
        return x, slack

    def logits(self, params, ids, first=0, served_experts=None):
        """(logits after each of the tokens ``first`` onward [T - first, V],
        the worst router slack)."""
        x, slack = self.hidden(params, ids, served_experts)
        return self._head(params, x[first:]), slack


def prompt_ids(config, prompt):
    """The language model's prompt: the instruction drawn from its seed,
    then the caller's words through the word hash, cut or repeated."""
    rw = config["rewrite"]
    vocab = config["vocab_size"]
    rng = np.random.default_rng(rw["instruction_seed"])
    instruction = rng.integers(0, vocab, rw["instruction_tokens"])
    words = [zlib.crc32(w.encode()) % vocab
             for w in prompt.lower().split()] or [0]
    n = rw["user_tokens"]
    user = (words * -(-n // len(words)))[:n]
    return np.concatenate([instruction, user]).astype(np.int32)


def position_errors(served, reference):
    """[new_tokens, V] served against reference -> per decoded position,
    RMS(difference) / std(reference row)."""
    served, reference = np.asarray(served, np.float64), np.asarray(
        reference, np.float64)
    diff = np.sqrt(np.mean(np.square(served - reference), axis=1))
    return diff / np.maximum(reference.std(axis=1), 1e-12)


def logit_readings(served, reference):
    """The numbers compared: the median of `position_errors` (what rounding
    moves, everywhere at once), their median over the last quarter of the
    decoded positions (a state kept in too low a precision drifts as it
    integrates: the late positions show it first) and their worst (a path
    broken at one position).  -> (the readings, the share of positions
    whose largest logit agrees, the per-position errors)."""
    rel = position_errors(served, reference)
    agree = float(np.mean(np.asarray(served).argmax(1)
                          == np.asarray(reference).argmax(1)))
    return {"lm_logit_rel_rmse_median": float(np.median(rel)),
            "lm_logit_rel_rmse_late": float(np.median(rel[-(len(rel) // 4):])),
            "lm_logit_rel_rmse_worst": float(rel.max())}, agree, rel


# -- prompt -> image ----------------------------------------------------------


def euler_tables(sched, steps):
    """(timesteps, sigmas with a final 0) of diffusers' EulerDiscrete,
    leading spacing."""
    ac = C.alphas_cumprod(sched)
    ts = C.leading_timesteps(sched, steps)
    sigmas = np.sqrt((1.0 - ac[ts]) / ac[ts])
    return ts, np.append(sigmas, 0.0).astype(np.float32)


def load_limits(config):
    lim = config["limits"]
    with open(os.path.join(BENCH_DIR, "limits", lim["file"] + ".json")) as f:
        limits = json.load(f)
    return limits[lim["section"]] if lim["section"] != "served" else limits


class Reference:
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
        from benchmark.families.nemotron_h_sdxl import latest_served

        want = prompt_ids(self.config, request["prompt"])
        for served in reversed(latest_served()):
            if np.array_equal(served.prompt_ids, want):
                return want, served
        raise LookupError(
            "the program kept no served rewrite whose prompt ids are the "
            "reference's own for this request")

    def compare_logits(self, lm_weights, prompt, served):
        """One teacher-forced forward over prompt + served ids - and, in the
        expert layers, over the served choice of experts - against the
        served logits -> [(name, value, limit, ok)], and the share of
        positions whose largest logit agrees."""
        new_ids = np.asarray(served.new_ids)
        ids = np.concatenate([prompt, new_ids[:-1]])
        of_prompt, of_new = (np.asarray(a) for a in served.experts)
        routing = np.concatenate([of_prompt, of_new[:-1].swapaxes(0, 1)],
                                 axis=1)
        reference, slack = self.lm.logits(lm_weights, ids,
                                          first=len(prompt) - 1,
                                          served_experts=routing)
        readings, agree, self.position_errors = logit_readings(
            served.logits, reference)
        readings["lm_router_slack_worst"] = slack
        return [(name, value, self.limits[name]["limit"],
                 bool(value <= self.limits[name]["limit"]))
                for name, value in readings.items()], agree

    def generate(self, weights, request):
        with jax.default_matmul_precision("highest"):
            return self._generate(weights, request)

    def _generate(self, weights, request):
        if request["guidance_scale"] > 1.0:
            raise NotImplementedError("the reference runs the cell's recipe: "
                                      "no guidance")
        prompt, served = self.served_rewrite(request)
        new_ids = np.asarray(served.new_ids)
        checks, agree = self.compare_logits(weights["lm"], prompt, served)
        print("lm logits, served against the float32 reference over "
              f"{len(new_ids)} decoded positions (largest logit agrees at "
              f"{100 * agree:.1f}%): " + "; ".join(
                  f"{name} value={value:.6g} limit={limit} "
                  f"{'ok' if ok else 'FAILED'}"
                  for name, value, limit, ok in checks), flush=True)
        image = self.image(weights, new_ids, request)
        if not all(ok for *_, ok in checks):
            return np.full_like(image, np.nan)
        return image

    def image(self, weights, new_ids, request):
        """The served ids' last ``prompt_tokens`` -> CLIP ids (an id's word
        is its decimal string) -> 4-step Euler, one UNet row -> image."""
        tok = self.config["tokenizer"]
        n = min(self.config["rewrite"]["prompt_tokens"],
                tok["model_max_length"] - 2)
        text = " ".join(str(int(i)) for i in new_ids[-n:])
        out = []
        for fn, p, c in zip(self._clip, weights["text"],
                            (self.config["text_encoder"],
                             self.config["text_encoder_2"])):
            ids = C.hash_tokenize([text], c["vocab_size"],
                                  tok["eos_token_id"], tok["bos_token_id"],
                                  tok["model_max_length"])
            out.append(fn(p, ids))
        (h1, _), (h2, pooled) = out
        enc = jnp.concatenate([h1[-2], h2[-2]], axis=-1)
        time_ids = jnp.asarray([[self.h, self.w, 0, 0, self.h, self.w]], F32)
        ts, sigmas = euler_tables(self.config["scheduler"], request["steps"])
        cin = self.config["unet"]["in_channels"]
        x = C.request_noise(request["seed"],
                            (self.h // 8, self.w // 8, cin))[None]
        x = x * np.sqrt(sigmas.max() ** 2 + 1.0)
        for i in range(request["steps"]):
            eps = self._unet(weights["unet"],
                             x / np.sqrt(sigmas[i] ** 2 + 1.0), int(ts[i]),
                             enc, pooled, time_ids)
            x = x + (sigmas[i + 1] - sigmas[i]) * eps
        return np.asarray(self._decode(weights["vae"], x)[0], np.float32)
