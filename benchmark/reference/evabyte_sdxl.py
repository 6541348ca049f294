"""Plain float32 reference of the byte-level think-then-rewrite cell: the
EvaByte language model's full forward, then few-step SDXL from the bytes it
ended on.

The language model, as its published configuration has it (and each
departure in the configuration's `assumed`): residual stream h,

    h <- h + Attn(RMSNorm(h)),   h <- h + W_down(silu(W_gate x) * W_up x)
    RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + w);  logits = RMSNorm(h) W_head

`Attn` is EVA: q, k, v = x W; rotary embedding (rotate-half, whole head) on
q and k; every COMPLETE chunk j of 16 positions pooled into one row,
a = softmax_m(phi . k_m), ks_j = sum a_m k_m + mu, vs_j = sum a_m v_m; the
query at t attends in one softmax over the keys of its own 2048-window up
to itself and the summaries of the chunks of every earlier window.  Here
that is an explicit mask [queries of a window, that window's keys +
summaries], one window after another only so that it fits: no cache, no
ring, no blocks of queries, no state carried between calls.  It imports
nothing of `distrifuser_tpu`.

What decides `correct` (run.py's one hook is `generate`): the request's
served bytes and the served logits of every decoded position - all eight
blocks of the head, 2560 columns - are taken from the family module, which
kept them; the reference runs ONE teacher-forced forward over prompt +
served bytes and compares logits position by position.  It prints every
comparison with its limit on one line, goes on to the image from the served
bytes (the 4-byte group rule, two CLIP towers, 4-step Euler without
guidance, VAE decode: `unet_sdxl`'s pieces), and hands back an image of
NaNs if a logit limit failed, so the harness's `image_rel_rmse` check fails
and `correct` is false.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import _common as C
from ._common import F32, f32, silu
from .nemotron_h_sdxl import euler_tables, load_limits, logit_readings
from .unet_sdxl import clip_text, unet

GROUP_BYTES, GROUP_BASE = 4, 331


# -- the language model -------------------------------------------------------


def lm_shape(config):
    """The sizes the reference needs, from the configuration's keys."""
    return {"heads": config["num_attention_heads"],
            "head_dim": config["hidden_size"] // config["num_attention_heads"],
            "window": config["window_size"], "chunk": config["chunk_size"],
            "eps": config["rms_norm_eps"], "theta": config["rope_theta"]}


def rms_norm(w, x, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * (1.0 + f32(w))


def rotary(x, theta):
    """x [T, H, D] at positions 0 .. T - 1, rotate-half over the whole head."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angle = jnp.arange(t, dtype=F32)[:, None] * inv[None]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(angle) + rotated * jnp.sin(angle)


def eva_attention(p, s, x):
    """x [T, d] -> [T, d]."""
    t = x.shape[0]
    h, d, window, chunk = s["heads"], s["head_dim"], s["window"], s["chunk"]
    q, k, v = (a.reshape(t, h, d) for a in
               jnp.split(x @ f32(p["qkv"]["kernel"]), 3, axis=-1))
    q, k = rotary(q, s["theta"]), rotary(k, s["theta"])
    n = t // chunk  # complete chunks
    kc, vc = (a[:n * chunk].reshape(n, chunk, h, d) for a in (k, v))
    a = jax.nn.softmax(jnp.einsum("jmhd,hd->jmh", kc, f32(p["phi"])), axis=1)
    ks = jnp.sum(a[..., None] * kc, axis=1) + f32(p["mu"])
    vs = jnp.sum(a[..., None] * vc, axis=1)
    out = []
    for lo in range(0, t, window):
        hi, earlier = min(t, lo + window), lo // chunk
        keys = jnp.concatenate([k[lo:hi], ks[:earlier]])
        values = jnp.concatenate([v[lo:hi], vs[:earlier]])
        mask = jnp.concatenate([
            jnp.tril(jnp.ones((hi - lo, hi - lo), bool)),
            jnp.ones((hi - lo, earlier), bool)], axis=1)
        logits = jnp.einsum("thd,shd->hts", q[lo:hi], keys) / np.sqrt(d)
        w = jax.nn.softmax(jnp.where(mask[None], logits, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hts,shd->thd", w, values))
    return jnp.concatenate(out).reshape(t, h * d) @ f32(p["o_proj"]["kernel"])


def mlp(p, x):
    gate, up = jnp.split(x @ f32(p["gate_up"]["kernel"]), 2, axis=-1)
    return (silu(gate) * up) @ f32(p["down"]["kernel"])


class LanguageModel:
    """The full forward, attention and feed-forward each one jitted piece
    (a layer's float32 temporaries at 4352 positions lie beside 13 GB of
    served weights: the two halves are compiled apart so that they fit)."""

    def __init__(self, config):
        self.shape = s = lm_shape(config)
        self._attn = jax.jit(lambda lp, h: h + eva_attention(
            lp["attn"], s, rms_norm(lp["attn_norm"]["scale"], h, s["eps"])))
        self._mlp = jax.jit(lambda lp, h: h + mlp(
            lp["mlp"], rms_norm(lp["mlp_norm"]["scale"], h, s["eps"])))
        self._head = jax.jit(lambda p, h: rms_norm(
            p["final_norm"]["scale"], h, s["eps"]) @ f32(p["head"]["kernel"]))

    def logits(self, params, ids, first=0):
        """ids [T] -> the logits after each of the ids ``first`` onward
        [T - first, 8 * V]."""
        h = f32(params["embed"][jnp.asarray(ids)])
        for lp in params["layers"]:
            h = self._mlp(lp, self._attn(lp, h))
        return self._head(params, h[first:])


def prompt_ids(config, prompt):
    """The language model's prompt: the instruction drawn from its seed over
    the 256 byte ids, then the caller's UTF-8 bytes cut or repeated, each
    plus the tokenizer's offset."""
    rw, offset = config["rewrite"], config["byte_offset"]
    rng = np.random.default_rng(rw["instruction_seed"])
    instruction = rng.integers(0, 256, rw["instruction_tokens"])
    text = list(prompt.encode("utf-8")) or [ord(" ")]
    n = rw["user_tokens"]
    user = (text * -(-n // len(text)))[:n]
    return (np.concatenate([instruction, user]) + offset).astype(np.int32)


def group_ids(byte_ids, vocab_size):
    """The 4-byte group rule: ids [4 n] of the language model -> n ids of a
    text encoder's vocabulary (its last two are BOS and EOS): the group read
    as a number in base 331, modulo the vocabulary less two."""
    groups = np.asarray(byte_ids, np.int64).reshape(-1, GROUP_BYTES)
    weights = GROUP_BASE ** np.arange(GROUP_BYTES - 1, -1, -1)
    return (groups @ weights) % (vocab_size - 2)


# -- prompt -> image ----------------------------------------------------------


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
        from benchmark.families.evabyte_sdxl import latest_served

        want = prompt_ids(self.config, request["prompt"])
        for served in reversed(latest_served()):
            if np.array_equal(served.prompt_ids, want):
                return want, served
        raise LookupError(
            "the program kept no served rewrite whose prompt ids are the "
            "reference's own for this request")

    def compare_logits(self, lm_weights, prompt, served):
        """One teacher-forced forward over prompt + served bytes against the
        served logits, all eight blocks -> [(name, value, limit, ok)], and
        the share of positions whose largest block-0 logit agrees."""
        new_ids = np.asarray(served.new_ids)
        ids = np.concatenate([prompt, new_ids[:-1]])
        reference = np.asarray(self.lm.logits(lm_weights, ids,
                                              first=len(prompt) - 1))
        readings, _, self.position_errors = logit_readings(
            served.logits, reference)
        vocab = self.config["vocab_size"]
        agree = float(np.mean(reference[:, :vocab].argmax(1) == new_ids))
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
              f"{len(new_ids)} decoded positions x "
              f"{np.asarray(served.logits).shape[1]} columns (largest "
              f"block-0 logit agrees at {100 * agree:.1f}%): " + "; ".join(
                  f"{name} value={value:.6g} limit={limit} "
                  f"{'ok' if ok else 'FAILED'}"
                  for name, value, limit, ok in checks), flush=True)
        image = self.image(weights, new_ids, request)
        if not all(ok for *_, ok in checks):
            return np.full_like(image, np.nan)
        return image

    def image(self, weights, new_ids, request):
        """The served bytes' last ``prompt_tokens`` -> CLIP ids by the group
        rule, BOS first, EOS to the end -> 4-step Euler, one UNet row ->
        image."""
        tok = self.config["tokenizer"]
        length = tok["model_max_length"]
        n = min(self.config["rewrite"]["prompt_tokens"] // GROUP_BYTES,
                length - 2)
        out = []
        for fn, p, c in zip(self._clip, weights["text"],
                            (self.config["text_encoder"],
                             self.config["text_encoder_2"])):
            ids = np.full((1, length), tok["eos_token_id"], np.int64)
            ids[0, 0] = tok["bos_token_id"]
            ids[0, 1:1 + n] = group_ids(new_ids[-GROUP_BYTES * n:],
                                        c["vocab_size"])
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
