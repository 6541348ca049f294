"""User-facing pipelines: DistriSDXLPipeline and DistriSDPipeline.

API parity with the reference (/root/reference/distrifuser/pipelines.py):
``from_pretrained(distri_config, pretrained_model_name_or_path, ...)`` then
``pipeline(prompt=..., seed=...)`` returning an object with ``.images``.
Differences are the TPU-native ones:

* The reference wraps a diffusers pipeline and swaps the UNet
  (pipelines.py:26-42); here the whole stack (text encoders, UNet, VAE,
  scheduler, denoise loop) is native JAX, and the denoise loop is one
  compiled program (parallel/runner.py) instead of CUDA-graph replay.
* ``prepare()`` (pipelines.py:60-165: record passes, buffer allocation,
  graph capture) reduces to ahead-of-time compilation of the loop — state
  buffers are created *by* the first traced step.
* Weights come from a local HuggingFace snapshot directory (safetensors),
  converted once via models/weights.py; ``from_params`` builds a pipeline
  from in-memory pytrees (tests, random weights).

Height/width are fixed at DistriConfig time exactly like the reference
(pipelines.py:47-55 forbids per-call height/width); guidance_scale is forced
to 1 when CFG is disabled (pipelines.py:52-58 — with its double-negation bug
fixed, SURVEY.md §2.6).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import os
import sys
import weakref
import zlib
from typing import Any, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .models import clip as clip_mod
from .models import unet as unet_mod
from .models import vae as vae_mod
from .models.language_model import POSITION_AXIS
from .models.weights import (
    convert_clip_state_dict,
    convert_unet_state_dict,
    convert_vae_state_dict,
    load_sharded_safetensors,
    params_nbytes,
    quantize_params,
)
from .parallel.runner import make_runner
from .schedulers import BaseScheduler, FlowMatchEulerScheduler, get_scheduler
from .utils import sync
from .utils.config import DistriConfig
from .utils.trace import phased, phases, span


class SimpleTokenizer:
    """Deterministic hash fallback tokenizer.

    Real generation quality needs the CLIP BPE vocab (pass a HF tokenizer or
    a snapshot dir to from_pretrained); this fallback keeps every pipeline
    path runnable — tests, benchmarks, random-weight smoke runs — on a box
    with no vocab files.
    """

    model_max_length = 77

    def __init__(self, vocab_size: int = 49408, eos: int = 49407, bos: int = 49406):
        self.vocab_size = vocab_size
        self.eos = eos
        self.bos = bos

    def __call__(self, texts: List[str], max_length: int = 77):
        ids = np.full((len(texts), max_length), self.eos, np.int64)
        for i, t in enumerate(texts):
            # crc32, not hash(): process-independent, so multi-host pods and
            # repeated runs tokenize identically
            toks = [self.bos] + [
                word_hash(w, self.vocab_size - 2)
                for w in t.lower().split()
            ][: max_length - 2]
            toks.append(self.eos)
            ids[i, : len(toks)] = toks
        return ids


def _hf_tokenizer(path: str):
    from transformers import CLIPTokenizer

    return CLIPTokenizer.from_pretrained(path)


def _tokenizer_or_fallback(path: str):
    """Native BPE tokenizer, else transformers, else the hash tokenizer with
    a LOUD warning.

    The primary is the in-repo native engine (native/bpe.py + clip_bpe.cc),
    which reads the snapshot's vocab.json/merges.txt directly — id-level
    parity with transformers is pinned by tests/test_native_tokenizer.py.
    The last-resort fallback keeps weightless smoke tests running, but on a
    real snapshot a broken tokenizer dir would silently ruin every generated
    image — so the degradation must never be silent."""
    try:
        from .native.bpe import NativeCLIPTokenizer

        return NativeCLIPTokenizer(path)
    except Exception:
        pass  # fall through to transformers (missing files error below)
    try:
        return _hf_tokenizer(path)
    except Exception as e:
        print(
            f"WARNING: failed to load CLIP tokenizer from {path!r} "
            f"({type(e).__name__}: {e}); falling back to the hash-based "
            "SimpleTokenizer. Generated images will NOT match real-prompt "
            "outputs.",
            file=sys.stderr,
            flush=True,
        )
        return SimpleTokenizer()


def _config_from_snapshot(root: str, subdir: str, loader, fallback):
    """Derive a model config from the snapshot's `<subdir>/config.json`
    (the way diffusers from_pretrained instantiates the architecture for the
    reference, /root/reference/distrifuser/pipelines.py:30-42); fall back to
    the named preset for bare weight dumps without config files."""
    path = os.path.join(root, subdir, "config.json")
    return loader(path) if os.path.exists(path) else fallback()


def _scheduler_from_snapshot(root: str, name: str | BaseScheduler) -> BaseScheduler:
    """Build the scheduler, honoring the snapshot's scheduler_config.json
    (prediction_type / betas / train steps) — this is how SD 2.x's
    v-prediction flows in, the way diffusers from_pretrained wires it for the
    reference."""
    if isinstance(name, BaseScheduler):
        return name
    kwargs = {}
    cfg_path = os.path.join(root, "scheduler", "scheduler_config.json")
    if os.path.exists(cfg_path):
        import json

        with open(cfg_path) as f:
            sc = json.load(f)
        for k in ("num_train_timesteps", "beta_start", "beta_end",
                  "beta_schedule", "steps_offset", "prediction_type"):
            if k in sc:
                kwargs[k] = sc[k]
    return get_scheduler(name, **kwargs)


def _prepare_init_latents(cfg, scheduler, encode_image, vae_config, image,
                          strength, num_inference_steps, n_prompts,
                          num_images_per_prompt, seed):
    """Shared img2img entry for every pipeline family: VAE-encode the init
    image (with the SD3-family shift re-centering — zero for the legacy
    families), noise it to the strength-offset schedule point, and return
    (latents, start_step) for the tail-only denoise.

    Canonical input range: uint8 [0, 255] or float [0, 1] (what
    output_type="np" produces) — no value sniffing beyond the dtype.
    Expansion is prompt-major, matching _batched_generate.  At least one
    denoise step always runs (strength*steps < 1 would otherwise ask for
    a zero-length schedule)."""
    assert 0.0 < strength <= 1.0, strength
    init_timestep = min(max(int(num_inference_steps * strength), 1),
                        num_inference_steps)
    start_step = num_inference_steps - init_timestep
    arr = np.asarray(image)
    arr = (arr.astype(np.float32) / 255.0 if arr.dtype == np.uint8
           else arr.astype(np.float32))
    if arr.ndim == 3:
        arr = arr[None]
    if arr.min() < 0.0 or arr.max() > 1.0:
        raise ValueError(
            "init image must be uint8 [0,255] or float [0,1] "
            f"(got range [{arr.min():.3f}, {arr.max():.3f}])"
        )
    arr = arr * 2.0 - 1.0  # VAE input range [-1,1]
    n_img = arr.shape[0]
    assert n_img in (1, n_prompts), (
        f"{n_img} init images for {n_prompts} prompts"
    )
    init = (
        encode_image(jnp.asarray(arr)) - vae_config.shift_factor
    ) * vae_config.scaling_factor
    assert init.shape[1:3] == (cfg.latent_height, cfg.latent_width), (
        f"init image encodes to {init.shape[1:3]}, config wants "
        f"{(cfg.latent_height, cfg.latent_width)}"
    )
    if n_img == 1 and n_prompts > 1:
        init = jnp.tile(init, (n_prompts, 1, 1, 1))
    init = jnp.repeat(init, num_images_per_prompt, axis=0)
    noise = jax.random.normal(jax.random.PRNGKey(seed), init.shape,
                              jnp.float32)
    return scheduler.add_noise(init, noise, start_step), start_step


def _check_scheduler_family(scheduler: BaseScheduler, *, flow: bool,
                            family: str) -> None:
    """Reject scheduler/model-family mismatches LOUDLY at construction.

    A rectified-flow sampler integrates the model output as a velocity
    over flow sigmas; the diffusion samplers integrate it as
    epsilon/v over beta schedules.  Crossing them runs without error and
    produces garbage images — the one failure mode a user cannot debug
    from the output alone, so every pipeline constructor calls this.
    """
    is_flow = isinstance(scheduler, FlowMatchEulerScheduler)
    if flow and not is_flow:
        raise ValueError(
            f"{family} is a rectified-flow model family: the scheduler "
            "must be FlowMatchEulerScheduler ('flow-euler'), got "
            f"{type(scheduler).__name__}"
        )
    if not flow and is_flow:
        raise ValueError(
            f"'flow-euler' on {family}: this family predicts epsilon/v "
            "over a beta schedule, not a rectified-flow velocity — use "
            "ddim / euler / dpm-solver ('flow-euler' is for "
            "DistriSD3Pipeline)"
        )


def _tokenize(tok, texts: List[str]) -> np.ndarray:
    if isinstance(tok, SimpleTokenizer):
        return tok(texts)
    out = tok(
        texts, padding="max_length", max_length=tok.model_max_length,
        truncation=True, return_tensors="np",
    )
    return np.asarray(out["input_ids"])


@dataclasses.dataclass(frozen=True)
class RewriteSpec:
    """The rewrite stage's lengths, in the language model's ids (words, or
    bytes for a byte-level model): a fixed instruction of
    ``instruction_tokens`` ids (drawn once from ``instruction_seed`` over the
    ids a text can hold), then ``user_tokens`` ids of the caller's text,
    ``new_tokens`` decoded greedily with no early stop (whole trips of the
    model's decode loop: `LanguageModel.decode_multiple`), of which the last
    ``prompt_tokens`` are the rewritten prompt (what follows the thinking
    trace, by position)."""

    instruction_tokens: int
    user_tokens: int
    new_tokens: int
    prompt_tokens: int
    instruction_seed: int = 0


class ServedRewrite(NamedTuple):
    """What one request's rewrite read and wrote: ``prompt_ids`` on the
    host, the rest still on the device."""

    prompt_ids: np.ndarray  # [instruction + user] int32
    new_ids: Any  # [new_tokens] int32
    # [new_tokens, ...] float32: what each id was chosen from (a head of
    # several predictions a position: all of them)
    logits: Any
    counters: Any  # int32, one a name of the model's `LanguageModel.counters`
    # what the model records beside ids and logits, of the prompt and of the
    # decoded ids (a model with routed experts: the experts every token
    # chose, [E layers, prompt, top_k] and [new_tokens, E layers, top_k];
    # one that decodes a block of positions in several passes: in which
    # pass each id was fixed, and the experts every pass's rows chose; one
    # with nothing to record: (), ())
    experts: Any


def word_hash(word: str, vocab_size: int) -> int:
    """crc32 of a word modulo a vocabulary, process-independent: the
    weightless tokenizers' one rule (`SimpleTokenizer` keeps the last two
    ids of its vocabulary for BOS / EOS)."""
    return zlib.crc32(word.encode()) % vocab_size


# a byte-level model's way out: GROUP_BYTES decoded ids are one text-encoder id
GROUP_BYTES, GROUP_BASE = 4, 331


def byte_group_ids(ids, modulus: int):
    """The 4-byte group rule: ids [4 n] of a byte-level model -> [n] ids
    below ``modulus``: each group read as a number in base 331 (first byte
    most significant), modulo ``modulus`` - reduced after every digit, so
    that it stays inside int32 on the device and is the same number on the
    host."""
    groups = ids.reshape(-1, GROUP_BYTES)
    out = groups[:, 0] % modulus
    for digit in range(1, GROUP_BYTES):
        out = (out * GROUP_BASE + groups[:, digit]) % modulus
    return out


class PromptRewriter:
    """Think, then rewrite: the stage in front of the text encoders.

    The language model is a value: ``config.language_model()`` gives its
    `models.language_model.LanguageModel` record (prefill, decode, the names
    of its counters, the multiple a prompt's length must keep, whether it
    reads words or bytes), and nothing here names a model.

    A request's prompt and a fixed instruction go through the model: one
    prefill program over exactly ``instruction_tokens + user_tokens`` ids -
    the caller's words through the word hash, or for a byte-level model the
    caller's text as its UTF-8 bytes (each plus the model's byte offset), cut
    or repeated to ``user_tokens`` so that there is one compiled shape and no
    padding to mask out of a state - then one decode program that runs all
    ``new_tokens`` greedy steps on the device, with no host visit a token,
    and ends by turning the last ``prompt_tokens`` ids into ids of the text
    encoders' vocabularies: a word id through a table of its word hash there
    (an id's word is its decimal string: no vocabulary ships with the repo),
    bytes four at a time through `byte_group_ids`.  So the ids reach the
    encoders without visiting the host and the request path stays
    asynchronous up to the image's copy.

    The instruction is the same in every request.  Where the model can take
    a prompt's suffix into the state its prefix left
    (`LanguageModel.prefill_from`), the instruction - as much of it as is
    whole ``prompt_multiple``s - is prefilled ONCE, by a program of its own
    (``rewrite_prefix``, on the first call: a server's warm-up request), and
    its decode state, its counters and what the model records of those ids
    are kept: the **snapshot**, this rewriter's, beside the weights for as
    long as a pipeline holds the rewriter (`drop_snapshot`).  A request's
    prefill program then takes the snapshot - read, not donated - and the
    remaining ids, and returns what the prefill of all the ids returns: the
    record too, the snapshot's positions put in front of the request's own
    inside that program.  Where the model cannot, prefill is computed in
    full on every request.

    The last ``keep`` requests' ids, logits, counters and what else the
    model records stay reachable in ``served`` (device arrays: nothing is
    copied until someone reads them)."""

    def __init__(self, config, params, spec: RewriteSpec, tokenizers,
                 keep: int = 2):
        if not all(isinstance(t, SimpleTokenizer) for t in tokenizers):
            raise ValueError("the rewrite stage hands ids on through the "
                             "weightless word hash; a vocabulary-backed "
                             "tokenizer would need the model's own detokenizer")
        lm = config.language_model()
        prompt_len = spec.instruction_tokens + spec.user_tokens
        if prompt_len % lm.prompt_multiple:
            raise ValueError(
                f"instruction_tokens + user_tokens = {prompt_len} is not a "
                f"multiple of the scan's or the pooling's chunk size "
                f"{lm.prompt_multiple}")
        if not 0 < spec.prompt_tokens <= spec.new_tokens:
            raise ValueError("prompt_tokens must lie in 1..new_tokens")
        if spec.new_tokens % lm.decode_multiple:
            raise ValueError(
                f"new_tokens = {spec.new_tokens} is not a multiple of the "
                f"{lm.decode_multiple} ids a trip of the model's decode "
                f"loop yields")
        by_bytes = lm.byte_offset is not None
        # ids of the language model that make one id of a text encoder
        per_id = GROUP_BYTES if by_bytes else 1
        if spec.prompt_tokens % per_id:
            raise ValueError(f"prompt_tokens must be whole groups of "
                             f"{per_id} bytes")
        self.lm, self.config, self.params, self.spec = lm, config, params, spec
        rng = np.random.default_rng(spec.instruction_seed)
        self.instruction = (rng.integers(
            0, 256 if by_bytes else lm.vocab_size, spec.instruction_tokens)
            + (lm.byte_offset or 0)).astype(np.int32)
        self.served = collections.deque(maxlen=keep)
        self._decode_args = None
        # ids of the instruction that the snapshot covers - whole multiples,
        # and a request's own program keeps some to take; 0: no snapshot
        prefix_len = 0 if lm.prefill_from is None else (
            min(spec.instruction_tokens, prompt_len - 1)
            // lm.prompt_multiple * lm.prompt_multiple)
        self._prefix_len, self._snapshot = prefix_len, None
        # a word model, per text encoder: each of its ids' word hash there
        self._tables = [] if by_bytes else [jnp.asarray(
            [word_hash(str(i), tok.vocab_size - 2)
             for i in range(lm.vocab_size)], jnp.int32)
            for tok in tokenizers]
        # per text encoder, the row the ids are set into: BOS, n ids, EOS to
        # the end
        frames = [(tok.bos, tok.eos,
                   min(spec.prompt_tokens // per_id, tok.model_max_length - 2),
                   tok.model_max_length, tok.vocab_size - 2)
                  for tok in tokenizers]

        max_len = prompt_len + spec.new_tokens

        def rewrite_prefix(params, ids):
            return lm.prefill(params, config, ids, max_len=max_len)[1:]

        def rewrite_prefill(params, ids, snapshot=None):
            """All of a request's ids, or those after the snapshot's - and
            then the record of the whole prompt all the same: the
            snapshot's positions in front of the request's own."""
            if snapshot is None:
                return lm.prefill(params, config, ids, max_len=max_len)
            state, counters, of_prefix = snapshot
            logits, state, counters, of_suffix = lm.prefill_from(
                params, config, ids, max_len=max_len, state=state,
                counters=counters, position=prefix_len)
            of_prompt = jax.tree.map(
                lambda *parts: jnp.concatenate(parts, axis=POSITION_AXIS),
                of_prefix, of_suffix)
            return logits, state, counters, of_prompt

        def rewrite_decode(params, logits, state, counters, tables):
            new_ids, chosen_from, recorded, state, counters = lm.decode(
                params, config, logits, state, counters, position=prompt_len,
                new_tokens=spec.new_tokens)
            encoder_ids = []
            for i, (bos, eos, n, length, modulus) in enumerate(frames):
                tail = new_ids[-n * per_id:]
                ids = (byte_group_ids(tail, modulus) if by_bytes
                       else tables[i][tail])
                row = jnp.full((length,), eos, jnp.int32).at[0].set(bos)
                encoder_ids.append(row.at[1:1 + n].set(ids)[None])
            return (new_ids, chosen_from, recorded, counters, encoder_ids,
                    state)

        self._prefix = jax.jit(rewrite_prefix)
        self._prefill = jax.jit(rewrite_prefill)
        # the decode state is the prefill's to give away: donated and handed
        # back, the loop carries it in place, with no second copy beside the
        # weights (the caller drops what comes back)
        self._decode = jax.jit(rewrite_decode, donate_argnums=2)

    def lm_ids(self, prompt: str) -> np.ndarray:
        """The instruction, then the prompt - its words through the word
        hash (an empty prompt is id 0), or its UTF-8 bytes plus the model's
        byte offset (an empty prompt is a space) - cut or repeated to
        ``user_tokens``."""
        if self.lm.byte_offset is None:
            text = [word_hash(w, self.lm.vocab_size)
                    for w in prompt.lower().split()] or [0]
        else:
            text = [b + self.lm.byte_offset
                    for b in prompt.encode("utf-8") or b" "]
        n = self.spec.user_tokens
        user = (text * -(-n // len(text)))[:n]
        return np.concatenate([self.instruction,
                               np.asarray(user, np.int32)])

    def snapshot(self):
        """(the decode state, the counters, what the model records of those
        ids) after the first ``_prefix_len`` ids of the instruction, made on
        first use and kept; None where the model cannot enter a state."""
        if self._snapshot is None and self._prefix_len:
            with span("distri.rewrite.prefix"):
                self._snapshot = self._prefix(
                    self.params, self.instruction[:self._prefix_len])
        return self._snapshot

    def drop_snapshot(self):
        """Give the snapshot's memory back (the next call makes it anew)."""
        self._snapshot = None

    def __call__(self, prompts: List[str]):
        """-> one [len(prompts), model_max_length] int32 device array per
        text encoder."""
        rows = []
        for prompt in prompts:
            ids = self.lm_ids(prompt)
            snapshot = self.snapshot()
            with span("distri.rewrite.prefill"):
                logits, state, counters, of_prompt = self._prefill(
                    self.params, ids[self._prefix_len:], snapshot)
            with span("distri.rewrite.decode"):
                args = (self.params, logits, state, counters, self._tables)
                if self._decode_args is None:
                    self._decode_args = jax.tree.map(
                        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
                (new_ids, chosen_from, of_new, counters, encoder_ids,
                 _) = self._decode(*args)
            self.served.append(ServedRewrite(
                ids, new_ids, chosen_from, counters, (of_prompt, of_new)))
            rows.append(encoder_ids)
        if len(rows) == 1:
            return rows[0]
        return [jnp.concatenate(per_encoder) for per_encoder in zip(*rows)]

    def decode_program_text(self) -> Optional[str]:
        """The compiled decode program as HLO text (every instruction with
        the named scopes it came from), once a request has run; through
        JAX's compile cache where one is set."""
        if self._decode_args is None:
            return None
        return self._decode.lower(*self._decode_args).compile().as_text()


@dataclasses.dataclass
class PipelineOutput:
    images: List[Any]
    # Set when any tokenizer degraded to the hash-based SimpleTokenizer
    # (weightless smoke/bench runs): the images are NOT real-prompt outputs
    # and must never be quality-judged.  Carried on the artifact itself —
    # a stderr warning alone scrolls away.
    weightless_tokenizer: bool = False
    warning: Optional[str] = None


_WEIGHTLESS_WARNING = (
    "generated with the hash-based SimpleTokenizer fallback (no CLIP/T5 "
    "vocab files were loadable): latency characteristics are valid, image "
    "content is NOT comparable to real-prompt outputs"
)


@dataclasses.dataclass
class PipelineStages:
    """Stage programs for one prepared (pipeline, steps) pair — the split
    request path the staged serving executor (serve/staging.py) pipelines
    across micro-batches:

    * ``rewrite(prompts) -> ids`` — only where a `PromptRewriter` is
      resident (else None): the language model thinks and rewrites each
      prompt, and hands the text encoders' ids on without a host visit;
      ``encode`` takes them as its third argument, and runs the stage
      itself when called without;
    * ``encode(prompts, negs) -> embeddings`` — tokenize + text-encode one
      compiled-batch-width chunk; the returned pytree is family-opaque
      (UNet: (embeds, added_cond); DiT: (embeds, caption_mask); MMDiT:
      (embeds, pooled)) and is exactly what ``denoise`` consumes;
    * ``denoise(embeddings, latents, guidance_scale) -> latent`` — the
      compiled denoise-loop program (the mesh bottleneck resource);
    * ``decode(latent) -> np images`` — chunked VAE decode plus the
      device->host conversion, float RGB [N,H,W,3] in [0,1].

    Every callable is the SAME code the monolithic ``__call__`` path runs
    (``_stage_encode`` / ``_denoise_chunk`` / ``_decode_to_np``), so staged
    and monolithic execution produce bit-identical images for identical
    (prompt, seed, steps) — pipelining changes WHEN stages run, never what
    they compute.  ``steps`` and the guidance mode are baked in: a stage
    set serves exactly one compiled executor identity (serve ExecKey).
    """

    steps: int
    batch_size: int
    encode: Any
    denoise: Any
    decode: Any
    init_noise_sigma: float
    rewrite: Any = None


def _mk_output(images, tokenizers) -> PipelineOutput:
    weightless = any(isinstance(t, SimpleTokenizer) for t in tokenizers)
    return PipelineOutput(
        images=images,
        weightless_tokenizer=weightless,
        warning=_WEIGHTLESS_WARNING if weightless else None,
    )


def _build_decoder(cfg: DistriConfig, vae_config: vae_mod.VAEConfig):
    """(jitted decode fn, parallel?) for the config's geometry: sequence-
    parallel over sp when the latent divides, row-tiled above 2048px, plain
    whole-latent otherwise (shared by the UNet and DiT pipelines).

    The decode fn is ``(vae params, latent, scaling, shift) -> float32
    image [N, H, W * C]`` (`_for_the_host`): the VAE's descale, ``latent /
    scaling + shift``, is the program's first op and not two eager ones in
    front of it.  Both are run-time scalars (Python floats, weakly typed as
    they were in the eager form), so the division stays a division by a
    number the compiler cannot see."""
    parallel = (
        cfg.is_sp and cfg.vae_sp
        and cfg.latent_height % cfg.n_device_per_batch == 0
    )
    if parallel:
        # Sequence-parallel decode over the same sp axis as the denoiser
        # (beyond the reference, which decodes replicated on every rank):
        # exact, n x faster, 1/n activation footprint.
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from .parallel.collectives import gather_rows
        from .utils.config import DP_AXIS, SP_AXIS

        n = cfg.n_device_per_batch

        def _dec(p, l, scaling, shift):
            return _for_the_host(shard_map(
                lambda p_, l_: gather_rows(
                    vae_mod.decode_sp(p_, vae_config, l_, n)
                ),
                mesh=cfg.mesh,
                in_specs=(P(), P(DP_AXIS, SP_AXIS)),
                out_specs=P(DP_AXIS),
                check_vma=False,
            )(p, l / scaling + shift))

        return jax.jit(_dec), True
    # Above 2048px the whole-latent decode's activations dominate HBM on one
    # chip; switch to the row-tiled decoder (models/vae.py).
    tile = 64 if cfg.latent_height > 128 else 0
    return jax.jit(
        lambda p, l, scaling, shift: _for_the_host(vae_mod.decode(
            p, vae_config, l / scaling + shift, tile=tile))
    ), False


def _for_the_host(image):
    """The decode programs' last op: the image ``[N, H, W, C]`` as float32
    ``[N, H, W * C]``.  Widening on the device is exact and costs it
    microseconds; on the chip machine's host the same cast took 12-13 ms an
    image, `ml_dtypes`' loop or integer shifts alike (scripts/
    to_host_forms.py; root PERF.md section 5).  The barrier keeps the
    decoder's own rounding to its dtype where it was - a convert to float32
    right behind it would let XLA drop the pair - and the minor dimension
    of W * C numbers instead of C leaves no lane padding to carry."""
    n, h, w, c = image.shape
    image = jax.lax.optimization_barrier(image).astype(jnp.float32)
    return image.reshape(n, h, w * c)


def _normalize_prompts(prompt, negative_prompt):
    """(prompts, negs) lists from the str-or-list call surface — one code
    path for every pipeline family's __call__ and the serve batcher."""
    prompts = [prompt] if isinstance(prompt, str) else list(prompt)
    negs = (
        [negative_prompt] * len(prompts)
        if isinstance(negative_prompt, str)
        else list(negative_prompt)
    )
    assert len(negs) == len(prompts), (
        f"{len(prompts)} prompts but {len(negs)} negative prompts"
    )
    return prompts, negs


def _wrap_chunk_callback(callback, n_real):
    """diffusers legacy signature callback(step, timestep, latents) with the
    padded tail rows stripped before the user sees them.  With more images
    than batch_size the callback fires per chunk (step indices restart per
    chunk)."""
    if callback is None:
        return None
    return lambda i, t, x: callback(i, t, x[:n_real])


def _pad_rows(arr, pad):
    """Pad a batch-major array to the compiled batch width by repeating its
    last row ``pad`` times (callers drop the padded outputs)."""
    if not pad:
        return arr
    return jnp.concatenate([arr, jnp.repeat(arr[-1:], pad, axis=0)])


def _pad_chunks(total: int, bs: int):
    """(start, stop, pad) triples covering [0, total) in fixed ``bs``-sized
    chunks — the ONE chunking convention shared by the denoise and decode
    paths (and, through generate_batch, the serve batcher): tail chunk
    padded, padded rows dropped by the caller."""
    for i in range(0, total, bs):
        n = min(bs, total - i)
        yield i, i + n, bs - n


@functools.partial(jax.jit, static_argnums=1)
def _seeded_latents(seeds, shape, sigma):
    noise = lambda s: jax.random.normal(  # noqa: E731
        jax.random.PRNGKey(s), shape, jnp.float32)
    for _ in range(seeds.ndim):
        noise = jax.vmap(noise)
    # the barrier keeps the scale a multiply of its own, as the eager op
    # was: without it XLA folds sigma into the normal's last constant and
    # the low bits move
    return jax.lax.optimization_barrier(noise(seeds)) * sigma


def seeded_latents(seeds, shape, sigma):
    """Initial noise scaled by the scheduler's ``sigma``, float32, as ONE
    cached program: ``seeds`` an int -> ``shape`` from that one key (the
    pipelines' ``seed=``); a sequence of ints -> ``[len(seeds), *shape]``,
    row r from ``PRNGKey(seeds[r])`` alone (the serve plane's seed a
    request; threefry counts depend on a row's element count, not on the
    leading axis).  Bit for bit what ``PRNGKey(seed)`` + ``normal`` +
    an eager multiply give for every seed ``PRNGKey`` takes: the seeds go in
    as int64, which jit narrows exactly as ``PRNGKey`` narrows a Python
    int, and the keys are built inside.  The host does no tracing here
    after the first call of a shape: stacking the keys and vmapping the
    draw eagerly traced it on every request, with the device idle (17-20
    ms under the profiler, 3-4 ms of a window's request: root PERF.md
    section 6, PR 46)."""
    return _seeded_latents(np.asarray(seeds, np.int64), tuple(shape), sigma)


def _batched_generate(cfg, scheduler, prompts, negs, num_images_per_prompt,
                      seed, latents, in_channels, run_chunk):
    """Arbitrary prompt counts over the fixed-batch jitted denoise loop.

    The reference passes diffusers' batching straight through
    (pipelines.py:47-58); here the compiled loop has a static batch of
    ``cfg.batch_size``, so each prompt is repeated ``num_images_per_prompt``
    times (diffusers order: a prompt's images are adjacent) and the expanded
    list runs in batch_size chunks — the tail chunk padded by repeating its
    last entry, the padded outputs dropped.  Initial noise is drawn ONCE for
    the whole expanded batch, so results do not depend on the chunking.
    """
    assert prompts, "need at least one prompt"
    assert num_images_per_prompt >= 1, num_images_per_prompt
    prompts = [p for p in prompts for _ in range(num_images_per_prompt)]
    negs = [n for n in negs for _ in range(num_images_per_prompt)]
    total = len(prompts)
    bs = cfg.batch_size
    lat_shape = (total, cfg.latent_height, cfg.latent_width, in_channels)
    if latents is None:
        with span("distri.pipe.latents"):
            latents = seeded_latents(seed, lat_shape,
                                     scheduler.init_noise_sigma)
    else:
        latents = jnp.asarray(latents, jnp.float32)
        assert latents.shape == lat_shape, (latents.shape, lat_shape)
    if total == bs:
        # one whole chunk (the serve plane's every call): the slice, the
        # padding and the concatenate below would each be an identity, and
        # an eager one is a program the device waits for
        return run_chunk(prompts, negs, latents, bs)
    outs = []
    for i, stop, pad in _pad_chunks(total, bs):
        cp, cn = prompts[i:stop], negs[i:stop]
        cl = latents[i:stop]
        if pad:
            cp = cp + [cp[-1]] * pad
            cn = cn + [cn[-1]] * pad
            cl = _pad_rows(cl, pad)
        out = run_chunk(cp, cn, cl, bs - pad)
        outs.append(out[:bs - pad] if pad else out)
    return jnp.concatenate(outs, axis=0)


def _decode_chunked(decode, vae_params, latent, bs, scaling, shift=0.0):
    """VAE-decode in fixed batch_size chunks (pad the tail, drop the padded
    rows): the jitted decoder traces once per shape, and the sequence-
    parallel decode's shard_map needs its dp-divisible batch — an arbitrary
    total from _batched_generate must not reach it directly.  ``shift`` is
    the SD3-family latent re-centering (VAEConfig.shift_factor); the
    descale by both is the decode program's own (`_build_decoder`)."""
    if latent.shape[0] == bs:  # one whole chunk: nothing to cut or join
        return decode(vae_params, latent, scaling, shift)
    outs = []
    for i, stop, pad in _pad_chunks(latent.shape[0], bs):
        cl = _pad_rows(latent[i:stop], pad)
        img = decode(vae_params, cl, scaling, shift)
        outs.append(img[:bs - pad] if pad else img)
    return jnp.concatenate(outs, axis=0)


def _quantize_aux(cfg, vae_params, text_encoders=(), t5_params=None):
    """Load-time quantization of the AUXILIARY models (VAE, CLIP text
    encoders, T5) under the ``weight_quant_aux`` sub-knob — one place for
    the policy every pipeline family shares, so a constructor can't
    quantize one component under the wrong knob or skip one.  The DENOISER
    stays with its caller: its ``weight_quant`` step has per-family
    ordering constraints (PixArt folds the size conditioning first).
    Returns ``(vae_params, [(cfg, params), ...], t5_params-or-None)``.
    """
    q = lambda p: quantize_params(p, cfg.weight_quant_aux)  # noqa: E731
    return (
        q(vae_params),
        [(tc, q(tp)) for tc, tp in text_encoders],
        None if t5_params is None else q(t5_params),
    )


class _HostImages:
    """Float32 host buffers for the images a pipeline hands out, taken back
    when the caller lets go of the image and handed out again.

    A fresh 13 MB array a request is memory the process has never touched:
    on the chip machine's kernel its page faults cost a request 25-40 ms -
    in some processes and not in others, by where glibc happens to put the
    allocating thread's arena - which a 2 s image shows as two clusters of
    runs 27 ms apart (root PERF.md section 6, PR 27).  A buffer that comes
    back is warm.  `take` returns an array whose memory belongs to a lease:
    every view of it (the per-image slices a batch is cut into) keeps the
    lease alive, and only when the last one is gone does the buffer return
    - so an image a caller still holds is never written again.  A caller
    that keeps every image simply gets fresh buffers, as before."""

    class _Lease:
        def __init__(self, buffer, give_back):
            self._buffer, self._give_back = buffer, give_back
            self.__array_interface__ = buffer.__array_interface__

        def __del__(self):
            self._give_back(self._buffer)

    def __init__(self, keep: int = 4):
        self._free, self._keep = [], keep
        self._lock = sync.Lock()

    def _give_back(self, buffer):
        with self._lock:
            if len(self._free) < self._keep:
                self._free.append(buffer)

    def take(self, shape):
        with self._lock:
            at = next((i for i, b in enumerate(self._free)
                       if b.shape == tuple(shape)), None)
            buffer = self._free.pop(at) if at is not None else None
        if buffer is None:
            buffer = np.empty(shape, np.float32)
        return np.asarray(self._Lease(buffer, self._give_back))


class _GenerationMixin:
    """Machinery shared by EVERY pipeline family (UNet, DiT, MMDiT): the
    output packaging tail of __call__, the staged-execution surface
    (`prepare_stages`), and the serve layer's pre-bucketed batched entry.
    Requires ``distri_config``, ``vae_config``, ``vae_params``, and
    ``_decode`` on the instance, plus the family hooks ``_stage_encode``
    (prompts, negs -> embeddings pytree) and ``_denoise_chunk``
    (embeddings, latents, ... -> latent)."""

    # SD3-family VAE latent re-centering (VAEConfig.shift_factor); zero for
    # the legacy families.  Instance attribute on DistriSD3Pipeline.
    _vae_shift: float = 0.0

    # Per-step denoise timeline (utils/trace.py StepTimeline), attached
    # via `attach_step_timeline`: None (the default) adds nothing to the
    # dispatch path.
    step_timeline = None

    @functools.cached_property
    def _host_images(self) -> _HostImages:
        return _HostImages()

    # The think-then-rewrite stage (`PromptRewriter`), resident beside the
    # diffusion model where a family takes one (`DistriSDXLPipeline`).
    rewriter = None

    def _rewrite(self, prompts):
        """The rewrite stage: enqueue the language model's programs for each
        prompt and return the text encoders' ids, still on the device.  Its
        host time is the ``rewrite`` stage clock, cut out of ``dispatch``
        (the same hand-over `_decode_to_np` makes at the other end)."""
        with phases("distri.pipe.dispatch", stage="dispatch") as ph:
            ph.next("distri.pipe.rewrite", stage="rewrite")
            try:
                return self.rewriter(prompts)
            finally:
                ph.next("distri.pipe.dispatch", stage="dispatch")

    def attach_step_timeline(self, timeline):
        """Record every generation's per-denoise-step wall timings
        (tagged warmup/full/shallow by the step-cache cadence) and LIVE
        comm-byte counters into ``timeline`` (`utils.trace.StepTimeline`).

        The live byte counter adds each *executed* step's per-phase wire
        bytes from the runner's byte model as the loop advances, so it
        equals the closed-form `comm_plan` exactly iff the loop really
        ran the phase sequence the plan predicts — the reconciliation
        tests/test_observability.py pins.  Timeline-carrying generations
        run the per-step callback dispatch path (host stepwise loop, or
        the fused io_callback program where the jaxlib supports it):
        per-step host visibility is that path's purpose — use for
        profiling, not steady-state serving."""
        self.step_timeline = timeline
        return timeline

    def _timeline_callback(self, num_inference_steps: int, callback,
                           start_step: int = 0, end_step=None):
        """Compose the user's per-step callback with the attached
        timeline's recorder (no-op passthrough when none is attached).
        Phase tags use the SAME arithmetic as the denoise loops and
        `stepcache.phase_step_counts`: steps [start, start + n_sync) are
        warmup, the rest follow the shallow-first cadence."""
        tl = self.step_timeline
        if tl is None:
            return callback
        from .parallel.stepcache import is_shallow_at

        cfg = self.distri_config
        steps_end = (num_inference_steps if end_step is None
                     else min(end_step, num_inference_steps))
        n_sync = min(cfg.warmup_steps + 1, steps_end - start_step)
        sc = cfg.step_cache_enabled
        interval = cfg.step_cache_interval

        def phase_of(i: int) -> str:
            if i < start_step + n_sync:
                return "warmup"
            if sc and is_shallow_at(i, start_step + n_sync, interval):
                return "shallow"
            return "full"

        try:
            plan = self.comm_plan(num_inference_steps)
            bytes_per_step = plan["bytes_per_step"]
        except (ValueError, AttributeError):
            # runner without a byte model (tensor parallelism, custom):
            # the timeline still records timings, bytes stay untracked
            bytes_per_step = None
        tl.begin_run(
            steps_end - start_step, phase_of, bytes_per_step=bytes_per_step,
            meta={"steps": num_inference_steps, "start_step": start_step,
                  "comm_compress": cfg.comm_compress},
        )

        def cb(i, t, x):
            tl.on_step(int(i))
            if callback is not None:
                callback(i, t, x)

        return cb

    def _timeline_end(self) -> None:
        if self.step_timeline is not None:
            self.step_timeline.end_run()

    def step_cache_plan(self, num_inference_steps: int) -> dict:
        """How the temporal step-cache cadence (docs/PERF.md) plays out over
        a run of ``num_inference_steps``: the serve executors read this for
        the shallow-step-share metrics, and it doubles as a user-facing
        what-will-actually-run probe."""
        from .parallel.stepcache import shallow_step_count

        cfg = self.distri_config
        shallow = (
            shallow_step_count(num_inference_steps, cfg.warmup_steps,
                               cfg.step_cache_interval)
            if cfg.step_cache_enabled else 0
        )
        return {
            "enabled": cfg.step_cache_enabled,
            "interval": cfg.step_cache_interval,
            "depth": cfg.step_cache_depth,
            "total_steps": num_inference_steps,
            "shallow_steps": shallow,
        }

    def comm_plan(self, num_inference_steps: int) -> dict:
        """What one generation will put on the wire: per-phase bytes per
        step (from the runner's comm report, compression-aware) times the
        phase step counts — the byte-level companion of step_cache_plan.
        ``total_bytes`` is per device, gathered-buffer convention; DiT/MMDiT
        shallow steps are scaled from the closed-form element ratio."""
        from .parallel.stepcache import phase_step_counts

        cfg = self.distri_config
        counts = phase_step_counts(
            num_inference_steps, cfg.warmup_steps,
            cfg.step_cache_interval if cfg.step_cache_enabled else 1,
        )
        per_step = {}
        runner = self.runner
        if hasattr(runner, "comm_volume_report"):  # UNet families
            rep = runner.comm_volume_report(per_phase=True)
            per_step = {ph: sum(kinds.values())
                        for ph, kinds in rep.get("bytes", {}).items()}
            if per_step and "stale" not in per_step:  # one-phase configs
                per_step["stale"] = per_step.get("sync", 0)
        elif hasattr(runner, "comm_report"):  # DiT/MMDiT closed forms
            rep = runner.comm_report()
            if "per_step_collective_bytes" in rep:
                per_step = {
                    "sync": rep.get("sync_step_collective_bytes", 0),
                    "stale": rep["per_step_collective_bytes"],
                }
                sc = rep.get("step_cache")
                elems = rep.get("per_step_collective_elems", 0)
                if sc and elems:
                    per_step["shallow"] = (
                        per_step["stale"]
                        * sc["shallow_per_step_collective_elems"] // elems
                    )
        if not per_step:
            # Every runner family now carries a byte model — the UNet
            # per-phase trace, the DiT/MMDiT closed forms (zero for
            # non-sp groups), and PipeFusionRunner.comm_report's per-hop
            # arithmetic.  A runner reaching this branch has NO byte
            # model (tensor parallelism, a custom runner): raise rather
            # than hand back a confident-looking empty plan a capacity
            # model would happily multiply by zero.
            raise ValueError(
                f"{type(runner).__name__} has no byte-modeled comm "
                "report (comm_volume_report bytes / comm_report "
                "per_step_collective_bytes): comm_plan cannot price this "
                "runner's traffic — add the closed form instead of "
                "guessing"
            )
        total = sum(per_step.get(ph, 0) * n for ph, n in counts.items())
        return {
            "comm_compress": cfg.comm_compress,
            # PCPP key (docs/PERF.md "Partial refresh"): the per-step
            # rows above are already fraction-aware — stale/shallow
            # refresh bytes shrink to fraction x full, sync stays whole —
            # so two plans differing only in refresh_fraction give the
            # byte-reduction ratio in closed form
            "refresh_fraction": cfg.refresh_fraction,
            "steps": counts,
            "bytes_per_step": per_step,
            "total_bytes": int(total),
        }

    def set_weight_quant(self, mode: str) -> None:
        """Re-quantize the DENOISER's weights to ``mode`` post-construction
        (docs/PERF.md "Quantized weights").

        The quantize direction ("none" -> int8/fp8) is the serve ladder's
        ``weight_quant_on`` rung promoted to a pipeline policy hook
        (serve.executors.apply_key_policy calls it for ExecKeys that
        request quantization from a full-precision builder): quantizing the
        already-converted dense tree is the exact same operation load-time
        quantization performs.  Call before `prepare()` — the quantized
        tree is a different pytree structure, so anything already compiled
        is dropped and retraces.

        The reverse direction raises: a quantized tree's full-precision
        values are gone (dequantizing bakes the rounding in), so a
        "full-precision" program recovered this way would silently carry
        quantization error — builders wanting both precisions must build
        from the dense weights per key."""
        from .parallel.compress import validate_weight_mode

        cfg = self.distri_config
        validate_weight_mode(mode)
        if mode == cfg.weight_quant:
            return
        if cfg.parallelism == "tensor":
            # same guard as DistriConfig.__post_init__: the tensor runner
            # pre-shards its kernels eagerly, and quantizing the sharded
            # tree post-hoc would feed QuantizedTensor leaves into lax
            # paths that never densify them.  (PipeFusion is fine: its
            # runner holds the full stacked tree and shard_map slices
            # payload and scale alike at trace time.)
            raise ValueError(
                f"weight_quant does not apply to parallelism="
                f"{cfg.parallelism!r} (pre-sharded kernels) — the ladder's "
                "weight_quant_on rung cannot degrade this pipeline"
            )
        if cfg.weight_quant != "none":
            raise ValueError(
                f"cannot switch weight_quant {cfg.weight_quant!r} -> "
                f"{mode!r}: the full-precision kernels are gone — rebuild "
                "the pipeline from the dense weights instead"
            )
        self.runner.params = quantize_params(
            self.runner.params, mode, compute=cfg.quant_compute)
        cfg.weight_quant = mode
        compiled = getattr(self.runner, "_compiled", None)
        if compiled:
            compiled.clear()

    def set_quant_compute(self, policy: str) -> None:
        """Re-tag the denoiser's quantized kernels with an EXECUTION
        policy (DistriConfig.quant_compute; docs/PERF.md "Quantized
        compute").  Unlike set_weight_quant this is
        payload-free — no values change, only which matmul path the next
        trace takes (ops/linear.py) — so it is safe in
        both directions and the serve layer forces it per
        ExecKey.quant_compute.  Drops compiled programs: policy lives in
        the pytree aux data, so a policy change is a different traced
        program."""
        from .parallel.compress import validate_quant_compute
        from .models.weights import set_quant_compute

        cfg = self.distri_config
        validate_quant_compute(policy, cfg.weight_quant)
        if policy == cfg.quant_compute:
            return
        self.runner.params = set_quant_compute(self.runner.params, policy)
        cfg.quant_compute = policy
        compiled = getattr(self.runner, "_compiled", None)
        if compiled:
            compiled.clear()

    def weight_report(self) -> dict:
        """Per-component weight-HBM bytes (models/weights.params_nbytes:
        quantized kernels count payload + scales) plus the active modes —
        what the serve executors surface into ``metrics_snapshot()`` next
        to the PR-4 wire bytes."""
        cfg = self.distri_config
        parts = {
            "denoiser": params_nbytes(self.runner.params),
            "vae": params_nbytes(self.vae_params),
        }
        text = 0
        for _tc, tparams in getattr(self, "text_encoders", ()) or ():
            text += params_nbytes(tparams)
        t5 = getattr(self, "t5", None)
        if t5 is not None and t5[1] is not None:
            text += params_nbytes(t5[1])
        parts["text_encoders"] = text
        if self.rewriter is not None:
            parts["rewriter"] = params_nbytes(self.rewriter.params)
        return {
            "weight_quant": cfg.weight_quant,
            "weight_quant_aux": cfg.weight_quant_aux,
            "quant_compute": cfg.quant_compute,
            "per_component_nbytes": parts,
            "total_bytes": sum(parts.values()),
        }

    def set_stepwise(self, enabled: bool = True) -> None:
        """Switch the denoise loop between the fused compiled scan and
        the host-driven stepwise loop (the reference's --no_cuda_graph
        path) — same numerics, per-step dispatch instead of one program.

        The serve layer's degradation ladder (serve/resilience.py) calls
        it as a *policy* when the fused program fails to compile or OOMs,
        because the stepwise loop is a far smaller program to compile and
        hold.  Call before
        `prepare()`/generation; already-compiled fused programs stay
        cached and are simply not dispatched to while disabled.

        PipeFusion pipelines reject the switch LOUDLY: `PipeFusionRunner`
        has no host-driven stepwise loop (its per-patch micro-pipeline IS
        the program), and silently flipping the flag after construction
        would report a degradation that changes nothing."""
        if enabled and self.distri_config.parallelism == "pipefusion":
            raise ValueError(
                "stepwise fallback does not apply to the PipeFusion patch "
                "pipeline: PipeFusionRunner has no host-driven stepwise "
                "loop (parallel/pipefusion.py).  The serve ladder never "
                "picks RUNG_STEPWISE for pipefusion keys — it degrades "
                "them via the pipeline_off rung (rebuild as displaced "
                "patch parallelism, serve/resilience.py) instead"
            )
        self.distri_config.use_cuda_graph = not enabled

    def _decode_to_np(self, latent) -> np.ndarray:
        """latent -> float RGB [N,H,W,3] in [0,1]: the chunked VAE decode
        plus device->host conversion tail — ONE code path shared by
        `_finalize` (the monolithic __call__) and the staged executor's
        decode stage, so the two execution modes decode identically.

        Enqueuing the decode is the end of the request's ``dispatch``
        phase (utils/trace.py `phases`: joined when __call__ or the serve
        executor opened it, opened here for a bare decode stage); what
        follows is the first host wait, the copy and the arithmetic, each
        a phase of its own.  ``post`` stays open until the outermost
        caller has the images."""
        with phases("distri.pipe.dispatch", stage="dispatch") as ph:
            with span("distri.pipe.decode"):
                image = _decode_chunked(
                    self._decode, self.vae_params, latent,
                    self.distri_config.batch_size,
                    self.vae_config.scaling_factor, self._vae_shift,
                )
            # the copy home starts when the decode ends, not when this
            # thread next runs
            image.copy_to_host_async()
            # the wait np.asarray made anyway, split from its copy
            ph.next("distri.pipe.wait_device", stage="device_wait")
            jax.block_until_ready(image)
            ph.next("distri.pipe.to_host", stage="to_host")
            # float32 already, [N, H, W * C] (`_for_the_host`)
            host = np.asarray(image).reshape(
                *image.shape[:2], -1, self.vae_config.out_channels)
            ph.next("distri.pipe.post", stage="post")
            # clip(image / 2 + 0.5), the first pass writing into host memory
            # this pipeline has used before (see `_HostImages`) and the rest
            # in place: the same bits, no second and third image-sized array
            # for the host to page in
            image = self._host_images.take(host.shape)
            np.multiply(host, 0.5, out=image)
            image += 0.5
            return np.clip(image, 0.0, 1.0, out=image)

    def prepare_stages(self, num_inference_steps: int) -> "PipelineStages":
        """Pre-build the request path as three separately-dispatchable
        stage programs (text-encode / denoise / VAE-decode) for a staged
        serving executor to overlap across micro-batches — batch k+1
        encodes and batch k-1 decodes in the shadow of batch k's denoise
        (serve/staging.py; docs/SERVING.md "Staged pipelining").

        Compiles the denoise loop ahead of time (the same `prepare()` the
        monolithic path uses) and fixes the scheduler's timestep table
        here, OFF the dispatch path — stage invocations never mutate
        shared scheduler state.  The returned callables are the exact
        functions `__call__` runs, so staged and monolithic execution are
        bit-identical (see `PipelineStages`)."""
        self.scheduler.set_timesteps(num_inference_steps)
        self.runner.prepare(num_inference_steps)
        steps = num_inference_steps
        # __call__ forces guidance_scale to 1 when CFG is off; the staged
        # denoise program must apply the same normalization for identity
        cfg_on = self.distri_config.do_classifier_free_guidance

        def denoise(enc, latents, guidance_scale):
            return self._denoise_chunk(
                enc, latents, guidance_scale if cfg_on else 1.0, steps)

        return PipelineStages(
            steps=steps,
            batch_size=self.distri_config.batch_size,
            encode=self._stage_encode,
            denoise=denoise,
            decode=self._decode_to_np,
            init_noise_sigma=float(self.scheduler.init_noise_sigma),
            rewrite=self._rewrite if self.rewriter is not None else None,
        )

    def _finalize(self, latent, output_type, tokenizers) -> "PipelineOutput":
        """latent -> PipelineOutput for 'latent' | 'np' | 'pil'."""
        if output_type == "latent":
            # one entry per image, matching the 'np'/'pil' contract
            return _mk_output(list(np.asarray(latent)), tokenizers)
        image = self._decode_to_np(latent)
        if output_type == "np":
            return _mk_output(list(image), tokenizers)
        from PIL import Image

        return _mk_output(
            [Image.fromarray((im * 255).round().astype(np.uint8))
             for im in image],
            tokenizers,
        )

    def generate_batch(self, prompts, negative_prompts=None,
                       **kwargs) -> "PipelineOutput":
        """Pre-bucketed batched entry (the serve micro-batcher's call path,
        distrifuser_tpu/serve): EXACTLY ``distri_config.batch_size`` prompts
        — the batch the compiled program was built for — so the call is one
        chunk with zero padding and can never retrace on batch shape.
        Delegates to __call__, so the one-shot and serving paths share one
        code path; ``kwargs`` are the __call__ surface (num_inference_steps,
        guidance_scale, seed, latents, output_type, ...)."""
        prompts = list(prompts)
        bs = self.distri_config.batch_size
        if len(prompts) != bs:
            raise ValueError(
                f"generate_batch is the pre-bucketed entry: expected exactly "
                f"batch_size={bs} prompts, got {len(prompts)} (pad upstream "
                "— serve.executors.PipelineExecutor does — or call the "
                "pipeline directly for arbitrary counts)"
            )
        if negative_prompts is None or isinstance(negative_prompts, str):
            negs = negative_prompts or ""  # __call__ broadcasts a str
        else:
            negs = list(negative_prompts)
            if len(negs) != bs:
                raise ValueError(
                    f"{len(negs)} negative prompts for {bs} prompts"
                )
        if kwargs.get("num_images_per_prompt", 1) != 1:
            raise ValueError(
                "generate_batch batches across requests; "
                "num_images_per_prompt must stay 1"
            )
        return self(prompt=prompts, negative_prompt=negs, **kwargs)


class _DistriPipelineBase(_GenerationMixin):
    """Shared machinery; subclasses define the text-encoding recipe."""

    def __init__(
        self,
        distri_config: DistriConfig,
        unet_config: unet_mod.UNetConfig,
        unet_params,
        vae_config: vae_mod.VAEConfig,
        vae_params,
        scheduler: BaseScheduler,
        tokenizers,
        text_encoders,  # list of (CLIPTextConfig, params)
    ):
        _check_scheduler_family(scheduler, flow=False,
                                family=type(self).__name__)
        self.distri_config = distri_config
        self.unet_config = unet_config
        self.vae_config = vae_config
        # load-time weight quantization (docs/PERF.md "Quantized weights"):
        # the denoiser under weight_quant, the aux models (text encoders +
        # VAE) under their own tolerance sub-knob — "none" is a no-op, so
        # the default config stays bit-identical
        unet_params = quantize_params(unet_params, distri_config.weight_quant,
                                      compute=distri_config.quant_compute)
        self.vae_params, self.text_encoders, _ = _quantize_aux(
            distri_config, vae_params, text_encoders)
        self.scheduler = scheduler
        self.tokenizers = tokenizers
        self.runner = make_runner(distri_config, unet_config, unet_params, scheduler)
        cfg = distri_config
        # public introspection: which decode path was installed
        self._decode, self.vae_decode_parallel = _build_decoder(cfg, vae_config)
        # jit one encoder forward per text-encoder config (re-encoding the
        # prompt every call would otherwise dispatch hundreds of eager ops)
        self._clip_jitted = [
            jax.jit(lambda prm, ids, _cfg=ccfg: clip_mod.clip_text_forward(prm, _cfg, ids))
            for ccfg, _ in self.text_encoders
        ]
        # and ONE program behind them for the joining, reshaping and dtype
        # pinning that make the denoiser's conditioning of their outputs:
        # op by op, each of those was a program the device waited for (root
        # PERF.md section 6, PR 46).  The forwards stay programs of their
        # own: inside a larger one XLA fuses their reductions otherwise, and
        # the embeddings' low bits move
        self._condition = jax.jit(self._conditioning, static_argnames="n_br")
        # jitted init-image encode for img2img, for the same reason as the
        # text encoders above (eager per-call dispatch otherwise)
        self._encode_image = jax.jit(
            lambda prm, x: vae_mod.encode(prm, vae_config, x)
        )
        if distri_config.verbose and distri_config.parallelism == "patch":
            # buffer-volume report at construction, like the reference's
            # create_buffer prints (utils.py:152-158)
            self.runner.comm_volume_report(batch_size=distri_config.batch_size)

    # -- reference API ---------------------------------------------------
    def set_progress_bar_config(self, **kwargs):  # parity no-op (rank gating)
        pass

    def prepare(self, num_inference_steps: int = 50, **kwargs) -> None:
        """Pre-build the denoise loop program(s) (the reference's
        record/capture phase, pipelines.py:60-165).  Delegates to the
        runner so the prepared program is exactly the one generate() will
        dispatch to (fused, or the hybrid stale-scan).  In per-step mode
        (use_cuda_graph=False) steps compile lazily on first use, like the
        reference's no-graph path."""
        self.runner.prepare(num_inference_steps)

    @phased("distri.pipe.dispatch", stage="dispatch")
    def __call__(
        self,
        prompt: str | List[str],
        negative_prompt: str | List[str] = "",
        num_inference_steps: int = 50,
        guidance_scale: float = 5.0,
        seed: int = 0,
        output_type: str = "pil",
        latents=None,
        num_images_per_prompt: int = 1,
        image=None,
        strength: float = 0.8,
        denoising_start: float = None,
        denoising_end: float = None,
        original_size=None,
        crops_coords_top_left=(0, 0),
        target_size=None,
        aesthetic_score: float = 6.0,
        negative_original_size=None,
        negative_crops_coords_top_left=None,
        negative_target_size=None,
        negative_aesthetic_score: float = 2.5,
        callback=None,
        **kwargs,
    ) -> PipelineOutput:
        cfg = self.distri_config
        if "height" in kwargs or "width" in kwargs:
            raise ValueError(
                "height and width are fixed in DistriConfig (reference "
                "pipelines.py:47-55)"
            )
        if not cfg.do_classifier_free_guidance:
            guidance_scale = 1.0
        prompts, negs = _normalize_prompts(prompt, negative_prompt)
        self.scheduler.set_timesteps(num_inference_steps)

        # base+refiner split (diffusers denoising_end / denoising_start
        # fractions, index-based here): the base stage stops at end_step and
        # hands its latent to a second pipeline (e.g. an SDXL refiner
        # checkpoint, which from_pretrained loads like any SDXL UNet) that
        # resumes at the same fraction.
        start_step = 0
        end_step = None
        if denoising_end is not None:
            assert 0.0 < denoising_end < 1.0, denoising_end
            # same index mapping as denoising_start below, so matched
            # fractions hand off without overlap or gap
            end_step = int(round(num_inference_steps * denoising_end))
            if end_step < 1:
                raise ValueError(
                    f"denoising_end={denoising_end} rounds to zero steps at "
                    f"num_inference_steps={num_inference_steps}"
                )
        if denoising_start is not None:
            assert 0.0 < denoising_start < 1.0, denoising_start
            assert image is None, (
                "denoising_start resumes mid-trajectory latents; use "
                "image+strength for img2img instead"
            )
            assert latents is not None, (
                "denoising_start requires the mid-trajectory latents from "
                "the previous stage"
            )
            start_step = int(round(num_inference_steps * denoising_start))

        if image is not None:
            # img2img (beyond the reference, which is text2img-only):
            # diffusers Img2Img timestep convention via the shared helper
            assert latents is None, "pass either image or latents, not both"
            latents, start_step = _prepare_init_latents(
                cfg, self.scheduler,
                lambda x: self._encode_image(self.vae_params, x),
                self.vae_config, image, strength, num_inference_steps,
                len(prompts), num_images_per_prompt, seed,
            )

        # SDXL micro-conditioning pass-through (diffusers kwargs the
        # reference forwards, pipelines.py:47-58); SD 1.x/2.x ignores it
        micro_cond = {
            "original_size": original_size,
            "crops_coords_top_left": crops_coords_top_left,
            "target_size": target_size,
            "aesthetic_score": aesthetic_score,
            "negative_original_size": negative_original_size,
            "negative_crops_coords_top_left": negative_crops_coords_top_left,
            "negative_target_size": negative_target_size,
            "negative_aesthetic_score": negative_aesthetic_score,
        }

        def run_chunk(cp, cn, cl, n_real):
            enc = self._encode(cp, cn, micro_cond)
            # timeline recording brackets the denoise loop only (encode
            # stays outside the per-step wall timings); one run per chunk
            cb = self._timeline_callback(
                num_inference_steps, _wrap_chunk_callback(callback, n_real),
                start_step=start_step, end_step=end_step,
            )
            try:
                return self._denoise_chunk(
                    enc, cl, guidance_scale, num_inference_steps,
                    start_step=start_step, end_step=end_step, callback=cb,
                )
            finally:
                self._timeline_end()

        # seeded noise for the whole expanded batch (diffusers passes a torch
        # Generator; the JAX analog is the integer seed); caller-supplied
        # ``latents`` must cover len(prompts) * num_images_per_prompt images
        latent = _batched_generate(
            cfg, self.scheduler, prompts, negs, num_images_per_prompt, seed,
            latents, self.unet_config.in_channels, run_chunk,
        )
        return self._finalize(latent, output_type, self.tokenizers)

    # -- helpers ----------------------------------------------------------
    def _clip(self, which: int, ids):
        _, cparams = self.text_encoders[which]
        # a rewriter's ids are already on the device, and stay there
        if not isinstance(ids, jax.Array):
            ids = np.asarray(ids)
        return self._clip_jitted[which](cparams, ids)

    def _conditioning(self, *encoded, n_br):
        """The traced body of ``self._condition``: the encoders' outputs,
        ``[n_br * B, ...]`` -> ``(embeds [n_br, B, L, C], added_cond)`` in
        the denoiser's dtype, as `runner.generate` takes them."""
        raise NotImplementedError

    def _encode(self, prompts, negs, micro_cond=None):
        raise NotImplementedError

    # -- stage hooks (prepare_stages / __call__ share these) ---------------
    def _stage_encode(self, prompts, negs, rewritten=None):
        """Encode-stage program: no micro-conditioning (the serve surface
        has none), which `_encode` resolves to the same defaults __call__
        passes — identical embeddings either way.  ``rewritten``: the
        rewrite stage's ids, where the caller ran that stage itself."""
        if rewritten is None:
            return self._encode(prompts, negs, None)
        return self._encode(prompts, negs, None, rewritten)

    def _denoise_chunk(self, enc, latents, guidance_scale,
                       num_inference_steps, *, start_step=0, end_step=None,
                       callback=None):
        embeds, added = enc
        with span("distri.pipe.denoise"):
            return self.runner.generate(
                latents, embeds,
                guidance_scale=guidance_scale,
                num_inference_steps=num_inference_steps,
                added_cond=added,
                start_step=start_step,
                end_step=end_step,
                callback=callback,
            )

    # -- step-granular carry hooks (serve/stepbatch.py; see mixin doc) ----
    def step_carry_init(self, latents, num_inference_steps):
        return self.runner.stepwise_carry_init(latents, num_inference_steps)

    def _step_pin_enc(self, enc):
        """The dtype pinning runner.generate applies before its stepwise
        loop — identical inputs => identical per-step programs."""
        embeds, added = enc
        embeds = jnp.asarray(embeds, self.distri_config.dtype)
        if added is not None and "text_embeds" in added:
            added = dict(added)
            added["text_embeds"] = jnp.asarray(added["text_embeds"],
                                               self.distri_config.dtype)
        return embeds, added

    def step_carry_step(self, carry, i, enc, guidance_scale,
                        num_inference_steps):
        embeds, added = self._step_pin_enc(enc)
        return self.runner.stepwise_carry_step(
            carry, i, embeds, added,
            np.float32(guidance_scale), num_inference_steps)

    def step_carry_latent(self, carry):
        return self.runner.stepwise_carry_latent(carry)

    # -- packed cohort hooks (serve/executors.py step_run) ----------------
    def step_carry_pack_supported(self):
        return self.runner.stepwise_rows_supported()

    def step_carry_signature(self, carry, i, num_inference_steps):
        return self.runner.stepwise_carry_signature(carry, i,
                                                    num_inference_steps)

    def step_carry_rows_axes(self, carry, enc, num_inference_steps):
        embeds, added = self._step_pin_enc(enc)
        return self.runner.stepwise_carry_rows_axes(carry, embeds, added,
                                                    num_inference_steps)

    def step_carry_pack_enc(self, encs, width):
        return _pack_enc_rows([self._step_pin_enc(e) for e in encs], width)

    def step_carry_step_rows(self, carry, i_rows, enc, gs_rows,
                             num_inference_steps):
        embeds, added = self._step_pin_enc(enc)
        return self.runner.stepwise_carry_step_rows(
            carry, i_rows, embeds, added, gs_rows, num_inference_steps)


def _pack_enc_rows(encs, width):
    """One packed encoding from each member's SOLO encoding: every enc
    leaf carries the batch at axis 1 (branch-major [2, B, ...] CFG layout,
    the stepwise enc_spec P(None, DP)), and a solo enc's rows are identical
    by construction (`_pad_batch` repeats the one real prompt), so member
    r's row 0 becomes packed row r, padded to ``width`` by repeating the
    last member."""
    def pack_leaves(*leaves):
        blocks = [jax.lax.index_in_dim(l, 0, axis=1, keepdims=True)
                  for l in leaves]
        blocks = blocks + [blocks[-1]] * (width - len(blocks))
        return jnp.concatenate(blocks, axis=1)

    return jax.tree.map(pack_leaves, *encs)


class DistriSDXLPipeline(_DistriPipelineBase):
    """SDXL: two text encoders, penultimate hidden states concatenated, pooled
    embeds + micro-conditioning time_ids (reference pipelines.py:10-167)."""

    @classmethod
    def from_pretrained(
        cls,
        distri_config: DistriConfig,
        pretrained_model_name_or_path: str,
        scheduler: str | BaseScheduler = "ddim",
        dtype=None,
        variant: Optional[str] = None,
        **kwargs,
    ) -> "DistriSDXLPipeline":
        root = pretrained_model_name_or_path
        if not os.path.isdir(root):
            raise FileNotFoundError(
                f"{root!r} is not a local model directory. This box has no "
                "network egress; download a HF snapshot (unet/, vae/, "
                "text_encoder/, text_encoder_2/, tokenizer/) first."
            )
        dtype = dtype or distri_config.dtype
        unet_params = convert_unet_state_dict(
            load_sharded_safetensors(os.path.join(root, "unet"), variant=variant), dtype
        )
        vae_params = convert_vae_state_dict(
            load_sharded_safetensors(os.path.join(root, "vae"), variant=variant), dtype
        )
        te1 = convert_clip_state_dict(
            load_sharded_safetensors(os.path.join(root, "text_encoder"), variant=variant), dtype
        )
        te2 = convert_clip_state_dict(
            load_sharded_safetensors(os.path.join(root, "text_encoder_2"), variant=variant), dtype
        )
        from .native import release_mappings

        release_mappings()  # converted trees are jax copies; unmap the shards
        tok1 = _tokenizer_or_fallback(os.path.join(root, "tokenizer"))
        tok2 = _tokenizer_or_fallback(os.path.join(root, "tokenizer_2"))
        sched = _scheduler_from_snapshot(root, scheduler)
        return cls(
            distri_config,
            _config_from_snapshot(
                root, "unet", unet_mod.unet_config_from_json, unet_mod.sdxl_config
            ),
            unet_params,
            _config_from_snapshot(
                root, "vae", vae_mod.vae_config_from_json, vae_mod.sdxl_vae_config
            ),
            vae_params,
            sched,
            [tok1, tok2],
            [
                (
                    _config_from_snapshot(
                        root, "text_encoder",
                        clip_mod.clip_config_from_json, clip_mod.clip_vit_l_config,
                    ),
                    te1,
                ),
                (
                    _config_from_snapshot(
                        root, "text_encoder_2",
                        clip_mod.clip_config_from_json, clip_mod.open_clip_bigg_config,
                    ),
                    te2,
                ),
            ],
        )

    @classmethod
    def from_params(cls, distri_config, unet_config, unet_params, vae_config,
                    vae_params, text_configs, text_params, scheduler="ddim",
                    tokenizers=None, rewriter=None):
        """``rewriter``: ``(a language model's configuration, its params,
        RewriteSpec)`` puts the think-then-rewrite stage in front of the
        text encoders (`PromptRewriter`: the configuration's
        ``language_model()`` is the model)."""
        if rewriter is not None and distri_config.world_size != 1:
            raise NotImplementedError(
                "the rewrite stage runs on one chip: the language model "
                "holds one chip's share and has no exchange between chips")
        sched = scheduler if isinstance(scheduler, BaseScheduler) else get_scheduler(scheduler)
        toks = tokenizers or [SimpleTokenizer(tc.vocab_size) for tc in text_configs]
        pipe = cls(
            distri_config, unet_config, unet_params, vae_config, vae_params,
            sched, toks, list(zip(text_configs, text_params)),
        )
        if rewriter is not None:
            pipe.rewriter = PromptRewriter(*rewriter, toks)
            # the snapshot is the pipeline's while it lives: whoever still
            # holds the rewriter afterwards (its served records, its program
            # text) does not hold that memory
            weakref.finalize(pipe, pipe.rewriter.drop_snapshot)
        return pipe

    def _encode(self, prompts, negs, micro_cond=None, rewritten=None):
        cfg = self.distri_config
        texts = negs + prompts if cfg.do_classifier_free_guidance else prompts
        n_br = 2 if cfg.do_classifier_free_guidance else 1
        b = len(prompts)

        if self.rewriter is not None and rewritten is None:
            rewritten = self._rewrite(prompts)
        with span("distri.pipe.tokenize"):
            if rewritten is None:
                ids1 = _tokenize(self.tokenizers[0], texts)
                ids2 = _tokenize(self.tokenizers[1], texts)
            else:
                # the negative branch keeps the caller's words
                ids1, ids2 = [
                    ids if n_br == 1 else jnp.concatenate(
                        [np.asarray(_tokenize(tok, negs), ids.dtype), ids])
                    for tok, ids in zip(self.tokenizers, rewritten)]
            time_ids = self._time_ids(n_br, b, micro_cond)
        with span("distri.pipe.encode"):
            out1 = self._clip(0, ids1)
            out2 = self._clip(1, ids2)
            return self._condition(
                out1["hidden_states"][-2], out2["hidden_states"][-2],
                out2["text_embeds"], time_ids, n_br=n_br)

    def _conditioning(self, hidden1, hidden2, pooled, time_ids, *, n_br):
        b = time_ids.shape[1]
        # SDXL conditioning: concat penultimate hidden states of both encoders
        emb = jnp.concatenate([hidden1, hidden2], axis=-1)
        emb = emb.reshape(n_br, b, *emb.shape[1:])
        dtype = self.distri_config.dtype
        return emb.astype(dtype), {
            "text_embeds": pooled.reshape(n_br, b, -1).astype(dtype),
            "time_ids": time_ids}

    def _time_ids(self, n_br, b, micro_cond):
        """The micro-conditioning ids ``[n_br, B, n_ids]``, float32, made on
        the host."""
        cfg = self.distri_config
        # time-id count is derived from the UNet's add-embedding width:
        # (proj_in - pooled) / per-id embed dim = 6 for SDXL-base
        # (orig h, w, crop top/left, target h, w) and 5 for refiner-style
        # configs (orig h, w, crop top/left, aesthetic score).
        ucfg = self.unet_config
        pooled_dim = self.text_encoders[-1][0].projection_dim
        extra = ucfg.projection_class_embeddings_input_dim - pooled_dim
        n_ids = extra // ucfg.addition_time_embed_dim
        if n_ids not in (5, 6) or extra % ucfg.addition_time_embed_dim:
            raise ValueError(
                f"cannot derive time-ids: add-embedding expects {n_ids} ids "
                f"(proj_in={ucfg.projection_class_embeddings_input_dim}, "
                f"pooled={pooled_dim}, "
                f"per-id={ucfg.addition_time_embed_dim}); only the SDXL-base "
                "(6) and refiner-style (5) layouts are supported"
            )
        mc = micro_cond or {}
        o_sz = mc.get("original_size") or (cfg.height, cfg.width)
        crops = mc.get("crops_coords_top_left") or (0, 0)
        t_sz = mc.get("target_size") or (cfg.height, cfg.width)

        def _ids(size, crop, target, score):
            if n_ids == 5:
                return [size[0], size[1], crop[0], crop[1], score]
            return [size[0], size[1], crop[0], crop[1], target[0], target[1]]

        pos = _ids(o_sz, crops, t_sz, mc.get("aesthetic_score", 6.0))
        if n_br == 2:
            # diffusers semantics differ by layout: the base (6-id) pipeline
            # reuses the positive add_time_ids for the uncond branch unless
            # BOTH negative_original_size AND negative_target_size are
            # passed (only then does it build a negative set, with uncond
            # crops defaulting to (0, 0)); the refiner (5-id) layout always
            # builds the branches separately because
            # negative_aesthetic_score defaults to 2.5, not 6.0
            both_neg_sizes = (mc.get("negative_original_size") is not None
                              and mc.get("negative_target_size") is not None)
            if n_ids == 6 and not both_neg_sizes:
                neg = pos
            else:
                neg = _ids(
                    mc.get("negative_original_size") or o_sz,
                    mc.get("negative_crops_coords_top_left") or (0, 0),
                    mc.get("negative_target_size") or t_sz,
                    mc.get("negative_aesthetic_score", 2.5),
                )
            branches = [neg, pos]
        else:
            branches = [pos]
        return np.tile(np.asarray(branches, np.float32)[:, None], (1, b, 1))


class DistriSDPipeline(_DistriPipelineBase):
    """SD 1.4/1.5/2.x: single text encoder, final hidden state
    (reference pipelines.py:170-299)."""

    @classmethod
    def from_pretrained(
        cls,
        distri_config: DistriConfig,
        pretrained_model_name_or_path: str,
        scheduler: str | BaseScheduler = "ddim",
        dtype=None,
        variant: Optional[str] = None,
        **kwargs,
    ) -> "DistriSDPipeline":
        root = pretrained_model_name_or_path
        if not os.path.isdir(root):
            raise FileNotFoundError(
                f"{root!r} is not a local model directory (no network egress)."
            )
        dtype = dtype or distri_config.dtype
        unet_params = convert_unet_state_dict(
            load_sharded_safetensors(os.path.join(root, "unet"), variant=variant), dtype
        )
        vae_params = convert_vae_state_dict(
            load_sharded_safetensors(os.path.join(root, "vae"), variant=variant), dtype
        )
        te = convert_clip_state_dict(
            load_sharded_safetensors(os.path.join(root, "text_encoder"), variant=variant), dtype
        )
        from .native import release_mappings

        release_mappings()
        tok = _tokenizer_or_fallback(os.path.join(root, "tokenizer"))
        sched = _scheduler_from_snapshot(root, scheduler)
        return cls(
            distri_config,
            _config_from_snapshot(
                root, "unet", unet_mod.unet_config_from_json, unet_mod.sd15_config
            ),
            unet_params,
            _config_from_snapshot(
                root, "vae", vae_mod.vae_config_from_json, vae_mod.sd_vae_config
            ),
            vae_params,
            sched,
            [tok],
            [
                (
                    _config_from_snapshot(
                        root, "text_encoder",
                        clip_mod.clip_config_from_json, clip_mod.clip_vit_l_config,
                    ),
                    te,
                )
            ],
        )

    @classmethod
    def from_params(cls, distri_config, unet_config, unet_params, vae_config,
                    vae_params, text_configs, text_params, scheduler="ddim",
                    tokenizers=None):
        sched = scheduler if isinstance(scheduler, BaseScheduler) else get_scheduler(scheduler)
        toks = tokenizers or [SimpleTokenizer(tc.vocab_size) for tc in text_configs]
        return cls(
            distri_config, unet_config, unet_params, vae_config, vae_params,
            sched, toks, list(zip(text_configs, text_params)),
        )

    def _encode(self, prompts, negs, micro_cond=None):
        # SD 1.x/2.x has no micro-conditioning; the kwarg is accepted for
        # the shared __call__ contract and ignored
        cfg = self.distri_config
        texts = negs + prompts if cfg.do_classifier_free_guidance else prompts
        n_br = 2 if cfg.do_classifier_free_guidance else 1
        with span("distri.pipe.tokenize"):
            ids = _tokenize(self.tokenizers[0], texts)
        with span("distri.pipe.encode"):
            out = self._clip(0, ids)
            return self._condition(out["last_hidden_state"], n_br=n_br), None

    def _conditioning(self, emb, *, n_br):
        emb = emb.reshape(n_br, -1, *emb.shape[1:])
        return emb.astype(self.distri_config.dtype)


class DistriPixArtPipeline(_GenerationMixin):
    """PixArt-alpha (DiT family): T5 text encoder + PixArt transformer + KL
    VAE, driven by the displaced-patch DiT runner or, with
    ``parallelism="pipefusion"``, the patch-pipeline runner.

    The model family is beyond the reference (it targets SD/SDXL only); the
    pipeline surface mirrors DistriSDXLPipeline so framework users switch
    model families without switching APIs.  Padded caption tokens are masked
    out of cross-attention (PixArt semantics) and the 1024-class micro-
    conditioning on (resolution, aspect) is folded into the timestep
    embedding bias ahead of the loop (models/dit.py fold_size_condition —
    exact, because the size embedding is timestep-independent).
    """

    # PixArt-alpha trains with 120 caption tokens
    max_token_length = 120

    def __init__(
        self,
        distri_config: DistriConfig,
        dit_config,
        dit_params,
        vae_config: vae_mod.VAEConfig,
        vae_params,
        scheduler: BaseScheduler,
        tokenizer,
        t5_config,
        t5_params,
    ):
        from .models import dit as dit_mod
        from .parallel.dit_sp import DiTDenoiseRunner
        from .parallel.pipefusion import PipeFusionRunner

        _check_scheduler_family(scheduler, flow=False,
                                family="DistriPixArtPipeline")
        cfg = distri_config
        self.distri_config = cfg
        self.dit_config = dit_config
        self.vae_config = vae_config
        self.vae_params, _, t5_q = _quantize_aux(cfg, vae_params,
                                                 t5_params=t5_params)
        self.scheduler = scheduler
        self.tokenizer = tokenizer
        self.t5 = (t5_config, t5_q)
        # fold the size conditioning BEFORE quantizing: it edits embedding
        # biases the quantizer must see in their final form
        dit_params = dit_mod.fold_size_condition(
            dit_params, dit_config, float(cfg.height), float(cfg.width)
        )
        dit_params = quantize_params(dit_params, cfg.weight_quant,
                                     compute=cfg.quant_compute)
        runner_cls = (
            PipeFusionRunner if cfg.parallelism == "pipefusion"
            else DiTDenoiseRunner
        )
        self.runner = runner_cls(cfg, dit_config, dit_params, scheduler)
        self._decode, self.vae_decode_parallel = _build_decoder(cfg, vae_config)
        if t5_params is not None:
            from .models.t5 import t5_encode

            self._t5_jitted = jax.jit(
                lambda prm, ids, mask: t5_encode(prm, t5_config, ids, mask)
            )

    @classmethod
    def from_pretrained(
        cls,
        distri_config: DistriConfig,
        pretrained_model_name_or_path: str,
        scheduler: str | BaseScheduler = "dpm-solver",
        dtype=None,
        variant: Optional[str] = None,
        **kwargs,
    ) -> "DistriPixArtPipeline":
        """Load a local PixArt snapshot (transformer/, vae/, text_encoder/
        (T5), tokenizer/)."""
        from .models import dit as dit_mod
        from .models import t5 as t5_mod
        from .models.weights import convert_pixart_state_dict, convert_t5_state_dict

        root = pretrained_model_name_or_path
        if not os.path.isdir(root):
            raise FileNotFoundError(
                f"{root!r} is not a local model directory (no network egress)."
            )
        dtype = dtype or distri_config.dtype
        dcfg = _config_from_snapshot(
            root, "transformer", dit_mod.dit_config_from_json,
            dit_mod.pixart_config,
        )
        dit_params = convert_pixart_state_dict(
            load_sharded_safetensors(os.path.join(root, "transformer"),
                                     variant=variant),
            patch_size=dcfg.patch_size, eps_channels=dcfg.out_channels,
            dtype=dtype,
        )
        vae_params = convert_vae_state_dict(
            load_sharded_safetensors(os.path.join(root, "vae"),
                                     variant=variant), dtype
        )
        t5cfg = _config_from_snapshot(
            root, "text_encoder", t5_mod.t5_config_from_json,
            t5_mod.t5_v1_1_xxl_config,
        )
        t5_params = convert_t5_state_dict(
            load_sharded_safetensors(os.path.join(root, "text_encoder"),
                                     variant=variant), dtype
        )
        from .native import release_mappings

        release_mappings()
        tok = _t5_tokenizer_or_fallback(
            os.path.join(root, "tokenizer"), t5cfg.vocab_size
        )
        sched = _scheduler_from_snapshot(root, scheduler)
        return cls(distri_config, dcfg, dit_params,
                   _config_from_snapshot(root, "vae",
                                         vae_mod.vae_config_from_json,
                                         vae_mod.sd_vae_config),
                   vae_params, sched, tok, t5cfg, t5_params)

    @classmethod
    def from_params(cls, distri_config, dit_config, dit_params, vae_config,
                    vae_params, t5_config=None, t5_params=None,
                    scheduler="ddim", tokenizer=None):
        sched = (scheduler if isinstance(scheduler, BaseScheduler)
                 else get_scheduler(scheduler))
        tok = tokenizer or SimpleTokenizer(
            vocab_size=t5_config.vocab_size if t5_config else 32128,
            eos=1, bos=0,
        )
        return cls(distri_config, dit_config, dit_params, vae_config,
                   vae_params, sched, tok, t5_config, t5_params)

    # -- reference API ----------------------------------------------------
    def set_progress_bar_config(self, **kwargs):
        pass

    def prepare(self, num_inference_steps: int = 20, **kwargs) -> None:
        self.runner.prepare(num_inference_steps)

    def _encode(self, prompts, negs):
        cfg = self.distri_config
        texts = negs + prompts if cfg.do_classifier_free_guidance else prompts
        n_br = 2 if cfg.do_classifier_free_guidance else 1
        b = len(prompts)
        t5cfg, t5p = self.t5
        with span("distri.pipe.tokenize"):
            ids, mask = self._caption_tokens(texts)
        with span("distri.pipe.encode"):
            if t5p is None:
                # weight-free smoke path: deterministic pseudo-embeddings,
                # so the random-weight runners still exercise the full
                # pipeline surface
                emb = jnp.stack([
                    jax.random.normal(
                        jax.random.PRNGKey(int(s) % (2**31)),
                        (ids.shape[1], self.dit_config.caption_dim),
                        jnp.float32,
                    )
                    for s in ids.sum(axis=1)
                ])
                mask = np.ones(ids.shape, np.float32)
            else:
                emb = self._t5_jitted(t5p, np.asarray(ids, np.int32),
                                      np.asarray(mask))
            emb = emb.reshape(n_br, b, emb.shape[1], emb.shape[2])
            mask = jnp.asarray(np.asarray(mask).reshape(n_br, b, -1))
            return emb, mask

    def _caption_tokens(self, texts):
        """(ids, attention mask) of the caption tokenizer."""
        if isinstance(self.tokenizer, SimpleTokenizer):
            ids = np.asarray(self.tokenizer(texts, self.max_token_length))
            # real tokens + the first (sentinel) EOS are attended, like a
            # transformers T5 attention_mask; the eos-padding tail is not
            mask = (ids != self.tokenizer.eos).astype(np.float32)
            first_eos = np.argmax(ids == self.tokenizer.eos, axis=1)
            mask[np.arange(len(ids)), first_eos] = 1.0
            return ids, mask
        # explicit max_length: tok.model_max_length is 512 (or unset =
        # effectively unbounded) for T5 tokenizers; the pipeline contract
        # is 120 caption tokens
        out = self.tokenizer(
            texts, padding="max_length",
            max_length=self.max_token_length, truncation=True,
            return_tensors="np",
        )
        return (np.asarray(out["input_ids"]),
                np.asarray(out["attention_mask"], np.float32))

    @phased("distri.pipe.dispatch", stage="dispatch")
    def __call__(
        self,
        prompt: str | List[str],
        negative_prompt: str | List[str] = "",
        num_inference_steps: int = 20,
        guidance_scale: float = 4.5,
        seed: int = 0,
        output_type: str = "pil",
        latents=None,
        num_images_per_prompt: int = 1,
        callback=None,
        **kwargs,
    ) -> PipelineOutput:
        cfg = self.distri_config
        if "height" in kwargs or "width" in kwargs:
            raise ValueError(
                "height and width are fixed in DistriConfig (reference "
                "pipelines.py:47-55)"
            )
        if not cfg.do_classifier_free_guidance:
            guidance_scale = 1.0
        prompts, negs = _normalize_prompts(prompt, negative_prompt)
        self.scheduler.set_timesteps(num_inference_steps)

        def run_chunk(cp, cn, cl, n_real):
            enc = self._encode(cp, cn)
            cb = self._timeline_callback(
                num_inference_steps, _wrap_chunk_callback(callback, n_real))
            try:
                return self._denoise_chunk(
                    enc, cl, guidance_scale, num_inference_steps,
                    callback=cb)
            finally:
                self._timeline_end()

        latent = _batched_generate(
            cfg, self.scheduler, prompts, negs, num_images_per_prompt, seed,
            latents, self.dit_config.in_channels, run_chunk,
        )
        return self._finalize(latent, output_type, [self.tokenizer])

    # -- stage hooks (prepare_stages / __call__ share these) ---------------
    def _stage_encode(self, prompts, negs):
        return self._encode(prompts, negs)

    def _denoise_chunk(self, enc, latents, guidance_scale,
                       num_inference_steps, *, callback=None):
        emb, mask = enc
        with span("distri.pipe.denoise"):
            return self.runner.generate(
                latents, emb, guidance_scale=guidance_scale,
                num_inference_steps=num_inference_steps, cap_mask=mask,
                callback=callback,
            )

    # -- step-granular carry hooks (serve/stepbatch.py) -------------------
    def step_carry_init(self, latents, num_inference_steps):
        return self.runner.stepwise_carry_init(latents, num_inference_steps)

    def _step_pin_enc(self, enc):
        """The mask default + pinning generate() applies before its
        stepwise loop — identical inputs => identical per-step programs."""
        emb, mask = enc
        if mask is None:
            mask = jnp.ones(emb.shape[:3], jnp.float32)
        return emb, jnp.asarray(mask, jnp.float32)

    def step_carry_step(self, carry, i, enc, guidance_scale,
                        num_inference_steps):
        emb, mask = self._step_pin_enc(enc)
        return self.runner.stepwise_carry_step(
            carry, i, emb, mask,
            np.float32(guidance_scale), num_inference_steps)

    def step_carry_latent(self, carry):
        return self.runner.stepwise_carry_latent(carry)

    # -- packed cohort hooks (serve/executors.py step_run) ----------------
    def step_carry_pack_supported(self):
        return self.runner.stepwise_rows_supported()

    def step_carry_signature(self, carry, i, num_inference_steps):
        return self.runner.stepwise_carry_signature(carry, i,
                                                    num_inference_steps)

    def step_carry_rows_axes(self, carry, enc, num_inference_steps):
        return self.runner.stepwise_carry_rows_axes(carry,
                                                    num_inference_steps)

    def step_carry_pack_enc(self, encs, width):
        return _pack_enc_rows([self._step_pin_enc(e) for e in encs], width)

    def step_carry_step_rows(self, carry, i_rows, enc, gs_rows,
                             num_inference_steps):
        emb, mask = self._step_pin_enc(enc)
        return self.runner.stepwise_carry_step_rows(
            carry, i_rows, emb, mask, gs_rows, num_inference_steps)


def _t5_tokenizer_or_fallback(path: str, vocab_size: int):
    """transformers T5 tokenizer from the snapshot dir, else the hash
    fallback with a LOUD warning (same policy as the CLIP loader)."""
    try:
        from transformers import AutoTokenizer

        return AutoTokenizer.from_pretrained(path)
    except Exception as e:
        print(
            f"WARNING: failed to load T5 tokenizer from {path!r} "
            f"({type(e).__name__}: {e}); falling back to the hash-based "
            "SimpleTokenizer. Generated images will NOT match real-prompt "
            "outputs.",
            file=sys.stderr,
            flush=True,
        )
        return SimpleTokenizer(vocab_size=vocab_size, eos=1, bos=0)


class DistriSD3Pipeline(_GenerationMixin):
    """SD3-class MMDiT pipeline — a model family BEYOND the reference
    (whose diffusers 0.24 pin predates SD3 entirely); built so the same
    displaced-patch machinery covers the current diffusion architecture.

    Text conditioning follows the published SD3 recipe: both CLIP
    encoders' penultimate hidden states concatenate along features and
    zero-pad to joint_attention_dim; T5 states (or zeros when no T5 is
    loaded — SD3 supports dropping it) append along the TOKEN axis; the
    pooled vector is the concat of both CLIP projected embeddings.
    Sampling is rectified-flow Euler (schedulers.FlowMatchEulerScheduler),
    denoising runs on parallel/mmdit_sp.MMDiTDenoiseRunner, and the
    SD3-family VAE re-centering (shift_factor) applies at decode.
    """

    def __init__(
        self,
        distri_config: DistriConfig,
        mmdit_config,
        mmdit_params,
        vae_config: vae_mod.VAEConfig,
        vae_params,
        scheduler: BaseScheduler,
        tokenizers,       # [clip_l_tok, clip_g_tok, t5_tok_or_None]
        text_encoders,    # [(CLIPTextConfig, params) x 2]
        t5_config=None,
        t5_params=None,
        max_t5_tokens: int = 77,
    ):
        from .parallel.mmdit_sp import MMDiTDenoiseRunner

        _check_scheduler_family(scheduler, flow=True,
                                family="DistriSD3Pipeline (SD3-class MMDiT)")
        cfg = distri_config
        self.distri_config = cfg
        self.mmdit_config = mmdit_config
        self.vae_config = vae_config
        self.vae_params, self.text_encoders, t5_q = _quantize_aux(
            cfg, vae_params, text_encoders, t5_params)
        self._vae_shift = vae_config.shift_factor
        self.scheduler = scheduler
        self.tokenizers = tokenizers
        text_encoders = self.text_encoders
        mmdit_params = quantize_params(mmdit_params, cfg.weight_quant,
                                       compute=cfg.quant_compute)
        self.t5 = (t5_config, t5_q)
        self.max_t5_tokens = max_t5_tokens
        pooled_dim = sum(
            tc.projection_dim or tc.hidden_size for tc, _ in text_encoders
        )
        if pooled_dim != mmdit_config.pooled_projection_dim:
            raise ValueError(
                f"CLIP projected widths sum to {pooled_dim}, but the "
                f"transformer expects pooled_projection_dim="
                f"{mmdit_config.pooled_projection_dim}"
            )
        clip_dim = sum(tc.hidden_size for tc, _ in text_encoders)
        if clip_dim > mmdit_config.joint_attention_dim:
            raise ValueError(
                f"CLIP hidden widths sum to {clip_dim} > joint_attention_dim "
                f"{mmdit_config.joint_attention_dim}"
            )
        self.runner = MMDiTDenoiseRunner(cfg, mmdit_config, mmdit_params,
                                         scheduler)
        self._decode, self.vae_decode_parallel = _build_decoder(cfg, vae_config)
        self._encode_image = jax.jit(
            lambda prm, x: vae_mod.encode(prm, vae_config, x)
        )
        self._clip_jitted = [
            jax.jit(lambda prm, ids, _cfg=ccfg: clip_mod.clip_text_forward(
                prm, _cfg, ids))
            for ccfg, _ in text_encoders
        ]
        if t5_params is not None:
            from .models.t5 import t5_encode

            self._t5_jitted = jax.jit(
                lambda prm, ids, mask: t5_encode(prm, t5_config, ids, mask)
            )
        # the encoders' outputs -> (joint text sequence, pooled vector) as
        # one program (as the UNet families' ``_condition``)
        self._condition = jax.jit(self._conditioning, static_argnames="n_br")

    @classmethod
    def from_pretrained(
        cls,
        distri_config: DistriConfig,
        pretrained_model_name_or_path: str,
        scheduler: str | BaseScheduler = "flow-euler",
        dtype=None,
        variant: Optional[str] = None,
        max_t5_tokens: int = 77,
        **kwargs,
    ) -> "DistriSD3Pipeline":
        """Load a local SD3 snapshot (transformer/, vae/, text_encoder/,
        text_encoder_2/, optional text_encoder_3/ (T5), tokenizer*/).
        The T5 encoder is optional exactly as in the published pipeline —
        absent weights degrade to the zero-embedding path."""
        from .models import mmdit as mmdit_mod
        from .models import t5 as t5_mod
        from .models.weights import convert_mmdit_state_dict, convert_t5_state_dict

        root = pretrained_model_name_or_path
        if not os.path.isdir(root):
            raise FileNotFoundError(
                f"{root!r} is not a local model directory (no network egress)."
            )
        dtype = dtype or distri_config.dtype
        mcfg = _config_from_snapshot(
            root, "transformer", mmdit_mod.mmdit_config_from_json,
            mmdit_mod.sd3_config,
        )
        mmdit_params = convert_mmdit_state_dict(
            load_sharded_safetensors(os.path.join(root, "transformer"),
                                     variant=variant), dtype
        )
        vae_params = convert_vae_state_dict(
            load_sharded_safetensors(os.path.join(root, "vae"),
                                     variant=variant), dtype
        )
        encs, toks = [], []
        for sub, tok_sub in (("text_encoder", "tokenizer"),
                             ("text_encoder_2", "tokenizer_2")):
            ccfg = _config_from_snapshot(
                root, sub, clip_mod.clip_config_from_json,
                clip_mod.tiny_clip_config,
            )
            cparams = convert_clip_state_dict(
                load_sharded_safetensors(os.path.join(root, sub),
                                         variant=variant), dtype
            )
            encs.append((ccfg, cparams))
            toks.append(_tokenizer_or_fallback(os.path.join(root, tok_sub)))
        t5cfg = t5p = None
        if os.path.isdir(os.path.join(root, "text_encoder_3")):
            t5cfg = _config_from_snapshot(
                root, "text_encoder_3", t5_mod.t5_config_from_json,
                t5_mod.t5_v1_1_xxl_config,
            )
            t5p = convert_t5_state_dict(
                load_sharded_safetensors(os.path.join(root, "text_encoder_3"),
                                         variant=variant), dtype
            )
            toks.append(_t5_tokenizer_or_fallback(
                os.path.join(root, "tokenizer_3"), t5cfg.vocab_size))
        else:
            toks.append(None)
        from .native import release_mappings

        release_mappings()
        if isinstance(scheduler, BaseScheduler):
            sched = scheduler  # family-checked by __init__
        elif scheduler != "flow-euler":
            raise ValueError(
                f"scheduler={scheduler!r}: SD3-class MMDiTs are "
                "rectified-flow models — only 'flow-euler' (or a "
                "FlowMatchEulerScheduler instance) is valid"
            )
        else:
            # SD3 scheduler_config carries the flow shift, not betas
            shift = 3.0
            sc_path = os.path.join(root, "scheduler", "scheduler_config.json")
            if os.path.exists(sc_path):
                import json as _json

                with open(sc_path) as f:
                    shift = _json.load(f).get("shift", 3.0)
            sched = FlowMatchEulerScheduler(shift=shift)
        return cls(distri_config, mcfg, mmdit_params,
                   _config_from_snapshot(root, "vae",
                                         vae_mod.vae_config_from_json,
                                         vae_mod.sd_vae_config),
                   vae_params, sched, toks, encs, t5cfg, t5p,
                   max_t5_tokens=max_t5_tokens)

    @classmethod
    def from_params(cls, distri_config, mmdit_config, mmdit_params,
                    vae_config, vae_params, clip_configs, clip_params,
                    t5_config=None, t5_params=None, scheduler="flow-euler",
                    tokenizers=None, max_t5_tokens: int = 77):
        sched = (scheduler if isinstance(scheduler, BaseScheduler)
                 else get_scheduler(scheduler))
        toks = tokenizers or [
            SimpleTokenizer(tc.vocab_size) for tc in clip_configs
        ] + [SimpleTokenizer(t5_config.vocab_size, eos=1, bos=0)
             if t5_config else None]
        return cls(distri_config, mmdit_config, mmdit_params, vae_config,
                   vae_params, sched, toks, list(zip(clip_configs,
                                                     clip_params)),
                   t5_config, t5_params, max_t5_tokens=max_t5_tokens)

    # -- reference API ----------------------------------------------------
    def set_progress_bar_config(self, **kwargs):
        pass

    def prepare(self, num_inference_steps: int = 20, **kwargs) -> None:
        self.runner.prepare(num_inference_steps)

    def _encode(self, prompts, negs):
        cfg = self.distri_config
        mcfg = self.mmdit_config
        texts = negs + prompts if cfg.do_classifier_free_guidance else prompts
        n_br = 2 if cfg.do_classifier_free_guidance else 1
        b = len(prompts)

        t5cfg, t5p = self.t5
        with span("distri.pipe.tokenize"):
            clip_ids = [_tokenize(self.tokenizers[which], texts)
                        for which in range(2)]
            if t5p is not None:
                tok = self.tokenizers[2]
                if isinstance(tok, SimpleTokenizer):
                    ids = tok(texts, self.max_t5_tokens)
                    mask = (ids != tok.eos).astype(np.float32)
                    first_eos = np.argmax(ids == tok.eos, axis=1)
                    mask[np.arange(len(ids)), first_eos] = 1.0
                else:
                    out = tok(texts, padding="max_length",
                              max_length=self.max_t5_tokens, truncation=True,
                              return_tensors="np")
                    ids = np.asarray(out["input_ids"])
                    mask = np.asarray(out["attention_mask"], np.float32)
        with span("distri.pipe.encode"):
            clip_states, pooleds = [], []
            for which in range(2):
                out = self._clip_jitted[which](
                    self.text_encoders[which][1],
                    np.asarray(clip_ids[which]))
                clip_states.append(out["hidden_states"][-2])
                pooleds.append(out.get("text_embeds", out["pooler_output"]))
            t5_emb = None if t5p is None else self._t5_jitted(
                t5p, np.asarray(ids, np.int32), np.asarray(mask))
            return self._condition(clip_states, pooleds, t5_emb, n_br=n_br)

    def _conditioning(self, clip_states, pooleds, t5_emb, *, n_br):
        """The traced body of ``self._condition``: the joint sequence
        ``[n_br, B, L_clip + L_t5, C]`` and the pooled vector ``[n_br, B, P]``
        of the CLIP towers' states and T5's (None: not loaded, zeros)."""
        mcfg = self.mmdit_config
        clip_emb = jnp.concatenate(clip_states, axis=-1)
        pad = mcfg.joint_attention_dim - clip_emb.shape[-1]
        clip_emb = jnp.pad(clip_emb, ((0, 0), (0, 0), (0, pad)))
        pooled = jnp.concatenate(pooleds, axis=-1)
        if t5_emb is None:
            t5_emb = jnp.zeros(
                (clip_emb.shape[0], self.max_t5_tokens,
                 mcfg.joint_attention_dim), clip_emb.dtype,
            )
        enc = jnp.concatenate([clip_emb, t5_emb.astype(clip_emb.dtype)],
                              axis=1)
        return (enc.reshape(n_br, -1, *enc.shape[1:]),
                pooled.reshape(n_br, -1, pooled.shape[-1]))

    @phased("distri.pipe.dispatch", stage="dispatch")
    def __call__(
        self,
        prompt: str | List[str],
        negative_prompt: str | List[str] = "",
        num_inference_steps: int = 28,
        guidance_scale: float = 7.0,
        seed: int = 0,
        output_type: str = "pil",
        latents=None,
        num_images_per_prompt: int = 1,
        image=None,
        strength: float = 0.8,
        callback=None,
        **kwargs,
    ) -> PipelineOutput:
        cfg = self.distri_config
        if "height" in kwargs or "width" in kwargs:
            raise ValueError(
                "height and width are fixed in DistriConfig (reference "
                "pipelines.py:47-55)"
            )
        if not cfg.do_classifier_free_guidance:
            guidance_scale = 1.0
        prompts, negs = _normalize_prompts(prompt, negative_prompt)
        self.scheduler.set_timesteps(num_inference_steps)

        start_step = 0
        if image is not None:
            # img2img under rectified flow: the flow add_noise interpolates
            # to the strength-offset sigma — same timestep convention and
            # shared helper as the UNet pipelines' img2img path
            assert latents is None, "pass either image or latents, not both"
            latents, start_step = _prepare_init_latents(
                cfg, self.scheduler,
                lambda x: self._encode_image(self.vae_params, x),
                self.vae_config, image, strength, num_inference_steps,
                len(prompts), num_images_per_prompt, seed,
            )

        def run_chunk(cp, cn, cl, n_real):
            enc = self._encode(cp, cn)
            cb = self._timeline_callback(
                num_inference_steps, _wrap_chunk_callback(callback, n_real),
                start_step=start_step)
            try:
                return self._denoise_chunk(
                    enc, cl, guidance_scale, num_inference_steps,
                    start_step=start_step, callback=cb,
                )
            finally:
                self._timeline_end()

        latent = _batched_generate(
            cfg, self.scheduler, prompts, negs, num_images_per_prompt, seed,
            latents, self.mmdit_config.in_channels, run_chunk,
        )
        toks = [t for t in self.tokenizers if t is not None]
        return self._finalize(latent, output_type, toks)

    # -- stage hooks (prepare_stages / __call__ share these) ---------------
    def _stage_encode(self, prompts, negs):
        return self._encode(prompts, negs)

    def _denoise_chunk(self, enc, latents, guidance_scale,
                       num_inference_steps, *, start_step=0, callback=None):
        emb, pooled = enc
        with span("distri.pipe.denoise"):
            return self.runner.generate(
                latents, emb, pooled, guidance_scale=guidance_scale,
                num_inference_steps=num_inference_steps,
                start_step=start_step,
                callback=callback,
            )

    # -- step-granular carry hooks (serve/stepbatch.py) -------------------
    def step_carry_init(self, latents, num_inference_steps):
        return self.runner.stepwise_carry_init(latents, num_inference_steps)

    def _step_pin_enc(self, enc):
        """The pooled pinning _generate_stepwise applies — identical
        inputs => identical per-step programs."""
        emb, pooled = enc
        return emb, jnp.asarray(pooled)

    def step_carry_step(self, carry, i, enc, guidance_scale,
                        num_inference_steps):
        emb, pooled = self._step_pin_enc(enc)
        return self.runner.stepwise_carry_step(
            carry, i, emb, pooled,
            np.float32(guidance_scale), num_inference_steps)

    def step_carry_latent(self, carry):
        return self.runner.stepwise_carry_latent(carry)

    # -- packed cohort hooks (serve/executors.py step_run) ----------------
    def step_carry_pack_supported(self):
        return self.runner.stepwise_rows_supported()

    def step_carry_signature(self, carry, i, num_inference_steps):
        return self.runner.stepwise_carry_signature(carry, i,
                                                    num_inference_steps)

    def step_carry_rows_axes(self, carry, enc, num_inference_steps):
        return self.runner.stepwise_carry_rows_axes(carry,
                                                    num_inference_steps)

    def step_carry_pack_enc(self, encs, width):
        return _pack_enc_rows([self._step_pin_enc(e) for e in encs], width)

    def step_carry_step_rows(self, carry, i_rows, enc, gs_rows,
                             num_inference_steps):
        emb, pooled = self._step_pin_enc(enc)
        return self.runner.stepwise_carry_step_rows(
            carry, i_rows, emb, pooled, gs_rows, num_inference_steps)
