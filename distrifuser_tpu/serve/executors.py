"""Adapters from the one-shot pipelines to the serve executor contract.

A serve executor owns one *prepared* pipeline at one bucket resolution:
DistriConfig fixes height/width at construction (the compiled program's
shape), so the bucket table in serve/batcher.py maps requests onto a small
set of pipeline instances, and the `ExecutorCache` bounds how many stay
resident.

Per-request seeds inside one coalesced batch are honored by drawing each
request's initial latent from its own PRNG key here and handing the stacked
batch to the pipeline's pre-bucketed entry (`generate_batch`) — the same
noise each request would have received running alone, so coalescing never
changes a request's image.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence

from ..utils.trace import phases, span
from .cache import ExecKey
from .errors import DegradationInapplicableError
from .faults import FaultPlan


def _tokenizer_hash(pipeline) -> int:
    """Stable identity of a pipeline's tokenizer stack for the prompt
    cache key: two executors of the same model share entries; a different
    vocabulary (or tokenizer implementation) never does."""
    import zlib

    parts = []
    for tok in getattr(pipeline, "tokenizers", ()) or ():
        parts.append(type(tok).__name__)
        vocab = getattr(tok, "vocab_size", None)
        if vocab is None:
            enc = getattr(tok, "encoder", None)
            vocab = len(enc) if hasattr(enc, "__len__") else 0
        parts.append(str(vocab))
    return zlib.crc32("|".join(parts).encode())


def _release_buffers(tree) -> None:
    """Best-effort early free of device buffers in a pytree — the staged
    pipeline's "latent donation between invocations": with up to
    ``max_inflight_batches`` batches resident, a consumed stage input
    (initial latents, embeddings) must hand its HBM back the moment its
    consumer finishes, not whenever host GC next runs."""
    import jax

    for leaf in jax.tree_util.tree_leaves(tree):
        delete = getattr(leaf, "delete", None)
        if delete is not None:
            try:
                delete()
            except Exception:  # noqa: BLE001 — already deleted / aliased
                pass


class PipelineExecutor:
    """Wrap a prepared distrifuser_tpu pipeline as a serve executor.

    ``pipeline`` must match the key it serves: built at (key.height,
    key.width) with do_classifier_free_guidance == key.cfg and the key's
    scheduler family; ``prepare(key.steps)`` should already have run (the
    factory in `pipeline_executor_factory` does all of this).

    Besides the monolithic ``__call__`` contract, the executor exposes the
    three-stage contract the staged serving pipeline (serve/staging.py)
    drives: ``encode_stage`` / ``denoise_stage`` / ``decode_stage``, built
    on the pipeline's `prepare_stages` programs — the same code paths as
    ``__call__``, so the two dispatch modes produce bit-identical images.

    ``fault_plan`` (serve/faults.py) injects at site ``"executor.execute"``
    for direct (server-less) executor use; a server-driven executor gets
    its faults from the server's own ``"execute"`` site instead.
    """

    def __init__(self, pipeline, steps: int, *,
                 key: Optional[ExecKey] = None,
                 fault_plan: Optional[FaultPlan] = None):
        self.pipeline = pipeline
        self.steps = steps
        self.key = key
        self.fault_plan = fault_plan
        self.batch_size = pipeline.distri_config.batch_size
        # scheduler timesteps are per-(pipeline, steps) state: fix them
        # ONCE here, on the prepare path — never from the per-dispatch
        # latent draw, which must not mutate shared scheduler state
        pipeline.scheduler.set_timesteps(steps)
        self._stages = None
        # per-invocation shallow-step count under the step-cache cadence
        # (0 with the cache off) — the server's shallow-share metrics read
        # this off every executor it dispatches to
        self.shallow_steps = pipeline.step_cache_plan(steps)["shallow_steps"]
        # weight-HBM ledger entry (pipelines.weight_report): what this
        # executor's resident param trees cost, quantization included —
        # surfaced per key by ExecutorCache.weight_bytes / metrics_snapshot
        report = getattr(pipeline, "weight_report", None)
        self.weight_nbytes = report()["total_bytes"] if report else None
        # a think-then-rewrite language model resident beside the diffusion
        # model (pipelines.PromptRewriter): its weights are in the ledger
        # entry above, its host time in the ``rewrite`` stage clock
        self.rewriter_resident = getattr(pipeline, "rewriter",
                                         None) is not None
        # prompt/embedding LRU (serve/promptcache.py), attached by the
        # owning server via attach_prompt_cache: None = encode always runs
        self.prompt_cache = None
        self._encode_cache_family = (type(pipeline).__name__,
                                     _tokenizer_hash(pipeline))
        # packed cohort dispatch state (step_run): pipeline support flag
        # (resolved lazily), rowpack axes plans cached per carry treedef
        # (None sentinel = ambiguous -> that structure stays sequential),
        # and the last step_run's pack-efficiency tallies for the server's
        # stepbatch_* counters / fill gauge
        self._pack_supported: Optional[bool] = None
        self._pack_axes: Dict[Any, Any] = {}
        self.step_pack_stats = {"dispatches": 0, "packed_rows": 0,
                                "rows_capacity": 0}

    # -- observability (utils/trace.py; docs/OBSERVABILITY.md) -------------

    def attach_step_timeline(self, timeline):
        """Record a per-denoise-step timeline (`utils.trace.StepTimeline`)
        for every monolithic dispatch through this executor: wall time
        per step tagged warmup/full/shallow plus live comm-byte counters
        reconciled against `comm_plan`.  Timeline-carrying generations
        run the per-step callback dispatch path — use for profiling
        runs, not steady-state serving."""
        self.pipeline.step_timeline = timeline
        return timeline

    def comm_plan(self) -> dict:
        """The closed-form wire-byte plan for one dispatch at this
        executor's step count (pipelines.comm_plan) — what the live
        timeline counters are checked against."""
        return self.pipeline.comm_plan(self.steps)

    def _in_channels(self) -> int:
        pipe = self.pipeline
        for attr in ("unet_config", "dit_config", "mmdit_config"):
            cfg = getattr(pipe, attr, None)
            if cfg is not None:
                return cfg.in_channels
        raise AttributeError(f"{type(pipe).__name__} has no model config")

    def _draw_latents(self, seeds: Sequence[int]):
        """Per-request seeded initial noise (scaled like _batched_generate's
        internal draw): row r from seed r's key alone, bit-identical to
        per-seed draws, as one cached program (`pipelines.seeded_latents`)."""
        from ..pipelines import seeded_latents

        cfg = self.pipeline.distri_config
        shape = (cfg.latent_height, cfg.latent_width, self._in_channels())
        with span("distri.pipe.latents"):
            return seeded_latents(seeds, shape,
                                  self.pipeline.scheduler.init_noise_sigma)

    def _pad_batch(self, prompts, negative_prompts, seeds):
        """Pad to the compiled batch width by repeating the tail (same
        convention as pipelines._pad_rows); callers drop padded outputs.
        ONE padding rule shared by ``__call__`` and ``encode_stage`` keeps
        the monolithic and staged dispatch modes in lockstep."""
        n_real = len(prompts)
        pad = (-n_real) % self.batch_size
        if pad:
            prompts = list(prompts) + [prompts[-1]] * pad
            negative_prompts = (list(negative_prompts)
                                + [negative_prompts[-1]] * pad)
            seeds = list(seeds) + [seeds[-1]] * pad
        return list(prompts), list(negative_prompts), list(seeds), n_real

    def attach_prompt_cache(self, cache):
        """Use ``cache`` (serve/promptcache.py) in front of every encode:
        repeated prompt chunks skip tokenize + text-encode.  Monolithic
        dispatch reroutes through the stage programs (encode -> denoise ->
        decode run serially), which are bit-identical to `generate_batch`
        per (prompt, seed, steps) — the PR-5 staging invariant — so
        caching changes latency, never images."""
        self.prompt_cache = cache
        return cache

    def _encode_chunk(self, stages, p_chunk, n_chunk):
        """One compiled-width encode, memoized by (family, tokenizer
        hash, prompt chunk) when a prompt cache is attached."""
        def encode():
            # the rewrite stage first, where a rewriter is resident
            if stages.rewrite is None:
                return stages.encode(p_chunk, n_chunk)
            return stages.encode(p_chunk, n_chunk, stages.rewrite(p_chunk))

        if self.prompt_cache is None:
            return encode()
        key = (self._encode_cache_family, tuple(p_chunk), tuple(n_chunk))
        return self.prompt_cache.get_or_encode(key, encode)

    def __call__(
        self,
        prompts: List[str],
        negative_prompts: List[str],
        guidance_scale: float,
        seeds: List[int],
    ) -> List[Any]:
        if self.fault_plan is not None:
            self.fault_plan.check("executor.execute", key=self.key,
                                  batch_size=len(prompts))
        return self._run_batch(prompts, negative_prompts, guidance_scale,
                               seeds)

    def _run_batch(self, prompts, negative_prompts, guidance_scale, seeds):
        with span("distri.exec.run", rows=len(prompts)):
            if self.prompt_cache is not None:
                # cached-encode path: the stage programs run serially (see
                # attach_prompt_cache) so the memoized embeddings slot in.
                # Each blocks on its own output, so ``dispatch`` here holds
                # the encode and denoise waits too
                with phases("distri.pipe.dispatch", stage="dispatch"):
                    work = self.encode_stage(prompts, negative_prompts,
                                             seeds)
                    work = self.denoise_stage(work, guidance_scale)
                    return self.decode_stage(work)
            prompts, negative_prompts, seeds, n_real = self._pad_batch(
                prompts, negative_prompts, seeds)
            bs = self.batch_size
            # A batch wider than the compiled width (batcher max_batch_size
            # > pipeline batch_size) runs as several exactly-bs invocations
            # of the same cached program — never a retrace, never a
            # contract error.
            latents = None
            images: List[Any] = []
            for i in range(0, len(prompts), bs):
                # one dispatch -> wait_device -> to_host -> post sequence a
                # chunk, opened here so that it holds the latent draw and
                # lasts until the images are this executor's (the
                # pipeline's __call__ and decode tail join it)
                with phases("distri.pipe.dispatch", stage="dispatch"):
                    if latents is None:
                        latents = self._draw_latents(seeds)
                    out = self.pipeline.generate_batch(
                        prompts[i:i + bs],
                        negative_prompts[i:i + bs],
                        num_inference_steps=self.steps,
                        guidance_scale=guidance_scale,
                        latents=latents[i:i + bs],
                        output_type="np",
                    )
                    images.extend(out.images)
            return images[:n_real]

    def warm(self) -> None:
        """Compile everything this executor will dispatch — text encoders,
        the denoise loop or per-step programs, VAE decode — by running one
        throwaway request through its own dispatch path, HERE on the build
        path.  `prepare()` only builds jit handles; XLA compiles at the
        first dispatch, and the server's first dispatch runs inside the
        watchdog: on one v5e the cold SDXL fused-loop compile took ~230 s
        against the 120 s `watchdog_timeout_s`, so the first request of
        every bucket — warmed or not — died as a hung batch (PR 21).

        Step-mode executors warm the solo per-step programs and, where
        rows pack (``batch_size >= 2``), a lockstep pair, which builds the
        packed-rows program of every step signature."""
        prompt, neg, seed, gs = "warm-up", "", 0, 5.0
        if getattr(self.key, "exec_mode", "fused") != "step":
            self._run_batch([prompt], [neg], gs, [seed])
            return
        work = self.step_begin(prompt, neg, seed, gs)
        while not self.step_done(work):
            self.step_run([work])
        self.step_finish(work)
        if self.batch_size >= 2 and self._step_pack_supported():
            pair = [self.step_begin(prompt, neg, seed, gs) for _ in range(2)]
            while not self.step_done(pair[0]):
                self.step_run(pair)
            for work in pair:
                self.step_abort(work)

    # -- staged contract (serve/staging.py) --------------------------------

    def prepare_stages(self):
        """Lazily build (and cache) the pipeline's stage programs — one
        `PipelineStages` per executor, at the executor's step count."""
        if self._stages is None:
            self._stages = self.pipeline.prepare_stages(self.steps)
        return self._stages

    def encode_stage(self, prompts: List[str], negative_prompts: List[str],
                     seeds: List[int]) -> Dict[str, Any]:
        """Stage 1: pad, tokenize + text-encode every compiled-width chunk
        and draw the per-request seeded latents — encoder/host work that
        rides in the shadow of another batch's denoise."""
        import jax

        stages = self.prepare_stages()
        prompts, negative_prompts, seeds, n_real = self._pad_batch(
            prompts, negative_prompts, seeds)
        bs = self.batch_size
        latents = self._draw_latents(seeds)
        encoded = [
            self._encode_chunk(stages, prompts[i:i + bs],
                               negative_prompts[i:i + bs])
            for i in range(0, len(prompts), bs)
        ]
        # block so the stage's service time (and the denoise worker's
        # queue) reflects real encode compute, not async dispatch
        jax.block_until_ready((encoded, latents))
        return {"n_real": n_real, "encoded": encoded, "latents": latents,
                # cached embeddings must NOT be "donated" after the
                # denoise consumes them — the cache still owns the buffers
                "encode_cached": self.prompt_cache is not None,
                "latent": None}

    def denoise_stage(self, work: Dict[str, Any],
                      guidance_scale: float) -> Dict[str, Any]:
        """Stage 2: the compiled denoise program — the mesh bottleneck the
        other stages hide behind.  Consumed inputs (initial latents,
        embeddings) are released immediately ("donated"): the next
        inflight batch reuses their HBM."""
        import jax
        import jax.numpy as jnp

        stages = self.prepare_stages()
        bs = self.batch_size
        lats = work["latents"]
        outs = [
            stages.denoise(enc, lats[i * bs:(i + 1) * bs], guidance_scale)
            for i, enc in enumerate(work["encoded"])
        ]
        latent = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
        latent = jax.block_until_ready(latent)
        encoded = work.pop("encoded")
        if not work.get("encode_cached"):
            # prompt-cache-owned embeddings stay resident for future hits;
            # everything else donates its HBM the moment denoise is done
            _release_buffers(encoded)
        _release_buffers(work.pop("latents"))
        work["latent"] = latent
        return work

    def decode_stage(self, work: Dict[str, Any]) -> List[Any]:
        """Stage 3: chunked VAE decode + device->host conversion, padded
        rows stripped — per-request np images, same convention as
        ``__call__``."""
        stages = self.prepare_stages()
        images = stages.decode(work["latent"])
        _release_buffers(work.pop("latent"))
        return list(images)[:work["n_real"]]

    # -- step-granular contract (serve/stepbatch.py) -----------------------
    #
    # One request per work: the slot pool holds each request's denoise
    # carry (latent + patch/KV state + scheduler state) EXTERNALLY and
    # advances it one step at a time, so requests join/leave/park between
    # steps.  Every step runs the request padded to the compiled batch
    # width alone — batch rows are independent end to end (the PR-1
    # coalescing invariant), so who else occupies the pool can never
    # touch this request's numerics, and a parked carry resumes
    # bit-identically: same per-step programs, same inputs, same order.

    def step_begin(self, prompt: str, negative_prompt: str, seed: int,
                   guidance_scale: float) -> Dict[str, Any]:
        """Admit one request into step-granular execution: encode (via
        the prompt cache when attached), draw the request's seeded
        latent, and initialize the explicit denoise carry.  Returns the
        work dict `step_run`/`step_finish`/`step_preview` consume."""
        import jax

        pipe = self.pipeline
        if not hasattr(pipe, "step_carry_init"):
            raise AttributeError(
                f"{type(pipe).__name__} has no step-granular carry hooks "
                "(PipeFusion runners have no host-driven per-step loop)"
            )
        stages = self.prepare_stages()
        prompts, negs, seeds, _ = self._pad_batch(
            [prompt], [negative_prompt], [seed])
        bs = self.batch_size
        # __call__ forces guidance_scale to 1 when CFG is off; the step
        # path applies the same normalization for identity (the exact
        # rule prepare_stages' denoise program uses)
        cfg_on = pipe.distri_config.do_classifier_free_guidance
        with span("distri.step.begin", stage="begin"):
            enc = self._encode_chunk(stages, prompts[:bs], negs[:bs])
            latents = self._draw_latents(seeds[:bs])
            carry = pipe.step_carry_init(latents, self.steps)
            jax.block_until_ready(
                jax.tree_util.tree_leaves((carry[0], latents)))
        return {
            "carry": carry,
            "enc": enc,
            "gs": guidance_scale if cfg_on else 1.0,
            "i": 0,
            # which batch row of the carry is this request's REAL row —
            # 0 in the solo layout; a packed dispatch re-homes it
            "row": 0,
            "encode_cached": self.prompt_cache is not None,
        }

    # -- packed cohort dispatch (parallel/rowpack.py) ----------------------

    def _step_pack_supported(self) -> bool:
        if self._pack_supported is None:
            fn = getattr(self.pipeline, "step_carry_pack_supported", None)
            self._pack_supported = bool(fn()) if fn is not None else False
        return self._pack_supported

    def step_signature(self, work: Dict[str, Any]):
        """Hashable pack-compatibility key of this work's NEXT step:
        works sharing a signature run the same compiled per-step program
        and may pack into one dispatch's batch rows.  ``None`` = this
        work can only run sequentially (unsupported pipeline or config).
        The executor's identity is part of the key — packing never spans
        executors."""
        if not self._step_pack_supported():
            return None
        sig = self.pipeline.step_carry_signature(work["carry"], work["i"],
                                                 self.steps)
        return (id(self), sig)

    def _step_axes(self, work: Dict[str, Any]):
        """The rowpack per-leaf plan for this work's carry structure
        (cached per treedef — the UNet carry's patch state appears after
        its first step, so one executor sees more than one structure).
        ``None`` = ambiguous layout; that structure stays sequential."""
        import jax

        from ..parallel import rowpack

        key = jax.tree_util.tree_structure(work["carry"])
        if key not in self._pack_axes:
            try:
                self._pack_axes[key] = self.pipeline.step_carry_rows_axes(
                    work["carry"], work["enc"], self.steps)
            except rowpack.AmbiguousPackAxisError:
                self._pack_axes[key] = None
        return self._pack_axes[key]

    def _step_ensure_solo(self, work: Dict[str, Any]) -> None:
        """Normalize a work back to the SOLO carry layout: its real row
        extracted from the shared packed carry and tiled across the
        width — byte-identical to a never-packed carry (a solo carry's
        rows are identical by construction).  Park/export/migration and
        singleton dispatches all run through this, so the PR-17 snapshot
        format and the solo per-step programs never see packed state."""
        grp = work.pop("pack", None)
        if grp is None:
            return
        from ..parallel import rowpack

        work["carry"] = rowpack.extract_row(
            work["carry"], work.get("row", 0), grp["axes"],
            self.batch_size)
        work["row"] = 0

    def _step_solo_one(self, work: Dict[str, Any]) -> None:
        """One sequential-legacy step: the pre-pack per-slot dispatch."""
        with span("distri.step.run", stage="steps", rows=1,
                  signature="solo"):
            self._step_ensure_solo(work)
            work["carry"] = self.pipeline.step_carry_step(
                work["carry"], work["i"], work["enc"], work["gs"],
                self.steps)
        work["i"] += 1
        stats = self.step_pack_stats
        stats["dispatches"] += 1
        stats["packed_rows"] += 1
        stats["rows_capacity"] += self.batch_size

    def _step_dispatch_packed(self, members: List[Dict[str, Any]],
                              signature) -> None:
        """Advance a same-signature group in ONE compiled dispatch:
        member r's real row rides batch row r of a shared packed carry,
        its step index and guidance scale ride [B] vectors.  Fast path:
        when the whole group is the SAME pack as last round (same shared
        carry, full membership, rows 0..n-1) the carry re-dispatches
        as-is — zero repack work in the steady state.  Otherwise the
        members' rows (solo or previously packed) repack into a fresh
        shared carry.  Ambiguous layouts fall back to sequential."""
        from ..parallel import rowpack

        pipe = self.pipeline
        bs = self.batch_size
        n = len(members)
        stats = self.step_pack_stats
        grp0 = members[0].get("pack")
        fast = (
            grp0 is not None
            and grp0.get("n") == n
            and all(m.get("pack") is grp0 for m in members)
            and all(m["carry"] is members[0]["carry"] for m in members)
            and sorted(m.get("row", 0) for m in members) == list(range(n))
        )
        axes = None if fast else self._step_axes(members[0])
        packed = fast or axes is not None
        # the repack is part of what a packed dispatch costs the host
        with span("distri.step.run", stage="steps", rows=n,
                  signature=str(signature)):
            if fast:
                members = sorted(members, key=lambda m: m["row"])
                carry, enc, grp = members[0]["carry"], grp0["enc"], grp0
            elif packed:
                try:
                    carry = rowpack.pack_rows(
                        [m["carry"] for m in members],
                        [m.get("row", 0) for m in members], axes, bs)
                except rowpack.AmbiguousPackAxisError:
                    packed = False
                else:
                    enc = pipe.step_carry_pack_enc(
                        [m["enc"] for m in members], bs)
                    grp = {"axes": axes, "n": n, "enc": enc}
            if packed:
                i_rows = [m["i"] for m in members]
                gs_rows = [float(m["gs"]) for m in members]
                i_rows += [i_rows[-1]] * (bs - n)
                gs_rows += [gs_rows[-1]] * (bs - n)
                new_carry = pipe.step_carry_step_rows(
                    carry, i_rows, enc, gs_rows, self.steps)
        if not packed:
            for m in members:
                self._step_solo_one(m)
            return
        for r, m in enumerate(members):
            m["carry"] = new_carry
            m["row"] = r
            m["pack"] = grp
            m["i"] += 1
        stats["dispatches"] += 1
        stats["packed_rows"] += n
        stats["rows_capacity"] += bs

    def step_run(self, works: List[Dict[str, Any]]) -> None:
        """Advance each work by exactly ONE denoise step (its own step
        index — cohort members may sit at different timesteps).  Blocks
        until the cohort's step compute is done so the step batcher's
        calibrated per-step service time is honest.

        Cohort members whose next step shares a compiled signature
        (`step_signature`: same phase / patch-state stage / shallow flag)
        advance in ONE padded dispatch — each member's real row rides its
        own batch row, legal and bit-identical by the PR-1 batch-row
        independence invariant (pinned in tests/test_stepbatch.py).
        Groups form in cohort (EDF) order, at most ``batch_size`` rows
        each; singleton groups, unsupported configs (`step_signature` ->
        None), and ambiguous carry layouts run the solo per-slot
        dispatch unchanged.  `step_pack_stats` tallies this call's
        dispatches / real rows / row capacity for the server's
        pack-efficiency counters."""
        import jax

        self.step_pack_stats = {"dispatches": 0, "packed_rows": 0,
                                "rows_capacity": 0}
        bs = self.batch_size
        groups: List[tuple] = []  # (signature, members)
        solos: List[Dict[str, Any]] = []
        open_group: Dict[Any, List[Dict[str, Any]]] = {}
        for w in works:
            sig = self.step_signature(w)
            if sig is None:
                solos.append(w)
                continue
            g = open_group.get(sig)
            if g is None or len(g) >= bs:
                g = []
                open_group[sig] = g
                groups.append((sig[1], g))
            g.append(w)
        for w in solos:
            self._step_solo_one(w)
        for signature, members in groups:
            if len(members) == 1:
                self._step_solo_one(members[0])
            else:
                self._step_dispatch_packed(members, signature)
        with span("distri.step.wait", stage="steps"):
            jax.block_until_ready([w["carry"][0] for w in works])

    def step_done(self, work: Dict[str, Any]) -> bool:
        return work["i"] >= self.steps

    def step_finish(self, work: Dict[str, Any]):
        """Decode the finished carry to the request's np image — the
        work's own packed row (row 0 in the solo layout)."""
        stages = self.prepare_stages()
        pipe = self.pipeline
        with span("distri.step.finish", stage="finish"):
            latent = pipe.step_carry_latent(work["carry"])
            images = stages.decode(latent)
        row = work.get("row", 0)
        grp = work.pop("pack", None)
        carry = work.pop("carry")
        if grp is None:
            _release_buffers(carry)
        # a packed carry is SHARED with the group's other members:
        # dropping this reference is the release — host GC reclaims the
        # buffers once the last member finishes/repacks away
        enc = work.pop("enc", None)
        if not work.get("encode_cached"):
            # prompt-cache-owned embeddings stay resident for future hits
            _release_buffers(enc)
        return list(images)[row]

    def step_abort(self, work: Dict[str, Any]) -> None:
        """Release a work's device buffers without decoding (failed or
        stopped mid-denoise) — the step path's `_release_buffers`
        donation, same convention as the staged pipeline.  A packed
        (shared) carry is only dereferenced, never deleted."""
        grp = work.pop("pack", None)
        carry = work.pop("carry", None)
        if grp is None:
            _release_buffers(carry)
        enc = work.pop("enc", None)
        if not work.get("encode_cached"):
            _release_buffers(enc)

    def step_park(self, work: Dict[str, Any]) -> None:
        """Preemption: pull the carry to HOST memory so the parked
        request stops holding device residency (the slot it frees goes
        to the preemptor).  A packed member first extracts back to its
        solo layout (`_step_ensure_solo` — byte-identical to a
        never-packed carry).  device->host->device is an exact byte
        round-trip, so the resumed denoise is bit-identical — pinned by
        tests/test_stepbatch.py."""
        import jax

        self._step_ensure_solo(work)
        work["carry"] = jax.device_get(work["carry"])

    def step_resume(self, work: Dict[str, Any]) -> None:
        """Resume a parked carry: nothing to do eagerly — the next
        `step_run` re-uploads the host leaves through its jitted call,
        byte-exactly."""

    def step_export(self, work: Dict[str, Any]):
        """Carry migration (serve/migration.py): flatten the request's
        denoise carry to HOST numpy leaves for serialization.  The same
        device->host round-trip `step_park` pins as bit-exact, so an
        importing replica resumes the identical bytes.  Returns
        ``(extra_meta, leaves)``: the executor-owned header fields
        (family + step index) and the flat leaf list; the work itself is
        left intact (the caller still releases it via `step_abort`).  A
        packed member exports its SOLO layout (`_step_ensure_solo`), so
        the snapshot format is identical whether or not the round it
        left in was packed."""
        import jax
        import numpy as np

        self._step_ensure_solo(work)
        host = jax.device_get(work["carry"])
        leaves = [np.asarray(leaf)
                  for leaf in jax.tree_util.tree_leaves(host)]
        extra = {"family": type(self.pipeline).__name__,
                 "step": int(work["i"])}
        return extra, leaves

    def step_import(self, meta: Dict[str, Any], leaves, prompt: str,
                    negative_prompt: str, seed: int,
                    guidance_scale: float) -> Dict[str, Any]:
        """Adopt an exported carry: rebuild the request's work via the
        deterministic `step_begin` machinery (re-encoded embeddings and
        a template carry give the treedef — encode is a pure function of
        the prompt, so the embeddings are bit-identical to the
        exporter's), validate every snapshot leaf against the template's
        shape/dtype, then graft the snapshot leaves in and resume at the
        exported step index.  Structure drift rejects TYPED
        (`MigrationRejectedError`) — resuming a mismatched carry would
        be silent corruption, and the fleet's fallback is a clean
        from-step-0 retry."""
        import jax

        from .errors import MigrationRejectedError

        family = type(self.pipeline).__name__
        if meta.get("family") != family:
            raise MigrationRejectedError(
                f"carry snapshot family {meta.get('family')!r} cannot "
                f"import into a {family} executor"
            )
        step = int(meta["step"])
        if not (0 <= step <= self.steps):
            raise MigrationRejectedError(
                f"carry snapshot step {step} out of range for a "
                f"{self.steps}-step executor"
            )
        work = self.step_begin(prompt, negative_prompt, seed,
                               guidance_scale)
        template = work["carry"]
        tmpl_leaves, treedef = jax.tree_util.tree_flatten(template)
        if len(leaves) != len(tmpl_leaves):
            self.step_abort(work)
            raise MigrationRejectedError(
                f"carry snapshot has {len(leaves)} leaves; this "
                f"executor's carry has {len(tmpl_leaves)}"
            )
        for i, (got, want) in enumerate(zip(leaves, tmpl_leaves)):
            got_shape = tuple(got.shape)
            want_shape = tuple(want.shape)
            got_dtype = str(got.dtype)
            want_dtype = str(want.dtype)
            if got_shape != want_shape or got_dtype != want_dtype:
                self.step_abort(work)
                raise MigrationRejectedError(
                    f"carry snapshot leaf {i} is {got_shape}/{got_dtype}"
                    f"; this executor's carry wants "
                    f"{want_shape}/{want_dtype}"
                )
        # graft the exported HOST leaves into the template's structure:
        # the next step_run re-uploads them through its jitted call,
        # byte-exactly — the park/resume protocol, across replicas
        work["carry"] = jax.tree_util.tree_unflatten(treedef, list(leaves))
        work["i"] = step
        _release_buffers(tmpl_leaves)
        return work

    def step_preview(self, work: Dict[str, Any],
                     max_size: int = 64):
        """Cheap intermediate preview: the request's CURRENT latent,
        host-side — first three latent channels min-max normalized and
        stride-downsampled to at most ``max_size`` per edge.  No compiled
        program, no VAE: previews cost O(latent bytes) host work, never
        mesh time."""
        import numpy as np

        pipe = self.pipeline
        lat = np.asarray(
            pipe.step_carry_latent(work["carry"]))[work.get("row", 0)]
        rgb = (lat[..., :3] if lat.shape[-1] >= 3
               else np.repeat(lat[..., :1], 3, axis=-1))
        lo, hi = float(rgb.min()), float(rgb.max())
        rgb = (rgb - lo) / ((hi - lo) or 1.0)
        stride = max(1, -(-max(rgb.shape[0], rgb.shape[1]) // int(max_size)))
        return rgb[::stride, ::stride].astype(np.float32)


def apply_key_policy(pipeline, key: ExecKey) -> None:
    """Make the built pipeline honor the key's degradation-relevant
    fields even when ``build_pipeline`` ignored them.

    The degradation ladder (serve/resilience.py) produces keys with the
    step cache disabled or ``exec_mode="stepwise"``; builders written
    before those fields existed construct their DistriConfig from
    (height, width, cfg, scheduler) only.  Both degraded directions are
    safe to force post-construction and pre-`prepare()`: turning the
    cadence OFF removes a compiled body, and the stepwise switch is the
    pipeline's own `set_stepwise` policy hook.  (The opposite direction —
    a key *requesting* a cadence the builder didn't configure — is the
    builder's job; forcing it here could violate the model's depth
    bounds, so it is left alone.)"""
    dcfg = pipeline.distri_config
    # Parallelization strategy is NOT forcible post-construction (the
    # runner class is chosen at pipeline build): a builder must construct
    # from key.parallelism/key.pipe_patches.  The key tracks exactly the
    # patch-vs-pipefusion distinction (tensor/naive_patch builders under
    # a "patch" key are the pre-existing legacy contract and stay legal);
    # crossing THAT line is deterministic for every rebuild of this
    # (builder, key) pair, so it raises TYPED — when the key was degraded
    # onto "patch" by the pipeline_off rung and the builder cannot honor
    # it, the retry loop retracts the rung instead of retrying into the
    # same wall (and when the key itself requested the impossible
    # strategy, the retraction no-ops and the build failure surfaces
    # normally).
    if (key.parallelism == "pipefusion") != (dcfg.parallelism == "pipefusion"):
        raise DegradationInapplicableError(
            f"key wants parallelism={key.parallelism!r} but the builder "
            f"constructed {dcfg.parallelism!r} — build_pipeline must read "
            "key.parallelism", rung="pipeline_off")
    if key.parallelism == "pipefusion" and key.pipe_patches:
        # ground truth is the RUNNER's effective patch count (a builder
        # that ignores the field leaves dcfg.pipe_patches=None and the
        # runner falls back to one patch per stage — comparing the config
        # field would wave that through under the ':pfN' cache identity)
        built = getattr(getattr(pipeline, "runner", None), "patches",
                        dcfg.pipe_patches)
        if built != key.pipe_patches:
            raise DegradationInapplicableError(
                f"key wants pipe_patches={key.pipe_patches} but the "
                f"builder constructed {built} — build_pipeline must read "
                "key.pipe_patches", rung="pipeline_off")
    if (key.step_cache_interval == 1
            and (dcfg.step_cache_interval, dcfg.step_cache_depth) != (1, 0)):
        dcfg.step_cache_interval = 1
        dcfg.step_cache_depth = 0
    # same convention for stale-refresh compression: forcing the exact
    # "none" direction is always safe (the uncompressed exchange has no
    # support requirements); a key *requesting* a mode the builder didn't
    # configure is the builder's job, like the cadence above
    if key.comm_compress == "none" and dcfg.comm_compress != "none":
        dcfg.comm_compress = "none"
    # PCPP partial refresh: the RESET direction (key at 1.0) always
    # forces safely, like comm_compress="none".  The partial direction
    # also forces pre-prepare — the fraction is read at trace time, adds
    # no weights and no carry-structure change — but ONLY onto gather-
    # layout builders, where every family's refresh path honors it; the
    # DiT/MMDiT ring/ulysses/usp layouts have no refresh collective to
    # thin, and silently setting the field post-construction would skip
    # the runner __init__ validation and cache a ':pr' key that moves
    # full bytes while the controller costs it as degraded.  Raising
    # makes the build fail loudly instead (the builder must construct
    # from key.refresh_fraction, or the tier table must not request it).
    if (key.parallelism == "patch" and dcfg.parallelism == "patch"
            and getattr(dcfg, "refresh_fraction", 1.0)
            != key.refresh_fraction):
        if key.refresh_fraction >= 1.0:
            dcfg.refresh_fraction = 1.0
        elif getattr(dcfg, "attn_impl", "gather") == "gather":
            from ..parallel.compress import validate_refresh_fraction

            validate_refresh_fraction(key.refresh_fraction)
            dcfg.refresh_fraction = float(key.refresh_fraction)
        else:
            raise ValueError(
                f"key wants refresh_fraction={key.refresh_fraction} but "
                f"the builder constructed attn_impl={dcfg.attn_impl!r} — "
                "partial refresh is forcible onto the gather layout only; "
                "build_pipeline must read key.refresh_fraction itself"
            )
    # weight_quant inverts the convention: here the QUANTIZE direction is
    # the safe post-construction force (quantizing the built dense tree is
    # exactly what load-time quantization does), and the ladder's
    # weight_quant_on rung depends on it working against builders that
    # ignore the field.  The reverse — a full-precision key against a
    # quantized builder — raises inside set_weight_quant: the dense
    # kernels are gone, and a silently dequantized "full-precision"
    # program would carry hidden rounding error.
    if (key.weight_quant != getattr(dcfg, "weight_quant", "none")
            and hasattr(pipeline, "set_weight_quant")):
        try:
            pipeline.set_weight_quant(key.weight_quant)
        except ValueError as exc:
            # deterministic for every rebuild of this (builder, key) pair
            # — the retry loop retracts the weight_quant_on rung instead
            # of retrying into the same wall (serve/errors.py)
            raise DegradationInapplicableError(
                str(exc), rung="weight_quant_on") from exc
    # quant_compute re-tags the EXECUTION policy of already-quantized
    # kernels (no payload change, no numerics until the next trace picks
    # its routed path) — always safe to force post-construction, in both
    # directions
    if (key.quant_compute != getattr(dcfg, "quant_compute", "auto")
            and hasattr(pipeline, "set_quant_compute")):
        pipeline.set_quant_compute(key.quant_compute)
    if key.exec_mode in ("stepwise", "step"):
        # both host-driven modes run the per-step compiled programs; the
        # "step" mode additionally exposes the explicit carry the slot
        # pool (serve/stepbatch.py) holds per request.  set_stepwise
        # keeps the monolithic __call__ on the SAME programs, so a solo
        # monolithic run at this key is bit-identical to the step path.
        try:
            pipeline.set_stepwise(True)
        except ValueError as exc:
            raise DegradationInapplicableError(
                str(exc), rung="stepwise_fallback") from exc


def pipeline_executor_factory(
    build_pipeline: Callable[[ExecKey], Any],
    fault_plan: Optional[FaultPlan] = None,
) -> Callable[[ExecKey], PipelineExecutor]:
    """Executor factory for `InferenceServer` from a pipeline builder.

    ``build_pipeline(key)`` constructs the pipeline for a bucket — e.g. a
    DistriConfig at (key.height, key.width) with
    do_classifier_free_guidance=key.cfg, then ``from_pretrained`` /
    ``from_params`` with key.scheduler.  The factory builds the programs
    (`prepare`) and compiles them with one throwaway request
    (`PipelineExecutor.warm`), so cache misses pay the full cost HERE, off
    the per-request path and outside the dispatch watchdog, and hands back
    a ready executor.  ``fault_plan`` injects at sites
    ``"executor.build"`` / ``"executor.execute"``.
    """

    def factory(key: ExecKey) -> PipelineExecutor:
        if fault_plan is not None:
            fault_plan.check("executor.build", key=key)
        pipe = build_pipeline(key)
        apply_key_policy(pipe, key)
        pipe.prepare(key.steps)
        executor = PipelineExecutor(pipe, key.steps, key=key,
                                    fault_plan=fault_plan)
        executor.warm()
        return executor

    return factory
