"""Patch-level pipeline parallelism (PipeFusion) as one XLA program.

The displaced-patch runner (parallel/runner.py) keeps every weight on every
device and shards the *sequence*; this runner shards the *depth*: the DiT's
stacked blocks are split over the ``sp`` mesh axis into P pipeline stages,
and the image's M token-chunks ("patches") stream through the stages like
micro-batches — patch-level pipeline parallelism for diffusion transformers
(PipeFusion, arXiv 2405.14430; PAPERS.md).  Weights per device shrink to
``depth/P`` blocks, and the per-hop traffic is ONE activation chunk
``[B, N/M, hidden]`` between mesh neighbors per tick — O(L/M) point-to-point
instead of the O(L) all-gather the displaced-patch layout refreshes.

Staleness makes the pipeline dense: a patch's self-attention at stage p
attends over the full sequence using each block's carried KV cache, where
its own rows are fresh-this-tick and other patches' rows are
newest-available (fresh-this-step for patches already through stage p this
step, previous-step otherwise) — the same input-temporal-redundancy argument
as DistriFusion's displaced patches, applied along the depth axis.

Schedule (steady state, item q = (step - warmup)*M + patch):
* stage p computes item q at tick ``q + p``; a ring `ppermute` hands its
  output to stage p+1 for tick q+p+1;
* stage P-1's output is the epsilon chunk; the same ring delivers it to
  stage 0 at tick ``q + P``, which CFG-combines it (all_gather over the
  ``cfg`` axis), scheduler-steps that patch's latent rows, and — in the very
  same tick with M == P — embeds the patch for its next step.  ``M >= P`` is
  exactly the condition that the refreshed latent is ready when re-embedding
  needs it.
* Warmup steps (reference counter <= warmup_steps semantics) run the full
  sequence as ONE mega-patch through the pipeline — serial across stages but
  numerically exact, and each stage's pass leaves fresh full-sequence KV in
  its caches, so the first displaced item is one-step-stale, never colder.

Everything — warmup, steady ticks, drain — is two `lax.scan`s inside one
`shard_map`/`jit` program over the (dp, cfg, sp) mesh; there is no host
round-trip per tick.  The per-tick KV commit is a `dynamic_update_slice`
into the scan carry, which XLA aliases in place.

Composition: the ``cfg`` axis still batch-parallelizes classifier-free
guidance (epsilon chunks are gathered and combined at stage 0), ``dp`` still
shards independent images, and the scheduler family (DDIM/Euler/DPM++ 2M)
steps patch-wise — its state is carried stacked per patch so DPM's
cross-step scalars stay correct while patches of adjacent steps interleave.

First-class knob composition (PR 7; ROADMAP item 2):

* **Temporal step cache** (``step_cache_interval``/``step_cache_depth``,
  parallel/stepcache.py): ``step_cache_depth`` counts *pipeline stages*
  here — on shallow steps the deepest K stages do not run their blocks.
  Each deep stage carries a per-patch residual delta ``out - in`` recorded
  at its last full pass (warmup passes record it too, so the first
  post-warmup step may already be shallow); on a shallow item the stage's
  tick body takes a `lax.cond` branch that emits ``h_in + delta[patch]``
  and leaves its KV cache untouched — the stage's block FLOPs and KV
  commits vanish from the shallow path while the tick schedule (and hence
  the static scan shape) stays uniform, so the compiled program carries
  exactly two tick bodies (full + pass-through) like the displaced
  runners' full/shallow pair.  The ring hops themselves still run on
  shallow ticks (a chunk must still travel to stage 0 for its scheduler
  update), so shallow wire bytes equal full-step bytes — ``comm_report``
  says so explicitly.
* **Wire compression** (``comm_compress``, parallel/compress.py): the
  inter-stage activation chunk is quantized before each steady-state
  `ppermute` hop and dequantized right after (int8/fp8 payload + one fp32
  scale per token row).  ``int8_residual`` delta-codes against the
  previous step's chunk for the same (patch, sender-stage) pair,
  closed-loop: sender and receiver both carry the *reconstructed*
  previous payload (seeded from the exact warmup hops), so quantization
  error never accumulates.  Warmup mega-patch hops never compress —
  warmup-only runs stay bit-identical.
* **Quantized weights** (``weight_quant``): the stacked block tree is
  quantized BEFORE the depth split with depth-leading per-tile scales
  (compress.QuantizedTensor), so shard_map slices payload and scale alike
  and each stage holds 1-byte stage-local kernels, dequantized at the
  consuming dot.
"""

from __future__ import annotations

import types
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ..models import dit as dit_mod
from ..models.dit import DiTConfig
from ..ops.linear import linear
from .compress import dequantize, fp8_dtype, quantize, wire_nbytes
from .guidance import branch_select, combine_guidance
from ..schedulers import BaseScheduler
from ..utils.config import CFG_AXIS, DP_AXIS, SP_AXIS, DistriConfig


def _tree_dynamic_index(tree, i):
    return jax.tree.map(
        lambda l: lax.dynamic_index_in_dim(l, i, axis=0, keepdims=False), tree
    )


def _tree_dynamic_update(tree, sub, i, pred):
    """Write ``sub`` at index ``i`` of stacked ``tree`` where ``pred``."""

    def upd(l, s):
        new = lax.dynamic_update_index_in_dim(l, s.astype(l.dtype), i, axis=0)
        return jnp.where(pred, new, l)

    return jax.tree.map(upd, tree, sub)


def _buf_update(buf, val, i, pred):
    """Write ``val`` at index ``i`` of per-patch buffer ``buf`` where
    ``pred`` (the masked commit idiom shared by the delta and predictor
    carries)."""
    new = lax.dynamic_update_index_in_dim(buf, val.astype(buf.dtype), i,
                                          axis=0)
    return jnp.where(pred, new, buf)


class PipeFusionRunner:
    """Compiled PipeFusion generation loop for a DiT.

    API mirrors DenoiseRunner.generate: latents/enc in, final latent out,
    every device returning the full denoised latent.
    """

    def __init__(
        self,
        distri_config: DistriConfig,
        dit_config: DiTConfig,
        params,
        scheduler: BaseScheduler,
        pipe_patches: Optional[int] = None,
    ):
        self.cfg = distri_config
        self.dcfg = dit_config
        self.params = params
        self.scheduler = scheduler
        cfg, dcfg = distri_config, dit_config
        if cfg.attn_impl != "gather":
            raise ValueError(
                f"attn_impl={cfg.attn_impl!r} applies to the displaced DiT "
                "runner (parallel/dit_sp.py); the pipeline's per-block KV "
                "cache is its own attention layout"
            )
        if cfg.mode == "no_sync":
            raise ValueError(
                "mode='no_sync' does not apply to the patch pipeline: its KV "
                "caches refresh every tick by construction (freezing warmup "
                "KV is the displaced runners' knob); use the displaced DiT "
                "runner for no_sync"
            )
        if not cfg.use_cuda_graph:
            raise ValueError(
                "use_cuda_graph=False (--no_cuda_graph) does not apply to "
                "the patch pipeline: the tick schedule exists only inside "
                "the fused scan program — there is no per-step host loop to "
                "fall back to"
            )
        self.stages = cfg.n_device_per_batch
        if pipe_patches is None:
            pipe_patches = cfg.pipe_patches  # may still be None
        self.patches = self.stages if pipe_patches is None else pipe_patches
        if cfg.step_cache_enabled and cfg.step_cache_depth >= self.stages:
            raise ValueError(
                "under PipeFusion, step_cache_depth counts PIPELINE STAGES "
                f"skipped on shallow steps: depth {cfg.step_cache_depth} "
                f"must be < the {self.stages} stages (stage 0 embeds and "
                "scheduler-steps, it can never be skipped)"
            )
        n_tok = dcfg.num_tokens
        if dcfg.depth % self.stages != 0:
            raise ValueError(
                f"DiT depth {dcfg.depth} must divide evenly into "
                f"{self.stages} pipeline stages"
            )
        if self.patches < self.stages:
            raise ValueError(
                f"pipe_patches ({self.patches}) must be >= pipeline stages "
                f"({self.stages}): the scheduler refresh of a patch returns to "
                "stage 0 exactly P ticks after it left, so fewer patches than "
                "stages would re-embed a latent that is not yet stepped"
            )
        if n_tok % self.patches != 0:
            raise ValueError(
                f"token count {n_tok} must be divisible by pipe_patches "
                f"({self.patches})"
            )
        if dcfg.hidden_size < dcfg.token_out_dim:
            raise ValueError(
                "hidden_size must be >= patch_size^2*out_channels so the "
                "epsilon chunk rides the activation ring payload"
            )
        if (cfg.height // 8 != dcfg.sample_size) or (cfg.width // 8 != dcfg.sample_size):
            raise ValueError(
                f"DistriConfig {cfg.height}x{cfg.width} implies latent "
                f"{cfg.latent_height}, but DiTConfig.sample_size is "
                f"{dcfg.sample_size} (square latents only for the DiT)"
            )
        # the depth stack lives sharded over the stages, everything else
        # replicated — the layout the programs' in_specs declare
        self.params = cfg.place(params, self._specs()[0])
        self._compiled: Dict[Any, Any] = {}

    # ------------------------------------------------------------------
    # pieces
    # ------------------------------------------------------------------

    def _branch_enc(self, enc):
        """Select this device's CFG branch of the text encoding [2, B, Lt, D]
        (same contract as DenoiseRunner._branch_inputs)."""
        my_enc, _, _ = branch_select(self.cfg, enc)
        return my_enc

    def _combine_eps(self, eps, gs, batch):
        """Guided epsilon from per-branch epsilon (chunk or full)."""
        return combine_guidance(self.cfg, eps, gs, batch)

    def _run_stage(self, blocks_local, cap_kv_local, kv_cache, h, c6, offset,
                   valid, cap_bias):
        """Run this device's Lp blocks on ``h`` [B, Lq, hid] against the
        full-sequence stale caches; returns (h_out, committed kv_cache)."""

        def body(carry, xs):
            hcur = carry
            bp, ckv, cache = xs
            h_out, (k_new, v_new) = dit_mod.dit_block(
                bp, self.dcfg, hcur, c6, ckv,
                self_kv=(cache[0], cache[1]), patch_start=offset,
                cap_bias=cap_bias,
            )
            return h_out, jnp.stack([k_new, v_new])

        h_out, fresh = lax.scan(body, h, (blocks_local, cap_kv_local, kv_cache))
        # fresh: [Lp, 2, B, Lq, hid] -> commit at the patch rows
        committed = lax.dynamic_update_slice(
            kv_cache, fresh.astype(kv_cache.dtype), (0, 0, 0, offset, 0)
        )
        kv_cache = jnp.where(valid, committed, kv_cache)
        return h_out, kv_cache

    # ------------------------------------------------------------------
    # the device program
    # ------------------------------------------------------------------

    def _tick_ctx(self, params, enc, cap_mask, gs, batch, num_steps, n_sync):
        """Setup + the two tick closures, shared by the fused loop and the
        hybrid pair of programs (everything here is carry-free: the ticks
        are pure functions of their carry)."""
        cfg, dcfg = self.cfg, self.dcfg
        sched = self.scheduler
        n_stage = self.stages
        n_patch = self.patches
        n_tok = dcfg.num_tokens
        chunk = n_tok // n_patch
        hid = dcfg.hidden_size
        d_in = dcfg.token_dim
        d_out = dcfg.token_out_dim
        p_idx = lax.axis_index(SP_AXIS)
        is_first = p_idx == 0
        is_last = p_idx == n_stage - 1

        my_enc = self._branch_enc(enc)
        my_mask, _, _ = branch_select(cfg, cap_mask)
        cap_bias = dit_mod.caption_mask_bias(my_mask)
        bloc = my_enc.shape[0]  # batch inside the pipeline (2B when folded)

        # knob composition (module docstring): wire compression of the
        # steady ring hops + the stage-skipping step cache
        mode = cfg.comm_compress
        use_sc = cfg.step_cache_enabled
        n_deep = cfg.step_cache_depth if use_sc else 0
        interval = cfg.step_cache_interval
        is_deep = p_idx >= (n_stage - n_deep)  # False everywhere when off

        compute_dtype = params["proj_in"]["kernel"].dtype
        pos = dit_mod.pos_embed_table(dcfg, compute_dtype)

        blocks_local = params["blocks"]  # leaves [Lp, ...] (sharded over sp)
        # model-dtype entry cast, exactly like precompute_caption_kv's (its
        # docstring explains the silent upcast leak): fp32 caption embeds
        # would otherwise yield fp32 cross-attention KV that promotes the
        # whole residual stream — at bf16 that broke the _run_stage scan
        # carry outright (f32 out vs bf16 in)
        y_cap = dit_mod.caption_project(
            params, my_enc.astype(compute_dtype))  # loop-invariant
        cap_kv_local = jax.vmap(lambda kvp: linear(kvp, y_cap))(
            blocks_local["cross_kv"]
        )  # [Lp, Bl, Lt, 2*hid]

        ts = sched.timesteps()
        temb_all = jax.vmap(lambda t: dit_mod.t_embed(params, dcfg, t))(ts)  # [T, hid]
        c6_all = jax.vmap(lambda e: dit_mod.adaln_table(params, dcfg, e))(temb_all)

        def embed_chunk(x_full, m, s):
            """Patch m of the latent, scaled + embedded for step s."""
            rows = lax.dynamic_slice(
                x_full, (0, m * chunk, 0), (batch, chunk, d_in)
            )
            rows = sched.scale_model_input(rows, s)
            tok = rows.astype(compute_dtype)
            if not cfg.cfg_split and cfg.do_classifier_free_guidance:
                tok = jnp.concatenate([tok, tok], axis=0)
            pos_rows = lax.dynamic_slice(pos, (m * chunk, 0), (chunk, hid))
            return dit_mod.embed_tokens(params, dcfg, tok, pos_rows)

        def sched_patch(x_full, sstate, eps_guided, m, s, pred):
            """Scheduler-step patch m's rows with its stacked state slice."""
            rows = lax.dynamic_slice(
                x_full, (0, m * chunk, 0), (batch, chunk, d_in)
            )
            st = _tree_dynamic_index(sstate, m)
            new_rows, new_st = sched.step(rows, eps_guided.astype(jnp.float32), s, st)
            x_new = lax.dynamic_update_slice(
                x_full, new_rows.astype(x_full.dtype), (0, m * chunk, 0)
            )
            x_full = jnp.where(pred, x_new, x_full)
            sstate = _tree_dynamic_update(sstate, new_st, m, pred)
            return x_full, sstate

        def split_patches(full):
            """[bloc, n_tok, hid] -> [n_patch, bloc, chunk, hid]."""
            return full.reshape(bloc, n_patch, chunk, hid).transpose(
                1, 0, 2, 3)

        def init_aux():
            """Knob-dependent extra carry: the per-stage step-cache delta
            and/or the residual coder's sender/receiver predictors.  One
            pytree shared by every tick body (warmup records, steady
            consumes), so the scan carry structure never depends on which
            step body runs."""
            aux = {}
            if use_sc:
                aux["delta"] = jnp.zeros(
                    (n_patch, bloc, chunk, hid), compute_dtype)
            if mode == "int8_residual":
                aux["send_pred"] = jnp.zeros(
                    (n_patch, bloc, chunk, hid), jnp.float32)
                aux["recv_pred"] = jnp.zeros(
                    (n_patch, bloc, chunk, hid), jnp.float32)
            return aux

        def steady_ring0():
            """Zero ring for the steady phase: raw chunk, or the
            (payload, scale) pair the compressed hops permute."""
            if mode == "none":
                return jnp.zeros((bloc, chunk, hid), compute_dtype)
            pdt = fp8_dtype() if mode == "fp8" else jnp.int8
            return (jnp.zeros((bloc, chunk, hid), pdt),
                    jnp.zeros((bloc, chunk), jnp.float32))

        def decode_hop(ring, aux, m_recv, ok_recv):
            """Reconstruct the received activation chunk from the ring
            carry (dequantize + residual predictor add), updating the
            receiver-side predictor closed-loop."""
            if mode == "none":
                return ring, aux
            payload, scale = ring
            dec = dequantize(payload, scale, jnp.float32)
            if mode == "int8_residual":
                pred = lax.dynamic_index_in_dim(
                    aux["recv_pred"], m_recv, axis=0, keepdims=False)
                dec = pred + dec
                aux = dict(aux)
                aux["recv_pred"] = _buf_update(
                    aux["recv_pred"], dec, m_recv, ok_recv)
            return dec.astype(compute_dtype), aux

        def encode_hop(payload, aux, m_my, ok_my):
            """Quantize the outgoing chunk (delta-coded for the residual
            mode, with the sender predictor advanced to the same
            reconstruction the receiver will compute)."""
            if mode == "none":
                return payload, aux
            src = payload.astype(jnp.float32)
            if mode == "int8_residual":
                pred = lax.dynamic_index_in_dim(
                    aux["send_pred"], m_my, axis=0, keepdims=False)
                q, s = quantize(src - pred, mode)
                recon = pred + dequantize(q, s, jnp.float32)
                aux = dict(aux)
                aux["send_pred"] = _buf_update(
                    aux["send_pred"], recon, m_my, ok_my)
            else:
                q, s = quantize(src, mode)
            return (q, s), aux

        def ring_permute(payload):
            perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]
            return jax.tree.map(
                lambda l: lax.ppermute(l, SP_AXIS, perm), payload)

        # ---------------- phase 1: synchronous mega-patch warmup ----------
        def warmup_tick(carry, tau):
            x_full, sstate, kv_cache, aux, ring = carry
            active = tau % n_stage
            s = tau // n_stage  # step being fed through the pipeline

            # stage-0 receive: epsilon of step s-1 completes as step s starts
            eps_full = ring[..., :d_out]
            guided = self._combine_eps(eps_full, gs, batch)
            do_recv = is_first & (active == 0) & (s >= 1) & (s <= num_steps)

            def step_all(args):
                x_full, sstate = args
                xs = x_full.reshape(batch, n_patch, chunk, -1).transpose(1, 0, 2, 3)
                gch = guided.reshape(batch, n_patch, chunk, -1).transpose(1, 0, 2, 3)
                new_xs, new_st = jax.vmap(
                    lambda xr, gr, st: sched.step(xr, gr, s - 1, st)
                )(xs, gch, sstate)
                x_new = new_xs.transpose(1, 0, 2, 3).reshape(x_full.shape)
                return x_new.astype(x_full.dtype), jax.tree.map(
                    lambda a, b: b.astype(a.dtype), sstate, new_st
                )

            x_new, st_new = step_all((x_full, sstate))
            x_full = jnp.where(do_recv, x_new, x_full)
            sstate = jax.tree.map(
                lambda old, new: jnp.where(do_recv, new, old), sstate, st_new
            )

            # stage-0 embed of step s (only when a fresh step enters)
            s_c = jnp.clip(s, 0, num_steps - 1)
            x_in = sched.scale_model_input(x_full, s_c).astype(compute_dtype)
            if not cfg.cfg_split and cfg.do_classifier_free_guidance:
                x_in = jnp.concatenate([x_in, x_in], axis=0)
            h0 = dit_mod.embed_tokens(params, dcfg, x_in, pos)

            h_in = jnp.where(is_first, h0, ring.astype(compute_dtype))
            valid = (p_idx == active) & (s < n_sync)
            c6 = c6_all[s_c]
            h_out, kv_cache = self._run_stage(
                blocks_local, cap_kv_local, kv_cache, h_in, c6, 0, valid,
                cap_bias,
            )
            if use_sc:
                # every warmup pass is a full run: refresh this stage's
                # per-patch deep delta so the first post-warmup step may
                # already be shallow (shallow-first cadence)
                aux = dict(aux)
                aux["delta"] = jnp.where(
                    valid, split_patches((h_out - h_in).astype(compute_dtype)),
                    aux["delta"])

            eps_out = dit_mod.final_layer(params, dcfg, h_out, temb_all[s_c])
            pad = jnp.zeros((bloc, n_tok, hid - d_out), eps_out.dtype)
            payload = jnp.where(
                is_last, jnp.concatenate([eps_out, pad], axis=-1), h_out
            )
            if mode == "int8_residual":
                # warmup hops are exact (never compressed); both coder ends
                # seed their predictors from the SAME raw values, so the
                # first steady-state delta is coded against a shared,
                # consistent reference
                aux = dict(aux)
                aux["send_pred"] = jnp.where(
                    valid, split_patches(payload.astype(jnp.float32)),
                    aux["send_pred"])
                consumed = (valid & ~is_first) | do_recv
                aux["recv_pred"] = jnp.where(
                    consumed, split_patches(ring.astype(jnp.float32)),
                    aux["recv_pred"])
            ring = lax.ppermute(
                payload, SP_AXIS,
                [(i, (i + 1) % n_stage) for i in range(n_stage)],
            )
            return (x_full, sstate, kv_cache, aux, ring), None

        # ---------------- phase 2: displaced patch streaming --------------
        n_items = (num_steps - n_sync) * n_patch

        def steady_tick(carry, tau):
            x_full, sstate, kv_cache, aux, ring = carry

            # what my ring predecessor processed last tick (= what I am
            # consuming now): item tau - p for stages > 0, item
            # tau - n_stage (the returning epsilon) for stage 0
            q_recv = (tau - 1) - ((p_idx - 1) % n_stage)
            ok_recv = (q_recv >= 0) & (q_recv < n_items)
            m_recv = jnp.clip(q_recv, 0, n_items - 1) % n_patch
            h_recv, aux = decode_hop(ring, aux, m_recv, ok_recv)

            # stage-0 receive: epsilon chunk of item tau - n_stage
            q_arr = tau - n_stage
            ok_arr = (q_arr >= 0) & (q_arr < n_items)
            q_arr_c = jnp.clip(q_arr, 0, n_items - 1)
            s_arr = n_sync + q_arr_c // n_patch
            m_arr = q_arr_c % n_patch
            eps_chunk = h_recv[..., :d_out]
            guided = self._combine_eps(eps_chunk, gs, batch)
            x_full, sstate = sched_patch(
                x_full, sstate, guided, m_arr, s_arr, is_first & ok_arr
            )

            # stage-0 embed: item tau enters the pipeline
            q_in = jnp.clip(tau, 0, n_items - 1)
            s_in = n_sync + q_in // n_patch
            m_in = q_in % n_patch
            h0 = embed_chunk(x_full, m_in, s_in)

            h_in = jnp.where(is_first, h0, h_recv.astype(compute_dtype))

            # my item this tick
            q_my = tau - p_idx
            ok_my = (q_my >= 0) & (q_my < n_items)
            q_my_c = jnp.clip(q_my, 0, n_items - 1)
            s_my = n_sync + q_my_c // n_patch
            m_my = q_my_c % n_patch
            c6 = c6_all[s_my]

            def run_blocks(h, kv):
                return self._run_stage(
                    blocks_local, cap_kv_local, kv, h, c6,
                    m_my * chunk, ok_my, cap_bias,
                )

            if use_sc:
                # shallow-first cadence over the post-warmup step index:
                # deep stages take a pass-through branch (carried delta,
                # untouched KV) on shallow items — a real lax.cond, so the
                # block FLOPs exist only on the full path
                shallow_my = (s_my - n_sync) % interval < interval - 1

                def full_branch(ops):
                    h, kv, delta = ops
                    h_out, kv = run_blocks(h, kv)
                    delta = _buf_update(
                        delta, h_out - h, m_my, ok_my & is_deep)
                    return h_out, kv, delta

                def shallow_branch(ops):
                    h, kv, delta = ops
                    d = lax.dynamic_index_in_dim(
                        delta, m_my, axis=0, keepdims=False)
                    return h + d.astype(h.dtype), kv, delta

                aux = dict(aux)
                h_out, kv_cache, aux["delta"] = lax.cond(
                    is_deep & shallow_my, shallow_branch, full_branch,
                    (h_in, kv_cache, aux["delta"]),
                )
            else:
                h_out, kv_cache = run_blocks(h_in, kv_cache)

            eps_out = dit_mod.final_layer(params, dcfg, h_out, temb_all[s_my])
            pad = jnp.zeros((bloc, chunk, hid - d_out), eps_out.dtype)
            payload = jnp.where(
                is_last, jnp.concatenate([eps_out, pad], axis=-1), h_out
            )
            payload, aux = encode_hop(payload, aux, m_my, ok_my)
            ring = ring_permute(payload)
            return (x_full, sstate, kv_cache, aux, ring), None

        return types.SimpleNamespace(
            warmup_tick=warmup_tick, steady_tick=steady_tick,
            init_aux=init_aux, steady_ring0=steady_ring0,
            n_items=n_items, n_stage=n_stage, is_first=is_first, bloc=bloc,
            chunk=chunk, hid=hid, compute_dtype=compute_dtype,
            l_per=dcfg.depth // n_stage, n_tok=n_tok,
        )

    def _init_carry(self, ctx, latents):
        """(x tokens, per-patch scheduler state, stale KV cache)."""
        dcfg, sched = self.dcfg, self.scheduler
        batch = latents.shape[0]
        x = dit_mod.patchify(dcfg, latents.astype(jnp.float32))
        # scheduler state stacked per patch (DPM's scalars must advance with
        # each patch's own step sequence while steps interleave in flight)
        sstate = jax.vmap(
            lambda _: sched.init_state((batch, ctx.chunk, dcfg.token_dim))
        )(jnp.arange(self.patches))
        kv_cache = jnp.zeros(
            (ctx.l_per, 2, ctx.bloc, ctx.n_tok, ctx.hid), ctx.compute_dtype
        )
        return x, sstate, kv_cache

    def _device_loop(self, params, latents, enc, cap_mask, gs, num_steps):
        cfg, dcfg = self.cfg, self.dcfg
        batch = latents.shape[0]
        # full_sync runs every step as the exact mega-patch (mirroring
        # dit_sp.py): the displaced schedule never engages
        n_sync = (
            num_steps
            if cfg.mode == "full_sync"
            else min(cfg.warmup_steps + 1, num_steps)
        )
        ctx = self._tick_ctx(params, enc, cap_mask, gs, batch, num_steps,
                             n_sync)
        x, sstate, kv_cache = self._init_carry(ctx, latents)

        ring0 = jnp.zeros((ctx.bloc, ctx.n_tok, ctx.hid), ctx.compute_dtype)
        carry = (x, sstate, kv_cache, ctx.init_aux(), ring0)
        n_warm_ticks = n_sync * ctx.n_stage + 1
        carry, _ = lax.scan(ctx.warmup_tick, carry, jnp.arange(n_warm_ticks))
        x, sstate, kv_cache, aux, _ = carry

        if n_sync >= num_steps:
            x_full = lax.psum(jnp.where(ctx.is_first, x, 0.0), SP_AXIS)
            return dit_mod.unpatchify(dcfg, x_full, dcfg.in_channels)

        carry = (x, sstate, kv_cache, aux, ctx.steady_ring0())
        carry, _ = lax.scan(
            ctx.steady_tick, carry, jnp.arange(ctx.n_items + ctx.n_stage)
        )
        x = carry[0]

        x_full = lax.psum(jnp.where(ctx.is_first, x, 0.0), SP_AXIS)
        return dit_mod.unpatchify(dcfg, x_full, dcfg.in_channels)

    # ------------------------------------------------------------------
    # observability
    # ------------------------------------------------------------------

    def comm_report(self, batch_size: int = 1) -> Dict[str, Any]:
        """Per-device memory/traffic accounting (counterpart of
        DenoiseRunner.comm_volume_report for the pipeline layout).

        Static arithmetic — no device work: PipeFusion's whole point is that
        weights shrink depth/P-fold and the per-hop wire traffic is one
        [B, N/M, hidden] chunk instead of the displaced-patch O(L) gathers.

        Byte accounting (``*_bytes`` keys, the contract
        ``pipelines.comm_plan`` consumes): one steady step is exactly
        ``patches`` ring ticks, each permuting one compressed-or-raw
        activation chunk between sp neighbors; one warmup (sync) step is
        ``stages`` ticks of the full-precision mega-patch payload.
        Shallow (step-cache) steps skip deep-stage COMPUTE and KV commits
        but the chunk still rides every hop to reach stage 0 for its
        scheduler update, so shallow wire bytes equal full-step bytes
        (``step_cache.shallow_per_step_collective_elems`` says so rather
        than implying a saving that does not exist).  The cfg-axis guidance
        gather is reported separately (``per_step_cfg_gather_bytes``) and
        excluded from ``per_step_collective_bytes``, matching the displaced
        DiT report which also counts only sp-axis traffic.
        """
        cfg, dcfg = self.cfg, self.dcfg
        n_tok = dcfg.num_tokens
        hid = dcfg.hidden_size
        l_per = dcfg.depth // self.stages
        chunk = n_tok // self.patches
        bloc = batch_size * (
            2 if (cfg.do_classifier_free_guidance and not cfg.cfg_split)
            else 1
        )
        one_block_params = sum(
            int(np.prod(l.shape[1:]))  # leading axis is the depth stack
            for l in jax.tree.leaves(self.params["blocks"])
        )
        shared_params = sum(
            int(np.prod(np.shape(l)))
            for k, v in self.params.items() if k != "blocks"
            for l in jax.tree.leaves(v)
        )
        itemsize = jnp.dtype(cfg.dtype).itemsize
        ring_active = self.stages > 1  # a 1-stage "ring" is a self-permute
        hop_bytes = (
            wire_nbytes((bloc, chunk, hid), itemsize, cfg.comm_compress)
            if ring_active else 0
        )
        warm_hop_bytes = bloc * n_tok * hid * itemsize if ring_active else 0
        per_step_elems = (self.patches * bloc * chunk * hid
                          if ring_active else 0)
        report = {
            "stages": self.stages,
            "patches": self.patches,
            "params_per_device": shared_params + one_block_params * l_per,
            "params_replicated_equiv": shared_params + one_block_params * dcfg.depth,
            "kv_cache_elems_per_device": l_per * 2 * bloc * n_tok * hid,
            "ring_payload_elems_per_tick": bloc * chunk * hid,
            "ticks_per_step_steady": self.patches,
            "bubble_ticks": self.stages,
            # wire bytes, closed form (compression-aware; warmup never
            # compresses)
            "comm_compress": cfg.comm_compress,
            "per_hop_bytes": int(hop_bytes),
            "warmup_hop_bytes": int(warm_hop_bytes),
            "per_step_collective_elems": int(per_step_elems),
            "per_step_collective_bytes": int(self.patches * hop_bytes),
            "sync_step_collective_bytes": int(self.stages * warm_hop_bytes),
            "per_step_cfg_gather_bytes": int(
                self.patches * batch_size * chunk * dcfg.token_out_dim
                * itemsize
                if cfg.cfg_split else 0
            ),
        }
        if cfg.step_cache_enabled:
            report["step_cache"] = {
                "interval": cfg.step_cache_interval,
                "depth": cfg.step_cache_depth,  # PIPELINE STAGES skipped
                # hops persist on shallow steps (docstring): bytes equal
                "shallow_per_step_collective_elems": int(per_step_elems),
            }
        return report

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def _specs(self):
        """(param_specs, lat_spec, enc_spec) shared by both builders."""
        block_specs = jax.tree.map(lambda _: P(SP_AXIS), self.params["blocks"])
        param_specs = {
            k: (block_specs if k == "blocks" else jax.tree.map(lambda _: P(), v))
            for k, v in self.params.items()
        }
        return param_specs, P(DP_AXIS), P(None, DP_AXIS)

    def _build(self, num_steps: int):
        cfg = self.cfg
        self.scheduler.set_timesteps(num_steps)
        device_loop = partial(self._device_loop, num_steps=num_steps)

        param_specs, lat_spec, enc_spec = self._specs()

        def loop(params, latents, enc, cap_mask, gs):
            return shard_map(
                device_loop,
                mesh=cfg.mesh,
                in_specs=(param_specs, lat_spec, enc_spec, enc_spec, P()),
                out_specs=lat_spec,
                check_vma=False,
            )(params, latents, enc, cap_mask, gs)

        return jax.jit(loop)

    def _build_hybrid(self, num_steps: int):
        """Warmup and steady phases as two ONE-body programs
        (cfg.hybrid_loop; same lever as dit_sp._build_hybrid): each program
        traces the stage stack once instead of twice, roughly halving the
        big program's (remote) compile.  The inter-phase carry — tokens,
        per-patch scheduler state, stale KV cache — is per-device state; it
        crosses the jit boundary with a fresh leading axis laid out over
        (dp, cfg, sp).  The ring buffer does NOT cross: the steady phase
        starts from a zero ring exactly as the fused loop does."""
        cfg, dcfg = self.cfg, self.dcfg
        self.scheduler.set_timesteps(num_steps)
        n_sync = min(cfg.warmup_steps + 1, num_steps)

        param_specs, lat_spec, enc_spec = self._specs()
        state_spec = P((DP_AXIS, CFG_AXIS, SP_AXIS))  # prefix for any pytree

        def device_warm(params, latents, enc, cap_mask, gs):
            batch = latents.shape[0]
            ctx = self._tick_ctx(params, enc, cap_mask, gs, batch, num_steps,
                                 n_sync)
            x, sstate, kv_cache = self._init_carry(ctx, latents)
            ring0 = jnp.zeros((ctx.bloc, ctx.n_tok, ctx.hid),
                              ctx.compute_dtype)
            carry, _ = lax.scan(
                ctx.warmup_tick, (x, sstate, kv_cache, ctx.init_aux(), ring0),
                jnp.arange(n_sync * ctx.n_stage + 1),
            )
            x, sstate, kv_cache, aux, _ = carry
            add_dev = lambda t: jax.tree.map(lambda l: l[None], t)  # noqa: E731
            return add_dev(x), add_dev(sstate), add_dev(kv_cache), add_dev(aux)

        def device_steady(params, x, sstate, kv_cache, aux, enc, cap_mask,
                          gs):
            x, sstate, kv_cache, aux = jax.tree.map(
                lambda l: l[0], (x, sstate, kv_cache, aux)
            )
            batch = x.shape[0]
            ctx = self._tick_ctx(params, enc, cap_mask, gs, batch, num_steps,
                                 n_sync)
            carry, _ = lax.scan(
                ctx.steady_tick, (x, sstate, kv_cache, aux,
                                  ctx.steady_ring0()),
                jnp.arange(ctx.n_items + ctx.n_stage),
            )
            x = carry[0]
            x_full = lax.psum(jnp.where(ctx.is_first, x, 0.0), SP_AXIS)
            return dit_mod.unpatchify(dcfg, x_full, dcfg.in_channels)

        warm = jax.jit(lambda p, l, e, m, g: shard_map(
            device_warm, mesh=cfg.mesh,
            in_specs=(param_specs, lat_spec, enc_spec, enc_spec, P()),
            out_specs=(state_spec, state_spec, state_spec, state_spec),
            check_vma=False,
        )(p, l, e, m, g))
        steady = jax.jit(lambda p, x, ss, kv, ax, e, m, g: shard_map(
            device_steady, mesh=cfg.mesh,
            in_specs=(param_specs, state_spec, state_spec, state_spec,
                      state_spec, enc_spec, enc_spec, P()),
            out_specs=lat_spec,
            check_vma=False,
        )(p, x, ss, kv, ax, e, m, g), donate_argnums=(1, 2, 3, 4))
        return warm, steady

    def generate(self, latents, enc, guidance_scale=5.0, num_inference_steps=20,
                 cap_mask=None, callback=None):
        """latents [B, H/8, W/8, C] fp32, enc [2, B, Lt, caption_dim]
        (uncond, cond branch-major, like DenoiseRunner).  ``cap_mask``
        [n_br, B, Lt] (1 = real token) masks padded caption tokens out of
        cross-attention; None attends to all.  Returns the final latent,
        full on every device."""
        if callback is not None:
            raise ValueError(
                "per-step callbacks are not available under PipeFusion: a "
                "denoising step is smeared across the pipeline's token "
                "ticks inside the scan, so there is no per-step boundary "
                "to fire from — use parallelism='patch' "
                "(DiTDenoiseRunner fires callbacks in every mode)"
            )
        # Re-pin the scheduler tables every call: a cached program can
        # re-trace later and must not read tables left by a different step
        # count (see DenoiseRunner.generate).
        self.scheduler.set_timesteps(num_inference_steps)
        gs = np.float32(guidance_scale)
        if cap_mask is None:
            cap_mask = jnp.ones(enc.shape[:3], jnp.float32)
        cap_mask = jnp.asarray(cap_mask, jnp.float32)
        if self._hybrid_dispatch(num_inference_steps):
            warm, steady = self._ensure_hybrid(num_inference_steps)
            x, sstate, kv, aux = warm(self.params, latents, enc, cap_mask,
                                      gs)
            return steady(self.params, x, sstate, kv, aux, enc, cap_mask,
                          gs)
        if num_inference_steps not in self._compiled:
            self._compiled[num_inference_steps] = self._build(num_inference_steps)
        return self._compiled[num_inference_steps](
            self.params, latents, enc, cap_mask, gs
        )

    def _hybrid_dispatch(self, num_steps: int) -> bool:
        cfg = self.cfg
        return (cfg.hybrid_loop and cfg.mode != "full_sync"
                and self.stages > 1
                and min(cfg.warmup_steps + 1, num_steps) < num_steps)

    def _ensure_hybrid(self, num_steps: int):
        key = ("hybrid", num_steps)
        if key not in self._compiled:
            self._compiled[key] = self._build_hybrid(num_steps)
        return self._compiled[key]

    def prepare(self, num_steps: int) -> None:
        """Pre-build exactly the program(s) generate() will dispatch to."""
        self.scheduler.set_timesteps(num_steps)
        if self._hybrid_dispatch(num_steps):
            self._ensure_hybrid(num_steps)
            return
        if num_steps not in self._compiled:
            self._compiled[num_steps] = self._build(num_steps)
