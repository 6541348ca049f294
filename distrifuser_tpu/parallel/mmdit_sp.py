"""Displaced patch parallelism for the MMDiT (SD3-class joint transformer).

DistriFusion's method applied to the joint-attention architecture.  The
token-major layout makes this the same shape as parallel/dit_sp.py: the
image-token sequence shards over the ``sp`` axis, and JOINT attention is
the only op that crosses patch boundaries — but here the attended keys are
``concat(context, image)``, which splits the problem cleanly in two:

* the **context stream** is short (77-333 tokens) and must stay exact (its
  activations feed every later block's modulation of the image stream), so
  every device computes the FULL context stream, replicated.  Its K/V need
  no assembly, no staleness, no collective.
* the **image stream**'s K/V are the only cross-device exchange:
  - sync phase (steps <= warmup, reference counter semantics §2.3): each
    block's fresh local image K/V are all-gathered — exact joint attention;
  - stale phase: each block attends over the previous step's gathered
    image K/V with its own slot overwritten fresh (the reference's
    pp/attn.py:135-140 displaced semantics), then all-gathers fresh K/V
    into the scan carry — consumed only next step, so XLA overlaps the
    collective with the remaining blocks' compute.

The replicated context stream does duplicate its (small) compute per
device; at SD3 scale that is ~¼ of one stream's tokens at n=8 vs a 4096-
token image sequence — noise next to the image-side saving.

Two layouts, selected by ``attn_impl`` (the same pair the UNet offers):
"gather" carries the full gathered stale image KV (reference buffer
layout, O(L) state); "ring" carries only the own chunk (O(L/n)) and
streams peers through the shared online-softmax ring, with the replicated
context KV merged as a NON-rotating static block (ring_pass kv_static) —
no refresh collective at all.  The head-sharding ulysses/usp layouts are
undefined for joint attention's two-origin queries and are rejected
loudly in __init__ rather than silently falling back.

Every device returns the full latent and steps the scheduler replicated —
the DenoiseRunner/DiTDenoiseRunner contract, so pipelines treat all three
interchangeably.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ..models import dit as dit_mod
from ..models import mmdit as mm
from ..models.mmdit import MMDiTConfig
from ..ops.linear import linear
from ..schedulers import BaseScheduler
from ..utils.config import CFG_AXIS, DP_AXIS, SP_AXIS, DistriConfig
from .collectives import all_gather_seq
from .compress import refresh_gather_seq, refresh_period, wire_nbytes
from .guidance import branch_select, combine_guidance
from .stepcache import is_shallow_at, run_cadence


class MMDiTDenoiseRunner:
    """Compiled displaced-patch generation loop for an MMDiT.

    API mirrors DiTDenoiseRunner.generate, with SD3 conditioning inputs:
    ``enc`` [n_br, B, Lc, joint_attention_dim] sequence embeddings and
    ``pooled`` [n_br, B, pooled_projection_dim] pooled text embeddings.
    """

    def __init__(
        self,
        distri_config: DistriConfig,
        mmdit_config: MMDiTConfig,
        params,
        scheduler: BaseScheduler,
    ):
        self.cfg = distri_config
        self.mcfg = mmdit_config
        self.params = distri_config.place(params)
        self.scheduler = scheduler
        if distri_config.attn_impl not in ("gather", "ring"):
            raise ValueError(
                f"attn_impl={distri_config.attn_impl!r}: the MMDiT runner "
                "implements 'gather' (reference-style full stale KV) and "
                "'ring' (O(L/n) state; the replicated context KV rides the "
                "ring as a non-rotating static block) — the head-sharding "
                "ulysses/usp layouts are not defined for joint attention's "
                "two-origin queries"
            )
        if distri_config.comm_batch:
            raise ValueError(
                "comm_batch applies to the UNet's per-layer halo/moment "
                "exchanges; the MMDiT path has one collective kind already"
            )
        if (distri_config.comm_compress != "none"
                and distri_config.attn_impl != "gather"):
            raise ValueError(
                "comm_compress compresses the displaced image-KV refresh "
                "gathers of attn_impl='gather'; 'ring' carries only the "
                "local chunk and has no refresh collective to compress"
            )
        if (distri_config.refresh_fraction < 1.0
                and distri_config.attn_impl != "gather"):
            raise ValueError(
                "refresh_fraction < 1 (PCPP) thins the displaced image-KV "
                "refresh gathers of attn_impl='gather'; 'ring' carries only "
                "the local chunk and has no refresh collective to thin"
            )
        n = distri_config.n_device_per_batch
        _rk = refresh_period(distri_config.refresh_fraction)
        if (_rk > 1 and mmdit_config.num_tokens % n == 0
                and (mmdit_config.num_tokens // n) % _rk != 0):
            raise ValueError(
                f"refresh_fraction=1/{_rk} needs the per-device token chunk "
                f"({mmdit_config.num_tokens // n}) divisible by {_rk} — "
                "each stale step gathers exactly one strided row group"
            )
        if mmdit_config.num_tokens % n != 0:
            raise ValueError(
                f"token count {mmdit_config.num_tokens} must be divisible "
                f"by the sp degree {n}"
            )
        if distri_config.step_cache_enabled:
            k_cache = distri_config.step_cache_depth
            max_k = mmdit_config.depth - max(
                mmdit_config.dual_attention_blocks, 1
            )
            if not 1 <= k_cache <= max_k:
                raise ValueError(
                    f"step_cache_depth={k_cache} must be in [1, {max_k}] for "
                    f"this {mmdit_config.depth}-block MMDiT: the cut must "
                    "stay below the dual-attention prefix "
                    f"({mmdit_config.dual_attention_blocks} blocks) and "
                    "leave at least one shallow block"
                )
        if (distri_config.height // 8 != mmdit_config.sample_size) or (
            distri_config.width // 8 != mmdit_config.sample_size
        ):
            raise ValueError(
                f"DistriConfig {distri_config.height}x{distri_config.width} "
                f"implies latent {distri_config.latent_height}, but "
                f"MMDiTConfig.sample_size is {mmdit_config.sample_size}"
            )
        self._compiled: Dict[int, Any] = {}
        # compiled-loop per-step callback target (_build_fused_callback)
        self._active_callback = None

    # ------------------------------------------------------------------

    def _eval_model(self, params, x_full, s, kv_state, phase_sync,
                    ctx0, vec_all, pos, shallow=False):
        """One MMDiT evaluation on this device's token rows.

        Returns (full guided-input velocity [Bl, N, D_out], new kv_state).
        ``kv_state``: gathered [depth, 2, Bl, N, hidden] stale image K/V —
        or, with dual-attention blocks (SD3.5-medium), a dict
        ``{"j": [depth, ...] joint-image KV, "d": [k_dual, ...] attn2 KV}``
        (attn2 is image-only self-attention over the same sharded rows, so
        its displaced state has the same per-block layout).  With the step
        cache enabled the whole thing wraps to ``{"kv": <that state>,
        "deep": [Bl, N/n, hidden]}``; ``shallow`` runs only the first
        ``depth - step_cache_depth`` blocks on the image stream and adds the
        carried deep residual (the skipped blocks' displaced KV rides
        through untouched — the cut always sits past the dual prefix).
        ``ctx0``: [Bl, Lc, hidden] projected context entering block 0 —
        recomputed per step is unnecessary (it is timestep-independent),
        but the stream EVOLVES through the blocks, so it restarts from
        ctx0 each step (unlike dit_sp's per-block constant caption KV).
        """
        cfg, mcfg = self.cfg, self.mcfg
        sched = self.scheduler
        n = cfg.n_device_per_batch
        chunk = mcfg.num_tokens // n
        sp_idx = lax.axis_index(SP_AXIS)
        offset = sp_idx * chunk
        compute_dtype = params["proj_in"]["kernel"].dtype

        x_in = sched.scale_model_input(x_full, s)
        rows = lax.dynamic_slice(
            x_in, (0, offset, 0), (x_in.shape[0], chunk, x_in.shape[2])
        ).astype(compute_dtype)
        if not cfg.cfg_split and cfg.do_classifier_free_guidance:
            rows = jnp.concatenate([rows, rows], axis=0)
        pos_rows = lax.dynamic_slice(pos, (offset, 0), (chunk, pos.shape[1]))
        h = linear(params["proj_in"], rows) + pos_rows[None]
        if jnp.ndim(s) == 0:
            vec = vec_all[s]  # [Bl, hidden] — one timestep for every row
        else:
            # per-row step indices (packed cohort dispatch): vec_all is
            # [S, Bl, hidden]; pick row b's own step on the diagonal, with
            # the step vector fold-doubled when the CFG branches ride the
            # batch dim (branch-major, same layout as ``rows`` above)
            sb = (jnp.concatenate([s, s])
                  if vec_all.shape[1] == 2 * s.shape[0] else s)
            vec = vec_all[sb, jnp.arange(vec_all.shape[1])]

        no_refresh = cfg.mode == "no_sync"  # keep warmup KV forever (§2.3)

        def _gather_assemble(kv_blk, box):
            """Displaced-KV assembly closure for one attention's image KV:
            sync -> all-gather fresh (exact); stale -> carried gathered KV
            with this device's slot overwritten fresh (reference
            pp/attn.py:135-140 semantics)."""

            @jax.named_scope("stale_kv")
            def assemble(k_fresh, v_fresh):
                if phase_sync:
                    kv = (all_gather_seq(k_fresh), all_gather_seq(v_fresh))
                else:
                    kv = (
                        lax.dynamic_update_slice(
                            kv_blk[0], k_fresh, (0, offset, 0)
                        ),
                        lax.dynamic_update_slice(
                            kv_blk[1], v_fresh, (0, offset, 0)
                        ),
                    )
                box["kv"] = kv
                return kv

            return assemble

        def _gather_refresh(box, kv_blk, k, v):
            # refresh for the NEXT step: deferred consumption lets XLA
            # overlap the gather with the remaining blocks' compute.  Stale
            # refreshes route through the compression layer
            # (parallel/compress.py): a plain tiled gather at
            # comm_compress="none", int8/fp8 payload + fp32 scales otherwise
            if phase_sync:
                return jnp.stack(list(box["kv"]))
            if no_refresh:
                return kv_blk
            return refresh_gather_seq(
                jnp.stack([k, v]), kv_blk, cfg.comm_compress, offset,
                fraction=cfg.refresh_fraction, step=s,
            )

        def block_body_gather(carry, xs):
            hx, hc = carry
            bp, kv_blk = xs  # kv_blk [2, Bl, N, hid] stale gathered image KV
            box = {}
            hx, hc, (k, v) = mm.mmdit_block(
                bp, mcfg, hx, hc, vec, kv_assemble=_gather_assemble(kv_blk, box)
            )
            return (hx, hc), _gather_refresh(box, kv_blk, k, v)

        def dual_body_gather(carry, xs):
            hx, hc = carry
            bp, dp, kv_blk, kv2_blk = xs
            box, box2 = {}, {}
            hx, hc, (k, v), (k2, v2) = mm.mmdit_block(
                bp, mcfg, hx, hc, vec,
                kv_assemble=_gather_assemble(kv_blk, box),
                dual_p=dp, kv2_assemble=_gather_assemble(kv2_blk, box2),
            )
            return (hx, hc), (
                _gather_refresh(box, kv_blk, k, v),
                _gather_refresh(box2, kv2_blk, k2, v2),
            )

        from ..ops.ring_attention import ring_pass

        def _ring_joint_core(kv_blk, box):
            def core(cq, xq, ckv, xkv):
                ck, cv = ckv
                xk, xv = xkv
                kv_own = jnp.concatenate([xk, xv], axis=-1)
                box["kv"] = kv_own
                static = jnp.concatenate([ck, cv], axis=-1)
                # sync phase rotates fresh peer chunks (exact); stale phase
                # rotates each peer's previous-step chunk from the carry.
                # The replicated context KV never moves: it merges as a
                # static block into every device's online softmax.
                rotating = kv_own if phase_sync else kv_blk
                q = jnp.concatenate([cq, xq], axis=1)
                out = ring_pass(q, kv_own, rotating, n, SP_AXIS,
                                heads=mcfg.num_heads, kv_static=static)
                b_, lq_ = q.shape[0], q.shape[1]
                out = out.astype(xq.dtype).transpose(0, 2, 1, 3)
                return out.reshape(b_, lq_, mcfg.hidden_size)

            return core

        def _ring_dual_core(kv2_blk, box2):
            def core2(q2, xkv2):
                k2, v2 = xkv2
                kv_own = jnp.concatenate([k2, v2], axis=-1)
                box2["kv"] = kv_own
                rotating = kv_own if phase_sync else kv2_blk
                out = ring_pass(q2, kv_own, rotating, n, SP_AXIS,
                                heads=mcfg.num_heads)
                b_, lq_ = q2.shape[0], q2.shape[1]
                out = out.astype(q2.dtype).transpose(0, 2, 1, 3)
                return out.reshape(b_, lq_, mcfg.hidden_size)

            return core2

        def _ring_refresh(box, kv_blk):
            # next step's stale state is this step's own fresh chunk — no
            # refresh collective at all (ring_attention.py semantics)
            if phase_sync or not no_refresh:
                return box["kv"]
            return kv_blk

        def block_body_ring(carry, xs):
            hx, hc = carry
            bp, kv_blk = xs  # kv_blk [Bl, chunk, 2*hid] own stale chunk
            box = {}
            hx, hc, _ = mm.mmdit_block(
                bp, mcfg, hx, hc, vec, attn_core=_ring_joint_core(kv_blk, box)
            )
            return (hx, hc), _ring_refresh(box, kv_blk)

        def dual_body_ring(carry, xs):
            hx, hc = carry
            bp, dp, kv_blk, kv2_blk = xs
            box, box2 = {}, {}
            hx, hc, _, _ = mm.mmdit_block(
                bp, mcfg, hx, hc, vec,
                attn_core=_ring_joint_core(kv_blk, box),
                dual_p=dp, attn2_core=_ring_dual_core(kv2_blk, box2),
            )
            return (hx, hc), (
                _ring_refresh(box, kv_blk), _ring_refresh(box2, kv2_blk)
            )

        ring = cfg.attn_impl == "ring"
        block_body = block_body_ring if ring else block_body_gather
        k_dual = mcfg.dual_attention_blocks
        sc = cfg.step_cache_enabled
        inner = kv_state["kv"] if sc else kv_state
        d_keep = mcfg.depth - cfg.step_cache_depth if sc else mcfg.depth

        def capture_body(carry, xs):
            # block_body wrapped to record the image stream at the cut, so
            # a full step can refresh the deep residual (h_final - h_mid)
            streams, h_mid = carry
            streams, fresh = block_body(streams, xs[1:])
            h_mid = jnp.where(xs[0] == d_keep - 1, streams[0], h_mid)
            return (streams, h_mid), fresh

        if k_dual:
            dual_body = dual_body_ring if ring else dual_body_gather
            kv_j, kv_d = inner["j"], inner["d"]
            bp_pre = jax.tree.map(lambda l: l[:k_dual], params["blocks"])
            (h, hc), (kvj_pre, kvd_new) = lax.scan(
                dual_body, (h, ctx0),
                (bp_pre, params["blocks_dual"], kv_j[:k_dual], kv_d),
            )
            if sc and shallow:
                bp_mid = jax.tree.map(
                    lambda l: l[k_dual:d_keep], params["blocks"]
                )
                (h, _), kvj_mid = lax.scan(
                    block_body, (h, hc), (bp_mid, kv_j[k_dual:d_keep])
                )
                h = h + kv_state["deep"]
                kv_new = {
                    "kv": {"j": jnp.concatenate(
                        [kvj_pre, kvj_mid, kv_j[d_keep:]], axis=0),
                        "d": kvd_new},
                    "deep": kv_state["deep"],
                }
            elif sc:
                bp_suf = jax.tree.map(lambda l: l[k_dual:], params["blocks"])
                ((h, _), h_mid), kvj_suf = lax.scan(
                    capture_body, ((h, hc), h),
                    (jnp.arange(k_dual, mcfg.depth), bp_suf, kv_j[k_dual:]),
                )
                kv_new = {
                    "kv": {"j": jnp.concatenate([kvj_pre, kvj_suf], axis=0),
                           "d": kvd_new},
                    "deep": h - h_mid,
                }
            else:
                bp_suf = jax.tree.map(lambda l: l[k_dual:], params["blocks"])
                (h, _), kvj_suf = lax.scan(
                    block_body, (h, hc), (bp_suf, kv_j[k_dual:])
                )
                kv_new = {"j": jnp.concatenate([kvj_pre, kvj_suf], axis=0),
                          "d": kvd_new}
        elif sc and shallow:
            head = jax.tree.map(
                lambda l: l[:d_keep], (params["blocks"], inner)
            )
            (h, _), kv_head = lax.scan(block_body, (h, ctx0), head)
            h = h + kv_state["deep"]
            kv_new = {
                "kv": jnp.concatenate([kv_head, inner[d_keep:]], axis=0),
                "deep": kv_state["deep"],
            }
        elif sc:
            ((h, _), h_mid), kv_all = lax.scan(
                capture_body, ((h, ctx0), h),
                (jnp.arange(mcfg.depth), params["blocks"], inner),
            )
            kv_new = {"kv": kv_all, "deep": h - h_mid}
        else:
            (h, _), kv_new = lax.scan(
                block_body, (h, ctx0), (params["blocks"], kv_state)
            )
        out_rows = mm.final_layer(params, mcfg, h, vec)
        out_full = all_gather_seq(out_rows)
        return out_full, kv_new

    def _make_step(self, params, enc, pooled, gs, batch):
        """Per-device step closure + local branch count and dtype."""
        cfg, mcfg = self.cfg, self.mcfg
        sched = self.scheduler
        my_enc, _, _ = branch_select(cfg, enc)
        my_pooled, _, _ = branch_select(cfg, pooled)
        compute_dtype = params["proj_in"]["kernel"].dtype
        pos = mm.pos_embed_cropped(mcfg, compute_dtype)
        ctx0 = linear(params["ctx_in"], my_enc.astype(compute_dtype))
        ts = sched.timesteps()
        # [S, Bl, hidden] — the conditioning vec varies per step (timestep
        # features) AND per batch row (pooled text), unlike the DiT's
        # scalar-timestep adaLN table
        vec_all = jax.vmap(
            lambda t: mm.cond_vec(params, mcfg, t, my_pooled)
        )(ts)

        def step(x, sstate, kv, s, phase_sync, shallow=False):
            out, kv = self._eval_model(
                params, x, s, kv, phase_sync, ctx0, vec_all, pos,
                shallow=shallow,
            )
            guided = combine_guidance(cfg, out, gs, batch)
            x, sstate = sched.step(x, guided.astype(jnp.float32), s, sstate)
            return x, sstate, kv

        return step, my_enc.shape[0], compute_dtype

    def _kv0(self, bloc, compute_dtype):
        """Per-device zero stale-KV state: a bare [depth, ...] array, or —
        with dual-attention blocks — ``{"j": [depth, ...], "d": [k, ...]}``
        (every consumer treats the state as a pytree)."""
        mcfg = self.mcfg
        if self.cfg.attn_impl == "ring":
            chunk = mcfg.num_tokens // self.cfg.n_device_per_batch

            def mk(d):
                return jnp.zeros(
                    (d, bloc, chunk, 2 * mcfg.hidden_size), compute_dtype
                )
        else:
            def mk(d):
                return jnp.zeros(
                    (d, 2, bloc, mcfg.num_tokens, mcfg.hidden_size),
                    compute_dtype,
                )

        if mcfg.dual_attention_blocks:
            kv = {"j": mk(mcfg.depth), "d": mk(mcfg.dual_attention_blocks)}
        else:
            kv = mk(mcfg.depth)
        if self.cfg.step_cache_enabled:
            chunk = mcfg.num_tokens // self.cfg.n_device_per_batch
            return {"kv": kv, "deep": jnp.zeros(
                (bloc, chunk, mcfg.hidden_size), compute_dtype)}
        return kv

    def _device_loop(self, params, latents, enc, pooled, gs, num_steps,
                     start_step=0, end_step=None):
        # end_step: exclusive stop index; start_step > 0 is the img2img
        # entry (latents already noised to that schedule point via
        # scheduler.add_noise) — warmup counts from the first step actually
        # executed, the same convention as runner._device_loop
        cfg, mcfg = self.cfg, self.mcfg
        num_steps, n_sync = self._exec_window(num_steps, start_step, end_step)
        batch = latents.shape[0]
        step, bloc, compute_dtype = self._make_step(
            params, enc, pooled, gs, batch
        )
        x = dit_mod.patchify(mcfg, latents.astype(jnp.float32))
        sstate = self.scheduler.init_state(x.shape)
        kv0 = self._kv0(bloc, compute_dtype)

        def sync_body(i, carry):
            x, ss, kv = carry
            return step(x, ss, kv, i, True)

        x, sstate, kv = lax.fori_loop(
            start_step, start_step + n_sync, sync_body, (x, sstate, kv0)
        )

        if cfg.step_cache_enabled:
            # temporal step-cache cadence (parallel/stepcache.py): super-
            # steps of (interval-1) shallow + 1 full after the warmup —
            # the same two-bodies-in-a-scan shape as the other runners
            steady_sync = cfg.mode == "full_sync" or not cfg.is_sp
            s0 = start_step + n_sync

            def run_step(carry, i, shallow):
                x, ss, kv = carry
                return step(x, ss, kv, i, steady_sync, shallow)

            x, _, _ = run_cadence(
                (x, sstate, kv), s0, num_steps - s0,
                cfg.step_cache_interval, run_step,
            )
            return dit_mod.unpatchify(mcfg, x, mcfg.out_channels)

        if start_step + n_sync < num_steps:
            def stale_body(carry, i):
                x, ss, kv = carry
                return step(x, ss, kv, i, False), None

            (x, _, _), _ = lax.scan(
                stale_body, (x, sstate, kv),
                jnp.arange(start_step + n_sync, num_steps)
            )
        return dit_mod.unpatchify(mcfg, x, mcfg.out_channels)

    # ------------------------------------------------------------------

    def _build(self, num_steps: int, start_step: int = 0,
               end_step: int = None):
        cfg = self.cfg
        self.scheduler.set_timesteps(num_steps)
        device_loop = partial(self._device_loop, num_steps=num_steps,
                              start_step=start_step, end_step=end_step)
        lat_spec = P(DP_AXIS)
        enc_spec = P(None, DP_AXIS)

        def loop(params, latents, enc, pooled, gs):
            return shard_map(
                device_loop,
                mesh=cfg.mesh,
                in_specs=(P(), lat_spec, enc_spec, enc_spec, P()),
                out_specs=lat_spec,
                check_vma=False,
            )(params, latents, enc, pooled, gs)

        return jax.jit(loop)

    # ------------------------------------------------------------------
    # per-step (uncompiled-loop) mode + compiled-loop callbacks
    # ------------------------------------------------------------------

    def _token_specs(self):
        """(x_spec, kv_spec, ss_spec, enc_spec) for the stepwise boundary:
        patchified tokens shard over dp on batch; the stale KV varies per
        device and stacks on a fresh leading (dp, cfg, sp) axis; scheduler
        state shards x-shaped leaves over dp, scalars replicate."""
        lat_spec = P(DP_AXIS)
        kv_spec = P((DP_AXIS, CFG_AXIS, SP_AXIS))
        mcfg = self.mcfg
        ss_shapes = self.scheduler.init_state(
            (1, mcfg.num_tokens, mcfg.token_dim)
        )
        ss_spec = jax.tree.map(
            lambda l: P(DP_AXIS) if jnp.ndim(l) >= 3 else P(), ss_shapes
        )
        return lat_spec, kv_spec, ss_spec, P(None, DP_AXIS)

    def _make_stepper(self, phase_sync: bool, shallow: bool = False):
        """Un-jitted shard_map'd single step over PATCHIFIED tokens
        [B, N, token_dim] (global-array signature): the host loop and the
        compiled-callback loop both drive it."""
        cfg = self.cfg
        x_spec, kv_spec, ss_spec, enc_spec = self._token_specs()

        def device_step(params, s, x, kv, sstate, enc, pooled, gs):
            step, _, _ = self._make_step(params, enc, pooled, gs, x.shape[0])
            kv_local = jax.tree.map(lambda l: l[0], kv)
            x, sstate, kv_new = step(x, sstate, kv_local, s, phase_sync,
                                     shallow)
            return x, sstate, jax.tree.map(lambda l: l[None], kv_new)

        def stepper(params, s, x, kv, sstate, enc, pooled, gs):
            return shard_map(
                device_step,
                mesh=cfg.mesh,
                in_specs=(P(), P(), x_spec, kv_spec, ss_spec, enc_spec,
                          enc_spec, P()),
                out_specs=(x_spec, ss_spec, kv_spec),
                check_vma=False,
            )(params, s, x, kv, sstate, enc, pooled, gs)

        return stepper

    def _kv0_global(self, batch):
        """Global stepwise-layout zeros: per-device _kv0 stacked over every
        mesh device on a fresh leading axis."""
        cfg = self.cfg
        n_total = cfg.mesh.devices.size
        bloc = (1 if cfg.cfg_split or not cfg.do_classifier_free_guidance
                else 2) * (batch // cfg.dp_degree)
        per_dev = self._kv0(bloc, self.params["proj_in"]["kernel"].dtype)
        return jax.tree.map(
            lambda l: jnp.zeros((n_total,) + l.shape, l.dtype), per_dev
        )

    def _exec_window(self, num_steps, start_step, end_step):
        num_exec_end = num_steps if end_step is None else end_step
        full_sync = self.cfg.mode == "full_sync" or not self.cfg.is_sp
        n_exec = num_exec_end - start_step
        n_sync = (n_exec if full_sync and not self.cfg.step_cache_enabled
                  else min(self.cfg.warmup_steps + 1, n_exec))
        return num_exec_end, n_sync

    def _ensure_stepper(self, num_steps: int, sync: bool,
                        shallow: bool = False):
        """Jitted per-step program, cached by (num_steps, phase, shallow):
        _make_step bakes the scheduler tables at trace time, so a different
        step count MUST get a fresh program (same convention as
        DenoiseRunner's ("stepwise", num_steps))."""
        fns = self._compiled.setdefault(("stepwise", num_steps), {})
        fkey = (sync, shallow)
        if fkey not in fns:
            fns[fkey] = jax.jit(self._make_stepper(sync, shallow),
                                donate_argnums=(3,))
        return fns[fkey]

    def _ensure_stale_scan(self, num_steps: int):
        """Hybrid mode's fused stale-only program for the default execution
        window (mirrors DenoiseRunner._ensure_stale_scan)."""
        n_sync = min(self.cfg.warmup_steps + 1, num_steps)
        skey = ("stale_scan", num_steps, n_sync)
        if skey not in self._compiled:
            self._compiled[skey] = self._build_stale_scan(num_steps, n_sync)
        return self._compiled[skey], n_sync

    def _generate_stepwise(self, latents, enc, pooled, gs, num_steps,
                           start_step=0, end_step=None, callback=None):
        """Python loop over per-step compiled calls (use_cuda_graph=False
        parity, same contract as DenoiseRunner._generate_stepwise):
        identical numerics to the fused loop, per-step latency visible
        from the host, diffusers legacy ``callback(i, t, latents)``."""
        cfg, mcfg = self.cfg, self.mcfg
        sched = self.scheduler
        sched.set_timesteps(num_steps)
        num_exec_end, n_sync = self._exec_window(num_steps, start_step,
                                                 end_step)
        x = dit_mod.patchify(mcfg, jnp.asarray(latents, jnp.float32))
        sstate = sched.init_state(x.shape)
        kv = self._kv0_global(latents.shape[0])
        pooled = jnp.asarray(pooled)
        sc = cfg.step_cache_enabled
        one_phase = cfg.mode == "full_sync" or not cfg.is_sp
        for i in range(start_step, num_exec_end):
            sync = one_phase or i < start_step + n_sync
            shallow = sc and is_shallow_at(
                i, start_step + n_sync, cfg.step_cache_interval
            )
            x, sstate, kv = self._ensure_stepper(num_steps, sync, shallow)(
                self.params, jnp.asarray(i), x, kv, sstate, enc, pooled, gs,
            )
            if callback is not None:
                callback(i, sched.timesteps()[i],
                         dit_mod.unpatchify(mcfg, x, mcfg.out_channels))
        return dit_mod.unpatchify(mcfg, x, mcfg.out_channels)

    # -- explicit-carry stepwise API (step-granular serve substrate) -------

    def stepwise_carry_init(self, latents, num_steps: int):
        """Start a host-driven denoise with the carry held EXTERNALLY:
        ``(x, sstate, kv)`` — the state one `_generate_stepwise`
        iteration threads, so the step-granular serve layer
        (serve/stepbatch.py) can park/resume/interleave requests between
        steps while each carry replays the identical per-step programs."""
        self.scheduler.set_timesteps(num_steps)
        x = dit_mod.patchify(self.mcfg, jnp.asarray(latents, jnp.float32))
        return (x, self.scheduler.init_state(x.shape),
                self._kv0_global(latents.shape[0]))

    def stepwise_carry_step(self, carry, i: int, enc, pooled, gs,
                            num_steps: int):
        """Advance one explicit carry by exactly step ``i`` — the SAME
        compiled stepper `_generate_stepwise` dispatches for this
        (phase, shallow) signature, so solo and interleaved executions
        are byte-identical."""
        cfg = self.cfg
        x, sstate, kv = carry
        _, n_sync = self._exec_window(num_steps, 0, None)
        one_phase = cfg.mode == "full_sync" or not cfg.is_sp
        sync = one_phase or i < n_sync
        shallow = cfg.step_cache_enabled and is_shallow_at(
            i, n_sync, cfg.step_cache_interval)
        return self._ensure_stepper(num_steps, sync, shallow)(
            self.params, jnp.asarray(i), x, kv, sstate, enc, pooled, gs)

    def stepwise_carry_latent(self, carry):
        """The carry's current GLOBAL latent [B, H/8, W/8, C] (preview +
        decode input) — does not consume the carry."""
        return dit_mod.unpatchify(self.mcfg, carry[0],
                                  self.mcfg.out_channels)

    # -- packed cohort rows (serve/executors.py step_run; parallel/rowpack) --

    def stepwise_rows_supported(self) -> bool:
        """Whether packed multi-row dispatch preserves bit-identity on this
        config.  DP-split batches can't carry a replicated per-row step
        vector; the PCPP partial-refresh rotation (`refresh_gather_seq`
        step=s) and per-tensor compression scales couple rows."""
        cfg = self.cfg
        return (cfg.dp_degree == 1 and cfg.refresh_fraction >= 1
                and cfg.comm_compress == "none")

    def stepwise_carry_signature(self, carry, i: int, num_steps: int):
        """Compiled-program key of step ``i`` — two carries whose next
        steps share this tuple run the SAME jitted stepper and may pack
        into one dispatch."""
        cfg = self.cfg
        _, n_sync = self._exec_window(num_steps, 0, None)
        one_phase = cfg.mode == "full_sync" or not cfg.is_sp
        sync = one_phase or i < n_sync
        shallow = cfg.step_cache_enabled and is_shallow_at(
            i, n_sync, cfg.step_cache_interval)
        return ("mmdit", sync, shallow, num_steps)

    def stepwise_carry_rows_axes(self, carry, num_steps: int):
        """Per-leaf rowpack plan for this runner's carry layout, found by
        comparing the carry's abstract shapes at batch widths w and 2w
        (rowpack.axes_from_shapes) — no hand-maintained layout table."""
        from . import rowpack

        x = carry[0]
        w = x.shape[0]

        def shapes(k):
            return jax.eval_shape(lambda: (
                jnp.zeros((w * k,) + x.shape[1:], x.dtype),
                self.scheduler.init_state((w * k,) + x.shape[1:]),
                self._kv0_global(w * k),
            ))

        return rowpack.axes_from_shapes(shapes(1), shapes(2))

    def stepwise_carry_step_rows(self, carry, i_rows, enc, pooled,
                                 gs_rows, num_steps: int):
        """Advance ``len(i_rows)`` packed rows in ONE dispatch of the same
        jitted stepper the solo path uses: row r steps by its own index
        ``i_rows[r]`` under its own scale ``gs_rows[r]``.  All rows must
        share one (phase, shallow) signature — callers group by
        `stepwise_carry_signature` first."""
        x, sstate, kv = carry
        sigs = {self.stepwise_carry_signature(carry, int(i), num_steps)
                for i in i_rows}
        if len(sigs) != 1:
            raise ValueError(
                f"packed rows span {len(sigs)} step signatures: {sigs}"
            )
        _, sync, shallow, _ = next(iter(sigs))
        return self._ensure_stepper(num_steps, sync, shallow)(
            self.params, jnp.asarray(list(i_rows)), x, kv, sstate, enc,
            pooled, jnp.asarray(list(gs_rows), jnp.float32))

    def _build_stale_scan(self, num_steps: int, n_start: int):
        """Fused stale steady-state ONLY (cfg.hybrid_loop; the MMDiT analog
        of DenoiseRunner._build_stale_scan): the sync warmup runs through
        the per-step programs, their KV state enters here across the
        shard_map boundary in the stepwise layout, and this ONE-body
        program scans the remaining stale steps — roughly half the fully
        fused program's (remote) compile at identical numerics."""
        cfg = self.cfg
        self.scheduler.set_timesteps(num_steps)
        x_spec, kv_spec, ss_spec, enc_spec = self._token_specs()

        def device_scan(params, x, kv, sstate, enc, pooled, gs):
            step, _, _ = self._make_step(params, enc, pooled, gs, x.shape[0])

            def body(carry, i):
                x, ss, kv = carry
                return step(x, ss, kv, i, False), None

            (x, _, _), _ = lax.scan(
                body, (x, sstate, jax.tree.map(lambda l: l[0], kv)),
                jnp.arange(n_start, num_steps)
            )
            return x

        def loop(params, x, kv, sstate, enc, pooled, gs):
            return shard_map(
                device_scan,
                mesh=cfg.mesh,
                in_specs=(P(), x_spec, kv_spec, ss_spec, enc_spec, enc_spec,
                          P()),
                out_specs=x_spec,
                check_vma=False,
            )(params, x, kv, sstate, enc, pooled, gs)

        # x and the incoming state (KV AND scheduler state — its x-shaped
        # leaves are latent-sized) die at this call; let XLA reuse the HBM
        return jax.jit(loop, donate_argnums=(1, 2, 3))

    def _hybrid_dispatch(self, num_steps: int) -> bool:
        cfg = self.cfg
        return (cfg.hybrid_loop and cfg.is_sp and cfg.mode != "full_sync"
                and min(cfg.warmup_steps + 1, num_steps) < num_steps)

    def _generate_hybrid(self, latents, enc, pooled, gs, num_steps):
        """Sync warmup via per-step programs + one fused stale-only scan."""
        cfg, mcfg = self.cfg, self.mcfg
        sched = self.scheduler
        sched.set_timesteps(num_steps)
        stale_scan, n_sync = self._ensure_stale_scan(num_steps)
        x = dit_mod.patchify(mcfg, jnp.asarray(latents, jnp.float32))
        sstate = sched.init_state(x.shape)
        kv = self._kv0_global(latents.shape[0])
        pooled = jnp.asarray(pooled)
        for i in range(n_sync):
            x, sstate, kv = self._ensure_stepper(num_steps, True)(
                self.params, jnp.asarray(i), x, kv, sstate, enc, pooled, gs,
            )
        out = stale_scan(self.params, x, kv, sstate, enc, pooled, gs)
        return dit_mod.unpatchify(mcfg, out, mcfg.out_channels)

    def _fire_callback(self, i, t, x):
        """Host trampoline for the compiled-loop callback (io_callback)."""
        cb = self._active_callback
        if cb is not None:
            cb(int(i), t, x)

    def _build_fused_callback(self, num_steps: int, start_step: int = 0,
                              end_step: int = None):
        """Compiled loop that fires per-step host callbacks — the MMDiT
        analog of DenoiseRunner._build_fused_callback: lax.scan over the
        shard_map'd stepwise step with ordered io_callback shipping the
        GLOBAL unpatchified latents after each step (scan for both
        segments; ordered effects are unsupported in fori bodies)."""
        from jax.experimental import io_callback

        cfg, mcfg = self.cfg, self.mcfg
        sched = self.scheduler
        sched.set_timesteps(num_steps)
        num_exec_end, n_sync = self._exec_window(num_steps, start_step,
                                                 end_step)
        sync_step = self._make_stepper(True)
        stale_step = self._make_stepper(False)

        def loop(params, latents, enc, pooled, gs):
            x = dit_mod.patchify(mcfg, latents.astype(jnp.float32))
            sstate = sched.init_state(x.shape)
            kv = self._kv0_global(latents.shape[0])
            tsteps = sched.timesteps()

            def body_for(step_fn):
                def body(carry, i):
                    x, kv, ss = carry
                    x, ss, kv = step_fn(params, i, x, kv, ss, enc, pooled,
                                        gs)
                    io_callback(
                        self._fire_callback, None, i, tsteps[i],
                        dit_mod.unpatchify(mcfg, x, mcfg.out_channels),
                        ordered=True,
                    )
                    return (x, kv, ss), None
                return body

            (x, kv, sstate), _ = lax.scan(
                body_for(sync_step), (x, kv, sstate),
                jnp.arange(start_step, start_step + n_sync),
            )
            if start_step + n_sync < num_exec_end:
                (x, kv, sstate), _ = lax.scan(
                    body_for(stale_step), (x, kv, sstate),
                    jnp.arange(start_step + n_sync, num_exec_end),
                )
            return dit_mod.unpatchify(mcfg, x, mcfg.out_channels)

        return jax.jit(loop)

    def comm_report(self, batch_size: int = 1) -> Dict[str, Any]:
        """Per-device stale-state and per-step collective volumes (elements)
        for the configured joint layout — closed-form, no tracing."""
        cfg, mcfg = self.cfg, self.mcfg
        n = cfg.n_device_per_batch
        layout = cfg.attn_impl
        if not cfg.is_sp:
            report = {"layout": layout, "kv_state_elems": 0,
                      "per_step_collective_elems": 0,
                      # byte model: a single-device group has no sp
                      # traffic — zero is the truth, not a guess
                      # (pipelines.comm_plan raises on runners that
                      # lack these keys)
                      "per_step_collective_bytes": 0,
                      "sync_step_collective_bytes": 0}
            if cfg.step_cache_enabled:
                report["step_cache"] = {
                    "interval": cfg.step_cache_interval,
                    "depth": cfg.step_cache_depth,
                    "shallow_per_step_collective_elems": 0,
                }
            return report
        n_br_local = (
            1 if cfg.cfg_split or not cfg.do_classifier_free_guidance else 2
        )
        b = batch_size * n_br_local
        n_tok, hid, depth = mcfg.num_tokens, mcfg.hidden_size, mcfg.depth
        # dual-attention blocks (SD3.5-medium) carry and exchange a second
        # image KV each, so they count double
        n_attn = depth + mcfg.dual_attention_blocks
        chunk = n_tok // n
        out_gather = b * n_tok * mcfg.patch_size**2 * mcfg.out_channels
        if layout == "ring":
            state = n_attn * b * chunk * 2 * hid
            # (n-1) ppermute hops of the local 2C chunk per block, in-step;
            # no refresh collective (next state = own fresh chunk)
            per_step = n_attn * (n - 1) * b * chunk * 2 * hid + out_gather
        else:
            state = n_attn * 2 * b * n_tok * hid
            per_step = n_attn * 2 * b * n_tok * hid + out_gather
        report = {"layout": layout, "kv_state_elems": int(state),
                  "per_step_collective_elems": int(per_step)}
        # wire bytes: sync full-precision always; stale compressed when
        # comm_compress is on, thinned to 1/k of the KV rows when
        # refresh_fraction = 1/k (gather layout only — ring rejects both
        # knobs).  full_refresh_* pins the fraction-1 closed form so the
        # PCPP reduction is a checked ratio.
        itemsize = jnp.dtype(cfg.dtype).itemsize
        kk = refresh_period(cfg.refresh_fraction)
        report["comm_compress"] = cfg.comm_compress
        report["refresh_fraction"] = cfg.refresh_fraction
        report["sync_step_collective_bytes"] = int(per_step) * itemsize
        if layout == "gather":
            full_refresh = n_attn * n * wire_nbytes(
                (2, b, chunk, hid), itemsize, cfg.comm_compress
            )
            part_refresh = n_attn * n * wire_nbytes(
                (2, b, chunk // kk, hid), itemsize, cfg.comm_compress
            )
            report["per_step_collective_bytes"] = int(
                part_refresh + out_gather * itemsize
            )
            report["full_refresh_per_step_collective_bytes"] = int(
                full_refresh + out_gather * itemsize
            )
        else:
            report["per_step_collective_bytes"] = int(per_step) * itemsize
            report["full_refresh_per_step_collective_bytes"] = (
                int(per_step) * itemsize
            )
        if cfg.step_cache_enabled:
            # shallow steps run d_keep of depth joint blocks (the dual
            # prefix always runs — the cut sits past it); the output gather
            # always runs
            d_keep = mcfg.depth - cfg.step_cache_depth
            n_attn_sh = d_keep + mcfg.dual_attention_blocks
            shallow = ((per_step - out_gather) * n_attn_sh // n_attn
                       + out_gather)
            report["step_cache"] = {
                "interval": cfg.step_cache_interval,
                "depth": cfg.step_cache_depth,
                "shallow_per_step_collective_elems": int(shallow),
            }
        return report

    def generate(self, latents, enc, pooled, guidance_scale=5.0,
                 num_inference_steps=20, start_step=0, end_step=None,
                 callback=None):
        """``latents`` [B, H/8, W/8, C] noise already scaled by
        init_noise_sigma — or, with ``start_step > 0`` (img2img), a clean
        latent noised to that schedule point via ``scheduler.add_noise``;
        ``enc`` [n_br, B, Lc, joint_dim]; ``pooled`` [n_br, B, pooled_dim].
        ``callback(i, t, latents)`` (diffusers legacy signature) fires
        after every step in every mode — from the host loop with
        use_cuda_graph=False, via ordered io_callback inside the compiled
        loop otherwise.  Returns the denoised latent NHWC."""
        assert 0 <= start_step < num_inference_steps, (start_step,
                                                       num_inference_steps)
        assert end_step is None or start_step < end_step <= num_inference_steps, (
            start_step, end_step, num_inference_steps)
        self.scheduler.set_timesteps(num_inference_steps)
        gs = np.float32(guidance_scale)
        if not self.cfg.use_compiled_step:
            return self._generate_stepwise(
                jnp.asarray(latents), enc, pooled, gs, num_inference_steps,
                start_step, end_step, callback,
            )
        if callback is not None:
            if self.cfg.step_cache_enabled:
                # step-cache callbacks take the host loop: the stepwise
                # steppers replay the exact cadence.
                return self._generate_stepwise(
                    jnp.asarray(latents), enc, pooled, gs,
                    num_inference_steps, start_step, end_step, callback,
                )
            key = ("fused_cb", num_inference_steps, start_step, end_step)
            if key not in self._compiled:
                self._compiled[key] = self._build_fused_callback(
                    num_inference_steps, start_step, end_step
                )
            self._active_callback = callback
            try:
                out = self._compiled[key](
                    self.params, jnp.asarray(latents), enc,
                    jnp.asarray(pooled), gs,
                )
                jax.effects_barrier()  # host callbacks drain before return
                jax.block_until_ready(out)
                return out
            finally:
                self._active_callback = None
        if (self._hybrid_dispatch(num_inference_steps)
                and start_step == 0 and end_step is None):
            return self._generate_hybrid(
                jnp.asarray(latents), enc, pooled, gs, num_inference_steps
            )
        key = (num_inference_steps if start_step == 0 and end_step is None
               else (num_inference_steps, start_step, end_step))
        if key not in self._compiled:
            self._compiled[key] = self._build(num_inference_steps,
                                              start_step, end_step)
        return self._compiled[key](
            self.params, latents, enc, jnp.asarray(pooled), gs
        )

    def prepare(self, num_steps: int) -> None:
        """Pre-build exactly the program generate() will dispatch to
        (per-step programs build lazily, like DenoiseRunner.prepare;
        hybrid mode pre-builds the big stale-scan program)."""
        if not self.cfg.use_compiled_step:
            return
        self.scheduler.set_timesteps(num_steps)
        if self._hybrid_dispatch(num_steps):
            self._ensure_stale_scan(num_steps)
            return
        if num_steps not in self._compiled:
            self._compiled[num_steps] = self._build(num_steps)
