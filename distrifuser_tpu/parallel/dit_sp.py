"""Displaced patch parallelism for the DiT — DistriFusion's method on the
transformer model family.

The reference implements displaced patches for the UNet only (its whole
module zoo exists to make convs/GroupNorm/attention patch-aware,
modules/pp/*).  A DiT needs none of that machinery: LayerNorm, the MLP, and
text cross-attention are strictly per-token, so **self-attention is the only
op that crosses patch boundaries**.  Sharding the token sequence over the
``sp`` axis therefore reduces DistriFusion to exactly one exchange:

* sync phase (steps <= warmup, reference counter semantics §2.3): each
  block's fresh local K/V are all-gathered — exact full attention;
* stale phase: each block attends over the *previous step's* gathered K/V
  with this device's own slot overwritten fresh (pp/attn.py:135-140
  semantics), and all-gathers its fresh K/V into the scan carry — consumed
  only next step, so XLA's latency-hiding scheduler overlaps the collective
  with the remaining blocks' compute, the role of the reference's async
  NCCL gathers (utils.py:170-190).

Per-block stale state depends on ``attn_impl``: "gather" carries the full
gathered [depth, 2, B, N, hidden] K/V (O(L), the reference's buffer
layout); "ring" carries only the own [depth, B, N/n, 2*hidden] chunk and
streams peers through the shared ``ring_pass`` online softmax — O(L/n)
state and no refresh collective at all.  Two exact (stateless) layouts
complete the menu: "ulysses" (head-sharding all_to_all over the whole sp
axis) and "usp" (the xDiT-style 2-level composition — sp factored into
``ulysses_degree`` x ring sub-axes, one all_to_all per block over the
inner axis and a fresh-KV ring over the outer one).  The pipeline runner
(pipefusion.py) and this runner are complementary points on the
memory/traffic trade (weights/depth-sharded + O(N/M) ring hops vs
weights-replicated + KV exchange).

Every device returns the full latent and steps the scheduler replicated —
the same contract as DenoiseRunner, so pipelines can treat both
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
from ..models.dit import DiTConfig
from ..ops.attention import sdpa
from ..schedulers import BaseScheduler
from ..utils.config import (
    CFG_AXIS,
    DP_AXIS,
    SP_AXIS,
    SP_R_AXIS,
    SP_U_AXIS,
    DistriConfig,
)
from .collectives import all_gather_seq
from .compress import refresh_gather_seq, refresh_period, wire_nbytes
from .guidance import branch_select, combine_guidance
from .stepcache import is_shallow_at, run_cadence


class DiTDenoiseRunner:
    """Compiled displaced-patch generation loop for a DiT.

    API mirrors DenoiseRunner/PipeFusionRunner.generate.
    """

    def __init__(
        self,
        distri_config: DistriConfig,
        dit_config: DiTConfig,
        params,
        scheduler: BaseScheduler,
    ):
        self.cfg = distri_config
        self.dcfg = dit_config
        self.params = distri_config.place(params)
        self.scheduler = scheduler
        # attn_impl="gather" carries full gathered KV per block (reference
        # layout); "ring" carries only the local chunk and streams peers
        # through the online-softmax ring (O(L/n) state, no refresh
        # collective) — the same pair of layouts the UNet offers.
        if distri_config.comm_batch:
            raise ValueError(
                "comm_batch applies to the UNet's per-layer halo/moment "
                "exchanges; the DiT path has one collective kind already"
            )
        if (distri_config.comm_compress != "none"
                and distri_config.attn_impl != "gather"):
            raise ValueError(
                f"comm_compress compresses the displaced KV refresh gathers "
                f"of attn_impl='gather'; {distri_config.attn_impl!r} has no "
                "refresh collective to compress (ring carries the local "
                "chunk; ulysses/usp are exact and stateless)"
            )
        if (distri_config.refresh_fraction < 1.0
                and distri_config.attn_impl != "gather"):
            raise ValueError(
                "refresh_fraction < 1 (PCPP) thins the displaced KV refresh "
                f"gathers of attn_impl='gather'; {distri_config.attn_impl!r} "
                "has no refresh collective to thin"
            )
        n = distri_config.n_device_per_batch
        if (
            distri_config.attn_impl == "ulysses"
            and dit_config.num_heads % n != 0
        ):
            raise ValueError(
                f"ulysses needs num_heads ({dit_config.num_heads}) divisible "
                f"by the sp degree ({n})"
            )
        if (
            distri_config.attn_impl == "usp"
            and dit_config.num_heads % distri_config.ulysses_degree != 0
        ):
            raise ValueError(
                f"usp needs num_heads ({dit_config.num_heads}) divisible by "
                f"ulysses_degree ({distri_config.ulysses_degree})"
            )
        # USP runs on the 4-axis factored view of the same device grid;
        # sequence-sharding ops address the composite (sp_u, sp_r) axis pair.
        self._usp = distri_config.attn_impl == "usp"
        self.mesh = distri_config.usp_mesh() if self._usp else distri_config.mesh
        self.seq_axes = (SP_U_AXIS, SP_R_AXIS) if self._usp else SP_AXIS
        if dit_config.num_tokens % n != 0:
            raise ValueError(
                f"token count {dit_config.num_tokens} must be divisible by "
                f"the sp degree {n}"
            )
        _rk = refresh_period(distri_config.refresh_fraction)
        if _rk > 1 and (dit_config.num_tokens // n) % _rk != 0:
            raise ValueError(
                f"refresh_fraction=1/{_rk} needs the per-device token chunk "
                f"({dit_config.num_tokens // n}) divisible by {_rk} — each "
                "stale step gathers exactly one strided row group"
            )
        if distri_config.step_cache_enabled and not (
            1 <= distri_config.step_cache_depth < dit_config.depth
        ):
            raise ValueError(
                f"step_cache_depth={distri_config.step_cache_depth} must be "
                f"in [1, {dit_config.depth - 1}] for this {dit_config.depth}-"
                "block DiT (at least one block must stay shallow)"
            )
        if (distri_config.height // 8 != dit_config.sample_size) or (
            distri_config.width // 8 != dit_config.sample_size
        ):
            raise ValueError(
                f"DistriConfig {distri_config.height}x{distri_config.width} "
                f"implies latent {distri_config.latent_height}, but "
                f"DiTConfig.sample_size is {dit_config.sample_size}"
            )
        self._compiled: Dict[int, Any] = {}
        # compiled-loop per-step callback target (_build_fused_callback)
        self._active_callback = None

    # ------------------------------------------------------------------

    def _eval_model(self, params, x_full, s, kv_state, phase_sync,
                    cap_kv, c6_all, temb_all, pos, cap_bias, shallow=False):
        """One DiT evaluation on this device's token rows.

        Returns (full guided-input epsilon [Bl, N, D_out], new kv_state).
        ``kv_state``: gathered [depth, 2, Bl, N, hidden] stale K/V
        (attn_impl="gather") or the own [depth, Bl, N/n, 2*hidden] chunk
        (attn_impl="ring") — or, with the step cache enabled,
        ``{"kv": <that state>, "deep": [Bl, N/n, hidden]}`` where ``deep``
        is the residual the deepest ``step_cache_depth`` blocks added on
        the last full step.  ``shallow`` runs only the first
        ``depth - step_cache_depth`` blocks and adds the carried residual
        (the skipped blocks' displaced KV rides through untouched).
        """
        cfg, dcfg = self.cfg, self.dcfg
        sched = self.scheduler
        n = cfg.n_device_per_batch
        n_tok = dcfg.num_tokens
        chunk = n_tok // n
        sp_idx = lax.axis_index(self.seq_axes)
        offset = sp_idx * chunk
        compute_dtype = params["proj_in"]["kernel"].dtype

        x_in = sched.scale_model_input(x_full, s)
        rows = lax.dynamic_slice(
            x_in, (0, offset, 0), (x_in.shape[0], chunk, x_in.shape[2])
        ).astype(compute_dtype)
        folded = not cfg.cfg_split and cfg.do_classifier_free_guidance
        if folded:
            rows = jnp.concatenate([rows, rows], axis=0)
        pos_rows = lax.dynamic_slice(pos, (offset, 0), (chunk, pos.shape[1]))
        h = dit_mod.embed_tokens(params, dcfg, rows, pos_rows)
        c6 = c6_all[s]
        temb = temb_all[s]
        if jnp.ndim(s) and folded:
            # per-row step indices (packed cohort dispatch): the [B, ...]
            # conditioning tables fold branch-major exactly like the rows
            c6 = jnp.concatenate([c6, c6], axis=0)
            temb = jnp.concatenate([temb, temb], axis=0)

        no_refresh = cfg.mode == "no_sync"  # keep warmup KV forever (§2.3)
        ring = cfg.attn_impl == "ring"
        ulysses = cfg.attn_impl == "ulysses"
        usp = self._usp

        def block_body_ulysses(carry, xs):
            """Ulysses SP (exact, stateless): all_to_all re-shards the
            sequence-sharded q/k/v to head-sharded full sequences, runs full
            attention on H/n heads, and re-shards back — the DeepSpeed-
            Ulysses layout (SURVEY §2.1 lists it absent in the reference).
            No staleness, so sync and stale phases are identical and the
            carry passes through untouched."""
            hcur = carry
            bp, ckv, kv_blk = xs
            heads = dcfg.num_heads
            d = dcfg.hidden_size // heads

            def core(q, k, v):
                b_, lq_ = q.shape[0], q.shape[1]

                def to_headshard(t):
                    th = t.reshape(b_, lq_, heads, d)
                    # split heads over sp, concat tokens -> [B, N, H/n, D]
                    return lax.all_to_all(
                        th, SP_AXIS, split_axis=2, concat_axis=1, tiled=True
                    )

                qg, kg, vg = to_headshard(q), to_headshard(k), to_headshard(v)
                n_full = qg.shape[1]
                h_loc = heads // n
                att = sdpa(
                    qg.reshape(b_, n_full, h_loc * d),
                    kg.reshape(b_, n_full, h_loc * d),
                    vg.reshape(b_, n_full, h_loc * d),
                    heads=h_loc,
                )
                att = att.reshape(b_, n_full, h_loc, d)
                back = lax.all_to_all(
                    att, SP_AXIS, split_axis=1, concat_axis=2, tiled=True
                )  # [B, chunk, H, D]
                return back.reshape(b_, lq_, dcfg.hidden_size)

            h_out, _ = dit_mod.dit_block(
                bp, dcfg, hcur, c6, ckv, attn_core=core, cap_bias=cap_bias
            )
            return h_out, kv_blk

        def block_body_usp(carry, xs):
            """USP (exact, stateless): the xDiT-style 2-level composition.
            The sp axis is factored (sp_u x sp_r); one all_to_all over sp_u
            turns [B, N/n, H, D] token shards into [B, N/r, H/u, D]
            head-sharded assemblies, the exact KV ring over sp_r streams the
            other r-1 assemblies through the online softmax (every chunk
            fresh — unlike the displaced "ring" layout there is no
            staleness), and the inverse all_to_all restores the token shard.
            Per block this moves 1/u of pure-ring bytes over the ring and
            1/r of pure-ulysses bytes through the all_to_alls — the knob
            (ulysses_degree) picks the point between them that fits the
            mesh."""
            from ..ops.ring_attention import ring_pass

            hcur = carry
            bp, ckv, kv_blk = xs
            heads = dcfg.num_heads
            d = dcfg.hidden_size // heads
            u = cfg.ulysses_degree
            r = n // u

            def core(q, k, v):
                b_, lq_ = q.shape[0], q.shape[1]

                def to_headshard(t):
                    th = t.reshape(b_, lq_, heads, d)
                    if u == 1:
                        return th
                    # split heads over sp_u, concat this u-group's tokens
                    return lax.all_to_all(
                        th, SP_U_AXIS, split_axis=2, concat_axis=1, tiled=True
                    )  # [B, N/r, H/u, D]

                qg, kg, vg = to_headshard(q), to_headshard(k), to_headshard(v)
                l_loc, h_loc = qg.shape[1], heads // u
                q2 = qg.reshape(b_, l_loc, h_loc * d)
                kv_local = jnp.concatenate(
                    [kg.reshape(b_, l_loc, h_loc * d),
                     vg.reshape(b_, l_loc, h_loc * d)], axis=-1
                )
                out = ring_pass(q2, kv_local, kv_local, r, SP_R_AXIS,
                                heads=h_loc)  # [B, H/u, N/r, D] fp32
                out = out.astype(q.dtype).transpose(0, 2, 1, 3)
                if u > 1:
                    out = lax.all_to_all(
                        out, SP_U_AXIS, split_axis=1, concat_axis=2, tiled=True
                    )  # [B, N/n, H, D]
                return out.reshape(b_, lq_, dcfg.hidden_size)

            h_out, _ = dit_mod.dit_block(
                bp, dcfg, hcur, c6, ckv, attn_core=core, cap_bias=cap_bias
            )
            return h_out, kv_blk

        def block_body_gather(carry, xs):
            hcur = carry
            bp, ckv, kv_blk = xs  # kv_blk [2, Bl, N, hid] stale gathered
            assembled = {}

            @jax.named_scope("stale_kv")
            def assemble(k_fresh, v_fresh):
                if phase_sync:
                    kv = (all_gather_seq(k_fresh), all_gather_seq(v_fresh))
                else:
                    kv = (
                        lax.dynamic_update_slice(kv_blk[0], k_fresh, (0, offset, 0)),
                        lax.dynamic_update_slice(kv_blk[1], v_fresh, (0, offset, 0)),
                    )
                assembled["kv"] = kv
                return kv

            h_out, (k, v) = dit_mod.dit_block(
                bp, dcfg, hcur, c6, ckv, kv_assemble=assemble, cap_bias=cap_bias
            )
            # refresh for the NEXT step: fresh gathered K/V flow only into
            # the carry (deferred consumption = overlappable collective).
            # Sync phase reuses the already-assembled gather; no_sync keeps
            # the carried state untouched after warmup.  Stale refreshes
            # route through the compression layer (parallel/compress.py) —
            # a plain tiled gather at comm_compress="none", an int8/fp8
            # payload + fp32-scale pair of gathers otherwise.
            if phase_sync:
                fresh = jnp.stack(list(assembled["kv"]))
            elif no_refresh:
                fresh = kv_blk
            else:
                fresh = refresh_gather_seq(
                    jnp.stack([k, v]), kv_blk, cfg.comm_compress, offset,
                    fraction=cfg.refresh_fraction, step=s,
                )
            return h_out, fresh

        def block_body_ring(carry, xs):
            from ..ops.ring_attention import ring_pass

            hcur = carry
            bp, ckv, kv_blk = xs  # kv_blk [Bl, chunk, 2*hid] own stale chunk

            def core(q, k, v):
                # with no kv_assemble/self_kv, dit_block hands the fresh
                # local (k, v) straight through — exactly the own chunk
                kv_local = jnp.concatenate([k, v], axis=-1)
                # sync phase rotates fresh chunks (exact); stale phase
                # rotates each peer's previous-step chunk from the carry
                rotating = kv_local if phase_sync else kv_blk
                out = ring_pass(q, kv_local, rotating, n, SP_AXIS,
                                heads=dcfg.num_heads)
                b_, lq_ = q.shape[0], q.shape[1]
                out = out.astype(q.dtype).transpose(0, 2, 1, 3)
                return out.reshape(b_, lq_, dcfg.hidden_size)

            h_out, (k, v) = dit_mod.dit_block(
                bp, dcfg, hcur, c6, ckv, attn_core=core, cap_bias=cap_bias
            )
            # next step's stale state is just this step's own fresh chunk —
            # no collective at all (ring_attention.py semantics).  Sync steps
            # always commit (that snapshot IS what no_sync freezes).
            if phase_sync or not no_refresh:
                fresh = jnp.concatenate([k, v], axis=-1)
            else:
                fresh = kv_blk
            return h_out, fresh

        if usp:
            block_body = block_body_usp
        elif ulysses:
            block_body = block_body_ulysses
        else:
            block_body = block_body_ring if ring else block_body_gather

        if cfg.step_cache_enabled:
            kv_blocks, deep = kv_state["kv"], kv_state["deep"]
            d_keep = dcfg.depth - cfg.step_cache_depth
            if shallow:
                # shallow body: only the first d_keep blocks execute; the
                # deepest blocks' contribution is the carried residual, and
                # their displaced KV (and the residual) pass through — so
                # their refresh collectives never appear in this body.
                head_xs = jax.tree.map(
                    lambda l: l[:d_keep],
                    (params["blocks"], cap_kv, kv_blocks),
                )
                h, kv_head = lax.scan(block_body, h, head_xs)
                h = h + deep
                kv_new = {
                    "kv": jax.tree.map(
                        lambda fresh, old: jnp.concatenate(
                            [fresh, old[d_keep:]], axis=0
                        ),
                        kv_head, kv_blocks,
                    ),
                    "deep": deep,
                }
            else:
                # full body: run everything, capturing h at the cut so the
                # deep residual (h_final - h_mid) refreshes the carry
                def full_body(carry, xs):
                    hcur, h_mid = carry
                    h2, fresh = block_body(hcur, xs[1:])
                    h_mid = jnp.where(xs[0] == d_keep - 1, h2, h_mid)
                    return (h2, h_mid), fresh

                (h, h_mid), kv_all = lax.scan(
                    full_body, (h, h),
                    (jnp.arange(dcfg.depth), params["blocks"], cap_kv,
                     kv_blocks),
                )
                kv_new = {"kv": kv_all, "deep": h - h_mid}
        else:
            h, kv_new = lax.scan(
                block_body, h, (params["blocks"], cap_kv, kv_state)
            )
        eps_rows = dit_mod.final_layer(params, dcfg, h, temb)
        eps_full = all_gather_seq(eps_rows, self.seq_axes)
        return eps_full, kv_new

    def _make_step(self, params, enc, cap_mask, gs, batch):
        """Per-device step closure + the local branch count and dtype —
        shared by the fused loop and the hybrid pair of programs."""
        cfg, dcfg = self.cfg, self.dcfg
        sched = self.scheduler
        my_enc, _, _ = branch_select(cfg, enc)
        my_mask, _, _ = branch_select(cfg, cap_mask)
        cap_bias = dit_mod.caption_mask_bias(my_mask)
        compute_dtype = params["proj_in"]["kernel"].dtype
        pos = dit_mod.pos_embed_table(dcfg, compute_dtype)
        cap_kv = dit_mod.precompute_caption_kv(params, dcfg, my_enc)
        ts = sched.timesteps()
        temb_all = jax.vmap(lambda t: dit_mod.t_embed(params, dcfg, t))(ts)
        c6_all = jax.vmap(lambda e: dit_mod.adaln_table(params, dcfg, e))(temb_all)

        def step(x, sstate, kv, s, phase_sync, shallow=False):
            eps, kv = self._eval_model(
                params, x, s, kv, phase_sync, cap_kv, c6_all, temb_all, pos,
                cap_bias, shallow=shallow,
            )
            guided = combine_guidance(cfg, eps, gs, batch)
            x, sstate = sched.step(x, guided.astype(jnp.float32), s, sstate)
            return x, sstate, kv

        return step, my_enc.shape[0], compute_dtype

    def _kv0(self, bloc, compute_dtype):
        cfg, dcfg = self.cfg, self.dcfg
        if cfg.attn_impl in ("ulysses", "usp"):
            # exact and stateless: a minimal placeholder keeps the block
            # scan's xs structure uniform
            kv = jnp.zeros((dcfg.depth, 1), compute_dtype)
        elif cfg.attn_impl == "ring":
            chunk = dcfg.num_tokens // cfg.n_device_per_batch
            kv = jnp.zeros(
                (dcfg.depth, bloc, chunk, 2 * dcfg.hidden_size), compute_dtype
            )
        else:
            kv = jnp.zeros(
                (dcfg.depth, 2, bloc, dcfg.num_tokens, dcfg.hidden_size),
                compute_dtype,
            )
        if cfg.step_cache_enabled:
            chunk = dcfg.num_tokens // cfg.n_device_per_batch
            return {"kv": kv, "deep": jnp.zeros(
                (bloc, chunk, dcfg.hidden_size), compute_dtype)}
        return kv

    def _device_loop(self, params, latents, enc, cap_mask, gs, num_steps):
        cfg, dcfg = self.cfg, self.dcfg
        batch = latents.shape[0]
        step, bloc, compute_dtype = self._make_step(
            params, enc, cap_mask, gs, batch
        )
        x = dit_mod.patchify(dcfg, latents.astype(jnp.float32))
        sstate = self.scheduler.init_state(x.shape)
        kv0 = self._kv0(bloc, compute_dtype)

        full_sync = cfg.mode == "full_sync" or not cfg.is_sp

        def sync_body(i, carry):
            x, ss, kv = carry
            return step(x, ss, kv, i, True)

        if cfg.step_cache_enabled:
            # temporal step-cache cadence (parallel/stepcache.py): full
            # warmup, then super-steps of (interval-1) shallow + 1 full —
            # the same two-bodies-in-a-scan shape as the UNet runner's
            n_sync = min(cfg.warmup_steps + 1, num_steps)
            x, sstate, kv = lax.fori_loop(
                0, n_sync, sync_body, (x, sstate, kv0)
            )

            def run_step(carry, i, shallow):
                x, ss, kv = carry
                return step(x, ss, kv, i, full_sync, shallow)

            x, _, _ = run_cadence(
                (x, sstate, kv), n_sync, num_steps - n_sync,
                cfg.step_cache_interval, run_step,
            )
            return dit_mod.unpatchify(dcfg, x, dcfg.in_channels)

        n_sync = num_steps if full_sync else min(cfg.warmup_steps + 1, num_steps)

        x, sstate, kv = lax.fori_loop(0, n_sync, sync_body, (x, sstate, kv0))

        if n_sync < num_steps:
            def stale_body(carry, i):
                x, ss, kv = carry
                return step(x, ss, kv, i, False), None

            (x, _, _), _ = lax.scan(
                stale_body, (x, sstate, kv), jnp.arange(n_sync, num_steps)
            )
        return dit_mod.unpatchify(dcfg, x, dcfg.in_channels)

    # ------------------------------------------------------------------

    def _build(self, num_steps: int):
        cfg = self.cfg
        self.scheduler.set_timesteps(num_steps)
        device_loop = partial(self._device_loop, num_steps=num_steps)
        lat_spec = P(DP_AXIS)
        enc_spec = P(None, DP_AXIS)

        def loop(params, latents, enc, cap_mask, gs):
            return shard_map(
                device_loop,
                mesh=self.mesh,
                in_specs=(P(), lat_spec, enc_spec, enc_spec, P()),
                out_specs=lat_spec,
                check_vma=False,
            )(params, latents, enc, cap_mask, gs)

        return jax.jit(loop)

    def _build_hybrid(self, num_steps: int):
        """Two ONE-body programs instead of one two-body program
        (cfg.hybrid_loop; the DiT analog of runner._build_stale_scan): the
        sync warmup fori and the stale scan each carry a single transformer
        body, roughly halving the big program's (remote) compile at
        identical numerics.  The carry crosses the jit boundary: tokens and
        scheduler state are replicated within a dp group (the CFG-combined
        scheduler step makes them identical on every device of the group),
        while the stale KV state varies per device and is laid out along
        (dp, cfg, sp) on a fresh leading axis."""
        cfg, dcfg = self.cfg, self.dcfg
        self.scheduler.set_timesteps(num_steps)
        n_sync = min(cfg.warmup_steps + 1, num_steps)
        lat_spec, kv_spec, ss_spec, enc_spec = self._token_specs()

        def device_sync(params, latents, enc, cap_mask, gs):
            batch = latents.shape[0]
            step, bloc, compute_dtype = self._make_step(
                params, enc, cap_mask, gs, batch
            )
            x = dit_mod.patchify(dcfg, latents.astype(jnp.float32))
            sstate = self.scheduler.init_state(x.shape)

            def sync_body(i, carry):
                x, ss, kv = carry
                return step(x, ss, kv, i, True)

            x, sstate, kv = lax.fori_loop(
                0, n_sync, sync_body,
                (x, sstate, self._kv0(bloc, compute_dtype)),
            )
            return x, sstate, kv[None]

        def device_stale(params, x, sstate, kv, enc, cap_mask, gs):
            batch = x.shape[0]
            step, _, _ = self._make_step(params, enc, cap_mask, gs, batch)

            def stale_body(carry, i):
                x, ss, kv = carry
                return step(x, ss, kv, i, False), None

            (x, _, _), _ = lax.scan(
                stale_body, (x, sstate, kv[0]),
                jnp.arange(n_sync, num_steps),
            )
            return dit_mod.unpatchify(dcfg, x, dcfg.in_channels)

        sync = jax.jit(lambda p, l, e, m, g: shard_map(
            device_sync, mesh=self.mesh,
            in_specs=(P(), lat_spec, enc_spec, enc_spec, P()),
            out_specs=(lat_spec, ss_spec, kv_spec),
            check_vma=False,
        )(p, l, e, m, g))
        stale = jax.jit(lambda p, x, ss, kv, e, m, g: shard_map(
            device_stale, mesh=self.mesh,
            in_specs=(P(), lat_spec, ss_spec, kv_spec, enc_spec, enc_spec,
                      P()),
            out_specs=lat_spec,
            check_vma=False,
        )(p, x, ss, kv, e, m, g), donate_argnums=(1, 2, 3))
        return sync, stale

    # ------------------------------------------------------------------
    # per-step (uncompiled-loop) mode + compiled-loop callbacks
    # ------------------------------------------------------------------

    def _token_specs(self):
        """(x_spec, kv_spec, ss_spec, enc_spec) for the stepwise boundary —
        the same layout _build_hybrid documents: tokens/scheduler state
        replicated within a dp group, the per-device stale KV stacked on a
        fresh leading (dp, cfg, sp...) axis."""
        seq = (self.seq_axes if isinstance(self.seq_axes, tuple)
               else (self.seq_axes,))
        kv_spec = P((DP_AXIS, CFG_AXIS) + seq)
        ss_shapes = self.scheduler.init_state(
            (1, self.dcfg.num_tokens, self.dcfg.token_dim)
        )
        ss_spec = jax.tree.map(
            lambda l: P(DP_AXIS) if jnp.ndim(l) >= 3 else P(), ss_shapes
        )
        return P(DP_AXIS), kv_spec, ss_spec, P(None, DP_AXIS)

    def _make_stepper(self, phase_sync: bool, shallow: bool = False):
        """Un-jitted shard_map'd single step over PATCHIFIED tokens
        [B, N, token_dim] (global-array signature)."""
        x_spec, kv_spec, ss_spec, enc_spec = self._token_specs()

        def device_step(params, s, x, kv, sstate, enc, cap_mask, gs):
            step, _, _ = self._make_step(params, enc, cap_mask, gs,
                                         x.shape[0])
            kv_local = jax.tree.map(lambda l: l[0], kv)
            x, sstate, kv_new = step(x, sstate, kv_local, s, phase_sync,
                                     shallow)
            return x, sstate, jax.tree.map(lambda l: l[None], kv_new)

        def stepper(params, s, x, kv, sstate, enc, cap_mask, gs):
            return shard_map(
                device_step,
                mesh=self.mesh,
                in_specs=(P(), P(), x_spec, kv_spec, ss_spec, enc_spec,
                          enc_spec, P()),
                out_specs=(x_spec, ss_spec, kv_spec),
                check_vma=False,
            )(params, s, x, kv, sstate, enc, cap_mask, gs)

        return stepper

    def _ensure_stepper(self, num_steps: int, sync: bool,
                        shallow: bool = False):
        """Jitted per-step program cached by (num_steps, phase, shallow) —
        the scheduler tables bake at trace time (same convention as the
        UNet and MMDiT runners)."""
        fns = self._compiled.setdefault(("stepwise", num_steps), {})
        fkey = (sync, shallow)
        if fkey not in fns:
            fns[fkey] = jax.jit(self._make_stepper(sync, shallow),
                                donate_argnums=(3,))
        return fns[fkey]

    def _kv0_global(self, batch):
        """Global stepwise-layout zeros: per-device _kv0 stacked over every
        mesh device on a fresh leading axis."""
        cfg = self.cfg
        n_total = self.mesh.devices.size
        bloc = (1 if cfg.cfg_split or not cfg.do_classifier_free_guidance
                else 2) * (batch // cfg.dp_degree)
        per_dev = self._kv0(bloc, self.params["proj_in"]["kernel"].dtype)
        return jax.tree.map(
            lambda l: jnp.zeros((n_total,) + l.shape, l.dtype), per_dev
        )

    def _exec_phases(self, num_steps: int):
        full_sync = self.cfg.mode == "full_sync" or not self.cfg.is_sp
        if full_sync and not self.cfg.step_cache_enabled:
            return num_steps
        return min(self.cfg.warmup_steps + 1, num_steps)

    def _generate_stepwise(self, latents, enc, cap_mask, gs, num_steps,
                           callback=None):
        """Python loop over per-step compiled calls (use_cuda_graph=False
        parity): same numerics as the fused loop, per-step latency visible
        from the host, diffusers legacy ``callback(i, t, latents)``."""
        cfg, dcfg = self.cfg, self.dcfg
        sched = self.scheduler
        sched.set_timesteps(num_steps)
        n_sync = self._exec_phases(num_steps)
        one_phase = cfg.mode == "full_sync" or not cfg.is_sp
        sc = cfg.step_cache_enabled
        x = dit_mod.patchify(dcfg, jnp.asarray(latents, jnp.float32))
        sstate = sched.init_state(x.shape)
        kv = self._kv0_global(latents.shape[0])
        for i in range(num_steps):
            shallow = sc and is_shallow_at(i, n_sync,
                                           cfg.step_cache_interval)
            x, sstate, kv = self._ensure_stepper(
                num_steps, one_phase or i < n_sync, shallow
            )(
                self.params, jnp.asarray(i), x, kv, sstate, enc, cap_mask,
                gs,
            )
            if callback is not None:
                callback(i, sched.timesteps()[i],
                         dit_mod.unpatchify(dcfg, x, dcfg.in_channels))
        return dit_mod.unpatchify(dcfg, x, dcfg.in_channels)

    # -- explicit-carry stepwise API (step-granular serve substrate) -------

    def stepwise_carry_init(self, latents, num_steps: int):
        """Start a host-driven denoise with the carry held EXTERNALLY:
        ``(x, sstate, kv)`` — the state one `_generate_stepwise`
        iteration threads, so the step-granular serve layer
        (serve/stepbatch.py) can park/resume/interleave requests between
        steps while each carry replays the identical per-step programs."""
        self.scheduler.set_timesteps(num_steps)
        x = dit_mod.patchify(self.dcfg, jnp.asarray(latents, jnp.float32))
        return (x, self.scheduler.init_state(x.shape),
                self._kv0_global(latents.shape[0]))

    def stepwise_carry_step(self, carry, i: int, enc, cap_mask, gs,
                            num_steps: int):
        """Advance one explicit carry by exactly step ``i`` — the SAME
        compiled stepper `_generate_stepwise` dispatches for this
        (phase, shallow) signature, so solo and interleaved executions
        are byte-identical."""
        cfg = self.cfg
        x, sstate, kv = carry
        n_sync = self._exec_phases(num_steps)
        one_phase = cfg.mode == "full_sync" or not cfg.is_sp
        shallow = cfg.step_cache_enabled and is_shallow_at(
            i, n_sync, cfg.step_cache_interval)
        return self._ensure_stepper(
            num_steps, one_phase or i < n_sync, shallow
        )(self.params, jnp.asarray(i), x, kv, sstate, enc, cap_mask, gs)

    def stepwise_carry_latent(self, carry):
        """The carry's current GLOBAL latent [B, H/8, W/8, C] (preview +
        decode input) — does not consume the carry."""
        return dit_mod.unpatchify(self.dcfg, carry[0],
                                  self.dcfg.in_channels)

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
        n_sync = self._exec_phases(num_steps)
        one_phase = cfg.mode == "full_sync" or not cfg.is_sp
        sync = one_phase or i < n_sync
        shallow = cfg.step_cache_enabled and is_shallow_at(
            i, n_sync, cfg.step_cache_interval)
        return ("dit", sync, shallow, num_steps)

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

    def stepwise_carry_step_rows(self, carry, i_rows, enc, cap_mask,
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
            cap_mask, jnp.asarray(list(gs_rows), jnp.float32))

    def _fire_callback(self, i, t, x):
        """Host trampoline for the compiled-loop callback (io_callback)."""
        cb = self._active_callback
        if cb is not None:
            cb(int(i), t, x)

    def _build_fused_callback(self, num_steps: int):
        """Compiled loop that fires per-step host callbacks: lax.scan over
        the shard_map'd stepwise step with ordered io_callback shipping the
        GLOBAL unpatchified latents after each step (scan for both
        segments; ordered effects are unsupported in fori bodies)."""
        from jax.experimental import io_callback

        cfg, dcfg = self.cfg, self.dcfg
        sched = self.scheduler
        sched.set_timesteps(num_steps)
        n_sync = self._exec_phases(num_steps)
        sync_step = self._make_stepper(True)
        stale_step = self._make_stepper(False)

        def loop(params, latents, enc, cap_mask, gs):
            x = dit_mod.patchify(dcfg, latents.astype(jnp.float32))
            sstate = sched.init_state(x.shape)
            kv = self._kv0_global(latents.shape[0])
            tsteps = sched.timesteps()

            def body_for(step_fn):
                def body(carry, i):
                    x, kv, ss = carry
                    x, ss, kv = step_fn(params, i, x, kv, ss, enc, cap_mask,
                                        gs)
                    io_callback(
                        self._fire_callback, None, i, tsteps[i],
                        dit_mod.unpatchify(dcfg, x, dcfg.in_channels),
                        ordered=True,
                    )
                    return (x, kv, ss), None
                return body

            (x, kv, sstate), _ = lax.scan(
                body_for(sync_step), (x, kv, sstate), jnp.arange(n_sync)
            )
            if n_sync < num_steps:
                (x, kv, sstate), _ = lax.scan(
                    body_for(stale_step), (x, kv, sstate),
                    jnp.arange(n_sync, num_steps),
                )
            return dit_mod.unpatchify(dcfg, x, dcfg.in_channels)

        return jax.jit(loop)

    def comm_report(self, batch_size: int = 1) -> Dict[str, Any]:
        """Per-device stale-state and per-step collective volumes (elements)
        for the configured attention layout — the DiT analog of
        DenoiseRunner.comm_volume_report / PipeFusionRunner.comm_report
        (reference verbose buffer stats, utils.py:152-158).  Closed-form from
        the architecture; no tracing."""
        cfg, dcfg = self.cfg, self.dcfg
        n = cfg.n_device_per_batch
        if not cfg.is_sp:
            report = {"layout": cfg.attn_impl, "kv_state_elems": 0,
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
        # Per-device folded batch (guidance.branch_select): cfg_split keeps
        # one branch locally; otherwise CFG rides the batch dim as 2B.
        n_br_local = (
            1 if cfg.cfg_split or not cfg.do_classifier_free_guidance else 2
        )
        b = batch_size * n_br_local
        n_tok, hid, depth = dcfg.num_tokens, dcfg.hidden_size, dcfg.depth
        chunk = n_tok // n
        # the final-layer epsilon gather runs in every layout; eps-only head
        # (out_channels), not diffusers' 2x (eps, sigma) head
        eps_gather = b * n_tok * dcfg.patch_size**2 * dcfg.out_channels
        if cfg.attn_impl == "gather":
            state = depth * 2 * b * n_tok * hid
            per_step = depth * 2 * b * n_tok * hid + eps_gather
        elif cfg.attn_impl == "ring":
            state = depth * b * chunk * 2 * hid
            # (n-1) ppermute hops of the local 2C chunk per block, in-step
            per_step = depth * (n - 1) * b * chunk * 2 * hid + eps_gather
        elif cfg.attn_impl == "ulysses":
            state = 0
            # 2 all_to_alls (qkv out + attn back) moving ~the local tokens
            per_step = depth * b * chunk * hid * 4 + eps_gather
        else:  # usp
            u = cfg.ulysses_degree
            r = n // u
            state = 0
            a2a = depth * b * chunk * hid * 4 if u > 1 else 0
            ring_hops = depth * (r - 1) * b * (chunk * u) * 2 * hid // u
            per_step = a2a + ring_hops + eps_gather
        report = {"layout": cfg.attn_impl, "kv_state_elems": int(state),
                  "per_step_collective_elems": int(per_step)}
        # wire bytes: sync steps always move full precision; stale steps
        # move the compressed payload + fp32 scales when comm_compress is
        # on, and only 1/k of the KV rows when refresh_fraction = 1/k
        # (gather layout only — the other layouts reject both knobs).
        # full_refresh_* is the same closed form at fraction 1, so the
        # PCPP reduction is a checked ratio, not a recomputation.
        itemsize = jnp.dtype(cfg.dtype).itemsize
        kk = refresh_period(cfg.refresh_fraction)
        report["comm_compress"] = cfg.comm_compress
        report["refresh_fraction"] = cfg.refresh_fraction
        report["sync_step_collective_bytes"] = int(per_step) * itemsize
        if cfg.attn_impl == "gather":
            full_refresh = depth * n * wire_nbytes(
                (2, b, chunk, hid), itemsize, cfg.comm_compress
            )
            part_refresh = depth * n * wire_nbytes(
                (2, b, chunk // kk, hid), itemsize, cfg.comm_compress
            )
            report["per_step_collective_bytes"] = int(
                part_refresh + eps_gather * itemsize
            )
            report["full_refresh_per_step_collective_bytes"] = int(
                full_refresh + eps_gather * itemsize
            )
        else:
            report["per_step_collective_bytes"] = int(per_step) * itemsize
            report["full_refresh_per_step_collective_bytes"] = (
                int(per_step) * itemsize
            )
        if cfg.step_cache_enabled:
            # shallow steps run only d_keep of depth blocks, so the
            # per-block exchange volume scales down proportionally; the
            # final epsilon gather always runs
            d_keep = depth - cfg.step_cache_depth
            shallow = (per_step - eps_gather) * d_keep // depth + eps_gather
            report["step_cache"] = {
                "interval": cfg.step_cache_interval,
                "depth": cfg.step_cache_depth,
                "shallow_per_step_collective_elems": int(shallow),
            }
        return report

    def generate(self, latents, enc, guidance_scale=5.0, num_inference_steps=20,
                 cap_mask=None, callback=None):
        """Same contract as PipeFusionRunner.generate.  ``cap_mask``
        [n_br, B, Lt] (1 = real caption token) masks padded text tokens out
        of cross-attention (PixArt semantics); None attends to all.
        ``callback(i, t, latents)`` (diffusers legacy signature) fires
        after every step in every mode — from the host loop with
        use_cuda_graph=False, via ordered io_callback inside the compiled
        loop otherwise."""
        self.scheduler.set_timesteps(num_inference_steps)
        gs = np.float32(guidance_scale)
        if cap_mask is None:
            cap_mask = jnp.ones(enc.shape[:3], jnp.float32)
        cap_mask = jnp.asarray(cap_mask, jnp.float32)
        if not self.cfg.use_compiled_step:
            return self._generate_stepwise(
                latents, enc, cap_mask, gs, num_inference_steps, callback,
            )
        if callback is not None:
            if self.cfg.step_cache_enabled:
                # step-cache callbacks take the host loop: the stepwise
                # steppers replay the exact cadence.
                return self._generate_stepwise(
                    latents, enc, cap_mask, gs, num_inference_steps, callback,
                )
            key = ("fused_cb", num_inference_steps)
            if key not in self._compiled:
                self._compiled[key] = self._build_fused_callback(
                    num_inference_steps
                )
            self._active_callback = callback
            try:
                out = self._compiled[key](
                    self.params, jnp.asarray(latents), enc, cap_mask, gs
                )
                jax.effects_barrier()  # host callbacks drain before return
                jax.block_until_ready(out)
                return out
            finally:
                self._active_callback = None
        if self._hybrid_dispatch(num_inference_steps):
            sync, stale = self._ensure_hybrid(num_inference_steps)
            x, sstate, kv = sync(self.params, latents, enc, cap_mask, gs)
            return stale(self.params, x, sstate, kv, enc, cap_mask, gs)
        if num_inference_steps not in self._compiled:
            self._compiled[num_inference_steps] = self._build(num_inference_steps)
        return self._compiled[num_inference_steps](
            self.params, latents, enc, cap_mask, gs
        )

    def _hybrid_dispatch(self, num_steps: int) -> bool:
        cfg = self.cfg
        return (cfg.hybrid_loop and cfg.is_sp and cfg.mode != "full_sync"
                and min(cfg.warmup_steps + 1, num_steps) < num_steps)

    def _ensure_hybrid(self, num_steps: int):
        key = ("hybrid", num_steps)
        if key not in self._compiled:
            self._compiled[key] = self._build_hybrid(num_steps)
        return self._compiled[key]

    def prepare(self, num_steps: int) -> None:
        """Pre-build exactly the program(s) generate() will dispatch to
        (per-step programs build lazily, like the other runners)."""
        if not self.cfg.use_compiled_step:
            return
        self.scheduler.set_timesteps(num_steps)
        if self._hybrid_dispatch(num_steps):
            self._ensure_hybrid(num_steps)
            return
        if num_steps not in self._compiled:
            self._compiled[num_steps] = self._build(num_steps)
