"""Functional replacement for the reference's comm-manager / stale-buffer protocol.

The reference (/root/reference/distrifuser/utils.py:112-199,
`PatchParallelismCommManager`) keeps mutable per-layer flat buffers: each
wrapped module registers a tensor slot, the host allocates one flat buffer per
peer, and modules `enqueue` fresh activations which an async NCCL all-gather
refreshes while the next layers compute; consumers `wait()` their handle one
step later.  JAX is functional, so the same displaced-patch mechanism becomes
*explicit carry state*:

* ``state_in``  — pytree ``{layer_name: gathered buffer}`` produced by the
  previous denoising step (one step stale, exactly like the reference's
  buffers after the async all-gather completes).
* ``state_out`` — dict the ops write their freshly-exchanged activations into
  during the trace; it is returned as the next step's ``state_in``.

Because the exchanged result is only *consumed* by the next compiled step,
XLA's latency-hiding scheduler is free to overlap each collective with the
remaining layers' compute inside the same step — the role NCCL async
all-gather + CUDA-graph capture plays in the reference.  There is no
registration pass: a synchronous (warmup) step simply *returns* the full state
pytree, which seeds the stale steps.  Buffer shape/dtype bookkeeping
(`register_tensor`/`create_buffer`, utils.py:130-164) disappears — pytree
structure is the registry.

Layer identity: the reference keys buffers by registration order; we key by
the module path string (e.g. ``"down_blocks.1.attentions.0.transformer_blocks.
0.attn1"``), which is stable across traces and readable in dumps.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.config import SP_AXIS

# Trace-time registry of state-name -> layer kind ("attn" | "gn" | "conv2d"
# | "stepcache" | "local"), filled by the emitting op itself (the only party
# that KNOWS its kind) so reports never classify by name heuristics.  Populated as a
# Python side effect during tracing; names are unique per architecture, so a
# flat map is safe across models.
KIND_REGISTRY: Dict[str, str] = {}

# Names carried through UNTOUCHED (not freshly exchanged) by the most recent
# carry_unconsumed() trace — how comm_volume_report distinguishes a shallow
# step's fresh refresh traffic from the deep state it merely passes along.
# Same trace-time side-effect convention as KIND_REGISTRY; callers that need
# it clear it before tracing one step.
CARRIED_REGISTRY: set = set()

# Trace-time wire accounting: state-name -> bytes the emitting exchange put
# on the wire (per device, gathered-buffer convention — the byte analog of
# the element counts comm_volume_report derives from the carry shapes).
# Only EXCEPTIONS register here: compressed refresh payloads (int8/fp8 +
# fp32 scales, parallel/compress.py) and wire-free local carries (own-rows
# residual seeds).  Entries absent from the registry default to the carried
# buffer's full elements x itemsize.  Cleared per trace, like
# CARRIED_REGISTRY.
WIRE_REGISTRY: Dict[str, int] = {}

# Suffix for the sender-side own-boundary-rows carry that "int8_residual"
# halos delta-code against: the receiver's stale halos hold the NEIGHBORS'
# previous rows, so the sender must carry its own (wire-free, kind "local").
OWN_SUFFIX = "#own"

# Static phases of the denoising loop. ``SYNC`` is the warmup / full_sync
# path (all collectives blocking-fresh, reference counter <= warmup_steps,
# e.g. pp/conv2d.py:92); ``STALE`` is the displaced-patch steady state.
PHASE_SYNC = "sync"
PHASE_STALE = "stale"


@dataclasses.dataclass
class PatchContext:
    """Per-trace context threaded through every patch-parallel op.

    Mirrors what the reference's `BaseModule` reads from `DistriConfig` +
    `PatchParallelismCommManager` (modules/base_module.py:6-29): the peer
    count, the sync mode, whether we are in warmup, and the stale buffers.
    """

    n: int  # devices on the patch axis (n_device_per_batch)
    mode: str  # one of SYNC_MODES
    phase: str  # PHASE_SYNC | PHASE_STALE (static per compilation)
    axis: str = SP_AXIS
    attn_impl: str = "gather"  # "gather" | "ring" (ops/ring_attention.py)
    # Batch the stale-phase refresh collectives: defer every layer's fresh
    # halo/KV/moment emission and run ONE flat ppermute pair + one all-gather
    # per dtype at the end of the step (`flush()`), instead of ~60 small
    # per-layer collectives.  The functional analog of the reference's
    # `comm_checkpoint` buffer batching (utils.py:181-190).  Trade-off: fewer
    # collective launches on ICI vs a narrower overlap window (the batched
    # exchange can only start once the last layer has produced its rows).
    batch_comm: bool = False
    # Stale-refresh payload compression (parallel/compress.py): "none",
    # "int8", "fp8", or "int8_residual".  Applies ONLY to the refresh
    # emissions below — sync-phase exchanges (ctx.emit paths) stay
    # full-precision and bit-exact.
    compress: str = "none"
    # PCPP partial refresh (arXiv 2412.02962; DistriConfig.refresh_fraction):
    # with fraction 1/k, each stale step refreshes only rows {r, r+k, ...}
    # (r = step % k) of every refreshable payload — KV token rows on the
    # gather path, halo columns on the conv path — and the rest of the
    # carried buffer stays as-is, so per-step refresh bytes are exactly
    # fraction x full and every row is at most k steps stale.  Applies to
    # the same kinds compression does (attn/conv2d — GroupNorm moments are
    # cancellation-sensitive and tiny, so they always refresh whole); sync
    # exchanges always move everything.  ``step`` is the traced absolute
    # step index driving the rotation (required when fraction < 1).
    refresh_fraction: float = 1.0
    step: Any = None
    state_in: Optional[Dict[str, Any]] = None
    state_out: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # deferred refresh emissions (batch_comm): name -> record dict with
    # either {"raw": <tensor(s)>} or the quantized parts
    # {"q": ..., "s": ..., "prev": ..., "dtype": ...}
    _def_gather: Dict[str, Any] = dataclasses.field(default_factory=dict)
    _def_halo: Dict[str, Tuple[Any, Any]] = dataclasses.field(default_factory=dict)
    # Precomputed text-encoder KV per cross-attention layer. The reference
    # caches these at counter==0 (modules/pp/attn.py:56,73-77); we compute
    # them once before the denoise loop.
    text_kv: Optional[Dict[str, Any]] = None

    @property
    def is_sync(self) -> bool:
        """Blocking-fresh collectives? (reference: mode=='full_sync' or warmup)."""
        return self.phase == PHASE_SYNC or self.mode == "full_sync"

    @property
    def refresh(self) -> bool:
        """Should ops exchange fresh activations for the next step?

        False only for ``no_sync`` steady state (reference pp/conv2d.py:111,
        pp/attn.py:139: enqueue skipped), where buffers stay warmup-stale
        forever.
        """
        return not (self.phase == PHASE_STALE and self.mode == "no_sync")

    def split_idx(self):
        """This device's patch index along the sp axis (traced)."""
        return jax.lax.axis_index(self.axis)

    def stale(self, name: str):
        buf = None if self.state_in is None else self.state_in.get(name)
        if buf is None:
            raise KeyError(
                f"no stale buffer for layer {name!r}: stale-phase steps must be "
                f"seeded by a sync-phase step's returned state"
            )
        return buf

    def emit(self, name: str, value: Any, kind: str = None) -> None:
        if name in self.state_out:
            raise ValueError(f"duplicate state emission for layer {name!r}")
        if kind is not None:
            KIND_REGISTRY[name] = kind
        self.state_out[name] = value

    # ------------------------------------------------------------------
    # refresh emissions (stale phase): immediate or deferred-batched
    # ------------------------------------------------------------------

    def _compress_for(self, kind: Optional[str]) -> Optional[str]:
        """Active compression mode for a refresh emission of this kind, or
        None when the payload goes out full-precision."""
        from .compress import COMPRESS_KINDS

        if self.compress == "none" or kind not in COMPRESS_KINDS:
            return None
        return self.compress

    def _partial_for(self, kind: Optional[str]):
        """Partial-refresh (period, rotation-index) for a refresh emission
        of this kind, or None for a full refresh.  Eligibility tracks
        COMPRESS_KINDS — the same payloads that tolerate lossy wires
        tolerate a strided refresh; GroupNorm moments do neither."""
        from .compress import COMPRESS_KINDS, refresh_period

        k = refresh_period(self.refresh_fraction)
        if k <= 1 or kind not in COMPRESS_KINDS:
            return None
        if self.step is None:
            raise ValueError(
                "partial refresh (refresh_fraction < 1) needs the traced "
                "step index on PatchContext.step for the rotation schedule"
            )
        return k, jnp.mod(jnp.asarray(self.step, jnp.int32), k)

    def emit_refresh_gather(self, name: str, local: Any, kind: str = None) -> None:
        """Record `local` as this layer's next-step gathered state
        ([n, *local.shape] after the all-gather) — immediately, or deferred
        into the step-end batched exchange under ``batch_comm``.  With
        ``compress`` active for this kind, the wire carries an int8/fp8
        payload plus per-tile fp32 scales instead of the raw tensor
        (residual mode delta-codes against this device's own slot of the
        stale buffer); the emitted carry value is the dequantized
        full-precision gather either way, so the carry pytree structure is
        mode-independent."""
        if kind is not None:
            KIND_REGISTRY[name] = kind
        mode = self._compress_for(kind or KIND_REGISTRY.get(name))
        if self.batch_comm:
            # DistriConfig rejects batch_comm x refresh_fraction < 1, so
            # the deferred records never carry a partial subset
            if name in self._def_gather or name in self.state_out:
                raise ValueError(f"duplicate state emission for layer {name!r}")
            self._def_gather[name] = self._gather_record(name, local, mode)
            return
        partial = self._partial_for(kind or KIND_REGISTRY.get(name))
        if partial is not None:
            self._partial_refresh_gather(name, local, mode, partial)
            return
        if mode is None:
            self.emit(name, lax.all_gather(local, self.axis))
            return
        from .compress import dequantize

        rec = self._gather_record(name, local, mode)
        gq = lax.all_gather(rec["q"], self.axis)
        gs = lax.all_gather(rec["s"], self.axis)
        new = dequantize(gq, gs, jnp.float32)
        if rec["prev"] is not None:
            new = rec["prev"].astype(jnp.float32) + new
        self.emit(name, new.astype(rec["dtype"]))

    def _partial_refresh_gather(self, name: str, local: Any,
                                mode: Optional[str], partial) -> None:
        """PCPP gather refresh: all-gather only this step's strided row
        group (``local`` rows {r, r+k, ...}) and scatter it into the
        carried gathered buffer — the other rows stay as the previous
        reconstruction, at most k steps stale.  Composes with the
        compression modes exactly like the full path; residual mode
        delta-codes each row against its own k-step-old slot, which every
        peer holds identically (closed-loop at stride k)."""
        from .compress import (
            dequantize,
            quantize,
            scatter_every_kth,
            take_every_kth,
            wire_nbytes,
        )

        k, r = partial
        prev = self.stale(name)  # [n, B, L, C] gathered carry
        sub = take_every_kth(local, k, r)
        itemsize = jnp.dtype(local.dtype).itemsize
        WIRE_REGISTRY[name] = self.n * wire_nbytes(
            sub.shape, itemsize, mode or "none"
        )
        if mode is None:
            g = lax.all_gather(sub, self.axis)  # [n, B, L/k, C]
            self.emit(name, scatter_every_kth(prev, g, k, r))
            return
        src = sub.astype(jnp.float32)
        if mode == "int8_residual":
            own = jnp.take(prev, self.split_idx(), axis=0)
            src = src - take_every_kth(own, k, r).astype(jnp.float32)
        q, s = quantize(src, mode)
        gq = lax.all_gather(q, self.axis)
        gs = lax.all_gather(s, self.axis)
        new = dequantize(gq, gs, jnp.float32)
        if mode == "int8_residual":
            new = take_every_kth(prev, k, r).astype(jnp.float32) + new
        self.emit(
            name, scatter_every_kth(prev, new.astype(local.dtype), k, r)
        )

    def _gather_record(self, name: str, local: Any, mode: Optional[str]):
        """Build the deferred-emission record for one gather refresh and
        register its wire bytes (gathered-buffer convention: n x the local
        payload, matching the element counts)."""
        from .compress import quantize, wire_nbytes

        itemsize = jnp.dtype(local.dtype).itemsize
        WIRE_REGISTRY[name] = self.n * wire_nbytes(
            local.shape, itemsize, mode or "none"
        )
        if mode is None:
            return {"raw": local}
        src = local.astype(jnp.float32)
        prev = None
        if mode == "int8_residual":
            # delta against this device's own previous emission — its slot
            # in the stale gathered buffer (identical content on every peer,
            # so the reconstruction below is replicated-consistent)
            prev = self.stale(name)
            src = src - jnp.take(prev, self.split_idx(), axis=0).astype(
                jnp.float32
            )
        q, s = quantize(src, mode)
        return {"q": q, "s": s, "prev": prev, "dtype": local.dtype}

    def emit_refresh_halos(self, name: str, x: Any, halo: int) -> None:
        """Record the fresh boundary rows of ``x`` [B, h, W, C] as this
        layer's next-step halo state [2, B, halo, W, C] (stacked
        from-prev/from-next, matching the sync-phase emission via
        ``emit_sync_halos``).  With ``compress`` active the neighbor
        permutes move int8/fp8 rows + fp32 scales; residual mode
        delta-codes against the sender's own previous rows (the
        ``OWN_SUFFIX`` carry this method also refreshes)."""
        KIND_REGISTRY[name] = "conv2d"
        mode = self._compress_for("conv2d")
        partial = self._partial_for("conv2d")
        if halo == 0 or self.n == 1:
            mode = None  # nothing real moves; keep the zero-halo semantics
            partial = None
        top, bottom = x[:, :halo], x[:, x.shape[1] - halo :]
        if self.batch_comm:
            if name in self._def_halo or name in self.state_out:
                raise ValueError(f"duplicate state emission for layer {name!r}")
            # halo == 0 defers zero rows, the same empty halos halo_exchange
            # returns on the unbatched path
            self._def_halo[name] = self._halo_record(name, top, bottom, mode)
            return
        if partial is not None:
            self._partial_refresh_halos(name, top, bottom, mode, partial)
            return
        if mode is None:
            from .collectives import halo_exchange

            t, b = halo_exchange(x, halo, self.n, self.axis)
            self.emit(name, jnp.stack([t, b]))
            return
        from .collectives import exchange_boundary_rows
        from .compress import dequantize

        rec = self._halo_record(name, top, bottom, mode)
        q_prev, q_next = exchange_boundary_rows(
            rec["q"][1], rec["q"][0], self.n, self.axis
        )
        s_prev, s_next = exchange_boundary_rows(
            rec["s"][1], rec["s"][0], self.n, self.axis
        )
        from_prev = dequantize(q_prev, s_prev, jnp.float32)
        from_next = dequantize(q_next, s_next, jnp.float32)
        if rec["prev"] is not None:
            from_prev = rec["prev"][0].astype(jnp.float32) + from_prev
            from_next = rec["prev"][1].astype(jnp.float32) + from_next
        self.emit(
            name, jnp.stack([from_prev, from_next]).astype(rec["dtype"])
        )

    def _halo_record(self, name: str, top: Any, bottom: Any,
                     mode: Optional[str]):
        """Deferred-emission record for one halo refresh + wire accounting
        (both boundary rows move).  In residual mode this also refreshes
        the own-rows predictor carry — with the RECONSTRUCTION (previous
        own + dequantized delta), never the raw rows: the predictor must
        equal the base each receiver accumulates onto, or the coding goes
        open-loop and quantization error grows with step count instead of
        cancelling (the closed-loop DPCM invariant; the gather path gets
        the same property from delta-coding against the stale buffer)."""
        from .compress import dequantize, quantize, wire_nbytes

        itemsize = jnp.dtype(top.dtype).itemsize
        WIRE_REGISTRY[name] = 2 * wire_nbytes(
            top.shape, itemsize, mode or "none"
        )
        if mode is None:
            return {"raw": (top, bottom)}
        t, b = top.astype(jnp.float32), bottom.astype(jnp.float32)
        prev = None
        if mode == "int8_residual":
            own = self.stale(name + OWN_SUFFIX)  # my previous [top, bottom]
            t = t - own[0].astype(jnp.float32)
            b = b - own[1].astype(jnp.float32)
            prev = self.stale(name)  # receiver-side base [from_prev, from_next]
        qt, st = quantize(t, mode)
        qb, sb = quantize(b, mode)
        if mode == "int8_residual":
            self._emit_own_halos(
                name,
                (own[0].astype(jnp.float32)
                 + dequantize(qt, st, jnp.float32)).astype(top.dtype),
                (own[1].astype(jnp.float32)
                 + dequantize(qb, sb, jnp.float32)).astype(top.dtype),
            )
        return {"q": (qt, qb), "s": (st, sb), "prev": prev,
                "dtype": top.dtype}

    def _partial_refresh_halos(self, name: str, top: Any, bottom: Any,
                               mode: Optional[str], partial) -> None:
        """PCPP halo refresh: exchange only this step's strided COLUMN
        group of the boundary rows (axis -2 of the [B, halo, W, C] layout
        is W) and scatter it into the carried halo state; the other
        columns keep their previous reconstruction, at most k steps
        stale.  Residual mode keeps the own-rows predictor carry in
        lockstep by scattering the same reconstructed subset into it."""
        from .collectives import exchange_boundary_rows
        from .compress import (
            dequantize,
            quantize,
            scatter_every_kth,
            take_every_kth,
            wire_nbytes,
        )

        k, r = partial
        prev = self.stale(name)  # [2, B, halo, W, C] from-prev/from-next
        sub_t = take_every_kth(top, k, r)
        sub_b = take_every_kth(bottom, k, r)
        itemsize = jnp.dtype(top.dtype).itemsize
        WIRE_REGISTRY[name] = 2 * wire_nbytes(
            sub_t.shape, itemsize, mode or "none"
        )
        if mode is None:
            from_prev, from_next = exchange_boundary_rows(
                sub_b, sub_t, self.n, self.axis
            )
            self.emit(name, jnp.stack([
                scatter_every_kth(prev[0], from_prev, k, r),
                scatter_every_kth(prev[1], from_next, k, r),
            ]))
            return
        t = sub_t.astype(jnp.float32)
        b = sub_b.astype(jnp.float32)
        own = None
        if mode == "int8_residual":
            own = self.stale(name + OWN_SUFFIX)  # my previous [top, bottom]
            t = t - take_every_kth(own[0], k, r).astype(jnp.float32)
            b = b - take_every_kth(own[1], k, r).astype(jnp.float32)
        qt, st = quantize(t, mode)
        qb, sb = quantize(b, mode)
        if mode == "int8_residual":
            # own-rows predictor: scatter the RECONSTRUCTED subset (prev
            # own + dequantized delta) so sender and receivers keep the
            # identical base — the closed-loop invariant at stride k
            rec_t = (take_every_kth(own[0], k, r).astype(jnp.float32)
                     + dequantize(qt, st, jnp.float32))
            rec_b = (take_every_kth(own[1], k, r).astype(jnp.float32)
                     + dequantize(qb, sb, jnp.float32))
            self._emit_own_halos(
                name,
                scatter_every_kth(own[0], rec_t.astype(top.dtype), k, r),
                scatter_every_kth(own[1], rec_b.astype(top.dtype), k, r),
            )
        q_prev, q_next = exchange_boundary_rows(qb, qt, self.n, self.axis)
        s_prev, s_next = exchange_boundary_rows(sb, st, self.n, self.axis)
        from_prev = dequantize(q_prev, s_prev, jnp.float32)
        from_next = dequantize(q_next, s_next, jnp.float32)
        if mode == "int8_residual":
            from_prev = (take_every_kth(prev[0], k, r).astype(jnp.float32)
                         + from_prev)
            from_next = (take_every_kth(prev[1], k, r).astype(jnp.float32)
                         + from_next)
        self.emit(name, jnp.stack([
            scatter_every_kth(prev[0], from_prev.astype(top.dtype), k, r),
            scatter_every_kth(prev[1], from_next.astype(top.dtype), k, r),
        ]))

    def _emit_own_halos(self, name: str, top: Any, bottom: Any) -> None:
        """Refresh the sender-side own-rows predictor carry for residual
        halo coding.  Wire-free (kind "local", 0 registered bytes); no-op
        outside ``int8_residual``.  Stale steps pass the RECONSTRUCTED rows
        (see ``_halo_record``); the sync seed is the exact fresh rows,
        which equal what receivers hold after an exact exchange."""
        if self.compress != "int8_residual":
            return
        own = name + OWN_SUFFIX
        KIND_REGISTRY[own] = "local"
        WIRE_REGISTRY[own] = 0
        self.emit(own, jnp.stack([top, bottom]))

    def emit_sync_halos(self, name: str, x: Any, halo: int):
        """Sync-phase halo exchange + emission (ops/conv.py's warmup path):
        exchanges FRESH halos (blocking, full-precision — the reference
        warmup all_gather), emits them as the stale phase's seed state, and
        in residual mode also seeds the own-rows carry the stale deltas
        code against.  Returns ``(from_prev, from_next)`` for the conv."""
        from .collectives import halo_exchange

        top, bottom = halo_exchange(x, halo, self.n, self.axis)
        self.emit(name, jnp.stack([top, bottom]), kind="conv2d")
        if self._compress_for("conv2d") is not None and halo and self.n > 1:
            self._emit_own_halos(name, x[:, :halo], x[:, x.shape[1] - halo:])
        return top, bottom

    def carry_unconsumed(self) -> None:
        """Pass every ``state_in`` entry this step did not re-emit through to
        ``state_out`` unchanged.

        The temporal step-cache (parallel/stepcache.py) skips whole layers on
        shallow steps, so their displaced buffers — and the deep-feature
        cache itself — must ride the carry untouched to keep the pytree
        structure identical across the full/shallow pair of loop bodies (a
        lax.scan carry cannot change structure).  Also covers full steps in
        ``no_sync`` mode, where no layer refreshes but the step-cache entry
        still does.  Call after ``flush()``; records the carried names in
        ``CARRIED_REGISTRY`` for the comm report."""
        assert not self._def_gather and not self._def_halo, (
            "carry_unconsumed must run after flush()"
        )
        if self.state_in is None:
            return
        for name, value in self.state_in.items():
            if name not in self.state_out:
                self.state_out[name] = value
                CARRIED_REGISTRY.add(name)

    def flush(self) -> None:
        """Run the batched refresh exchanges deferred by ``batch_comm``.

        One `lax.all_gather` per participating dtype carries every layer's
        flattened KV/moment tensor; one non-wrapping `lax.ppermute` pair
        carries every conv's boundary rows.  Compressed layers contribute
        their int8/fp8 payload to the payload-dtype batch and their fp32
        scales to the fp32 batch (scales share a flat gather with any raw
        fp32 traffic), and dequantize after the split.  Results match the
        per-layer shapes and values the unbatched path would have produced,
        so the carry pytree is identical either way.  No-op when nothing
        was deferred.
        """
        from .compress import dequantize

        if self._def_gather:
            parts = []  # (name, part key, tensor)
            for name, rec in self._def_gather.items():
                if "raw" in rec:
                    parts.append((name, "raw", rec["raw"]))
                else:
                    parts.append((name, "q", rec["q"]))
                    parts.append((name, "s", rec["s"]))
            gathered = self._batched_gather(parts)
            for name, rec in self._def_gather.items():
                if "raw" in rec:
                    self.state_out[name] = gathered[(name, "raw")]
                    continue
                new = dequantize(
                    gathered[(name, "q")], gathered[(name, "s")], jnp.float32
                )
                if rec["prev"] is not None:
                    new = rec["prev"].astype(jnp.float32) + new
                self.state_out[name] = new.astype(rec["dtype"])
            self._def_gather.clear()
        if self._def_halo:
            parts = []  # (name, part key, (top, bottom))
            for name, rec in self._def_halo.items():
                if "raw" in rec:
                    parts.append((name, "raw", rec["raw"]))
                else:
                    parts.append((name, "q", rec["q"]))
                    parts.append((name, "s", rec["s"]))
            exchanged = self._batched_halo_exchange(parts)
            for name, rec in self._def_halo.items():
                if "raw" in rec:
                    self.state_out[name] = jnp.stack(exchanged[(name, "raw")])
                    continue
                q_prev, q_next = exchanged[(name, "q")]
                s_prev, s_next = exchanged[(name, "s")]
                from_prev = dequantize(q_prev, s_prev, jnp.float32)
                from_next = dequantize(q_next, s_next, jnp.float32)
                if rec["prev"] is not None:
                    from_prev = rec["prev"][0].astype(jnp.float32) + from_prev
                    from_next = rec["prev"][1].astype(jnp.float32) + from_next
                self.state_out[name] = jnp.stack(
                    [from_prev, from_next]
                ).astype(rec["dtype"])
            self._def_halo.clear()

    @jax.named_scope("stale_gather")
    def _batched_gather(self, parts) -> Dict[Tuple[str, str], Any]:
        """One flat all_gather per dtype over ``(name, part, tensor)``
        entries; returns {(name, part): [n, *tensor.shape]}."""
        by_dtype: Dict[Any, list] = {}
        for name, part, t in parts:
            by_dtype.setdefault(jnp.dtype(t.dtype), []).append((name, part, t))
        out: Dict[Tuple[str, str], Any] = {}
        for items in by_dtype.values():
            flat = jnp.concatenate([t.reshape(-1) for _, _, t in items])
            gathered = lax.all_gather(flat, self.axis)  # [n, total]
            off = 0
            for name, part, t in items:
                out[(name, part)] = gathered[:, off : off + t.size].reshape(
                    (gathered.shape[0],) + t.shape
                )
                off += t.size
        return out

    def _batched_halo_exchange(self, parts) -> Dict[Tuple[str, str], Any]:
        """One flat non-wrapping ppermute pair per dtype over
        ``(name, part, (top, bottom))`` entries; returns
        {(name, part): (from_prev, from_next)}.  My bottom rows become the
        next device's from-prev halo; my top rows the previous device's
        from-next halo."""
        from .collectives import exchange_boundary_rows

        by_dtype: Dict[Any, list] = {}
        for name, part, (top, bottom) in parts:
            by_dtype.setdefault(jnp.dtype(top.dtype), []).append(
                (name, part, top, bottom)
            )
        out: Dict[Tuple[str, str], Any] = {}
        for items in by_dtype.values():
            bottoms = jnp.concatenate([b.reshape(-1) for _, _, _, b in items])
            tops = jnp.concatenate([t.reshape(-1) for _, _, t, _ in items])
            from_prev, from_next = exchange_boundary_rows(
                bottoms, tops, self.n, self.axis
            )
            off = 0
            for name, part, top, _ in items:
                size, shape = top.size, top.shape
                out[(name, part)] = (
                    from_prev[off : off + size].reshape(shape),
                    from_next[off : off + size].reshape(shape),
                )
                off += size
        return out
