"""The compiled denoising loop: displaced patch parallelism as one XLA program.

This is the TPU-native replacement for the reference's hot path
(SURVEY.md §3.3): where the reference replays three CUDA graphs per
counter phase (pipelines.py:147-165, distri_sdxl_unet_pp.py:74-116) around a
replicated diffusers scheduler loop, here the *entire* generation — warmup
steps, stale steps, CFG combination, scheduler — is a single `jax.jit`
program over the ("dp", "cfg", "sp") mesh:

* step 0 runs the synchronous path and *creates* the stale-activation state
  pytree (the reference needs two recording passes + buffer allocation,
  pipelines.py:131-145; here the state is just the step's return value);
* steps 1..warmup run the sync path in `lax.fori_loop` (reference: counter <=
  warmup_steps selects sync everywhere, §2.3);
* the remaining steps run the displaced path in `lax.scan`, carrying
  (latents, patch-state, scheduler-state).  Each step's refresh collectives
  produce values consumed only by the *next* iteration, so XLA's latency-
  hiding scheduler overlaps them with compute — the role of the reference's
  async NCCL all-gathers (utils.py:170-190);
* every device computes the full gathered output and runs the scheduler
  replicated, matching the reference contract (distri_sdxl_unet_pp.py:162-169).

`use_compiled_step=False` (the reference's --no_cuda_graph) swaps the single
fused program for per-step jitted calls driven from Python — same numerics,
visible per-step latency.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ..models.unet import (
    DenseDispatch,
    PatchDispatch,
    UNetConfig,
    precompute_text_kv,
    unet_forward,
)
from ..schedulers import BaseScheduler
from ..utils.config import CFG_AXIS, DP_AXIS, SP_AXIS, DistriConfig
from .collectives import gather_cols, gather_rows
from .context import (
    CARRIED_REGISTRY,
    KIND_REGISTRY,
    PHASE_STALE,
    PHASE_SYNC,
    WIRE_REGISTRY,
    PatchContext,
)
from .guidance import branch_select, combine_guidance
from .stepcache import STEPCACHE_KEY, is_shallow_at, run_cadence


class _AotProgramHandle:
    """Lazily compiled-OR-deserialized wrapper around one jitted program.

    `compiled_handle` returns an uncompiled `jax.jit` callable — XLA
    compilation happens at the first dispatch, when concrete argument
    shapes exist.  When a persistent AOT store was active for the build
    (`utils.aot.aot_activation`, installed by the serve layer's
    `ExecutorCache` around every executor build), this wrapper captures
    the (store, scope) pair at build time and intercepts that first
    dispatch: it fingerprints the program as
    ``scope | tag | abstract-value signature`` plus mesh shape and
    donation layout, loads a persisted executable when one matches
    (milliseconds), and otherwise compiles via ``lower().compile()`` and
    persists the result for the next replica.  A loaded executable IS
    the serialized compile — same XLA program, bit-identical outputs.

    Any failure in the AOT path (an executable the runtime refuses to
    serialize, an exotic call signature) falls back PERMANENTLY to the
    plain jitted callable — the store is an accelerator, never a
    correctness dependency.  Attribute access (``lower`` for
    `compiled_hlo`, etc.) delegates to the wrapped jit handle.
    """

    def __init__(self, fn, *, store, scope: str, tag: str, mesh,
                 layout: str):
        self._fn = fn
        self._store = store
        self._scope = scope
        self._tag = tag
        self._mesh_shape = str(dict(mesh.shape))
        # the program's own devices, in mesh order: a persisted
        # executable must load onto exactly these
        self._devices = list(mesh.devices.flat)
        self._layout = layout
        self._executables: Dict[str, Any] = {}
        self._fallback = False

    def _signature(self, args) -> str:
        parts = []
        for leaf in jax.tree_util.tree_leaves(args):
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is None or dtype is None:
                parts.append(f"py.{type(leaf).__name__}")
            else:
                parts.append(f"{np.dtype(dtype).name}{tuple(shape)}")
        import hashlib

        return hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]

    def _acquire(self, sig: str, args):
        fp = self._store.fingerprint(
            f"{self._scope}|{self._tag}|{sig}",
            mesh_shape=self._mesh_shape, layout=self._layout)
        ex = self._store.load_executable(fp, self._devices)
        if ex is None:
            ex = self._fn.lower(*args).compile()
            self._store.save_executable(fp, ex)
        return ex

    def __call__(self, *args):
        if self._fallback:
            return self._fn(*args)
        sig = self._signature(args)
        ex = self._executables.get(sig)
        if ex is None:
            try:
                ex = self._acquire(sig, args)
            except Exception:
                # the jit path is always correct; the store only ever
                # saves time.  One bad interaction disables it for this
                # handle rather than risking a dispatch loop of retries.
                self._fallback = True
                return self._fn(*args)
            self._executables[sig] = ex
        return ex(*args)

    def __getattr__(self, name):
        return getattr(self._fn, name)


def _check_geometry(cfg: DistriConfig, ucfg: UNetConfig) -> None:
    if not cfg.is_sp:
        return
    depth = len(ucfg.block_out_channels) - 1  # number of downsamples
    n = cfg.n_device_per_batch
    h = cfg.latent_height
    if cfg.parallelism == "patch" or cfg.split_scheme in ("row", "alternate"):
        if h % (n * (1 << depth)) != 0:
            raise ValueError(
                f"latent height {h} must be divisible by n_devices*2^depth = "
                f"{n * (1 << depth)} for row patching"
            )
    if cfg.parallelism == "naive_patch" and cfg.split_scheme in ("col", "alternate"):
        w = cfg.latent_width
        if w % (n * (1 << depth)) != 0:
            raise ValueError(
                f"latent width {w} must be divisible by n_devices*2^depth = "
                f"{n * (1 << depth)} for column patching"
            )


class DenoiseRunner:
    """Builds and runs the compiled generation loop for one (config, model).

    Functional analog of the reference's model wrappers + pipeline prepare():
    `DistriUNetPP` / `NaivePatchUNet` behavior is selected by
    ``distri_config.parallelism`` ("patch" | "naive_patch"); tensor
    parallelism has its own dispatch (models/unet_tp.py) wired through
    ``tp_dispatch_factory``.
    """

    def __init__(
        self,
        distri_config: DistriConfig,
        unet_config: UNetConfig,
        params,
        scheduler: BaseScheduler,
        tp_dispatch_factory=None,
        param_specs=None,
    ):
        self.cfg = distri_config
        self.ucfg = unet_config
        self.scheduler = scheduler
        self.tp_dispatch_factory = tp_dispatch_factory
        # Weight sharding layout: P() (replicated) for patch/naive modes —
        # the reference also replicates weights in PP mode (§2.1) — and the
        # per-leaf TP spec tree for tensor parallelism.
        self.param_specs = param_specs if param_specs is not None else P()
        self.params = distri_config.place(params, self.param_specs)
        if distri_config.parallelism == "tensor" and tp_dispatch_factory is None:
            raise ValueError("tensor parallelism needs a tp_dispatch_factory")
        if distri_config.parallelism == "pipefusion":
            raise ValueError(
                "pipefusion is a DiT strategy (parallel/pipefusion.py); the "
                "UNet's heterogeneous stages cannot pipeline — use "
                "parallelism='patch' here"
            )
        if distri_config.attn_impl in ("ulysses", "usp"):
            raise ValueError(
                f"attn_impl={distri_config.attn_impl!r} is a DiT strategy "
                "(parallel/dit_sp.py): head counts vary per UNet level, so "
                "the all-to-all head shard does not apply — use 'gather' or "
                "'ring' here"
            )
        n_levels = len(unet_config.block_out_channels)
        if distri_config.step_cache_enabled and not (
            1 <= distri_config.step_cache_depth < n_levels
        ):
            raise ValueError(
                f"step_cache_depth={distri_config.step_cache_depth} must be "
                f"in [1, {n_levels - 1}] for this {n_levels}-level UNet "
                "(at least one level must stay shallow)"
            )
        _check_geometry(distri_config, unet_config)
        self._compiled: Dict[Any, Any] = {}
        self._builds = 0  # fused-loop builds (cache_info observability)
        # fused-mode per-step callback target (_build_fused_callback): the
        # compiled program's io_callback reads this indirection so one
        # program serves any callback object
        self._active_callback = None

    # ------------------------------------------------------------------
    # per-device pieces (run inside shard_map)
    # ------------------------------------------------------------------

    def _branch_inputs(self, enc, added):
        """Select this device's CFG branch (cfg_split) or fold branches into
        the batch dim (single-device CFG, reference world_size==1 path)."""
        return branch_select(self.cfg, enc, added)

    def _unet_local(self, params, x_in, t, my_enc, my_added, text_kv, phase,
                    pstate, shallow=False, step=None):
        """One UNet evaluation on this device; returns (full-latent output
        for this branch-batch, new patch state).  ``shallow`` (step-cache
        cadence) skips the deep subtree and substitutes the carried deep
        feature; a non-shallow call with the cache enabled re-emits it.
        ``step`` is the traced absolute step index — the PCPP partial-
        refresh rotation schedule reads it off the context."""
        cfg, ucfg = self.cfg, self.ucfg
        if cfg.parallelism == "patch":
            ctx = PatchContext(
                n=cfg.n_device_per_batch,
                mode=cfg.mode,
                phase=phase,
                attn_impl=cfg.attn_impl,
                batch_comm=cfg.comm_batch,
                compress=cfg.comm_compress,
                refresh_fraction=cfg.refresh_fraction,
                step=step,
                state_in=pstate,
                text_kv=text_kv,
            )
            cd = cfg.step_cache_depth if cfg.step_cache_enabled else 0
            if cd:
                out_local, deep = unet_forward(
                    params, ucfg, x_in, t, my_enc,
                    dispatch=PatchDispatch(ctx), added_cond=my_added,
                    cache_depth=cd,
                    deep_cache=ctx.stale(STEPCACHE_KEY) if shallow else None,
                )
                if deep is not None:  # full step: refresh the temporal cache
                    ctx.emit(STEPCACHE_KEY, deep, kind="stepcache")
            else:
                out_local = unet_forward(
                    params, ucfg, x_in, t, my_enc,
                    dispatch=PatchDispatch(ctx), added_cond=my_added,
                )
            ctx.flush()  # batched refresh exchange (no-op unless comm_batch)
            if cd:
                # skipped layers' buffers (and, on shallow steps, the deep
                # cache) ride the carry untouched: the full/shallow bodies
                # must return one pytree structure
                ctx.carry_unconsumed()
            out = gather_rows(out_local) if cfg.is_sp else out_local
            new_state = ctx.state_out if ctx.state_out else pstate
            return out, new_state
        if cfg.parallelism == "naive_patch":
            return self._naive_patch_unet(params, x_in, t, my_enc, my_added, text_kv, pstate)
        # tensor parallelism: activations stay full-size, no patch state
        d = self.tp_dispatch_factory(text_kv)
        out = unet_forward(
            params, ucfg, x_in, t, my_enc, dispatch=d, added_cond=my_added
        )
        return out, pstate

    def _naive_patch_unet(self, params, x_in, t, my_enc, my_added, text_kv, step_or_state):
        """Naive patch parallelism (models/naive_patch_sdxl.py): slice the
        latent, run the *unmodified* UNet on the slice, gather.  No cross-
        patch ops, no state; `alternate` flips row/col by step parity
        (naive_patch_sdxl.py:157-174)."""
        cfg = self.cfg
        n = cfg.n_device_per_batch
        d = DenseDispatch(text_kv=text_kv)
        idx = lax.axis_index(SP_AXIS)

        def run_rows(x):
            h_loc = x.shape[1] // n
            xs = lax.dynamic_slice_in_dim(x, idx * h_loc, h_loc, axis=1)
            y = unet_forward(params, self.ucfg, xs, t, my_enc, dispatch=d,
                             added_cond=my_added)
            return gather_rows(y)

        def run_cols(x):
            w_loc = x.shape[2] // n
            xs = lax.dynamic_slice_in_dim(x, idx * w_loc, w_loc, axis=2)
            y = unet_forward(params, self.ucfg, xs, t, my_enc, dispatch=d,
                             added_cond=my_added)
            return gather_cols(y)

        if not cfg.is_sp:
            out = unet_forward(params, self.ucfg, x_in, t, my_enc, dispatch=d,
                               added_cond=my_added)
        elif cfg.split_scheme == "row":
            out = run_rows(x_in)
        elif cfg.split_scheme == "col":
            out = run_cols(x_in)
        else:  # alternate
            step_idx = step_or_state["step"]
            out = lax.cond(step_idx % 2 == 0, run_rows, run_cols, x_in)
        return out, step_or_state

    def _cfg_combine(self, out, gs, batch):
        return combine_guidance(self.cfg, out, gs, batch)

    def _make_step(self, phase, shallow=False):
        sched = self.scheduler

        def step(params, i, x, pstate, sstate, my_enc, my_added, text_kv, gs):
            cfg = self.cfg
            batch = x.shape[0]
            t = sched.timesteps()[i]
            x_in = sched.scale_model_input(x, i)
            if not cfg.cfg_split and cfg.do_classifier_free_guidance:
                x_in = jnp.concatenate([x_in, x_in], axis=0)
                if jnp.ndim(t):
                    # per-row step indices (packed cohort dispatch): the
                    # timestep vector folds branch-major exactly like x_in
                    t = jnp.concatenate([t, t], axis=0)
            if cfg.parallelism == "naive_patch" and cfg.split_scheme == "alternate":
                pstate = {"step": i}
            out, new_pstate = self._unet_local(
                params, x_in, t, my_enc, my_added, text_kv, phase, pstate,
                shallow=shallow, step=i,
            )
            guided = self._cfg_combine(out, gs, batch)
            x_next, sstate = sched.step(x, guided.astype(jnp.float32), i, sstate)
            return x_next, new_pstate, sstate

        return jax.named_scope(f"phase_{phase}")(step)

    # ------------------------------------------------------------------
    # the full loop (traced once per num_steps)
    # ------------------------------------------------------------------

    def _device_loop(self, params, latents, enc, added, gs, num_steps,
                     start_step=0, end_step=None):
        # end_step: exclusive stop index (diffusers denoising_end analog);
        # the schedule tables stay those of the full num_steps run, only
        # the executed range narrows.  Stateful schedulers (DPM-Solver 2M)
        # resume a split run with FRESH solver history — the first resumed
        # step is first-order, exactly as diffusers behaves across separate
        # base/refiner pipeline objects; only stateless schedulers (DDIM,
        # Euler) replay the uninterrupted trajectory bit-for-bit.
        num_steps = num_steps if end_step is None else end_step
        cfg = self.cfg
        sched = self.scheduler
        my_enc, my_added, _ = self._branch_inputs(enc, added)
        # Text KV computed once per generation (reference kv_cache at
        # counter==0, pp/attn.py:56).  TP recomputes per step with sharded
        # kernels, like the reference's TP attention (no cache there).
        text_kv = (
            {} if cfg.parallelism == "tensor" else precompute_text_kv(params, my_enc)
        )

        step_sync = self._make_step(PHASE_SYNC)
        step_stale = self._make_step(PHASE_STALE)

        x = latents.astype(jnp.float32)
        sstate = sched.init_state(x.shape)

        def state_zeros(pstate_seed):
            """The patch-state carry structure, discovered WITHOUT inlining an
            extra UNet copy: sync steps never read their input state (each
            re-emits fresh gathered activations — _unet_local returns
            ctx.state_out), so the fori carry can start as zeros of the right
            shape instead of unrolling step 0.  The unroll was a third full
            UNet body in the 50-step program — a third of the multi-ten-minute
            remote compile that cost round 2 its benchmark number."""
            _, pshape, _ = jax.eval_shape(
                step_sync, params, jnp.asarray(0), x, pstate_seed, sstate,
                my_enc, my_added, text_kv, gs,
            )
            return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), pshape)

        if cfg.step_cache_enabled:
            # Temporal step-cache cadence (parallel/stepcache.py): full sync
            # warmup, then super-steps of (interval-1) shallow + 1 full —
            # exactly two step bodies composed into the scan, the same
            # full-program shape as the sync/stale pair.  In one-phase
            # configs (full_sync / single-device patch) both cadence bodies
            # run the sync phase; the temporal deep reuse applies either way.
            one_phase = cfg.mode == "full_sync" or not cfg.is_sp
            step_full = step_sync if one_phase else step_stale
            step_shallow = self._make_step(
                PHASE_SYNC if one_phase else PHASE_STALE, shallow=True
            )
            interval = cfg.step_cache_interval
            n_sync = min(cfg.warmup_steps + 1, num_steps - start_step)

            def warm_body(i, carry):
                x, ps, ss = carry
                return step_sync(params, i, x, ps, ss, my_enc, my_added,
                                 text_kv, gs)

            x, pstate, sstate = lax.fori_loop(
                start_step, start_step + n_sync, warm_body,
                (x, state_zeros(None), sstate)
            )
            s0 = start_step + n_sync

            def run_step(carry, i, shallow):
                x, ps, ss = carry
                fn = step_shallow if shallow else step_full
                return fn(params, i, x, ps, ss, my_enc, my_added, text_kv,
                          gs)

            x, _, _ = run_cadence((x, pstate, sstate), s0, num_steps - s0,
                                  interval, run_step)
            return x

        if cfg.parallelism != "patch" or cfg.mode == "full_sync" or not cfg.is_sp:
            # one phase for everything: naive_patch / tensor / full_sync —
            # and single-device patch, where _unet_local ignores the phase
            # entirely (not is_sp), so compiling a separate stale body would
            # double the program (and the remote compile) for nothing.
            # The {} seed also covers naive_patch/alternate: step()
            # unconditionally overwrites pstate with {"step": i} there, so
            # eval_shape returns the right carry structure from any seed.

            def body(i, carry):
                x, ps, ss = carry
                return step_sync(params, i, x, ps, ss, my_enc, my_added, text_kv, gs)

            x, _, _ = lax.fori_loop(
                start_step, num_steps, body, (x, state_zeros({}), sstate)
            )
            return x

        # displaced patch parallelism: sync warmup then stale steady state.
        # counter <= warmup_steps selects sync (reference §2.3), so steps
        # 0..warmup inclusive are synchronous.  An img2img entry (start_step
        # > 0) counts its warmup from the first step actually executed.
        n_sync = min(cfg.warmup_steps + 1, num_steps - start_step)

        def sync_body(i, carry):
            x, ps, ss = carry
            return step_sync(params, i, x, ps, ss, my_enc, my_added, text_kv, gs)

        x, pstate, sstate = lax.fori_loop(
            start_step, start_step + n_sync, sync_body,
            (x, state_zeros(None), sstate)
        )

        if start_step + n_sync >= num_steps:
            # all steps synchronous (e.g. short A/B runs): a zero-length scan
            # would still compile its dead stale UNet body
            return x

        def stale_body(carry, i):
            x, ps, ss = carry
            x, ps, ss = step_stale(params, i, x, ps, ss, my_enc, my_added, text_kv, gs)
            return (x, ps, ss), None

        (x, _, _), _ = lax.scan(
            stale_body, (x, pstate, sstate),
            jnp.arange(start_step + n_sync, num_steps)
        )
        return x

    def _build(self, num_steps: int, start_step: int = 0,
               end_step: int = None):
        cfg = self.cfg
        self.scheduler.set_timesteps(num_steps)

        device_loop = partial(self._device_loop, num_steps=num_steps,
                              start_step=start_step, end_step=end_step)

        # Inputs/outputs shard over the dp axis on the image-batch dim; with
        # dp_degree == 1 this degenerates to replication.
        lat_spec = P(DP_AXIS)
        enc_spec = P(None, DP_AXIS)

        def loop(params, latents, enc, added, gs):
            return shard_map(
                device_loop,
                mesh=cfg.mesh,
                in_specs=(self.param_specs, lat_spec, enc_spec, enc_spec, P()),
                out_specs=lat_spec,
                check_vma=False,
            )(params, latents, enc, added, gs)

        return jax.jit(loop)

    def _build_stale_scan(self, num_steps: int, n_start: int):
        """Fused stale steady-state ONLY (hybrid loop mode).

        The sync warmup runs through the per-step programs; their returned
        patch state enters here across the shard_map boundary in the
        stepwise layout.  The payoff is compile time: this program carries
        ONE UNet body (the stale step) where the fully fused loop carries
        two (sync fori + stale scan) — on slow remote-compile days the
        difference decides whether a fused-quality number lands inside the
        bench watchdog window, while per-step dispatch overhead still only
        applies to the handful of warmup steps.
        """
        cfg = self.cfg
        self.scheduler.set_timesteps(num_steps)
        state_spec = P((DP_AXIS, CFG_AXIS, SP_AXIS))
        lat_spec = P(DP_AXIS)
        enc_spec = P(None, DP_AXIS)

        def device_scan(params, x, pstate, sstate, enc, added, gs):
            my_enc, my_added, _ = self._branch_inputs(enc, added)
            text_kv = precompute_text_kv(params, my_enc)
            step_stale = self._make_step(PHASE_STALE)

            def body(carry, i):
                x, ps, ss = carry
                return step_stale(params, i, x, ps, ss, my_enc, my_added,
                                  text_kv, gs), None

            (x, _, _), _ = lax.scan(
                body, (x, pstate, sstate), jnp.arange(n_start, num_steps)
            )
            return x

        def loop(params, x, pstate, sstate, enc, added, gs):
            return shard_map(
                device_scan,
                mesh=cfg.mesh,
                in_specs=(self.param_specs, lat_spec, state_spec, P(),
                          enc_spec, enc_spec, P()),
                out_specs=lat_spec,
                check_vma=False,
            )(params, x, pstate, sstate, enc, added, gs)

        # x and the incoming state die at this call; let XLA reuse the HBM
        return jax.jit(loop, donate_argnums=(1, 2))

    def _hybrid_dispatch(self) -> bool:
        cfg = self.cfg
        return (cfg.hybrid_loop and cfg.parallelism == "patch"
                and cfg.mode != "full_sync" and cfg.is_sp)

    def _aot_wrap(self, fn, tag: str, layout: str = "donate="):
        """Wrap a freshly built jitted program in the persistent-AOT
        handle when a store is active for this build thread (the serve
        layer's `ExecutorCache` activates one around executor builds
        when `ServeConfig.aot_cache.dir` is configured).  No store, no
        wrapper — the production default is byte-for-byte today's path."""
        from ..utils.aot import active_aot_scope

        act = active_aot_scope()
        if act is None:
            return fn
        store, scope = act
        return _AotProgramHandle(
            fn, store=store, scope=scope, tag=tag, mesh=self.cfg.mesh,
            layout=layout)

    def _ensure_stale_scan(self, num_steps: int, n_sync: int):
        skey = ("stale_scan", num_steps, n_sync)
        if skey not in self._compiled:
            self._compiled[skey] = self._aot_wrap(
                self._build_stale_scan(num_steps, n_sync),
                tag=f"stale_scan:{num_steps}:{n_sync}",
                layout="donate=1,2")
        return self._compiled[skey]

    def compiled_handle(self, num_steps: int, start_step: int = 0,
                        end_step: Optional[int] = None):
        """The jitted fused-loop callable for this signature, built (and
        cached) on first use — the handle generate() dispatches to.

        Public so callers that manage their own executable lifecycle (the
        serve layer's compiled-executable cache, warmup prefetchers) can pin
        or pre-build programs without a throwaway generate() call, and so a
        cached handle is observably the SAME object across calls instead of
        an implementation detail."""
        key = (num_steps if start_step == 0 and end_step is None
               else (num_steps, start_step, end_step))
        if key not in self._compiled:
            # Chaos hook (utils/chaos.py, plans authored in serve/faults.py):
            # the process-global fault plan, when installed, can fail this
            # build deterministically — the injection site for "the compile
            # service is down" scenarios that the serve layer's degradation
            # ladder must survive.  The registry is a stdlib-only utils
            # leaf, so this does NOT pull the serving subsystem into the
            # parallel layer; production runs never install a plan.
            from ..utils.chaos import active_fault_plan

            plan = active_fault_plan()
            if plan is not None:
                plan.check("runner.compile")
            self._builds += 1
            # AOT store hook (utils/aot.py, store in serve/aotcache.py):
            # same layering as the chaos hook above — when the serve
            # layer activated a persistent executable store around this
            # build, the handle's first dispatch deserializes a persisted
            # compile instead of paying XLA, and persists fresh compiles
            # for the next replica.  No activation = plain jit handle.
            self._compiled[key] = self._aot_wrap(
                self._build(num_steps, start_step, end_step),
                tag=f"fused:{key}")
        return self._compiled[key]

    def cache_info(self) -> Dict[str, Any]:
        """Compiled-program cache observability: which signatures are
        resident and how many builds have happened (a retrace on the request
        path shows up as builds growing after warmup)."""
        return {
            "entries": sorted(str(k) for k in self._compiled),
            "builds": self._builds,
        }

    def prepare(self, num_steps: int) -> None:
        """Pre-build exactly the program(s) generate() will dispatch to
        (pipelines.prepare delegates here).  Per-step programs build
        lazily; hybrid mode pre-builds the big stale-scan program."""
        if not self.cfg.use_compiled_step:
            return
        if self._hybrid_dispatch():
            n_sync = min(self.cfg.warmup_steps + 1, num_steps)
            if n_sync < num_steps:
                self._ensure_stale_scan(num_steps, n_sync)
            return
        # scheduler tables must match the trace (see generate()'s re-pin)
        self.scheduler.set_timesteps(num_steps)
        self.compiled_handle(num_steps)

    def _generate_hybrid(self, latents, enc, added, gs, num_steps):
        """Sync warmup via per-step programs + one fused stale-only scan."""
        cfg = self.cfg
        self.scheduler.set_timesteps(num_steps)
        x = jnp.asarray(latents, jnp.float32)
        sstate = self.scheduler.init_state(x.shape)
        pstate = None
        n_sync = min(cfg.warmup_steps + 1, num_steps)

        fns = self._compiled.setdefault(("stepwise", num_steps), {})
        for i in range(n_sync):
            fkey = (PHASE_SYNC, pstate is not None, False)
            if fkey not in fns:
                fns[fkey] = self._build_stepwise(PHASE_SYNC, pstate is not None)
            x, pstate, sstate = fns[fkey](
                self.params, jnp.asarray(i), x, pstate, sstate, enc, added, gs
            )
        if n_sync >= num_steps:
            return x
        return self._ensure_stale_scan(num_steps, n_sync)(
            self.params, x, pstate, sstate, enc, added, gs
        )

    # ------------------------------------------------------------------
    # per-step (uncompiled-loop) mode: the reference's --no_cuda_graph
    # ------------------------------------------------------------------

    def _make_stepper(self, phase, with_state: bool, shallow: bool = False):
        """Un-jitted shard_map'd single step with the global-array signature.

        The patch state crosses the shard_map boundary here, so its leaves are
        laid out along ("cfg","sp") on axis 0: stale activations vary across
        CFG branches and (for the ring layout) across patch peers.
        Returns (stepper, donate_argnums): _build_stepwise jits it directly;
        _build_fused_callback embeds it in a compiled scan.
        """
        cfg = self.cfg
        # Patch-parallel state varies across CFG branches and (ring layout)
        # across sp peers -> lay leaves out along ("cfg","sp") on axis 0.
        # naive_patch's step counter / tensor's empty state are replicated.
        state_spec = (
            P((DP_AXIS, CFG_AXIS, SP_AXIS))
            if cfg.parallelism == "patch" and with_state
            else P()
        )

        def device_step(params, i, x, pstate, sstate, enc, added, gs):
            my_enc, my_added, _ = self._branch_inputs(enc, added)
            text_kv = (
                {} if cfg.parallelism == "tensor" else precompute_text_kv(params, my_enc)
            )
            step = self._make_step(phase, shallow=shallow)
            return step(params, i, x, pstate, sstate, my_enc, my_added, text_kv, gs)

        lat_spec = P(DP_AXIS)
        enc_spec = P(None, DP_AXIS)

        def stepper(params, i, x, pstate, sstate, enc, added, gs):
            return shard_map(
                device_step,
                mesh=cfg.mesh,
                in_specs=(self.param_specs, P(), lat_spec, state_spec, P(),
                          enc_spec, enc_spec, P()),
                out_specs=(
                    lat_spec,
                    P((DP_AXIS, CFG_AXIS, SP_AXIS))
                    if cfg.parallelism == "patch"
                    else state_spec,
                    P(),
                ),
                check_vma=False,
            )(params, i, x, pstate, sstate, enc, added, gs)

        # Donate the stale-state buffers: each step's input state is dead the
        # moment the refreshed state returns, so XLA reuses the HBM in place
        # (gather-layout state is O(L) per layer — the dominant allocation at
        # high resolution).  The fused loop gets this for free from the scan.
        donate = (3,) if with_state and cfg.parallelism == "patch" else ()
        return stepper, donate

    def _build_stepwise(self, phase, with_state: bool, shallow: bool = False):
        """One jitted denoising step driven from Python."""
        stepper, donate = self._make_stepper(phase, with_state, shallow)
        return jax.jit(stepper, donate_argnums=donate)

    def _stepwise_state_seed(self):
        """Initial patch-state value for a host-driven loop — mirrors what
        each parallelism mode expects before its first step."""
        cfg = self.cfg
        if cfg.parallelism == "naive_patch" and cfg.split_scheme == "alternate":
            return {"step": jnp.asarray(0)}
        return {} if cfg.parallelism != "patch" else None

    def _fire_callback(self, i, t, x):
        """Host-side trampoline for the fused-mode per-step callback
        (io_callback target).  Reads the active callback from the instance
        so one compiled program serves any callback object."""
        cb = self._active_callback
        if cb is not None:
            cb(int(i), t, x)

    def _build_fused_callback(self, num_steps: int, start_step: int = 0,
                              end_step: int = None):
        """Fused loop variant that fires per-step host callbacks.

        The reference gets diffusers' legacy callback for free in ALL modes
        because even its CUDA-graph path keeps the step loop in Python
        (pipelines.py:47-58 delegation to diffusers __call__).  Our fused
        mode has no host loop, so the callback rides
        ``jax.experimental.io_callback(ordered=True)`` inside the compiled
        program: the scan body is the shard_map'd stepwise step (stepwise
        state layout crossing the shard_map boundary each step), and after
        each step the GLOBAL latents ship to the host and reach
        ``self._active_callback``.  Both segments use ``lax.scan`` — ordered
        effects are unsupported in ``while_loop``/``fori_loop`` bodies.

        Built only when a callback is actually passed: the callback-free
        fused program keeps its in-device carry and never syncs the host.
        """
        from jax.experimental import io_callback

        cfg = self.cfg
        sched = self.scheduler
        sched.set_timesteps(num_steps)
        num_exec_end = num_steps if end_step is None else end_step
        one_phase = (cfg.parallelism != "patch" or cfg.mode == "full_sync"
                     or not cfg.is_sp)
        n_sync = (num_exec_end - start_step if one_phase
                  else min(cfg.warmup_steps + 1, num_exec_end - start_step))
        seed = self._stepwise_state_seed()
        seed_step, _ = self._make_stepper(PHASE_SYNC, seed is not None)
        sync_step, _ = self._make_stepper(PHASE_SYNC, True)
        stale_step, _ = self._make_stepper(PHASE_STALE, True)

        def loop(params, latents, enc, added, gs):
            x = latents.astype(jnp.float32)
            sstate = sched.init_state(x.shape)
            tsteps = sched.timesteps()
            # carry structure without unrolling a step: sync steps never
            # read their input state (see _device_loop.state_zeros), so
            # zeros of the eval_shape'd GLOBAL state layout start the scan
            _, pshape, _ = jax.eval_shape(
                seed_step, params, jnp.asarray(0), x, seed, sstate, enc,
                added, gs,
            )
            ps = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), pshape)

            def body_for(step_fn):
                def body(carry, i):
                    x, ps, ss = carry
                    x, ps, ss = step_fn(params, i, x, ps, ss, enc, added, gs)
                    io_callback(self._fire_callback, None, i, tsteps[i], x,
                                ordered=True)
                    return (x, ps, ss), None
                return body

            (x, ps, sstate), _ = lax.scan(
                body_for(sync_step), (x, ps, sstate),
                jnp.arange(start_step, start_step + n_sync),
            )
            if start_step + n_sync < num_exec_end:
                (x, ps, sstate), _ = lax.scan(
                    body_for(stale_step), (x, ps, sstate),
                    jnp.arange(start_step + n_sync, num_exec_end),
                )
            return x

        return jax.jit(loop)

    def _stepwise_phase(self, i: int, start_step: int, num_exec_end: int):
        """(phase, shallow) of step ``i`` in a host-driven loop — a pure
        function of the step index and config, shared by the in-place
        stepwise loop and the explicit-carry API so interleaved and
        contiguous executions replay the identical per-step programs."""
        cfg = self.cfg
        sc = cfg.step_cache_enabled
        one_phase = (cfg.parallelism != "patch" or cfg.mode == "full_sync"
                     or not cfg.is_sp)
        n_sync = (num_exec_end - start_step if one_phase and not sc
                  else min(cfg.warmup_steps + 1, num_exec_end - start_step))
        phase = (PHASE_SYNC if one_phase or i < start_step + n_sync
                 else PHASE_STALE)
        # the same shallow-first pattern run_cadence compiles
        shallow = sc and is_shallow_at(
            i, start_step + n_sync, cfg.step_cache_interval
        )
        return phase, shallow

    def _stepwise_fn(self, num_steps: int, phase, with_state: bool,
                     shallow: bool):
        """The jitted single-step program for one (phase, state, shallow)
        signature, built on first use and shared by every host-driven
        loop at this step count."""
        key = ("stepwise", num_steps)
        if key not in self._compiled:
            self._compiled[key] = {}
        fns = self._compiled[key]
        fkey = (phase, with_state, shallow)
        if fkey not in fns:
            fns[fkey] = self._build_stepwise(phase, with_state, shallow)
        return fns[fkey]

    def _generate_stepwise(self, latents, enc, added, gs, num_steps,
                           start_step=0, end_step=None, callback=None):
        """Python loop over per-step compiled calls (reference no-CUDA-graph
        path, distri_sdxl_unet_pp.py:117-193): same numerics as the fused
        loop, per-step latency visible from the host.
        ``callback(step_index, timestep, latents)`` fires after each step —
        the diffusers legacy-callback signature; only this mode has a host
        loop to fire it from."""
        num_exec_end = num_steps if end_step is None else end_step
        self.scheduler.set_timesteps(num_steps)
        x = jnp.asarray(latents, jnp.float32)
        sstate = self.scheduler.init_state(x.shape)
        pstate: Any = self._stepwise_state_seed()
        for i in range(start_step, num_exec_end):
            phase, shallow = self._stepwise_phase(i, start_step,
                                                  num_exec_end)
            fn = self._stepwise_fn(num_steps, phase, pstate is not None,
                                   shallow)
            x, pstate, sstate = fn(
                self.params, jnp.asarray(i), x, pstate, sstate, enc, added, gs
            )
            if callback is not None:
                callback(i, self.scheduler.timesteps()[i], x)
        return x

    # ------------------------------------------------------------------
    # explicit-carry stepwise API (the step-granular serve substrate)
    # ------------------------------------------------------------------

    def stepwise_carry_init(self, latents, num_steps: int):
        """Start a host-driven denoise with the carry held EXTERNALLY:
        returns ``(x, pstate, sstate)`` — exactly the state one iteration
        of `_generate_stepwise` threads.  The step-granular serve layer
        (serve/stepbatch.py) holds one carry per slot, so requests park,
        resume, and interleave between steps while each carry replays the
        identical per-step programs a contiguous solo loop runs —
        bit-identical by construction."""
        self.scheduler.set_timesteps(num_steps)
        x = jnp.asarray(latents, jnp.float32)
        return (x, self._stepwise_state_seed(),
                self.scheduler.init_state(x.shape))

    def stepwise_carry_step(self, carry, i: int, enc, added, gs,
                            num_steps: int):
        """Advance one explicit carry by exactly step ``i``; returns the
        new carry.  The per-step program is the SAME compiled fn
        `_generate_stepwise` dispatches for this (phase, state, shallow)
        signature, so solo, interleaved, and parked-then-resumed
        executions of one request are byte-identical.  ``enc`` must be
        dtype-pinned like generate() pins it (the serve executor does)."""
        x, pstate, sstate = carry
        phase, shallow = self._stepwise_phase(i, 0, num_steps)
        fn = self._stepwise_fn(num_steps, phase, pstate is not None,
                               shallow)
        return fn(self.params, jnp.asarray(i), x, pstate, sstate, enc,
                  added, gs)

    def stepwise_carry_latent(self, carry):
        """The carry's current latent [B, H/8, W/8, C] (preview + decode
        input) — does not consume the carry."""
        return carry[0]

    # -- packed cohort rows (serve/executors.py step_run; parallel/rowpack) --

    def stepwise_rows_supported(self) -> bool:
        """Whether this config's per-step program accepts per-row step
        indices (the packed cohort dispatch).  Gated off — falling back to
        sequential per-slot dispatch — where a vector step index would
        change the traced program's CONTROL FLOW or couple batch rows:
        naive-alternate's row/col parity cond, the PCPP partial-refresh
        rotation, lossy refresh compression (per-tensor scales couple
        rows), and dp sharding (the replicated [B] index does not shard
        with the dp-split batch)."""
        cfg = self.cfg
        return (cfg.dp_degree == 1
                and cfg.refresh_fraction >= 1
                and cfg.comm_compress == "none"
                and not (cfg.parallelism == "naive_patch"
                         and cfg.split_scheme == "alternate"))

    def stepwise_carry_signature(self, carry, i: int, num_steps: int):
        """Hashable compiled-program identity of advancing ``carry`` by
        step ``i``: carries sharing a signature run the SAME per-step
        program and may pack into one dispatch's batch rows."""
        phase, shallow = self._stepwise_phase(i, 0, num_steps)
        return ("unet", phase, carry[1] is not None, shallow, num_steps)

    def stepwise_carry_rows_axes(self, carry, enc, added, num_steps: int):
        """Per-leaf batch-axis plan (parallel/rowpack.py) for this
        carry's structure, discovered by shape comparison at two widths:
        latents/scheduler state analytically, the patch-state tree via
        ``jax.eval_shape`` of the sync stepper (which CREATES the state
        structure from the seed — no layout table to drift)."""
        from . import rowpack

        x, pstate, sstate = carry
        w = x.shape[0]

        def widen(leaf, axis, k):
            shape = list(jnp.shape(leaf))
            shape[axis] = shape[axis] * k
            return jax.ShapeDtypeStruct(tuple(shape), jnp.result_type(leaf))

        def carry_shapes(k):
            xs = widen(x, 0, k)
            ss = self.scheduler.init_state((w * k,) + x.shape[1:])
            if pstate is None or not jax.tree_util.tree_leaves(pstate):
                return (xs, pstate, ss)
            seed = self._stepwise_state_seed()
            stepper, _ = self._make_stepper(PHASE_SYNC, seed is not None)
            enc_k = jax.tree.map(lambda l: widen(l, 1, k), enc)
            added_k = (None if added is None
                       else jax.tree.map(lambda l: widen(l, 1, k), added))
            _, pshape, _ = jax.eval_shape(
                stepper, self.params, jnp.asarray(0), xs, seed, ss, enc_k,
                added_k, jnp.asarray(1.0, jnp.float32),
            )
            return (xs, pshape, ss)

        return rowpack.axes_from_shapes(carry_shapes(1), carry_shapes(2))

    def stepwise_carry_step_rows(self, carry, i_rows, enc, added, gs_rows,
                                 num_steps: int):
        """Advance a PACKED carry: row ``r`` moves by exactly step
        ``i_rows[r]`` at guidance ``gs_rows[r]``.  All rows must share
        one compiled signature (the executor groups by
        `stepwise_carry_signature`); the dispatched program is the SAME
        jitted `_stepwise_fn` the solo path uses — the step index and
        guidance scale are traced inputs, so the [B]-shaped call is just
        another cached trace of the same program and each row's numerics
        are byte-identical to its solo dispatch (batch-row independence,
        pinned in tests/test_stepbatch.py)."""
        x, pstate, sstate = carry
        sigs = {self._stepwise_phase(int(i), 0, num_steps)
                for i in i_rows}
        if len(sigs) != 1:
            raise ValueError(
                f"packed rows span {len(sigs)} step signatures: {sigs}"
            )
        (phase, shallow), = sigs
        fn = self._stepwise_fn(num_steps, phase, pstate is not None,
                               shallow)
        return fn(self.params, jnp.asarray(list(i_rows)), x, pstate,
                  sstate, enc, added,
                  jnp.asarray(list(gs_rows), jnp.float32))

    # ------------------------------------------------------------------
    # observability
    # ------------------------------------------------------------------

    def comm_volume_report(self, batch_size: int = None, text_len: int = 77,
                           *, per_phase: bool = False):
        """Per-layer-type stale-buffer element counts.

        Parity with the reference's verbose buffer stats at create_buffer
        time (utils.py:152-158): reports how many elements per device the
        displaced-patch state holds, grouped by layer type.  Computed with
        jax.eval_shape — no device work.

        ``per_phase=True`` returns the step-cache-aware breakdown instead:
        ``{"phases": {"sync"|"stale"|"shallow": {kind: fresh-exchange
        elements}}, "bytes": {phase: {kind: wire bytes}}, "flops": {...}}``
        — per phase, only the state a step FRESHLY exchanges is counted
        (carried-through deep buffers are excluded via CARRIED_REGISTRY).
        ``bytes`` is wire-accurate: compressed refresh payloads count their
        int8/fp8 elements + fp32 scales (context.WIRE_REGISTRY, populated
        at emit time by the exchanging op itself), wire-free local carries
        (the step-cache deep feature, residual own-rows) count zero, and
        everything else defaults to elements x dtype itemsize — so
        warmup/sync bytes are identical across comm_compress modes by
        construction, and the stale-phase reduction is a checked number.
        ``flops`` estimates the full-vs-shallow step cost via XLA cost
        analysis (``_flop_estimate``), so the cache's compute and comm
        savings are inspectable without a chip.
        """
        cfg = self.cfg
        if per_phase:
            return self._comm_volume_per_phase(batch_size, text_len)
        if cfg.parallelism != "patch" or not cfg.is_sp:
            return {}
        self.scheduler.set_timesteps(2)
        step = self._make_step(PHASE_SYNC)

        def one_step(params, latents, enc, added, gs):
            my_enc, my_added, _ = self._branch_inputs(enc, added)
            text_kv = (
                {} if cfg.parallelism == "tensor" else precompute_text_kv(params, my_enc)
            )
            sstate = self.scheduler.init_state(latents.shape)
            _, pstate, _ = step(
                params, 0, latents.astype(jnp.float32), None, sstate,
                my_enc, my_added, text_kv, gs,
            )
            return pstate

        lat, enc, added, gs = self._abstract_inputs(
            batch_size, text_len, per_group=True
        )

        shapes = jax.eval_shape(
            lambda p, l, e, a, g: shard_map(
                one_step, mesh=cfg.mesh,
                in_specs=(self.param_specs, P(), P(), P(), P()),
                out_specs=P(), check_vma=False,
            )(p, l, e, a, g),
            self.params, lat, enc, added, gs,
        )

        # The eval_shape trace above just populated KIND_REGISTRY: each op
        # declares its own kind at emit time, so classification never falls
        # back to name heuristics.
        report: Dict[str, int] = {}
        for name, s in shapes.items():
            t = KIND_REGISTRY.get(name, "other")
            report[t] = report.get(t, 0) + int(np.prod(s.shape))
        if cfg.verbose:
            total = sum(report.values())
            print(
                f"Stale-state buffers: {total / 1e6:.3f}M elements over "
                f"{len(shapes)} tensors per device."
            )
            for t, numel in sorted(report.items()):
                print(f"  {t}: {numel / 1e6:.3f}M elements")
        return report

    def _comm_volume_per_phase(self, batch_size: int = None,
                               text_len: int = 77) -> Dict[str, Any]:
        """Step-cache-aware comm/compute breakdown (comm_volume_report
        per_phase=True).  Each phase is traced with jax.eval_shape through
        the same step closures the loops run; a phase's count is the
        elements it freshly exchanges (state it merely carries — skipped
        deep layers, the deep cache on shallow steps — is subtracted via
        CARRIED_REGISTRY)."""
        cfg = self.cfg
        if cfg.parallelism != "patch":
            return {"phases": {}, "bytes": {}, "flops": None}
        self.scheduler.set_timesteps(2)
        lat, enc, added, gs = self._abstract_inputs(
            batch_size, text_len, per_group=True
        )
        # kinds that live in the carry without ever touching the wire
        wire_free = ("stepcache", "local")

        def trace(step, pstate_in):
            has_state = pstate_in is not None

            def one_step(params, latents, enc, added, gs, *maybe_state):
                my_enc, my_added, _ = self._branch_inputs(enc, added)
                text_kv = precompute_text_kv(params, my_enc)
                sstate = self.scheduler.init_state(latents.shape)
                _, pout, _ = step(
                    params, 1, latents.astype(jnp.float32),
                    maybe_state[0] if has_state else None, sstate,
                    my_enc, my_added, text_kv, gs,
                )
                return pout

            args = (self.params, lat, enc, added, gs)
            specs = (self.param_specs, P(), P(), P(), P())
            if has_state:
                args += (pstate_in,)
                specs += (P(),)
            CARRIED_REGISTRY.clear()
            WIRE_REGISTRY.clear()
            shapes = jax.eval_shape(
                lambda *a: shard_map(
                    one_step, mesh=cfg.mesh, in_specs=specs,
                    out_specs=P(), check_vma=False,
                )(*a),
                *args,
            )
            carried = set(CARRIED_REGISTRY)
            wire = dict(WIRE_REGISTRY)
            if shapes is None:  # stateless step (single device, cache off)
                shapes = {}
            report: Dict[str, int] = {}
            nbytes: Dict[str, int] = {}
            for name, s in shapes.items():
                if name in carried:
                    continue
                t = KIND_REGISTRY.get(name, "other")
                numel = int(np.prod(s.shape))
                report[t] = report.get(t, 0) + numel
                if name in wire:
                    b = wire[name]
                elif t in wire_free:
                    b = 0
                else:
                    b = numel * jnp.dtype(s.dtype).itemsize
                nbytes[t] = nbytes.get(t, 0) + b
            return shapes, report, nbytes

        phases: Dict[str, Dict[str, int]] = {}
        bytes_: Dict[str, Dict[str, int]] = {}
        sync_shapes, phases["sync"], bytes_["sync"] = trace(
            self._make_step(PHASE_SYNC), None
        )
        one_phase = cfg.mode == "full_sync" or not cfg.is_sp
        if not one_phase:
            _, phases["stale"], bytes_["stale"] = trace(
                self._make_step(PHASE_STALE), sync_shapes
            )
        if cfg.step_cache_enabled:
            steady = PHASE_SYNC if one_phase else PHASE_STALE
            _, phases["shallow"], bytes_["shallow"] = trace(
                self._make_step(steady, shallow=True), sync_shapes
            )
        return {"phases": phases, "bytes": bytes_,
                # PCPP key: the stale/shallow byte rows above are already
                # fraction-aware (WIRE_REGISTRY entries register the
                # strided subset the emit actually gathers) — this records
                # WHICH fraction priced them, so comm_plan and the benches
                # can label the reduction
                "refresh_fraction": cfg.refresh_fraction,
                "flops": self._flop_estimate(batch_size, text_len)}

    def _flop_estimate(self, batch_size: int = None,
                       text_len: int = 77) -> Optional[Dict[str, float]]:
        """{"full", "shallow", "shallow_ratio"}: estimated FLOPs of one
        steady-state denoise step vs its shallow-cadence counterpart, from
        XLA cost analysis of the lowered per-step programs (abstract inputs
        — no execution, no chip).  None when the cache is off or the
        backend's cost model is unavailable."""
        cfg = self.cfg
        if not cfg.step_cache_enabled:
            return None
        lat, enc, added, gs = self._abstract_inputs(batch_size, text_len)
        self.scheduler.set_timesteps(2)
        sstate = self.scheduler.init_state(lat.shape)
        seed_step, _ = self._make_stepper(PHASE_SYNC, False)
        _, pshape, _ = jax.eval_shape(
            seed_step, self.params, jnp.asarray(1), lat, None, sstate, enc,
            added, gs,
        )
        steady = (PHASE_SYNC if cfg.mode == "full_sync" or not cfg.is_sp
                  else PHASE_STALE)
        out: Dict[str, float] = {}
        for name, shallow in (("full", False), ("shallow", True)):
            stepper, _ = self._make_stepper(steady, True, shallow)
            try:
                ca = jax.jit(stepper).lower(
                    self.params, jnp.asarray(1), lat, pshape, sstate, enc,
                    added, gs,
                ).cost_analysis()
                if not isinstance(ca, dict):  # older API: list per device
                    ca = ca[0]
                out[name] = float(ca["flops"])
            except Exception:
                return None
        if out["full"] > 0:
            out["shallow_ratio"] = out["shallow"] / out["full"]
        return out

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def _abstract_inputs(self, batch_size: int = None, text_len: int = 77,
                         *, per_group: bool = False):
        """ShapeDtypeStructs for (lat, enc, added, gs) — the single source of
        truth for the abstract program signature, shared by
        comm_volume_report and compiled_hlo so the two observability paths
        can never trace different programs (they once drifted on the enc
        dtype).  generate() casts its real inputs to the same dtypes, so a
        program lowered from these specs is the program that runs — to the
        argument attributes: the encoders' outputs reach generate()
        committed to the mesh, replicated (the pipelines), latents, time ids
        and the scale uncommitted, and the specs say the same, so that
        compiled_hlo() is a hit in JAX's compile cache after the served
        program's compile and not a second compile of minutes.

        ``per_group=False`` gives the global-batch signature of the fused
        loop (batch splits over the dp axis inside shard_map);
        ``per_group=True`` gives the per-image-group shapes
        comm_volume_report feeds its replicated-spec trace."""
        cfg = self.cfg
        b = cfg.batch_size if batch_size is None else batch_size
        if b % cfg.dp_degree != 0:
            raise ValueError(
                f"batch_size {b} not divisible by dp_degree {cfg.dp_degree}"
            )
        if per_group:
            b = b // cfg.dp_degree
        n_br = 2 if cfg.do_classifier_free_guidance else 1
        replicated = jax.sharding.NamedSharding(cfg.mesh, P())
        lat = jax.ShapeDtypeStruct(
            (b, cfg.latent_height, cfg.latent_width, self.ucfg.in_channels),
            jnp.float32,
        )
        enc = jax.ShapeDtypeStruct(
            (n_br, b, text_len, self.ucfg.cross_attention_dim), cfg.dtype,
            sharding=replicated,
        )
        added = None
        if self.ucfg.addition_embed_type == "text_time":
            emb = (
                self.ucfg.projection_class_embeddings_input_dim
                - 6 * self.ucfg.addition_time_embed_dim
            )
            added = {
                "text_embeds": jax.ShapeDtypeStruct(
                    (n_br, b, emb), cfg.dtype, sharding=replicated),
                "time_ids": jax.ShapeDtypeStruct((n_br, b, 6), jnp.float32),
            }
        gs = jax.ShapeDtypeStruct((), jnp.float32)
        return lat, enc, added, gs

    def compiled_hlo(self, num_inference_steps: int = 4, batch_size: int = None,
                     text_len: int = 77) -> str:
        """Optimized-HLO text of the fused loop (abstract inputs, no device
        execution beyond compilation).  Feed to utils/overlap.py to verify
        the refresh collectives stay carry-only on this backend."""
        lat, enc, added, gs = self._abstract_inputs(batch_size, text_len)
        # seed the jit cache: a following generate() with the same step count
        # reuses this program instead of re-compiling (jit caches by shape)
        fn = self.compiled_handle(num_inference_steps)
        return fn.lower(self.params, lat, enc, added, gs).compile().as_text()

    @staticmethod
    def exchange_report(hlo_text: str):
        """What the COMPILED loop exchanges, from its own text (e.g.
        ``compiled_hlo()``): per phase (``phase_sync`` / ``phase_stale``,
        the scopes `_make_step` puts its step under) and exchange kind
        (``halo``, ``stale_kv``, ``gn_stats``, ``stale_gather``,
        ``out_gather``, ``cfg_combine``) ``{"collectives": instructions,
        "inline": those this iteration computes with, "bytes": wire bytes
        per device and step}``.  comm_volume_report / comm_plan are the
        model of these bytes; this is the program's count."""
        from ..utils.overlap import exchange_report

        return exchange_report(hlo_text)

    def generate(
        self,
        latents,
        prompt_embeds,
        *,
        guidance_scale: float = 5.0,
        num_inference_steps: int = 50,
        added_cond: Optional[Dict[str, Any]] = None,
        start_step: int = 0,
        end_step: Optional[int] = None,
        callback=None,
    ):
        """Run the denoising loop.

        ``latents``: [B, H/8, W/8, C] initial noise **already scaled** by
        ``scheduler.init_noise_sigma`` — or, with ``start_step > 0``
        (img2img), a clean latent noised to that schedule point via
        ``scheduler.add_noise``.  ``prompt_embeds``: [n_branches, B, L, C]
        with branch 0 = unconditional (reference rank layout,
        utils.py:98-104).  Returns the denoised latent [B, H/8, W/8, C].
        """
        added = added_cond if added_cond is not None else None
        if jax.process_count() > 1:
            # Multi-controller (pod) mode: host-local numpy must become
            # global replicated arrays before entering the jitted program —
            # the analog of every torchrun rank feeding identical inputs.
            from jax.sharding import NamedSharding

            sharding = NamedSharding(self.cfg.mesh, P())
            mk = lambda x: jax.make_array_from_process_local_data(  # noqa: E731
                sharding, np.asarray(x)
            )
            latents = mk(latents)
            prompt_embeds = mk(prompt_embeds)
            if added is not None:
                added = jax.tree.map(mk, added)
        # Pin inputs to the abstract signature (_abstract_inputs): embeds in
        # the model dtype, latents/time_ids fp32.  Without this, fp32-embeds
        # callers silently retrace a second program that a compiled_hlo-seeded
        # jit cache (and its overlap analysis) never describes.
        prompt_embeds = jnp.asarray(prompt_embeds, self.cfg.dtype)
        if added is not None and "text_embeds" in added:
            added = dict(added)
            added["text_embeds"] = jnp.asarray(added["text_embeds"], self.cfg.dtype)
        assert 0 <= start_step < num_inference_steps, (start_step,
                                                       num_inference_steps)
        assert end_step is None or start_step < end_step <= num_inference_steps, (
            start_step, end_step, num_inference_steps)
        if callback is not None and self.cfg.use_compiled_step:
            if self.cfg.step_cache_enabled:
                # step-cache runs take the host loop when a callback is
                # requested: the stepwise steppers replay the exact cadence
                # without teaching the io_callback program a third body.
                return self._generate_stepwise(
                    jnp.asarray(latents), prompt_embeds, added,
                    np.float32(guidance_scale),
                    num_inference_steps, start_step, end_step, callback,
                )
            # fused/hybrid modes: the callback rides io_callback inside a
            # dedicated compiled loop (_build_fused_callback) — same step
            # numerics, one dispatch, per-step host sync only in THIS
            # program.  Callback-free generates keep the host-free loop.
            self.scheduler.set_timesteps(num_inference_steps)
            key = ("fused_cb", num_inference_steps, start_step, end_step)
            if key not in self._compiled:
                self._compiled[key] = self._build_fused_callback(
                    num_inference_steps, start_step, end_step
                )
            self._active_callback = callback
            try:
                out = self._compiled[key](
                    self.params,
                    jnp.asarray(latents),
                    prompt_embeds,
                    added,
                    np.float32(guidance_scale),
                )
                # block_until_ready only waits on the OUTPUT buffer; host
                # callbacks drain on a separate thread, so without this
                # barrier an async-dispatch backend could reach the finally
                # (clearing _active_callback) before the last steps fire
                jax.effects_barrier()
                jax.block_until_ready(out)
                return out
            finally:
                self._active_callback = None
        if not self.cfg.use_compiled_step:
            return self._generate_stepwise(
                jnp.asarray(latents),
                jnp.asarray(prompt_embeds),
                added,
                np.float32(guidance_scale),
                num_inference_steps,
                start_step,
                end_step,
                callback,
            )
        if (self._hybrid_dispatch()
                and start_step == 0 and end_step is None):
            return self._generate_hybrid(
                jnp.asarray(latents), jnp.asarray(prompt_embeds), added,
                np.float32(guidance_scale), num_inference_steps,
            )
        # Re-pin the scheduler tables on every call, not just at build time:
        # a cached jitted loop can RE-trace later (new input shapes), and the
        # trace reads the mutable scheduler — which a generate() with a
        # different step count may have re-tabled in between.
        self.scheduler.set_timesteps(num_inference_steps)
        fn = self.compiled_handle(num_inference_steps, start_step, end_step)
        return fn(
            self.params,
            jnp.asarray(latents),
            jnp.asarray(prompt_embeds),
            added,
            np.float32(guidance_scale),
        )


def make_runner(
    distri_config: DistriConfig,
    unet_config: UNetConfig,
    params,
    scheduler: BaseScheduler,
) -> DenoiseRunner:
    """Wire the right parallelism for ``distri_config.parallelism``.

    The analog of the reference's model selection in from_pretrained
    (pipelines.py:30-37): patch -> DistriUNetPP, naive_patch ->
    NaivePatchUNet, tensor -> DistriUNetTP (weights sharded in place).
    """
    if distri_config.parallelism == "tensor" and distri_config.n_device_per_batch > 1:
        from ..models.unet_tp import TPDispatch, head_dim_table, prepare_tp_params

        n = distri_config.n_device_per_batch
        tp_params, specs = prepare_tp_params(params, unet_config, n)
        head_dims = head_dim_table(unet_config)
        factory = lambda text_kv: TPDispatch(n, head_dims)  # noqa: E731
        return DenoiseRunner(
            distri_config, unet_config, tp_params, scheduler,
            tp_dispatch_factory=factory, param_specs=specs,
        )
    if distri_config.parallelism == "tensor":
        # single device: TP degenerates to dense
        from ..models.unet import DenseDispatch

        return DenoiseRunner(
            distri_config, unet_config, params, scheduler,
            tp_dispatch_factory=lambda text_kv: DenseDispatch(text_kv=text_kv),
        )
    return DenoiseRunner(distri_config, unet_config, params, scheduler)
