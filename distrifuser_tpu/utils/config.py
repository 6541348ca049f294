"""Distributed run configuration and mesh bootstrap.

TPU-native re-design of the reference's `DistriConfig`
(/root/reference/distrifuser/utils.py:23-109).  The reference bootstraps one
NCCL process per GPU under torchrun, derives (rank, world_size), and builds
`batch_group` / `split_group` NCCL communicators.  On TPU the idiomatic shape
is single-controller SPMD: one process drives every local chip through a named
`jax.sharding.Mesh`, and the two process-group families become mesh axes
(plus a data-parallel axis the reference lacks):

* axis ``"cfg"`` (size 2 when classifier-free guidance is batch-split, else 1)
  — the reference's *split_group* direction (utils.py:91-94): ranks holding the
  same spatial patch for the two CFG branches.
* axis ``"sp"`` (size ``n_device_per_batch``) — the reference's *batch_group*
  direction (utils.py:87-90): the patch/sequence-parallel peers within one CFG
  branch.
* axis ``"dp"`` (size ``dp_degree``, default 1) — independent image groups,
  an extension over the reference's separate-job sweeps.

Device order matches the reference's rank layout (utils.py:98-109):
linear device index r maps to ``cfg_idx = r // n_device_per_batch`` and
``split_idx = r % n_device_per_batch``, so ``mesh.devices.reshape(cfg, sp)``
is row-major over the device list.

Multi-host pods: call `jax.distributed.initialize()` (via ``init_multihost``)
before constructing the config; `jax.devices()` then spans every host and the
same mesh code scales from one chip to a pod with collectives riding ICI/DCN.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .env import check_env, default_backend, is_power_of_2

# Axis names used across the whole framework.
DP_AXIS = "dp"
CFG_AXIS = "cfg"
SP_AXIS = "sp"
# USP (attn_impl="usp") factors the sp axis into two named sub-axes:
# all_to_all head-sharding rides SP_U, the exact KV ring rides SP_R.
SP_U_AXIS = "sp_u"
SP_R_AXIS = "sp_r"

SYNC_MODES = (
    "separate_gn",
    "stale_gn",
    "corrected_async_gn",
    "sync_gn",
    "full_sync",
    "no_sync",
)
PARALLELISMS = ("patch", "tensor", "naive_patch", "pipefusion")
SPLIT_SCHEMES = ("row", "col", "alternate")


def validate_step_cache_knobs(interval: int, depth: int) -> None:
    """The step-cache knob pairing contract, shared by DistriConfig and
    ServeConfig so the serve layer rejects a bad cadence at config time
    with the same rule the pipeline builder will enforce."""
    if interval < 1:
        raise ValueError(f"step_cache_interval must be >= 1, got {interval}")
    if depth < 0:
        raise ValueError(f"step_cache_depth must be >= 0, got {depth}")
    if (interval > 1) != (depth > 0):
        raise ValueError(
            "step-cache needs BOTH knobs: step_cache_interval >= 2 picks "
            "the full/shallow cadence and step_cache_depth >= 1 picks how "
            f"deep the shallow steps cut (got interval={interval}, "
            f"depth={depth})"
        )


def init_multihost(**kwargs: Any) -> None:
    """Multi-host bootstrap: the TPU analog of `torchrun` + NCCL rendezvous.

    The reference's process rendezvous is `dist.init_process_group("nccl")`
    inside DistriConfig (utils.py:40).  On a TPU pod slice the runtime already
    knows the topology; `jax.distributed.initialize` wires the hosts together
    and is a no-op on a single host.
    """
    try:
        jax.distributed.initialize(**kwargs)
    except (RuntimeError, ValueError) as e:
        # Already initialized, or single-process environment: mirror the
        # reference's graceful single-device fallback (utils.py:44-47),
        # which also prints the failure so pod misconfigurations are visible.
        print(f"jax.distributed.initialize failed ({e}); continuing single-process")


@dataclasses.dataclass
class DistriConfig:
    """All run parameters plus the device mesh.

    Field names follow the reference (utils.py:24-37) so users can port call
    sites unchanged; TPU-specific fields are appended at the end.
    ``use_cuda_graph`` is kept for API parity and exposed under its honest
    TPU name via the ``use_compiled_step`` property — on TPU the compiled
    jit step *is* the graph.
    """

    height: int = 1024
    width: int = 1024
    do_classifier_free_guidance: bool = True
    split_batch: bool = True
    warmup_steps: int = 4
    # Parity knob (utils.py:31): the reference flushes its async all-gather
    # queue every `comm_checkpoint` tensors to bound NCCL launch overhead.
    # XLA schedules and fuses collectives at compile time, so this has no
    # effect here; it is validated and carried for API compatibility.
    comm_checkpoint: int = 60
    mode: str = "corrected_async_gn"
    use_cuda_graph: bool = True  # parity alias; see use_compiled_step
    parallelism: str = "patch"
    split_scheme: str = "row"
    verbose: bool = False
    # Patch self-attention layout: "gather" assembles full KV per device
    # (reference-faithful, pp/attn.py:134-138); "ring" streams peer KV chunks
    # around the sp axis with ppermute + online softmax, shrinking per-layer
    # state from O(L) to O(L/n) — the idiomatic TPU long-context path.
    attn_impl: str = "gather"
    # attn_impl="usp" only: factor the sp axis into ulysses_degree (head-
    # sharding all_to_all sub-axis) x ring sub-axis — the xDiT-style USP
    # composition.  Must divide n_device_per_batch.
    ulysses_degree: int = 1
    # Batch the stale-phase refresh collectives into one flat exchange per
    # step (per collective kind) — the TPU-native analog of the reference's
    # `comm_checkpoint` buffer batching (utils.py:181-190).  Off by default:
    # per-layer deferred collectives give XLA's latency-hiding scheduler a
    # wider overlap window; turn on if an ICI profile shows per-collective
    # launch overhead dominating (~60 small collectives/step at 8-way).
    comm_batch: bool = False
    # Lossy compression of the stale-phase refresh payloads
    # (parallel/compress.py): "none" (default, bit-identical), "int8"
    # (symmetric per-tile int8 + fp32 scales, ~2x bf16 / ~4x fp32 byte
    # reduction), "fp8" (float8_e4m3fn payload where the jax build has it),
    # or "int8_residual" (int8 over the delta against the previous stale
    # value carried in the patch state — adjacent denoising steps are
    # near-identical, so the residual's dynamic range and hence the error
    # is far smaller).  Warmup/sync exchanges always stay full-precision;
    # GroupNorm moment exchanges never compress (tiny, cancellation-
    # sensitive).  Composes with comm_batch and the step cache.  Under
    # parallelism="pipefusion" the same knob compresses the inter-stage
    # activation ring hops instead (parallel/pipefusion.py; the residual
    # mode delta-codes against the previous step's chunk for the same
    # (patch, stage) pair); warmup mega-patch hops never compress.
    comm_compress: str = "none"
    # Quantized-weight serving (parallel/compress.py QuantizedTensor;
    # models/weights.py quantize_params): hold the DENOISER's matmul/conv
    # kernels as int8 (or fp8 where the jax build has float8_e4m3fn)
    # payloads with one fp32 scale per output-channel tile, dequantized on
    # the fly at the consuming dot/conv — XLA fuses the convert, so HBM
    # residency and weight streaming drop to ~1 byte/element.  "none"
    # (default) is bit-identical to today.  Norm/bias/embedding leaves
    # never quantize.  Composes with the step cache, comm_compress,
    # comm_batch, and the fused/stepwise loops.  PipeFusion quantizes its
    # stacked block tree BEFORE the depth split (the per-tile scales keep
    # the depth-leading layout, so shard_map slices payload and scale
    # alike and the stage-local payloads never densify); tensor
    # parallelism pre-shards its kernels eagerly and still rejects the
    # knob loudly.
    weight_quant: str = "none"
    # Same knob for the AUXILIARY models (CLIP/T5 text encoders + VAE):
    # a separate sub-knob because their tolerance budgets differ from the
    # denoiser's — the text embedding feeds every denoise step, and VAE
    # decode error lands directly in output pixels (docs/PERF.md
    # "Quantized weights" for the measured tolerances).
    weight_quant_aux: str = "none"
    # Quantized COMPUTE (ops/linear.py): how the weight_quant kernels
    # execute at their consuming matmuls.  "off" pins PR-6 storage-only
    # semantics (dequantize to the compute dtype, dense matmul — bytes
    # saved, zero FLOPs).  "auto" (default): a real int8/fp8 dot_general on
    # TPU at the MXU's 2x int8 MAC rate, with dynamic per-token activation
    # quantization and the per-channel-tile scale applied after the
    # accumulate, from 32 tokens up; dequant on CPU.  "dot" forces the
    # low-precision path (requires weight_quant != "none").  Changes
    # numerics vs "off" — activations quantize too; docs/PERF.md "Quantized
    # compute" pins the tolerances.  No effect when weight_quant="none".
    quant_compute: str = "auto"
    # Sequence-parallel VAE decode over the sp axis (exact: fresh halo convs,
    # psum'd GroupNorm, ring mid attention — models/vae.py decode_sp).  The
    # reference decodes the full latent replicated on every rank; this is n x
    # faster with 1/n the activation HBM.  Disable to replicate the dense
    # decode instead.
    vae_sp: bool = True
    # Hybrid loop (displaced patch only): sync warmup through the per-step
    # programs + ONE fused stale-only scan.  Same numerics as the fully
    # fused loop; the big program carries one UNet body instead of two, so
    # its (remote) compile roughly halves — the resilient choice when the
    # compile service is slow.  Per-step dispatch overhead applies only to
    # the warmup steps.
    hybrid_loop: bool = False
    # Temporal step-cache (parallel/stepcache.py): after warmup, run only
    # one FULL network evaluation every `step_cache_interval` steps; the
    # other steps execute just the shallow layers and reuse the carried
    # deep-block output (UNet: mid + deepest `step_cache_depth` levels;
    # DiT/MMDiT: the deepest `step_cache_depth` transformer blocks).  Off by
    # default (interval=1, depth=0); enable BOTH knobs together.  The
    # cadence is static per compilation — two requests differing only in
    # cadence run different XLA programs (serve keys them separately).
    step_cache_interval: int = 1
    step_cache_depth: int = 0
    # PCPP partial refresh (Partially Conditioned Patch Parallelism,
    # arXiv 2412.02962; parallel/context.py): fraction 1/k of each stale
    # step's refresh payload actually moves — step i refreshes only the
    # strided row group {i%k, i%k + k, ...} of every KV slab (token rows)
    # and conv halo (columns), the rest of the carried buffer stays as the
    # previous reconstruction (at most k steps stale).  Per-step refresh
    # bytes are exactly fraction x full; GroupNorm moments always refresh
    # whole (tiny, cancellation-sensitive — same exclusion as
    # comm_compress).  1.0 (default) is the exact DistriFusion protocol.
    # Composes with comm_compress and the step cache; requires
    # parallelism="patch" (the displaced-patch families) and is mutually
    # exclusive with comm_batch (the flat batched exchange assumes
    # whole-buffer records).  The fraction is part of the compiled
    # program's identity (serve ExecKey.refresh_fraction).
    refresh_fraction: float = 1.0
    # PipeFusion only (parallelism="pipefusion"): how many token-chunks
    # ("patches") stream through the pipeline stages.  None = one per
    # stage (the minimum); more patches shrink the per-hop payload and
    # deepen the overlap at the cost of more in-flight scheduler state.
    # Part of the compiled program's identity (serve ExecKey.pipe_patches).
    pipe_patches: Optional[int] = None

    # --- TPU-specific ---
    devices: Optional[Sequence[Any]] = None  # explicit device list (tests)
    dtype: Any = None  # computation/param dtype; default bf16 on tpu, f32 on cpu
    batch_size: int = 1  # images per CFG branch (total across dp groups)
    # Data parallelism over images — beyond the reference, which runs
    # multi-image sweeps as separate torchrun jobs (generate_coco.py --split,
    # SURVEY.md §2.1 "Data parallelism: no"). dp_degree independent image
    # groups each run cfg x sp displaced-patch generation.
    dp_degree: int = 1

    # derived (filled in __post_init__)
    world_size: int = dataclasses.field(init=False, default=1)
    n_device_per_batch: int = dataclasses.field(init=False, default=1)
    mesh: Mesh = dataclasses.field(init=False, default=None)

    def __post_init__(self) -> None:
        check_env()
        if self.mode not in SYNC_MODES:
            raise ValueError(f"mode must be one of {SYNC_MODES}, got {self.mode!r}")
        if self.parallelism not in PARALLELISMS:
            raise ValueError(
                f"parallelism must be one of {PARALLELISMS}, got {self.parallelism!r}"
            )
        if self.split_scheme not in SPLIT_SCHEMES:
            raise ValueError(
                f"split_scheme must be one of {SPLIT_SCHEMES}, got {self.split_scheme!r}"
            )
        if self.attn_impl not in ("gather", "ring", "ulysses", "usp"):
            raise ValueError(
                "attn_impl must be 'gather', 'ring', 'ulysses', or 'usp' "
                f"(ulysses/usp: DiT only), got {self.attn_impl!r}"
            )
        if self.ulysses_degree < 1:
            raise ValueError(
                f"ulysses_degree must be >= 1, got {self.ulysses_degree}"
            )
        if self.ulysses_degree > 1 and self.attn_impl != "usp":
            raise ValueError(
                "ulysses_degree applies to attn_impl='usp' only (pure "
                "head-sharding is attn_impl='ulysses')"
            )
        if self.height % 8 != 0 or self.width % 8 != 0:
            # Same constraint as the reference pipelines (pipelines.py:71).
            raise ValueError("height and width must be multiples of 8")
        # lazy import: parallel.compress imports SP_AXIS from this module
        from ..parallel.compress import validate_mode, validate_weight_mode

        validate_mode(self.comm_compress)
        if (self.comm_compress != "none"
                and self.parallelism not in ("patch", "pipefusion")):
            raise ValueError(
                "comm_compress targets the displaced-patch refresh "
                "exchanges (parallelism='patch') or the PipeFusion "
                f"inter-stage activation hops; {self.parallelism!r} has "
                "no stale refresh traffic to compress"
            )
        from ..parallel.compress import validate_refresh_fraction

        validate_refresh_fraction(self.refresh_fraction)
        if self.refresh_fraction < 1.0:
            if self.parallelism != "patch":
                raise ValueError(
                    "refresh_fraction < 1 (PCPP partial refresh) rides the "
                    "displaced-patch stale-refresh exchanges "
                    f"(parallelism='patch'); {self.parallelism!r} has no "
                    "per-step refresh traffic to thin"
                )
            if self.comm_batch:
                raise ValueError(
                    "refresh_fraction < 1 and comm_batch are mutually "
                    "exclusive: the flat batched exchange defers whole-"
                    "buffer records — use the per-layer deferred path for "
                    "partial refresh"
                )
        validate_weight_mode(self.weight_quant)
        validate_weight_mode(self.weight_quant_aux)
        from ..parallel.compress import validate_quant_compute

        validate_quant_compute(self.quant_compute, self.weight_quant)
        if self.weight_quant != "none" and self.parallelism == "tensor":
            raise ValueError(
                "weight_quant quantizes whole kernels ahead of the mesh "
                "split; parallelism='tensor' pre-shards its param tree "
                "eagerly and would silently densify the payloads — keep "
                "weight_quant='none' there (PipeFusion quantizes the "
                "stacked block tree before the depth split and is fine)"
            )
        validate_step_cache_knobs(self.step_cache_interval,
                                  self.step_cache_depth)
        if self.step_cache_enabled:
            if self.parallelism not in ("patch", "pipefusion"):
                raise ValueError(
                    "step-cache rides the displaced-patch carry state "
                    "(parallelism='patch') or the PipeFusion per-stage "
                    f"delta carry; {self.parallelism!r} has no cross-step "
                    "activation carry to stash the deep cache in"
                )
            if self.hybrid_loop:
                raise ValueError(
                    "step-cache and hybrid_loop are mutually exclusive: the "
                    "cadence adds a second (shallow) body to the steady-state "
                    "scan, defeating hybrid's one-body compile-time rationale "
                    "— use the fully fused loop with the step cache"
                )
        if self.pipe_patches is not None:
            if self.parallelism != "pipefusion":
                raise ValueError(
                    "pipe_patches configures the PipeFusion patch stream "
                    f"(parallelism='pipefusion'); {self.parallelism!r} has "
                    "no pipeline to stream patches through"
                )
            if self.pipe_patches < 1:
                raise ValueError(
                    f"pipe_patches must be >= 1, got {self.pipe_patches}"
                )

        if self.devices is None:
            try:
                self.devices = tuple(jax.devices())
            except RuntimeError as e:
                # Mirror the reference's explicit failure surface
                # (utils.py:44-47) with TPU guidance instead of hanging.
                raise RuntimeError(
                    "no usable JAX backend (TPU runtime failed to initialize "
                    "and no CPU fallback is configured); set JAX_PLATFORMS=cpu "
                    f"for a CPU run. Original error: {e}"
                ) from e
        else:
            self.devices = tuple(self.devices)
        world_size = len(self.devices)
        # Reference asserts power-of-2 world size (utils.py:49).
        assert is_power_of_2(world_size), "world size must be a power of 2"
        self.world_size = world_size

        if self.dp_degree < 1:
            raise ValueError(f"dp_degree must be >= 1, got {self.dp_degree}")
        if world_size % self.dp_degree != 0:
            raise ValueError(
                f"dp_degree {self.dp_degree} must divide world size {world_size}"
            )
        if self.batch_size % self.dp_degree != 0:
            raise ValueError(
                f"batch_size {self.batch_size} must be divisible by dp_degree "
                f"{self.dp_degree}"
            )
        group = world_size // self.dp_degree  # devices per image group

        if self.do_classifier_free_guidance and self.split_batch:
            self.n_device_per_batch = max(group // 2, 1)
        else:
            self.n_device_per_batch = group

        cfg_dim = group // self.n_device_per_batch  # 2 or 1
        dev_array = np.array(self.devices, dtype=object).reshape(
            self.dp_degree, cfg_dim, self.n_device_per_batch
        )
        self.mesh = Mesh(dev_array, axis_names=(DP_AXIS, CFG_AXIS, SP_AXIS))
        if self.attn_impl == "usp" and (
            self.n_device_per_batch % self.ulysses_degree != 0
        ):
            raise ValueError(
                f"ulysses_degree {self.ulysses_degree} must divide the sp "
                f"degree {self.n_device_per_batch}"
            )

        if self.dtype is None:
            import jax.numpy as jnp

            self.dtype = jnp.bfloat16 if default_backend() == "tpu" else jnp.float32

    # ------------------------------------------------------------------
    # Rank bookkeeping, kept for parity with the reference (utils.py:98-109).
    # In single-controller SPMD there is no per-process "rank"; these map a
    # linear device index to its mesh coordinates.
    # ------------------------------------------------------------------
    def usp_mesh(self) -> Mesh:
        """The 4-axis view of the same device grid for attn_impl='usp':
        sp factored into (SP_U_AXIS, SP_R_AXIS) with |sp_u| = ulysses_degree.
        Linearized (sp_u, sp_r) coordinates equal the 3-axis mesh's sp index,
        so rank bookkeeping (batch_idx/split_idx) is unchanged."""
        u = self.ulysses_degree
        n = self.n_device_per_batch
        cfg_dim = self.group_size // n
        dev_array = np.array(self.devices, dtype=object).reshape(
            self.dp_degree, cfg_dim, u, n // u
        )
        return Mesh(
            dev_array, axis_names=(DP_AXIS, CFG_AXIS, SP_U_AXIS, SP_R_AXIS)
        )

    @property
    def use_compiled_step(self) -> bool:
        """TPU-native alias for ``use_cuda_graph``: run the denoise loop as a
        single compiled program rather than per-step dispatch."""
        return self.use_cuda_graph

    @property
    def step_cache_enabled(self) -> bool:
        """Temporal step-cache cadence active? (parallel/stepcache.py)."""
        return self.step_cache_interval > 1 and self.step_cache_depth > 0

    @property
    def group_size(self) -> int:
        """Devices per image group (world / dp_degree)."""
        return self.world_size // self.dp_degree

    @property
    def cfg_split(self) -> bool:
        return (
            self.do_classifier_free_guidance
            and self.split_batch
            and self.group_size >= 2
        )

    def batch_idx(self, rank: int) -> int:
        """CFG-branch index of linear device `rank` (utils.py:98-104).

        The reference returns ``1 - int(rank < world//2)`` i.e. ranks
        [0, n) are branch 0 (unconditional), [n, 2n) branch 1 (conditional).
        With dp_degree > 1 the mapping applies within each image group.
        """
        if self.cfg_split:
            return (rank % self.group_size) // self.n_device_per_batch
        return 0

    def split_idx(self, rank: int) -> int:
        """Patch index of linear device `rank` (utils.py:106-109)."""
        return rank % self.n_device_per_batch

    def dp_idx(self, rank: int) -> int:
        """Image-group index of linear device `rank` (dp extension)."""
        return rank // self.group_size

    # latent-space geometry -------------------------------------------------
    @property
    def latent_height(self) -> int:
        return self.height // 8

    @property
    def latent_width(self) -> int:
        return self.width // 8

    def patch_height(self, scale: int = 1) -> int:
        """Rows per device at a given down-sampling scale of the latent."""
        h = self.latent_height // scale
        n = self.n_device_per_batch
        assert h % n == 0, (
            f"latent height {h} (scale {scale}) not divisible by {n} devices"
        )
        return h // n

    @property
    def is_sp(self) -> bool:
        """True when the spatial/sequence axis is actually split."""
        return self.parallelism in ("patch", "naive_patch") and self.n_device_per_batch > 1

    @property
    def mesh_plan(self) -> str:
        """Compact mesh descriptor, e.g. ``"dp1.cfg2.sp4"`` — part of the
        serve layer's compiled-executable cache key: two configs with the
        same resolution but different meshes compile different programs."""
        cfg_dim = self.group_size // self.n_device_per_batch
        return f"dp{self.dp_degree}.cfg{cfg_dim}.sp{self.n_device_per_batch}"

    def place(self, tree, specs=PartitionSpec()):
        """Commit a pytree's arrays to this mesh ONCE: ``specs`` is one
        PartitionSpec for every leaf (default: replicated) or a tree of
        them mirroring ``tree``.

        Runners place their weights with this at construction.  Arrays
        fresh from ``init_*_params`` or a checkpoint load are uncommitted
        on the default device; fed as they are to a program over this
        mesh, JAX re-transfers them on every dispatch — once per image in
        the fused loop, once per STEP in the stepwise/step modes — and a
        one-chip replica on any chip but the first reads its weights from
        chip 0 forever.  Abstract leaves (``eval_shape`` trees used for
        AOT lowering) have nothing to place and pass through.
        """

        def put(x, spec):
            if not isinstance(x, (jax.Array, np.ndarray)):
                return x
            return jax.device_put(x, NamedSharding(self.mesh, spec))

        if isinstance(specs, PartitionSpec):
            return jax.tree.map(lambda x: put(x, specs), tree)
        return jax.tree.map(put, tree, specs)


# Default resolution bucket table for the serve layer: the SDXL training
# resolutions ladder up to the repo's benchmarked 2048px high-res point.
DEFAULT_BUCKETS = (
    (512, 512),
    (768, 768),
    (1024, 1024),
    (1024, 2048),
    (2048, 1024),
    (2048, 2048),
)


@dataclasses.dataclass
class ObservabilityConfig:
    """Observability knobs for the serve layer (utils/trace.py +
    utils/metrics.py; docs/OBSERVABILITY.md); lives beside ServeConfig so
    one module owns every run-shaping knob.

    * ``trace`` — request-scoped tracing on/off.  Off (the default) the
      request path executes no tracing code at all (`InferenceServer`
      holds no Tracer); on, every request records its whole life as
      spans exportable via ``server.tracer.export(path)`` /
      ``server.dump_observability(dir)`` as Perfetto-loadable JSON.
    * ``trace_capacity`` — ring bound on retained trace records (oldest
      dropped first, drop count reported): bounded memory no matter how
      long the service runs, same convention as `RingLog`.
    * ``metrics_port`` — when not None, `server.start()` serves the
      unified `MetricsRegistry` over stdlib HTTP on this port
      (``/metrics`` Prometheus text, ``/metrics.json``, ``/healthz``);
      0 binds an ephemeral port (read ``server.metrics_endpoint.port``).
    * ``metrics_host`` — bind address for that endpoint.  Loopback by
      default (a metrics plane should not be world-readable by
      accident); set "0.0.0.0" for containerized deployments whose
      scraper lives outside the host.
    * ``slo_window`` — ring size of the per-SLO-class rolling p50/p99
      windows (`RollingQuantile`) — the signal ROADMAP item 3's
      closed-loop controller reads via ``server.slo_snapshot()``.
    * ``slo_max_age_s`` — maximum age of a sample in those windows
      (server clock).  Without it the windows are time-blind: completions
      from minutes ago keep steering the SLO controller long after the
      load that produced them is gone — an idle server would pin its old
      p99 forever.  Samples older than this are excluded from every
      quantile/snapshot read (the ring still holds them; they simply stop
      counting).  None disables aging.
    """

    trace: bool = False
    trace_capacity: int = 8192
    metrics_port: Optional[int] = None
    metrics_host: str = "127.0.0.1"
    slo_window: int = 512
    slo_max_age_s: Optional[float] = 300.0

    def __post_init__(self) -> None:
        if self.trace_capacity < 1:
            raise ValueError(
                f"trace_capacity must be >= 1, got {self.trace_capacity}"
            )
        if self.metrics_port is not None and not (
                0 <= int(self.metrics_port) <= 65535):
            raise ValueError(
                f"metrics_port must be in [0, 65535], got {self.metrics_port}"
            )
        if not self.metrics_host:
            raise ValueError("metrics_host must be a non-empty bind address")
        if self.slo_window < 1:
            raise ValueError(
                f"slo_window must be >= 1, got {self.slo_window}"
            )
        if self.slo_max_age_s is not None and self.slo_max_age_s <= 0:
            raise ValueError(
                f"slo_max_age_s must be > 0 or None, got {self.slo_max_age_s}"
            )


@dataclasses.dataclass
class StepBatchConfig:
    """Step-level continuous batching (serve/stepbatch.py `StepBatcher`);
    lives beside ServeConfig so one module owns every run-shaping knob.

    With ``enabled``, the server's denoise loop becomes a SLOT POOL of
    per-request (latent, PRNG, step-index, timestep-schedule) state:
    between any two denoise steps the scheduler admits queued requests
    into free slots, retires finished ones, reorders the step cohort by
    deadline slack (EDF over remaining-steps x calibrated per-step
    service), and can preempt the slackest running request mid-denoise —
    its slot state parks and later resumes bit-identically.  Executors
    run step-granular (``ExecKey.exec_mode="step"``, compile-distinct
    from the fused loop).  Mutually exclusive with ``pipeline_stages``
    (the staged pipeline owns whole batches; the slot pool owns steps)
    and with pipefusion buckets (no host-driven per-step loop exists
    there).

    Knobs:
      * ``slots`` — slot-pool capacity: how many requests hold denoise
        state (latents + patch carry) resident at once.  The HBM analog
        of ``max_inflight_batches``.
      * ``step_width`` — max slots advanced per scheduling round (0 =
        all occupied).  Below ``slots`` it turns EDF from an admission
        policy into true per-round step reordering: the cohort is the
        ``step_width`` tightest-slack slots.
      * ``preview_interval`` — every K steps an occupied slot emits a
        cheap downsampled-latent preview through the request's
        ``on_progress`` callback (0 disables).  Previews are host-side
        (no new compiled program) and traced as their own span.
      * ``preview_size`` — max edge length of the preview image (the
        latent decode is downsampled to at most this).
      * ``allow_preemption`` — let an arriving request that would miss
        its deadline park the occupied slot with the MOST deadline
        slack (state resumes bit-identically when a slot frees).
      * ``preempt_margin_s`` — a victim is only parked when its own
        slack exceeds the newcomer's shortfall by this margin, so
        preemption never trades one miss for another.
      * ``step_service_prior_s`` — per-step service-time estimate used
        for EDF slack until measured steps calibrate it (the controller's
        calibrated estimate takes over when the controller is on).
      * ``export_carries`` — on server stop/drain, serialize each
        resident request's denoise carry (serve/migration.py) and fail
        its future with `CarryExportedError` carrying the snapshot, so
        the fleet router can migrate the request to a healthy replica
        and resume at the SAME step instead of re-running from step 0.
        Off, stop falls back to the plain `ServerClosedError` path
        (every completed step is wasted and re-executed on retry).
      * ``pack_align`` — when ``step_width`` truncates the cohort, fill
        it with slots that share the EDF head's compiled step signature
        (same phase / patch-state stage / shallow flag — the grouping
        the executor packs into ONE dispatch) before the rest, so the
        width the round pays for lands in the fewest compiled calls.
        The tightest-slack request always runs first regardless; off,
        the cohort is the plain ``step_width`` tightest slots.
    """

    enabled: bool = False
    slots: int = 8
    step_width: int = 0
    preview_interval: int = 0
    preview_size: int = 64
    allow_preemption: bool = True
    preempt_margin_s: float = 0.0
    step_service_prior_s: float = 0.01
    export_carries: bool = True
    pack_align: bool = True

    def __post_init__(self) -> None:
        if self.slots < 1:
            raise ValueError(f"slots must be >= 1, got {self.slots}")
        if self.step_width < 0:
            raise ValueError(
                f"step_width must be >= 0 (0 = all occupied), got "
                f"{self.step_width}"
            )
        if self.preview_interval < 0:
            raise ValueError(
                f"preview_interval must be >= 0 (0 disables), got "
                f"{self.preview_interval}"
            )
        if self.preview_size < 1:
            raise ValueError(
                f"preview_size must be >= 1, got {self.preview_size}"
            )
        if self.preempt_margin_s < 0:
            raise ValueError(
                f"preempt_margin_s must be >= 0, got {self.preempt_margin_s}"
            )
        if self.step_service_prior_s <= 0:
            raise ValueError(
                "step_service_prior_s must be > 0, got "
                f"{self.step_service_prior_s}"
            )


@dataclasses.dataclass
class ResilienceConfig:
    """Failure-handling policy for the serve layer (serve/resilience.py);
    lives beside ServeConfig so one module owns every run-shaping knob.

    Retry/backoff:
      * ``max_retries`` — extra attempts per batch dispatch beyond the
        first (0 disables in-server retries).
      * ``retry_budget`` — GLOBAL retry token bucket across all requests;
        when a correlated failure storm empties it, failures surface
        immediately instead of amplifying load.
        ``retry_budget_refill_per_s`` trickles tokens back (up to the
        bucket size) so routine transient blips over days of uptime never
        permanently strip a long-lived server of retries; 0 makes the
        budget a strict lifetime cap.
      * ``backoff_*`` — exponential schedule between attempts:
        ``min(base * multiplier**n, max)`` with ± ``jitter`` fraction of
        seeded randomness (``seed``).

    Circuit breaking (per compiled-executor key):
      * ``breaker_failure_threshold`` consecutive TERMINAL dispatch
        failures (a batch whose retries were exhausted, a fatal error, a
        contract violation — never an individual retried attempt) trip
        the key's breaker OPEN; requests for it shed fast with
        `CircuitOpenError` (503-style) instead of burning queue time.
      * ``breaker_cooldown_s`` later the breaker goes HALF_OPEN and lets
        one probe batch through; success closes it, failure re-opens.

    Watchdog:
      * ``watchdog_timeout_s`` — wall-time bound on one batch execution;
        a hung batch fails with `WatchdogTimeoutError` (and is retried)
        while the scheduler thread keeps serving.  0 disables.

    Degradation ladder (OOM / compile failure, serve/resilience.py):
      * ``allow_batch_split`` — halve an OOM'd coalesced batch and retry
        the halves (bit-identical outputs: per-request seeded latents).
      * ``allow_step_cache_off`` — recompile the bucket without the
        temporal step-cache cadence.
      * ``allow_stepwise_fallback`` — swap the fused scan for the
        host-driven stepwise loop (same numerics, far smaller program).
      * ``allow_bucket_fallback`` — serve at the next smaller bucket;
        OFF by default because it changes the output-resolution contract.
      * ``max_degradations`` — cap on sticky per-key rungs.
    """

    max_retries: int = 2
    retry_budget: int = 10_000
    retry_budget_refill_per_s: float = 1.0
    backoff_base_s: float = 0.05
    backoff_multiplier: float = 2.0
    backoff_max_s: float = 2.0
    backoff_jitter: float = 0.1
    breaker_failure_threshold: int = 3
    breaker_cooldown_s: float = 5.0
    watchdog_timeout_s: float = 120.0
    max_degradations: int = 3
    # LRU bound on per-key resilience state (breakers, degradation rungs):
    # ExecKey space is request-controlled, so tracked keys — and the
    # health payload serializing them — must not grow one entry per
    # distinct key ever seen.  Eviction prefers closed/undegraded state.
    max_tracked_keys: int = 256
    allow_batch_split: bool = True
    # staged servers only (ServeConfig.pipeline_stages): let the ladder
    # stop pipelining an OOM-ing key's batches — overlap holds up to
    # max_inflight_batches of residency, the cheapest HBM to give back,
    # and the rung changes neither the program nor the numerics
    allow_staging_off: bool = True
    allow_step_cache_off: bool = True
    # PipeFusion keys only (ExecKey.parallelism="pipefusion"): on OOM or
    # compile failure, rebuild the key as displaced patch parallelism
    # (parallelism="patch", pipe_patches dropped) — the degraded key is
    # EXACTLY the key a patch-parallel bucket would use, so the rebuild is
    # bit-identical to a fresh patch executor for the same bucket.  This
    # replaces stepwise_fallback for pipefusion keys (the fused tick
    # schedule has no host-driven stepwise loop to fall back to; the
    # stepwise rung never applies to them).  ON by default: the
    # alternative for a failing pipefusion key is no program-level rung at
    # all.  Outputs change only as much as the two parallelization
    # strategies differ (both are tolerance-pinned against the same
    # oracles).
    allow_pipeline_off: bool = True
    allow_stepwise_fallback: bool = True
    # OOM/compile ladder rung below stepwise: rebuild the key with int8
    # quantized weights (ExecKey.weight_quant="int8") — roughly halves the
    # executor's weight HBM, the biggest single give-back on the ladder.
    # OFF by default because, unlike the rungs above it, outputs change
    # (within the pinned parity tolerances, docs/PERF.md "Quantized
    # weights"); opt in like bucket_fallback when availability under OOM
    # outranks bit-stability.
    allow_weight_quant_on: bool = False
    allow_bucket_fallback: bool = False
    last_errors_capacity: int = 16
    seed: int = 0

    def __post_init__(self) -> None:
        if self.max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {self.max_retries}")
        if self.retry_budget < 0:
            raise ValueError(
                f"retry_budget must be >= 0, got {self.retry_budget}"
            )
        if self.retry_budget_refill_per_s < 0:
            raise ValueError(
                "retry_budget_refill_per_s must be >= 0, got "
                f"{self.retry_budget_refill_per_s}"
            )
        if self.backoff_base_s < 0 or self.backoff_max_s < self.backoff_base_s:
            raise ValueError(
                "need 0 <= backoff_base_s <= backoff_max_s, got "
                f"base={self.backoff_base_s}, max={self.backoff_max_s}"
            )
        if self.backoff_multiplier < 1.0:
            raise ValueError(
                f"backoff_multiplier must be >= 1, got {self.backoff_multiplier}"
            )
        if not (0.0 <= self.backoff_jitter < 1.0):
            raise ValueError(
                f"backoff_jitter must be in [0, 1), got {self.backoff_jitter}"
            )
        if self.breaker_failure_threshold < 1:
            raise ValueError(
                "breaker_failure_threshold must be >= 1, got "
                f"{self.breaker_failure_threshold}"
            )
        if self.breaker_cooldown_s < 0:
            raise ValueError(
                f"breaker_cooldown_s must be >= 0, got {self.breaker_cooldown_s}"
            )
        if self.max_degradations < 0:
            raise ValueError(
                f"max_degradations must be >= 0, got {self.max_degradations}"
            )
        if self.max_tracked_keys < 1:
            raise ValueError(
                f"max_tracked_keys must be >= 1, got {self.max_tracked_keys}"
            )
        if self.last_errors_capacity < 1:
            raise ValueError(
                "last_errors_capacity must be >= 1, got "
                f"{self.last_errors_capacity}"
            )


@dataclasses.dataclass
class ControllerConfig:
    """Closed-loop SLO controller policy (serve/controller.py); lives
    beside ServeConfig so one module owns every run-shaping knob.

    The controller walks an ordered *tier table* over the quality/cost
    lattice per SLO class — full quality first, then progressively
    cheaper compiled programs (step cache, wire compression, PCPP partial
    refresh, reduced steps), with admission control past the last tier —
    and dispatches each batch at the least-degraded tier whose PREDICTED
    latency holds the class's p99 target under the current queue depth
    and rolling windows (``server.slo_snapshot()``).  All decisions run
    on the injected server clock, so replayed load produces identical
    tier walks.

    Knobs:
      * ``enabled`` — off (default) keeps today's behavior exactly: no
        controller object is built, no per-dispatch work added.
      * ``slo_p99_s`` — {slo_class: p99 target seconds}.  Classes absent
        from the map use the ``"default"`` entry (one is required).
      * ``tiers`` — the tier table (serve/controller.py TierSpec list);
        () uses the built-in DEFAULT_TIERS.  Validated: unique names,
        strictly decreasing predicted-cost multipliers, first tier cost
        1.0 (the identity/full tier).
      * ``escalate_cooldown_s`` / ``retract_cooldown_s`` — minimum time
        between tier moves per class, one rung per move (the hysteresis
        that keeps a boundary load from flapping).  Retraction (back
        toward full quality) additionally requires the richer tier's
        predicted latency to hold with ``retract_margin`` headroom.
      * ``min_samples`` — observed-p99 breach checks wait for this many
        live window samples (prediction steers from the first dispatch).
      * ``service_prior_s`` — per-batch service-time estimate used until
        real completions calibrate it (``service_window`` ring).
      * ``encode_share`` — fraction of a batch's service time spent in
        text-encode: with a prompt cache attached, predicted service
        scales by ``1 - encode_share * hit_rate`` (a cache hit is a
        cheaper tier input).
    """

    enabled: bool = False
    slo_p99_s: Any = dataclasses.field(
        default_factory=lambda: {"default": 2.0}
    )
    tiers: Sequence[Any] = ()
    escalate_cooldown_s: float = 0.25
    retract_cooldown_s: float = 1.0
    retract_margin: float = 0.6
    min_samples: int = 4
    service_prior_s: float = 0.05
    service_window: int = 32
    encode_share: float = 0.0

    def __post_init__(self) -> None:
        slo = dict(self.slo_p99_s or {})
        if "default" not in slo:
            raise ValueError(
                "slo_p99_s needs a 'default' entry — classes absent from "
                "the map fall back to it"
            )
        for cls, target in slo.items():
            if float(target) <= 0:
                raise ValueError(
                    f"slo_p99_s[{cls!r}] must be > 0, got {target}"
                )
        self.slo_p99_s = {str(c): float(t) for c, t in slo.items()}
        if self.escalate_cooldown_s < 0 or self.retract_cooldown_s < 0:
            raise ValueError(
                "cooldowns must be >= 0, got escalate="
                f"{self.escalate_cooldown_s}, retract="
                f"{self.retract_cooldown_s}"
            )
        if not (0.0 < self.retract_margin <= 1.0):
            raise ValueError(
                f"retract_margin must be in (0, 1], got {self.retract_margin}"
            )
        if self.min_samples < 1:
            raise ValueError(
                f"min_samples must be >= 1, got {self.min_samples}"
            )
        if self.service_prior_s <= 0:
            raise ValueError(
                f"service_prior_s must be > 0, got {self.service_prior_s}"
            )
        if self.service_window < 1:
            raise ValueError(
                f"service_window must be >= 1, got {self.service_window}"
            )
        if not (0.0 <= self.encode_share < 1.0):
            raise ValueError(
                f"encode_share must be in [0, 1), got {self.encode_share}"
            )
        # Lazy import, same convention as BucketTable below: the serve
        # package imports this module at load time.  Normalization owns
        # the tier-table invariants (ordering, knob validity) in ONE place.
        from ..serve.controller import normalize_tier_table

        self.tiers = normalize_tier_table(self.tiers)


@dataclasses.dataclass
class AotCacheConfig:
    """Persistent AOT executable store (serve/aotcache.py): compiled
    denoise programs serialized to a content-addressed on-disk cache so
    a fresh replica warms from deserialized executables in seconds
    instead of paying the full XLA compile campaign (the elastic-
    autoscale gate, ROADMAP item 2).

    * ``dir`` — store directory; None (default) disables the store
      entirely.  Replicas sharing a config share the directory, which
      is the point: a scale-up replica warms from an earlier replica's
      compiles.
    * ``max_bytes`` — on-disk byte budget; least-recently-LOADED
      entries evict first once a save pushes the total over.
    * ``readonly`` — CI/canary mode: loads serve, saves count a skip
      and write nothing (a test run never grows or reorders the shared
      store).
    """

    dir: Optional[str] = None
    max_bytes: int = 2 * 1024**3
    readonly: bool = False

    def __post_init__(self) -> None:
        if self.max_bytes < 1:
            raise ValueError(
                f"aot_cache.max_bytes must be >= 1, got {self.max_bytes}"
            )


@dataclasses.dataclass
class AutoscaleConfig:
    """Elastic replica-pool autoscaling (serve/autoscale.py
    `Autoscaler`, driven from the fleet housekeeping tick).

    Pressure is the fleet's step-granular utilization: (occupied step
    slots + queued/parked work, weighted by remaining steps) over the
    SERVING replicas' slot capacity — the PR-15 occupancy model the SLO
    controller already trusts.  Sustained pressure above
    ``pressure_high`` for ``up_sustain_s`` starts one stopped replica
    (warm-from-cache when an `aot_cache` store is configured);
    sustained pressure below ``pressure_low`` for ``down_sustain_s``
    drains one (bounded by ``drain_deadline_s`` — the drain rides the
    PR-17 carry-migration path, so scale-down discards no steps).
    ``cooldown_s`` separates consecutive scale actions so one load
    swing never slams the pool between bounds; ``min_replicas`` /
    ``max_replicas`` (0 = every configured slot) bound the pool.
    """

    enabled: bool = False
    min_replicas: int = 1
    max_replicas: int = 0
    pressure_high: float = 0.8
    pressure_low: float = 0.25
    up_sustain_s: float = 0.5
    down_sustain_s: float = 5.0
    cooldown_s: float = 5.0
    drain_deadline_s: float = 30.0

    def __post_init__(self) -> None:
        if self.min_replicas < 1:
            raise ValueError(
                f"autoscale.min_replicas must be >= 1, got "
                f"{self.min_replicas}"
            )
        if self.max_replicas < 0:
            raise ValueError(
                "autoscale.max_replicas must be >= 0 (0 = all configured "
                f"replicas), got {self.max_replicas}"
            )
        if self.max_replicas and self.max_replicas < self.min_replicas:
            raise ValueError(
                f"autoscale.max_replicas ({self.max_replicas}) must be >= "
                f"min_replicas ({self.min_replicas})"
            )
        if self.pressure_high <= 0:
            raise ValueError(
                f"autoscale.pressure_high must be > 0, got "
                f"{self.pressure_high}"
            )
        if not (0.0 <= self.pressure_low < self.pressure_high):
            raise ValueError(
                "autoscale.pressure_low must be in [0, pressure_high), "
                f"got {self.pressure_low} (high={self.pressure_high})"
            )
        for name in ("up_sustain_s", "down_sustain_s", "cooldown_s"):
            if getattr(self, name) < 0:
                raise ValueError(
                    f"autoscale.{name} must be >= 0, got "
                    f"{getattr(self, name)}"
                )
        if self.drain_deadline_s <= 0:
            raise ValueError(
                "autoscale.drain_deadline_s must be > 0, got "
                f"{self.drain_deadline_s}"
            )


@dataclasses.dataclass
class FleetConfig:
    """Multi-replica fleet policy (serve/fleet.py `FleetRouter`); lives
    beside ServeConfig so one module owns every run-shaping knob.

    Routing and health scoring:
      * Each replica is scored in [0, 1] from its own serve signals
        (`Replica.health_score`): open-circuit share, SLO-controller tier
        depth, and rolling p99 vs ``p99_ref_s`` (None skips the latency
        term).  The router dispatches to the serving replica maximizing
        ``score * capacity_weight / (1 + queue_depth + inflight)`` —
        weighted least-degraded, so mixed-capability replicas
        (``Replica.capacity_weight``) are held to one SLO by steering
        load toward spare healthy capacity.

    Failover:
      * A replica's TERMINAL dispatch failure (retries exhausted,
        circuit open, watchdog, replica killed) re-dispatches the request
        onto a different replica, at most ``max_failovers`` times per
        request, each drawing from the fleet-wide `RetryBudget`
        (``failover_budget`` + ``failover_budget_refill_per_s`` — the
        same storm-bounding token bucket the in-server retry loop uses).
        A request is only ever re-dispatched after its prior replica's
        outcome is terminal, so its result is delivered exactly once and
        a dispatch that failed before completing never runs twice (a
        watchdog-ABANDONED dispatch may still finish in the background
        with its result discarded — the single-server watchdog caveat,
        unchanged).  When no replica can take the request right now it
        is PARKED in the router and re-dispatched from the housekeeping
        tick.

    Fleet-level graceful degradation (the per-key `CircuitBreaker`
    semantics lifted one level up):
      * ``health_floor`` — a serving replica whose score reaches this
        floor is auto-DRAINED (stops admitting, finishes in-flight);
        so is one that accumulates ``drain_failure_threshold``
        consecutive terminal failures.
      * ``probe_cooldown_s`` later the drained replica is probed
        half-open style: exactly one live request routes to it; success
        returns it to serving, failure re-drains and re-arms the
        cooldown.
      * ``auto_restart`` (+ ``restart_cooldown_s``) — a replica whose
        server STOPPED (e.g. the ``"replica"`` fault site's kill) is
        rebuilt and re-warmed in the background instead of probed.

    ``tick_s`` is the housekeeping cadence (auto-drain checks, probe
    arming, parked re-dispatch); 0 disables the tick thread — tests
    drive `FleetRouter.tick()` manually on an injected clock.
    """

    health_floor: float = 0.05
    drain_failure_threshold: int = 3
    probe_cooldown_s: float = 5.0
    max_failovers: int = 3
    failover_budget: int = 10_000
    failover_budget_refill_per_s: float = 1.0
    tick_s: float = 0.05
    p99_ref_s: Optional[float] = None
    auto_restart: bool = False
    restart_cooldown_s: float = 10.0
    # Elastic pool sizing between min/max bounds from the step-granular
    # occupancy model, riding drain/warm-up + carry migration so scale
    # events drop no steps — see AutoscaleConfig above and
    # docs/SERVING.md "AOT cache & elastic autoscale".  Off by default.
    autoscale: "AutoscaleConfig" = dataclasses.field(
        default_factory=AutoscaleConfig
    )

    def __post_init__(self) -> None:
        if not isinstance(self.autoscale, AutoscaleConfig):
            raise ValueError(
                "autoscale must be an AutoscaleConfig, got "
                f"{type(self.autoscale).__name__}"
            )
        if not (0.0 <= self.health_floor < 1.0):
            raise ValueError(
                f"health_floor must be in [0, 1), got {self.health_floor}"
            )
        if self.drain_failure_threshold < 1:
            raise ValueError(
                "drain_failure_threshold must be >= 1, got "
                f"{self.drain_failure_threshold}"
            )
        if self.probe_cooldown_s < 0:
            raise ValueError(
                f"probe_cooldown_s must be >= 0, got {self.probe_cooldown_s}"
            )
        if self.max_failovers < 0:
            raise ValueError(
                f"max_failovers must be >= 0, got {self.max_failovers}"
            )
        if self.failover_budget < 0:
            raise ValueError(
                f"failover_budget must be >= 0, got {self.failover_budget}"
            )
        if self.failover_budget_refill_per_s < 0:
            raise ValueError(
                "failover_budget_refill_per_s must be >= 0, got "
                f"{self.failover_budget_refill_per_s}"
            )
        if self.tick_s < 0:
            raise ValueError(f"tick_s must be >= 0, got {self.tick_s}")
        if self.p99_ref_s is not None and self.p99_ref_s <= 0:
            raise ValueError(
                f"p99_ref_s must be > 0 or None, got {self.p99_ref_s}"
            )
        if self.restart_cooldown_s < 0:
            raise ValueError(
                "restart_cooldown_s must be >= 0, got "
                f"{self.restart_cooldown_s}"
            )


@dataclasses.dataclass
class TenantConfig:
    """One tenant's share of the serve plane (serve/tenancy.py).

    * ``weight`` — relative long-run share of scheduler service under
      contention: the deficit-round-robin queue credits each tenant
      ``drr_quantum * weight`` denoise steps per round, so a weight-3
      tenant sustains 3x a weight-1 tenant's step throughput when both
      are backlogged.  Idle share is never reserved — a lone tenant gets
      the whole scheduler regardless of weight.
    * ``rate_rps`` / ``burst`` — token-bucket admission quota: sustained
      requests/second and the bucket capacity (how large an instant
      burst admits before the rate limit bites).  ``rate_rps=0`` means
      unlimited (no bucket); ``burst=0`` with a positive rate defaults
      the capacity to ``max(1, rate_rps)``.
    """

    name: str
    weight: float = 1.0
    rate_rps: float = 0.0
    burst: float = 0.0

    def __post_init__(self) -> None:
        if not isinstance(self.name, str) or not self.name:
            raise ValueError(
                f"tenant name must be a non-empty string, got {self.name!r}"
            )
        if self.weight <= 0:
            raise ValueError(
                f"tenant {self.name!r}: weight must be > 0, got {self.weight}"
            )
        if self.rate_rps < 0:
            raise ValueError(
                f"tenant {self.name!r}: rate_rps must be >= 0, got "
                f"{self.rate_rps}"
            )
        if self.burst < 0:
            raise ValueError(
                f"tenant {self.name!r}: burst must be >= 0, got {self.burst}"
            )
        if self.rate_rps > 0 and self.burst == 0:
            self.burst = max(1.0, float(self.rate_rps))


@dataclasses.dataclass
class GatewayConfig:
    """HTTP/SSE gateway + multi-tenancy block (serve/gateway.py,
    serve/tenancy.py; docs/SERVING.md "Gateway & multi-tenancy").

    * ``port`` — gateway listen port (0 = ephemeral); None means no
      gateway is auto-started (the tenancy knobs still apply to
      in-process submits).
    * ``tenants`` — the tenant table.  Empty (default) disables tenant
      accounting entirely: the queue stays the PR-15 pure-EDF queue.
      Non-empty activates per-tenant token buckets + weighted DRR; a
      tenant named ``default_tenant`` is implicitly added (weight 1,
      unlimited rate) if absent, so untagged requests keep working.
    * ``drr_quantum`` — denoise-step credit added to a backlogged
      tenant's deficit per round-robin pass (scaled by its weight).
      Larger quanta batch a tenant's turns together (fewer executor
      key switches); smaller quanta interleave tenants more finely.
    * ``max_events`` — per-request SSE buffer depth; a slow consumer's
      preview frames drop OLDEST beyond this (counted, never blocking
      the scheduler thread).  Terminal events are never dropped.
    * ``max_threads`` — bound on concurrent gateway handler threads
      (excess connections wait in the listen backlog).
    * ``max_requests`` — retention bound on the gateway's connection
      table; oldest FINISHED entries are evicted beyond it (pending
      entries are never evicted).
    """

    port: Optional[int] = None
    host: str = "127.0.0.1"
    tenants: Sequence["TenantConfig"] = ()
    default_tenant: str = "default"
    drr_quantum: float = 8.0
    max_events: int = 64
    max_threads: int = 8
    max_requests: int = 1024

    def __post_init__(self) -> None:
        if self.port is not None and int(self.port) < 0:
            raise ValueError(f"gateway port must be >= 0, got {self.port}")
        seen = set()
        for t in self.tenants:
            if not isinstance(t, TenantConfig):
                raise ValueError(
                    f"tenants entries must be TenantConfig, got "
                    f"{type(t).__name__}"
                )
            if t.name in seen:
                raise ValueError(f"duplicate tenant name {t.name!r}")
            seen.add(t.name)
        self.tenants = tuple(self.tenants)
        if not self.default_tenant:
            raise ValueError("default_tenant must be non-empty")
        if self.drr_quantum <= 0:
            raise ValueError(
                f"drr_quantum must be > 0, got {self.drr_quantum}"
            )
        if self.max_events < 2:
            raise ValueError(
                f"max_events must be >= 2 (room for one preview plus the "
                f"terminal event), got {self.max_events}"
            )
        if self.max_threads < 1:
            raise ValueError(
                f"max_threads must be >= 1, got {self.max_threads}"
            )
        if self.max_requests < 1:
            raise ValueError(
                f"max_requests must be >= 1, got {self.max_requests}"
            )


@dataclasses.dataclass
class ServeConfig:
    """Configuration block for ``distrifuser_tpu.serve`` (the long-lived
    inference service).  Kept here, beside DistriConfig, so one module owns
    every run-shaping knob; the serve subsystem never invents defaults.

    Admission control:
      * ``max_queue_depth`` — bound on requests waiting for a batch slot;
        submissions beyond it are rejected 429-style (QueueFullError), the
        backpressure signal for upstream load balancers.
      * ``default_ttl_s`` — per-request deadline when the caller gives none;
        a request that waits past its deadline is *rejected*, never executed
        (late work is wasted mesh time).

    Micro-batching:
      * ``max_batch_size`` — cap on requests coalesced into one invocation.
      * ``batch_window_s`` — how long the batcher lingers for compatible
        followers after the first request of a batch arrives.  0 disables
        coalescing-by-wait (batches still form from a backlog).

    Shape bucketing / compiled cache:
      * ``buckets`` — (height, width) table; a request snaps to the smallest
        bucket covering it, so the compiled program for a bucket is reused
        across nearby resolutions.
      * ``cache_capacity`` — LRU bound on resident compiled executables.
      * ``warmup_buckets`` — (height, width[, steps]) tuples compiled at
        startup so steady-state traffic never pays a request-path retrace;
        ``warmup_cfg`` is the guidance mode they compile for (match it to
        your traffic — a CFG-off service warming cfg=True executors buys
        nothing and burns an LRU slot).
    """

    max_queue_depth: int = 64
    default_ttl_s: float = 120.0
    max_batch_size: int = 8
    batch_window_s: float = 0.02
    buckets: Sequence[Sequence[int]] = DEFAULT_BUCKETS
    cache_capacity: int = 8
    warmup_buckets: Sequence[Sequence[int]] = ()
    warmup_cfg: bool = True
    default_steps: int = 50
    # Service-wide step-cache cadence (DistriConfig.step_cache_* semantics):
    # threaded into every ExecKey so a cadence change invalidates compiled
    # executors, and surfaced as the shallow-step share in serve metrics.
    # The pipeline builder behind executor_factory must construct its
    # DistriConfig with the same knobs.
    step_cache_interval: int = 1
    step_cache_depth: int = 0
    # Service-wide stale-refresh compression (DistriConfig.comm_compress
    # semantics): threaded into every ExecKey — a mode change invalidates
    # compiled executors, the same contract as the cadence knobs.  The
    # pipeline builder behind executor_factory must construct its
    # DistriConfig with the same mode.
    comm_compress: str = "none"
    # Service-wide DENOISER weight quantization (DistriConfig.weight_quant
    # semantics): threaded into every ExecKey — full-precision and
    # quantized executables are different compiled programs and coexist in
    # one fleet under distinct keys.  The pipeline builder behind
    # executor_factory must construct its DistriConfig with the same mode
    # (serve.executors.apply_key_policy force-quantizes builders that
    # ignore the field, so ladder-degraded keys work against any builder).
    # The aux-model sub-knob (weight_quant_aux) stays a builder decision:
    # it is fixed per builder, so it needs no per-key identity.
    weight_quant: str = "none"
    # Service-wide quantized-COMPUTE policy (DistriConfig.quant_compute
    # semantics): threaded into every ExecKey — storage-only ("off") and
    # compute-routed ("auto"/"dot") programs trace different
    # matmul paths, so they are distinct executables.  "auto" (default)
    # means the PR-9 tier ladder's int8 rungs and the fleet inherit the
    # low-precision execution path with no further serve-layer changes.
    quant_compute: str = "auto"
    # Service-wide PCPP partial-refresh fraction (DistriConfig.
    # refresh_fraction semantics): threaded into every ExecKey — the
    # strided refresh schedule is traced into the program, so a fraction
    # change is a different executable.  1.0 (default) is the exact
    # protocol; the SLO controller's partial_refresh tier overrides this
    # per dispatch.  The pipeline builder behind executor_factory must
    # construct its DistriConfig from key.refresh_fraction
    # (serve.executors.apply_key_policy forces the field pre-prepare).
    refresh_fraction: float = 1.0
    # Service-wide parallelization strategy (DistriConfig.parallelism
    # semantics, "patch" or "pipefusion"): threaded into every ExecKey —
    # patch-parallel and pipeline-parallel executors are different XLA
    # programs coexisting in one fleet under distinct keys.  The builder
    # behind executor_factory must construct its DistriConfig from
    # key.parallelism (serve.executors.apply_key_policy rejects a
    # mismatch with a typed error so the ladder can retract).
    parallelism: str = "patch"
    # With parallelism="pipefusion": DistriConfig.pipe_patches for the
    # built pipelines (None = one patch per stage), a compile-identity
    # field on ExecKey like the cadence knobs.
    pipe_patches: Optional[int] = None
    # Per-resolution-bucket strategy overrides: {(height, width):
    # "patch" | "pipefusion"} keyed by BUCKET (post-snap) resolution.
    # PipeFusion wins at high resolution and deep meshes (docs/PERF.md
    # "When pipeline beats displaced patches"); the map lets one fleet
    # serve small buckets patch-parallel and big buckets
    # pipeline-parallel simultaneously.  Buckets absent from the map use
    # the service-wide ``parallelism``.
    bucket_parallelism: Any = dataclasses.field(default_factory=dict)
    # Staged pipelining (serve/staging.py, docs/SERVING.md "Staged
    # pipelining"): overlap text-encode, denoise, and VAE-decode across
    # micro-batches so batch k+1 encodes and batch k-1 decodes in the
    # shadow of batch k's denoise.  Off by default: staged and monolithic
    # execution are bit-identical per request, but staging holds up to
    # ``max_inflight_batches`` batches of device buffers resident (the
    # HBM cap) and trades the in-line retry loop for throughput (a stage
    # failure is one terminal dispatch failure; sticky degradations —
    # including the staging_off rung — handle repeat offenders).
    pipeline_stages: bool = False
    max_inflight_batches: int = 2
    # Step-level continuous batching (serve/stepbatch.py, docs/SERVING.md
    # "Step-level continuous batching"): the denoise loop becomes a slot
    # pool of per-request state — requests join and leave the in-flight
    # denoise BETWEEN STEPS, the cohort reorders by deadline slack (EDF),
    # low-slack arrivals can preempt the slackest slot (park + bit-
    # identical resume), and occupied slots stream cheap latent previews
    # every K steps.  Executors key at ExecKey.exec_mode="step" (compile-
    # distinct).  Off by default; see StepBatchConfig above.  Mutually
    # exclusive with pipeline_stages and with pipefusion parallelism.
    step_batching: "StepBatchConfig" = dataclasses.field(
        default_factory=StepBatchConfig
    )
    # Prompt/embedding LRU cache in front of the text-encode stage
    # (serve/promptcache.py): repeated prompts — the dominant production
    # pattern — skip text-encode entirely.  Keyed by (family, tokenizer
    # hash, prompt chunk); hit rate lands in the MetricsRegistry
    # (serve_prompt_cache) and feeds the SLO controller's predicted
    # service time (ControllerConfig.encode_share).  0 (default) disables.
    prompt_cache_capacity: int = 0
    # Closed-loop SLO controller (serve/controller.py, docs/SERVING.md
    # "Closed-loop SLO control"): load-driven tier selection over the
    # quality/cost lattice per slo_class, with admission control at the
    # extreme.  Off by default — see ControllerConfig above.
    controller: "ControllerConfig" = dataclasses.field(
        default_factory=ControllerConfig
    )
    # Failure handling: retries/backoff, per-key circuit breakers, the
    # execution watchdog, and the graceful-degradation ladder — see
    # ResilienceConfig above and docs/SERVING.md "Failure modes & tuning".
    resilience: ResilienceConfig = dataclasses.field(
        default_factory=ResilienceConfig
    )
    # Tracing + metrics plane: request-scoped spans, the unified
    # MetricsRegistry HTTP endpoint, and the per-SLO-class rolling
    # latency windows — see ObservabilityConfig above and
    # docs/OBSERVABILITY.md.
    observability: ObservabilityConfig = dataclasses.field(
        default_factory=ObservabilityConfig
    )
    # HTTP/SSE gateway + per-tenant fair queuing (serve/gateway.py,
    # serve/tenancy.py): the wire front end over submit(), and the
    # tenant table that turns the request queue into token-bucket +
    # weighted-DRR fair queuing — see GatewayConfig above and
    # docs/SERVING.md "Gateway & multi-tenancy".
    gateway: GatewayConfig = dataclasses.field(default_factory=GatewayConfig)
    # Persistent AOT executable store (serve/aotcache.py): warmup and
    # ladder rebuilds consult it before compiling and populate it on
    # miss, so a fresh replica warms from serialized executables instead
    # of a compile campaign — see AotCacheConfig above and
    # docs/SERVING.md "AOT cache & elastic autoscale".  Disabled unless
    # ``aot_cache.dir`` is set.
    aot_cache: "AotCacheConfig" = dataclasses.field(
        default_factory=AotCacheConfig
    )

    def __post_init__(self) -> None:
        if self.max_queue_depth < 1:
            raise ValueError(
                f"max_queue_depth must be >= 1, got {self.max_queue_depth}"
            )
        if self.max_batch_size < 1:
            raise ValueError(
                f"max_batch_size must be >= 1, got {self.max_batch_size}"
            )
        if self.default_ttl_s <= 0:
            raise ValueError(
                f"default_ttl_s must be > 0, got {self.default_ttl_s}"
            )
        if self.batch_window_s < 0:
            raise ValueError(
                f"batch_window_s must be >= 0, got {self.batch_window_s}"
            )
        if self.cache_capacity < 1:
            raise ValueError(
                f"cache_capacity must be >= 1, got {self.cache_capacity}"
            )
        if self.max_inflight_batches < 1:
            raise ValueError(
                "max_inflight_batches must be >= 1, got "
                f"{self.max_inflight_batches}"
            )
        if self.prompt_cache_capacity < 0:
            raise ValueError(
                "prompt_cache_capacity must be >= 0, got "
                f"{self.prompt_cache_capacity}"
            )
        validate_step_cache_knobs(self.step_cache_interval,
                                  self.step_cache_depth)
        from ..parallel.compress import (
            validate_mode,
            validate_quant_compute,
            validate_refresh_fraction,
            validate_weight_mode,
        )

        validate_mode(self.comm_compress)
        validate_refresh_fraction(self.refresh_fraction)
        validate_weight_mode(self.weight_quant)
        validate_quant_compute(self.quant_compute, self.weight_quant)
        _SERVE_PARALLELISMS = ("patch", "pipefusion")
        if self.parallelism not in _SERVE_PARALLELISMS:
            raise ValueError(
                f"ServeConfig.parallelism must be one of "
                f"{_SERVE_PARALLELISMS}, got {self.parallelism!r}"
            )
        if self.pipe_patches is not None and int(self.pipe_patches) < 1:
            raise ValueError(
                f"pipe_patches must be >= 1, got {self.pipe_patches}"
            )
        norm_bp = {}
        for hw, strat in dict(self.bucket_parallelism or {}).items():
            if strat not in _SERVE_PARALLELISMS:
                raise ValueError(
                    f"bucket_parallelism[{tuple(hw)}] must be one of "
                    f"{_SERVE_PARALLELISMS}, got {strat!r}"
                )
            norm_bp[(int(hw[0]), int(hw[1]))] = strat
        self.bucket_parallelism = norm_bp
        # BucketTable owns bucket validation and the area-major ordering
        # invariant ("smallest covering bucket" scans front-to-back) — one
        # normalization, not a copy here that could drift.  Lazy import:
        # the serve package imports this module at load time.
        from ..serve.batcher import BucketTable

        self.buckets = BucketTable(self.buckets).buckets
        for hw in self.bucket_parallelism:
            if hw not in self.buckets:
                raise ValueError(
                    f"bucket_parallelism key {hw} is not a configured "
                    f"bucket (buckets: {tuple(self.buckets)}) — the map is "
                    "keyed by post-snap bucket resolution"
                )
        warm = []
        for b in self.warmup_buckets:
            if len(b) not in (2, 3):
                raise ValueError(
                    f"warmup bucket {tuple(b)}: expected (h, w) or (h, w, steps)"
                )
            warm.append(tuple(int(x) for x in b))
        self.warmup_buckets = tuple(warm)
        if not isinstance(self.resilience, ResilienceConfig):
            raise ValueError(
                "resilience must be a ResilienceConfig, got "
                f"{type(self.resilience).__name__}"
            )
        if not isinstance(self.controller, ControllerConfig):
            raise ValueError(
                "controller must be a ControllerConfig, got "
                f"{type(self.controller).__name__}"
            )
        if not isinstance(self.step_batching, StepBatchConfig):
            raise ValueError(
                "step_batching must be a StepBatchConfig, got "
                f"{type(self.step_batching).__name__}"
            )
        if self.step_batching.enabled:
            if self.pipeline_stages:
                raise ValueError(
                    "step_batching and pipeline_stages are mutually "
                    "exclusive: the staged pipeline owns whole batches "
                    "while the slot pool owns individual steps — pick one "
                    "dispatch mode per server"
                )
            if (self.parallelism == "pipefusion"
                    or "pipefusion" in set(self.bucket_parallelism.values())):
                raise ValueError(
                    "step_batching requires patch-parallel buckets: the "
                    "PipeFusion tick pipeline has no host-driven per-step "
                    "loop to schedule at step granularity"
                )
        if not isinstance(self.observability, ObservabilityConfig):
            raise ValueError(
                "observability must be an ObservabilityConfig, got "
                f"{type(self.observability).__name__}"
            )
        if not isinstance(self.gateway, GatewayConfig):
            raise ValueError(
                "gateway must be a GatewayConfig, got "
                f"{type(self.gateway).__name__}"
            )
        if not isinstance(self.aot_cache, AotCacheConfig):
            raise ValueError(
                "aot_cache must be an AotCacheConfig, got "
                f"{type(self.aot_cache).__name__}"
            )
