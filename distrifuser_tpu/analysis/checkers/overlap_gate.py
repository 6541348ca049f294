"""Jaxpr overlap gate: the stale-exchange deferral contract, on CPU, fast.

The displaced-patch design's latency claim — stale-refresh collectives
are consumed only by the NEXT step, so XLA overlaps them with compute
(the role of the reference's async NCCL gathers; the PipeFusion /
FastUSP overlap contracts, PAPERS.md arXiv 2405.14430 / 2602.10940) — is
verified today by `slow`-marked 8-device HLO tests (tests/test_overlap.py,
test_stepcache.py) that compile for minutes and never run on the 2-core
tier-1 runner.  A regression that turns a refresh collective inline
(e.g. an accidental same-step consumer added to a context emit path)
would land invisible to tier-1 and surface as a silent throughput cliff
on real chips.

This checker runs the same structural assertion at TRACE time
(analysis/jaxpr_overlap.py) on the tiny config — seconds, CPU-only,
tier-1-runnable:

* **stale scan** (corrected_async_gn): the steady-state body's ppermute
  halo refreshes and all_gather KV refreshes must all classify
  deferred/deferred_compute; inline is allowed ONLY for all_gather (the
  per-step CFG/output combine, synchronous in the reference too) and at
  most 2 of them — the exact envelope the HLO test pins;
* **compressed stale scan** (comm_compress=int8): the quantized refresh
  pairs land in deferred/deferred_compute (the elementwise dequant
  carve-out), same inline envelope;
* **negative control** (full_sync): the sync body must classify inline
  collectives — proving the analyzer still discriminates, so the gate
  cannot rot into a vacuous pass.
"""

from __future__ import annotations

from typing import List

from ..core import CheckContext, Finding

NAME = "jaxpr-overlap"
DESCRIPTION = ("stale-exchange collectives classify deferred at trace "
               "time on the tiny config (CPU-fast mirror of the slow "
               "HLO tests)")

RUNNER_PATH = "distrifuser_tpu/parallel/runner.py"

#: the HLO test's envelope (tests/test_overlap.py): at most this many
#: inline collectives in the stale scan, all of them gathers
MAX_INLINE = 2
MIN_DEFERRED = 10


def _finding(rule: str, message: str) -> Finding:
    return Finding(checker=NAME, path=RUNNER_PATH, line=0,
                   message=message, identity=rule)


def _trace_tiny(mode: str, steps: int, comm_compress: str = "none"):
    """Trace (never compile) the tiny-config fused loop; returns the
    ClosedJaxpr.  Mirrors tests/test_overlap.py::_compiled_hlo minus
    ``.compile()``."""
    import jax
    import jax.numpy as jnp

    from ...models import unet as unet_mod
    from ...parallel.runner import DenoiseRunner
    from ...schedulers import get_scheduler
    from ...utils.config import DistriConfig

    devices = jax.devices()[:8]
    ucfg = unet_mod.tiny_config(sdxl=False)
    params = unet_mod.init_unet_params(jax.random.PRNGKey(0), ucfg)
    depth = len(ucfg.block_out_channels) - 1
    cfg = DistriConfig(
        devices=devices, height=8 * 8 * (1 << depth) * 2, width=128,
        warmup_steps=1, parallelism="patch", mode=mode,
        comm_compress=comm_compress,
    )
    runner = DenoiseRunner(cfg, ucfg, params, get_scheduler("ddim"))
    lat = jnp.zeros((1, cfg.latent_height, cfg.latent_width,
                     ucfg.in_channels))
    enc = jnp.zeros((2, 1, 7, ucfg.cross_attention_dim))
    fn = runner._build(steps)
    return fn.trace(params, lat, enc, None, 5.0).jaxpr


def _gate_stale(reports, tag: str) -> List[Finding]:
    from ..jaxpr_overlap import JaxprLoopReport  # noqa: F401

    findings: List[Finding] = []
    if not reports:
        return [_finding(f"{tag}:no-loops",
                         f"[{tag}] no loop collectives found in the "
                         "traced patch program — the analyzer lost the "
                         "scan, or the loop structure changed")]
    stale = max(reports, key=lambda r: r.n_deferred + r.n_deferred_compute)
    hidden = {**stale.deferred, **stale.deferred_compute}
    if stale.n_inline > MAX_INLINE:
        findings.append(_finding(
            f"{tag}:inline-count",
            f"[{tag}] stale scan has {stale.n_inline} inline "
            f"collectives (> {MAX_INLINE}): {stale.inline} — a "
            "stale-exchange collective gained a same-step consumer and "
            "now serializes against compute"))
    bad = [p for p in stale.inline.values() if p != "all_gather"]
    if bad:
        findings.append(_finding(
            f"{tag}:inline-kind",
            f"[{tag}] only the per-step output/CFG all_gather may be "
            f"inline in the stale scan; got {stale.inline} — ppermute/"
            "psum serializing means a refresh path broke its deferral"))
    if "ppermute" not in hidden.values():
        findings.append(_finding(
            f"{tag}:halo-missing",
            f"[{tag}] no deferred ppermute in the stale scan — the halo "
            "refresh exchanges are missing from the carry"))
    if "all_gather" not in hidden.values():
        findings.append(_finding(
            f"{tag}:kv-missing",
            f"[{tag}] no deferred all_gather in the stale scan — the KV "
            "refresh gathers are missing from the carry"))
    if len(hidden) < MIN_DEFERRED:
        findings.append(_finding(
            f"{tag}:deferred-count",
            f"[{tag}] only {len(hidden)} collectives classify "
            f"deferred/deferred-compute (< {MIN_DEFERRED}) — the "
            "refresh set shrank or the classifier regressed"))
    return findings


def run(ctx: CheckContext) -> List[Finding]:
    try:
        import jax
    except Exception as exc:  # pragma: no cover - env without jax
        return [_finding("no-jax",
                         f"jax unavailable, overlap gate cannot run: "
                         f"{exc}")]
    if len(jax.devices()) < 8:
        return [_finding(
            "no-devices",
            "overlap gate needs the fake 8-device CPU mesh — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 (and "
            "JAX_PLATFORMS=cpu) before jax is first imported; the CLI "
            "entry point does this automatically")]

    from ..jaxpr_overlap import analyze_jaxpr_collectives

    findings: List[Finding] = []
    findings.extend(_gate_stale(
        analyze_jaxpr_collectives(_trace_tiny("corrected_async_gn", 4)),
        "stale"))
    findings.extend(_gate_stale(
        analyze_jaxpr_collectives(
            _trace_tiny("corrected_async_gn", 4, comm_compress="int8")),
        "stale-int8"))
    # negative control: the analyzer must still see sync gathers as
    # inline, or every assertion above passes vacuously
    sync_reports = analyze_jaxpr_collectives(_trace_tiny("full_sync", 5))
    if not any(r.n_inline > 0 for r in sync_reports):
        findings.append(_finding(
            "sync-control",
            "negative control failed: full_sync collectives did not "
            "classify inline — the jaxpr analyzer lost discrimination "
            "and the deferral gate is vacuous"))
    return findings
