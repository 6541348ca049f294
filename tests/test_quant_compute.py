"""Quantized COMPUTE (ISSUE 12): int8/fp8 matmuls as an execution path.

Covers: per-family parity of the low-precision dot path vs the PR-6
dequant-bf16 fallback (pinned tolerances), the HLO-level guarantee that a
compute-routed transformer block runs an int8 ``dot`` with NO
dequantize-to-float convert feeding it, which path a leaf's policy takes
(forced policy -> platform -> token count), channel-tile scale grouping,
and ExecKey distinctness across (none / int8-storage / int8-compute).
"""

import dataclasses
import importlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distrifuser_tpu.models import dit as dit_mod
from distrifuser_tpu.models import mmdit as mmdit_mod
from distrifuser_tpu.models import unet as unet_mod
from distrifuser_tpu.models.weights import quantize_params, set_quant_compute
from distrifuser_tpu.parallel.compress import (
    QuantizedTensor,
    fp8_supported,
    quantize_weight,
    validate_quant_compute,
)
from distrifuser_tpu.serve import ExecKey

linear_mod = importlib.import_module("distrifuser_tpu.ops.linear")

MODES = ["int8"] + (["fp8"] if fp8_supported() else [])

# Pinned compute-path tolerances: max |Δ| of the raw tiny-model forward vs
# the DENSE forward (fixed seeds below).  The low-precision paths quantize
# ACTIVATIONS too (dynamic per-token), so their budget sits above the
# storage-only dequant numbers but within ~2x of them — the relative
# assertion below pins that ratio, these absolute ceilings pin the scale.
TOL_COMPUTE = {
    "int8": {"unet": 0.12, "dit": 0.02, "mmdit": 0.025},
    "fp8": {"unet": 0.5, "dit": 0.09, "mmdit": 0.12},
}


# --------------------------------------------------------------------------
# family forwards (tiny configs, fixed seeds)
# --------------------------------------------------------------------------


def _family_forward(family):
    """(params, forward(params) -> array) for one tiny family model."""
    k = jax.random.PRNGKey(1)
    if family == "unet":
        cfg = unet_mod.tiny_config(sdxl=False)
        p = unet_mod.init_unet_params(jax.random.PRNGKey(0), cfg)
        sample = jax.random.normal(k, (2, 16, 16, cfg.in_channels))
        enc = jax.random.normal(
            jax.random.fold_in(k, 1), (2, 7, cfg.cross_attention_dim))
        t = jnp.array([7.0, 7.0])
        return p, lambda q: unet_mod.unet_forward(q, cfg, sample, t, enc)
    if family == "dit":
        cfg = dit_mod.tiny_dit_config(depth=4)
        p = dit_mod.init_dit_params(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(k, (1, 16, 16, 4))
        enc = jax.random.normal(
            jax.random.fold_in(k, 2), (1, 9, cfg.caption_dim))
        return p, lambda q: dit_mod.dit_forward(
            q, cfg, x, jnp.asarray(500.0), enc)
    assert family == "mmdit"
    cfg = mmdit_mod.tiny_mmdit_config()
    p = mmdit_mod.init_mmdit_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(
        k, (2, cfg.sample_size, cfg.sample_size, cfg.in_channels))
    enc = jax.random.normal(
        jax.random.fold_in(k, 1), (2, 7, cfg.joint_attention_dim))
    pooled = jax.random.normal(
        jax.random.fold_in(k, 2), (2, cfg.pooled_projection_dim))
    return p, lambda q: mmdit_mod.mmdit_forward(
        q, cfg, x, jnp.asarray(500.0), enc, pooled)


@pytest.mark.parametrize("family", ["unet", "dit", "mmdit"])
@pytest.mark.parametrize("mode", MODES)
def test_family_compute_path_parity(family, mode):
    """int8/fp8 matmul execution stays within the pinned tolerance of the
    dense forward on every family, and within 2x of the storage-only
    dequant path's error (the compute path adds activation quantization,
    not a different weight rounding)."""
    params, fwd = _family_forward(family)
    dense = np.asarray(fwd(params), np.float64)
    dq = np.asarray(
        fwd(quantize_params(params, mode, compute="dequant")), np.float64)
    dot = np.asarray(
        fwd(quantize_params(params, mode, compute="dot")), np.float64)
    err_dq = np.abs(dq - dense).max()
    err_dot = np.abs(dot - dense).max()
    assert err_dot <= TOL_COMPUTE[mode][family], (family, mode, err_dot)
    assert err_dot <= 2.0 * err_dq + 1e-6, (
        f"{family}/{mode}: compute path error {err_dot} is more than 2x "
        f"the storage-only error {err_dq}"
    )


def test_channel_tile_partial_last_tile_roundtrip():
    """channel_tile grouping: scale length is ceil(N/tile) (partial last
    tile), dequantization expands it back per channel, and the error stays
    bounded by the TILE amax."""
    rng = np.random.RandomState(5)
    w = jnp.asarray(rng.randn(32, 50).astype(np.float32))
    qt = quantize_weight(w, "int8", channel_tile=16)
    assert qt.scale.shape == (4,)  # ceil(50/16)
    back = np.asarray(qt.__jax_array__(), np.float64)
    amax = np.abs(np.asarray(w, np.float64)).max(axis=0)
    tile_amax = np.array([
        amax[i * 16:(i + 1) * 16].max() for i in range(4)])
    bound = np.repeat(tile_amax, 16)[:50] / 254.0
    assert (np.abs(back - np.asarray(w, np.float64)) <= bound + 1e-7).all()
    # a misaligned rebuild (the pre-fix loader bug: tile size dropped ->
    # per-channel assumed) refuses loudly instead of dequantizing with
    # wrong scales
    with pytest.raises(ValueError, match="misalignment"):
        QuantizedTensor(qt.payload, qt.scale, qt.dtype)


# --------------------------------------------------------------------------
# HLO: the hot path really runs an int8 dot, with no dequant convert
# --------------------------------------------------------------------------


_DEF = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[")
_OPERANDS = re.compile(r"\(([^)]*)\)")


def _hlo_defs(hlo):
    """{instr name: (result type prefix, opcode, [operand names])}."""
    defs = {}
    for line in hlo.splitlines():
        m = _DEF.match(line)
        if not m or " = " not in line:
            continue
        name, ty = m.group(1), m.group(2)
        rhs = line.split(" = ", 1)[1]
        op = rhs.split("[", 1)[0].strip() if "[" in rhs else ""
        opcode = re.match(r"\w+\[[^\]]*\]\{?[^ ]* (\w[\w\-]*)\(", rhs)
        opcode = opcode.group(1) if opcode else rhs.split("(", 1)[0].split()[-1]
        args = []
        paren = rhs.find("(")
        if paren >= 0:
            depth, j = 0, paren
            for j, ch in enumerate(rhs[paren:], start=paren):
                depth += ch == "("
                depth -= ch == ")"
                if depth == 0:
                    break
            for tok in rhs[paren + 1:j].split(","):
                tok = tok.strip().lstrip("%")
                # operands print either as bare names or as "type name"
                args.append(tok.split()[-1].lstrip("%") if tok else tok)
        defs[name] = (ty, opcode, args)
    return defs


_PASSTHROUGH = frozenset({
    "multiply", "add", "subtract", "broadcast", "reshape", "transpose",
    "convert", "copy", "slice", "concatenate", "pad", "negate",
})


def _dequant_feeds_a_dot(hlo) -> bool:
    """True when some float dot consumes (transitively through elementwise
    / data movement) a convert FROM an integer-quantized value TO float —
    the storage-only lazy-dequant signature."""
    defs = _hlo_defs(hlo)
    tainted = set()
    changed = True
    while changed:
        changed = False
        for name, (ty, opcode, args) in defs.items():
            if name in tainted:
                continue
            if opcode == "convert" and ty.startswith(("f", "bf")):
                src = defs.get(args[0]) if args else None
                if src and src[0] == "s8":
                    tainted.add(name)
                    changed = True
                    continue
            if opcode in _PASSTHROUGH and any(a in tainted for a in args):
                tainted.add(name)
                changed = True
    return any(
        opcode == "dot" and ty.startswith(("f", "bf"))
        and any(a in tainted for a in args)
        for ty, opcode, args in defs.values()
    )


def _int8_dot_present(hlo) -> bool:
    defs = _hlo_defs(hlo)
    return any(
        opcode == "dot"
        and sum(1 for a in args if defs.get(a, ("",))[0] == "s8") >= 2
        for ty, opcode, args in defs.values()
    )


def _lowered_block_hlo(compute):
    """Lowered (pre-optimization) HLO of one quantized DiT transformer
    block — the serving hot path's repeating unit."""
    cfg = dit_mod.tiny_dit_config(depth=2)
    params = quantize_params(
        dit_mod.init_dit_params(jax.random.PRNGKey(0), cfg),
        "int8", compute=compute)
    bp = jax.tree.map(lambda l: l[0], params["blocks"])
    h = jnp.zeros((1, 64, cfg.hidden_size))
    c6 = jnp.zeros((6, cfg.hidden_size))
    kv = jnp.zeros((1, 9, 2 * cfg.hidden_size))

    def block(bp, h, c6, kv):
        out, _ = dit_mod.dit_block(bp, cfg, h, c6, kv)
        return out

    return jax.jit(block).lower(bp, h, c6, kv).as_text(dialect="hlo")


def test_block_hlo_int8_dot_and_no_dequant_convert():
    """Acceptance: with compute routing forced on, the transformer block's
    lowered HLO contains an int8 ``dot`` and NO dequantize-to-float
    convert feeding any dot; the storage-only program shows exactly the
    opposite (the discrimination control)."""
    hot = _lowered_block_hlo("dot")
    assert _int8_dot_present(hot), "no s8 x s8 dot in the compute-routed block"
    assert not _dequant_feeds_a_dot(hot), (
        "compute-routed block still dequantizes a kernel into a float dot"
    )
    cold = _lowered_block_hlo("dequant")
    assert not _int8_dot_present(cold)
    assert _dequant_feeds_a_dot(cold), (
        "control lost discrimination: storage-only block shows no "
        "dequant-convert-fed dot"
    )


# --------------------------------------------------------------------------
# routing resolution
# --------------------------------------------------------------------------


def test_resolve_order_env_policy_table_analytic(monkeypatch):
    """Which path `_quantized_matmul` takes: a forced policy first, then
    (auto) the platform, then the token count.  The int8 path's output
    differs from the dequantized one (activations quantize too), so the
    path shows in the lowered program: an s8 x s8 dot, or none."""

    class _Dev:
        def __init__(self, platform):
            self.platform = platform

    w = jax.random.normal(jax.random.PRNGKey(0), (64, 64))

    def takes_dot(policy, m, platform):
        monkeypatch.setattr(jax, "devices", lambda: [_Dev(platform)])
        qt = quantize_weight(w, "int8", compute=policy)
        hlo = jax.jit(lambda x: linear_mod.linear({"kernel": qt}, x)).lower(
            jnp.zeros((m, 64))).as_text(dialect="hlo")
        return _int8_dot_present(hlo)

    # forced policies hold on every platform and at every token count
    for platform in ("cpu", "tpu"):
        for m in (2, 4096):
            assert not takes_dot("dequant", m, platform)
            assert takes_dot("dot", m, platform)
    # auto: dequant on the cpu; dot on the chip from DOT_MIN_M tokens up
    assert not takes_dot("auto", 4096, "cpu")
    assert takes_dot("auto", 4096, "tpu")
    assert not takes_dot("auto", 2, "tpu")
    assert takes_dot("auto", linear_mod.DOT_MIN_M, "tpu")
    assert not takes_dot("auto", linear_mod.DOT_MIN_M - 1, "tpu")
    # an unknown policy never reaches a matmul: the leaf refuses it
    with pytest.raises(ValueError, match="compute policy"):
        quantize_weight(w, "int8", compute="pallas")


def test_set_quant_compute_retags_without_touching_payloads():
    params, fwd = _family_forward("dit")
    q = quantize_params(params, "int8", compute="dequant")
    q2 = set_quant_compute(q, "dot")
    a = q["blocks"]["attn_q"]["kernel"]
    b = q2["blocks"]["attn_q"]["kernel"]
    assert a.compute == "dequant" and b.compute == "dot"
    assert b.payload is a.payload and b.scale is a.scale
    # "off" maps to the leaf-level "dequant"
    q3 = set_quant_compute(q2, "off")
    assert q3["blocks"]["attn_q"]["kernel"].compute == "dequant"
    with pytest.raises(ValueError, match="quant_compute"):
        set_quant_compute(q, "int8")
    # re-quantizing an already-quantized tree at the same mode re-tags too
    q4 = quantize_params(q, "int8", compute="auto")
    assert q4["blocks"]["attn_q"]["kernel"].compute == "auto"
    assert q4["blocks"]["attn_q"]["kernel"].payload is a.payload


def test_validate_quant_compute():
    for p in ("off", "auto", "dot"):
        validate_quant_compute(p, "int8")
    # a kernel route that is gone is input from outside like any other bad
    # value: the same typed error, naming what is left
    with pytest.raises(ValueError, match=r"\('off', 'auto', 'dot'\)"):
        validate_quant_compute("pallas", "int8")
    validate_quant_compute("auto", "none")
    with pytest.raises(ValueError, match="quant_compute"):
        validate_quant_compute("dequant", "int8")  # leaf-level name
    with pytest.raises(ValueError, match="no quantized kernels"):
        validate_quant_compute("dot", "none")


# --------------------------------------------------------------------------
# serve identity: none / int8-storage / int8-compute are three programs
# --------------------------------------------------------------------------


def test_exec_key_distinct_none_storage_compute():
    base = ExecKey(model_id="m", scheduler="ddim", height=512, width=512,
                   steps=4, cfg=True, mesh_plan="dp1.cfg1.sp1")
    storage = dataclasses.replace(base, weight_quant="int8",
                                  quant_compute="off")
    compute = dataclasses.replace(base, weight_quant="int8",
                                  quant_compute="auto")
    forced = dataclasses.replace(base, weight_quant="int8",
                                 quant_compute="dot")
    keys = {base, storage, compute, forced}
    assert len(keys) == 4
    tags = {k.short() for k in keys}
    assert len(tags) == 4, tags
    assert "qc-off" in storage.short()
    assert "qc-dot" in forced.short()
    with pytest.raises(ValueError, match="quant_compute"):
        dataclasses.replace(base, weight_quant="int8", quant_compute="pallas")
    # the fleet default ("auto") needs no tag — PR-9/PR-10 rungs that set
    # weight_quant="int8" inherit the compute path without a key change
    assert "qc-" not in compute.short()
    with pytest.raises(ValueError, match="no quantized kernels"):
        dataclasses.replace(base, quant_compute="dot")


def test_pipeline_quant_compute_hook(devices8):
    from test_pipelines import build_sd_pipeline

    kw = dict(batch_size=1, do_classifier_free_guidance=False)
    pipe, _ = build_sd_pipeline(devices8, 1, weight_quant="int8", **kw)
    assert pipe.weight_report()["quant_compute"] == "auto"
    gen = lambda p: np.stack(  # noqa: E731
        p(["a cat"], num_inference_steps=1, seed=5, guidance_scale=1.0,
          output_type="np").images).astype(np.float64)
    auto = gen(pipe)  # on CPU "auto" routes dequant: storage numerics
    pipe.set_quant_compute("off")
    np.testing.assert_array_equal(gen(pipe), auto)
    # forcing the low-precision path end to end stays within the same
    # family budget the storage-only knob is pinned at (docs/PERF.md)
    pipe.set_quant_compute("dot")
    assert pipe.weight_report()["quant_compute"] == "dot"
    delta = np.abs(gen(pipe) - auto).max()
    assert delta <= 2e-2, delta
    with pytest.raises(ValueError, match="no quantized kernels"):
        build_sd_pipeline(devices8, 1, weight_quant="none",
                          quant_compute="dot", **kw)
