"""Dense / MLP primitives.

Params are plain pytrees: ``{"kernel": [in, out], "bias": [out]?}`` (JAX
layout; the torch->JAX converter in models/weights.py transposes).  Matmuls
hit the MXU; inputs stay in the model dtype (bf16 on TPU) with XLA's native
fp32 accumulation.

Quantized kernels (`parallel.compress.QuantizedTensor`, the
DistriConfig.weight_quant tree) dispatch here to one of two execution
paths (`_quantized_matmul`): ``dot`` — activations quantize dynamically
per token, the MACs run as a real int8/fp8 ``dot_general`` at the MXU's 2x
int8 rate with ``preferred_element_type`` accumulation, and the
per-channel-tile weight scale applies after the accumulate — or
``dequant``, dequantize to the compute dtype and a dense matmul (storage
semantics: bytes saved, no FLOPs; also what norm/bias/output heads, which
never quantize, amount to).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.compress import QuantizedTensor, quantize


# `auto`'s minimum token count for the low-precision dot: below this the
# per-token activation quantize (3 elementwise passes over M*K) and the M*N
# scale apply are not paid back by halving M*K*N MAC time.  The
# time/conditioning-embedding linears (M = batch, single digits) stay on
# dequant; every token-stream matmul (M = B*L, thousands) runs low.
DOT_MIN_M = 32


def _quantized_matmul(x, qt: QuantizedTensor):
    """x [..., K] @ QuantizedTensor [K, N] by the leaf's compute policy
    (DistriConfig.quant_compute, the one way to force a path): "dequant"
    and "dot" are what they say; "auto" is dequant on the CPU (XLA's CPU
    int8 dot upcasts to int32 — all overhead, no win) and dot elsewhere
    from DOT_MIN_M tokens up."""
    out_dtype = jnp.result_type(x.dtype, qt.dtype)
    m = math.prod(x.shape[:-1])
    use_dot = qt.compute == "dot" or (
        qt.compute == "auto" and m >= DOT_MIN_M
        and jax.devices()[0].platform != "cpu")
    # stacked/conv layouts never reach linear() unsliced; if one does,
    # dequant is always correct
    if qt.ndim != 2 or not use_dot:
        return (x @ qt.__jax_array__()).astype(out_dtype)

    # dynamic per-token activation quantization (one scale per [..., K]
    # row — the reduction-axis granularity that keeps the product's error
    # per-(token, channel) bounded)
    mode = "int8" if qt.payload.dtype == jnp.int8 else "fp8"
    xq, sx = quantize(x, mode, axis=-1)
    acc = lax.dot_general(
        xq, qt.payload, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32 if mode == "int8" else jnp.float32,
    )
    # channel_scale(): [N] fp32, channel_tile expanded
    y = acc.astype(jnp.float32) * sx[..., None] * qt.channel_scale()
    return y.astype(out_dtype)


@jax.named_scope("linear")
def linear(p, x):
    kern = p["kernel"]
    if isinstance(kern, QuantizedTensor):
        y = _quantized_matmul(x, kern)
    else:
        y = x @ kern
    if "bias" in p:
        y = y + p["bias"]
    return y


def geglu(p, x):
    """GEGLU gate: diffusers `GEGLU` (hidden, gate = proj(x).chunk(2); hidden*gelu(gate)).

    The reference's TP shard of this op is tp/feed_forward.py:20-36; here the
    dense version.  Exact (erf) GeLU to match torch's default.
    """
    h = linear(p["proj"], x)
    a, g = jnp.split(h, 2, axis=-1)
    return a * jax.nn.gelu(g, approximate=False)


@jax.named_scope("ff")
def feed_forward(p, x):
    """diffusers `FeedForward` with GEGLU activation: net.0 = GEGLU, net.2 = Linear
    (reference shards it in tp/feed_forward.py; dense path here)."""
    return linear(p["net_2"], geglu(p["net_0"], x))
