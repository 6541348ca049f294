"""Dense / MLP primitives.

Params are plain pytrees: ``{"kernel": [in, out], "bias": [out]?}`` (JAX
layout; the torch->JAX converter in models/weights.py transposes).  Matmuls
hit the MXU; inputs stay in the model dtype (bf16 on TPU) with XLA's native
fp32 accumulation.

Quantized kernels (`parallel.compress.QuantizedTensor`, the
DistriConfig.weight_quant tree) dispatch here to a real low-precision
execution path (ops/gemm_routing.py picks dequant vs int8/fp8 dot_general
vs the Pallas tiled kernel per shape): activations quantize dynamically
per token, the MACs run at the MXU's 2x int8 rate with
``preferred_element_type`` accumulation, and the per-channel-tile weight
scale applies after the accumulate.  The dequantize-to-dense path
survives as the routed fallback (and for norm/bias/output heads, which
never quantize).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.compress import QuantizedTensor, quantize


def _quantized_matmul(x, qt: QuantizedTensor):
    """x [..., K] @ QuantizedTensor [K, N] via the routed execution path."""
    from .gemm_routing import resolve

    out_dtype = jnp.result_type(x.dtype, qt.dtype)
    if qt.ndim != 2:
        # stacked/conv layouts never reach linear() unsliced; if one does,
        # dequant is always correct
        return (x @ qt.__jax_array__()).astype(out_dtype)
    k, n = qt.shape
    m = 1
    for d in x.shape[:-1]:
        m *= int(d)
    mode = "int8" if qt.payload.dtype == jnp.int8 else "fp8"
    route = resolve(mode, m, k, n, qt.compute)
    if route.impl == "dequant":
        return (x @ qt.__jax_array__()).astype(out_dtype)

    # dynamic per-token activation quantization (one scale per [..., K]
    # row — the reduction-axis granularity that keeps the product's error
    # per-(token, channel) bounded)
    xq, sx = quantize(x, mode, axis=-1)
    sw = qt.channel_scale()  # [N] fp32, channel_tile expanded
    if route.impl == "dot":
        acc = lax.dot_general(
            xq, qt.payload, (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=(jnp.int32 if mode == "int8"
                                    else jnp.float32),
        )
        y = acc.astype(jnp.float32) * sx[..., None] * sw
    else:  # pallas
        from .quant_matmul import quant_matmul

        interpret = jax.devices()[0].platform == "cpu"
        y = quant_matmul(
            xq.reshape(m, k), qt.payload, sw,
            block_m=route.block_m, block_n=route.block_n,
            block_k=route.block_k, interpret=interpret,
        )
        y = y.reshape(*x.shape[:-1], n) * sx[..., None]
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
