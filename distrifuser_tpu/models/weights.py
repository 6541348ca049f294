"""HuggingFace torch checkpoints -> JAX param pytrees.

The reference loads weights through diffusers `from_pretrained`
(/root/reference/distrifuser/pipelines.py:26-28); the TPU equivalent is a
one-time mechanical conversion of the safetensors state_dicts into the param
trees the models in this package consume:

* conv kernels  [O, I, kh, kw] -> HWIO [kh, kw, I, O]
* linear kernels [O, I] -> [I, O]
* norm ``weight`` -> ``scale``
* diffusers quirks normalized: ``to_out.0`` -> ``to_out``, ``ff.net.0.proj``
  -> ``ff.net_0.proj``, ``ff.net.2`` -> ``ff.net_2``
* UNet attention ``to_k``/``to_v`` fused into one ``to_kv`` kernel — the
  layout the displaced-patch attention computes with (reference fuses the
  same way at wrap time, modules/pp/attn.py:23-39)

Converted trees can be cached to disk with `save_params` / `load_params`
(msgpack-free: a flat .npz) so the torch -> JAX conversion runs once.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

_NORM_HINTS = ("norm", "ln_", "layer_norm", "layernorm")


def load_safetensors(path: str) -> Dict[str, np.ndarray]:
    # Preferred path: the native mmap reader (zero copy, threaded page-in,
    # distrifuser_tpu/native/fast_safetensors.cc); falls back to the Python
    # safetensors package.
    from ..native import load_safetensors_fast

    fast = load_safetensors_fast(path)
    if fast is not None:
        return fast
    from safetensors.numpy import load_file

    return load_file(path)


def load_sharded_safetensors(
    model_dir: str, prefix: str = "", variant: Optional[str] = None
) -> Dict[str, np.ndarray]:
    """Load *.safetensors shards in a directory into one state dict.

    HF snapshots may carry both base and variant weights (e.g.
    ``diffusion_pytorch_model.safetensors`` and ``...fp16.safetensors``) with
    identical tensor names; mixing them would be nondeterministic.  With
    ``variant`` set (e.g. "fp16") only those files load; otherwise variant
    files are skipped whenever base files exist.
    """
    names = sorted(
        f for f in os.listdir(model_dir) if f.endswith(".safetensors")
    )
    if variant:
        names = [f for f in names if f".{variant}." in f]
        if not names:
            raise FileNotFoundError(
                f"no .{variant}. safetensors shards in {model_dir}"
            )
    else:
        # "name.safetensors" / "name-00001-of-00002.safetensors" are base;
        # "name.fp16.safetensors" is a variant (3 dot-segments)
        base = [f for f in names if len(f.split(".")) == 2]
        if base:
            names = base
    sd: Dict[str, np.ndarray] = {}
    for fname in names:
        sd.update(load_safetensors(os.path.join(model_dir, fname)))
    if prefix:
        sd = {k[len(prefix):]: v for k, v in sd.items() if k.startswith(prefix)}
    return sd


def _rename(parts: List[str]) -> List[str]:
    out: List[str] = []
    i = 0
    while i < len(parts):
        p = parts[i]
        if p == "net" and i + 1 < len(parts) and parts[i + 1] in ("0", "2"):
            out.append(f"net_{parts[i + 1]}")
            i += 2
            continue
        if p == "to_out" and i + 1 < len(parts) and parts[i + 1] == "0":
            out.append("to_out")
            i += 2
            continue
        out.append(p)
        i += 1
    return out


def _convert_leaf(parts: List[str], value: np.ndarray):
    leaf = parts[-1]
    v = np.asarray(value)
    if leaf == "weight":
        if "embedding" in parts[-2] or parts[-2] in ("token_embedding", "position_embedding"):
            return parts[:-1] + ["__direct__"], v
        if v.ndim == 4:
            return parts[:-1] + ["kernel"], v.transpose(2, 3, 1, 0)
        if v.ndim == 2:
            return parts[:-1] + ["kernel"], v.T
        return parts[:-1] + ["scale"], v
    if leaf == "bias":
        return parts[:-1] + ["bias"], v
    return parts, v


def _assign(tree: Dict[str, Any], parts: List[str], value) -> None:
    node = tree
    for p in parts[:-1]:
        node = node.setdefault(p, {})
    if parts[-1] == "__direct__":
        # whole-tensor param (embeddings): collapse into the parent key
        raise AssertionError("handled by caller")
    node[parts[-1]] = value


def _listify(tree):
    """Turn dicts whose keys are all digits into lists."""
    if not isinstance(tree, dict):
        return tree
    tree = {k: _listify(v) for k, v in tree.items()}
    if tree and all(k.isdigit() for k in tree):
        return [tree[str(i)] for i in range(len(tree))]
    return tree


def _fuse_kv(tree):
    """Fuse to_k + to_v into to_kv wherever both exist (UNet attention)."""
    if isinstance(tree, list):
        return [_fuse_kv(v) for v in tree]
    if not isinstance(tree, dict):
        return tree
    tree = {k: _fuse_kv(v) for k, v in tree.items()}
    if "to_k" in tree and "to_v" in tree and "to_q" in tree and "group_norm" not in tree:
        k, v = tree.pop("to_k"), tree.pop("to_v")
        fused = {"kernel": np.concatenate([k["kernel"], v["kernel"]], axis=1)}
        if "bias" in k:
            fused["bias"] = np.concatenate([k["bias"], v["bias"]])
        tree["to_kv"] = fused
    return tree


def _convert(sd: Dict[str, np.ndarray], *, skip=()) -> Dict[str, Any]:
    tree: Dict[str, Any] = {}
    for key, val in sd.items():
        if any(s in key for s in skip):
            continue
        parts = _rename(key.split("."))
        parts, v = _convert_leaf(parts, val)
        if parts[-1] == "__direct__":
            node = tree
            for p in parts[:-2]:
                node = node.setdefault(p, {})
            node[parts[-2]] = v
        else:
            _assign(tree, parts, v)
    return _listify(tree)


def _cast(tree, dtype):
    import jax

    # jnp.array (copy=True), NOT jnp.asarray: on the CPU backend asarray can
    # be zero-copy over a numpy view into the loader's mmap, and the
    # release_mappings() call after conversion would then unmap live param
    # memory — garbage weights or SIGSEGV on first use.  TPU always copies to
    # HBM, which is why only CPU runs could hit it.
    return jax.tree.map(lambda a: jnp.array(a, dtype), tree)


def convert_unet_state_dict(sd: Dict[str, np.ndarray], dtype=jnp.float32):
    """diffusers UNet2DConditionModel state_dict -> unet.py param tree."""
    tree = _convert(sd, skip=("position_ids",))
    tree = _fuse_kv(tree)
    return _cast(tree, dtype)


def convert_vae_state_dict(sd: Dict[str, np.ndarray], dtype=jnp.float32):
    """diffusers AutoencoderKL state_dict -> vae.py param tree (to_k/to_v kept
    separate — the VAE mid attention uses them unfused)."""
    renames = {"query": "to_q", "key": "to_k", "value": "to_v", "proj_attn": "to_out"}
    sd = {
        ".".join(renames.get(p, p) for p in k.split(".")): v for k, v in sd.items()
    }
    return _cast(_convert(sd), dtype)


def convert_clip_state_dict(sd: Dict[str, np.ndarray], dtype=jnp.float32):
    """transformers CLIPTextModel(-WithProjection) state_dict -> clip.py tree."""
    out: Dict[str, np.ndarray] = {}
    for k, v in sd.items():
        if k.endswith("position_ids"):
            continue
        k = k.replace("text_model.", "")
        k = k.replace("embeddings.token_embedding", "token_embedding")
        k = k.replace("embeddings.position_embedding", "position_embedding")
        k = k.replace("encoder.layers", "layers")
        out[k] = v
    return _cast(_convert(out), dtype)


def _stack_layers(layers: List[Dict[str, Any]]):
    """Per-layer trees -> one tree with a leading [depth] axis (the
    lax.scan / pipeline-stage layout of models/dit.py and models/t5.py)."""
    import jax

    return jax.tree.map(lambda *ls: np.stack(ls), *layers)


def convert_t5_state_dict(sd: Dict[str, np.ndarray], dtype=jnp.float32):
    """transformers T5EncoderModel state_dict -> t5.py param tree.

    Linear kernels transpose [O, I] -> [I, O]; the relative-position bias
    embedding (owned by block 0, shared by all layers in transformers) maps
    to the single top-level table t5_encode reads; per-block leaves stack
    into the leading [num_layers] axis.
    """
    get = lambda k: np.asarray(sd[k])
    n_layers = 1 + max(
        int(k.split(".")[2]) for k in sd if k.startswith("encoder.block.")
    )
    gated = "encoder.block.0.layer.1.DenseReluDense.wi_0.weight" in sd

    def lin(key):
        return {"kernel": get(key).T}

    layers = []
    for i in range(n_layers):
        a = f"encoder.block.{i}.layer.0"
        f = f"encoder.block.{i}.layer.1"
        ff = (
            {"wi_0": lin(f"{f}.DenseReluDense.wi_0.weight"),
             "wi_1": lin(f"{f}.DenseReluDense.wi_1.weight"),
             "wo": lin(f"{f}.DenseReluDense.wo.weight")}
            if gated
            else {"wi": lin(f"{f}.DenseReluDense.wi.weight"),
                  "wo": lin(f"{f}.DenseReluDense.wo.weight")}
        )
        layers.append({
            "attn": {
                "q": lin(f"{a}.SelfAttention.q.weight"),
                "k": lin(f"{a}.SelfAttention.k.weight"),
                "v": lin(f"{a}.SelfAttention.v.weight"),
                "o": lin(f"{a}.SelfAttention.o.weight"),
            },
            "attn_norm": get(f"{a}.layer_norm.weight"),
            "ff": ff,
            "ff_norm": get(f"{f}.layer_norm.weight"),
        })
    tree = {
        "shared": get("shared.weight"),
        "relative_attention_bias": get(
            "encoder.block.0.layer.0.SelfAttention.relative_attention_bias.weight"
        ),
        "layers": _stack_layers(layers),
        "final_norm": get("encoder.final_layer_norm.weight"),
    }
    return _cast(tree, dtype)


def convert_pixart_state_dict(
    sd: Dict[str, np.ndarray], *, patch_size: int = 2, eps_channels: int = 4,
    dtype=jnp.float32,
):
    """diffusers PixArtTransformer2DModel state_dict -> dit.py param tree.

    Key moves beyond the mechanical transpose:

    * ``pos_embed.proj`` (the ps x ps patch-embed conv) becomes the
      ``proj_in`` linear over patchify's (p, q, c)-ordered token vector;
    * per-block ``attn{1,2}.to_k/to_v`` fuse into ``attn_kv``/``cross_kv``
      (same layout convert_unet_state_dict produces);
    * ``proj_out`` [ps*ps*2C, hidden] carries PixArt's learned-sigma head;
      the epsilon rows (channel-innermost token layout, matching
      dit.unpatchify) are kept, sigma discarded (our runners use fixed
      variance, like the reference's SDXL path);
    * blocks stack into the leading [depth] scan axis.
    """
    get = lambda k: np.asarray(sd[k])

    def lin(key):
        w = {"kernel": get(f"{key}.weight").T}
        if f"{key}.bias" in sd:
            w["bias"] = get(f"{key}.bias")
        return w

    def fused(key_k, key_v):
        out = {"kernel": np.concatenate(
            [get(f"{key_k}.weight").T, get(f"{key_v}.weight").T], axis=1)}
        if f"{key_k}.bias" in sd:
            out["bias"] = np.concatenate(
                [get(f"{key_k}.bias"), get(f"{key_v}.bias")])
        return out

    n_blocks = 1 + max(
        int(k.split(".")[1]) for k in sd if k.startswith("transformer_blocks.")
    )
    blocks = []
    for i in range(n_blocks):
        b = f"transformer_blocks.{i}"
        blocks.append({
            "scale_shift_table": get(f"{b}.scale_shift_table"),
            "attn_q": lin(f"{b}.attn1.to_q"),
            "attn_kv": fused(f"{b}.attn1.to_k", f"{b}.attn1.to_v"),
            "attn_out": lin(f"{b}.attn1.to_out.0"),
            "cross_q": lin(f"{b}.attn2.to_q"),
            "cross_kv": fused(f"{b}.attn2.to_k", f"{b}.attn2.to_v"),
            "cross_out": lin(f"{b}.attn2.to_out.0"),
            "mlp_fc1": lin(f"{b}.ff.net.0.proj"),
            "mlp_fc2": lin(f"{b}.ff.net.2"),
        })

    ps = patch_size
    # conv [hidden, C, ps, ps] -> linear [(p, q, c) -> hidden]
    pw = get("pos_embed.proj.weight")
    hidden = pw.shape[0]
    proj_in = {
        "kernel": pw.transpose(2, 3, 1, 0).reshape(-1, hidden),
        "bias": get("pos_embed.proj.bias"),
    }
    # learned-sigma head: keep the eps channels of the (p, q, c) output layout
    ow = get("proj_out.weight")      # [ps*ps*out2, hidden]
    ob = get("proj_out.bias")
    out2 = ow.shape[0] // (ps * ps)
    ow = ow.reshape(ps, ps, out2, hidden)[:, :, :eps_channels]
    ob = ob.reshape(ps, ps, out2)[:, :, :eps_channels]
    final_out = {
        "kernel": ow.reshape(ps * ps * eps_channels, hidden).T,
        "bias": ob.reshape(-1),
    }

    tree = {
        "proj_in": proj_in,
        "t_fc1": lin("adaln_single.emb.timestep_embedder.linear_1"),
        "t_fc2": lin("adaln_single.emb.timestep_embedder.linear_2"),
        "adaln": lin("adaln_single.linear"),
        "cap_fc1": lin("caption_projection.linear_1"),
        "cap_fc2": lin("caption_projection.linear_2"),
        "final_table": get("scale_shift_table"),
        "final_out": final_out,
        "blocks": _stack_layers(blocks),
    }
    # 1024-class checkpoints micro-condition on resolution/aspect
    # (use_additional_conditions; dit.py applies them when cfg enables it)
    for name in ("resolution_embedder", "aspect_ratio_embedder"):
        k1 = f"adaln_single.emb.{name}.linear_1"
        if f"{k1}.weight" in sd:
            tree[name] = {
                "fc1": lin(k1),
                "fc2": lin(f"adaln_single.emb.{name}.linear_2"),
            }
    return _cast(tree, dtype)


def convert_mmdit_state_dict(sd: Dict[str, np.ndarray], dtype=jnp.float32):
    """diffusers SD3Transformer2DModel state_dict -> mmdit.py param tree.

    Mapping conventions (pinned by tests/test_mmdit_weights.py against a
    synthetic state dict — real-checkpoint validation needs mounted SD3
    weights, which this image does not have; the layout follows the
    published diffusers module structure):

    * ``pos_embed.proj`` (ps x ps patch-embed conv) -> ``proj_in`` linear
      over patchify's (p, q, c) token order; the fixed sin-cos
      ``pos_embed.pos_embed`` buffer is ignored (computed functionally by
      mmdit.pos_embed_cropped);
    * per-block q/k/v (``attn.to_{q,k,v}``, ``attn.add_{q,k,v}_proj``)
      fuse into ``x_qkv``/``c_qkv`` [h, 3h];
    * adaLN chunk orders differ per module family and are normalized to
      mmdit_block's (shift, scale, gate) x (attn, mlp):
      - ``norm1.linear`` / ``norm1_context.linear`` (AdaLayerNormZero,
        6 chunks) are already (shift, scale, gate, shift, scale, gate);
      - the FINAL block's ``norm1_context.linear`` and the top-level
        ``norm_out.linear`` (AdaLayerNormContinuous, 2 chunks) are
        (scale, shift) and get SWAPPED into (shift, scale);
    * the final block has no context attn-out/MLP (context_pre_only) and
      no context queries: the uniform stacked layout zero-fills
      ``c_out``/``c_fc*``/the gate+MLP modulation chunks/the q third of
      ``c_qkv`` — all of which feed only the DISCARDED final context
      stream (gates are zero, so the context residual passes through
      bit-exactly);
    * SD3.5-medium dual attention (``attn2`` present): the block's
      ``norm1.linear`` is AdaLayerNormZeroX (9 chunks) — the first 6
      chunks are the standard layout and map to ``x_mod``, the last 3
      (shift_msa2, scale_msa2, gate_msa2) to ``blocks_dual.x_mod2``;
      ``attn2.to_{q,k,v}`` fuse into ``x2_qkv``; dual blocks must form a
      contiguous prefix (the published layout) since the stacked-scan
      model splits at ``dual_attention_blocks``.
    """
    get = lambda k: np.asarray(sd[k])

    def lin(key):
        w = {"kernel": get(f"{key}.weight").T}
        if f"{key}.bias" in sd:
            w["bias"] = get(f"{key}.bias")
        return w

    def fused3(kq, kk, kv):
        """Three [h_out, h_in] torch linears -> one [h_in, 3h_out] kernel."""
        out = {"kernel": np.concatenate(
            [get(f"{kq}.weight").T, get(f"{kk}.weight").T,
             get(f"{kv}.weight").T], axis=1)}
        if f"{kq}.bias" in sd:
            out["bias"] = np.concatenate(
                [get(f"{kq}.bias"), get(f"{kk}.bias"), get(f"{kv}.bias")])
        return out

    def swap_scale_shift(m):
        """AdaLayerNormContinuous (scale, shift) -> (shift, scale)."""
        w, b = m["kernel"], m["bias"]
        h = w.shape[1] // 2
        return {
            "kernel": np.concatenate([w[:, h:], w[:, :h]], axis=1),
            "bias": np.concatenate([b[h:], b[:h]]),
        }

    n_blocks = 1 + max(
        int(k.split(".")[1]) for k in sd if k.startswith("transformer_blocks.")
    )
    dual_idx = [i for i in range(n_blocks)
                if f"transformer_blocks.{i}.attn2.to_q.weight" in sd]
    if dual_idx != list(range(len(dual_idx))):
        raise ValueError(
            f"dual-attention blocks at {dual_idx}: only the published "
            "contiguous-prefix layout is implemented"
        )
    blocks = []
    blocks_dual = []
    for i in range(n_blocks):
        b = f"transformer_blocks.{i}"
        hidden = get(f"{b}.attn.to_q.weight").shape[0]
        pre_only = f"{b}.attn.to_add_out.weight" not in sd
        is_dual = i < len(dual_idx)

        if pre_only:
            # context stream of the last block: K/V only.  Zero the query
            # third (its attention rows are computed and discarded) and
            # every output-side context weight; map the 2-chunk continuous
            # modulation into the (shift, scale) attn slots with zero gates.
            kdt = get(f"{b}.attn.add_k_proj.weight").dtype
            ckv = {
                "kernel": np.concatenate(
                    [np.zeros((hidden, hidden), kdt),
                     get(f"{b}.attn.add_k_proj.weight").T,
                     get(f"{b}.attn.add_v_proj.weight").T], axis=1),
                "bias": np.concatenate(
                    [np.zeros((hidden,), kdt),
                     get(f"{b}.attn.add_k_proj.bias"),
                     get(f"{b}.attn.add_v_proj.bias")]),
            }
            cont = swap_scale_shift(lin(f"{b}.norm1_context.linear"))
            zeros_mod_w = np.zeros_like(cont["kernel"])
            zeros_mod_b = np.zeros_like(cont["bias"])
            c_mod = {
                # (shift, scale) into the attn slots; gate + all MLP slots 0
                "kernel": np.concatenate(
                    [cont["kernel"], zeros_mod_w[:, :hidden],
                     zeros_mod_w, zeros_mod_w[:, :hidden]], axis=1),
                "bias": np.concatenate(
                    [cont["bias"], zeros_mod_b[:hidden],
                     zeros_mod_b, zeros_mod_b[:hidden]]),
            }
            zlin = {"kernel": np.zeros((hidden, hidden), ckv["kernel"].dtype),
                    "bias": np.zeros((hidden,), ckv["kernel"].dtype)}
            mlp_w = get(f"{b}.ff.net.0.proj.weight")
            zfc1 = {"kernel": np.zeros((hidden, mlp_w.shape[0]), mlp_w.dtype),
                    "bias": np.zeros((mlp_w.shape[0],), mlp_w.dtype)}
            zfc2 = {"kernel": np.zeros((mlp_w.shape[0], hidden), mlp_w.dtype),
                    "bias": np.zeros((hidden,), mlp_w.dtype)}
            c_out, c_fc1, c_fc2 = zlin, zfc1, zfc2
        else:
            ckv = fused3(f"{b}.attn.add_q_proj", f"{b}.attn.add_k_proj",
                         f"{b}.attn.add_v_proj")
            c_mod = lin(f"{b}.norm1_context.linear")
            c_out = lin(f"{b}.attn.to_add_out")
            c_fc1 = lin(f"{b}.ff_context.net.0.proj")
            c_fc2 = lin(f"{b}.ff_context.net.2")

        x_mod = lin(f"{b}.norm1.linear")
        if is_dual:
            # AdaLayerNormZeroX: 9 chunks; the first 6 are the standard
            # (shift, scale, gate) x (attn, mlp) layout, the last 3 are
            # the dual attention's (shift_msa2, scale_msa2, gate_msa2)
            x_mod2 = {"kernel": x_mod["kernel"][:, 6 * hidden:],
                      "bias": x_mod["bias"][6 * hidden:]}
            x_mod = {"kernel": x_mod["kernel"][:, :6 * hidden],
                     "bias": x_mod["bias"][:6 * hidden]}
            dual_block = {
                "x_mod2": x_mod2,
                "x2_qkv": fused3(f"{b}.attn2.to_q", f"{b}.attn2.to_k",
                                 f"{b}.attn2.to_v"),
                "x2_out": lin(f"{b}.attn2.to_out.0"),
            }
            if f"{b}.attn2.norm_q.weight" in sd:
                dual_block["x2_qnorm"] = get(f"{b}.attn2.norm_q.weight")
                dual_block["x2_knorm"] = get(f"{b}.attn2.norm_k.weight")
            blocks_dual.append(dual_block)
        block = {
            "x_mod": x_mod,
            "c_mod": c_mod,
            "x_qkv": fused3(f"{b}.attn.to_q", f"{b}.attn.to_k",
                            f"{b}.attn.to_v"),
            "c_qkv": ckv,
            "x_out": lin(f"{b}.attn.to_out.0"),
            "c_out": c_out,
            "x_fc1": lin(f"{b}.ff.net.0.proj"),
            "x_fc2": lin(f"{b}.ff.net.2"),
            "c_fc1": c_fc1,
            "c_fc2": c_fc2,
        }
        if f"{b}.attn.norm_q.weight" in sd:
            # SD3.5 per-head q/k RMSNorm (qk_norm="rms_norm"); the final
            # block has no context queries, so its absent norm_added_q
            # weight is filled with ones (that norm's output is part of
            # the discarded context-query rows)
            block["x_qnorm"] = get(f"{b}.attn.norm_q.weight")
            block["x_knorm"] = get(f"{b}.attn.norm_k.weight")
            block["c_knorm"] = get(f"{b}.attn.norm_added_k.weight")
            block["c_qnorm"] = (
                get(f"{b}.attn.norm_added_q.weight")
                if f"{b}.attn.norm_added_q.weight" in sd
                else np.ones_like(block["x_qnorm"])
            )
        blocks.append(block)

    pw = get("pos_embed.proj.weight")  # conv [hidden, C, ps, ps]
    hidden = pw.shape[0]
    proj_in = {
        "kernel": pw.transpose(2, 3, 1, 0).reshape(-1, hidden),
        "bias": get("pos_embed.proj.bias"),
    }
    tree = {
        "proj_in": proj_in,
        "ctx_in": lin("context_embedder"),
        "t_fc1": lin("time_text_embed.timestep_embedder.linear_1"),
        "t_fc2": lin("time_text_embed.timestep_embedder.linear_2"),
        "pool_fc1": lin("time_text_embed.text_embedder.linear_1"),
        "pool_fc2": lin("time_text_embed.text_embedder.linear_2"),
        "final_mod": swap_scale_shift(lin("norm_out.linear")),
        "final_out": lin("proj_out"),
        "blocks": _stack_layers(blocks),
    }
    if blocks_dual:
        tree["blocks_dual"] = _stack_layers(blocks_dual)
    return _cast(tree, dtype)


# ---------------------------------------------------------------------------
# quantized-weight trees (DistriConfig.weight_quant / weight_quant_aux)
# ---------------------------------------------------------------------------

# Layer names whose kernels NEVER quantize: the model output heads.  Their
# rounding error adds directly to the predicted noise/velocity (no
# downstream layer attenuates it), and they are a vanishing fraction of the
# param bytes — the classic "keep first/last layers dense" PTQ policy,
# applied to the last layer only (the input embeds feed deep stacks that
# wash their error out).
_DENSE_LAYERS = frozenset({"conv_out", "final_out"})


def quantize_params(tree, mode: str, *, compute: str = "dequant",
                    channel_tile: int = 1):
    """Quantize every matmul/conv kernel of a converted param tree to the
    weight mode ("int8" / "fp8"; "none" returns the tree untouched — the
    bit-identity guarantee of the default config, so it REFUSES trees that
    already carry quantized leaves).

    ``compute`` tags each QuantizedTensor with its execution policy
    ("dequant" = PR-6 lazy-dequant storage semantics; "auto"/"dot"
    run the consuming matmul as a low-precision dot, ops/linear.py —
    DistriConfig.quant_compute maps "off" to "dequant" here).  ``channel_tile`` groups output channels per scale
    (1 = per-channel, the parity-pinned default).  On an ALREADY-quantized
    tree at the same mode, payloads and scales are kept bit-identical and
    only the compute policy re-tags (a reloaded archive carries storage,
    not policy).

    Only leaves under a ``"kernel"`` dict key with ndim >= 2 quantize — the
    layout contract of this module's converters puts exactly the matmul and
    conv weights there.  Norm ``scale``s, biases, embeddings, modulation
    tables, and every other leaf stay full precision: they are small, and
    (for norms/embeddings) precision-critical far beyond their byte share.
    The OUTPUT HEAD (`_DENSE_LAYERS`: UNet conv_out, DiT/MMDiT final_out)
    also stays dense — standard post-training-quantization serving policy:
    its rounding error lands unattenuated in the predicted noise/velocity,
    it is a vanishing byte share, and keeping it dense is what holds the
    end-to-end parity inside the pinned tolerances (docs/PERF.md).
    Each kernel becomes a `parallel.compress.QuantizedTensor` (int8/fp8
    payload + one fp32 scale per output-channel tile) that dequantizes
    lazily at its consuming dot/conv, so XLA fuses the convert and HBM
    holds the 1-byte payload.
    """
    from ..parallel.compress import (
        QuantizedTensor,
        quantize_weight,
        validate_weight_mode,
    )

    validate_weight_mode(mode)
    # config-level "off" (DistriConfig.quant_compute) is the leaf-level
    # "dequant" policy
    compute = "dequant" if compute == "off" else compute
    if mode == "none":
        # "none" is the bit-identity guarantee of the default config — a
        # tree still carrying QuantizedTensor leaves (a quantized .npz
        # cache loaded into a weight_quant="none" pipeline) would silently
        # serve quantized numerics while config / weight_report / ExecKey
        # all claim full precision.  Refuse like the mode-switch path;
        # dequantize_params is the explicit opt-in to quantized values
        # under a dense layout.
        def check(node):
            if isinstance(node, list):
                for v in node:
                    check(v)
            elif isinstance(node, dict):
                for v in node.values():
                    check(v)
            elif isinstance(node, QuantizedTensor):
                raise ValueError(
                    "quantize_params('none') on an already-quantized "
                    "tree: 'none' promises bit-identity with the dense "
                    "weights, which this tree no longer holds — rebuild "
                    "from the dense tree, construct the pipeline with "
                    "weight_quant matching the archive, or densify "
                    "explicitly via dequantize_params"
                )

        check(tree)
        return tree

    def walk(node, name=""):
        if isinstance(node, list):
            return [walk(v, name) for v in node]
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if (k == "kernel" and not isinstance(v, (dict, list))
                        and getattr(v, "ndim", 0) >= 2
                        and name not in _DENSE_LAYERS):
                    if isinstance(v, QuantizedTensor):
                        # idempotent at the SAME mode (a pre-quantized
                        # .npz cache loads straight into a
                        # weight_quant=mode pipeline); a mode switch
                        # would requantize quantized values and compound
                        # the rounding error — refuse
                        have = ("int8" if v.payload.dtype == jnp.int8
                                else "fp8")
                        if have == mode:
                            # storage is baked (payload, scale, tile
                            # granularity); the EXECUTION policy re-tags
                            # to this call's config
                            out[k] = (v if v.compute == compute else
                                      QuantizedTensor(v.payload, v.scale,
                                                      v.dtype, compute,
                                                      v.channel_tile))
                            continue
                        raise ValueError(
                            f"quantize_params({mode!r}) on a tree already "
                            f"quantized at {have!r}: requantizing "
                            "compounds the rounding error — rebuild from "
                            "the dense tree"
                        )
                    out[k] = quantize_weight(jnp.asarray(v), mode,
                                             compute=compute,
                                             channel_tile=channel_tile)
                else:
                    out[k] = walk(v, k)
            return out
        return node

    return walk(tree)


def set_quant_compute(tree, policy: str):
    """Re-tag every `QuantizedTensor` leaf's EXECUTION policy without
    touching payloads or scales (DistriConfig.quant_compute semantics:
    "off" maps to the leaf-level "dequant").  Cheap and numerics-free on
    its own — the policy only selects which matmul path the next trace
    takes — so pipelines apply it to reloaded archives (which carry
    storage, not policy) and the serve layer applies ExecKey.quant_compute
    through it.  Identity on dense trees."""
    from ..parallel.compress import QuantizedTensor

    leaf = "dequant" if policy == "off" else policy
    if leaf not in ("dequant", "auto", "dot"):
        raise ValueError(
            f"quant_compute policy must be 'off', 'auto', or 'dot', got "
            f"{policy!r}"
        )

    def walk(node):
        if isinstance(node, list):
            return [walk(v) for v in node]
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, QuantizedTensor) and node.compute != leaf:
            return QuantizedTensor(node.payload, node.scale, node.dtype,
                                   leaf, node.channel_tile)
        return node

    return walk(tree)


def dequantize_params(tree):
    """Densify every `QuantizedTensor` leaf back to a plain array.  The
    values are the *dequantized* kernels — exactly what the quantized
    forward computed with, NOT the original full-precision weights (the
    per-tile rounding is baked in)."""
    from ..parallel.compress import QuantizedTensor

    def walk(node):
        if isinstance(node, list):
            return [walk(v) for v in node]
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, QuantizedTensor):
            return node.__jax_array__()
        return node

    return walk(tree)


def params_nbytes(tree) -> int:
    """Exact weight-HBM bytes of a param tree: the closed-form sum over
    leaves (`QuantizedTensor` kernels count payload + scales — its leaves
    ARE the resident buffers).  The serve fleet's per-executor weight
    reports and scripts/bench_weights.py both read this."""
    import jax

    total = 0
    for leaf in jax.tree.leaves(tree):
        total += int(leaf.size) * int(np.dtype(leaf.dtype).itemsize)
    return total


# ---------------------------------------------------------------------------
# on-disk cache of converted trees
# ---------------------------------------------------------------------------

# Reserved npz leaf names for a QuantizedTensor kernel: payload, fp32
# scales, and the (compute dtype, payload dtype, channel_tile) record —
# npz does not round-trip ml_dtypes' float8 (older numpy loads it as a
# void view; newer versions can refuse the descr outright), so fp8
# payloads are stored as EXPLICIT uint8 byte views and the recorded dtype
# is viewed back on load.  channel_tile must be recorded too: with
# grouped scales the scale length is ceil(out/tile), which is NOT
# derivable from the payload shape when the last tile is partial — a
# loader that assumed per-channel scales would rebuild a misaligned
# QuantizedTensor (QuantizedTensor.__init__ now refuses that loudly).
# Legacy archives (2-element dtype record, raw payload) still load.
_QT_PAYLOAD, _QT_SCALE, _QT_DTYPES = "__wq__", "__wqs__", "__wqd__"

# Dense leaves with ml_dtypes dtypes (bfloat16 trees) hit the same npz void
# problem as fp8 payloads: store a uint8 byte view plus the dtype name and
# view back on load.
_RAW_VALUE, _RAW_DTYPE = "__wqr__", "__wqrd__"


def _weight_payload_dtype(name: str):
    if name == "int8":
        return np.dtype(np.int8)
    from ..parallel.compress import fp8_dtype

    dt = fp8_dtype()
    if dt is None or np.dtype(dt).name != name:
        raise ValueError(
            f"saved quantized payload dtype {name!r} is not available in "
            "this jax build"
        )
    return np.dtype(dt)


def _flatten(tree, prefix=""):
    from ..parallel.compress import QuantizedTensor

    flat = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            flat.update(_flatten(v, f"{prefix}{k}."))
    elif isinstance(tree, list):
        for i, v in enumerate(tree):
            flat.update(_flatten(v, f"{prefix}{i}."))
    elif isinstance(tree, QuantizedTensor):
        payload = np.asarray(tree.payload)
        if payload.dtype.kind == "V":  # ml_dtypes fp8: store uint8 bytes
            payload = np.ascontiguousarray(payload).view(np.uint8)
        flat[f"{prefix}{_QT_PAYLOAD}"] = payload
        flat[f"{prefix}{_QT_SCALE}"] = np.asarray(tree.scale, np.float32)
        flat[f"{prefix}{_QT_DTYPES}"] = np.array(
            [np.dtype(tree.dtype).name, np.dtype(tree.payload.dtype).name,
             str(tree.channel_tile)]
        )
    else:
        v = np.asarray(tree)
        if v.dtype.kind == "V":  # ml_dtypes (bf16/fp8): npz would void it
            flat[f"{prefix}{_RAW_VALUE}"] = (
                np.ascontiguousarray(v).view(np.uint8))
            flat[f"{prefix}{_RAW_DTYPE}"] = np.array(v.dtype.name)
        else:
            flat[prefix[:-1]] = v
    return flat


def save_params(path: str, tree) -> None:
    """Cache a converted tree as one flat .npz — quantized trees included
    (int8/fp8 payload + fp32 scales in the same archive), so conversion
    AND quantization run once and a server restart mmaps the result."""
    np.savez(path, **_flatten(tree))


def _restore(tree, dtype):
    """Nested npz dicts -> param tree: QuantizedTensor markers rebuilt
    (payload dtype viewed back — npz voids fp8), everything else cast to
    ``dtype``.  jnp.array copies (never zero-copy views) for the same
    mmap-lifetime reason as _cast."""
    from ..parallel.compress import QuantizedTensor

    if isinstance(tree, list):
        return [_restore(v, dtype) for v in tree]
    if isinstance(tree, dict):
        if _QT_PAYLOAD in tree:
            names = [str(x) for x in tree[_QT_DTYPES]]
            pdt = _weight_payload_dtype(names[1])
            # legacy (pre-channel_tile) archives recorded only the dtype
            # pair; they were always per-channel
            ct = int(names[2]) if len(names) > 2 else 1
            payload = np.asarray(tree[_QT_PAYLOAD])
            if payload.dtype != pdt:
                # uint8 byte view (current archives) or numpy's void view
                # of an ml_dtypes payload (legacy): both are 1-byte and
                # view back shape-preserving
                payload = payload.view(pdt)
            return QuantizedTensor(
                jnp.array(payload),
                jnp.array(tree[_QT_SCALE], jnp.float32),
                jnp.dtype(names[0]),
                channel_tile=ct,
            )
        if _RAW_VALUE in tree:
            raw = np.asarray(tree[_RAW_VALUE]).view(
                np.dtype(str(tree[_RAW_DTYPE])))
            return jnp.array(raw, dtype)
        return {k: _restore(v, dtype) for k, v in tree.items()}
    return jnp.array(tree, dtype)


def load_params(path: str, dtype=None):
    """Load a `save_params` archive back into a param tree.

    A DENSE archive casts to ``dtype`` (default float32), exactly like the
    converters always did.  A QUANTIZED archive's compute dtype comes from
    the archive itself — the per-tile scales were baked against the
    quantized kernel's original dtype — and the WHOLE tree (norms, biases,
    embeddings included) adopts it, so a reload never produces a
    mixed-precision tree the quantize-at-load path cannot.  Passing an
    explicit ``dtype`` that disagrees with a quantized archive raises:
    a caller wanting a different compute dtype rebuilds from the dense
    weights."""
    data = np.load(path)
    tree: Dict[str, Any] = {}
    recorded = set()
    for key in data.files:
        _assign(tree, key.split("."), data[key])
        if key.split(".")[-1] == _QT_DTYPES:
            recorded.add(str(data[key][0]))
    if recorded:
        if len(recorded) > 1:
            raise ValueError(
                f"quantized archive {path!r} mixes compute dtypes "
                f"{sorted(recorded)}"
            )
        archived = jnp.dtype(recorded.pop())
        if dtype is not None and jnp.dtype(dtype) != archived:
            raise ValueError(
                f"load_params(dtype={jnp.dtype(dtype).name!r}) on a "
                f"quantized archive with compute dtype {archived.name!r}: "
                "the per-tile scales were baked against the archived dtype "
                "— rebuild from the dense weights to change compute dtype"
            )
        dtype = archived
    return _restore(_listify(tree), dtype or jnp.float32)
