"""Pallas flash attention vs the XLA softmax oracle (interpret mode on CPU)."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distrifuser_tpu.ops.attention import sdpa
from distrifuser_tpu.ops.flash_attention import flash_sdpa


@pytest.mark.parametrize("b,l,heads,d", [(1, 256, 2, 16), (2, 384, 1, 32)])
def test_flash_matches_sdpa(b, l, heads, d):
    c = heads * d
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (b, l, c))
    k = jax.random.normal(keys[1], (b, l, c))
    v = jax.random.normal(keys[2], (b, l, c))
    want = sdpa(q, k, v, heads=heads)
    got = flash_sdpa(q, k, v, heads=heads, block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_cross_lengths():
    # Lq != Lk (e.g. stale-KV patch attention: local q, global kv)
    b, heads, d = 1, 2, 16
    c = heads * d
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(keys[0], (b, 128, c))
    k = jax.random.normal(keys[1], (b, 512, c))
    v = jax.random.normal(keys[2], (b, 512, c))
    want = sdpa(q, k, v, heads=heads)
    got = flash_sdpa(q, k, v, heads=heads, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_numerical_stability_large_logits():
    b, heads, d = 1, 1, 8
    c = d
    q = jnp.ones((b, 128, c)) * 30.0
    k = jnp.ones((b, 256, c)) * 30.0
    v = jax.random.normal(jax.random.PRNGKey(2), (b, 256, c))
    got = flash_sdpa(q, k, v, heads=heads, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    # all logits equal -> output is the mean of v
    np.testing.assert_allclose(
        np.asarray(got[0, 0]), np.asarray(v.mean(axis=1)[0]), atol=1e-4
    )


def test_routing_gates(monkeypatch):
    from distrifuser_tpu.ops.sdpa_routing import route

    monkeypatch.delenv("DISTRIFUSER_TPU_FLASH", raising=False)
    # CPU default: no flash
    assert route(256, 256, 32, 2, "cpu").impl == "xla"
    monkeypatch.setenv("DISTRIFUSER_TPU_FLASH", "1")
    assert route(256, 256, 32, 2, "cpu").impl == "inrepo"
    # unaligned length -> never
    assert route(200, 256, 32, 2, "cpu").impl == "xla"


def test_forced_flash_on_cpu_uses_interpret(monkeypatch):
    """DISTRIFUSER_TPU_FLASH=1 on a CPU backend must route sdpa through the
    interpret-mode kernel (Mosaic only compiles for TPU) and match XLA."""
    b, l, heads, d = 1, 128, 2, 16
    c = heads * d
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(keys[0], (b, l, c))
    k = jax.random.normal(keys[1], (b, l, c))
    v = jax.random.normal(keys[2], (b, l, c))
    plain = sdpa(q, k, v, heads=heads)
    monkeypatch.setenv("DISTRIFUSER_TPU_FLASH", "1")
    forced = sdpa(q, k, v, heads=heads)
    np.testing.assert_allclose(np.asarray(forced), np.asarray(plain), atol=2e-5)


def test_chunked_sdpa_matches_direct(monkeypatch):
    """Query chunking must be numerically identical to the direct path."""
    import importlib

    attn_mod = importlib.import_module("distrifuser_tpu.ops.attention")

    # l=500 does NOT divide the chunk counts below, so both branches must
    # actually pad queries to uniform chunks and slice the pad rows off
    b, l, heads, d = 1, 500, 2, 16
    c = heads * d
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(keys[0], (b, l, c))
    k = jax.random.normal(keys[1], (b, l, c))
    v = jax.random.normal(keys[2], (b, l, c))
    direct = sdpa(q, k, v, heads=heads)
    # force chunking by shrinking the threshold: 1<<16 -> 8 chunks, the
    # UNROLLED branch (n_chunks <= 16); 500 % 8 != 0 -> pad to 504
    monkeypatch.setattr(attn_mod, "_CHUNK_LOGITS_ELEMS", 1 << 16)
    chunked = sdpa(q, k, v, heads=heads)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(direct), atol=1e-5)
    # 1<<13 -> 64 chunks, the ROLLED lax.map branch (compile-size bound);
    # 500 % 64 != 0 -> pad to 512
    monkeypatch.setattr(attn_mod, "_CHUNK_LOGITS_ELEMS", 1 << 13)
    rolled = sdpa(q, k, v, heads=heads)
    np.testing.assert_allclose(np.asarray(rolled), np.asarray(direct), atol=1e-5)


def test_flash_bf16_inputs():
    """The on-TPU dtype: bf16 q/k/v with fp32 accumulators."""
    b, l, heads, d = 1, 256, 2, 16
    c = heads * d
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(keys[0], (b, l, c), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, l, c), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, l, c), jnp.bfloat16)
    got = flash_sdpa(q, k, v, heads=heads, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = sdpa(q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), heads=heads)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), atol=0.03
    )


def test_padded_flash_matches_reference():
    """Pad-and-mask flash for unaligned lengths (SD3's joint stream): the
    kv_len mask must make alignment padding numerically invisible."""
    from distrifuser_tpu.ops.flash_attention import padded_flash_sdpa

    b, heads, d = 2, 2, 16
    c = heads * d
    # 330 = unaligned; pads to 384 with 54 masked KV columns
    lq = lk = 330
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(keys[0], (b, lq, c))
    k = jax.random.normal(keys[1], (b, lk, c))
    v = jax.random.normal(keys[2], (b, lk, c))

    import importlib
    attn_mod = importlib.import_module("distrifuser_tpu.ops.attention")
    ref = attn_mod._sdpa_xla(
        q.reshape(b, lq, heads, d), k.reshape(b, lk, heads, d),
        v.reshape(b, lk, heads, d), 1.0 / d**0.5,
    ).reshape(b, lq, c)

    out = padded_flash_sdpa(q, k, v, heads=heads, impl="inrepo",
                            interpret=True)
    assert out.shape == (b, lq, c)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    # aligned input degenerates to the plain kernel (no mask, no slice)
    q128 = q[:, :256]
    out128 = padded_flash_sdpa(q128, k[:, :256], v[:, :256], heads=heads,
                               impl="inrepo", interpret=True)
    ref128 = attn_mod._sdpa_xla(
        q128.reshape(b, 256, heads, d), k[:, :256].reshape(b, 256, heads, d),
        v[:, :256].reshape(b, 256, heads, d), 1.0 / d**0.5,
    ).reshape(b, 256, c)
    np.testing.assert_allclose(np.asarray(out128), np.asarray(ref128),
                               atol=2e-5, rtol=2e-5)


def test_padding_segment_ids_match_kv_len_semantics():
    """The upstream SegmentIds pad mask, built for an unaligned
    shape, must encode exactly the in-repo kernel's static kv_len mask —
    real query rows attend the first lk KV positions and nothing else.
    Pure mask math, CI-exercisable without a Mosaic compile."""
    from distrifuser_tpu.ops.flash_attention import padding_segment_ids

    b, lq, lk = 2, 330, 215  # both unaligned; pad to 384 / 256
    lq_pad, lk_pad = 384, 256
    seg = padding_segment_ids(b, lq, lq_pad, lk, lk_pad)
    assert seg.q.shape == (b, lq_pad) and seg.kv.shape == (b, lk_pad)
    # the upstream kernel masks cross-segment pairs: allowed = equal ids
    allowed = np.asarray(seg.q)[:, :, None] == np.asarray(seg.kv)[:, None, :]
    col = np.arange(lk_pad)
    for i in range(lq):  # real rows: exactly the kv_len mask col < lk
        np.testing.assert_array_equal(allowed[0, i], col < lk)
    # pad rows attend only pad KV (garbage rows the caller slices off) —
    # never real tokens, so they cannot perturb the normalizer of real rows
    for i in range(lq, lq_pad):
        np.testing.assert_array_equal(allowed[0, i], col >= lk)


def test_padded_flash_runs_the_resolved_kernel_or_raises(monkeypatch):
    """The resolved kernel runs or the call raises: a failing upstream
    kernel is never replaced by the in-repo one (or by XLA softmax) behind
    the caller's back, and impl="inrepo" keeps padded_flash_sdpa off the
    upstream segment-ids path."""
    import importlib

    attn_mod = importlib.import_module("distrifuser_tpu.ops.attention")
    fa = importlib.import_module("distrifuser_tpu.ops.flash_attention")

    b, heads, d = 1, 2, 16
    c = heads * d
    lq = lk = 200  # unaligned -> pads to 256
    keys = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(keys[0], (b, lq, c))
    k = jax.random.normal(keys[1], (b, lk, c))
    v = jax.random.normal(keys[2], (b, lk, c))

    class MosaicRefused(RuntimeError):
        pass

    def failing_upstream(*a, **kw):
        raise MosaicRefused("upstream kernel refused")

    inrepo_calls = []
    real_flash = fa.flash_sdpa

    def spy_inrepo(*a, **kw):
        inrepo_calls.append(kw)
        return real_flash(*a, **kw)

    monkeypatch.setattr(fa, "upstream_flash_sdpa", failing_upstream)
    monkeypatch.setattr(fa, "flash_sdpa", spy_inrepo)
    monkeypatch.delenv("DISTRIFUSER_TPU_FLASH", raising=False)

    # 1) default route = upstream: its failure propagates, nothing else runs
    with pytest.raises(MosaicRefused):
        fa.padded_flash_sdpa(q, k, v, heads=heads)
    assert not inrepo_calls

    # 2) interpret mode belongs to the in-repo kernel only
    with pytest.raises(ValueError, match="impl='inrepo'"):
        fa.padded_flash_sdpa(q, k, v, heads=heads, interpret=True)

    # 3) impl="inrepo" is an explicit route to the in-repo kernel; any
    # other name is refused, never read as one of the two
    out = fa.padded_flash_sdpa(q, k, v, heads=heads, interpret=True,
                               impl="inrepo")
    assert out.shape == (b, lq, c) and len(inrepo_calls) == 1
    with pytest.raises(ValueError, match="'upstream' or 'inrepo'"):
        fa.padded_flash_sdpa(q, k, v, heads=heads, impl="xla")

    # 4) sdpa on a TPU platform: the padded route raises through sdpa (no
    # XLA-softmax fall-through), and so does the aligned table route
    class _Dev:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda: [_Dev()])
    long_q = jnp.zeros((1, 1100, c))  # unaligned and >= FLASH_MIN_LEN
    with pytest.raises(MosaicRefused):
        attn_mod.sdpa(long_q, long_q, long_q, heads=heads)
    far = jnp.zeros((1, 16384, 2 * 64))  # d=64, 8320..32768: upstream's
    with pytest.raises(MosaicRefused):
        attn_mod.sdpa(far, far, far, heads=2)
    assert len(inrepo_calls) == 1

    # ... and the cells' shapes, which the table sends to the in-repo
    # kernel: when IT is refused, neither the upstream kernel nor XLA runs
    def failing_inrepo(*a, **kw):
        raise MosaicRefused("in-repo kernel refused")

    upstream_calls = []
    monkeypatch.setattr(fa, "flash_sdpa", failing_inrepo)
    monkeypatch.setattr(fa, "upstream_flash_sdpa",
                        lambda *a, **kw: upstream_calls.append(kw))
    monkeypatch.setattr(attn_mod, "_sdpa_xla",
                        lambda *a, **kw: upstream_calls.append(kw))
    aligned = jnp.zeros((1, 1024, 2 * 64))  # d=64, 768..1408
    with pytest.raises(MosaicRefused, match="in-repo"):
        attn_mod.sdpa(aligned, aligned, aligned, heads=2)
    assert not upstream_calls


def test_upstream_route_on_cpu_is_an_error(monkeypatch):
    """The upstream Mosaic kernel cannot run on the CPU platform; asking
    for it there raises instead of quietly running the in-repo kernel."""
    from distrifuser_tpu.ops import sdpa_routing
    from distrifuser_tpu.ops.sdpa_routing import Route

    monkeypatch.setattr(sdpa_routing, "route",
                        lambda *a: Route("upstream"))
    x = jnp.zeros((1, 128, 32))
    with pytest.raises(ValueError, match="needs a TPU"):
        sdpa(x, x, x, heads=2)


def _oracle(q, k, v, heads, kv_len=None):
    """`_sdpa_xla` over the first `kv_len` KV positions, on [B, L, C]."""
    from distrifuser_tpu.ops.attention import _sdpa_xla

    b, lq, c = q.shape
    d = c // heads
    if kv_len is not None:
        k, v = k[:, :kv_len], v[:, :kv_len]
    lk = k.shape[1]
    return _sdpa_xla(
        q.reshape(b, lq, heads, d), k.reshape(b, lk, heads, d),
        v.reshape(b, lk, heads, d), 1.0 / d**0.5).reshape(b, lq, c)


@pytest.mark.parametrize("block_q,block_k", [(128, 128), (256, 128),
                                             (128, 256)])
@pytest.mark.parametrize("lq,lk,kv_len", [
    (256, 256, None),   # the cells' self-attention: Lq == Lk
    (256, 512, None),   # patch-parallel: local Q rows, gathered KV
    (256, 256, 200),    # the pad mask crosses a chunk (and, at 256, is the
    (256, 512, 300),    # whole loop); whole chunks beyond it never run
    (256, 512, 256),    # the mask ends exactly on a chunk boundary
])
@pytest.mark.parametrize("d", [64, 72])
def test_seq_minor_kernel_matches_xla(d, lq, lk, kv_len, block_q, block_k):
    """The sequence-minor kernel against the XLA softmax at the cells' head
    dims (SDXL's 64, PixArt's 72), every loop shape `kv_len` can make."""
    b, heads = 2, 2
    c = heads * d
    keys = jax.random.split(jax.random.PRNGKey(d + lq + lk), 3)
    q = jax.random.normal(keys[0], (b, lq, c))
    k = jax.random.normal(keys[1], (b, lk, c))
    v = jax.random.normal(keys[2], (b, lk, c))
    got = flash_sdpa(q, k, v, heads=heads, block_q=block_q, block_k=block_k,
                     interpret=True, kv_len=kv_len)
    assert got.shape == (b, lq, c)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_oracle(q, k, v, heads, kv_len)),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kv_len", [None, 2200])
def test_seq_minor_kernel_long_kv_takes_the_grouped_loop(kv_len):
    """More KV chunks than the kernel lays out as straight-line code: whole
    groups in a loop, the remainder after it, then the masked chunk."""
    from distrifuser_tpu.ops.flash_attention import _KV_UNROLL

    b, heads, d, lq, lk, block_k = 1, 1, 64, 128, 2304, 128
    assert (kv_len or lk) // block_k > 2 * _KV_UNROLL
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (b, lq, d))
    k = jax.random.normal(keys[1], (b, lk, d))
    v = jax.random.normal(keys[2], (b, lk, d))
    got = flash_sdpa(q, k, v, heads=heads, block_q=128, block_k=block_k,
                     interpret=True, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_oracle(q, k, v, heads, kv_len)),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("d", [64, 72])
def test_seq_minor_kernel_bf16_against_float32_softmax(d):
    """bf16 operands, float32 statistics and accumulator: as far from a
    float32 softmax as bf16 inputs put any kernel, and no further."""
    b, heads, lq, lk = 1, 2, 256, 512
    c = heads * d
    keys = jax.random.split(jax.random.PRNGKey(d), 3)
    q, k, v = (jax.random.normal(kk, (b, l, c), jnp.bfloat16)
               for kk, l in zip(keys, (lq, lk, lk)))
    got = flash_sdpa(q, k, v, heads=heads, block_q=128, block_k=256,
                     interpret=True)
    assert got.dtype == jnp.bfloat16
    want = _oracle(*(x.astype(jnp.float32) for x in (q, k, v)), heads)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=5e-3)


def test_seq_minor_kernel_refuses_what_does_not_fit():
    """Tiles that do not divide the lengths, and a KV too long for one
    head's K and V to stay in VMEM, are refused at trace - loudly."""
    x = jnp.zeros((1, 256, 128))
    with pytest.raises(ValueError, match="block_q"):
        flash_sdpa(x, x, x, heads=2, block_q=96, interpret=True)
    with pytest.raises(ValueError, match="block_k"):
        flash_sdpa(x, x, x, heads=2, block_k=96, interpret=True)
    long_kv = jax.ShapeDtypeStruct((1, 1 << 20, 128), jnp.bfloat16)
    q = jax.ShapeDtypeStruct((1, 256, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="VMEM"):
        jax.eval_shape(lambda q, k, v: flash_sdpa(q, k, v, heads=2,
                                                  interpret=True),
                       q, long_kv, long_kv)
