"""Persistent AOT executable cache (distrifuser_tpu/serve/aotcache.py):
the checksummed envelope and its typed rejections, store round-trip +
self-healing fallback, readonly/CI mode, LRU byte-budget eviction,
chaos on the load/save wire, warm-from-store replica start on fakes,
and bit-identity of cache-warm vs cold-compile on the real tiny config.
"""

import os
import struct
import tempfile

import numpy as np
import pytest

from distrifuser_tpu.serve.aotcache import (
    FORMAT_VERSION,
    MAGIC,
    AotExecutableCache,
    decode_entry,
    encode_entry,
    entry_address,
)
from distrifuser_tpu.serve.errors import AotCacheRejectedError
from distrifuser_tpu.serve.faults import FaultPlan, FaultRule
from distrifuser_tpu.serve.replica import Replica
from distrifuser_tpu.serve.testing import FakeExecutorFactory
from distrifuser_tpu.utils.aot import (
    active_aot_scope,
    aot_activation,
    runtime_fingerprint,
)
from distrifuser_tpu.utils.config import AotCacheConfig, ServeConfig


def mk_store(tmp_path, **kw):
    kw.setdefault("dir", str(tmp_path))
    return AotExecutableCache(AotCacheConfig(**kw))


def fp_for(store, scope="unet:64x64", **kw):
    return store.fingerprint(scope, **kw)


# --------------------------------------------------------------------------
# envelope: round-trip + every rejection class
# --------------------------------------------------------------------------


def test_envelope_round_trip():
    fp = {"scope": "s", "jax": "1", "jaxlib": "2", "backend": "cpu",
          "mesh_shape": "", "layout": ""}
    payload = b"program-bytes" * 100
    data = encode_entry(fp, payload)
    assert data[:4] == MAGIC
    assert decode_entry(data, fp) == payload


def test_envelope_rejects_truncation_and_corruption():
    fp = {"scope": "s", "jaxlib": "2"}
    data = encode_entry(fp, b"x" * 64)
    with pytest.raises(AotCacheRejectedError, match="truncated"):
        decode_entry(data[:8], fp)
    with pytest.raises(AotCacheRejectedError, match="checksum"):
        decode_entry(data[:-10], fp)  # digest no longer matches
    flipped = bytearray(data)
    flipped[len(data) // 2] ^= 0xFF
    with pytest.raises(AotCacheRejectedError, match="checksum"):
        decode_entry(bytes(flipped), fp)


def _resign(body: bytes) -> bytes:
    import hashlib

    return body + hashlib.sha256(body).digest()


def test_envelope_rejects_bad_magic_and_version_skew():
    fp = {"scope": "s"}
    data = encode_entry(fp, b"payload")
    body = data[:-32]
    with pytest.raises(AotCacheRejectedError, match="bad magic"):
        decode_entry(_resign(b"XXXX" + body[4:]), fp)
    # rewrite the header with a future format version and re-sign: the
    # checksum is fine, the version gate must fire
    (hlen,) = struct.unpack_from(">I", body, 4)
    import json

    meta = json.loads(body[8:8 + hlen])
    meta["format"] = FORMAT_VERSION + 1
    hdr = json.dumps(meta, sort_keys=True).encode()
    rebuilt = MAGIC + struct.pack(">I", len(hdr)) + hdr + body[8 + hlen:]
    with pytest.raises(AotCacheRejectedError, match="format version"):
        decode_entry(_resign(rebuilt), fp)


def test_envelope_rejects_fingerprint_skew():
    """A structurally intact entry whose fingerprint names a different
    jaxlib must reject, naming the differing field — version skew never
    loads a foreign program."""
    fp = {"scope": "s", "jax": "1.2.3", "jaxlib": "1.2.3"}
    data = encode_entry(fp, b"payload")
    other = dict(fp, jaxlib="1.2.4")
    with pytest.raises(AotCacheRejectedError, match="jaxlib"):
        decode_entry(data, other)


# --------------------------------------------------------------------------
# store: round-trip, self-heal, addressing
# --------------------------------------------------------------------------


def test_store_round_trip_and_miss(tmp_path):
    store = mk_store(tmp_path)
    fp = fp_for(store)
    assert store.get(fp) is None  # cold
    assert store.put(fp, b"hello world")
    assert store.get(fp) == b"hello world"
    s = store.stats()
    assert s["hits"] == 1 and s["misses"] == 1 and s["saves"] == 1
    assert s["entries"] == 1 and s["rejects"] == 0
    # a second store on the same dir adopts the entry (persistence)
    store2 = mk_store(tmp_path)
    assert store2.get(fp_for(store2)) == b"hello world"
    assert store2.stats()["hits"] == 1


def test_runtime_version_is_part_of_the_address(tmp_path):
    """Entries from a different jax/jaxlib live at different addresses:
    skew is a MISS (compile fresh), and the foreign entry survives for
    the runtime that wrote it."""
    store = mk_store(tmp_path)
    fp = fp_for(store)
    store.put(fp, b"ours")
    foreign = dict(fp, jaxlib="0.0.0-other")
    assert entry_address(foreign) != entry_address(fp)
    assert store.get(foreign) is None
    assert store.stats()["rejects"] == 0
    assert store.get(fp) == b"ours"


def test_on_disk_corruption_rejects_and_self_heals(tmp_path):
    store = mk_store(tmp_path)
    fp = fp_for(store)
    store.put(fp, b"good bytes")
    path = os.path.join(str(tmp_path), entry_address(fp) + ".aot")
    raw = bytearray(open(path, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    assert store.get(fp) is None  # typed reject -> counted -> fallback
    s = store.stats()
    assert s["rejects"] == 1 and s["entries"] == 0
    assert not os.path.exists(path)  # the bad entry was deleted
    # the raw `load` raises typed (the un-counted primitive `get` wraps)
    store.put(fp, b"good bytes")
    raw2 = bytearray(open(path, "rb").read())
    raw2[-1] ^= 0xFF
    open(path, "wb").write(bytes(raw2))
    with pytest.raises(AotCacheRejectedError, match="checksum"):
        store.load(fp)


def test_renamed_entry_never_loads_as_wrong_program(tmp_path):
    """The 'never a wrong program' guarantee: a file copied onto another
    fingerprint's address fails the header fingerprint check even though
    its bytes are intact."""
    store = mk_store(tmp_path)
    fp_a = fp_for(store, scope="prog-a")
    fp_b = fp_for(store, scope="prog-b")
    store.put(fp_a, b"program-a")
    os.rename(os.path.join(str(tmp_path), entry_address(fp_a) + ".aot"),
              os.path.join(str(tmp_path), entry_address(fp_b) + ".aot"))
    store2 = mk_store(tmp_path)  # re-scan picks up the renamed file
    assert store2.get(fp_b) is None
    assert store2.stats()["rejects"] == 1


# --------------------------------------------------------------------------
# readonly mode + LRU eviction
# --------------------------------------------------------------------------


def test_readonly_store_loads_but_never_writes(tmp_path):
    writer = mk_store(tmp_path)
    fp = fp_for(writer)
    writer.put(fp, b"payload")
    ro = mk_store(tmp_path, readonly=True)
    assert ro.get(fp_for(ro)) == b"payload"  # loads serve
    assert not ro.put(fp_for(ro, scope="new"), b"nope")
    s = ro.stats()
    assert s["save_skips"] == 1 and s["saves"] == 0
    assert sorted(os.listdir(str(tmp_path))) == [
        entry_address(fp) + ".aot"]  # nothing new on disk


def test_lru_eviction_honors_byte_budget_and_recency(tmp_path):
    entry_overhead = len(encode_entry(
        fp_for(mk_store(tmp_path / "probe"), scope="s0"), b""))
    budget = 2 * (entry_overhead + 100) + 50  # room for two entries
    store = mk_store(tmp_path, max_bytes=budget)
    fps = [fp_for(store, scope=f"s{i}") for i in range(3)]
    store.put(fps[0], b"a" * 100)
    store.put(fps[1], b"b" * 100)
    store.get(fps[0])  # touch s0: s1 becomes the coldest
    store.put(fps[2], b"c" * 100)  # over budget -> evict s1
    s = store.stats()
    assert s["evictions"] == 1 and s["entries"] == 2
    assert store.get(fps[0]) is not None
    assert store.get(fps[2]) is not None
    assert store.get(fps[1]) is None  # evicted
    assert s["total_bytes"] <= budget


# --------------------------------------------------------------------------
# chaos on the wire: corrupt/truncate -> fallback to compile
# --------------------------------------------------------------------------


@pytest.mark.parametrize("site,kind", [
    ("aotcache.load", "snapshot_corrupt"),
    ("aotcache.load", "snapshot_truncate"),
    ("aotcache.save", "snapshot_corrupt"),
    ("aotcache.save", "snapshot_truncate"),
])
def test_fault_injection_falls_back_to_compile(tmp_path, site, kind):
    plan = FaultPlan([FaultRule(site=site, kind=kind, p=1.0,
                                max_fires=1)], seed=0)
    store = AotExecutableCache(AotCacheConfig(dir=str(tmp_path)),
                               fault_plan=plan)
    fp = fp_for(store)
    store.put(fp, b"the program")
    got = store.get(fp)
    assert plan.fired() == {f"{site}/{kind}": 1}
    if site == "aotcache.load":
        # intact on disk, mangled on the read: reject + self-heal
        assert got is None and store.stats()["rejects"] == 1
    else:
        # mangled on the write: the load sees a corrupt entry exactly
        # once, rejects typed, deletes it
        assert got is None and store.stats()["rejects"] == 1
    # the fallback recompiles and re-persists cleanly
    store.put(fp, b"the program")
    assert store.get(fp) == b"the program"


# --------------------------------------------------------------------------
# activation hook
# --------------------------------------------------------------------------


def test_activation_is_scoped_and_nests(tmp_path):
    store = mk_store(tmp_path)
    assert active_aot_scope() is None
    with aot_activation(store, "outer"):
        assert active_aot_scope() == (store, "outer")
        with aot_activation(store, "inner"):
            assert active_aot_scope() == (store, "inner")
        assert active_aot_scope() == (store, "outer")
    assert active_aot_scope() is None


def test_runtime_fingerprint_shape():
    fp = runtime_fingerprint()
    assert set(fp) == {"jax", "jaxlib", "backend"}
    assert all(isinstance(v, str) and v for v in fp.values())


# --------------------------------------------------------------------------
# warm-from-store replica start on fakes (the scale-up latency lever)
# --------------------------------------------------------------------------


def _replica(name, factory, store_dir):
    cfg = ServeConfig(warmup_buckets=((64, 64, 2),), default_steps=2,
                      aot_cache=AotCacheConfig(dir=store_dir))
    return Replica(name, factory, cfg)


def test_replica_warm_start_skips_the_build_delay(tmp_path):
    d = str(tmp_path)
    cold_fac = FakeExecutorFactory(build_delay_s=0.15)
    r0 = _replica("r0", cold_fac, d).start()
    try:
        cold = r0.last_warmup_s
        assert cold >= 0.15 and cold_fac.aot_warmed == 0
        assert r0.server.aot_store.stats()["saves"] >= 1
    finally:
        r0.stop()
    warm_fac = FakeExecutorFactory(build_delay_s=0.15)
    r1 = _replica("r1", warm_fac, d).start()
    try:
        warm = r1.last_warmup_s
        assert warm_fac.aot_warmed == 1  # the persisted entry was used
        assert warm < cold / 3, (
            f"warm start {warm:.3f}s not ≥3x faster than cold {cold:.3f}s"
        )
        aot = r1.server.cache.stats()["aot"]
        assert aot["hits"] >= 1 and aot["rejects"] == 0
        # the server's metrics plane exposes the store
        rendered = r1.server.registry.to_prometheus()
        assert "aot_cache_hits" in rendered
        assert "replica_warmup_s" in rendered
    finally:
        r1.stop()


def test_replica_warm_start_survives_corrupt_store(tmp_path):
    """Chaos between generations: every persisted entry corrupted on
    disk -> the next replica rejects them all (typed, counted), compiles
    fresh, and still serves."""
    d = str(tmp_path)
    r0 = _replica("r0", FakeExecutorFactory(build_delay_s=0.0), d).start()
    r0.stop()
    for name in os.listdir(d):
        path = os.path.join(d, name)
        raw = bytearray(open(path, "rb").read())
        raw[len(raw) // 2] ^= 0xFF
        open(path, "wb").write(bytes(raw))
    fac = FakeExecutorFactory(build_delay_s=0.0)
    r1 = _replica("r1", fac, d).start()
    try:
        assert fac.aot_warmed == 0
        st = r1.server.aot_store.stats()
        assert st["rejects"] >= 1
        out = r1.submit("p", height=64, width=64,
                        num_inference_steps=2).result(timeout=30)
        assert out is not None
    finally:
        r1.stop()


# --------------------------------------------------------------------------
# real tiny config: cache-warm == cold-compile, bit-identical
# --------------------------------------------------------------------------


def test_real_runner_cache_warm_is_bit_identical(tmp_path):
    """The acceptance gate: a denoise through executables deserialized
    from the store is byte-equal to the cold-compiled run that populated
    it — same config, same seeds, fresh runner."""
    import jax

    from distrifuser_tpu import DistriConfig
    from distrifuser_tpu.models.unet import init_unet_params, tiny_config
    from distrifuser_tpu.parallel.runner import DenoiseRunner
    from distrifuser_tpu.schedulers import get_scheduler
    store = mk_store(tmp_path)

    def run():
        cfg = DistriConfig(devices=jax.devices()[:1], height=64, width=64,
                           warmup_steps=1, mode="full_sync")
        ucfg = tiny_config()
        params = init_unet_params(jax.random.PRNGKey(0), ucfg)
        runner = DenoiseRunner(cfg, ucfg, params, get_scheduler("ddim"))
        k = jax.random.PRNGKey(7)
        lat = jax.random.normal(k, (1, 8, 8, 4))
        enc = jax.random.normal(jax.random.fold_in(k, 1),
                                (2, 1, 7, ucfg.cross_attention_dim))
        with aot_activation(store, "bitident"):
            return np.asarray(
                runner.generate(lat, enc, num_inference_steps=3))

    cold = run()
    s0 = store.stats()
    assert s0["saves"] >= 1 and s0["hits"] == 0
    warm = run()
    s1 = store.stats()
    assert s1["hits"] >= 1, "second run did not load from the store"
    assert s1["deserialize_seconds"] > 0.0
    np.testing.assert_array_equal(cold, warm)
