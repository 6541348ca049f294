"""Persistent AOT executable cache: compile once per fleet, not per replica.

`ExecutorCache` (serve/cache.py) already makes XLA compilation a
*startup* cost instead of a *request* cost — but only within one
process.  Every fresh replica still pays the full compile campaign for
every warmup bucket, which is exactly the latency that blocks elastic
scale-up (ROADMAP item 2: "a persistent AOT compiled-program cache so a
fresh replica warms from serialized executables in seconds").  This
module is that store: compiled programs serialized through
`jax.experimental.serialize_executable` into a **content-addressed
on-disk** entry a later replica
— same binary versions, same mesh, same compile identity — loads in
milliseconds instead of recompiling.

Keying.  An entry's fingerprint is the full provenance of the program:

* ``scope`` — the compile identity, `ExecKey.short()` (every field that
  changes the XLA program: model, scheduler, bucket, steps, cfg, mesh
  plan, cadence, compression, quantization, exec mode, parallelism)
  plus the runner-level program tag and abstract-value signature;
* ``jax`` / ``jaxlib`` / ``backend`` — `utils.aot.runtime_fingerprint`:
  serialized executables do not survive version skew, so the versions
  are part of the address AND re-checked from the header at load;
* ``mesh_shape`` — the device mesh layout the program was lowered for;
* ``layout`` — donation/layout fingerprint (donate_argnums et al.).

The fingerprint hashes into the file name (content addressing: a
different fingerprint can never alias an entry) and travels verbatim in
the envelope header, so a load proves — not assumes — the entry matches.

Envelope layout mirrors serve/migration.py (same checksum-first rule)::

    MAGIC(4) | u32 header_len | header json | payload | sha256(32)

Every validation failure — truncation, bad magic, version skew,
checksum mismatch, malformed header, fingerprint drift, an executable
payload the runtime refuses to deserialize — raises
`AotCacheRejectedError` (typed, retryable); `get`/`load_executable`
catch it, count a reject, DELETE the bad entry, and return None so the
caller falls back to a fresh compile.  A bad entry costs one compile;
it never loads a wrong program.

Fault injection: `FaultPlan.mutate` sites ``"aotcache.save"`` (bytes on
their way to disk) and ``"aotcache.load"`` (bytes read back) take the
``snapshot_truncate``/``snapshot_corrupt`` kinds, proving the
fallback-to-compile path end to end; the plan is taken from the
constructor or the process-global chaos hook.

Thread model: file I/O runs outside ``_lock``; the index and every
counter mutate only under it.  Multiple processes may share one store
directory (that is the point — a scale-up replica warms from an earlier
replica's compiles); writes are atomic (`os.replace` of a temp file),
and a racing eviction at worst costs the loser a recompile.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import re
import struct
import time
from typing import Any, Dict, List, Optional, Sequence

from ..utils import sync
from ..utils.aot import runtime_fingerprint
from ..utils.chaos import active_fault_plan
from .errors import AotCacheRejectedError

MAGIC = b"DFAC"  # DistriFuser Aot Cache
FORMAT_VERSION = 1

_HEADER_LEN = struct.Struct(">I")
_DIGEST_BYTES = 32  # sha256
_SUFFIX = ".aot"


def entry_address(fingerprint: Dict[str, str]) -> str:
    """Content address of one fingerprint: a sanitized scope prefix for
    operator greppability + the sha256 of the canonical fingerprint
    JSON.  Distinct fingerprints can never alias one file."""
    blob = json.dumps({k: str(v) for k, v in fingerprint.items()},
                      sort_keys=True)
    digest = hashlib.sha256(blob.encode("utf-8")).hexdigest()[:32]
    scope = re.sub(r"[^A-Za-z0-9._-]+", "_",
                   str(fingerprint.get("scope", "")))[:48]
    return f"{scope}-{digest}" if scope else digest


def encode_entry(fingerprint: Dict[str, str], payload: bytes) -> bytes:
    """Wrap one serialized executable in the self-describing envelope."""
    meta = {
        "format": FORMAT_VERSION,
        "fingerprint": {k: str(v) for k, v in fingerprint.items()},
        "payload_len": len(payload),
    }
    header = json.dumps(meta, sort_keys=True).encode("utf-8")
    body = bytearray()
    body += MAGIC
    body += _HEADER_LEN.pack(len(header))
    body += header
    body += payload
    body += hashlib.sha256(bytes(body)).digest()
    return bytes(body)


def decode_entry(data: bytes, expect: Dict[str, str]) -> bytes:
    """Validate one envelope against the fingerprint the LOADER computed;
    every failure is typed.  Order matters: the checksum is verified
    FIRST (over everything before the digest), so a flipped bit anywhere
    rejects as corruption before any field is trusted; only then are
    magic, version, header shape, and the fingerprint interpreted."""
    if not isinstance(data, (bytes, bytearray, memoryview)):
        raise AotCacheRejectedError(
            f"aot cache entry must be bytes, got {type(data).__name__}"
        )
    data = bytes(data)
    floor = len(MAGIC) + _HEADER_LEN.size + _DIGEST_BYTES
    if len(data) < floor:
        raise AotCacheRejectedError(
            f"aot cache entry truncated: {len(data)} bytes < the "
            f"{floor}-byte envelope floor"
        )
    payload, digest = data[:-_DIGEST_BYTES], data[-_DIGEST_BYTES:]
    if hashlib.sha256(payload).digest() != digest:
        raise AotCacheRejectedError(
            "aot cache entry checksum mismatch: bytes corrupt or "
            "truncated on disk"
        )
    if payload[:len(MAGIC)] != MAGIC:
        raise AotCacheRejectedError(
            f"aot cache entry bad magic {payload[:len(MAGIC)]!r} "
            f"(want {MAGIC!r})"
        )
    (header_len,) = _HEADER_LEN.unpack_from(payload, len(MAGIC))
    header_off = len(MAGIC) + _HEADER_LEN.size
    if header_off + header_len > len(payload):
        raise AotCacheRejectedError(
            "aot cache entry truncated: header extends past the payload"
        )
    try:
        meta = json.loads(payload[header_off:header_off + header_len])
    except ValueError as exc:
        raise AotCacheRejectedError(
            f"aot cache entry header is not valid JSON: {exc}"
        ) from exc
    version = meta.get("format")
    if version != FORMAT_VERSION:
        raise AotCacheRejectedError(
            f"aot cache entry format version {version!r} is not the "
            f"supported {FORMAT_VERSION} — refusing cross-version load"
        )
    for field in ("fingerprint", "payload_len"):
        if field not in meta:
            raise AotCacheRejectedError(
                f"aot cache entry header missing field {field!r}"
            )
    body = payload[header_off + header_len:]
    if int(meta["payload_len"]) != len(body):
        raise AotCacheRejectedError(
            f"aot cache entry payload length {len(body)} does not match "
            f"the header's {meta['payload_len']}"
        )
    want = {k: str(v) for k, v in expect.items()}
    have = meta["fingerprint"]
    if have != want:
        diff = sorted(
            k for k in set(want) | set(have) if want.get(k) != have.get(k)
        )
        raise AotCacheRejectedError(
            "aot cache entry fingerprint mismatch (version skew or "
            f"foreign entry; differs in {', '.join(diff)}): entry "
            f"{have}, this runtime {want}"
        )
    return body


def _serialize_compiled(compiled) -> bytes:
    """Compiled jax executable -> opaque bytes: the serializer returns
    (payload, in_tree, out_tree) and all three are needed to reload, so
    the byte form is a pickle of the triple.  Raises whatever the runtime
    raises on unserializable programs (callbacks, host-pinned buffers)."""
    from jax.experimental import serialize_executable

    return pickle.dumps(serialize_executable.serialize(compiled), protocol=4)


def _deserialize_compiled(data: bytes, devices: Sequence[Any]):
    """Inverse of `_serialize_compiled`, loaded onto ``devices``.  Only
    ever fed bytes whose envelope checksum and fingerprint this process
    verified (`get`), i.e. bytes this program wrote."""
    from jax.experimental import serialize_executable

    payload, in_tree, out_tree = pickle.loads(data)
    return serialize_executable.deserialize_and_load(
        payload, in_tree, out_tree, execution_devices=list(devices))


class AotExecutableCache:
    """The on-disk store: bytes API (`get`/`put`) used by fakes and
    tests, executable API (`load_executable`/`save_executable`) used by
    the runner.

    ``config`` is `utils.config.AotCacheConfig`: ``dir`` (None disables
    the store entirely), ``max_bytes`` (LRU eviction bound — least
    recently LOADED entries evict first), ``readonly`` (CI mode: loads
    serve, saves count `save_skips` and write nothing).
    """

    def __init__(self, config: Any, *, fault_plan: Optional[Any] = None):
        self.config = config
        self.dir: Optional[str] = config.dir
        self.readonly = bool(config.readonly)
        self.max_bytes = int(config.max_bytes)
        self.fault_plan = fault_plan
        self._runtime = dict(runtime_fingerprint())
        self._lock = sync.Lock()
        # address -> [path, size, last_used_tick]; recency is load/save
        # order within this process, seeded from file mtimes at scan
        self._index: Dict[str, List[Any]] = {}
        self._tick = 0
        self.hits = 0
        self.misses = 0
        self.rejects = 0
        self.saves = 0
        self.save_skips = 0
        self.evictions = 0
        self.unserializable = 0
        self.bytes_loaded = 0
        self.bytes_saved = 0
        self.deserialize_seconds = 0.0
        self.serialize_seconds = 0.0
        if self.dir:
            if not self.readonly:
                os.makedirs(self.dir, exist_ok=True)
            with self._lock:
                self._scan_locked()

    # -- internals -----------------------------------------------------------

    def _scan_locked(self) -> None:
        """Adopt pre-existing entries (a prior replica's compiles — the
        whole point of persistence), oldest mtime = coldest."""
        try:
            names = sorted(os.listdir(self.dir))
        except OSError:
            return
        found = []
        for name in names:
            if not name.endswith(_SUFFIX):
                continue
            path = os.path.join(self.dir, name)
            try:
                st = os.stat(path)
            except OSError:
                continue
            found.append((st.st_mtime, name[:-len(_SUFFIX)], path,
                          int(st.st_size)))
        for mtime, address, path, size in sorted(found):
            self._tick += 1
            self._index[address] = [path, size, self._tick]

    def _path(self, address: str) -> str:
        return os.path.join(self.dir, address + _SUFFIX)

    def _plan(self) -> Optional[Any]:
        return self.fault_plan if self.fault_plan is not None \
            else active_fault_plan()

    def _evict_over_budget_locked(self) -> List[str]:
        """Least-recently-loaded entries leave first until the byte
        budget holds; returns the file paths to unlink (outside the
        lock).  An entry larger than the whole budget evicts itself —
        the bound is honest even for pathological payloads."""
        doomed: List[str] = []
        while self._index and sum(
                e[1] for e in self._index.values()) > self.max_bytes:
            address = min(self._index, key=lambda a: self._index[a][2])
            path, _, _ = self._index.pop(address)
            self.evictions += 1
            doomed.append(path)
        return doomed

    # -- the bytes API -------------------------------------------------------

    def fingerprint(self, scope: str, *, mesh_shape: str = "",
                    layout: str = "") -> Dict[str, str]:
        """The full provenance key for one program under THIS runtime."""
        fp = dict(self._runtime)
        fp["scope"] = str(scope)
        fp["mesh_shape"] = str(mesh_shape)
        fp["layout"] = str(layout)
        return fp

    def load(self, fingerprint: Dict[str, str]) -> Optional[bytes]:
        """Validated payload bytes for a fingerprint; None on miss.
        Every validation failure raises `AotCacheRejectedError` — use
        `get` for the counted, self-healing fallback wrapper."""
        if not self.dir:
            return None
        address = entry_address(fingerprint)
        with self._lock:
            entry = self._index.get(address)
        if entry is None:
            with self._lock:
                self.misses += 1
            return None
        try:
            with open(entry[0], "rb") as fh:
                data = fh.read()
        except OSError:
            # another process evicted the file under us: a miss, not a
            # rejection — nothing was corrupt, the entry is just gone
            with self._lock:
                self.misses += 1
                self._index.pop(address, None)
            return None
        plan = self._plan()
        if plan is not None:
            data = plan.mutate("aotcache.load", data,
                               key=fingerprint.get("scope"))
        payload = decode_entry(data, fingerprint)
        with self._lock:
            self.hits += 1
            self.bytes_loaded += len(payload)
            self._tick += 1
            live = self._index.get(address)
            if live is not None:
                live[2] = self._tick
        return payload

    def get(self, fingerprint: Dict[str, str]) -> Optional[bytes]:
        """`load` with the fallback contract: a rejected entry is
        counted, deleted, and reported as None — the caller compiles
        fresh, and the next replica finds a clean slot."""
        try:
            return self.load(fingerprint)
        except AotCacheRejectedError:
            with self._lock:
                self.rejects += 1
            self.discard(fingerprint)
            return None

    def put(self, fingerprint: Dict[str, str], payload: bytes) -> bool:
        """Persist one payload under its fingerprint (atomic replace);
        returns whether the entry landed.  Readonly mode counts a skip
        and writes nothing; the LRU byte budget evicts coldest-first
        after the write."""
        if not self.dir:
            return False
        if self.readonly:
            with self._lock:
                self.save_skips += 1
            return False
        data = encode_entry(fingerprint, bytes(payload))
        plan = self._plan()
        if plan is not None:
            data = plan.mutate("aotcache.save", data,
                               key=fingerprint.get("scope"))
        address = entry_address(fingerprint)
        path = self._path(address)
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "wb") as fh:
                fh.write(data)
            os.replace(tmp, path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False
        with self._lock:
            self.saves += 1
            self.bytes_saved += len(data)
            self._tick += 1
            self._index[address] = [path, len(data), self._tick]
            doomed = self._evict_over_budget_locked()
        for victim in doomed:
            try:
                os.unlink(victim)
            except OSError:
                pass
        return True

    def discard(self, fingerprint: Dict[str, str]) -> None:
        """Drop one entry (file + index) — the reject path's self-heal."""
        address = entry_address(fingerprint)
        with self._lock:
            entry = self._index.pop(address, None)
        path = entry[0] if entry is not None \
            else (self._path(address) if self.dir else None)
        if path is not None:
            try:
                os.unlink(path)
            except OSError:
                pass

    # -- the executable API --------------------------------------------------

    def load_executable(self, fingerprint: Dict[str, str],
                        devices: Sequence[Any]) -> Optional[Any]:
        """Deserialize a persisted executable onto ``devices`` (the
        program's own mesh devices — without them the runtime loads onto
        every local device and a one-chip replica's program on a
        four-chip host fails its first dispatch); None on miss or on any
        rejection (counted + entry deleted — the caller's contract is
        always compile-on-None)."""
        data = self.get(fingerprint)
        if data is None:
            return None
        t0 = time.monotonic()
        try:
            try:
                compiled = _deserialize_compiled(data, devices)
            except Exception as exc:
                raise AotCacheRejectedError(
                    f"aot cache entry failed executable deserialization "
                    f"under this runtime: {exc}"
                ) from exc
        except AotCacheRejectedError:
            with self._lock:
                self.rejects += 1
            self.discard(fingerprint)
            return None
        with self._lock:
            self.deserialize_seconds += time.monotonic() - t0
        return compiled

    def save_executable(self, fingerprint: Dict[str, str],
                        compiled: Any) -> bool:
        """Serialize one compiled program into the store.  Programs the
        runtime cannot serialize (host callbacks, exotic buffers) count
        `unserializable` and are simply not cached — never an error."""
        if not self.dir or self.readonly:
            # skip BEFORE paying serialization: readonly exists for CI,
            # where serializing a program nobody will write is pure waste
            return self._count_skip_if_readonly()
        t0 = time.monotonic()
        try:
            payload = _serialize_compiled(compiled)
        except Exception:
            with self._lock:
                self.unserializable += 1
            return False
        with self._lock:
            self.serialize_seconds += time.monotonic() - t0
        return self.put(fingerprint, payload)

    def _count_skip_if_readonly(self) -> bool:
        if self.readonly and self.dir:
            with self._lock:
                self.save_skips += 1
        return False

    # -- observability -------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "dir": self.dir,
                "readonly": self.readonly,
                "entries": len(self._index),
                "total_bytes": sum(e[1] for e in self._index.values()),
                "max_bytes": self.max_bytes,
                "hits": self.hits,
                "misses": self.misses,
                "rejects": self.rejects,
                "saves": self.saves,
                "save_skips": self.save_skips,
                "evictions": self.evictions,
                "unserializable": self.unserializable,
                "bytes_loaded": self.bytes_loaded,
                "bytes_saved": self.bytes_saved,
                "deserialize_seconds": round(self.deserialize_seconds, 6),
                "serialize_seconds": round(self.serialize_seconds, 6),
            }
