"""HLO-level comm/compute overlap verification.

The displaced-patch design claims its stale-refresh collectives are *latency
hidden*: each stale step's halo exchanges and KV all-gathers produce values
consumed only by the NEXT scan iteration, so XLA's latency-hiding scheduler
is free to run them concurrently with the current step's convs/matmuls.  The
reference gets the same effect imperatively with async NCCL all-gathers
waited one step later (/root/reference/distrifuser/utils.py:170-190,
modules/pp/attn.py:123-143); here the property is structural — and therefore
checkable from the compiled HLO, not assumed.

`analyze_loop_collectives(hlo_text)` parses every while-loop body in a
compiled module and classifies each collective (all-gather / collective-
permute / all-reduce / reduce-scatter, sync or async-start form) as

* **deferred** — its value reaches ONLY the loop carry (the ROOT tuple),
  travelling exclusively through data-movement ops (copies, reshapes,
  concatenates, layout fusions that contain no arithmetic).  Nothing in the
  current iteration computes with it; the scheduler may overlap it with all
  remaining compute of the iteration.
* **inline** — some transitive consumer does arithmetic this iteration
  (attention matmuls on sync-phase KV gathers, scheduler math on the final
  output gather).  These serialize against compute.

The steady-state (stale scan) body of a patch-parallel program must have
inline collectives ONLY for the per-step full-output gather + CFG combine
(the reference's output gather is synchronous too, distri_sdxl_unet_pp.py:
162-169); every refresh collective must classify deferred.
tests/test_overlap.py asserts this, with the sync path as negative control.
`python -m distrifuser_tpu.utils.overlap <file.hlo>` prints the report for
any dumped module (e.g. from a real-chip run with XLA dump flags).

Each collective also carries what its `op_name` metadata says of it — the
exchange it belongs to (the innermost of the `EXCHANGE_KINDS` named scopes)
and the loop phase (`phase_sync` / `phase_stale`, parallel/runner.py) — and
its wire bytes per device by `comm_plan`'s gathered-buffer convention (an
all-gather: its result; a permute: the rows sent).  `exchange_report` sums
them per phase and kind: the compiled program's own count of what
`comm_volume_report` models.  TPU text is read as well as CPU text: a
`-start` / `-done` pair counts once (at the start), an `async-start`
wrapper or a fusion that holds a collective counts as that collective, and
the start / overlapped-compute / done fusions the TPU compiler splits one
all-gather into (one `channel_id`) count once, classified at the done.  The
TPU compiler's own form of `concatenate` (each part padded with -inf, then
`maximum`) is data movement, recognised by what the fusion holds and not by
its name.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List

_COLLECTIVE_OPS = frozenset({
    "all-gather", "collective-permute", "all-reduce", "reduce-scatter",
    "all-to-all", "collective-broadcast", "ragged-all-to-all",
    "all-gather-start", "collective-permute-start", "all-reduce-start",
})
# instructions that may hold a collective in the computation they call: the
# generic async wrapper, and the fusions a TPU compiler puts one into
_WRAPPER_OPS = frozenset({"async-start", "fusion", "call"})
# pure data movement: consuming a value through these does not compute with it
_DM_OPS = frozenset({
    "copy", "bitcast", "bitcast-convert", "convert", "reshape", "transpose",
    "concatenate", "pad", "slice", "dynamic-slice", "dynamic-update-slice",
    "broadcast", "reverse", "tuple", "get-tuple-element",
    "all-gather-done", "collective-permute-done", "all-reduce-done",
    "optimization-barrier",
    # the other halves of async ops (a TPU compiler's own copies and slices,
    # the generic wrapper's update and done)
    "async-update", "async-done", "copy-start", "copy-done", "slice-start",
    "slice-done",
})
# ops that may appear in a data-movement fusion without consuming anything
_DM_SOURCES = frozenset({"parameter", "constant", "iota"})
# cheap elementwise arithmetic a *carry-only* chain may traverse and still
# count as latency-hidden (``elementwise_carry=True``): the compressed
# refresh path's dequantize (convert x scale-multiply [+ residual add],
# parallel/compress.py) lands here — the scheduler can sink these past all
# of the iteration's real compute exactly like a copy, since nothing this
# iteration reads their result.  Deliberately excludes dot/convolution/
# reduce and every collective opcode: traversing those means real compute
# (or another exchange) consumed the value this iteration.
_EW_OPS = frozenset({
    "add", "subtract", "multiply", "divide", "negate", "abs", "sign",
    "maximum", "minimum", "clamp", "compare", "select",
    "round-nearest-even", "round-nearest-afz",
})

# the exchanges' named scopes (ops/conv.py, ops/attention.py,
# ops/normalization.py, parallel/context.py, collectives.py, guidance.py)
EXCHANGE_KINDS = ("halo", "stale_kv", "gn_stats", "stale_gather",
                  "out_gather", "cfg_combine")
PHASES = ("phase_sync", "phase_stale")
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
             "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
             "f64": 8, "c64": 8, "c128": 16}
_LEAF = re.compile(r"\b([a-z]+\d+[a-z0-9]*|pred)\[([\d,]*)\]")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CHANNEL = re.compile(r"channel_id=(\d+)")

_ATTR_REF = re.compile(r"(?:condition|body)=%[\w.\-]+")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_TOKEN = re.compile(r"%([\w.\-]+)")
_DEF = re.compile(r"^(ROOT )?%?([\w.\-]+) = ")
_BLOCK_HEAD = re.compile(r"^(?:ENTRY )?%?([\w.\-]+)\s*\(.*\)\s*->\s*.*\{$")
_OPCODE = re.compile(r"([\w\-]+)\(")  # at the start of what follows the result


def parse_computations(hlo_text: str) -> Dict[str, List[str]]:
    """Split printed HLO into {computation name: [instruction lines]}."""
    blocks: Dict[str, List[str]] = {}
    cur, acc = None, []
    for line in hlo_text.splitlines():
        m = _BLOCK_HEAD.match(line)
        if m:
            cur, acc = m.group(1), []
            continue
        if line.startswith("}"):
            if cur is not None:
                blocks[cur] = acc
            cur = None
            continue
        if cur is not None:
            acc.append(line.strip())
    return blocks


def _split_result(line: str):
    """(result type, the rest from the opcode on) of an instruction line.
    The result type is skipped whole, because a TPU layout holds parentheses
    of its own (`bf16[8,128]{1,0:T(8,128)(2,1)S(1)}`) and an async start
    returns a tuple."""
    rhs = line.split(" = ", 1)[1]
    if not rhs.startswith("("):
        result, _, rest = rhs.partition(" ")
        return result, rest
    depth = 0
    for i, ch in enumerate(rhs):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return rhs[: i + 1], rhs[i + 1 :].lstrip()
    return rhs, ""


def _opcode(line: str) -> str:
    m = _OPCODE.match(_split_result(line)[1])
    return m.group(1) if m else "?"


def _tuple_elements(result: str) -> List[str]:
    """Top-level elements of a tuple result type (itself, if no tuple)."""
    if not result.startswith("("):
        return [result]
    out, depth, start = [], 0, 1
    for i, ch in enumerate(result):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if (ch == "," and depth == 1) or (ch == ")" and depth == 0):
            out.append(result[start:i].strip())
            start = i + 1
    return out


def _nbytes(result: str) -> int:
    """Logical bytes of every array in a result type (layout padding is the
    compiler's, not the wire's)."""
    total = 0
    for dtype, dims in _LEAF.findall(result):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _ITEMSIZE.get(dtype, 1)
    return total


def _wire_bytes(opcode: str, result: str) -> int:
    """Wire bytes per device of one collective instruction, gathered-buffer
    convention.  An async start returns (operands, results, context...):
    the results are what moves."""
    if opcode.endswith("-start"):
        parts = _tuple_elements(result)
        return _nbytes(parts[1] if len(parts) > 1 else parts[0])
    return _nbytes(result)


def _scoped(op_name: str, names) -> str:
    """The innermost path component of ``op_name`` that is one of ``names``."""
    for part in reversed(op_name.split("/")):
        if part in names:
            return part
    return ""


# what may stand between the parts of a padded concatenate and its result
_CONCAT_GLUE = frozenset({"convert", "bitcast", "copy", "reshape"})


def _is_padded_concatenate(lines: List[str]) -> bool:
    """A TPU compiler writes `concatenate` as a fusion that pads each part
    with -inf out to the result's shape and takes the `maximum` of the
    padded parts: arithmetic by opcode, data movement by what it does.  True
    for a computation that is exactly that and nothing else: parameters, one
    kind of constant (-inf), `pad` with that constant, `maximum` over pads
    (through converts of the float-normalisation pass) and other such
    maxima.  A multiply, an add, a pad with another value or a maximum
    against a live value makes it arithmetic like any other."""
    defs = {}
    for ln in lines:
        m = _DEF.match(ln)
        if m:
            defs[m.group(2)] = (_opcode(ln), ln)

    def operands(ln):
        return [t for t in _TOKEN.findall(ln.split(" = ", 1)[1]) if t in defs]

    def source(name):  # the op a value comes from, glue looked through
        op, ln = defs[name]
        while op in _CONCAT_GLUE and operands(ln):
            op, ln = defs[operands(ln)[0]]
        return op

    n_max = 0
    for op, ln in defs.values():
        if op == "parameter" or op in _CONCAT_GLUE:
            continue
        if op == "constant":
            if "constant(-inf)" not in ln:
                return False
        elif op == "pad":
            ops = operands(ln)
            if len(ops) != 2 or defs[ops[1]][0] != "constant":
                return False
        elif op == "maximum":
            n_max += 1
            if any(source(o) not in ("pad", "maximum") for o in operands(ln)):
                return False
        else:
            return False
    return n_max > 0


@dataclasses.dataclass(frozen=True)
class Collective:
    """One collective of a loop body, as its instruction states it."""

    opcode: str  # all-gather, collective-permute-start, ...
    kind: str  # one of EXCHANGE_KINDS, "" where op_name names none
    phase: str  # one of PHASES, "" where op_name names none
    nbytes: int  # wire bytes per device
    inline: bool  # computed with in this iteration (not carry-only)


@dataclasses.dataclass
class LoopReport:
    body: str
    deferred: Dict[str, str]  # instruction name -> opcode
    inline: Dict[str, str]
    # collectives whose value reaches only the carry but through cheap
    # elementwise arithmetic (the compressed-refresh dequantize chain);
    # populated only under ``elementwise_carry=True`` — the default
    # classification keeps them in ``inline``, preserving the strict
    # pure-data-movement invariant of the uncompressed program.
    deferred_compute: Dict[str, str] = dataclasses.field(default_factory=dict)
    # instruction name -> what its own line says of it (kind, phase, bytes)
    collectives: Dict[str, Collective] = dataclasses.field(default_factory=dict)

    @property
    def n_deferred(self) -> int:
        return len(self.deferred)

    @property
    def n_inline(self) -> int:
        return len(self.inline)

    @property
    def n_deferred_compute(self) -> int:
        return len(self.deferred_compute)


class _Analyzer:
    def __init__(self, hlo_text: str):
        self.blocks = parse_computations(hlo_text)
        self._dm_comp: Dict[str, bool] = {}
        self._ew_comp: Dict[str, bool] = {}
        self._inner: Dict[str, str | None] = {}

    def _computation_is_dm(self, name: str) -> bool:
        """True if a (fusion) computation contains no arithmetic at all."""
        return self._computation_ok(name, self._dm_comp, _DM_OPS)

    def _computation_is_ew(self, name: str) -> bool:
        """True if a (fusion) computation contains at most data movement
        and the cheap elementwise arithmetic of ``_EW_OPS``."""
        return self._computation_ok(name, self._ew_comp, _DM_OPS | _EW_OPS)

    def _computation_ok(self, name: str, cache: Dict[str, bool],
                        allowed) -> bool:
        if name in cache:
            return cache[name]
        cache[name] = False  # cycle guard
        ok = True
        for ln in self.blocks.get(name, ()):
            if " = " not in ln:
                continue
            op = _opcode(ln)
            if op in allowed or op in _DM_SOURCES:
                continue
            if op == "fusion":
                m = _CALLS.search(ln)
                if m and self._computation_ok(m.group(1), cache, allowed):
                    continue
            ok = False
            break
        cache[name] = ok
        return ok

    def analyze_body(self, body: str,
                     elementwise_carry: bool = False) -> LoopReport | None:
        lines = self.blocks.get(body, [])
        defs: Dict[str, str] = {}
        root = None
        for ln in lines:
            m = _DEF.match(ln)
            if m:
                defs[m.group(2)] = ln
                if m.group(1):
                    root = m.group(2)
        if root is None:
            return None
        consumers: Dict[str, List[str]] = {n: [] for n in defs}
        for n, ln in defs.items():
            rhs = _ATTR_REF.sub("", ln.split(" = ", 1)[1])
            rhs = _CALLS.sub("", rhs)
            for op in _TOKEN.findall(rhs):
                if op in defs and op != n:
                    consumers[op].append(n)

        def passthrough_consumer(name: str, allow_ew: bool) -> bool:
            """Consuming instruction is pure data movement (or, with
            ``allow_ew``, cheap elementwise arithmetic)?"""
            ln = defs[name]
            op = _opcode(ln)
            if op in _DM_OPS or (allow_ew and op in _EW_OPS):
                return True
            if op == "fusion":
                m = _CALLS.search(ln)
                if not m:
                    return False
                if allow_ew:
                    return self._computation_is_ew(m.group(1))
                return (self._computation_is_dm(m.group(1))
                        or _is_padded_concatenate(
                            self.blocks.get(m.group(1), ())))
            return False

        def deferred(coll: str, allow_ew: bool = False) -> bool:
            """Value reaches only the carry, via passthrough ops only."""
            seen, frontier = set(), [coll]
            while frontier:
                n = frontier.pop()
                if n in seen:
                    continue
                seen.add(n)
                if not consumers[n] and n != root:
                    continue  # dead value: harmless
                for u in consumers[n]:
                    if u == root and _opcode(defs[u]) == "tuple":
                        continue
                    if passthrough_consumer(u, allow_ew):
                        frontier.append(u)
                    else:
                        return False
            return True

        # name -> the line that states the collective (its own, or the one
        # inside the computation a wrapper calls)
        found: Dict[str, str] = {}
        wrapped: Dict[tuple, str] = {}  # (opcode, channel) -> last wrapper
        for n, ln in defs.items():
            op = _opcode(ln)
            if op in _COLLECTIVE_OPS:
                found[n] = ln
            elif op in _WRAPPER_OPS:
                inner = self._inner_collective(ln)
                if inner is None:
                    continue
                ch = _CHANNEL.search(inner)
                key = (_opcode(inner), ch.group(1) if ch else n)
                # one all-gather split into start / overlapped compute /
                # done fusions: the last of them holds the value
                found.pop(wrapped.get(key), None)
                wrapped[key] = n
                found[n] = inner
        d, dc, i, info = {}, {}, {}, {}
        for n, stated in found.items():
            op = _opcode(stated)
            if deferred(n):
                d[n] = op
            elif elementwise_carry and deferred(n, allow_ew=True):
                dc[n] = op
            else:
                i[n] = op
            m = _OP_NAME.search(defs[n]) or _OP_NAME.search(stated)
            op_name = m.group(1) if m else ""
            info[n] = Collective(
                opcode=op, kind=_scoped(op_name, EXCHANGE_KINDS),
                phase=_scoped(op_name, PHASES),
                nbytes=_wire_bytes(op, _split_result(stated)[0]),
                inline=n in i)
        if info:
            return LoopReport(body, d, i, dc, info)
        return None

    def _inner_collective(self, line: str) -> str | None:
        """The collective instruction inside the computation ``line`` calls
        (through nested wrappers), or None; found once a computation."""
        m = _CALLS.search(line)
        if not m:
            return None
        comp = m.group(1)
        if comp not in self._inner:
            self._inner[comp] = None  # cycle guard
            for ln in self.blocks.get(comp, ()):
                if " = " not in ln:
                    continue
                op = _opcode(ln)
                inner = ln if op in _COLLECTIVE_OPS else (
                    self._inner_collective(ln) if op in _WRAPPER_OPS else None)
                if inner is not None:
                    self._inner[comp] = inner
                    break
        return self._inner[comp]


def analyze_loop_collectives(
    hlo_text: str, elementwise_carry: bool = False
) -> List[LoopReport]:
    """Classify every while-body collective as deferred (carry-only through
    data movement) or inline (computed with this iteration).

    ``elementwise_carry=True`` adds a third bucket, ``deferred_compute``:
    carry-only through data movement PLUS cheap elementwise arithmetic —
    where the compressed refresh path's quantize/dequantize converts land
    (comm_compress, parallel/compress.py).  Off by default so the strict
    invariant of uncompressed programs (pure data movement to the carry)
    keeps being checked as-is."""
    analyzer = _Analyzer(hlo_text)
    bodies = set(re.findall(r"body=%?([\w.\-]+)", hlo_text))
    reports = []
    for body in sorted(bodies):
        r = analyzer.analyze_body(body, elementwise_carry)
        if r is not None:
            reports.append(r)
    return reports


def exchange_report(hlo_text: str) -> Dict[str, Dict[str, Dict[str, int]]]:
    """{phase: {kind: {"collectives", "inline", "bytes"}}} over every
    while-body collective of a compiled program: how many instructions each
    exchange became, how many of them this iteration computes with, and the
    wire bytes per device of one pass through the body.  A collective whose
    `op_name` names no phase or no exchange is filed under "".  Inline is the
    strict reading (pure data movement to the carry): through cheap
    elementwise arithmetic alone the CFG combine, too, reaches only the
    carry - by way of the scheduler's update - and it is no hidden exchange."""
    out: Dict[str, Dict[str, Dict[str, int]]] = {}
    for r in analyze_loop_collectives(hlo_text):
        for c in r.collectives.values():
            row = out.setdefault(c.phase, {}).setdefault(
                c.kind, {"collectives": 0, "inline": 0, "bytes": 0})
            row["collectives"] += 1
            row["inline"] += c.inline
            row["bytes"] += c.nbytes
    return out


# -- where a loop's carried state lives while a row is written into it --------

_MEMORY_SPACE = re.compile(r"S\((\d+)\)")


def _arrays(result: str):
    """[(dims, lane-padded bytes, memory space)] of the arrays a result type
    names, in order.  A TPU layout pads the last axis to whole 128-lane
    tiles (a 64-wide bf16 row moves as 128) and names its memory space
    `S(n)`: none is the HBM, 1 the VMEM."""
    out = []
    for m in _LEAF.finditer(result):
        dims = [int(d) for d in filter(None, m.group(2).split(","))]
        if not dims:
            continue
        layout = result[m.end():].split("}", 1)[0] \
            if result[m.end():m.end() + 1] == "{" else ""
        space = _MEMORY_SPACE.search(layout)
        n = math.prod(dims[:-1]) * -(-dims[-1] // 128) * 128
        out.append((tuple(dims), n * _ITEMSIZE.get(m.group(1), 1),
                    int(space.group(1)) if space else 0))
    return out


def cache_staging(hlo_text: str, rows: int = 0, shapes=()) -> Dict[str, int]:
    """What a compiled TPU program's while bodies do with the state they
    carry, beside what the program asked for: {"staged_bytes": the bytes one
    pass moves BETWEEN memory spaces in asynchronous copies (`copy-start`,
    `slice-start`) of which source or destination is cache-shaped, lane
    padding counted; "staged_copies": how many; "writes": the cache-shaped
    `dynamic-update-slice` (a row's write, alone or as a fusion's root);
    "writes_outside_hbm": those whose result lies in another memory space}.

    Cache-shaped: an array of two or more axes whose leading axes multiply
    to ``rows`` (a cache [rows, C], or the same rows as blocks of a leading
    axis), or one whose dims are in ``shapes``.  A program whose memory-space
    assignment moves a whole cache into VMEM for one row's write and back
    reads its size twice a layer here; one that writes the row in place, 0."""
    shapes = {tuple(s) for s in shapes}

    def cached(dims):
        return dims in shapes or bool(rows) and len(dims) >= 2 and (
            math.prod(dims[:-1]) == rows)

    comps = parse_computations(hlo_text)
    out = {"staged_bytes": 0, "staged_copies": 0, "writes": 0,
           "writes_outside_hbm": 0}
    for body in sorted(set(re.findall(r"body=%?([\w.\-]+)", hlo_text))):
        for ln in comps.get(body, ()):
            if " = " not in ln:
                continue
            result, rest = _split_result(ln)
            op = _opcode(ln)
            if op in ("copy-start", "slice-start"):
                # (destination, source, context) / ((source), destination,
                # context): what moves is the destination's size
                ends = _arrays(result)[:2]
                if len(ends) == 2 and ends[0][2] != ends[1][2] and any(
                        cached(dims) for dims, _, _ in ends):
                    out["staged_bytes"] += ends[op == "slice-start"][1]
                    out["staged_copies"] += 1
                continue
            called = _CALLS.search(rest) if op == "fusion" else None
            if called:
                root = [l for l in comps.get(called.group(1), ())
                        if l.startswith("ROOT ")]
                op = _opcode(root[0]) if root else op
            if op == "dynamic-update-slice":
                for dims, _, space in _arrays(result)[:1]:
                    if cached(dims):
                        out["writes"] += 1
                        out["writes_outside_hbm"] += space != 0
    return out


def format_report(reports: List[LoopReport]) -> str:
    from collections import Counter

    out = []
    for r in reports:
        out.append(
            f"loop body {r.body}: {r.n_deferred} deferred"
            + (f" / {r.n_deferred_compute} deferred-compute"
               if r.deferred_compute else "")
            + f" / {r.n_inline} inline"
        )
        if r.deferred:
            out.append(f"  deferred (overlappable): {dict(Counter(r.deferred.values()))}")
        if r.deferred_compute:
            out.append(
                "  deferred-compute (dequant chains): "
                f"{dict(Counter(r.deferred_compute.values()))}"
            )
        if r.inline:
            out.append(f"  inline (serializing):    {dict(Counter(r.inline.values()))}")
        rows: Dict[tuple, List[int]] = {}
        for c in r.collectives.values():
            row = rows.setdefault(
                (c.phase or "-", c.kind or "-", c.opcode), [0, 0, 0])
            row[0] += 1
            row[1] += c.inline
            row[2] += c.nbytes
        out.append(f"  {'phase':<12}{'kind':<13}{'opcode':<25}"
                   f"{'n':>5}{'inline':>8}{'bytes':>14}")
        for (phase, kind, opcode), (n, inline, nbytes) in sorted(rows.items()):
            out.append(f"  {phase:<12}{kind:<13}{opcode:<25}{n:>5}"
                       f"{inline:>8}{nbytes:>14}")
    return "\n".join(out) if out else "no while-loop collectives found"


if __name__ == "__main__":
    import sys

    with open(sys.argv[1]) as f:
        print(format_report(analyze_loop_collectives(f.read())))
