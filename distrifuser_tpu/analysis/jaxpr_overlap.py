"""Trace-time comm/compute overlap classification (jaxpr, not HLO).

`utils/overlap.py` proves the displaced-patch overlap contract — every
stale-exchange collective's value reaches ONLY the loop carry, through
data movement (plus, under comm_compress, the cheap elementwise dequant
chain) — from **compiled HLO**.  That check is exact but expensive: the
fake-8-device CPU compile of even the tiny config takes minutes, so the
HLO tests are `slow`-marked and never run on the 2-core tier-1 runner.

This module proves the same structural property one stage earlier, from
the **jaxpr**: tracing is seconds where compiling is minutes, because no
XLA optimization runs.  The classification is necessarily a conservative
mirror of the HLO one — XLA only ever *moves collectives earlier* (its
latency-hiding scheduler) and never introduces a same-iteration consumer
that the jaxpr didn't have — so:

* a collective classified **deferred** here (carry-only through data
  movement) is guaranteed overlappable in the compiled program;
* **deferred_compute** = carry-only but through `_EW_PRIMS` elementwise
  arithmetic — where the compressed-refresh dequantize chains land
  (parallel/compress.py), matching `LoopReport.deferred_compute`;
* **inline** = some transitive consumer does real work this iteration
  (attention matmuls on sync KV, the CFG combine) — these serialize.

`lax.fori_loop` with static bounds and `lax.scan` both trace to `scan`
primitives; unrolled `while` bodies are analyzed the same way with every
output treated as carry.  Call-like primitives (pjit, shard_map, remat,
custom_jvp/vjp) are inlined into one flat dataflow graph; nested control
flow stays opaque (a collective consumed by a nested loop counts inline
— conservative) and is analyzed as its own loop.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

#: collective primitives whose placement the overlap contract governs
COLLECTIVE_PRIMS = frozenset({
    "ppermute", "all_gather", "psum", "all_to_all", "psum_scatter",
    "reduce_scatter", "pmin", "pmax", "pgather",
})
#: pure data movement: consuming a value through these does not compute
#: with it (jaxpr analog of overlap._DM_OPS)
_DM_PRIMS = frozenset({
    "convert_element_type", "bitcast_convert_type", "reshape", "transpose",
    "concatenate", "pad", "slice", "dynamic_slice", "dynamic_update_slice",
    "broadcast_in_dim", "squeeze", "expand_dims", "rev", "copy", "gather",
    "split", "stop_gradient", "device_put", "optimization_barrier",
})
#: cheap elementwise arithmetic a carry-only chain may traverse and still
#: count latency-hidden (the dequant convert/scale-multiply/residual-add
#: chains) — jaxpr analog of overlap._EW_OPS.  Deliberately excludes
#: dot_general/conv/reduce_* and every collective: traversing those means
#: real compute (or another exchange) consumed the value this iteration.
_EW_PRIMS = frozenset({
    "add", "sub", "mul", "div", "neg", "abs", "sign", "max", "min",
    "clamp", "select_n", "eq", "ne", "ge", "gt", "le", "lt",
    "round", "floor", "ceil", "and", "or", "not", "xor", "rem",
    "integer_pow",
})
#: call-like primitives inlined transparently into the dataflow graph
_CALL_PRIMS = frozenset({
    "pjit", "closed_call", "core_call", "xla_call", "remat", "checkpoint",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "shard_map", "custom_partitioning",
})
_LOOP_PRIMS = frozenset({"scan", "while"})


def _jaxpr_types():
    from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

    return Jaxpr, ClosedJaxpr, Literal


def _sub_jaxprs(eqn) -> List[Any]:
    Jaxpr, ClosedJaxpr, _ = _jaxpr_types()
    out = []
    for v in eqn.params.values():
        if isinstance(v, (Jaxpr, ClosedJaxpr)):
            out.append(v)
        elif isinstance(v, (tuple, list)):
            out.extend(x for x in v if isinstance(x, (Jaxpr, ClosedJaxpr)))
    return out


def _open(jx):
    _, ClosedJaxpr, _ = _jaxpr_types()
    return jx.jaxpr if isinstance(jx, ClosedJaxpr) else jx


@dataclasses.dataclass
class JaxprLoopReport:
    """Per-loop classification, same buckets as overlap.LoopReport."""

    kind: str  # "scan" | "while"
    deferred: Dict[str, str]  # instruction label -> primitive name
    inline: Dict[str, str]
    deferred_compute: Dict[str, str]

    @property
    def n_deferred(self) -> int:
        return len(self.deferred)

    @property
    def n_inline(self) -> int:
        return len(self.inline)

    @property
    def n_deferred_compute(self) -> int:
        return len(self.deferred_compute)

    @property
    def n_collectives(self) -> int:
        return self.n_deferred + self.n_inline + self.n_deferred_compute


class _FlatGraph:
    """The loop body flattened across call-like primitives into one SSA
    graph: nodes are integers, `alias` maps each scope's Vars onto them
    (Vars are unique objects per jaxpr, so ``id()`` keys are sound for
    the lifetime of the traced object we hold a reference to)."""

    def __init__(self):
        self.eqns: List[Tuple[str, List[int], List[int]]] = []
        self._alias: Dict[int, int] = {}
        self._n = 0
        self._keepalive: List[Any] = []  # pin Vars so id() stays unique

    def node_for(self, var) -> Optional[int]:
        _, _, Literal = _jaxpr_types()
        if isinstance(var, Literal):
            return None
        key = id(var)
        if key not in self._alias:
            self._alias[key] = self._n
            self._keepalive.append(var)
            self._n += 1
        return self._alias[key]

    def alias(self, var, node: int) -> None:
        self._alias[id(var)] = node
        self._keepalive.append(var)

    def add(self, jx) -> None:
        jaxpr = _open(jx)
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            subs = _sub_jaxprs(eqn)
            if name in _CALL_PRIMS and len(subs) == 1:
                sub = _open(subs[0])
                # call invars align with the tail of eqn.invars (leading
                # entries, when present, are closed-over consts)
                n_in = len(sub.invars)
                evs = (eqn.invars[-n_in:] if len(eqn.invars) >= n_in
                       else eqn.invars)
                for sv, ev in zip(sub.invars, evs):
                    node = self.node_for(ev)
                    if node is not None:
                        self.alias(sv, node)
                self.add(subs[0])
                for ov, sv in zip(eqn.outvars, sub.outvars):
                    node = self.node_for(sv)
                    if node is not None:
                        self.alias(ov, node)
                continue
            ins = [n for n in (self.node_for(v) for v in eqn.invars)
                   if n is not None]
            outs = [self.node_for(v) for v in eqn.outvars]
            self.eqns.append((name, ins, [o for o in outs if o is not None]))


def analyze_loop_body(body, num_carry: Optional[int],
                      kind: str) -> Optional[JaxprLoopReport]:
    """Classify every collective in one loop body.  ``num_carry=None``
    treats every outvar as carry (while loops)."""
    jaxpr = _open(body)
    graph = _FlatGraph()
    graph.add(body)
    # only the NON-carry outvars (stacked per-iteration ys) matter to
    # classification: reaching one means same-iteration consumption
    n_carry = len(jaxpr.outvars) if num_carry is None else num_carry
    ys_nodes = set()
    for i, ov in enumerate(jaxpr.outvars[n_carry:]):
        node = graph.node_for(ov)
        if node is not None:
            ys_nodes.add(node)

    consumers: Dict[int, List[int]] = {}
    for idx, (_, ins, _outs) in enumerate(graph.eqns):
        for n in ins:
            consumers.setdefault(n, []).append(idx)

    def classify(out_nodes: Sequence[int]) -> str:
        seen = set()
        frontier = list(out_nodes)
        ew_used = False
        while frontier:
            node = frontier.pop()
            if node in seen:
                continue
            seen.add(node)
            if node in ys_nodes:
                # stacked per-iteration output: consumed outside the
                # carry contract — same-iteration work in disguise
                return "inline"
            for cdx in consumers.get(node, []):
                cname, _cins, couts = graph.eqns[cdx]
                if cname in _DM_PRIMS:
                    frontier.extend(couts)
                elif cname in _EW_PRIMS:
                    ew_used = True
                    frontier.extend(couts)
                else:
                    return "inline"
        return "deferred_compute" if ew_used else "deferred"

    deferred: Dict[str, str] = {}
    inline: Dict[str, str] = {}
    deferred_compute: Dict[str, str] = {}
    count = 0
    for name, _ins, outs in graph.eqns:
        if name not in COLLECTIVE_PRIMS:
            continue
        label = f"{name}#{count}"
        count += 1
        bucket = classify(outs)
        {"deferred": deferred, "inline": inline,
         "deferred_compute": deferred_compute}[bucket][label] = name
    if count == 0:
        return None
    return JaxprLoopReport(kind=kind, deferred=deferred, inline=inline,
                           deferred_compute=deferred_compute)


def find_loops(closed_jaxpr) -> List[Any]:
    """Every scan/while eqn anywhere in the jaxpr tree (call-likes and
    loop bodies are both descended, so nested loops are found too)."""
    loops = []

    def walk(jx):
        jaxpr = _open(jx)
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in _LOOP_PRIMS:
                loops.append(eqn)
            for sub in _sub_jaxprs(eqn):
                walk(sub)

    walk(closed_jaxpr)
    return loops


def analyze_jaxpr_collectives(closed_jaxpr) -> List[JaxprLoopReport]:
    """Classify every loop-body collective of a traced program —
    the jaxpr counterpart of `overlap.analyze_loop_collectives`."""
    reports = []
    for eqn in find_loops(closed_jaxpr):
        if eqn.primitive.name == "scan":
            report = analyze_loop_body(eqn.params["jaxpr"],
                                       eqn.params["num_carry"], "scan")
        else:
            report = analyze_loop_body(eqn.params["body_jaxpr"], None,
                                       "while")
        if report is not None:
            reports.append(report)
    return reports


def format_reports(reports: Sequence[JaxprLoopReport]) -> str:
    from collections import Counter

    out = []
    for r in reports:
        out.append(f"{r.kind} body: {r.n_deferred} deferred / "
                   f"{r.n_deferred_compute} deferred-compute / "
                   f"{r.n_inline} inline")
        for label, bucket in (("deferred", r.deferred),
                              ("deferred-compute", r.deferred_compute),
                              ("inline", r.inline)):
            if bucket:
                out.append(f"  {label}: {dict(Counter(bucket.values()))}")
    return "\n".join(out) if out else "no loop collectives found"
