"""Request-scoped tracing: spans, events, and Perfetto-loadable export.

DistriFusion's value proposition is latency — the async stale exchange is
*hidden under compute* — yet until this module the repo could only infer
where a request's time went from aggregate histograms.  `Tracer` records
the full life of every request through the serve layer (enqueue, queue
wait, coalescing into a micro-batch, executor cache hit/miss/build, retry
attempts, breaker/ladder events, per-stage execution, completion) as
spans and instant events on named tracks, and `StepTimeline` records the
per-denoise-step view inside one generation (wall time per step, tagged
warmup/full/shallow, plus live comm-byte counters reconciled against the
closed-form `pipelines.comm_plan`).  `span` (further down) is the one
primitive every layer boundary of the request path enters - serve ->
executor -> pipeline stages, names prefixed ``distri.`` - writing to the
profiler's trace (the device ops' own clock), to the `Tracer` when there
is one, and to the stage clocks every `ServeResult` carries
(docs/OBSERVABILITY.md has the table of names).

Design constraints, in order:

* **Deterministic** — the clock is injectable (the PR-3 pattern: policy
  math testable without sleeping), every id comes from tracer-local
  counters (never the process-global request id), and `export()` orders
  events by (timestamp, sequence) with stable JSON serialization — same
  injected clock + same call sequence ⇒ byte-identical export, which the
  trace tests pin.
* **Bounded** — completed records land in a ring (``capacity``); a
  service that has traced a million requests still answers "what
  happened *lately*" in O(capacity) memory, with the drop count
  reported, never silent (`RingLog` convention).
* **A stated budget, not "zero when off"** — the `Tracer` exists only
  when ``ObservabilityConfig.trace`` is on (the serve layer holds
  ``tracer = None`` otherwise and guards its call sites), but `span`
  below is entered on every request: a fixed number of entries per
  dispatch — never per denoise step of a fused loop, never inside jitted
  code, never per op.  On the whole-batch path that is <= 16 entries and
  <= 10 clock reads a request (14 and 5 today, plus the two clock reads
  `_dispatch` always made); an entry costs ~0.5 us with no profiler
  session and ~1 us under one (this sandbox's CPU), < 0.001% of the
  shortest image the benchmark serves.  PERF.md section 6 (PR 24) has the
  chip's measurement of both sides.

Export is the Chrome/Perfetto trace-event JSON format
(``{"traceEvents": [...]}``, "X"/"B"/"i"/"s"/"f" phases): load the file
at https://ui.perfetto.dev or chrome://tracing.  Tracks are logical
(``req/<trace>``, ``scheduler``, ``cache``, ``stage/denoise``, ...), not
OS threads — each maps to a synthetic tid with a thread_name metadata
record, so the UI shows one swimlane per logical actor.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterable, List, Optional

from jax.profiler import TraceAnnotation

from . import sync

# One synthetic process for the whole service; tracks are "threads".
_PID = 1


def _us(t: float) -> int:
    """Seconds (clock domain) -> integer microseconds (trace domain).
    Integer so serialization is exact and exports byte-stable."""
    return int(round(t * 1e6))


@dataclasses.dataclass
class RequestTrace:
    """The per-request handle the serve layer stashes on `Request.trace`:
    the tracer-local trace id, the request's track name, and the span ids
    the lifecycle hooks close later.  Tracer-local ids (NOT the process-
    global request_id) keep exports deterministic across runs."""

    trace_id: int
    track: str
    root: int
    queue_span: Optional[int] = None
    flow_id: Optional[int] = None
    done: bool = False


class Tracer:
    """Bounded, thread-safe span/event recorder (module docstring).

    ``begin``/``end`` bracket open spans (cross-thread: begin on the
    submit thread, end on the scheduler thread); ``complete`` records a
    span whose start/end times are already known; ``event`` records an
    instant.  ``trace`` groups records belonging to one request;
    ``track`` picks the swimlane.  All timestamps come from the injected
    ``clock`` unless passed explicitly (same domain).
    """

    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 capacity: int = 8192):
        assert capacity >= 1, capacity
        self.clock = clock
        self.capacity = capacity
        self._lock = sync.Lock()
        self._records: deque = deque()
        self._open: Dict[int, Dict[str, Any]] = {}
        self._next_trace = 0
        self._next_span = 0
        self._next_seq = 0
        self._next_flow = 0
        self.dropped = 0
        self._t0 = clock()  # export origin: traces start near ts=0

    # -- id allocation ------------------------------------------------------

    def new_trace(self) -> int:
        with self._lock:
            self._next_trace += 1
            return self._next_trace

    def new_flow(self) -> int:
        with self._lock:
            self._next_flow += 1
            return self._next_flow

    # -- recording ----------------------------------------------------------

    def _push(self, rec: Dict[str, Any]) -> None:
        """Append one finished record to the ring (caller holds no lock)."""
        with self._lock:
            rec["seq"] = self._next_seq
            self._next_seq += 1
            if len(self._records) >= self.capacity:
                self._records.popleft()
                self.dropped += 1
            self._records.append(rec)

    def begin(self, name: str, *, track: str, trace: Optional[int] = None,
              parent: Optional[int] = None, args: Optional[dict] = None,
              t: Optional[float] = None) -> int:
        """Open a span; returns its id for `end`.  ``parent`` is another
        span id, recorded in args for structural assertions (the UI nests
        by track + time containment)."""
        with self._lock:
            self._next_span += 1
            sid = self._next_span
            self._open[sid] = {
                "name": name, "track": track, "trace": trace,
                "parent": parent, "t0": self.clock() if t is None else t,
                "args": dict(args or {}),
            }
        return sid

    def end(self, span_id: Optional[int], args: Optional[dict] = None,
            t: Optional[float] = None) -> None:
        """Close a span opened by `begin` (tolerates None/unknown ids —
        a raced double-close must never take down the scheduler)."""
        if span_id is None:
            return
        with self._lock:
            sp = self._open.pop(span_id, None)
        if sp is None:
            return
        t1 = self.clock() if t is None else t
        a = sp["args"]
        if args:
            a.update(args)
        self._emit_span(sp["name"], sp["track"], sp["trace"], sp["parent"],
                        span_id, sp["t0"], t1, a)

    def complete(self, name: str, t0: float, t1: float, *, track: str,
                 trace: Optional[int] = None, parent: Optional[int] = None,
                 args: Optional[dict] = None) -> int:
        """Record a span whose start/end are already measured (e.g. the
        executor invocation window the dispatch path timed anyway)."""
        with self._lock:
            self._next_span += 1
            sid = self._next_span
        self._emit_span(name, track, trace, parent, sid, t0, t1,
                        dict(args or {}))
        return sid

    def _emit_span(self, name, track, trace, parent, sid, t0, t1, args):
        a = dict(args)
        if trace is not None:
            a["trace"] = trace
        if parent is not None:
            a["parent"] = parent
        a["span"] = sid
        self._push({
            "ph": "X", "name": name, "track": track,
            "ts": _us(t0 - self._t0), "dur": max(0, _us(t1 - t0)),
            "args": a,
        })

    def event(self, name: str, *, track: str, trace: Optional[int] = None,
              args: Optional[dict] = None, t: Optional[float] = None) -> None:
        """Instant event on a track."""
        a = dict(args or {})
        if trace is not None:
            a["trace"] = trace
        self._push({
            "ph": "i", "name": name, "track": track,
            "ts": _us((self.clock() if t is None else t) - self._t0),
            "s": "t", "args": a,
        })

    def flow(self, flow_id: int, phase: str, *, track: str,
             t: Optional[float] = None, name: str = "link") -> None:
        """One end of a flow arrow (``phase`` "s" = start, "f" = finish):
        the serve layer draws batch-span -> member-request links with
        these.  Timestamps must fall inside an enclosing slice on the
        same track for the UI to anchor the arrow."""
        assert phase in ("s", "f"), phase
        rec: Dict[str, Any] = {
            "ph": phase, "name": name, "track": track, "id": flow_id,
            "ts": _us((self.clock() if t is None else t) - self._t0),
        }
        if phase == "f":
            rec["bp"] = "e"
        self._push(rec)

    # -- export -------------------------------------------------------------

    def records(self) -> List[Dict[str, Any]]:
        """Finished records, oldest first (copies — safe to mutate)."""
        with self._lock:
            return [dict(r) for r in self._records]

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "records": len(self._records),
                "dropped": self.dropped,
                "open_spans": len(self._open),
                "capacity": self.capacity,
                "traces": self._next_trace,
            }

    def trace_events(self) -> List[Dict[str, Any]]:
        """The Chrome trace-event list: metadata (track names) first, then
        every record ordered by (ts, seq) with tracks mapped to synthetic
        tids by sorted name — deterministic regardless of which thread
        registered a track first."""
        with self._lock:
            records = [dict(r) for r in self._records]
            open_spans = [
                (sid, dict(sp)) for sid, sp in sorted(self._open.items())
            ]
        # un-ended spans surface as "B" (begin-only) records so a trace
        # snapshotted mid-request still shows the in-flight work
        for sid, sp in open_spans:
            a = dict(sp["args"])
            if sp["trace"] is not None:
                a["trace"] = sp["trace"]
            if sp["parent"] is not None:
                a["parent"] = sp["parent"]
            a["span"] = sid
            records.append({
                "ph": "B", "name": sp["name"], "track": sp["track"],
                "ts": _us(sp["t0"] - self._t0), "args": a,
                "seq": 10**9 + sid,  # after every finished record at its ts
            })
        tracks = sorted({r["track"] for r in records})
        tids = {name: i + 1 for i, name in enumerate(tracks)}
        events: List[Dict[str, Any]] = [
            {"ph": "M", "name": "process_name", "pid": _PID, "tid": 0,
             "args": {"name": "distrifuser-serve"}},
        ]
        for name in tracks:
            events.append({
                "ph": "M", "name": "thread_name", "pid": _PID,
                "tid": tids[name], "args": {"name": name},
            })
        for r in sorted(records, key=lambda r: (r["ts"], r["seq"])):
            e = {k: v for k, v in r.items() if k not in ("track", "seq")}
            e["pid"] = _PID
            e["tid"] = tids[r["track"]]
            events.append(e)
        return events

    def export(self, path: Optional[str] = None) -> Dict[str, Any]:
        """The Perfetto-loadable payload; with ``path``, also written to
        disk with stable formatting (sorted keys, no whitespace churn) so
        deterministic runs produce byte-identical files."""
        payload = {"traceEvents": self.trace_events(),
                   "displayTimeUnit": "ms"}
        if path is not None:
            with open(path, "w") as f:
                json.dump(payload, f, sort_keys=True,
                          separators=(",", ":"))
                f.write("\n")
        return payload


# --------------------------------------------------------------------------
# The span primitive: one call site, three sinks
# --------------------------------------------------------------------------
#
# `span` is what every layer boundary of the request path enters (serve ->
# executor -> pipeline stages; names prefixed ``distri.``).  One entry
# writes to
#
# * the profiler: a `jax.profiler.TraceAnnotation`, live only while a
#   profiler session is (the C++ TraceMe builds its name lazily and returns
#   at once otherwise), so "tracing on" is "someone started the profiler" and
#   the span lands in the same ``.xplane.pb``, on the same clock, as the
#   device ops;
# * the `Tracer`, when one is given or the thread's `Scope` carries one
#   (``observability.trace``): the same name through `Tracer.complete`;
# * the dispatch's stage clocks (`Scope.stage_s` -> `ServeResult.stage_s`),
#   when the span names a ``stage`` the scope keeps.  Always on: the stall
#   they exist to catch never falls in the one request a profiler saw.
#
# The clock is read only for the last two, so a span with neither costs one
# TraceAnnotation and nothing else.

_ambient = threading.local()


class Scope:
    """What the serve plane knows about the dispatch a thread is running,
    made ambient for the layers below it: the server's injectable clock,
    the stage clocks this kind of server keeps (fixed keys, seconds), the
    `Tracer` with its track and the args its records carry when tracing is
    on (tracer-local trace ids: the export stays free of process-global
    ids), and the args every profiler span of the dispatch shares
    (``request_id``, ``batch``).  Thread-local: the server enters it on
    the thread that calls the executor."""

    __slots__ = ("clock", "stage_s", "tracer", "track", "tracer_args",
                 "args", "_outer")

    def __init__(self, clock: Callable[[], float], stages: Iterable[str], *,
                 tracer: Optional[Tracer] = None, track: str = "executor",
                 tracer_args: Optional[dict] = None, **args):
        self.clock = clock
        self.stage_s: Dict[str, float] = dict.fromkeys(stages, 0.0)
        self.tracer = tracer
        self.track = track
        self.tracer_args = tracer_args or {}
        self.args = args

    def __enter__(self) -> "Scope":
        self._outer = getattr(_ambient, "scope", None)
        _ambient.scope = self
        return self

    def __exit__(self, *exc) -> None:
        _ambient.scope = self._outer


class span:
    """``with span("distri.layer.what", **args):`` - see the section
    comment.  ``tracer`` / ``track`` / ``trace`` default to the thread's
    `Scope`; ``stage`` names the stage clock the span's seconds add to
    (ignored where the scope does not keep that key)."""

    __slots__ = ("name", "stage", "args", "_scope", "_tracer", "_track",
                 "_trace", "_tracer_args", "_clock", "_ann", "_t0")

    def __init__(self, name: str, *, tracer: Optional[Tracer] = None,
                 track: Optional[str] = None, trace: Optional[int] = None,
                 stage: Optional[str] = None, **args):
        sc = getattr(_ambient, "scope", None)
        self._tracer_args = args
        if sc is not None:
            if tracer is None and sc.tracer is not None:
                tracer = sc.tracer
                self._tracer_args = {**sc.tracer_args, **args}
            if sc.args:
                args = {**sc.args, **args}
            track = track or sc.track
        self.name, self.stage, self.args = name, stage, args
        self._tracer, self._track, self._trace = tracer, track, trace
        # the scope, if this span adds to one of the clocks it keeps
        self._scope = sc if sc is not None and stage in sc.stage_s else None
        self._clock = (self._scope.clock if self._scope is not None
                       else tracer.clock if tracer is not None else None)

    def set(self, **args) -> None:
        """Args known only once the work is done (a cache ``hit``)."""
        self.args.update(args)
        if self._tracer_args is not self.args:
            self._tracer_args.update(args)
        self._ann.set_metadata(**args)

    def __enter__(self) -> "span":
        self._open()
        return self

    def __exit__(self, *exc) -> None:
        self._close(*exc)

    def _open(self, t: Optional[float] = None) -> None:
        """``t``: a clock reading already taken (`phases.next`)."""
        self._ann = TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        if self._clock is not None:
            self._t0 = self._clock() if t is None else t

    def _close(self, *exc, t: Optional[float] = None) -> None:
        if self._clock is not None:
            t0, t1 = self._t0, self._clock() if t is None else t
            if self._scope is not None:
                self._scope.stage_s[self.stage] += t1 - t0
            if self._tracer is not None:
                self._tracer.complete(
                    self.name, t0, t1, track=self._track or "spans",
                    trace=self._trace, args=self._tracer_args)
        self._ann.__exit__(*(exc or (None, None, None)))


class phases:
    """Consecutive sibling spans that hand over to one another across
    function boundaries: ``with phases(first) as ph:`` opens the first,
    ``ph.next(name)`` closes the open one and opens the next at the same
    clock reading, and leaving the block closes the last.  Re-entrant per
    thread - a block entered while another is open joins it and closes
    nothing - so the executor, the pipeline's ``__call__`` and its decode
    tail share ONE dispatch -> wait_device -> to_host -> post sequence
    whichever of them is the outermost caller."""

    __slots__ = ("_first", "_cur", "_owner")

    def __init__(self, name: str, **kw):
        self._first = (name, kw)
        self._cur: Optional[span] = None

    def __enter__(self) -> "phases":
        owner = getattr(_ambient, "phases", None)
        self._owner = owner if owner is not None else self
        if owner is None:
            _ambient.phases = self
            name, kw = self._first
            self._cur = span(name, **kw).__enter__()
        return self._owner

    def next(self, name: str, **kw) -> None:
        old, new = self._cur, span(name, **kw)
        clock = old._clock or new._clock
        t = clock() if clock is not None else None
        old._close(t=t)
        new._open(t)
        self._cur = new

    def __exit__(self, *exc) -> None:
        if self._owner is self:
            _ambient.phases = None
            self._cur.__exit__(*exc)


def span_ids(requests) -> Dict[str, Any]:
    """The args every span of one dispatch carries, on whichever thread:
    ``request_id`` (the first request's) and, where the dispatch serves
    several, ``batch`` (all of them)."""
    ids: Dict[str, Any] = {"request_id": requests[0].request_id}
    if len(requests) > 1:
        ids["batch"] = ",".join(str(r.request_id) for r in requests)
    return ids


def phased(name: str, **kw):
    """Decorator form of `phases` for a function whose whole body is the
    block (the pipelines' ``__call__``)."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*a, **k):
            with phases(name, **kw):
                return fn(*a, **k)
        return inner
    return wrap


# --------------------------------------------------------------------------
# Per-step denoise timeline
# --------------------------------------------------------------------------

# StepTimeline phase tags -> pipelines.comm_plan / stepcache phase keys
PHASE_TO_COMM = {"warmup": "sync", "full": "stale", "shallow": "shallow"}


class StepTimeline:
    """Wall-time and live comm-byte accounting per denoise step.

    Attach to a pipeline (``pipeline.step_timeline = StepTimeline()``) and
    every generation records one run: per-step wall timings tagged
    ``warmup``/``full``/``shallow`` (the step-cache cadence phases), plus
    a live comm-byte counter that adds each *executed* step's wire bytes
    from the runner's per-phase byte model as the loop advances.  Because
    the closed-form ``pipelines.comm_plan`` multiplies the same per-step
    bytes by `stepcache.phase_step_counts`, the two agree exactly iff the
    loop really executed the phase sequence the plan predicts — the byte
    model becomes a checked invariant instead of documentation
    (``tests/test_observability.py`` pins it).

    Driven by the per-step callback, so a timeline-carrying generation
    runs the callback dispatch path (the host stepwise loop, or the fused
    loop's ``io_callback`` variant where the jaxlib supports it) — per-
    step host visibility is exactly what that path exists for.  Single
    writer (the loop thread); ``snapshot()`` is read-anywhere.

    ``tracer``/``track`` optionally mirror every step into a `Tracer` as
    ``step/<phase>`` spans, putting the denoise micro-timeline on the
    same Perfetto timeline as the request spans around it.
    """

    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 tracer: Optional[Tracer] = None, track: str = "denoise"):
        self.clock = clock
        self.tracer = tracer
        self.track = track
        self._lock = sync.Lock()
        self.runs: List[Dict[str, Any]] = []
        self._cur: Optional[Dict[str, Any]] = None
        self._phase_of: Optional[Callable[[int], str]] = None
        self._bytes_per_step: Dict[str, int] = {}
        self._t_last = 0.0

    def begin_run(self, num_steps: int,
                  phase_of: Callable[[int], str],
                  bytes_per_step: Optional[Dict[str, int]] = None,
                  meta: Optional[dict] = None) -> None:
        """Start recording one generation: ``phase_of(i)`` tags each step
        (the pipeline passes the exact cadence arithmetic the loop runs);
        ``bytes_per_step`` is comm_plan's per-phase wire-byte model keyed
        ``sync``/``stale``/``shallow`` (None = bytes untracked, e.g. a
        runner without a byte model)."""
        with self._lock:
            self._cur = {
                "num_steps": int(num_steps),
                "steps": [],
                "phase_steps": {"warmup": 0, "full": 0, "shallow": 0},
                "phase_wall_s": {"warmup": 0.0, "full": 0.0, "shallow": 0.0},
                "comm_bytes": 0,
                "comm_bytes_tracked": bytes_per_step is not None,
                "meta": dict(meta or {}),
            }
            self._phase_of = phase_of
            self._bytes_per_step = dict(bytes_per_step or {})
            self._t_last = self.clock()

    def on_step(self, i: int) -> None:
        """Record step ``i`` finishing now (the per-step callback)."""
        t = self.clock()
        with self._lock:
            cur = self._cur
            if cur is None:
                return
            phase = self._phase_of(int(i))
            dt = t - self._t_last
            cur["steps"].append(
                {"step": int(i), "phase": phase, "wall_s": dt}
            )
            cur["phase_steps"][phase] += 1
            cur["phase_wall_s"][phase] += dt
            cur["comm_bytes"] += int(
                self._bytes_per_step.get(PHASE_TO_COMM[phase], 0)
            )
            t_prev, self._t_last = self._t_last, t
        if self.tracer is not None:
            self.tracer.complete(f"step/{phase}", t_prev, t,
                                 track=self.track, args={"step": int(i)})

    def end_run(self) -> None:
        with self._lock:
            if self._cur is not None:
                self.runs.append(self._cur)
                self._cur = None

    # -- reads --------------------------------------------------------------

    @property
    def comm_bytes(self) -> int:
        """Live wire bytes across every completed run (per device,
        gathered-buffer convention — the same unit as comm_plan)."""
        with self._lock:
            return sum(r["comm_bytes"] for r in self.runs)

    def snapshot(self) -> Dict[str, Any]:
        """JSON-friendly aggregate: per-phase step counts and wall time
        across runs, live comm bytes, and the per-run records."""
        with self._lock:
            runs = [dict(r) for r in self.runs]
        agg_steps = {"warmup": 0, "full": 0, "shallow": 0}
        agg_wall = {"warmup": 0.0, "full": 0.0, "shallow": 0.0}
        for r in runs:
            for ph in agg_steps:
                agg_steps[ph] += r["phase_steps"][ph]
                agg_wall[ph] += r["phase_wall_s"][ph]
        return {
            "runs": len(runs),
            "phase_steps": agg_steps,
            "phase_wall_s": agg_wall,
            "comm_bytes": sum(r["comm_bytes"] for r in runs),
            "comm_bytes_tracked": all(
                r["comm_bytes_tracked"] for r in runs) if runs else False,
            "per_run": runs,
        }
