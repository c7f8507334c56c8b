"""Query tracing: a zero-dependency span recorder.

Reference parity: the reference engine's query event pipeline + live
web-UI timeline (execution/QueryStats.java stage/task/operator
timelines, webapp timeline.jsx) — reimagined for an engine whose
compiled fragments are opaque fused XLA programs: what the reference
gets from per-operator OperationTimers, we get from spans around the
phases the host CAN see (parse/plan/execute, fragment schedule, task
execution, page pulls, XLA compiles, hedged attempts) plus XLA
cost-analysis / profiler attribution INSIDE programs
(observe/profile.py).

Model: one `Tracer` per query records `Span`s — query -> phase ->
fragment -> task -> attempt — identified by DETERMINISTIC ids (a
process counter, never a random source or the clock, so seeded chaos
runs replay identical id sequences).  Trace context propagates to
cluster workers in the `X-Presto-Trace` header (`trace_id;span_id`);
workers record task spans locally and ship them back on the task
status payload, where the coordinator merges every span carrying this
query's trace id into ONE trace.  A dropped header degrades to a
worker-LOCAL trace (fresh trace id; the coordinator counts the
foreign spans it refused) — never an error.

Export is Chrome trace-event JSON (`chrome_trace`): load the payload
from `/v1/query/{id}/trace` (server/protocol.py) or
`QueryStats.trace_spans` in Perfetto / chrome://tracing.  Lanes: each
process is a `pid` row (coordinator / worker:PORT), each thread a
`tid` row — so hedge monitors, compile-ahead workers, and retried
attempts appear as their own lanes instead of being inferred from
counters.

This module also owns the engine's span CLOCKS (`clock_ns`, `wall_s`):
the test_lint AST rule confines `time.time` / `time.perf_counter*`
to observe/, so every wall measurement that can end up in a span or a
metric routes through here.
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

from presto_tpu.observe.names import SPAN_PREFIX

#: the trace-context propagation header (coordinator -> worker):
#: "trace_id;parent_span_id"
TRACE_HEADER = "X-Presto-Trace"

#: span kinds, outermost to innermost (docs/OBSERVABILITY.md)
KINDS = ("query", "phase", "fragment", "task", "attempt", "compile",
         "span")


# ---------------------------------------------------------------------------
# clocks (the only module allowed to read them — test_lint rule)
# ---------------------------------------------------------------------------


def clock_ns() -> int:
    """Monotonic high-resolution clock for durations."""
    return time.perf_counter_ns()


def wall_s() -> float:
    """Unix wall clock (seconds) for timestamps that leave the process
    (HMAC signing, trace alignment across coordinator/worker)."""
    return time.time()


#: Unix epoch of clock_ns()'s zero, read once: every span stamp is then ONE
#: monotonic read (the same read feeds QueryStats.phase_ns), and stamps of
#: one process cannot step backwards with the wall clock
_EPOCH_OFFSET_NS = time.time_ns() - time.perf_counter_ns()


def epoch_us(ns: Optional[int] = None) -> float:
    """Unix epoch microseconds — the chrome trace `ts` unit — of the
    clock_ns() reading `ns` (default: now).  Coordinator and worker
    spans align on it (same-host resolution is more than enough for
    HTTP-hop-sized spans)."""
    if ns is None:
        ns = time.perf_counter_ns()
    return (ns + _EPOCH_OFFSET_NS) / 1_000.0


def ns_of_wall(wall_s: float) -> int:
    """The clock_ns() reading at Unix time `wall_s` (seconds): where a time
    another clock stamped (JAX's compile events read time.time()) lies on
    the span clock.  epoch_us(ns_of_wall(t)) == t * 1e6."""
    return int(wall_s * 1e9) - _EPOCH_OFFSET_NS


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Span:
    trace_id: str
    span_id: str
    parent_id: str  # "" = root
    name: str
    kind: str = "span"
    start_us: float = 0.0
    end_us: float = 0.0  # 0 = still open
    lane: str = "coordinator"  # process lane (chrome pid)
    tid: str = ""  # thread lane within the process (chrome tid)
    args: Dict[str, object] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> dict:
        d = {"trace_id": self.trace_id, "span_id": self.span_id,
             "parent_id": self.parent_id, "name": self.name,
             "kind": self.kind, "start_us": self.start_us,
             "end_us": self.end_us, "lane": self.lane, "tid": self.tid}
        if self.args:
            d["args"] = {k: v for k, v in self.args.items()
                         if isinstance(v, (str, int, float, bool))
                         or v is None}
        return d

    @property
    def dur_us(self) -> float:
        return max(self.end_us - self.start_us, 0.0)


# deterministic id sources: process-scoped counters, never a clock or a
# random source (seeded chaos runs must replay identical id sequences)
_trace_seq = itertools.count(1)


def _fresh_trace_id() -> str:
    return f"tr-{os.getpid():x}-{next(_trace_seq)}"


class Tracer:
    """Per-query span recorder.  Thread-safe: the span list takes a
    lock; the *nesting stack* is per-thread (each thread that calls
    `span()` nests under its own enclosing span, falling back to the
    tracer's root)."""

    def __init__(self, trace_id: Optional[str] = None,
                 lane: str = "coordinator",
                 root_parent: str = ""):
        self.trace_id = trace_id or _fresh_trace_id()
        self.lane = lane
        #: parent id for this tracer's root spans (the coordinator span
        #: a worker-side tracer hangs its task span under)
        self.root_parent = root_parent
        self.root: Optional[Span] = None
        #: QueryStats.query_id of the query this tracer records
        #: (QueryMonitor sets it); every annotation of the query carries it
        self.query_id: Optional[str] = None
        self.spans: List[Span] = []
        self.dropped = 0  # foreign-trace spans refused by add_spans
        self._lock = threading.Lock()
        self._seq = itertools.count(1)
        self._stacks: Dict[int, List[Span]] = {}  # thread ident -> stack

    # -- ids -----------------------------------------------------------
    def new_id(self) -> str:
        return f"{self.trace_id}.{next(self._seq)}"

    # -- manual begin/end (cross-thread spans) -------------------------
    def begin(self, name: str, kind: str = "span",
              parent: Optional[object] = None,
              at_ns: Optional[int] = None, **args) -> Span:
        if parent is None:
            parent_id = self._thread_parent_id()
        elif isinstance(parent, Span):
            parent_id = parent.span_id
        else:
            parent_id = str(parent)
        sp = Span(trace_id=self.trace_id, span_id=self.new_id(),
                  parent_id=parent_id, name=name, kind=kind,
                  start_us=epoch_us(at_ns), lane=self.lane,
                  tid=threading.current_thread().name, args=dict(args))
        with self._lock:
            self.spans.append(sp)
        return sp

    def end(self, sp: Optional[Span], at_ns: Optional[int] = None,
            **args) -> None:
        if sp is None:
            return
        sp.end_us = epoch_us(at_ns)
        if args:
            sp.args.update(args)

    def _thread_parent_id(self) -> str:
        stack = self._stacks.get(threading.get_ident())
        if stack:
            return stack[-1].span_id
        if self.root is not None:
            return self.root.span_id
        return self.root_parent

    # -- structured nesting --------------------------------------------
    def begin_root(self, name: str, kind: str = "query", **args) -> Span:
        self.root = self.begin(name, kind=kind, parent=self.root_parent,
                               **args)
        return self.root

    @contextmanager
    def span(self, name: str, kind: str = "span", **args):
        sp = self.push(name, kind, None, args)
        try:
            yield sp
        finally:
            self.pop(sp, None)

    def push(self, name: str, kind: str, at_ns: Optional[int],
             args: dict) -> Span:
        """Open a span on this thread's nesting stack."""
        sp = self.begin(name, kind=kind, at_ns=at_ns, **args)
        self._stacks.setdefault(threading.get_ident(), []).append(sp)
        return sp

    def pop(self, sp: Span, at_ns: Optional[int]) -> None:
        """Close the span push() opened (same thread)."""
        stack = self._stacks.get(threading.get_ident())
        if stack and stack[-1] is sp:
            stack.pop()
        self.end(sp, at_ns=at_ns)

    # -- merge / export ------------------------------------------------
    def add_spans(self, span_dicts, require_trace: bool = True) -> int:
        """Merge externally recorded spans (a worker's task spans riding
        its status payload).  Spans carrying a DIFFERENT trace id are
        refused and counted (`dropped`) — a worker that never saw the
        X-Presto-Trace header produced a worker-local trace, which must
        not be grafted into this query's tree under made-up parents."""
        merged = 0
        for d in span_dicts or []:
            try:
                tid = str(d.get("trace_id", ""))
                if require_trace and tid != self.trace_id:
                    self.dropped += 1
                    continue
                sp = Span(trace_id=tid or self.trace_id,
                          span_id=str(d["span_id"]),
                          parent_id=str(d.get("parent_id", "")),
                          name=str(d.get("name", "span")),
                          kind=str(d.get("kind", "span")),
                          start_us=float(d.get("start_us", 0.0)),
                          end_us=float(d.get("end_us", 0.0)),
                          lane=str(d.get("lane", "remote")),
                          tid=str(d.get("tid", "")),
                          args=dict(d.get("args") or {}))
            except (KeyError, TypeError, ValueError):
                self.dropped += 1
                continue
            with self._lock:
                self.spans.append(sp)
            merged += 1
        return merged

    def snapshot(self) -> List[dict]:
        """Spans as JSON-safe dicts (open spans are closed at 'now' so a
        crash mid-span still exports a valid trace)."""
        now = epoch_us()
        with self._lock:
            spans = list(self.spans)
        out = []
        for sp in spans:
            d = sp.to_dict()
            if not d["end_us"]:
                d["end_us"] = now
                d.setdefault("args", {})["unclosed"] = True
            out.append(d)
        return out

    def to_chrome(self) -> dict:
        return chrome_trace(self.snapshot(), self.trace_id)


def chrome_trace(span_dicts: List[dict], trace_id: str = "") -> dict:
    """Span dicts -> Chrome trace-event JSON (loads in Perfetto /
    chrome://tracing).  Each distinct `lane` becomes a pid row, each
    (lane, tid) a named thread row; spans are complete ('X') events."""
    pids: Dict[str, int] = {}
    tids: Dict[tuple, int] = {}
    events: List[dict] = []
    for d in span_dicts:
        lane = d.get("lane") or "coordinator"
        if lane not in pids:
            pids[lane] = len(pids) + 1
            events.append({"ph": "M", "name": "process_name",
                           "pid": pids[lane], "tid": 0,
                           "args": {"name": lane}})
        tkey = (lane, d.get("tid") or "main")
        if tkey not in tids:
            tids[tkey] = len(tids) + 1
            events.append({"ph": "M", "name": "thread_name",
                           "pid": pids[lane], "tid": tids[tkey],
                           "args": {"name": tkey[1]}})
        args = dict(d.get("args") or {})
        args["kind"] = d.get("kind", "span")
        args["span_id"] = d.get("span_id", "")
        if d.get("parent_id"):
            args["parent_id"] = d["parent_id"]
        start = float(d.get("start_us", 0.0))
        events.append({
            "ph": "X", "name": d.get("name", "span"),
            "cat": d.get("kind", "span"),
            "ts": start,
            "dur": max(float(d.get("end_us", start)) - start, 0.0),
            "pid": pids[lane], "tid": tids[tkey], "args": args})
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"traceId": trace_id}}


# ---------------------------------------------------------------------------
# thread-local activation + wire context
# ---------------------------------------------------------------------------

_tls = threading.local()


@contextmanager
def activate(tracer: Optional[Tracer]):
    """Route this thread's span recording to `tracer` (None = no-op).
    Nested activations shadow; the previous tracer is restored."""
    prev = getattr(_tls, "tracer", None)
    _tls.tracer = tracer
    try:
        yield tracer
    finally:
        _tls.tracer = prev


def current() -> Optional[Tracer]:
    return getattr(_tls, "tracer", None)


_UNSET = object()
_annotation = None      # jax.profiler.TraceAnnotation, imported on first use


def _helper_failed() -> None:
    """The span helper's own code raised: never the query's problem.
    Counted in presto_tpu_trace_errors_total (observe/metrics.py)."""
    try:
        from presto_tpu.observe import metrics as M

        M.trace_error()
    except Exception:  # noqa: BLE001 — instrumentation cannot fail a query
        pass


class span:
    """`with span(name):` — THE instrumentation one-liner.  Two sinks, one
    clock read each at entry and exit:

    (a) a `jax.profiler.TraceAnnotation` named SPAN_PREFIX + name, so the
        span lies on the profiler's clock beside the device's operations
        (a fraction of a microsecond when no profile is being taken);
    (b) a Span on the thread's active `Tracer` (or `tracer=`), when there
        is one — what `/v1/query/{id}/trace` serves.

    Same-thread `with` blocks only (annotations nest per thread);
    `Tracer.begin/end` stay for cross-thread spans and annotate nothing.
    Every annotation carries the query's id: `query_id=` where the caller
    has one, else the active tracer's.  `elapsed_ns` holds the duration
    after exit.  An exception raised by this class's own code — not by
    the body — is swallowed and counted: instrumentation cannot fail a
    query."""

    __slots__ = ("name", "kind", "args", "tracer", "elapsed_ns",
                 "_t0", "_ann", "_sp")

    def __init__(self, name: str, kind: str = "span", tracer=_UNSET,
                 **args):
        self.name = name
        self.kind = kind
        self.args = args
        self.tracer = tracer
        self.elapsed_ns = 0
        self._ann = self._sp = None

    def __enter__(self) -> Optional[Span]:
        self._t0 = t0 = time.perf_counter_ns()
        try:
            global _annotation
            if _annotation is None:
                from jax.profiler import TraceAnnotation

                _annotation = TraceAnnotation
            tr = current() if self.tracer is _UNSET else self.tracer
            self.tracer = tr
            ids = {k: str(v) for k, v in self.args.items()
                   if k.endswith("_id") and v is not None} \
                if self.args else {}
            if tr is not None and tr.query_id is not None:
                ids.setdefault("query_id", tr.query_id)
            ann = _annotation(SPAN_PREFIX + self.name, **ids)
            ann.__enter__()
            self._ann = ann
            if tr is not None:
                self._sp = tr.push(self.name, self.kind, t0, self.args)
        except Exception:  # noqa: BLE001 — see _helper_failed
            _helper_failed()
        return self._sp

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter_ns()
        self.elapsed_ns = t1 - self._t0
        try:
            if self._sp is not None:
                self.tracer.pop(self._sp, t1)
        except Exception:  # noqa: BLE001
            _helper_failed()
        try:
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
        except Exception:  # noqa: BLE001
            _helper_failed()
        return False


def propagation_enabled() -> bool:
    """Header-propagation kill switch (chaos-tested degradation hook):
    PRESTO_TPU_TRACE_PROPAGATION=off strips the X-Presto-Trace header
    from every outbound request, so workers fall back to worker-local
    traces."""
    return os.environ.get("PRESTO_TPU_TRACE_PROPAGATION", "").lower() \
        not in ("off", "0", "false")


def wire_context() -> Optional[str]:
    """The X-Presto-Trace header value for an outbound request:
    `trace_id;current_span_id` (None = no active tracer / propagation
    off)."""
    if not propagation_enabled():
        return None
    tr = current()
    if tr is None:
        return None
    return f"{tr.trace_id};{tr._thread_parent_id()}"


def from_wire(header: Optional[str]):
    """Header value -> (trace_id, parent_span_id) or (None, "")."""
    if not header or ";" not in header:
        return None, ""
    trace_id, _, parent = header.partition(";")
    trace_id = trace_id.strip()
    return (trace_id or None), parent.strip()


# ---------------------------------------------------------------------------
# session policy
# ---------------------------------------------------------------------------


def detail(session) -> str:
    """`trace_detail` session property: off | basic | full.  `basic`
    (default) records query/phase/fragment/task/attempt/compile spans;
    `full` adds page-pull and per-exchange spans in cluster mode; `off`
    disables the recorder (the observability_overhead A/B lever)."""
    try:
        d = str(session.properties.get("trace_detail", "basic")).lower()
    except Exception:
        return "basic"
    return d if d in ("off", "basic", "full") else "basic"


def enabled(session) -> bool:
    return detail(session) != "off"
