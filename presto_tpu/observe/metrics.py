"""Cluster-wide metrics: a process-level registry + Prometheus text.

Reference parity: the reference engine exports every QueryStats /
operator counter through JMX (presto-main jmx beans, scraped by the
jmx connector and the ops dashboards).  Our answer is a dependency-free
registry — counters, gauges, and histograms —
served as Prometheus text exposition from `/v1/metrics` on BOTH the
coordinator (server/protocol.py) and every cluster worker
(parallel/cluster.py), replacing the ad-hoc JSON-only aggregation that
previously lived on `/v1/info` as the sole ops surface.

The registry is the process-wide sink every subsystem rolls into:

- every numeric `QueryStats` counter field folds in at query
  completion (`observe_query`, called by QueryMonitor.finish/fail) as
  `presto_tpu_query_<field>_total` — the field list is ENUMERATED from
  the dataclass (`querystats_counter_fields`), and the schema-drift
  test asserts each one appears in a live `/v1/metrics` scrape, so a
  new QueryStats counter can never silently miss the ops surface;
- cluster recovery counters (`presto_tpu_query_recovery_total{kind}`)
  and per-phase wall (`presto_tpu_query_phase_seconds_total{phase}`);
- worker task counters (`presto_tpu_worker_*`, parallel/cluster.py);
- event-listener failures (`presto_tpu_listener_errors_total`,
  observe/events.py — previously swallowed silently);
- failures of the span helper's own code
  (`presto_tpu_trace_errors_total`, observe/trace.py: swallowed, a query
  is never failed by its instrumentation).

Naming scheme (docs/OBSERVABILITY.md): `presto_tpu_<subsystem>_<what>
_<unit-or-total>`; labels are bounded-cardinality enums only (state,
mode, phase, kind, listener class) — never query ids or SQL text.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple


def _escape_help(s: str) -> str:
    return s.replace("\\", "\\\\").replace("\n", "\\n")


def _escape_label(s: str) -> str:
    return s.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt(v: float) -> str:
    if v == float("inf"):
        return "+Inf"
    f = float(v)
    return repr(int(f)) if f == int(f) else repr(f)


def _labels_key(label_names: Sequence[str], labels: Dict[str, object]):
    if set(labels) != set(label_names):
        raise ValueError(f"expected labels {label_names}, got "
                         f"{sorted(labels)}")
    return tuple(str(labels[n]) for n in label_names)


class Metric:
    kind = "untyped"

    def __init__(self, name: str, help_: str = "",
                 label_names: Sequence[str] = ()):
        self.name = name
        self.help = help_
        self.label_names = tuple(label_names)
        self._lock = threading.Lock()

    def header(self) -> List[str]:
        return [f"# HELP {self.name} {_escape_help(self.help)}",
                f"# TYPE {self.name} {self.kind}"]

    def _series(self, suffix: str, key: tuple, value: float,
                extra: Sequence[Tuple[str, str]] = ()) -> str:
        pairs = [(n, v) for n, v in zip(self.label_names, key)]
        pairs += list(extra)
        lbl = ",".join(f'{n}="{_escape_label(str(v))}"' for n, v in pairs)
        return f"{self.name}{suffix}{{{lbl}}} {_fmt(value)}" if lbl \
            else f"{self.name}{suffix} {_fmt(value)}"


class Counter(Metric):
    kind = "counter"

    def __init__(self, name, help_="", label_names=()):
        super().__init__(name, help_, label_names)
        self._values: Dict[tuple, float] = {}
        if not self.label_names:
            self._values[()] = 0.0  # appear in scrapes before first inc

    def inc(self, amount: float = 1.0, **labels) -> None:
        key = _labels_key(self.label_names, labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + float(amount)

    def value(self, **labels) -> float:
        key = _labels_key(self.label_names, labels)
        with self._lock:
            return self._values.get(key, 0.0)

    def render(self) -> List[str]:
        with self._lock:
            items = sorted(self._values.items())
        return self.header() + [self._series("", k, v) for k, v in items]


class Gauge(Metric):
    kind = "gauge"

    def __init__(self, name, help_="", label_names=()):
        super().__init__(name, help_, label_names)
        self._values: Dict[tuple, float] = {}
        self._fn: Optional[Callable[[], float]] = None
        if not self.label_names:
            self._values[()] = 0.0

    def set(self, value: float, **labels) -> None:
        key = _labels_key(self.label_names, labels)
        with self._lock:
            self._values[key] = float(value)

    def set_fn(self, fn: Callable[[], float]) -> None:
        """Collect-time callback (unlabeled gauges only) — e.g. uptime,
        queue depth read at scrape time."""
        self._fn = fn

    def render(self) -> List[str]:
        if self._fn is not None:
            try:
                v = float(self._fn())
            except Exception:  # noqa: BLE001 — a broken probe reads 0
                v = 0.0
            return self.header() + [self._series("", (), v)]
        with self._lock:
            items = sorted(self._values.items())
        return self.header() + [self._series("", k, v) for k, v in items]


#: default histogram buckets: wall-clock style, milliseconds-friendly
DEFAULT_BUCKETS = (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                   500.0, 1000.0, 2500.0, 5000.0, 10000.0, 30000.0,
                   float("inf"))

class Histogram(Metric):
    """Cumulative-bucket histogram: Prometheus buckets, sum and count."""

    kind = "histogram"

    def __init__(self, name, help_="", buckets: Sequence[float] = None):
        super().__init__(name, help_, ())
        bs = tuple(buckets) if buckets else DEFAULT_BUCKETS
        if bs[-1] != float("inf"):
            bs = bs + (float("inf"),)
        self.buckets = bs
        self._counts = [0] * len(bs)
        self._sum = 0.0
        self._count = 0

    def observe(self, value: float) -> None:
        v = float(value)
        with self._lock:
            self._sum += v
            self._count += 1
            for i, b in enumerate(self.buckets):
                if v <= b:
                    self._counts[i] += 1
                    break

    def render(self) -> List[str]:
        with self._lock:
            counts = list(self._counts)
            total, s = self._count, self._sum
        out = self.header()
        cum = 0
        for b, c in zip(self.buckets, counts):
            cum += c
            out.append(self._series("_bucket", (), cum, [("le", _fmt(b))]))
        out.append(self._series("_sum", (), s))
        out.append(self._series("_count", (), total))
        return out


class Registry:
    def __init__(self):
        self._metrics: Dict[str, Metric] = {}
        self._lock = threading.Lock()

    def _get_or_make(self, cls, name, help_, **kw) -> Metric:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, help_, **kw)
            elif not isinstance(m, cls):
                raise TypeError(f"metric {name} registered as "
                                f"{type(m).__name__}")
            return m

    def counter(self, name, help_="", label_names=()) -> Counter:
        return self._get_or_make(Counter, name, help_,
                                 label_names=label_names)

    def gauge(self, name, help_="", label_names=()) -> Gauge:
        return self._get_or_make(Gauge, name, help_,
                                 label_names=label_names)

    def histogram(self, name, help_="", buckets=None) -> Histogram:
        return self._get_or_make(Histogram, name, help_, buckets=buckets)

    def get(self, name) -> Optional[Metric]:
        with self._lock:
            return self._metrics.get(name)

    def render(self) -> str:
        """Prometheus text exposition (text/plain; version=0.0.4)."""
        with self._lock:
            metrics = sorted(self._metrics.items())
        lines: List[str] = []
        for _name, m in metrics:
            lines += m.render()
        return "\n".join(lines) + "\n"


#: THE process-wide registry (coordinator and worker scrapes read it)
REGISTRY = Registry()


# ---------------------------------------------------------------------------
# QueryStats -> registry (the schema-drift contract)
# ---------------------------------------------------------------------------

#: numeric QueryStats fields that are NOT monotone counters (timestamps)
NON_COUNTER_FIELDS = frozenset({"create_time", "end_time"})


def querystats_counter_fields() -> List[str]:
    """Every numeric counter field of the QueryStats dataclass, detected
    from the field DEFAULTS (int/float, bool excluded) minus the
    timestamp fields — the single source of truth the exporter, the
    audit log, and the schema-drift test all enumerate."""
    from presto_tpu.observe.stats import QueryStats

    out = []
    for f in dataclasses.fields(QueryStats):
        if f.name in NON_COUNTER_FIELDS:
            continue
        if isinstance(f.default, bool):
            continue
        if isinstance(f.default, (int, float)):
            out.append(f.name)
    return out


def query_metric_name(field: str) -> str:
    return f"presto_tpu_query_{field}_total"


_FIELD_HELP = "Sum of QueryStats.{f} across completed queries"


def ensure_query_metrics() -> None:
    """Pre-register every QueryStats counter metric (plus the lifecycle
    families) so a scrape covers the full schema from process start —
    on workers too, which never run whole queries themselves."""
    for f in querystats_counter_fields():
        REGISTRY.counter(query_metric_name(f), _FIELD_HELP.format(f=f))
    REGISTRY.counter("presto_tpu_queries_total",
                     "Completed queries by terminal state and mode",
                     ("state", "mode"))
    REGISTRY.counter("presto_tpu_query_phase_seconds_total",
                     "Wall seconds per query phase", ("phase",))
    REGISTRY.counter("presto_tpu_query_recovery_total",
                     "Cluster recovery actions by kind "
                     "(docs/ROBUSTNESS.md schema)", ("kind",))
    REGISTRY.counter("presto_tpu_query_agg_strategy_total",
                     "Grouped aggregates executed per planned strategy "
                     "(plan/agg_strategy.py: one_pass/final_only/"
                     "two_phase)", ("strategy",))
    REGISTRY.counter("presto_tpu_query_fusion_skips_total",
                     "Exchange edges kept on the HTTP path per skip "
                     "reason (plan/fusion_cost.py: cost/kind/memo/"
                     "cross_host)", ("reason",))
    REGISTRY.histogram("presto_tpu_query_wall_ms",
                       "End-to-end query wall time (ms)")
    REGISTRY.counter("presto_tpu_listener_errors_total",
                     "Event-listener exceptions swallowed by dispatch",
                     ("listener",))
    REGISTRY.counter(TRACE_ERRORS, _TRACE_ERRORS_HELP)


def observe_query(stats) -> None:
    """Fold one finished QueryStats into the registry (called by
    QueryMonitor.finish/fail — every execution path ends there)."""
    ensure_query_metrics()
    mode = getattr(stats, "execution_mode", "") or "none"
    REGISTRY.counter("presto_tpu_queries_total", "", ("state", "mode")) \
        .inc(state=getattr(stats, "state", "UNKNOWN") or "UNKNOWN",
             mode=mode)
    for f in querystats_counter_fields():
        v = getattr(stats, f, 0) or 0
        if v:
            REGISTRY.counter(query_metric_name(f)).inc(float(v))
    for phase, ns in (getattr(stats, "phase_ns", None) or {}).items():
        REGISTRY.counter("presto_tpu_query_phase_seconds_total", "",
                         ("phase",)).inc(ns / 1e9, phase=phase)
    for kind, n in (getattr(stats, "recovery", None) or {}).items():
        REGISTRY.counter("presto_tpu_query_recovery_total", "",
                         ("kind",)).inc(float(n), kind=kind)
    for strat, n in (getattr(stats, "agg_strategy", None) or {}).items():
        REGISTRY.counter("presto_tpu_query_agg_strategy_total", "",
                         ("strategy",)).inc(float(n), strategy=strat)
    for reason, n in (getattr(stats, "fusion_skips", None) or {}).items():
        REGISTRY.counter("presto_tpu_query_fusion_skips_total", "",
                         ("reason",)).inc(float(n), reason=reason)
    REGISTRY.histogram("presto_tpu_query_wall_ms").observe(
        getattr(stats, "total_ns", 0) / 1e6)


def record_recovery(kind: str, n: int = 1) -> None:
    """Count a recovery action that happens OUTSIDE a query's own
    RunContext — e.g. protocol-level adoption of a dead peer's
    journaled queries (server/protocol._adopt_from), which runs before
    any QueryStats exists to fold the counter through observe_query.
    Same family as the per-query recovery keys, so dashboards see one
    `presto_tpu_query_recovery_total{kind}` surface either way."""
    ensure_query_metrics()
    REGISTRY.counter("presto_tpu_query_recovery_total", "",
                     ("kind",)).inc(float(n), kind=kind)


def listener_error(listener_class: str) -> None:
    """Count one swallowed event-listener failure (observe/events.py)."""
    REGISTRY.counter("presto_tpu_listener_errors_total",
                     "Event-listener exceptions swallowed by dispatch",
                     ("listener",)).inc(listener=listener_class)


TRACE_ERRORS = "presto_tpu_trace_errors_total"
_TRACE_ERRORS_HELP = ("Exceptions raised by the span helper's own code "
                      "(observe/trace.span) and swallowed: a query is "
                      "never failed by its instrumentation")


def trace_error() -> None:
    """Count one swallowed failure of the span helper itself."""
    REGISTRY.counter(TRACE_ERRORS, _TRACE_ERRORS_HELP).inc()


def set_fleet_gauges(fleet_stats: Dict[str, object]) -> None:
    """Fleet-coordination gauges (server/fleet.py): ring size, slot
    leases in flight, gossip/invalidation traffic, front-door routing.
    Scrape-time refresh like the serving-tier gauges — the fleet stats
    dict is the source of truth, the registry is the exposition."""
    ring = fleet_stats.get("ring")
    if isinstance(ring, (list, tuple)):
        REGISTRY.gauge("presto_tpu_fleet_coordinators",
                       "Coordinators on the ownership ring"
                       ).set(len(ring))
    slots = fleet_stats.get("slots")
    if isinstance(slots, dict):
        for k, v in slots.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                name = "".join(c if c.isalnum() or c == "_" else "_"
                               for c in str(k)).lower()
                REGISTRY.gauge(f"presto_tpu_fleet_slot_{name}",
                               f"Worker slot-lease {k}").set(v)
    for k, v in fleet_stats.items():
        if k in ("ring", "slots") or not isinstance(v, (int, float)) \
                or isinstance(v, bool):
            continue
        name = "".join(c if c.isalnum() or c == "_" else "_"
                       for c in str(k)).lower()
        REGISTRY.gauge(f"presto_tpu_fleet_{name}",
                       f"Fleet {k}").set(v)


def render_scrape(extra_counters: Optional[Dict[str, float]] = None,
                  prefix: str = "presto_tpu_worker_") -> str:
    """The /v1/metrics payload: the registry, plus (on workers) the
    task-accounting counters dict folded in as gauges under `prefix` —
    the same numbers /v1/info has always served as JSON."""
    ensure_query_metrics()
    text = REGISTRY.render()
    if extra_counters:
        lines = []
        for k, v in sorted(extra_counters.items()):
            name = prefix + "".join(
                c if c.isalnum() or c == "_" else "_" for c in str(k))
            lines.append(f"# HELP {name} Worker counter {k}")
            lines.append(f"# TYPE {name} gauge")
            lines.append(f"{name} {_fmt(float(v))}")
        text += "\n".join(lines) + "\n"
    return text
