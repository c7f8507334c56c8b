"""From the traced run's profile to the ENGINE's names: device time by the
program's kernel scopes and plan nodes, idle time by the program's own host
spans, and each span's time per query.

`trace_reduce.py` reads what any JAX program leaves in a profile (XLA's
operation names, the harness's `query:<class>` annotation).  This file reads
what presto_tpu adds (`presto_tpu/observe/names.py`):

- host spans: `jax.profiler.TraceAnnotation`s named `presto:<span>` on the
  thread that did the work, so on the profiler's clock;
- device scopes: `jax.named_scope`s that end up in an HLO instruction's
  `op_name` — `.../Aggregate/k:fused_group_sums/...`.  A device event
  holds the instruction's name (on the TPU its whole HLO line, without the
  metadata) and the module's (a CPU's events as a stat, a TPU's through
  the module event that runs meanwhile); the table
  `compile_cache.scope_tables()`, built from the compiled programs' own
  HLO text, gives the op_name of (module, instruction).  The engine names
  its modules `jit_<fn>_s<SCOPE_VERSION>_<plan fingerprint>`, so that one
  query's program is told from another's.

Rules (`reduce_events`):

- A device operation belongs to the INNERMOST `k:`/`x:` scope of its op_name
  and to the INNERMOST plan-node scope (a CamelCase component; the executor's
  plan scopes nest as its recursion does, so the outermost is always the
  root).  A fusion takes its root's.  Times are self times.
- Every idle gap of the first device plane goes to the `presto:` span open
  NEAREST THE DEVICE during it.  Client, handler and worker are three
  threads of one request: within a thread the innermost open span counts;
  across threads a fixed order by name, PRECEDENCE below — the worker's
  spans before the handler's before the client's.  What no span covers is
  `in_query:unnamed` inside a query span and `between_queries` outside.
- A span's or a scope's time belongs to the class whose `query:<class>` span
  it overlaps (several classes at once share it by their overlap): exact with
  one client or one class, which is every cell there is.

A program without these spans and scopes (the parent of the PR that added
them) reduces to zeros, never to an error.  Run by the per-layer metrics
through `reduced(run)`; parsed once per process; prints one earlier line,
`engine_breakdown`, for the reader.
"""

import bisect
import glob
import json
import os
import re

import trace_reduce as tr     # benchmarks/ is the script's directory

HERE = os.path.dirname(os.path.abspath(__file__))
TRACES = os.path.join(HERE, ".cache", "trace")
ENGINE = "presto:"
QUERY = "query:"
# a CPU has no device plane: a rehearsal reads the CPU client's own lines
DEVICE = ("/device:TPU:", "XLA Ops")
MODULE_LINE = "XLA Modules"
REHEARSAL_DEVICE = ("/host:CPU", "tf_XLAPjRtCpuClient")
HOST_PLANE = "/host:"

#: nearest the device first.  A name not listed ranks with its prefix's
#: last entry (`exec.`, `coalesce.`, `http.`, `client.`), else after all.
PRECEDENCE = (
    "exec.dispatch", "exec.materialize", "exec.wait_fetch", "xla_compile",
    "result.rows", "coalesce.window", "coalesce.ride", "prepared.bind",
    "admission.wait", "parse", "plan", "execute",
    "http.encode", "http.submit", "http.grace_wait", "http.long_poll",
    "http.post", "http.get",
    "client.poll_sleep", "client.post", "client.get")
#: spans that only say "somewhere in the request": idle time they get is
#: not named (idle_named_share)
CATCH_ALL = frozenset({"execute", "http.post", "http.get", "client.post",
                       "client.get"})
UNNAMED = "in_query:unnamed"
BETWEEN = "between_queries"

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_PLAN_NODE = re.compile(r"^[A-Z][A-Za-z0-9]*$")


def rank(name):
    if name in PRECEDENCE:
        return PRECEDENCE.index(name)
    prefix = name.split(".", 1)[0] + "."
    same = [i for i, p in enumerate(PRECEDENCE) if p.startswith(prefix)]
    return same[-1] if same else len(PRECEDENCE)


# ---------------------------------------------------------------------------
# the profile, flattened
# ---------------------------------------------------------------------------


def newest_xplane(root=TRACES):
    files = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load(path):
    """-> (ops, host).  ops: {device plane: [(name, start_ns, end_ns,
    hlo module or None, hlo_op or None)]}; host: {thread line: [(name,
    start_ns, end_ns)]} of the `presto:` and `query:` annotations.  A
    TPU's operation events hold no module: it is the event of the plane's
    MODULE_LINE that runs meanwhile, `<module>(<fingerprint>)`."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    dev = DEVICE if any(p.name.startswith(DEVICE[0]) for p in planes) \
        else REHEARSAL_DEVICE
    ops, host = {}, {}
    for plane in planes:
        on_device = plane.name.startswith(dev[0])
        on_host = plane.name.startswith(HOST_PLANE)
        modules = []
        for n, line in enumerate(plane.lines):
            if on_device and line.name == MODULE_LINE:
                modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name.split("(", 1)[0])
                                 for ev in line.events)
            if on_device and line.name.startswith(dev[1]):
                out = ops.setdefault(plane.name, [])
                for ev in line.events:
                    if ev.name.startswith(("ThreadpoolListener",
                                           "ThunkExecutor")):
                        continue    # the CPU client's own bookkeeping
                    st = dict(ev.stats)
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                st.get("hlo_module"), st.get("hlo_op")))
            if on_host:
                for ev in line.events:
                    if ev.name.startswith((ENGINE, QUERY)):
                        host.setdefault((plane.name, n, line.name), []).append(
                            (ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns))
        if modules and plane.name in ops:
            starts = [m[0] for m in modules]
            ops[plane.name] = [
                ev if ev[3] else ev[:3] + (module_at(modules, starts, ev[1]),
                                           ev[4])
                for ev in ops[plane.name]]
    return ops, host


def module_at(modules, starts, t):
    i = bisect.bisect_right(starts, t) - 1
    return modules[i][2] if i >= 0 and t < modules[i][1] else None


def scope_tables():
    """The program's instruction -> op_name tables; {} from a program that
    has none (the parent of the PR that added them)."""
    try:
        from presto_tpu.exec import compile_cache as CC

        return CC.scope_tables()
    except Exception:  # noqa: BLE001 — an older program: nothing to read
        return {}


def op_name_of(event, tables):
    """The op_name of a device event: in its own name where the line holds
    its metadata, else in the tables by module and instruction."""
    name, _, _, module, hlo_op = event
    m = _OP_NAME.search(name)
    if m:
        return m.group(1)
    return tables.get(module, {}).get(hlo_op or tr.short_name(name))


def scopes_of(op_name):
    """-> (innermost k:/x: scope or None, innermost plan node or None)."""
    kernel = node = None
    for part in (op_name or "").split("/"):
        if part.startswith(("k:", "x:")):
            kernel = part
        elif _PLAN_NODE.match(part):
            node = part
    return kernel, node


def unscoped_label(op_name, short):
    """What to call device time no k:/x: scope covers: the plan node and
    the primitive its op_name ends in (`Join/cumsum`), which say what to
    scope next; XLA's own name where the program gave none."""
    _, node = scopes_of(op_name)
    if not op_name or node is None:
        return short
    return f"{node}/{op_name.rsplit('/', 1)[-1]}"


# ---------------------------------------------------------------------------
# interval arithmetic on sorted disjoint lists
# ---------------------------------------------------------------------------


def intersect(a, b):
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b):
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def total(a):
    return sum(e - s for s, e in a)


def innermost_segments(spans):
    """One thread's nested [(name, start, end)] -> [(name, start, end)]
    that do not overlap: each instant under its innermost open span."""
    out, stack = [], []

    def emit(name, s, e):
        if e > s:
            out.append((name, s, e))

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            top = stack.pop()
            emit(top[0], top[3], top[2])
            if stack:
                stack[-1][3] = max(stack[-1][3], top[2])
        if stack:
            emit(stack[-1][0], stack[-1][3], s)
            e = min(e, stack[-1][2])
        stack.append([name, s, e, s])   # [name, start, end, uncovered from]
    while stack:
        top = stack.pop()
        emit(top[0], top[3], top[2])
        if stack:
            stack[-1][3] = max(stack[-1][3], top[2])
    return out


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------


class ByClass:
    """Shares an interval's nanoseconds among the classes whose query
    spans it overlaps."""

    def __init__(self, queries):
        self.queries = sorted(queries, key=lambda q: q[1])
        self.starts = [q[1] for q in self.queries]
        self.longest = max((e - s for _, s, e in self.queries), default=0)
        classes = {c for c, _, _ in queries}
        self.only = classes.pop() if len(classes) == 1 else None

    def add(self, into, key, s, e, ns=None):
        ns = e - s if ns is None else ns
        if self.only is not None:
            share = {self.only: 1.0}
        else:
            over = {}
            lo = bisect.bisect_left(self.starts, s - self.longest)
            for cls, qs, qe in self.queries[lo:bisect.bisect_right(self.starts, e)]:
                o = min(e, qe) - max(s, qs)
                if o > 0 or (e == s and qs <= s <= qe):
                    over[cls] = over.get(cls, 0) + max(o, 1)
            share = {c: o / sum(over.values()) for c, o in over.items()}
        for cls, w in share.items():
            d = into.setdefault(cls, {})
            d[key] = d.get(key, 0.0) + ns * w


def reduce_events(ops, host, tables, top=12):
    """-> None without a query span, else {"window_s", "busy_s",
    "queries_by_class", "device_by_kernel", "device_by_node",
    "device_unscoped" (where no k:/x: scope covers: unscoped_label),
    "idle_by_span", "idle_in_query_s", "idle_named_s",
    "span_ns_by_class": {class: {span: ns}},
    "kernel_ns_by_class", "node_ns_by_class": {class: {scope: ns}}}.
    Seconds unless the key says ns; device numbers are means over planes,
    idle numbers are the first plane's."""
    queries = [(n[len(QUERY):], s, e) for evs in host.values()
               for n, s, e in evs if n.startswith(QUERY)]
    if not queries:
        return None
    t0 = min(s for _, s, _ in queries)
    t1 = max(e for _, _, e in queries)
    by_class = ByClass(queries)
    n_by_class = {}
    for cls, _, _ in queries:
        n_by_class[cls] = n_by_class.get(cls, 0) + 1

    # host: per thread the innermost open span, then one union per name
    span_ns, per_name = {}, {}
    for evs in host.values():
        mine = [(n[len(ENGINE):], max(s, t0), min(e, t1)) for n, s, e in evs
                if n.startswith(ENGINE) and e > t0 and s < t1]
        for name, s, e in mine:
            by_class.add(span_ns, name, s, e)
        for name, s, e in innermost_segments(mine):
            per_name.setdefault(name, []).append((s, e))
    per_name = {n: tr.union(iv) for n, iv in per_name.items()}

    # device: self time by scope, busy union, the first plane's gaps
    kernel_ns, node_ns = {}, {}
    by_kernel, by_node, unscoped, busy, gaps = {}, {}, {}, [], None
    for plane in sorted(ops):
        clipped = [(ev, max(ev[1], t0), min(ev[2], t1)) for ev in ops[plane]
                   if ev[2] > t0 and ev[1] < t1]
        keyed = [((i, s, e), s, e) for i, (_, s, e) in enumerate(clipped)]
        for (i, s, e), ns in tr.self_times(keyed):
            ev = clipped[i][0]
            op_name = op_name_of(ev, tables)
            kernel, node = scopes_of(op_name)
            by_kernel[kernel] = by_kernel.get(kernel, 0) + ns
            by_node[node] = by_node.get(node, 0) + ns
            if kernel is None:
                label = unscoped_label(op_name, tr.short_name(ev[0]))
                unscoped[label] = unscoped.get(label, 0) + ns
            by_class.add(kernel_ns, kernel, s, e, ns)
            by_class.add(node_ns, node, s, e, ns)
        merged = tr.union([(s, e) for _, s, e in clipped])
        busy.append(total(merged))
        if gaps is None:
            gaps = subtract([(t0, t1)], merged)
    n_planes = max(len(ops), 1)

    # idle: each gap to the span nearest the device that is open in it;
    # `named` is what a span other than a catch-all took INSIDE queries (a
    # span can outlast its query's annotation by microseconds)
    idle, left, named = {}, gaps or [], 0
    in_query = intersect(left, tr.union([(s, e) for _, s, e in queries]))
    for name in sorted(per_name, key=rank):
        got = intersect(left, per_name[name])
        if got:
            idle[name] = total(got)
            left = subtract(left, got)
            if name not in CATCH_ALL:
                named += total(intersect(got, in_query))
    inside = total(intersect(left, in_query))
    if inside:
        idle[UNNAMED] = inside
    if total(left) > inside:
        idle[BETWEEN] = total(left) - inside

    def ranked(d, scale, skip_none=True):
        rows = [(k, v) for k, v in d.items() if not (skip_none and k is None)]
        return [[k, v / scale] for k, v in
                sorted(rows, key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy) / n_planes / 1e9,
        "self_s": sum(by_kernel.values()) / n_planes / 1e9,
        "scoped_s": sum(v for k, v in by_kernel.items()
                        if k is not None) / n_planes / 1e9,
        "queries_by_class": n_by_class,
        "device_by_kernel": ranked(by_kernel, 1e9 * n_planes),
        "device_by_node": ranked(by_node, 1e9 * n_planes),
        "device_unscoped": ranked(unscoped, 1e9 * n_planes),
        "idle_by_span": ranked(idle, 1e9),
        "idle_in_query_s": total(in_query) / 1e9,
        "idle_named_s": named / 1e9,
        "span_ns_by_class": span_ns,
        "kernel_ns_by_class": {c: {k: v / n_planes for k, v in d.items()}
                               for c, d in kernel_ns.items()},
        "node_ns_by_class": {c: {k: v / n_planes for k, v in d.items()}
                             for c, d in node_ns.items()},
    }


# ---------------------------------------------------------------------------
# what the metric files call
# ---------------------------------------------------------------------------

_parsed = {}


def reduced(run):
    """The newest traced profile under .cache/trace/, reduced; None when
    the run was not traced or left no profile.  Parsed once per process."""
    if run.trace is None:
        return None
    path = newest_xplane()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _parsed:
        ops, host = load(path)
        _parsed[key] = r = reduce_events(ops, host, scope_tables())
        if r is not None:
            print(json.dumps({"engine_breakdown": {
                "device_by_kernel": r["device_by_kernel"],
                "device_by_node": r["device_by_node"],
                "device_unscoped": r["device_unscoped"],
                "idle_by_span": r["idle_by_span"],
                "span_ms_per_query": {
                    c: {k: v / 1e6 / r["queries_by_class"][c]
                        for k, v in sorted(d.items())}
                    for c, d in r["span_ns_by_class"].items()}}}),
                flush=True)
    return _parsed[key]


def ms_per_query(r, table, keys, cls=None):
    """Mean over classes (or the one class `cls`) of the class's total
    under `keys` (names, or a predicate on a name) per query of the class,
    in ms.  0.0 where nothing of the kind occurred."""
    match = keys if callable(keys) else (lambda k: k in keys)
    per = []
    for c, n in r["queries_by_class"].items():
        if cls is not None and c != cls:
            continue
        ns = sum(v for k, v in r[table].get(c, {}).items()
                 if k is not None and match(k))
        per.append(ns / 1e6 / n)
    return sum(per) / len(per) if per else 0.0
