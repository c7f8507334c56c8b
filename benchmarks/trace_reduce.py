"""From a profiler trace (.xplane.pb) to three things: device busy
seconds, the device operations that took most time, and the idle gaps
labelled by what the client was waiting for.

Reads the trace with nothing but JAX (`jax.profiler.ProfileData`).  A
trace is first flattened to plain tuples (`load`), so the arithmetic
(`reduce_events`) can be checked by hand on a small fixture.

What counts as the device: the planes whose name starts with
DEVICE_PLANE, and of their lines only OP_LINE — one event per XLA
operation as the chip ran it.  ("XLA Modules" spans whole programs, gaps
between their operations included; "Steps" groups them.)  Query spans are
the harness's own `jax.profiler.TraceAnnotation`s, named
ANNOTATION + <class>, found on any line of a host plane.
"""

import glob
import os

DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:"
ANNOTATION = "query:"
LABELLED_GAPS = 2000    # the longest; the rest is summed under one label


def newest_xplane(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path, device_plane=DEVICE_PLANE, op_line=OP_LINE):
    """-> (ops, spans, layout).  ops: {device plane name: [(name, start_ns,
    end_ns)]}; spans: [(class, start_ns, end_ns)]; layout: every plane and
    line with its event count, for a reader who has not seen this trace."""
    from jax.profiler import ProfileData

    ops, spans, layout = {}, [], []
    for plane in ProfileData.from_file(path).planes:
        on_device = plane.name.startswith(device_plane)
        on_host = plane.name.startswith(HOST_PLANE)
        for line in plane.lines:
            n = 0
            for ev in line.events:
                n += 1
                if on_device and line.name.startswith(op_line):
                    ops.setdefault(plane.name, []).append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
                elif on_host and ev.name.startswith(ANNOTATION):
                    spans.append((ev.name[len(ANNOTATION):], ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
            layout.append((plane.name, line.name, n))
    return ops, spans, layout


def union(intervals):
    """Sorted, merged copy of [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def short_name(op):
    """XLA names a device event by its whole HLO line; the result's name
    (before " = ") is what stays the same from run to run."""
    return op.split(" = ", 1)[0].lstrip("%")


def self_times(events):
    """[(name, start, end)] -> [(name, self ns)]: an operation that holds
    others (a while loop and its body) keeps only the time they leave."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        if stack and e <= stack[-1][2]:
            stack[-1][3][1] -= e - s
        entry = [name, e - s]
        out.append(entry)
        stack.append((name, s, e, entry))
    return [(name, max(ns, 0)) for name, ns in out]


def reduce_events(ops, spans, top=10):
    """The slice is [first span start, last span end]: what the clients
    saw.  Device events are clipped to it.

    -> {"window_s", "busy_s" (union of op intervals, mean over device
        planes), "queries" (span-equivalents inside the slice: whole spans
        count 1; every span lies inside by construction),
        "queries_by_class", "device_ops": [[short name, self s]] summed over
        planes and divided by their number, "idle_gaps": [[label, s]] summed per label
        on the first device plane, "longest_gaps": [[label of its largest
        part, s]]}
    or None when there is no span or no device event to read."""
    if not spans or not ops:
        return None
    t0 = min(s for _, s, _ in spans)
    t1 = max(e for _, _, e in spans)
    by_op, busy_ns, gaps_of_first = {}, [], None
    for plane in sorted(ops):
        clipped = [(name, max(s, t0), min(e, t1)) for name, s, e in ops[plane]
                   if e > t0 and s < t1]
        for name, ns in self_times(clipped):
            by_op[short_name(name)] = by_op.get(short_name(name), 0) + ns
        merged = union([(s, e) for _, s, e in clipped])
        busy_ns.append(sum(e - s for s, e in merged))
        if gaps_of_first is None:
            edges = [t0] + [x for se in merged for x in se] + [t1]
            gaps_of_first = [(edges[i], edges[i + 1])
                             for i in range(0, len(edges), 2)
                             if edges[i + 1] > edges[i]]
    n_planes = len(ops)
    by_class = {}
    for cls, _, _ in spans:
        by_class[cls] = by_class.get(cls, 0) + 1

    def parts(gs, ge):
        """One gap's nanoseconds by label: what lies inside a query span goes
        to in_query:<class> (overlapping spans of several clients share it in
        proportion), the rest to between_queries."""
        by_cls, inside = {}, []
        for cls, s, e in spans:
            lo, hi = max(s, gs), min(e, ge)
            if hi > lo:
                by_cls[cls] = by_cls.get(cls, 0) + (hi - lo)
                inside.append((lo, hi))
        covered = sum(e - s for s, e in union(inside))
        out = {f"in_query:{c}": covered * v / sum(by_cls.values())
               for c, v in by_cls.items()}
        if ge - gs > covered:
            out["between_queries"] = ge - gs - covered
        return out

    by_label, longest = {}, []
    # labelling is spans x gaps: only the gaps that carry the idle time
    gaps_of_first.sort(key=lambda g: g[0] - g[1])
    for gs, ge in gaps_of_first[:LABELLED_GAPS]:
        split = parts(gs, ge)
        for lab, ns in split.items():
            by_label[lab] = by_label.get(lab, 0) + ns
        if len(longest) < top:
            longest.append([max(split, key=split.get), (ge - gs) / 1e9])
    rest = sum(ge - gs for gs, ge in gaps_of_first[LABELLED_GAPS:])
    if rest:
        by_label["short_gaps_not_labelled"] = rest

    def ranked(d, scale):
        return [[k, v / scale] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy_ns) / n_planes / 1e9,
        "queries": len(spans),
        "queries_by_class": by_class,
        "device_ops": ranked(by_op, 1e9 * n_planes),
        "idle_gaps": ranked(by_label, 1e9),
        "longest_gaps": longest,
    }


def reduce_trace(trace_dir, device_plane=DEVICE_PLANE, op_line=OP_LINE):
    """-> (reduced or None, layout)."""
    ops, spans, layout = load(newest_xplane(trace_dir), device_plane, op_line)
    return reduce_events(ops, spans), layout
