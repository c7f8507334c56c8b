#!/usr/bin/env python3
"""One run of one benchmark cell: served queries against the engine on the chip.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process.  Set-up: the session and server the cell's configuration file
describes, tables generated on the device, every class of the cell warmed
(compiled, or loaded from the persistent compile cache), expected answers
from the reference (its cache, or computed on a thread beside the
compile).  Window: the cell's clients, each a closed loop over the REST
protocol, for --seconds.  After it: every answer checked, the server's own
QueryStats and counters read once, metrics computed by the files that
BENCHMARK.json names, one JSON line printed last.

Everything that belongs to one cell, configuration, query or metric is in
a file found by its name (README.md); nothing of the kind is in here.
--rehearse is the builder's: any backend, every scale 0.01, and the line
says "correct": false so that it cannot be taken for a result.
"""

import time

T_START = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(HERE, ".cache")
FOLDERS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}
REHEARSAL_SF = 0.01
WARM_PASSES = 8             # concurrent warm-up passes before giving up
CLEAN_PASSES = 2            # ... that must build nothing, in a row
COMPILES = "presto_tpu_query_compiles_total"    # the server's counter of builds
# a CPU has no device plane: a rehearsal reads the CPU client's own lines
REHEARSAL_TRACE = {"device_plane": "/host:CPU", "op_line": "tf_XLAPjRtCpuClient"}


class BenchError(Exception):
    """The cell cannot run as described; the message names what is wrong."""


def info(**kw):
    """An earlier line of the output: for the reader, never for the driver."""
    print(json.dumps(kw), flush=True)


def load_module(rel):
    name = "bench_" + rel[:-3].replace(os.sep, "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path_of(rel, "module"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def path_of(rel, what):
    path = os.path.join(HERE, rel)
    if not os.path.exists(path):
        raise BenchError(f"unknown {what}: no file benchmarks/{rel}")
    return path


def load_json(rel, what):
    with open(path_of(rel, what)) as f:
        return json.load(f)


def read_text(rel, what):
    with open(path_of(rel, what)) as f:
        return f.read().strip()


# ---------------------------------------------------------------------------
# what a metric file is given
# ---------------------------------------------------------------------------


@dataclass
class Query:
    """One request as its client saw it, and the server's row for it."""

    client: int
    cls: str
    text: int               # index into the class's texts and expected answers
    wall: float             # time.time() at the POST
    t0: float               # perf_counter at the POST
    t1: float = 0.0         # ... at the last row
    rows: list = None
    error: str = None
    ok: bool = False        # answered as the reference does
    stats: object = None    # the QueryStats row, while the history holds it

    @property
    def ms(self):
        return (self.t1 - self.t0) * 1e3


@dataclass
class Run:
    """All a metric's `compute(run)` may read."""

    workload: dict
    config: dict
    sf: float
    seconds: float              # as asked for; a client's own window ends
    setup_seconds: float        # with the block in which they pass
    t_open: float = 0.0         # perf_counter when the window opened
    queries: list = field(default_factory=list)   # every Query, by start
    counters_before: dict = field(default_factory=dict)
    counters_after: dict = field(default_factory=dict)
    memory_peak_bytes: int = 0
    trace: dict = None          # trace_reduce.reduce_events(...), or None
    trace_end_wall: float = 0   # time.time() when the profiler had stopped
    counters_slice_end: dict = field(default_factory=dict)  # traced slice's end
    bytes_by_class: dict = field(default_factory=dict)
    peaks: dict = None          # this device's row of peaks.json

    def sibling(self, metric):
        """Another per-layer metric's module, for an arithmetic two share."""
        return metric_module("per_layer", metric)

    def good(self):
        """Answered, and as the reference does: what the metrics count."""
        return [q for q in self.queries if q.ok]

    def with_stats(self):
        """good() queries whose QueryStats row the history still held at
        the window's end (its last 1000) and, in a traced run, that began
        after the traced slice: host numbers without the profiler on."""
        return [q for q in self.good()
                if q.stats is not None and q.wall >= self.trace_end_wall]

    def classes(self):
        return [c["name"] for c in self.workload["classes"]]

    def class_medians(self, value, queries=None):
        """{class: median of value(q)} over `queries` (default: good())."""
        by = {}
        for q in (self.good() if queries is None else queries):
            by.setdefault(q.cls, []).append(value(q))
        return {c: statistics.median(v) for c, v in by.items()}

    def mean_of_class_medians(self, value):
        """For host numbers that may be 0: classes weigh alike."""
        med = self.class_medians(value, self.with_stats())
        return statistics.fmean(med.values()) if med else None

    def counter_delta(self, series_prefix, *having, slice_only=False):
        """Sum over the counter series that start with `series_prefix` and
        hold every string of `having`: after the window (or, with
        slice_only, at the traced slice's end) minus before."""
        def total(c):
            return sum(v for k, v in c.items() if k.startswith(series_prefix)
                       and all(h in k for h in having))
        after = self.counters_slice_end if slice_only else self.counters_after
        return total(after) - total(self.counters_before)


def read_counters(srv):
    """What GET /v1/metrics shows, read in the process: {series: value}."""
    out = {}
    for line in srv.metrics_payload().splitlines():
        if line and not line.startswith("#"):
            series, _, value = line.rpartition(" ")
            try:
                out[series] = float(value)
            except ValueError:
                pass
    return out


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


class Background:
    """fn(*args) on a daemon thread; result() joins and re-raises.  The
    reference is host numpy only (minutes at SF10), so it runs beside the
    warm-up, which spends its time compiling."""

    def __init__(self, fn, *args):
        self._out = self._exc = None

        def run():
            t0 = time.perf_counter()
            try:
                self._out = fn(*args)
            except BaseException as e:  # noqa: BLE001 — result() re-raises
                self._exc = e
            self.seconds = time.perf_counter() - t0

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def result(self):
        self._thread.join()
        if self._exc is not None:
            raise self._exc
        return self._out


def entry_point(dotted):
    mod, _, name = dotted.partition(":")
    return getattr(importlib.import_module(mod), name)


def build_server(cfg, sf):
    import presto_tpu
    from presto_tpu.server import PrestoTpuServer
    from presto_tpu.server.resource_groups import ResourceGroupManager

    session = presto_tpu.connect(
        entry_point(cfg["catalog_factory"])(sf, cache_dir=None))
    for k, v in cfg["session_properties"].items():
        session.set(k, v)
    rgm = ResourceGroupManager()
    rgm.load_config(cfg["server"]["resource_groups"])
    srv = PrestoTpuServer(session, resource_groups=rgm,
                          max_concurrent=cfg["server"]["max_concurrent"])
    if not cfg["session_properties"].get("result_cache_enabled", True) \
            and srv.serving.result_cache is not None:
        raise BenchError("the configuration has no result cache, the server has")
    return session, srv.start()


def send(uri, sql):
    from presto_tpu.client import StatementClient

    return [list(r) for r in StatementClient(uri, sql).rows()]


def last_stats(session, sql):
    """The server's own account of the newest execution of `sql`."""
    for st in reversed(session.history_snapshot()):
        if st.sql.strip() == sql:
            return st
    raise BenchError(f"no history row for {sql[:60]!r}")


def class_texts(spec, binds, uri, ref):
    """The request texts of one class, one per bind; PREPAREs where asked."""
    if "prepare" in spec:
        stmt = f"bench_{spec['name']}"
        send(uri, f"PREPARE {stmt} FROM "
             + read_text(f"queries/{spec['prepare']}.sql", "prepared signature"))
        return [f"EXECUTE {stmt} USING "
                + ", ".join(str(v) for v in ref.bind_values(b)) for b in binds]
    return [read_text(f"queries/{spec['query']}.sql", "query")] * len(binds)


def expected_answers(ref, cfg_name, sf, classes, binds):
    """{class: [expected rows per bind]}, and whether the streamed
    reference had to be computed (else it came from this checkout's cache)."""
    streamed = sorted({c["check"] for c in classes if c["check"] in ref.STREAMED})
    made, computed = ref.cached_streamed(CACHE, cfg_name, sf, streamed)
    out = {}
    for c in classes:
        if c["check"] in ref.STREAMED:
            out[c["name"]] = [made[c["check"]]] * len(binds[c["name"]])
        else:
            out[c["name"]] = ref.POINT[c["check"]](sf, binds[c["name"]])
    return out, computed


def traffic(classes, n_texts, seed, client):
    """The one generator: an endless sequence of blocks for one client, a
    block a list of (class index, text index).  A block holds every class
    `share` times in an order the seed shuffles, so every seed sends the
    same mix in another order; a class with several texts (binds) draws one
    uniformly."""
    import numpy as np

    rng = np.random.default_rng([seed, client])
    block = [i for i, c in enumerate(classes) for _ in range(int(c["share"]))]
    while True:
        yield [(int(i), int(rng.integers(n_texts[i])))
               for i in rng.permutation(block)]


def no_span(cls):
    return contextlib.nullcontext()


def closed_loops(uri, classes, texts, seed, clients, seconds, annotate,
                 seed_offset=0):
    """`clients` threads, each sending its next query when the last row of
    the previous one has arrived.  A client stops at the end of the block
    in which `seconds` pass: every window holds whole blocks, so the mix a
    rate is taken over does not depend on where the seed's order is cut.
    Returns once the window is open; join_all() waits for the clients.
    -> (threads, queries per client, perf_counter and time.time() at the
    opening)."""
    names = [c["name"] for c in classes]
    n_texts = [len(texts[n]) for n in names]
    per_client = [[] for _ in range(clients)]
    gate = threading.Barrier(clients + 1)
    opened = {}

    def loop(cid):
        plan = traffic(classes, n_texts, seed + seed_offset, cid)
        out = per_client[cid]
        gate.wait()
        while time.perf_counter() < opened["t"] + seconds:
            for ci, ti in next(plan):
                q = Query(cid, names[ci], ti, time.time(), time.perf_counter())
                try:
                    with annotate(q.cls):
                        q.rows = send(uri, texts[q.cls][ti])
                except Exception as e:  # noqa: BLE001 — a failed query is counted
                    q.error = f"{type(e).__name__}: {e}"
                q.t1 = time.perf_counter()
                out.append(q)

    threads = [threading.Thread(target=loop, args=(i,), daemon=True)
               for i in range(clients)]
    for t in threads:
        t.start()
    opened["t"] = time.perf_counter()
    wall = time.time()
    gate.wait()
    return threads, per_client, opened["t"], wall


def join_all(threads, per_client):
    for t in threads:
        t.join()
    return sorted((q for qs in per_client for q in qs), key=lambda q: q.t0)


def warm_up(session, srv, classes, texts, seed, wl):
    """Every class once (compiles, or loads from the persistent cache),
    then once more, which must build nothing.  With several clients the
    server batches concurrent binds into programs of their own, so the
    cell's traffic then runs for `warmup_seconds` at a time until
    CLEAN_PASSES passes in a row have built nothing."""
    uri = srv.uri
    for c in classes:
        sqls = texts[c["name"]]
        for n, sql in enumerate((sqls[0], sqls[-1])):
            t0 = time.perf_counter()
            send(uri, sql)
            st = last_stats(session, sql)
            info(warm=c["name"], pass_=n, ms=(time.perf_counter() - t0) * 1e3,
                 mode=st.execution_mode, fallback=st.fallback_reason,
                 compiles=st.compiles, compile_ms=st.compile_ms,
                 compile_cache_hits=st.compile_cache_hits)
            if n and st.compiles:
                raise BenchError(f"class {c['name']} compiled again when warm")
    if wl["clients"] == 1:
        return
    clean = 0
    for n in range(WARM_PASSES):
        before = read_counters(srv).get(COMPILES, 0.0)
        threads, per_client, _, _ = closed_loops(
            uri, classes, texts, seed, wl["clients"], wl["warmup_seconds"],
            no_span, seed_offset=1 + n)
        done = join_all(threads, per_client)
        built = read_counters(srv).get(COMPILES, 0.0) - before
        info(warm="concurrent", pass_=n, queries=len(done), compiles=built)
        clean = 0 if built else clean + 1
        if clean == CLEAN_PASSES:
            return
    raise BenchError(f"still compiling after {WARM_PASSES} concurrent warm-up passes")


# ---------------------------------------------------------------------------
# after the window
# ---------------------------------------------------------------------------


def pair_stats(queries, texts, history, wall_open):
    """Give each query the server's row for it: same text, same order.
    The history is a deque of 1000, so rows pair from the newest backwards
    and the oldest queries of a busy window go without."""
    rows, sent = {}, {}
    for st in history:
        if st.create_time >= wall_open - 1e-3:
            rows.setdefault(st.sql.strip(), []).append(st)
    for q in queries:
        sent.setdefault(texts[q.cls][q.text], []).append(q)
    for sql, qs in sent.items():
        for q, st in zip(reversed(qs), reversed(rows.get(sql, []))):
            q.stats = st


def metric_entries(bench, kind, workload):
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def metric_module(kind, name):
    return load_module(os.path.join(FOLDERS[kind], name + ".py"))


def compute_metrics(bench, kind, run, workload):
    out = {}
    for m in metric_entries(bench, kind, workload):
        value = metric_module(kind, m["name"]).compute(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def device_report(devs, run):
    d0 = devs[0]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    run.memory_peak_bytes = max(peaks)
    dev = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs),
           "memory_peak_bytes": run.memory_peak_bytes}
    if run.trace is not None:
        dev["busy_s"] = run.trace["busy_s"]
        dev["window_s"] = run.trace["window_s"]
    return dev


# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the builder's: any backend, every scale "
                         f"{REHEARSAL_SF}, and the line says correct: false")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    wl = load_json(f"workloads/{args.workload}.json", "workload")
    cfg = load_json(f"configs/{wl['config']}.json", "configuration")
    ref = load_module(wl.get("reference", "reference.py"))
    reduce = load_module("trace_reduce.py")
    classes = wl["classes"]
    for c in classes:
        if c["check"] not in ref.STREAMED and c["check"] not in ref.POINT:
            raise BenchError(f"unknown class check: {c['check']}")
        if c["binds"] not in ref.BINDS:
            raise BenchError(f"unknown binds rule: {c['binds']}")
    for kind in FOLDERS:    # a metric without its file fails here, by name
        for m in metric_entries(bench, kind, args.workload):
            metric_module(kind, m["name"])

    import jax
    import numpy as np

    devs = jax.devices()
    if devs[0].platform != "tpu" and not args.rehearse:
        raise BenchError(f"no TPU (jax found {devs[0].platform!r})")
    if len(devs) < cfg["chips"]:
        raise BenchError(f"the cell needs {cfg['chips']} chips, jax found {len(devs)}")
    devs = devs[:cfg["chips"]] if args.rehearse else devs
    sf = REHEARSAL_SF if args.rehearse else cfg["scale_factor"]
    peaks = load_json("peaks.json", "table of peaks")
    if devs[0].device_kind in peaks:
        peaks = peaks[devs[0].device_kind]
    elif args.rehearse:     # any row: the arithmetic runs, the number means nothing
        peaks = next(iter(peaks.values()))
    else:
        raise BenchError(f"no peaks for device kind {devs[0].device_kind!r}")

    from presto_tpu.exec import compile_cache as CC

    info(workload=args.workload, config=wl["config"], sf=sf, seed=args.seed,
         compile_cache_dir=CC.resolve_cache_dir(), rehearse=args.rehearse)

    # binds and expected answers on a thread beside the device's set-up
    rng = np.random.default_rng(args.seed)
    binds = {c["name"]: ref.BINDS[c["binds"]](sf, rng, c) for c in classes}
    expected_bg = Background(expected_answers, ref, wl["config"], sf, classes,
                             binds)
    session, srv = build_server(cfg, sf)
    try:
        texts = {c["name"]: class_texts(c, binds[c["name"]], srv.uri, ref)
                 for c in classes}
        warm_up(session, srv, classes, texts, args.seed, wl)
        t_wait = time.perf_counter()
        expected, computed = expected_bg.result()
        info(reference_s=expected_bg.seconds, reference_computed=computed,
             waited_for_reference_s=time.perf_counter() - t_wait)

        run = Run(workload=wl, config=cfg, sf=sf, seconds=args.seconds,
                  setup_seconds=0.0, peaks=peaks)
        run.bytes_by_class = {
            c["name"]: ref.bytes_read(sf, c.get("columns_read", {}))
            for c in classes}
        trace_dir = os.path.join(CACHE, "trace", args.workload)
        annotate = no_span
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # it would hook every Python call
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)

            def annotate(cls):
                return jax.profiler.TraceAnnotation(reduce.ANNOTATION + cls)

        run.counters_before = read_counters(srv)
        run.setup_seconds = time.perf_counter() - T_START
        threads, per_client, t_open, wall_open = closed_loops(
            srv.uri, classes, texts, args.seed, wl["clients"], args.seconds,
            annotate)
        if args.trace:
            time.sleep(min(wl["trace_seconds"], args.seconds))
            run.counters_slice_end = read_counters(srv)
            jax.profiler.stop_trace()
            run.trace_end_wall = time.time()
        run.t_open = t_open
        run.queries = join_all(threads, per_client)
        run.counters_after = read_counters(srv)
        history = session.history_snapshot()
    finally:
        srv.stop()

    for q in run.queries:
        q.ok = q.error is None and ref.rows_equal(
            q.rows, expected[q.cls][q.text], cfg["guarantees"]["float_rel"])
    pair_stats(run.queries, texts, history, wall_open)
    failed = [q for q in run.queries if not q.ok]
    for q in failed[:5]:
        info(failed=q.cls, text=texts[q.cls][q.text][:80], error=q.error,
             got=q.rows and q.rows[:2], want=expected[q.cls][q.text][:2])

    breakdown = None
    if args.trace:
        run.trace, layout = reduce.reduce_trace(
            trace_dir, **(REHEARSAL_TRACE if args.rehearse else {}))
        if run.trace is None:
            info(trace_layout=layout)
            raise BenchError("the trace holds no device operation or no query span")
        breakdown = {"device_ops": run.trace["device_ops"],
                     "idle_gaps": run.trace["idle_gaps"]}
        info(trace_window_s=run.trace["window_s"], busy_s=run.trace["busy_s"],
             queries_in_slice=run.trace["queries_by_class"],
             longest_gaps=run.trace["longest_gaps"])

    device = device_report(devs, run)
    by_class = {}
    for q in run.good():
        by_class.setdefault(q.cls, []).append(q.ms)
    server_ms = run.class_medians(lambda q: q.stats.total_ns / 1e6, run.with_stats())
    info(seconds=args.seconds, per_class={
        c: {"n": len(v), "median_ms": statistics.median(v), "min_ms": min(v),
            "max_ms": max(v), "server_median_ms": server_ms.get(c)}
        for c, v in by_class.items()},
        with_stats=len(run.with_stats()),
        window_s_by_client=[max(q.t1 for q in qs) - t_open for qs in per_client if qs],
        compiles_in_window=run.counter_delta(COMPILES))
    metrics = compute_metrics(bench, "per_layer" if args.trace else "end_to_end",
                              run, args.workload)
    line = {"correct": not failed and bool(run.queries) and not args.rehearse,
            "attempted": len(run.queries), "failed": len(failed),
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchError as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        sys.exit(2)
