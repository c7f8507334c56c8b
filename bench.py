"""Benchmark driver entry point.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Metric: TPC-H rows/sec/chip across the bench query set, measured on the
real device with 1 prewarm + BENCH_RUNS timed runs (methodology trimmed
from the reference's benchto 2+6 runs,
presto-benchto-benchmarks/.../tpch.yaml).

Baselines (VERDICT r1 asked for an honest one):
- vs_baseline / vs_numpy: wall-clock speedup vs hand-tuned vectorized
  numpy pipelines for the same queries over the same arrays
  (bench_baselines.py) — a DuckDB-class single-core columnar yardstick.
- vs_sqlite: the old oracle ratio (single-threaded row store; flattering,
  kept for continuity with the first round's records).

Extra keys: per_query_ms (warm best per query), compile_economics
(per-query cold_ms/warm_ms + compiles/compile_ms/cache_hits/ahead_hits
from exec/compile_cache.py; warm_compiles > 0 flags a warm-path
retrace), agg_economics (per-query plan/agg_strategy.py block:
strategy chosen, observed partial reduction ratio, bypass flips /
re-enables), sf, note, scale_configs
(ALWAYS the committed records from BENCH_SCALE_PROGRESS.json; a default
run never re-measures them — re-measuring is BENCH_SCALE=1 opt-in and
runs after the line prints, under a budget sized to finish before the
driver's 3600s kill).
Env knobs: BENCH_SF, BENCH_QUERIES, BENCH_RUNS, BENCH_F32,
BENCH_SCALE (=1 re-measures scale configs post-emit), BENCH_SF1_TESTS,
BENCH_TIME_BUDGET, BENCH_TOTAL_BUDGET (default 3300s).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SF = float(os.environ.get("BENCH_SF", "1.0"))
QUERY_IDS = [int(x) for x in os.environ.get("BENCH_QUERIES", "1,3,6,18").split(",")]
RUNS = int(os.environ.get("BENCH_RUNS", "3"))

# Whole-PROCESS wall-clock budget.  Four rounds of rc=124 proved the
# driver kills the process at 3600s before it exits on its own — the
# old 3600s in-process budget (and a SIGALRM set at remaining+60) could
# only ever fire AFTER the external kill.  The budget now sits 300s
# under the driver's limit and the backstop fires exactly at the
# budget, so the process always reaches its own clean exit first.
# Everything after the emitted JSON line is best-effort and gated on
# _remaining().
_T0 = time.perf_counter()
TOTAL_BUDGET_S = float(os.environ.get("BENCH_TOTAL_BUDGET", "3300"))


def _remaining():
    return TOTAL_BUDGET_S - (time.perf_counter() - _T0)


def _install_deadline_backstop():
    import signal

    def _bail(signum, frame):
        print("bench: total budget exhausted mid-config; progress is "
              "checkpointed, exiting 0", file=sys.stderr)
        sys.stderr.flush()
        os._exit(0)  # the JSON line is long since out; exit CLEAN

    try:
        signal.signal(signal.SIGALRM, _bail)
        # at the budget, NOT beyond it: the old +60s grace pushed the
        # backstop past the driver's own kill, which is how four rounds
        # of rc=124 shipped
        signal.alarm(max(int(_remaining()), 1))
    except (ValueError, OSError, AttributeError):
        pass  # non-main thread / platform without SIGALRM


def main():
    import presto_tpu
    from presto_tpu.catalog import tpch_catalog
    from presto_tpu.connectors import tpch as tpch_gen
    from tests.tpch_queries import QUERIES

    cat = tpch_catalog(SF, cache_dir="/tmp/presto_tpu_cache")
    session = presto_tpu.connect(cat)

    lineitem_rows = tpch_gen.row_count("lineitem", SF)

    # DOUBLE math in f32 on device (f64 merges); the TPU emulates f64 in
    # software, and the tolerance loss (~1e-7 rel) is far inside the
    # result-checksum tolerance.  BENCH_F32=0 restores strict f64.
    if os.environ.get("BENCH_F32", "1") != "0":
        session.set("float32_compute", True)

    engine_times = {}
    sort_econ = {}
    compile_econ = {}
    df_econ = {}
    ff_econ = {}
    agg_econ = {}
    for qid in QUERY_IDS:
        t0 = time.perf_counter()
        r = session.sql(QUERIES[qid])  # prewarm == the COLD run
        cold = time.perf_counter() - t0
        if r.stats is not None:  # round-8 sort economics per query
            sort_econ[str(qid)] = {
                "taken": r.stats.sorts_taken,
                "elided": r.stats.sorts_elided,
                "memo_hits": r.stats.sort_memo_hits}
        if r.stats is not None:  # round-12 fragment-fusion economics
            # (single-node runs report zeros; the fused-vs-cut numbers
            # live in the committed MULTICHIP record below)
            ff_econ[str(qid)] = {
                "fragments_fused": r.stats.fragments_fused,
                "exchange_bytes_host": r.stats.exchange_bytes_host,
                "exchange_bytes_collective":
                    r.stats.exchange_bytes_collective}
        if r.stats is not None:  # round-17 adaptive-agg economics
            agg_econ[str(qid)] = {
                "strategy": dict(r.stats.agg_strategy) or None,
                "ratio": round(r.stats.partial_agg_ratio, 3),
                "bypass_flips": r.stats.partial_aggs_bypassed,
                "reenabled": r.stats.partial_aggs_reenabled}
        if r.stats is not None:  # round-10 dynamic-filter economics
            df_econ[str(qid)] = {
                "produced": r.stats.df_filters_produced,
                "applied": r.stats.df_filters_applied,
                "declined": r.stats.df_filters_declined,
                "rows_pruned": r.stats.df_rows_pruned,
                "chunks_pruned": r.stats.df_chunks_pruned,
                "splits_pruned": r.stats.df_splits_pruned,
                "wait_ms": round(r.stats.df_wait_ms, 1)}
        best = float("inf")
        warm_compiles = 0
        for _ in range(RUNS):
            t0 = time.perf_counter()
            rw = session.sql(QUERIES[qid])
            best = min(best, time.perf_counter() - t0)
            if rw.stats is not None:
                warm_compiles += rw.stats.compiles
        engine_times[qid] = best
        if r.stats is not None:  # round-9 compile economics per query
            compile_econ[str(qid)] = {
                "cold_ms": round(cold * 1000, 1),
                "warm_ms": round(best * 1000, 1),
                "compiles": r.stats.compiles,
                "compile_ms": round(r.stats.compile_ms, 1),
                "cache_hits": r.stats.compile_cache_hits,
                "ahead_hits": r.stats.compile_ahead_hits,
                # any nonzero here is a warm-path retrace — a regression
                "warm_compiles": warm_compiles}

    total_engine = sum(engine_times.values())
    # rows processed: dominated by lineitem scans per query
    rows_per_sec = lineitem_rows * len(QUERY_IDS) / total_engine

    vs_numpy = numpy_speedup(cat, engine_times)
    vs_sqlite = sqlite_speedup(engine_times)
    gate = perf_gate(engine_times)
    recovery_ms = recovery_bench()
    serve = serve_gate_summary()
    obs_overhead = observability_overhead(session, engine_times)

    # ONE line on stdout, emitted IMMEDIATELY after the SF1 measurements
    # (round-2 lesson: the scale configs below can outlive the caller's
    # process timeout; holding the line until after them lost the whole
    # round's perf record).  scale_configs in the line are the committed
    # records from BENCH_SCALE_PROGRESS.json; re-measuring them is
    # BENCH_SCALE=1 opt-in, after the line prints.
    print(json.dumps({
        "metric": f"tpch_sf{SF:g}_q{'_'.join(map(str, QUERY_IDS))}_rows_per_sec_per_chip",
        "value": round(rows_per_sec, 1),
        "unit": "rows/sec/chip",
        "vs_baseline": vs_numpy if vs_numpy is not None else vs_sqlite,
        "vs_numpy": vs_numpy,
        "vs_sqlite": vs_sqlite,
        "per_query_ms": {str(q): round(t * 1000, 1)
                         for q, t in engine_times.items()},
        "perf_gate": gate,
        "recovery_ms": recovery_ms,
        "serve": serve,
        "write": write_gate_summary(),
        "spill": spill_gate_summary(),
        "observability_overhead": obs_overhead,
        "sort_economics": sort_econ or None,
        "compile_economics": compile_econ or None,
        "dynamic_filter": df_econ or None,
        "fragment_fusion": ff_econ or None,
        "agg_economics": agg_econ or None,
        "multichip": multichip_summary(),
        "sf": SF,
        "scale_configs": {k: v for k, v in (load_scale_progress() or {}).items()
                          if k != "sf1_test_tier"} or None,
        "sf1_tests": (load_scale_progress() or {}).get("sf1_test_tier"),
        "note": ("vs_numpy = tuned vectorized numpy single-core; "
                 "vs_sqlite = row-store oracle (flattering); "
                 "warm times include one launch + host sync per query; "
                 "scale_configs = BASELINE SF10/SF100 wall-clock on "
                 "one chip (device-side generation + chunked "
                 "execution), committed records (each entry carries "
                 "asof; BENCH_SCALE=1 re-measures post-emit)"
                 + ("" if vs_numpy is not None
                    else "; NUMPY BASELINE FAILED - vs_baseline fell "
                         "back to sqlite")), }, ), flush=True)

    # Post-emit phases (best-effort; the record above is already out).
    # scale_configs in the emitted line always come from the COMMITTED
    # progress file; re-MEASURING them is opt-in (BENCH_SCALE=1) because
    # the re-measure phase is what overran the driver's timeout four
    # rounds running — a default bench run now does SF1 + the SF1 test
    # tier and exits 0 well inside the external limit.
    _install_deadline_backstop()
    if os.environ.get("BENCH_SCALE", "0") == "1":
        scale_configs(session_factory=_scale_session)
    if os.environ.get("BENCH_SF1_TESTS", "1") != "0" and _remaining() > 600:
        run_sf1_tier()


SCALE_PROGRESS_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BENCH_SCALE_PROGRESS.json")


# warm per-query times include a fixed launch + host-sync round trip
# (its size on a directly attached chip: not measured on this tree);
# the gate models that floor explicitly so round-trip-dominated queries
# (Q1/Q6) are held to the floor, not to 1.2x of a number that is mostly
# overhead
GATE_RTT_FLOOR_MS = 100.0
GATE_RATIO = 1.2


def perf_gate(engine_times):
    """Per-query regression gate vs committed reference warm times
    (tests/perf_reference.json): FAIL when any query exceeds
    RTT_floor + 1.2x its reference COMPUTE time (ref - floor), reported
    in the emitted line so a regressed round is visibly red.  The old
    1.5x-of-total gate let a 40% Q1 regression "pass" (round-5 VERDICT
    weak #3) because 1.5x of an RTT-dominated reference hides ~70ms of
    real compute regression.  Only meaningful on the real chip at SF1."""
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "tests", "perf_reference.json")) as f:
            ref = json.load(f).get("tpu_sf1_ms", {})
    except (OSError, ValueError):
        return None
    if SF != 1.0 or not ref:
        return None
    import jax

    if jax.devices()[0].platform == "cpu":
        return None  # reference times are for the real chip
    bad = {}
    for qid, t in engine_times.items():
        r = ref.get(str(qid))
        if r is None:
            continue
        limit = GATE_RTT_FLOOR_MS + GATE_RATIO * max(r - GATE_RTT_FLOOR_MS,
                                                     0.0)
        if t * 1000 > limit:
            bad[str(qid)] = (f"{t * 1000:.0f}ms > limit {limit:.0f}ms "
                             f"(ref {r:.0f}ms, {GATE_RATIO}x over "
                             f"{GATE_RTT_FLOOR_MS:.0f}ms RTT floor)")
    return ("FAIL: " + "; ".join(f"q{k} {v}" for k, v in bad.items())) \
        if bad else "pass"


# observability-overhead gate (ISSUE 9): tracing + metrics ON (the
# default) must cost <= 2% warm wall vs OFF on the SF1 gate queries,
# with a small per-query noise floor so RTT/timer jitter on sub-100ms
# queries can't flip the verdict
OBS_GATE_RATIO = 1.02
OBS_NOISE_FLOOR_MS_PER_QUERY = 2.0


def observability_overhead(session, engine_times):
    """A/B the observability layer: `engine_times` already holds the
    warm best-of runs with trace_detail=basic (the default — spans
    recorded, metrics folded at completion); re-measure with
    trace_detail=off and gate the ratio.  The off-run pays one
    unmeasured warm-up per query first, because flipping the property
    re-keys the program caches (the property map rides every cache
    key) and a cold compile would poison the comparison."""
    from tests.tpch_queries import QUERIES

    off = {}
    try:
        session.set("trace_detail", "off")
        for qid in QUERY_IDS:
            session.sql(QUERIES[qid])  # warm the off-keyed executables
            best = float("inf")
            for _ in range(RUNS):
                t0 = time.perf_counter()
                session.sql(QUERIES[qid])
                best = min(best, time.perf_counter() - t0)
            off[qid] = best
    except Exception as e:  # noqa: BLE001 — the A/B must not kill the record
        return {"gate": f"SKIP: {type(e).__name__}: {e}"}
    finally:
        session.set("trace_detail", "basic")
    on_ms = sum(engine_times.values()) * 1000
    off_ms = sum(off.values()) * 1000
    limit = off_ms * OBS_GATE_RATIO \
        + OBS_NOISE_FLOOR_MS_PER_QUERY * len(QUERY_IDS)
    overhead_pct = (on_ms / off_ms - 1) * 100 if off_ms else 0.0
    return {
        "on_ms": round(on_ms, 1), "off_ms": round(off_ms, 1),
        "overhead_pct": round(overhead_pct, 2),
        "per_query_off_ms": {str(q): round(t * 1000, 1)
                             for q, t in off.items()},
        "gate": "pass" if on_ms <= limit else (
            f"FAIL: tracing+metrics on {on_ms:.0f}ms > limit "
            f"{limit:.0f}ms ({OBS_GATE_RATIO}x of off {off_ms:.0f}ms "
            f"+ {OBS_NOISE_FLOOR_MS_PER_QUERY:g}ms/query floor)"),
    }


WRITE_RECORD_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "WRITE_r01.json")


def load_write_record():
    try:
        with open(WRITE_RECORD_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def write_gate_summary():
    """The write-path benchmark as registered in the default bench
    artifact: reports the COMMITTED WRITE_r01.json record (bench.py
    --write re-measures it), so a default run exits 0 on committed
    records and a regressed write round is visibly red in the record's
    own gate."""
    rec = load_write_record()
    if rec is None:
        return None
    return {"ctas_rows_per_sec": rec.get("ctas_rows_per_sec"),
            "layout_ctas_rows_per_sec": rec.get("layout_ctas_rows_per_sec"),
            "readback_speedup": rec.get("readback_speedup"),
            "stripes_pruned": rec.get("stripes_pruned"),
            "gate": rec.get("gate"), "asof": rec.get("asof")}


WRITE_GATE_THROUGHPUT_RATIO = 0.5  # FAIL below this share of committed
WRITE_GATE_SPEEDUP_RATIO = 0.7     # FAIL below this share of committed


def _write_gate(record, committed):
    if committed is None \
            or committed.get("platform") != record["platform"] \
            or committed.get("sf") != record["sf"]:
        return "pass (no comparable committed record)"
    prev = committed.get("ctas_rows_per_sec")
    if prev and record["ctas_rows_per_sec"] < \
            WRITE_GATE_THROUGHPUT_RATIO * prev:
        return (f"FAIL: ctas {record['ctas_rows_per_sec']:.0f} rows/s < "
                f"{WRITE_GATE_THROUGHPUT_RATIO}x committed {prev:.0f}")
    prev_sp = committed.get("readback_speedup")
    if prev_sp and record["readback_speedup"] < \
            WRITE_GATE_SPEEDUP_RATIO * prev_sp:
        return (f"FAIL: read-back speedup {record['readback_speedup']} < "
                f"{WRITE_GATE_SPEEDUP_RATIO}x committed {prev_sp}")
    if not record.get("checksums_equal", True):
        return "FAIL: bucketed CTAS checksum != flat CTAS checksum"
    return "pass"


def write_bench():
    """Write-path benchmark (`bench.py --write`): CTAS rows/sec through
    the PageSink pipeline (flat vs bucketed+sorted layout, exec/writer.py)
    and the read-back payoff — a selective sort-key query against the
    bucketed+sorted rollup vs the flat copy (zone-map stripe pruning +
    ordering-aware grouping on engine-written tables, docs/WRITES.md).
    Emits WRITE_r01.json with a regression gate vs the committed record."""
    import shutil
    import tempfile

    import jax

    import presto_tpu
    from presto_tpu.catalog import tpch_catalog

    sf = float(os.environ.get("BENCH_WRITE_SF", "0.01"))
    runs = max(RUNS, 3)
    session = presto_tpu.connect(
        tpch_catalog(sf, cache_dir="/tmp/presto_tpu_cache"))
    if os.environ.get("BENCH_F32", "1") != "0":
        session.set("float32_compute", True)
    root = tempfile.mkdtemp(prefix="presto_tpu_write_bench_")
    q = ("SELECT l_orderkey, l_suppkey, l_extendedprice, l_quantity "
         "FROM lineitem")
    try:
        session.sql(q + " LIMIT 1")  # prewarm the scan

        def ctas(name, props, drop_first=True):
            if drop_first:
                session.sql(f"DROP TABLE IF EXISTS {name}")
                shutil.rmtree(os.path.join(root, name), ignore_errors=True)
            t0 = time.perf_counter()
            r = session.sql(
                f"CREATE TABLE {name} WITH (connector='localfile', "
                f"directory='{root}/{name}'{props}) AS {q}")
            return time.perf_counter() - t0, r

        best_flat = best_layout = float("inf")
        rows = 0
        for _ in range(runs):
            dt, r = ctas("wflat", "")
            best_flat = min(best_flat, dt)
            rows = r.rows[0][0]
        for _ in range(runs):
            dt, r = ctas(
                "wroll",
                ", bucketed_by=ARRAY['l_orderkey'], bucket_count=8, "
                "sorted_by=ARRAY['l_orderkey']")
            best_layout = min(best_layout, dt)

        hi = session.sql("SELECT max(l_orderkey) FROM wflat").rows[0][0]
        lo, span = int(hi * 0.4), max(int(hi * 0.01), 1)
        probe = ("SELECT count(*), sum(l_extendedprice) FROM {t} WHERE "
                 f"l_orderkey BETWEEN {lo} AND {lo + span}")
        checks = {}
        best_rb = {}
        for t in ("wflat", "wroll"):
            session.sql(probe.format(t=t))  # prewarm/compile
            best = float("inf")
            for _ in range(runs):
                t0 = time.perf_counter()
                checks[t] = session.sql(probe.format(t=t)).rows
                best = min(best, time.perf_counter() - t0)
            best_rb[t] = best
        troll = session.catalog.get("wroll")
        scan_doms = None
        try:
            from presto_tpu.exec.executor import (_collect_tablescans,
                                                  plan_statement)
            from presto_tpu.sql.parser import parse as _parse

            plan = plan_statement(session, _parse(probe.format(t="wroll")))
            scans = []
            _collect_tablescans(plan.root, scans)
            scan_doms = getattr(scans[0], "scan_domains", None)
        except Exception:
            pass
        kept, total = troll.pruned_stats(scan_doms) if scan_doms \
            else (None, None)
        eq = (checks["wflat"][0][0] == checks["wroll"][0][0]
              and abs(checks["wflat"][0][1] - checks["wroll"][0][1])
              <= 1e-6 * max(abs(checks["wflat"][0][1]), 1.0))
        record = {
            "metric": "localfile_ctas_rows_per_sec",
            "ctas_rows_per_sec": round(rows / best_flat, 1),
            "layout_ctas_rows_per_sec": round(rows / best_layout, 1),
            "rows": rows,
            "readback_flat_ms": round(best_rb["wflat"] * 1000, 2),
            "readback_layout_ms": round(best_rb["wroll"] * 1000, 2),
            "readback_speedup": round(best_rb["wflat"]
                                      / max(best_rb["wroll"], 1e-9), 2),
            "stripes_pruned": (None if kept is None
                               else f"{total - kept}/{total}"),
            "checksums_equal": bool(eq),
            "sf": sf,
            "platform": jax.devices()[0].platform,
            "asof": time.strftime("%Y-%m-%d"),
            "note": ("flat vs bucketed(range,8)+sorted CTAS of the same "
                     "4-column lineitem query; read-back = selective "
                     "1% sort-key range probe, warm best-of-"
                     f"{runs}; layout CTAS pays the sort/bucket split "
                     "at write time, the read-back pays it BACK via "
                     "zone-map stripe pruning"),
        }
        record["gate"] = _write_gate(record, load_write_record())
        with open(WRITE_RECORD_PATH, "w") as f:
            json.dump(record, f, indent=1)
        print(json.dumps(record), flush=True)
        sys.exit(0 if not str(record["gate"]).startswith("FAIL") else 1)
    finally:
        for t in ("wflat", "wroll"):
            try:
                session.sql(f"DROP TABLE IF EXISTS {t}")
            except Exception:
                pass
        shutil.rmtree(root, ignore_errors=True)


SERVE_RECORD_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "SERVE_r02.json")
SERVE_R01_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "SERVE_r01.json")


def load_serve_record():
    try:
        with open(SERVE_RECORD_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def load_serve_r01():
    """The pre-coalescing round-11 record: the baseline the SERVE_r02
    coalescing speedup claims are measured against."""
    try:
        with open(SERVE_R01_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def serve_gate_summary():
    """The serving QPS gate as registered in the default bench artifact:
    reports the COMMITTED SERVE_r02.json record (bench.py --serve
    re-measures it) so a default run exits 0 on committed records and a
    regressed serve round is visibly red in the record's own gate."""
    rec = load_serve_record()
    if rec is None:
        return None
    out = {"qps_per_chip": rec.get("qps_per_chip"),
           "p50_ms": rec.get("p50_ms"), "p95_ms": rec.get("p95_ms"),
           "p99_ms": rec.get("p99_ms"), "gate": rec.get("gate"),
           "coalesce_burst": rec.get("coalesce_burst"),
           "asof": rec.get("asof")}
    # round-19 coordinator scale-out: the committed SERVE_r03 fleet
    # record rides the default line next to the r02 serving record
    r03 = load_serve_r03()
    if r03 is not None:
        out["fleet"] = {
            "coordinators": (r03.get("fleet") or {}).get("coordinators"),
            "qps_ratio": (r03.get("scaling") or {}).get("qps_ratio"),
            "p99_ratio": (r03.get("scaling") or {}).get("p99_ratio"),
            "burst_coalesce_batches": ((r03.get("fleet") or {})
                                       .get("burst") or {})
            .get("coalesce_batches"),
            "cores": r03.get("cores"),
            "gate": r03.get("gate"),
            "asof": r03.get("asof")}
    # round-20 incremental MVs: the committed SERVE_r04 dashboard
    # record — p99 flat across refresh cut-overs, routed >= 5x faster
    # than recomputing the view
    r04 = load_serve_r04()
    if r04 is not None:
        out["mv_dashboard"] = {
            "p99_flat_ratio": r04.get("p99_flat_ratio"),
            "routed_speedup": r04.get("routed_speedup"),
            "wrong_results": r04.get("wrong_results"),
            "refresh_modes": r04.get("refresh_modes"),
            "gate": r04.get("gate"),
            "asof": r04.get("asof")}
    return out


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return None
    i = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[i]


def serve_bench():
    """Closed-loop concurrency benchmark (`bench.py --serve`): N client
    sessions issue a mixed q1 / q6 / point-lookup / prepared-EXECUTE
    workload over the HTTP protocol against an in-process server with
    admission control — the serving tier under real contention
    (docs/SERVING.md).  Closed loop: each session issues its next query
    when the previous one completes, so offered load tracks capacity.

    Round-16 (query coalescing): the point-lookup class is PREPARED
    (`point_exec`, an EXECUTE of one shared signature — the
    coalescing-heavy class; concurrent binds batch into one vmap
    launch), with a small `point_adhoc` class preserving the round-11
    ad-hoc text measurement (its per-literal compile bill was the old
    `point` class's 151ms p50).  The `approx_dashboard` class is a
    prepared APPROX_DISTINCT + APPROX_PERCENTILE rollup issued
    binds-only (the NDV-dashboard refresh shape), gated on its own
    p99 against the committed record.  A second phase runs a point_exec-only
    burst with coalescing OFF then ON (same box, same isolation) and
    records the launch-amortization speedup plus the comparison against
    SERVE_r01's pre-coalescing point+execute classes — the ROADMAP
    gate's QPS/chip claim.  Emits everything to SERVE_r02.json with a
    regression gate vs the committed record; compiles are prewarmed OUT
    of the timed loops (cold-start economics are the main bench's
    compile_economics)."""
    import threading

    import jax

    import presto_tpu
    from presto_tpu.catalog import tpch_catalog
    from presto_tpu.client import StatementClient
    from presto_tpu.server import PrestoTpuServer
    from presto_tpu.server.resource_groups import ResourceGroupManager
    from tests.tpch_queries import QUERIES

    sf = float(os.environ.get("BENCH_SERVE_SF", "0.01"))
    n_sessions = int(os.environ.get("BENCH_SERVE_SESSIONS", "8"))
    per_session = int(os.environ.get("BENCH_SERVE_QUERIES", "25"))
    concurrency = int(os.environ.get("BENCH_SERVE_CONCURRENCY", "4"))
    burst_per_session = int(os.environ.get("BENCH_SERVE_BURST", "40"))

    session = presto_tpu.connect(
        tpch_catalog(sf, cache_dir="/tmp/presto_tpu_cache"))
    if os.environ.get("BENCH_F32", "1") != "0":
        session.set("float32_compute", True)
    rgm = ResourceGroupManager()
    rgm.add_group("global.serve", hard_concurrency_limit=concurrency,
                  max_queued=10_000)
    rgm.add_selector("global.serve")
    srv = PrestoTpuServer(session, max_concurrent=concurrency,
                          resource_groups=rgm).start()

    max_key = max(int(6_000_000 * sf * 4), 8)

    def point_sql(seed):
        k = 1 + (seed * 7919) % max_key
        return (f"SELECT count(*) c, sum(l_extendedprice) s "
                f"FROM lineitem WHERE l_orderkey = {k}")

    def run_one(sql):
        rows = list(StatementClient(srv.uri, sql).rows())
        return rows

    run_one("PREPARE serve_point FROM SELECT count(*) c, "
            "sum(l_extendedprice) s FROM lineitem WHERE l_orderkey = ?")
    run_one("PREPARE serve_dash FROM SELECT l_returnflag rf, "
            "approx_distinct(l_partkey) parts, "
            "approx_percentile(l_extendedprice, 0.5) med "
            "FROM lineitem WHERE l_orderkey <= ? GROUP BY l_returnflag")

    def exec_sql(seed):
        return f"EXECUTE serve_point USING {1 + (seed * 4547) % max_key}"

    def dash_sql(seed):
        return f"EXECUTE serve_dash USING {1 + (seed * 2741) % max_key}"

    def pick(seed):
        r = seed % 8
        if r == 0:
            return "q1", QUERIES[1]
        if r in (1, 5):
            return "q6", QUERIES[6]
        if r == 2:
            # the preserved round-11 ad-hoc point variant: every
            # distinct literal is a distinct text — the per-literal
            # compile bill the prepared signature amortizes away
            return "point_adhoc", point_sql(seed)
        if r == 4:
            # sketch-aggregate dashboard rollup: one prepared
            # APPROX_DISTINCT + APPROX_PERCENTILE signature, binds-only
            # — the NDV-dashboard refresh an observability frontend
            # hammers; warm EXECUTEs must stay compile-free like
            # serve_point's
            return "approx_dashboard", dash_sql(seed)
        # the coalescing-heavy class: one prepared signature, binds-only
        return "point_exec", exec_sql(seed)

    # prewarm: one of each class so the timed loop measures serving,
    # not first-compile
    for cls, sql in (pick(0), pick(1), pick(2), pick(3), pick(4)):
        run_one(sql)

    lat = {"q1": [], "q6": [], "point_adhoc": [], "point_exec": [],
           "approx_dashboard": []}
    lat_lock = threading.Lock()
    failures = []
    depth_samples = []
    stop = threading.Event()

    def sampler():
        while not stop.is_set():
            try:
                depth_samples.append(sum(
                    g["queued"] for g in rgm.info() if g["name"] == "global"))
            except Exception:
                pass
            stop.wait(0.02)

    def client(sid):
        for i in range(per_session):
            cls, sql = pick(sid * per_session + i + 17)
            t0 = time.perf_counter()
            try:
                run_one(sql)
            except Exception as e:  # noqa: BLE001 — recorded, not raised
                failures.append(f"{cls}: {type(e).__name__}: {e}")
                continue
            dt = (time.perf_counter() - t0) * 1000.0
            with lat_lock:
                lat[cls].append(dt)

    samp = threading.Thread(target=sampler, daemon=True)
    samp.start()
    t_wall = time.perf_counter()
    threads = [threading.Thread(target=client, args=(sid,))
               for sid in range(n_sessions)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_wall
    stop.set()
    samp.join(timeout=2)

    import urllib.request

    info = json.loads(urllib.request.urlopen(
        f"{srv.uri}/v1/info", timeout=30).read())

    # ---- coalesce burst: the point_exec class in isolation, OFF vs ON
    # (distinct key offsets per leg keep the result cache out of the
    # measurement; the serving history's coalesce counters attribute
    # the ON leg's batching)
    def burst(leg_tag, offset):
        errs = []

        def bclient(sid, n, base):
            for i in range(n):
                try:
                    run_one(exec_sql(base + sid * n + i))
                except Exception as e:  # noqa: BLE001
                    errs.append(f"{leg_tag}: {type(e).__name__}: {e}")

        def wave(n, base):
            ths = [threading.Thread(target=bclient, args=(sid, n, base))
                   for sid in range(n_sessions)]
            for t in ths:
                t.start()
            for t in ths:
                t.join()

        # untimed prewarm: a concurrent mini-wave builds the leg's
        # program key AND (on the coalescing leg) the pow2 batch-size
        # buckets — compiles are out of the timed loop in every leg,
        # matching the mixed phase's prewarm policy
        wave(4, offset + 500_000)
        t0 = time.perf_counter()
        wave(burst_per_session, offset)
        w = time.perf_counter() - t0
        failures.extend(errs)
        return n_sessions * burst_per_session / w if w else 0.0

    session.set("query_coalescing", "off")
    burst_qps_off = burst("burst_off", 1_000_003)
    session.set("query_coalescing", "auto")
    # a batch can never exceed the admission concurrency; waiting the
    # window for more is pure latency, so the burst dispatches as soon
    # as every in-flight slot has joined
    session.set("coalesce_max_batch", concurrency)
    co_before = (srv.serving.coalescer_stats() or {})
    burst_qps_on = burst("burst_on", 2_000_003)
    co_after = (srv.serving.coalescer_stats() or {})
    session.set("coalesce_max_batch", 16)

    # prepared + coalescing economics summed over the run's history
    binds = hits = fallbacks = 0
    co_sizes = []
    for st in session.history_snapshot():
        binds += getattr(st, "prepared_binds", 0)
        hits += getattr(st, "prepared_plan_hits", 0)
        fallbacks += getattr(st, "prepared_fallbacks", 0)
        if getattr(st, "coalesced_batch_size", 0) > 1:
            co_sizes.append(st.coalesced_batch_size)
    srv.stop()

    all_lat = sorted(x for v in lat.values() for x in v)
    total = len(all_lat)
    chips = 1 if jax.devices()[0].platform == "cpu" else len(jax.devices())

    # SERVE_r01 comparison: the pre-coalescing record's point (ad-hoc)
    # + execute (prepared) classes, as per-class QPS derived from its
    # committed mix (2/8 point + 3/8 execute of `queries` over wall_s)
    r01 = load_serve_r01()
    vs_r01 = None
    if r01 and r01.get("wall_s"):
        r01_pe_qps = (5 / 8) * r01["queries"] / r01["wall_s"] / chips
        vs_r01 = {
            "r01_point_execute_qps_per_chip": round(r01_pe_qps, 2),
            "r02_coalesced_burst_qps_per_chip": round(
                burst_qps_on / chips, 2),
            "speedup": round(burst_qps_on / chips / r01_pe_qps, 2)
            if r01_pe_qps else None,
        }

    record = {
        "metric": "serve_closed_loop_qps_per_chip",
        "platform": jax.devices()[0].platform,
        "sf": sf,
        "sessions": n_sessions,
        "per_session": per_session,
        "concurrency_limit": concurrency,
        "queries": total,
        "failures": len(failures),
        "failure_samples": failures[:5],
        "wall_s": round(wall, 2),
        "qps": round(total / wall, 2) if wall else None,
        "qps_per_chip": round(total / wall / chips, 2) if wall else None,
        "p50_ms": _percentile(all_lat, 0.50),
        "p95_ms": _percentile(all_lat, 0.95),
        "p99_ms": _percentile(all_lat, 0.99),
        "per_class_p50_ms": {k: round(_percentile(sorted(v), 0.50), 1)
                             for k, v in lat.items() if v},
        "per_class_p99_ms": {k: round(_percentile(sorted(v), 0.99), 1)
                             for k, v in lat.items() if v},
        "per_class_qps": {k: round(len(v) / wall, 1)
                          for k, v in lat.items() if v},
        "coalesce_burst": {
            "queries_per_leg": n_sessions * burst_per_session,
            "qps_off": round(burst_qps_off, 1),
            "qps_on": round(burst_qps_on, 1),
            "speedup_on_vs_off": round(burst_qps_on / burst_qps_off, 2)
            if burst_qps_off else None,
            "batches": (co_after.get("batches", 0)
                        - co_before.get("batches", 0)),
            "riders_coalesced": (co_after.get("ridersCoalesced", 0)
                                 - co_before.get("ridersCoalesced", 0)),
            "fallbacks": co_after.get("fallbacks", 0),
            "vs_serve_r01": vs_r01,
        },
        "coalescing": info["serving"].get("coalescing"),
        "mean_coalesced_batch": round(
            sum(co_sizes) / len(co_sizes), 2) if co_sizes else 0.0,
        "admission": {
            "peak_queue_depth": max(depth_samples, default=0),
            "mean_queue_depth": round(
                sum(depth_samples) / len(depth_samples), 2)
            if depth_samples else 0,
            "admitted": info["serving"]["admitted"],
            "shed": info["serving"]["shed"],
        },
        "caches": {
            "result_cache": info["serving"]["resultCache"],
            "prepared": {"binds": binds, "plan_hits": hits,
                         "fallbacks": fallbacks},
        },
        "box_sort_ms": _box_speed_ms(),
        "asof": _today(),
    }
    for k in ("p50_ms", "p95_ms", "p99_ms"):
        if record[k] is not None:
            record[k] = round(record[k], 1)
    record["gate"] = _serve_gate(record, load_serve_record())
    try:
        with open(SERVE_RECORD_PATH, "w") as f:
            json.dump(record, f, indent=1, sort_keys=True)
    except OSError:
        pass
    print(json.dumps(record), flush=True)
    return record


SERVE_GATE_QPS_RATIO = 0.75  # FAIL below this share of the committed QPS
SERVE_GATE_P99_RATIO = 1.5   # FAIL above this multiple of committed p99


def _box_speed_ms():
    """Engine-independent box fingerprint: best-of-3 numpy stable sort
    of a fixed 4M-int array.  Serve records carry it so the absolute
    qps/p99 gate legs can compare runs from differently-provisioned CI
    containers (observed: the same unmodified tree serves 173 qps on
    one 1-core box and 92 on another, red-gating itself) WITHOUT
    normalizing away engine regressions — numpy's sort time cannot see
    engine changes, so a real regression still trips the scaled bar."""
    import numpy as _np

    a = _np.random.default_rng(7).integers(0, 1 << 30, 1 << 22)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        _np.sort(a, kind="stable")
        best = min(best, time.perf_counter() - t0)
    return round(best * 1000, 2)


def _serve_gate(record, committed):
    """Regression gate vs the committed record, platform-matched (a CPU
    dev box must not gate against chip numbers or vice versa) and
    box-matched through the records' speed fingerprints."""
    if record["failures"]:
        return f"FAIL: {record['failures']} query failures"
    if committed is None \
            or committed.get("platform") != record["platform"] \
            or committed.get("sf") != record["sf"]:
        return "pass (no comparable committed record)"
    # the sketch-dashboard class must exist before any absolute leg: a
    # silently-vanished class would otherwise RAISE aggregate qps
    prev_dash = (committed.get("per_class_p99_ms")
                 or {}).get("approx_dashboard")
    cur_dash = (record.get("per_class_p99_ms")
                or {}).get("approx_dashboard")
    if prev_dash and not cur_dash:
        return "FAIL: approx_dashboard class ran no queries"
    # box-speed scale: committed box twice as fast -> fair qps bar
    # halves here (and the p99 bar doubles)
    prev_box = committed.get("box_sort_ms")
    cur_box = record.get("box_sort_ms")
    if not (prev_box and cur_box):
        return ("pass (committed record has no box fingerprint — "
                "absolute qps/p99 legs skipped)")
    scale = prev_box / cur_box
    prev_qps = committed.get("qps_per_chip")
    if prev_qps and record["qps_per_chip"] is not None \
            and record["qps_per_chip"] \
            < SERVE_GATE_QPS_RATIO * prev_qps * scale:
        return (f"FAIL: qps/chip {record['qps_per_chip']} < "
                f"{SERVE_GATE_QPS_RATIO}x committed {prev_qps} "
                f"(box-scaled x{round(scale, 2)})")
    prev_p99 = committed.get("p99_ms")
    if prev_p99 and record["p99_ms"] is not None \
            and record["p99_ms"] > SERVE_GATE_P99_RATIO * prev_p99 / scale:
        return (f"FAIL: p99 {record['p99_ms']}ms > "
                f"{SERVE_GATE_P99_RATIO}x committed {prev_p99}ms "
                f"(box-scaled x{round(1 / scale, 2)})")
    prev_burst = (committed.get("coalesce_burst") or {}).get("qps_on")
    cur_burst = (record.get("coalesce_burst") or {}).get("qps_on")
    if prev_burst and cur_burst \
            and cur_burst < SERVE_GATE_QPS_RATIO * prev_burst * scale:
        return (f"FAIL: coalesced burst qps {cur_burst} < "
                f"{SERVE_GATE_QPS_RATIO}x committed {prev_burst} "
                f"(box-scaled x{round(scale, 2)})")
    # the sketch-dashboard class gates on its own p99: a regression in
    # the prepared APPROX_DISTINCT path (e.g. warm EXECUTEs
    # recompiling) shows up here even when the cheap point classes
    # keep the aggregate percentiles green
    if prev_dash and cur_dash \
            and cur_dash > SERVE_GATE_P99_RATIO * prev_dash / scale:
        return (f"FAIL: approx_dashboard p99 {cur_dash}ms > "
                f"{SERVE_GATE_P99_RATIO}x committed {prev_dash}ms "
                f"(box-scaled x{round(1 / scale, 2)})")
    return "pass"


# ---------------------------------------------------------------------------
# round-20 MV-routed dashboard serving (`bench.py --serve [--mv]`): a
# dashboard query stream served from a materialized view while a
# background loop ingests batches and REFRESHes the view — the
# incremental-MV record (SERVE_r04.json)
# ---------------------------------------------------------------------------

SERVE_R04_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "SERVE_r04.json")

# churn p99 <= this multiple of steady p99 — enforced when the box has
# a second core for the co-located refresh compute (on a 1-core box the
# warm ~45ms delta refresh steals the ONLY serving core, a physical
# limit no engine dodges; the ratio is still measured and committed
# there, the same core-aware enforcement rule FLEET_GATE_QPS_SCALING
# uses)
MV_GATE_P99_FLAT = 1.3
MV_GATE_ROUTED_SPEEDUP = 5.0  # routed read vs full view recompute


def load_serve_r04():
    try:
        with open(SERVE_R04_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def mv_serve_bench():
    """MV-routed dashboard serving under refresh churn (`bench.py
    --serve --mv`; a plain `--serve` run appends this phase): N client
    sessions hammer the dashboard rollup over HTTP while an ingest
    loop appends batches to the source and REFRESHes the materialized
    view through the same protocol front door.  The result cache is
    OFF for this phase so every response is an actual routed read —
    otherwise the steady leg would be pure cache hits and the
    p99-flatness ratio would compare a memcpy against an MV scan.

    Every response is verified against the workload's arithmetic
    invariant: batch b appends `rep` rows of value b to EVERY group,
    so any consistent snapshot after k batches reads count = k*rep and
    sum = rep*k*(k-1)/2 in every group, and approx_distinct(v) ~= k.
    A response mixing files from two snapshots cannot satisfy it, so
    `wrong_results` counts cut-over isolation violations, not just
    transport errors.  A final routed-vs-recompute leg times the
    identical dashboard text with MV routing on (rollup read) and off
    (full view recompute over the grown source) and asserts the two
    row sets are IDENTICAL — exact aggregates and sketch estimates
    both — before recording the O(history) -> O(rollup) speedup.
    Emits SERVE_r04.json with the box-fingerprint-scaled gate."""
    import threading

    import jax
    import numpy as np

    import presto_tpu
    from presto_tpu.client import StatementClient
    from presto_tpu.server import PrestoTpuServer

    n_groups = int(os.environ.get("BENCH_MV_GROUPS", "64"))
    rep = int(os.environ.get("BENCH_MV_REP", "512"))
    seed_batches = int(os.environ.get("BENCH_MV_SEED", "6"))
    refreshes = int(os.environ.get("BENCH_MV_REFRESHES", "6"))
    n_sessions = int(os.environ.get("BENCH_MV_SESSIONS", "4"))
    steady_q = int(os.environ.get("BENCH_MV_STEADY_QUERIES", "30"))
    compare_iters = int(os.environ.get("BENCH_MV_COMPARE", "7"))

    session = presto_tpu.connect()
    session.set("result_cache_enabled", False)
    srv = PrestoTpuServer(session).start()
    session.sql("CREATE TABLE events (g BIGINT, v BIGINT)")
    tbl = session.catalog.get("events")

    def ingest(b):
        tbl.append({
            "g": np.repeat(np.arange(n_groups, dtype=np.int64), rep),
            "v": np.full(n_groups * rep, b, dtype=np.int64)})

    for b in range(seed_batches):
        ingest(b)

    dash = ("SELECT g, count(*) AS c, sum(v) AS s, "
            "approx_distinct(v) AS ad FROM events GROUP BY g")
    session.sql("CREATE MATERIALIZED VIEW mv_events "
                f"WITH (connector='memory') AS {dash}")
    # prewarm the delta-refresh path out of the timed loop (first
    # refresh compiles the delta query, ~600ms; warm refreshes ~45ms —
    # same prewarm policy as serve_bench's client classes)
    ingest(seed_batches)
    session.sql("REFRESH MATERIALIZED VIEW mv_events")
    warm_batches = seed_batches + 1

    def run_one(sql):
        return list(StatementClient(srv.uri, sql).rows())

    failures = []
    wrong = []
    unrouted = 0

    def check(rows):
        if len(rows) != n_groups \
                or {r[0] for r in rows} != set(range(n_groups)):
            return "incomplete group set"
        counts = {r[1] for r in rows}
        if len(counts) != 1:
            return f"torn counts across groups: {sorted(counts)[:4]}"
        c = counts.pop()
        if c % rep:
            return f"count {c} is not a whole number of batches"
        k = c // rep
        if not seed_batches <= k <= seed_batches + 1 + refreshes:
            return f"count {c} outside any published snapshot"
        want_s = rep * k * (k - 1) // 2
        for g_, _c, s_, ad_ in rows:
            if s_ != want_s:
                return f"group {g_}: sum {s_} != {want_s} at k={k}"
            if abs(ad_ - k) > max(1, 0.25 * k):
                return f"group {g_}: approx_distinct {ad_} far from {k}"
        return None

    # prewarm + route probe: the dashboard text must actually MV-route
    probe = session.sql(dash)
    if probe.stats.execution_mode != "mv_routed":
        unrouted += 1
    err = check(probe.rows)
    if err:
        wrong.append(f"probe: {err}")
    run_one(dash)

    lat_steady, lat_churn = [], []
    lat_lock = threading.Lock()

    def wave(lat_list, n_per_session=None, until=None):
        def go(_sid):
            i = 0
            while (until.is_set() is False if until is not None
                   else i < n_per_session):
                t0 = time.perf_counter()
                try:
                    rows = run_one(dash)
                except Exception as e:  # noqa: BLE001 — recorded below
                    failures.append(f"{type(e).__name__}: {e}")
                    i += 1
                    continue
                dt = (time.perf_counter() - t0) * 1000.0
                bad = check(rows)
                with lat_lock:
                    if bad:
                        wrong.append(bad)
                    lat_list.append(dt)
                i += 1
        ths = [threading.Thread(target=go, args=(sid,))
               for sid in range(n_sessions)]
        for t in ths:
            t.start()
        return ths

    # steady leg: no ingest, no refresh — the flatness baseline
    for t in wave(lat_steady, n_per_session=steady_q):
        t.join()

    # churn leg: clients hammer while the ingest loop appends a batch
    # and REFRESHes the view.  Refresh runs EMBEDDED (the coordinator's
    # maintenance path — co-located with serving but never occupying a
    # client admission slot; the protocol REFRESH head has its own
    # integration tests), so what this leg measures is the cut-over
    # itself: whether publishing a new snapshot perturbs in-flight
    # routed reads.
    stop = threading.Event()
    refresh_modes = {}
    last_refresh = {}
    ths = wave(lat_churn, until=stop)
    try:
        for b in range(warm_batches, warm_batches + refreshes):
            ingest(b)
            r = session.sql("REFRESH MATERIALIZED VIEW mv_events")
            mode = r.rows[0][1]
            last_refresh = {
                "mv_delta_splits": r.stats.mv_delta_splits,
                "mv_source_splits": r.stats.mv_source_splits}
            refresh_modes[mode] = refresh_modes.get(mode, 0) + 1
            time.sleep(0.05)
    finally:
        stop.set()
        for t in ths:
            t.join()

    # routed-vs-recompute: the same text against the same final state
    def best_ms(n):
        res, best = None, float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            res = session.sql(dash)
            best = min(best, (time.perf_counter() - t0) * 1000.0)
        return res, best

    routed_res, routed_ms = best_ms(compare_iters)
    if routed_res.stats.execution_mode != "mv_routed":
        unrouted += 1
    session.set("materialized_view_routing", False)
    recompute_res, recompute_ms = best_ms(max(3, compare_iters // 2))
    session.set("materialized_view_routing", True)
    if sorted(routed_res.rows) != sorted(recompute_res.rows):
        wrong.append("routed rows != recompute rows at final state")
    srv.stop()

    s_sorted = sorted(lat_steady)
    c_sorted = sorted(lat_churn)
    p99_steady = _percentile(s_sorted, 0.99)
    p99_churn = _percentile(c_sorted, 0.99)
    record = {
        "metric": "mv_dashboard_p99_flat_across_refresh_cutovers",
        "platform": jax.devices()[0].platform,
        "cores": os.cpu_count(),
        "groups": n_groups,
        "rows_per_batch": n_groups * rep,
        "batches": warm_batches + refreshes,
        "sessions": n_sessions,
        "queries_steady": len(lat_steady),
        "queries_churn": len(lat_churn),
        "refreshes": refreshes,
        "refresh_modes": refresh_modes,
        "last_refresh": last_refresh,
        "failures": len(failures),
        "failure_samples": failures[:5],
        "wrong_results": len(wrong),
        "wrong_samples": wrong[:5],
        "unrouted": unrouted,
        "p50_steady_ms": round(_percentile(s_sorted, 0.50) or 0, 1),
        "p99_steady_ms": round(p99_steady or 0, 1),
        "p50_churn_ms": round(_percentile(c_sorted, 0.50) or 0, 1),
        "p99_churn_ms": round(p99_churn or 0, 1),
        "p99_flat_ratio": round(p99_churn / p99_steady, 2)
        if p99_steady and p99_churn is not None else None,
        "routed_ms": round(routed_ms, 2),
        "recompute_ms": round(recompute_ms, 2),
        "routed_speedup": round(recompute_ms / routed_ms, 1)
        if routed_ms else None,
        "box_sort_ms": _box_speed_ms(),
        "asof": _today(),
    }
    record["gate"] = _mv_serve_gate(record, load_serve_r04())
    try:
        with open(SERVE_R04_PATH, "w") as f:
            json.dump(record, f, indent=1, sort_keys=True)
    except OSError:
        pass
    print(json.dumps(record), flush=True)
    return record


def _mv_serve_gate(record, committed):
    """SERVE_r04's gate: correctness legs are absolute (zero failures,
    zero invariant violations, every dashboard query actually
    MV-routed); the p99-flatness and routed-speedup legs are ratios
    measured WITHIN the run, box-independent by construction; the one
    absolute leg — churn p99 against the committed record — is scaled
    through the records' box fingerprints like _serve_gate's."""
    if record["failures"]:
        return f"FAIL: {record['failures']} query failures"
    if record["wrong_results"]:
        return (f"FAIL: {record['wrong_results']} responses violated "
                "the snapshot-consistency invariant")
    if record.get("unrouted"):
        return (f"FAIL: {record['unrouted']} dashboard probes missed "
                "the MV route")
    flat = record.get("p99_flat_ratio")
    if flat is not None and flat > MV_GATE_P99_FLAT \
            and (record.get("cores") or 1) >= 2:
        return (f"FAIL: churn p99 {record['p99_churn_ms']}ms is "
                f"{flat}x steady p99 {record['p99_steady_ms']}ms "
                f"(> {MV_GATE_P99_FLAT}x — refresh cut-overs are "
                "visible to readers)")
    sp = record.get("routed_speedup")
    if sp is not None and sp < MV_GATE_ROUTED_SPEEDUP:
        return (f"FAIL: routed read {record['routed_ms']}ms only "
                f"{sp}x faster than recompute "
                f"{record['recompute_ms']}ms "
                f"(< {MV_GATE_ROUTED_SPEEDUP}x)")
    note = ""
    if flat is not None and flat > MV_GATE_P99_FLAT:
        # only reachable on a <2-core box (the >=2-core case FAILed
        # above): the refresh compute shares the lone serving core
        note = (f" (1-core box: flatness {flat}x measured, "
                "not enforced)")
    if committed is None \
            or committed.get("platform") != record["platform"]:
        return "pass (no comparable committed record)" + note
    prev_box = committed.get("box_sort_ms")
    cur_box = record.get("box_sort_ms")
    if not (prev_box and cur_box):
        return ("pass (committed record has no box fingerprint — "
                "absolute p99 leg skipped)") + note
    scale = prev_box / cur_box
    prev_p99 = committed.get("p99_churn_ms")
    # the absolute leg shares the flatness leg's core condition: on a
    # 1-core box churn p99 is scheduler-interleaving noise (observed
    # 27ms..95ms from the same tree), not an engine signal — there the
    # within-run ratio legs above carry the gate
    if prev_p99 and record.get("p99_churn_ms") is not None \
            and (record.get("cores") or 1) >= 2 \
            and record["p99_churn_ms"] \
            > SERVE_GATE_P99_RATIO * prev_p99 / scale:
        return (f"FAIL: churn p99 {record['p99_churn_ms']}ms > "
                f"{SERVE_GATE_P99_RATIO}x committed {prev_p99}ms "
                f"(box-scaled x{round(1 / scale, 2)})")
    return "pass" + note


# ---------------------------------------------------------------------------
# round-19 fleet serving (`bench.py --serve --coordinators N`): N
# coordinator PROCESSES behind the fleet front door (server/fleet.py),
# sharing one catalog cache, with signature-affinity routing between
# them — the coordinator scale-out record (SERVE_r03.json)
# ---------------------------------------------------------------------------

SERVE_R03_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "SERVE_r03.json")

# scaling gate: the N-coordinator leg must reach this multiple of the
# single-coordinator leg's aggregate QPS — enforced when the box has at
# least one core per coordinator (process scale-out cannot beat one
# CPU-bound core; the ratio is still measured and committed there, the
# same platform-matching rule _serve_gate applies to chip-vs-cpu)
FLEET_GATE_QPS_SCALING = 1.6
FLEET_GATE_P99_RATIO = 1.5   # fleet p99 <= this multiple of single-leg p99


def load_serve_r03():
    try:
        with open(SERVE_R03_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def serve_child():
    """Subprocess coordinator for the fleet bench: one embedded session
    behind the full protocol front door, joined to a static-peer fleet
    (same coordinator ids in every process => every process derives the
    IDENTICAL ownership ring).  Config rides BENCH_FLEET_CHILD; the
    ready line on stdout carries the bound URI."""
    import presto_tpu
    from presto_tpu.catalog import tpch_catalog
    from presto_tpu.server import PrestoTpuServer
    from presto_tpu.server.fleet import FleetMember
    from presto_tpu.server.resource_groups import ResourceGroupManager

    cfg = json.loads(os.environ["BENCH_FLEET_CHILD"])
    session = presto_tpu.connect(
        tpch_catalog(float(cfg["sf"]), cache_dir="/tmp/presto_tpu_cache"))
    if os.environ.get("BENCH_F32", "1") != "0":
        session.set("float32_compute", True)
    session.set("fleet_affinity", cfg.get("affinity", "proxy"))
    # a batch can never exceed the admission concurrency (same rule as
    # serve_bench's burst phase)
    session.set("coalesce_max_batch", int(cfg["concurrency"]))
    rgm = ResourceGroupManager()
    rgm.add_group("global.serve",
                  hard_concurrency_limit=int(cfg["concurrency"]),
                  max_queued=10_000)
    rgm.add_selector("global.serve")
    fleet = FleetMember(cfg["coord_id"],
                        f"http://127.0.0.1:{cfg['port']}",
                        peers=cfg.get("peers") or {})
    srv = PrestoTpuServer(session, port=int(cfg["port"]),
                          max_concurrent=int(cfg["concurrency"]),
                          resource_groups=rgm, fleet=fleet)
    print(json.dumps({"ready": True, "uri": srv.uri}), flush=True)
    srv.httpd.serve_forever()


def _free_ports(n):
    import socket

    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _spawn_fleet(ncoord, sf, concurrency, affinity="proxy"):
    """Launch `ncoord` coordinator processes with a shared static peer
    map; returns (procs, uris) once every child reports ready."""
    import subprocess

    from presto_tpu.parallel.mesh import refuse_cpu_children

    refuse_cpu_children("bench.py's coordinator fleet")
    ports = _free_ports(ncoord)
    ids = [f"coord{i}" for i in range(ncoord)]
    uris = [f"http://127.0.0.1:{p}" for p in ports]
    procs = []
    for i in range(ncoord):
        cfg = {"coord_id": ids[i], "port": ports[i], "sf": sf,
               "concurrency": concurrency, "affinity": affinity,
               "peers": {ids[j]: uris[j]
                         for j in range(ncoord) if j != i}}
        env = dict(os.environ)
        env["BENCH_FLEET_CHILD"] = json.dumps(cfg)
        env["JAX_PLATFORMS"] = "cpu"
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--serve-child"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, env=env))
    for p in procs:
        line = p.stdout.readline()
        if not line or not json.loads(line).get("ready"):
            raise RuntimeError("fleet coordinator failed to start")
    return procs, uris


def fleet_serve_bench(ncoord=2):
    """Coordinator scale-out record: a single-coordinator leg and an
    N-coordinator leg run the SAME closed-loop client load (round-robin
    across front doors on the fleet leg), then an affinity burst drives
    one prepared signature through EVERY front door — the ring routes
    each EXECUTE to its owner, so coalescing batches still form at
    fleet scale instead of fragmenting 1/N per coordinator.  Emits
    SERVE_r03.json with a core-aware scaling gate."""
    import threading
    import urllib.request

    from presto_tpu.client import StatementClient
    from tests.tpch_queries import QUERIES

    sf = float(os.environ.get("BENCH_SERVE_SF", "0.01"))
    n_sessions = int(os.environ.get("BENCH_SERVE_SESSIONS", "8"))
    per_session = int(os.environ.get("BENCH_SERVE_QUERIES", "15"))
    concurrency = int(os.environ.get("BENCH_SERVE_CONCURRENCY", "4"))
    burst_per_session = int(os.environ.get("BENCH_SERVE_BURST", "30"))
    try:
        cores = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        cores = os.cpu_count() or 1

    max_key = max(int(6_000_000 * sf * 4), 8)

    def point_sql(seed):
        k = 1 + (seed * 7919) % max_key
        return (f"SELECT count(*) c, sum(l_extendedprice) s "
                f"FROM lineitem WHERE l_orderkey = {k}")

    def exec_sql(seed):
        return f"EXECUTE serve_point USING {1 + (seed * 4547) % max_key}"

    def pick(seed):
        r = seed % 8
        if r == 0:
            return "q1", QUERIES[1]
        if r in (1, 5):
            return "q6", QUERIES[6]
        if r == 2:
            return "point_adhoc", point_sql(seed)
        return "point_exec", exec_sql(seed)

    def run_leg(n):
        procs, uris = _spawn_fleet(n, sf, concurrency)
        try:
            def run_one(uri, sql):
                return list(StatementClient(uri, sql).rows())

            # PREPARE once through door 0: the fleet replicates the
            # signature to every peer (server/fleet.replicate_prepare)
            run_one(uris[0], "PREPARE serve_point FROM SELECT count(*) c,"
                    " sum(l_extendedprice) s FROM lineitem WHERE "
                    "l_orderkey = ?")
            # prewarm every class on every door (compiles out of the
            # timed loop, matching serve_bench's prewarm policy)
            for uri in uris:
                for s_ in range(4):
                    run_one(uri, pick(s_)[1])

            lat = []
            lat_lock = threading.Lock()
            failures = []

            def client(sid):
                uri = uris[sid % len(uris)]
                for i in range(per_session):
                    cls, sql = pick(sid * per_session + i + 17)
                    t0 = time.perf_counter()
                    try:
                        run_one(uri, sql)
                    except Exception as e:  # noqa: BLE001 — recorded
                        failures.append(
                            f"{cls}: {type(e).__name__}: {e}")
                        continue
                    with lat_lock:
                        lat.append((time.perf_counter() - t0) * 1000.0)

            t0 = time.perf_counter()
            ths = [threading.Thread(target=client, args=(sid,))
                   for sid in range(n_sessions)]
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            wall = time.perf_counter() - t0

            # affinity burst: the coalescing-heavy class through EVERY
            # door; the ring concentrates each signature on its owner
            errs = []

            def bclient(sid):
                uri = uris[sid % len(uris)]
                for i in range(burst_per_session):
                    try:
                        run_one(uri, exec_sql(3_000_003
                                              + sid * burst_per_session
                                              + i))
                    except Exception as e:  # noqa: BLE001
                        errs.append(f"burst: {type(e).__name__}: {e}")

            tb = time.perf_counter()
            ths = [threading.Thread(target=bclient, args=(sid,))
                   for sid in range(n_sessions)]
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            burst_wall = time.perf_counter() - tb
            failures.extend(errs)

            infos = []
            for uri in uris:
                try:
                    infos.append(json.loads(urllib.request.urlopen(
                        f"{uri}/v1/info", timeout=30).read()))
                except Exception:  # noqa: BLE001
                    infos.append({})
            lat.sort()
            total = n_sessions * per_session - len(failures)
            co_batches = sum(
                ((i.get("serving") or {}).get("coalescing") or {})
                .get("batches", 0) for i in infos)
            fleet_counts = {}
            for i in infos:
                for k, v in (i.get("fleet") or {}).items():
                    if isinstance(v, (int, float)) \
                            and not isinstance(v, bool):
                        fleet_counts[k] = fleet_counts.get(k, 0) + v
            return {
                "coordinators": n,
                "queries": total,
                "failures": len(failures),
                "failure_samples": failures[:5],
                "wall_s": round(wall, 2),
                "qps": round(total / wall, 2) if wall else None,
                "p50_ms": round(_percentile(lat, 0.50), 1) if lat
                else None,
                "p99_ms": round(_percentile(lat, 0.99), 1) if lat
                else None,
                "burst": {
                    "queries": n_sessions * burst_per_session,
                    "qps": round(
                        n_sessions * burst_per_session / burst_wall, 1)
                    if burst_wall else None,
                    "coalesce_batches": co_batches,
                },
                "fleet_counters": {k: round(v, 2)
                                   for k, v in sorted(fleet_counts.items())
                                   if v},
            }
        finally:
            for p in procs:
                p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except Exception:  # noqa: BLE001
                    p.kill()

    import jax

    single = run_leg(1)
    fleet = run_leg(max(int(ncoord), 2))
    ratio = round(fleet["qps"] / single["qps"], 2) \
        if single.get("qps") and fleet.get("qps") else None
    p99_ratio = round(fleet["p99_ms"] / single["p99_ms"], 2) \
        if single.get("p99_ms") and fleet.get("p99_ms") else None
    record = {
        "metric": "fleet_serve_scaling",
        "platform": jax.devices()[0].platform,
        "cores": cores,
        "sf": sf,
        "sessions": n_sessions,
        "per_session": per_session,
        "concurrency_limit": concurrency,
        "single": single,
        "fleet": fleet,
        "scaling": {"qps_ratio": ratio, "p99_ratio": p99_ratio},
        "asof": _today(),
    }
    record["gate"] = _fleet_serve_gate(record, load_serve_r03())
    try:
        with open(SERVE_R03_PATH, "w") as f:
            json.dump(record, f, indent=1, sort_keys=True)
    except OSError:
        pass
    print(json.dumps(record), flush=True)
    return record


def _fleet_serve_gate(record, committed):
    """SERVE_r03's own gate: zero failures always; coalescing batches
    must form on the affinity burst always; the >=1.6x QPS scaling and
    p99 bound apply when the box can actually run the coordinators in
    parallel (cores >= coordinator count) — the same platform-matching
    rule the r02 gate applies to chip-vs-cpu records."""
    single, fleet = record["single"], record["fleet"]
    fails = single["failures"] + fleet["failures"]
    if fails:
        return f"FAIL: {fails} query failures"
    if not fleet["burst"]["coalesce_batches"]:
        return "FAIL: no coalescing batches formed on the affinity burst"
    ratio = record["scaling"]["qps_ratio"]
    p99_ratio = record["scaling"]["p99_ratio"]
    if ratio is not None and ratio >= FLEET_GATE_QPS_SCALING \
            and (p99_ratio is None or p99_ratio <= FLEET_GATE_P99_RATIO):
        # thresholds met outright (possible even on a shared core when
        # the single leg is admission-bound rather than CPU-bound)
        return "pass"
    if record["cores"] >= fleet["coordinators"]:
        if ratio is not None and ratio < FLEET_GATE_QPS_SCALING:
            return (f"FAIL: fleet qps {ratio}x single < "
                    f"{FLEET_GATE_QPS_SCALING}x")
        if p99_ratio is not None and p99_ratio > FLEET_GATE_P99_RATIO:
            return (f"FAIL: fleet p99 {p99_ratio}x single > "
                    f"{FLEET_GATE_P99_RATIO}x")
    else:
        # scale-out cannot beat a CPU-bound single core; the committed
        # ratio is still regression-gated below
        if committed is not None \
                and committed.get("platform") == record["platform"] \
                and committed.get("sf") == record["sf"] \
                and committed.get("cores") == record["cores"]:
            prev = (committed.get("scaling") or {}).get("qps_ratio")
            if prev and ratio is not None \
                    and ratio < SERVE_GATE_QPS_RATIO * prev:
                return (f"FAIL: scaling ratio {ratio} < "
                        f"{SERVE_GATE_QPS_RATIO}x committed {prev}")
        return (f"pass ({record['cores']} core(s) for "
                f"{fleet['coordinators']} coordinators: scaling gate "
                f"applies at >= 1 core per coordinator)")
    return "pass"


MULTICHIP_RECORD_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "MULTICHIP_r08.json")


SPILL_RECORD_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "SPILL_r01.json")


def load_spill_record():
    try:
        with open(SPILL_RECORD_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def spill_gate_summary():
    """The spill degradation-curve benchmark as registered in the
    default bench artifact: the COMMITTED SPILL_r01.json record
    (bench.py --spill re-measures it) — a default run exits 0 on
    committed records and a broken tier is visibly red in the record's
    own gate."""
    rec = load_spill_record()
    if rec is None:
        return None
    return {"tiers": {q: {t: leg.get("wall_ms") for t, leg in legs.items()}
                      for q, legs in (rec.get("tiers") or {}).items()},
            "checksums_equal": rec.get("checksums_equal"),
            "gate": rec.get("gate"), "asof": rec.get("asof")}


def spill_bench():
    """`bench.py --spill`: the beyond-HBM degradation curve (ISSUE 11).

    Two query shapes — q18 (join-heavy, the ROADMAP item-1 gate shape)
    and a q67-class high-cardinality GROUP BY — run at every forced
    degradation tier (resident / partial spill / recursive
    partitioning), recording wall-clock, spill bytes/partitions/
    restores/recursions, and CHECKSUM EQUIVALENCE against the resident
    run; then a descending HBM-budget sweep on q18 records where the
    memory-driven planner flips resident -> hybrid -> hard-fail.
    Emits SPILL_r01.json and one JSON line.  Env: BENCH_SPILL_SF."""
    import presto_tpu
    from presto_tpu.catalog import tpch_catalog
    from tests.tpch_queries import QUERIES

    sf = float(os.environ.get("BENCH_SPILL_SF", "0.1"))
    q67_class = ("SELECT l_orderkey, count(*) c, sum(l_quantity) sq, "
                 "min(l_extendedprice) mn, max(l_discount) mx "
                 "FROM lineitem GROUP BY l_orderkey ORDER BY l_orderkey")
    shapes = {"q18": QUERIES[18], "q67_class": q67_class}

    def mk_session():
        s = presto_tpu.connect(
            tpch_catalog(sf, cache_dir="/tmp/presto_tpu_cache"))
        s.set("execution_mode", "dynamic")
        return s

    def cksum(rows):
        # floats to 8 significant digits: partition-wise sums
        # legitimately reassociate float addition (see
        # tests/test_spill_tiers.canon)
        return hash(tuple(sorted(
            tuple(float(f"{v:.8g}") if isinstance(v, float) else v
                  for v in r) for r in rows)))

    session = mk_session()
    tiers = {}
    all_equal = True
    for name, sql in shapes.items():
        legs = {}
        t0 = time.perf_counter()
        base = session.sql(sql)
        legs["resident"] = {
            "wall_ms": round((time.perf_counter() - t0) * 1000, 1),
            "spill_bytes": 0, "tier": base.stats.degradation_tier}
        want = cksum(base.rows)
        for mode, tier in (("partial", 1), ("recursive", 2)):
            session.set("force_spill", mode)
            try:
                t0 = time.perf_counter()
                r = session.sql(sql)
                wall = (time.perf_counter() - t0) * 1000
            finally:
                session.set("force_spill", "")
            equal = cksum(r.rows) == want
            all_equal = all_equal and equal \
                and r.stats.degradation_tier == tier
            legs[mode] = {
                "wall_ms": round(wall, 1), "tier": r.stats.degradation_tier,
                "spill_bytes": r.stats.spill_bytes,
                "spill_partitions": r.stats.spill_partitions,
                "spill_restores": r.stats.spill_restores,
                "spill_recursions": r.stats.spill_recursions,
                "checksum_equal": equal}
        tiers[name] = legs

    # descending HBM-budget sweep: where does the memory-driven planner
    # flip resident -> hybrid -> hard-fail?  q18's semi-join-pruned
    # LIVE set sits far under the capacity peak (the df-resident
    # re-probe holds it resident until scan accounting itself fails);
    # the q67-class aggregation has no filter escape, so it walks the
    # full resident -> partial band before the scan floor
    sweep = {}
    for name in shapes:
        session.sql(shapes[name])
        peak = session.last_stats.peak_memory_bytes or (64 << 20)
        want = cksum(session.sql(shapes[name]).rows)
        legs = []
        for frac in (1.0, 0.6, 0.4, 0.25, 0.15, 0.1, 0.05):
            budget = int(peak * frac)
            s2 = mk_session()
            s2.set("query_max_memory_bytes", budget)
            t0 = time.perf_counter()
            try:
                r = s2.sql(shapes[name])
                legs.append({
                    "budget_bytes": budget, "frac_of_resident_peak": frac,
                    "outcome": ["resident", "partial", "recursive"][
                        r.stats.degradation_tier],
                    "wall_ms": round((time.perf_counter() - t0) * 1000, 1),
                    "spill_bytes": r.stats.spill_bytes,
                    "checksum_equal": cksum(r.rows) == want})
                all_equal = all_equal and cksum(r.rows) == want
            except Exception as e:
                legs.append({"budget_bytes": budget,
                             "frac_of_resident_peak": frac,
                             "outcome": f"fail ({type(e).__name__})"})
        sweep[name] = legs

    record = {
        "metric": "spill_degradation_curve",
        "sf": sf,
        "platform": "cpu" if os.environ.get("JAX_PLATFORMS") == "cpu"
        else "chip",
        "tiers": tiers,
        "budget_sweep": sweep,
        "checksums_equal": all_equal,
        "gate": "pass" if all_equal
        else "FAIL: a degradation tier diverged from the resident run",
        "asof": _today(),
        "note": ("forced tiers via the force_spill session knob "
                 "(PRESTO_TPU_FORCE_SPILL env equivalent); sweep budgets "
                 "are fractions of the resident run's peak_memory_bytes; "
                 "dynamic execution mode (the spillable path)"),
    }
    with open(SPILL_RECORD_PATH, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps(record), flush=True)


def load_multichip_record():
    try:
        with open(MULTICHIP_RECORD_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def multichip_summary():
    """The committed fused-vs-cut-vs-auto record (bench.py --multichip
    re-measures it); a default run reports it without re-measuring."""
    rec = load_multichip_record()
    if rec is None:
        return None
    return {"platform": rec.get("platform"),
            "n_devices": rec.get("n_devices"), "sf": rec.get("sf"),
            "queries": {q: {"fused_warm_ms": v.get("fused_warm_ms"),
                            "cut_warm_ms": v.get("cut_warm_ms"),
                            "auto_warm_ms": v.get("auto_warm_ms"),
                            "speedup": v.get("speedup"),
                            "auto_vs_best": v.get("auto_vs_best")}
                        for q, v in (rec.get("queries") or {}).items()},
            "gate": rec.get("gate"), "asof": rec.get("asof")}


#: the auto leg must land within this factor of the BETTER forced leg
#: (the round-18 fusion-cost acceptance bar: no silent fuse-regressions)
MULTICHIP_AUTO_RATIO = 1.1


def multichip_bench(hosts=0):
    """`bench.py --multichip [--hosts N]`: the distributed gate queries
    (q3/q18) — three legs per query: fragment_fusion=force (round 12's
    one-shard_map-program policy), =off (per-fragment HTTP pages), and
    =auto (the round-18 plan/fusion_cost.py per-edge cost model; runs
    LAST so the decision memo has both forced legs' observed walls —
    exactly the steady state a production A/B reaches).  Cold + warm
    wall-clock, checksum equality across all three, exchange-byte
    counters, and the per-edge skip reasons.  The gate requires the
    auto leg within MULTICHIP_AUTO_RATIO of the BETTER forced leg on
    every query — a silent fuse-regression (the old q18 2056ms-vs-747ms
    shape) is now a red record.  Without --hosts the cluster is one
    in-process worker declaring the local device mesh; with --hosts N
    it is N worker SUBPROCESSES joined into one jax.distributed gloo
    mesh (round 21), so the force leg runs cross-host collectives and
    must drive exchange_bytes_host to ~0 on the fused attempt.  Writes
    MULTICHIP_r08.json; on a CPU host the record anchors the MECHANISM,
    chip wall-clock comes from re-running this on real hardware."""
    import jax

    import presto_tpu
    from presto_tpu.catalog import tpch_catalog
    from presto_tpu.parallel import cluster as C
    from tests.tpch_queries import QUERIES

    sf = float(os.environ.get("BENCH_MULTICHIP_SF", "0.01"))
    runs = int(os.environ.get("BENCH_MULTICHIP_RUNS", "3"))
    session = presto_tpu.connect(
        tpch_catalog(sf, cache_dir="/tmp/presto_tpu_cache"))
    worker = None
    if hosts >= 2:
        # CPU worker processes: launch_local_cluster refuses under a
        # TPU parent, so the record's platform is the workers' own
        ldev = int(os.environ.get("BENCH_MULTICHIP_LOCAL_DEVICES", "2"))
        ndev = hosts * ldev
        cs = C.launch_local_cluster(
            session, f"tpch:{sf}:/tmp/presto_tpu_cache", nworkers=hosts,
            multihost=True, local_devices=ldev)
    else:
        ndev = len(jax.devices())
        worker = C.WorkerServer(f"tpch:{sf}:/tmp/presto_tpu_cache",
                                mesh_devices=ndev).start()
        cs = C.ClusterSession(session, [worker.url])

    def norm(rows):
        return sorted(tuple(round(x, 4) if isinstance(x, float) else x
                            for x in r) for r in rows)

    def leg(q, mode):
        session.set("fragment_fusion", mode)
        t0 = time.perf_counter()
        r = cs.sql(q)
        cold = (time.perf_counter() - t0) * 1000
        best = float("inf")
        for _ in range(runs):
            t0 = time.perf_counter()
            r = cs.sql(q)
            best = min(best, (time.perf_counter() - t0) * 1000)
        return r, round(cold, 1), round(best, 1)

    record = {"metric": "multichip_fused_vs_cut_vs_auto_wall_ms",
              "platform": jax.devices()[0].platform,
              "n_devices": ndev, "hosts": max(hosts, 1), "sf": sf,
              "runs": runs, "queries": {}, "asof": _today()}
    failures = []
    try:
        for qid in (3, 18):
            q = QUERIES[qid]
            rf, f_cold, f_warm = leg(q, "force")
            rc, c_cold, c_warm = leg(q, "off")
            ra, a_cold, a_warm = leg(q, "auto")
            session.set("fragment_fusion", "auto")
            equal = norm(rf.rows) == norm(rc.rows) == norm(ra.rows)
            best_forced = min(f_warm, c_warm)
            auto_ok = a_warm <= MULTICHIP_AUTO_RATIO * best_forced
            if not equal or rf.stats.fragments_fused == 0:
                failures.append(f"q{qid}")
            if not auto_ok:
                failures.append(f"q{qid}-auto")
            if hosts >= 2 and rf.stats.exchange_bytes_host > 0:
                # a fused cross-host leg that still moved HTTP bytes
                # means some collective-eligible edge fell off the mesh
                failures.append(f"q{qid}-dcn")
            record["queries"][f"q{qid}"] = {
                "fused_cold_ms": f_cold, "fused_warm_ms": f_warm,
                "cut_cold_ms": c_cold, "cut_warm_ms": c_warm,
                "auto_cold_ms": a_cold, "auto_warm_ms": a_warm,
                "speedup": round(c_warm / f_warm, 2) if f_warm else None,
                "auto_vs_best": round(a_warm / best_forced, 2)
                if best_forced else None,
                "fragments_fused": rf.stats.fragments_fused,
                "auto_fragments_fused": ra.stats.fragments_fused,
                "auto_fusion_skips": dict(ra.stats.fusion_skips),
                "auto_edges_mispredicted":
                    ra.stats.fusion_edges_mispredicted,
                "exchange_bytes_host_fused":
                    rf.stats.exchange_bytes_host,
                "exchange_bytes_collective":
                    rf.stats.exchange_bytes_collective,
                "exchange_bytes_dcn": rf.stats.exchange_bytes_dcn,
                "exchange_bytes_host_cut": rc.stats.exchange_bytes_host,
                "checksums_equal": equal}
    finally:
        if worker is not None:
            worker.stop()
        for p in getattr(cs, "_procs", []):
            p.kill()
    record["gate"] = ("FAIL: " + ",".join(failures)) if failures else \
        (f"pass (fused>0, checksums equal, auto <= "
         f"{MULTICHIP_AUTO_RATIO}x best forced leg"
         + (", host bytes 0 on fused cross-host legs)" if hosts >= 2
            else ")"))
    try:
        with open(MULTICHIP_RECORD_PATH, "w") as f:
            json.dump(record, f, indent=1, sort_keys=True)
    except OSError:
        pass
    print(json.dumps(record), flush=True)
    return record


def recovery_bench():
    """Robustness cost metric (docs/ROBUSTNESS.md): wall-clock ms from
    an injected worker crash (fault-plan scripted, in-process cluster at
    tiny SF) to query completion on the survivors — the bench trajectory
    tracks recovery latency alongside raw query latency.  BENCH_RECOVERY=0
    skips it; any failure reports None rather than failing the bench."""
    if os.environ.get("BENCH_RECOVERY", "1") == "0":
        return None
    try:
        import presto_tpu
        from presto_tpu.catalog import tpch_catalog
        from presto_tpu.parallel import cluster as C
        from presto_tpu.parallel import faults as F

        session = presto_tpu.connect(
            tpch_catalog(0.01, cache_dir="/tmp/presto_tpu_cache"))
        # hard per-query budget: this runs BEFORE the bench line is
        # emitted, so it must fail fast rather than ever hang the bench
        session.properties["cluster_query_deadline_s"] = 60.0
        workers = [C.WorkerServer("tpch:0.01:/tmp/presto_tpu_cache",
                                  faults=F.FaultPlan([])).start()
                   for _ in range(2)]
        cs = C.ClusterSession(session, [w.url for w in workers])
        try:
            q = "SELECT count(*) c, sum(o_totalprice) s FROM orders"
            cs.sql(q)  # prewarm: compile + page-path caches
            plan = F.FaultPlan.parse("exec:EXEC:*:1:crash")
            workers[1].faults = plan
            cs.sql(q)  # crash fires mid-wave; survivors finish the query
            if not plan.fired:
                return None
            done = time.monotonic()
            return round((done - plan.fired[0][0]) * 1000, 1)
        finally:
            for w in workers:
                if not w.crashed:
                    w.stop()
    except Exception as e:
        print(f"bench: recovery bench FAILED ({type(e).__name__}: {e})",
              file=sys.stderr)
        return None


CHAOS_RECORD_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "CHAOS_r01.json")


def load_chaos_record():
    try:
        with open(CHAOS_RECORD_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


CHAOS_GATE_MTTR_RATIO = 3.0  # FAIL above this multiple of committed MTTR


def _chaos_gate(record, committed):
    """Regression gate vs the committed record (committed-record exit-0
    discipline, like the other *_r*.json records): a failed leg FAILs;
    MTTR regressions gate platform-matched with generous headroom —
    recovery walls are single-digit-to-hundreds of ms, so scheduler
    noise needs a wide band."""
    for leg in ("task_rerun", "worker_crash", "coordinator_adoption"):
        if not record[leg].get("ok"):
            return f"FAIL: {leg} leg did not recover"
    if committed is None \
            or committed.get("platform") != record["platform"]:
        return "pass (no comparable committed record)"
    for leg in ("task_rerun", "worker_crash", "coordinator_adoption"):
        old = committed.get(leg, {}).get("mttr_ms")
        new = record[leg].get("mttr_ms")
        if old and new and new > old * CHAOS_GATE_MTTR_RATIO:
            return (f"FAIL: {leg} MTTR {new}ms vs committed {old}ms "
                    f"(> {CHAOS_GATE_MTTR_RATIO}x)")
    return "pass"


def chaos_bench():
    """`--chaos`: MTTR-style recovery latencies under seeded FaultPlans
    (docs/ROBUSTNESS.md "Recovery matrix"), each vs a fault-free
    baseline on the same in-process cluster: single-task rerun
    (task-granular restart inside the attempt), worker crash mid-wave
    (survivor remap), and coordinator death with journaled adoption
    (ring-successor resume over the durable exchange).  Emits
    CHAOS_r01.json; the committed record is the regression reference."""
    import shutil
    import tempfile

    import jax

    import presto_tpu
    from presto_tpu.catalog import tpch_catalog
    from presto_tpu.parallel import cluster as C
    from presto_tpu.parallel import faults as F
    from presto_tpu.server import fleet as FL

    q = ("SELECT o_orderpriority, count(*) c FROM orders "
         "GROUP BY o_orderpriority ORDER BY 1")
    cat = tpch_catalog(0.01, cache_dir="/tmp/presto_tpu_cache")
    session = presto_tpu.connect(cat)
    session.properties["cluster_query_deadline_s"] = 120.0
    workers = [C.WorkerServer("tpch:0.01:/tmp/presto_tpu_cache",
                              faults=F.FaultPlan([])).start()
               for _ in range(2)]
    urls = [w.url for w in workers]
    cs = C.ClusterSession(session, urls)
    tmp = tempfile.mkdtemp(prefix="pt_chaos_bench_")
    record = {"platform": jax.devices()[0].platform, "sf": 0.01,
              "task_rerun": {"ok": False}, "worker_crash": {"ok": False},
              "coordinator_adoption": {"ok": False}, "asof": _today()}
    try:
        want = cs.sql(q).rows  # prewarm: compile + page-path caches
        walls = []
        for _ in range(3):
            t0 = time.monotonic()
            cs.sql(q)
            walls.append((time.monotonic() - t0) * 1000)
        record["baseline_ms"] = round(sorted(walls)[1], 1)

        # leg 1: ONE task fails mid-wave -> same-attempt slot rerun
        plan = F.FaultPlan.parse("exec:EXEC:*:1:fail")
        workers[1].faults = plan
        t0 = time.monotonic()
        ok = cs.sql(q).rows == want
        done = time.monotonic()
        rec = session.last_stats.recovery
        record["task_rerun"] = {
            "ok": bool(ok and plan.fired
                       and rec.get("tasks_rerun", 0) == 1),
            "wall_ms": round((done - t0) * 1000, 1),
            "mttr_ms": round((done - plan.fired[0][0]) * 1000, 1)
            if plan.fired else None,
            "tasks_rerun": rec.get("tasks_rerun", 0)}
        workers[1].faults = F.FaultPlan([])

        # leg 2: coordinator A dies with the query journaled mid-flight;
        # B (the ring successor) adopts and resumes from the durable
        # exchange — MTTR is death verdict -> adopted rows in hand
        props = {"spill_path": os.path.join(tmp, "spill"),
                 "query_journal_path": os.path.join(tmp, "journal"),
                 "cluster_query_retries": 0, "cluster_task_restarts": 0,
                 "cluster_query_deadline_s": 120.0}
        d = FL.FleetDirectory()
        ma = d.join("A", "http://a.invalid")
        mb = d.join("B", "http://b.invalid")
        for w in workers:
            d.slots.register_worker(w.url, 8)
        sa = presto_tpu.connect(cat)
        sa.properties.update(props)
        ca = C.ClusterSession(sa, urls, fleet=ma)
        ca._journal_keep = True  # A dies before its cleanup runs
        workers[1].faults = F.FaultPlan.parse("exec:EXEC:*:1:fail")
        try:
            ca.sql(q)
        except Exception:
            pass  # the scripted death of coordinator A
        workers[1].faults = F.FaultPlan([])
        t0 = time.monotonic()
        d.leave("A")
        sb = presto_tpu.connect(cat)
        sb.properties.update(props)
        cb = C.ClusterSession(sb, urls, fleet=mb)
        out = cb.adopt_journaled("A")
        done = time.monotonic()
        rec = sb.last_stats.recovery
        record["coordinator_adoption"] = {
            "ok": bool(len(out) == 1
                       and not isinstance(out[0][1], Exception)
                       and out[0][1].rows == want
                       and rec.get("queries_adopted", 0) == 1),
            "mttr_ms": round((done - t0) * 1000, 1),
            "queries_adopted": rec.get("queries_adopted", 0),
            "adoption_ms": rec.get("adoption_ms", 0)}

        # leg 3 (destructive, last): worker crash mid-wave -> survivors
        plan = F.FaultPlan.parse("exec:EXEC:*:1:crash")
        workers[1].faults = plan
        ok = cs.sql(q).rows == want
        done = time.monotonic()
        record["worker_crash"] = {
            "ok": bool(ok and plan.fired),
            "mttr_ms": round((done - plan.fired[0][0]) * 1000, 1)
            if plan.fired else None}
    except Exception as e:
        print(f"bench: chaos bench FAILED ({type(e).__name__}: {e})",
              file=sys.stderr)
    finally:
        for w in workers:
            if not w.crashed:
                w.stop()
        shutil.rmtree(tmp, ignore_errors=True)
    record["gate"] = _chaos_gate(record, load_chaos_record())
    try:
        with open(CHAOS_RECORD_PATH, "w") as f:
            json.dump(record, f, indent=1, sort_keys=True)
    except OSError:
        pass
    print(json.dumps(record), flush=True)
    return record


def load_scale_progress():
    try:
        with open(SCALE_PROGRESS_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def run_sf1_tier():
    """SF1 scale-test tier as part of the default bench run, so spill and
    capacity-guard paths at non-toy scale cannot regress silently."""
    import subprocess

    env = dict(os.environ, PRESTO_TPU_SCALE_TESTS="1")
    try:
        rc = subprocess.call(
            [sys.executable, "-m", "pytest", "tests/test_scale_sf1.py", "-q"],
            env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=max(_remaining() - 60, 60))
    except subprocess.TimeoutExpired:
        rc = 124
    out = load_scale_progress() or {}
    out["sf1_test_tier"] = {"rc": rc, "asof": _today()}
    try:
        with open(SCALE_PROGRESS_PATH, "w") as f:
            json.dump(out, f)
    except OSError:
        pass


def _today():
    return time.strftime("%Y-%m-%d")


def _scale_session(sf, family="tpch"):
    """One session-construction path for every scale config.  TPC-H
    generates fully on device (no disk cache needed); TPC-DS fact
    tables stream through chunked execution while dimension tables
    host-generate once into the disk cache (config 4, SF100 q64)."""
    import presto_tpu
    from presto_tpu.catalog import tpch_catalog, tpcds_catalog

    if family == "tpcds":
        cat = tpcds_catalog(sf, cache_dir="/tmp/presto_tpu_cache")
    else:
        cat = tpch_catalog(sf, cache_dir=None)
    s = presto_tpu.connect(cat)
    if family == "tpcds":
        # q64's 18-join chunk fragment: 6M-row chunks keep the chunk
        # working set under the 16G chip; the bounded accumulator path
        # (exec/chunked._chunk_loop_accumulate) keeps the pipelined
        # loop's buffering under chunk_buffer_max_rows
        s.properties["chunk_fact_rows"] = 6_000_000
    if os.environ.get("BENCH_F32", "1") != "0":
        s.set("float32_compute", True)
    return s


# rough cold wall-clock per scale config (compile-dominated), used to
# skip configs the remaining budget cannot fit.  With a populated
# persistent XLA cache (presto_tpu/__init__.py) "cold" is a cache load,
# not a compile, so the gates drop accordingly.
_SCALE_ESTIMATES_S = {"sf10_q3": 420, "sf100_q18": 2700, "sf100_q9": 2700,
                      "sf100_q64": 3600, "sf300_q18": 3600}
_SCALE_ESTIMATES_CACHED_S = {"sf10_q3": 180, "sf100_q18": 600,
                             "sf100_q9": 600, "sf100_q64": 900,
                             "sf300_q18": 1200}


def _scale_estimate(name, out):
    """Per-config wall-clock estimate: the cheap 'cached' figure only
    applies to a config that has completed before on this machine (its
    XLA programs are in the persistent cache); the cache dir being
    non-empty says nothing about THIS config's programs."""
    if isinstance(out.get(name), dict) and "cold_s" in out[name]:
        return _SCALE_ESTIMATES_CACHED_S.get(name, 600)
    return _SCALE_ESTIMATES_S.get(name, 600)


def scale_configs(session_factory):
    """BASELINE configs above SF1: per-query cold+warm wall seconds.
    SF10 runs whole-table on device generation; SF100 streams through
    chunked (grouped) execution.  Runs under BENCH_TIME_BUDGET wall
    seconds (default 5400) — configs that cannot fit are recorded as
    skipped.  Results merge into BENCH_SCALE_PROGRESS.json (committed;
    the emitted bench line reports its last-known contents), stalest
    entry refreshed first so a tight budget rotates rather than
    starves."""
    from tests.tpch_queries import QUERIES

    # never promise the scale tier more than the PROCESS has left (keep
    # 120s back for the sf1 tier gate + clean exit)
    budget = min(float(os.environ.get("BENCH_TIME_BUDGET", "5400")),
                 max(_remaining() - 120, 0))
    t_start = time.perf_counter()
    configs = [("sf10_q3", 10.0, 3, "tpch"), ("sf100_q18", 100.0, 18, "tpch"),
               ("sf100_q9", 100.0, 9, "tpch"),
               ("sf100_q64", 100.0, 64, "tpcds"),
               # BASELINE config 5 at its NOMINAL scale (round-3 VERDICT
               # item 3: sf300 had never been attempted)
               ("sf300_q18", 300.0, 18, "tpch")]
    out = load_scale_progress() or {}
    # stalest first: refresh the entry whose record is oldest
    configs.sort(key=lambda c: (out.get(c[0]) or {}).get("asof", ""))

    def checkpoint():
        try:
            with open(SCALE_PROGRESS_PATH, "w") as f:
                json.dump(out, f)
        except OSError:
            pass

    from tests.tpcds_queries import QUERIES as DS_QUERIES

    for name, sf, qid, family in configs:
        q = (DS_QUERIES if family == "tpcds" else QUERIES)[qid]
        remaining = budget - (time.perf_counter() - t_start)
        if remaining < _scale_estimate(name, out):
            if name not in out:
                out[name] = {"skipped":
                             f"time budget ({remaining:.0f}s left)"}
                checkpoint()
            continue
        try:
            s = session_factory(sf, family)
            t0 = time.perf_counter()
            r = s.sql(q)
            cold = time.perf_counter() - t0
            t0 = time.perf_counter()
            s.sql(q)
            warm = time.perf_counter() - t0
            out[name] = {"cold_s": round(cold, 1), "warm_s": round(warm, 1),
                         "rows": len(r.rows), "asof": _today()}
        except Exception as e:
            out[name] = {"error": f"{type(e).__name__}: {str(e)[:120]}",
                         "asof": _today()}
        finally:
            checkpoint()
            # catalog<->table reference cycles would otherwise keep the
            # previous config's device columns resident into the next one
            import gc

            try:
                del s, r
            except NameError:
                pass
            gc.collect()
    return out


def numpy_speedup(cat, engine_times):
    """Tuned numpy pipelines over the same in-memory arrays (honest
    CPU-core baseline; see bench_baselines.py)."""
    try:
        from bench_baselines import NUMPY_QUERIES

        tables = {t: cat.get(t) for t in ("lineitem", "orders", "customer")}
        total = 0.0
        covered = 0.0
        for qid in engine_times:
            fn = NUMPY_QUERIES.get(qid)
            if fn is None:
                continue
            fn(tables)  # warm (column reads cache)
            best = float("inf")
            for _ in range(RUNS):  # same run count as the engine
                t0 = time.perf_counter()
                fn(tables)
                best = min(best, time.perf_counter() - t0)
            total += best
            covered += engine_times[qid]
        if covered == 0.0:
            return None
        return round(total / covered, 2)
    except Exception as e:
        # vs_baseline must not silently degrade to the flattering sqlite
        # ratio — make the failure visible
        print(f"bench: numpy baseline FAILED ({type(e).__name__}: {e})",
              file=sys.stderr)
        return None


def sqlite_speedup(engine_times):
    try:
        from tests.sqlite_oracle import build_sqlite, to_sqlite
        from tests.tpch_queries import QUERIES

        conn = build_sqlite(min(SF, 0.1))  # cap oracle size; scale measured time
        scale = SF / min(SF, 0.1)
        total = 0.0
        for qid in engine_times:
            t0 = time.perf_counter()
            conn.execute(to_sqlite(QUERIES[qid])).fetchall()
            total += (time.perf_counter() - t0) * scale
        return round(total / sum(engine_times.values()), 2)
    except Exception:
        return None


if __name__ == "__main__":
    if "--serve-child" in sys.argv:
        serve_child()
    elif "--serve" in sys.argv and "--coordinators" in sys.argv:
        serve_fleet_n = int(sys.argv[sys.argv.index("--coordinators") + 1])
        fleet_serve_bench(serve_fleet_n)
    elif "--serve" in sys.argv and "--mv" in sys.argv:
        mv_serve_bench()
    elif "--serve" in sys.argv:
        serve_bench()
        mv_serve_bench()
    elif "--multichip" in sys.argv:
        multichip_hosts = int(sys.argv[sys.argv.index("--hosts") + 1]) \
            if "--hosts" in sys.argv else 0
        multichip_bench(multichip_hosts)
    elif "--write" in sys.argv:
        write_bench()
    elif "--spill" in sys.argv:
        spill_bench()
    elif "--chaos" in sys.argv:
        chaos_bench()
    else:
        main()
