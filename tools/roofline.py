"""Roofline accounting for the engine's hot kernels on the real chip.

Round-3 VERDICT weak #5: the headline rows/sec number had no in-repo
framing against what the hardware can actually do.  An analytic SQL
engine on TPU is HBM-BANDWIDTH bound (scans, sorts, gathers — there are
almost no matmuls), so the roofline that matters is bytes/sec, not MXU
FLOPs; "MFU" here is achieved HBM bandwidth / peak HBM bandwidth.

Methodology: a program's launch + host sync can exceed a kernel's own
time, so every measurement runs K iterations INSIDE one jitted program
(lax.fori_loop with a loop-carried dependence so XLA cannot hoist),
returns a scalar, and subtracts the measured empty-program round trip;
per-iteration time = (t - t_rtt) / K.

Prints ONE JSON line; run `python tools/roofline.py` on the chip
(`python tools/roofline.py dynfilter`: the dynamic-filter sweep alone, at
the shapes of the benchmark's sf1_join).
The numbers land in docs/PERF.md.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

K = 30


def timed(fn, *args, runs=3):
    """Best wall time of fn(*args) -> scalar, forced to host."""
    float(fn(*args))  # compile + warm
    best = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter()
        float(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def exchange_sweep(per_iter, rng):
    """Exchange economics: host HTTP shuffle vs in-trace all_to_all.

    Anchors the fragment-fusion cost model (plan/fusion_cost.py): what
    one repartition edge costs on the per-fragment HTTP path (pack PTPG
    page -> loopback POST -> GET -> unpack -> host hash_partition — the
    floor; real DCN adds network) vs lowered into the traced program as
    ONE lax.all_to_all over the mesh.  Swept rows x ndev; cells the
    host can't run (fewer local devices than ndev) are skipped.  The
    `--calibrate` mode fits these cells into a per-platform fusion
    profile (least-squares intercept + slope per lane)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from presto_tpu.batch import Batch as PBatch
    from presto_tpu.parallel import cluster as CL
    from presto_tpu.parallel import exchange as EXC
    from presto_tpu.parallel.mesh import AXIS, make_mesh
    from presto_tpu.parallel import dist_executor as DX
    from jax.sharding import NamedSharding, PartitionSpec as PSpec

    import threading
    import urllib.request
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    page_store = {}

    class _Echo(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def do_POST(self):
            page_store["page"] = self.rfile.read(
                int(self.headers.get("Content-Length", 0)))
            self.send_response(200)
            self.send_header("Content-Length", "0")
            self.end_headers()

        def do_GET(self):
            body = page_store.get("page", b"")
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    echo = ThreadingHTTPServer(("127.0.0.1", 0), _Echo)
    threading.Thread(target=echo.serve_forever, daemon=True).start()
    echo_url = f"http://127.0.0.1:{echo.server_address[1]}/page"

    ndev_avail = len(jax.devices())
    xout = {}
    for rexp in (16, 18, 20):
        rows = 1 << rexp
        kh = rng.integers(0, 1 << 31, rows).astype(np.int64)
        vh = rng.normal(size=rows)
        cols = {"k": (kh, None), "v": (vh, None)}
        cell = {"bytes": int(kh.nbytes + vh.nbytes)}

        def host_trip(nd):
            page = CL.pack_columns(cols)
            req = urllib.request.Request(echo_url, data=page,
                                         method="POST")
            urllib.request.urlopen(req, timeout=30).read()
            body = urllib.request.urlopen(echo_url, timeout=30).read()
            out_cols = CL.unpack_columns(body)
            CL.hash_partition(out_cols, ["k"], nd)

        for nd in (2, 4, 8):
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                host_trip(nd)
                best = min(best, time.perf_counter() - t0)
            cell[f"host_nd{nd}_ms"] = round(best * 1000, 2)
            if nd > ndev_avail:
                cell[f"coll_nd{nd}_ms"] = None  # not enough devices
                continue
            mesh = make_mesh(nd)
            spec = NamedSharding(mesh, PSpec(AXIS))
            kd = jax.device_put(kh, spec)
            vd = jax.device_put(vh, spec)

            def inner(k, v):
                from presto_tpu import types as _PT
                from presto_tpu.batch import Column as _PCol

                def body(i, s):
                    b = PBatch(
                        {"k": _PCol(k ^ s, None, _PT.BIGINT, None),
                         "v": _PCol(v, None, _PT.DOUBLE, None)},
                        jnp.ones(k.shape, bool))
                    ob, _ov = EXC.repartition_batch(
                        b, [b.columns["k"]], nd, AXIS)
                    # REAL loop-carried dep through the exchanged data
                    # (a maskable dep lets XLA DCE the all_to_all)
                    return s + ob.columns["k"].data[0]
                return lax.fori_loop(0, K, body, jnp.int64(0))

            coll = jax.jit(DX._shard_mapped(
                inner, mesh, (PSpec(AXIS), PSpec(AXIS)), PSpec()))
            t = per_iter(timed(coll, kd, vd))
            cell[f"coll_nd{nd}_ms"] = round(t * 1000, 2)
        xout[f"r{rows >> 10}k"] = cell
    echo.shutdown()
    return xout


def dcn_child(coord, nproc, pid, ldev):
    """`--dcn-child` (spawned by dcn_sweep, never by hand): process
    `pid` of an `nproc`-process jax.distributed CPU mesh with `ldev`
    virtual local devices, timing the SAME repartition fori_loop the
    exchange sweep uses — but over the GLOBAL mesh, so every
    all_to_all crosses process boundaries through gloo loopback (the
    CI stand-in for the TPU DCN fabric).  Rank 0 prints ONE JSON line
    {"r64k": ms_per_iter, ...}; other ranks print nothing."""
    import numpy as np

    from presto_tpu.parallel import mesh as MH

    MH.init_multihost(coord, nproc, pid)

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as PSpec

    import presto_tpu  # noqa: F401  (x64 + compile cache)
    from presto_tpu.batch import Batch as PBatch
    from presto_tpu.parallel import dist_executor as DX
    from presto_tpu.parallel import exchange as EXC
    from presto_tpu.parallel.mesh import AXIS, make_mesh

    nd = nproc * ldev
    mesh = make_mesh(nd)
    rng = np.random.default_rng(0)
    rtt = timed(jax.jit(lambda x: x + 1.0), jnp.float32(1.0))
    out = {}
    for rexp in (16, 18, 20):
        rows = 1 << rexp
        kh = rng.integers(0, 1 << 31, rows).astype(np.int64)
        vh = rng.normal(size=rows)
        spec = NamedSharding(mesh, PSpec(AXIS))
        kd = DX._put(kh, spec)
        vd = DX._put(vh, spec)

        def inner(k, v):
            from presto_tpu import types as _PT
            from presto_tpu.batch import Column as _PCol

            def body(i, s):
                b = PBatch(
                    {"k": _PCol(k ^ s, None, _PT.BIGINT, None),
                     "v": _PCol(v, None, _PT.DOUBLE, None)},
                    jnp.ones(k.shape, bool))
                ob, _ov = EXC.repartition_batch(
                    b, [b.columns["k"]], nd, AXIS)
                return s + ob.columns["k"].data[0]
            return lax.fori_loop(0, K, body, jnp.int64(0))

        coll = jax.jit(DX._shard_mapped(
            inner, mesh, (PSpec(AXIS), PSpec(AXIS)), PSpec()))
        t = max(timed(coll, kd, vd) - rtt, 1e-9) / K
        out[f"r{rows >> 10}k"] = round(t * 1000, 2)
    if pid == 0:
        print(json.dumps(out), flush=True)


def dcn_sweep(nprocs=(2, 4), local_devices=2):
    """Multi-process collective lane: for each process count, boot that
    many `--dcn-child` subprocesses as one jax.distributed mesh and
    collect rank 0's per-iteration all_to_all walls.  Returns cells
    keyed like the exchange sweep ({"r64k": {"dcn_np2_ms": ..}, ...});
    a process count that fails to boot (no gloo, port trouble) is
    skipped — calibration degrades, never fails."""
    import socket
    import subprocess

    from presto_tpu.parallel.mesh import refuse_cpu_children

    refuse_cpu_children("roofline.py's multi-process sweep")
    cells = {}
    for nproc in nprocs:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        env["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count="
                     f"{local_devices}"])
        procs = [subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--dcn-child",
             f"127.0.0.1:{port}", str(nproc), str(pid),
             str(local_devices)],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, env=env) for pid in range(nproc)]
        try:
            line = procs[0].communicate(timeout=600)[0].strip()
            for p in procs[1:]:
                p.communicate(timeout=60)
            walls = json.loads(line.splitlines()[-1])
        except Exception:  # noqa: BLE001 — skip the lane, keep priors
            for p in procs:
                p.kill()
            print(json.dumps({"dcn_skipped": nproc}),
                  file=sys.stderr, flush=True)
            continue
        for label, ms in walls.items():
            cells.setdefault(label, {})[f"dcn_np{nproc}_ms"] = ms
    return cells


def calibrate(out_path=None, multiproc=False):
    """`tools/roofline.py --calibrate [--multiproc] [out.json]`: run
    ONLY the exchange sweep and fit a per-platform fusion-cost profile
    (plan/fusion_cost.profile_from_exchange_sweep) the engine loads via
    the PRESTO_TPU_FUSION_PROFILE env var or the `fusion_profile`
    session property.  Default output: fusion_profile_<platform>.json
    next to this script.

    `--multiproc` adds the dcn lane (dcn_sweep subprocess meshes) and
    writes `fusion_profile_<platform>-multiproc.json` — the numbers
    that seed DEFAULT_PROFILES["cpu-multiproc"]; on a TPU pod the same
    flag measures the real DCN fabric and replaces the documented
    tpu dcn priors."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import presto_tpu  # noqa: F401  (x64 + compile cache)
    from presto_tpu.plan import fusion_cost as FC

    rng = np.random.default_rng(0)
    rtt = timed(jax.jit(lambda x: x + 1.0), jnp.float32(1.0))

    def per_iter(t):
        return max(t - rtt, 1e-9) / K

    platform = jax.devices()[0].platform
    sweep = exchange_sweep(per_iter, rng)
    if multiproc:
        for label, cell in dcn_sweep().items():
            sweep.setdefault(label, {}).update(cell)
        platform = f"{platform}-multiproc"
    prof = FC.profile_from_exchange_sweep(sweep, platform)
    prof["calibrated_from"] = "tools/roofline.py --calibrate (exchange sweep)"
    prof["n_devices"] = len(jax.devices())
    prof["sweep"] = sweep
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            f"fusion_profile_{platform}.json")
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump(prof, f, indent=1, sort_keys=True)
    print(json.dumps({"profile": {k: v for k, v in prof.items()
                                  if k != "sweep"},
                      "path": out_path}), flush=True)
    return prof


def fleet_sweep(max_coord=4):
    """`tools/roofline.py --fleet [N]`: coordinator-dispatch saturation
    sweep for the multi-coordinator fleet (server/fleet.py, ISSUE 16).

    The serving tier's admission gate (concurrency slots + queue) makes
    a SINGLE front door admission-bound long before the executor is
    compute-bound; this sweep measures aggregate EXECUTE throughput as
    coordinators are added — in-process servers over ONE shared catalog
    and one FleetDirectory, signature-affinity proxying on — and reports
    where the marginal door stops paying (<10% QPS gain), i.e. where
    dispatch has saturated the machine rather than the admission gate.
    Prints ONE JSON line."""
    import threading

    import numpy as np

    import presto_tpu
    from presto_tpu import types as T
    from presto_tpu.client import connect_http
    from presto_tpu.server import PrestoTpuServer
    from presto_tpu.server import fleet as FL

    nrow, clients, per_client = 100_000, 8, 25
    out = {"metric": "fleet_dispatch_saturation", "rows": nrow,
           "clients": clients, "per_client": per_client,
           "cores": os.cpu_count()}

    def one_leg(ncoord):
        d = FL.FleetDirectory()
        servers = []
        base = None
        for i in range(ncoord):
            s = presto_tpu.connect(coalesce_max_batch=4)
            if base is None:
                base = s
                s.catalog.register_memory(
                    "t", {"k": T.BIGINT, "x": T.DOUBLE},
                    {"k": np.arange(nrow, dtype=np.int64),
                     "x": np.arange(nrow, dtype=np.float64) * 1.5})
            else:
                s.catalog = base.catalog
            srv = PrestoTpuServer(s).start()
            m = d.join(f"c{i}", srv.uri)
            srv.fleet = m
            srv.serving.attach_fleet(m)
            servers.append(srv)
        try:
            connect_http(servers[0].uri).execute(
                "PREPARE fq FROM SELECT count(*) c, sum(x) s FROM t "
                "WHERE k < ?")
            for srv in servers:  # per-door warm (compile + route maps)
                connect_http(srv.uri).execute("EXECUTE fq USING 10")
            lat, errs = [], []

            def run(cid):
                uri = servers[cid % ncoord].uri
                for i in range(per_client):
                    t0 = time.perf_counter()
                    try:
                        connect_http(uri).execute(
                            f"EXECUTE fq USING {100 + cid * 997 + i}"
                        ).fetchall()
                        lat.append(time.perf_counter() - t0)
                    except Exception as e:  # noqa: BLE001
                        errs.append(str(e))

            ths = [threading.Thread(target=run, args=(c,))
                   for c in range(clients)]
            t0 = time.perf_counter()
            for th in ths:
                th.start()
            for th in ths:
                th.join()
            wall = time.perf_counter() - t0
            lat.sort()
            return {"coordinators": ncoord,
                    "queries": len(lat), "failures": len(errs),
                    "qps": round(len(lat) / wall, 1),
                    "p50_ms": round(lat[len(lat) // 2] * 1000, 1),
                    "p99_ms": round(lat[int(len(lat) * 0.99)] * 1000, 1)}
        finally:
            for srv in servers:
                srv.stop()

    legs, prev_qps, saturated_at = {}, None, None
    n = 1
    while n <= max_coord:
        leg = one_leg(n)
        legs[f"c{n}"] = leg
        if prev_qps is not None and saturated_at is None \
                and leg["qps"] < prev_qps * 1.10:
            saturated_at = n  # the marginal door stopped paying
        prev_qps = leg["qps"]
        n *= 2
    out["legs"] = legs
    out["saturated_at_coordinators"] = saturated_at
    print(json.dumps(out), flush=True)
    return out


def sketch_sweep(per_iter, rng, nexps=(20, 22, 23)):
    """Sketch economics: exact distinct shuffle vs mergeable HLL states.

    Anchors the SKETCH lane (plan/agg_strategy.py, plan/distribute.py,
    plan/fusion_cost.py): per rows x cardinality cell, the exact leg is
    what a distributed count(DISTINCT x) must execute — NCHUNK per-shard
    dedup passes, a repartition of every surviving distinct value, one
    final grouping pass over the union — while the hll leg is what the
    sketch decomposition emits instead: per-shard hll_partial register
    rows folded by ONE elementwise-max merge (the op that lowers to
    lax.pmax on a fused mesh).  The exchange payloads are static facts
    of the two plans, not measurements: the exact edge ships up to
    per-shard-distinct x 8B values, the sketch edge always ships
    NCHUNK x m register bytes regardless of cardinality — that
    constant-size edge is the whole point, so it is recorded next to
    the measured compute."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from presto_tpu import types as PT
    from presto_tpu.batch import Column as PCol
    from presto_tpu.exec import kernels as KK

    NCHUNK = 8
    M = 1024  # the engine's default register count (~3.25% std error)
    sout = {"m_registers": M, "nchunk": NCHUNK}
    for nexp in nexps:
        n = 1 << nexp
        rows_c = n // NCHUNK
        cell = {}
        for ndv, label in ((1_000, "1k"), (100_000, "100k"),
                           (10_000_000, "10M")):
            keys = jnp.asarray(rng.integers(0, ndv, n).astype(np.int64))
            h = KK.hll_hash64(PCol(keys, None, PT.BIGINT, None))
            exact_ndv = int(np.unique(np.asarray(keys)).size)
            # static capacities the exact plan must provision: per-shard
            # distinct bound, then the union of all shards' survivors
            ccap = min(1 << max(min(ndv, rows_c) - 1, 1).bit_length(),
                       rows_c)
            gcap = min(1 << max(min(ndv, n) - 1, 1).bit_length(), n)

            @jax.jit
            def exact_leg(k):
                def body(i, s):
                    pk_parts = []
                    for c in range(NCHUNK):
                        kc = lax.dynamic_slice(k, (c * rows_c,),
                                               (rows_c,)) + s
                        gid, rep, ex, ov = KK.group_ids_static(kc, ccap)
                        pk_parts.append(kc[rep])
                    pk = jnp.concatenate(pk_parts)
                    gid, rep, ex, ov = KK.group_ids_static(pk, gcap)
                    # loop-carried data dependence: XLA cannot hoist
                    return ((rep[0] ^ gid[0]) & 1).astype(jnp.int64)
                return lax.fori_loop(0, K, body, jnp.int64(0))

            @jax.jit
            def hll_leg(h):
                def body(i, s):
                    hh = h ^ s
                    ones = jnp.ones((rows_c,), bool)
                    zg = jnp.zeros((rows_c,), jnp.int32)
                    regs = []
                    for c in range(NCHUNK):
                        hc = lax.dynamic_slice(hh, (c * rows_c,),
                                               (rows_c,))
                        regs.append(KK.hll_partial(hc, ones, zg, 1, m=M))
                    R = jnp.concatenate(regs)  # (NCHUNK, M) partials
                    est = KK.hll_merge_estimate(
                        R, None, jnp.zeros((NCHUNK,), jnp.int32), 1)
                    return (est[0] & 1).astype(jnp.uint64)
                return lax.fori_loop(0, K, body, jnp.uint64(0))

            # accuracy sanity next to the timing: one unperturbed
            # estimate vs the true cardinality of this cell's data
            regs0 = KK.hll_partial(h, jnp.ones((n,), bool),
                                   jnp.zeros((n,), jnp.int32), 1, m=M)
            est0 = int(KK.hll_merge_estimate(
                regs0, None, jnp.zeros((1,), jnp.int32), 1)[0])
            cell[f"ndv{label}"] = {
                "exact_ms": round(
                    per_iter(timed(exact_leg, keys)) * 1000, 2),
                "hll_ms": round(per_iter(timed(hll_leg, h)) * 1000, 2),
                "exact_exchange_kb": round(NCHUNK * ccap * 8 / 1024, 1),
                "hll_exchange_kb": round(NCHUNK * M / 1024, 1),
                "hll_err_pct": round(
                    abs(est0 - exact_ndv) / max(exact_ndv, 1) * 100, 2),
            }
        sout[f"n{n >> 20}M"] = cell
    return sout


def dynfilter_sweep(per_iter, rng, shapes=((1 << 22, 1 << 14),),
                    pcts=(1, 10, 50, 90)):
    """Dynamic filtering: what a runtime filter costs and what a
    compaction fed by it would give back.

    Per (probe rows, build rows) shape and probe selectivity: the
    build-side summary (rf_build) and the probe-side mask (rf_probe)
    per membership structure — the routing constants in exec/kernels.py
    (RF_EXACT_MAX, bloom sizing) — and build_probe at the probe's full
    capacity against the probe compacted to the survivors' power-of-two
    bound, its compaction included.  On this engine a static join's
    cost scales with capacity, so compaction is the only place pruned
    rows turn into wall-clock: a compiled program that only ANDs the
    mask into sel pays *_probe_ms and gets nothing (PERF.md section 6,
    PR 28).  The build is the whole key range [0, nbuild) and the probe
    is uniform over nbuild * 100 / pct values, so pct % of the probe
    rows survive."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from presto_tpu import types as PT
    from presto_tpu.batch import Column as PCol
    from presto_tpu.exec import kernels as KK

    def col(v):
        return PCol(v, None, PT.BIGINT, None)

    out = {}
    for nprobe, nbuild in shapes:
        live = jnp.ones((nbuild,), bool)
        bvals = jnp.asarray(rng.permutation(nbuild).astype(np.int64))
        sb = jnp.sort(bvals)
        shape_out = {}
        for pct in pcts:
            pvals = jnp.asarray(rng.integers(0, nbuild * 100 // pct, nprobe))
            cell = {}
            for structure in ("exact", "bloom"):
                @jax.jit
                def build_loop(bv):
                    def body(i, s):
                        summ = KK.rf_build(col(bv ^ s), live,
                                           structure=structure)
                        arr = summ["keys" if structure == "exact"
                                   else "bits"]
                        return arr[-1].astype(jnp.int64) & 1

                    return lax.fori_loop(0, K, body, jnp.int64(0))

                cell[f"{structure}_build_ms"] = round(
                    per_iter(timed(build_loop, bvals)) * 1000, 2)
                summary = KK.rf_build(col(bvals), live, structure=structure)
                kind = summary.pop("kind")

                # the summary rides as an argument: a 4-64 MB bitset
                # would otherwise be baked into the program as a constant
                @jax.jit
                def probe_loop(summ, pv):
                    def body(i, s):
                        m = KK.rf_probe({"kind": kind, **summ},
                                        col(pv ^ (s & 1)))
                        return jnp.sum(m).astype(jnp.int64)

                    return lax.fori_loop(0, K, body, jnp.int64(0))

                cell[f"{structure}_probe_ms"] = round(
                    per_iter(timed(probe_loop, summary, pvals)) * 1000, 2)
            # downstream: full-capacity join (off) vs compact-then-join
            ncap = 1 << max(int(np.ceil(np.log2(nprobe * pct / 100 * 1.25))),
                            12)
            mask = pvals < nbuild   # the exact mask, made for free here

            @jax.jit
            def join_full(pv):
                def body(i, s):
                    _o, lb, ub = KK.build_probe(sb, pv ^ s)
                    return (ub[0] - lb[0]).astype(jnp.int32)

                return lax.fori_loop(0, K, body, jnp.int32(0))

            @jax.jit
            def join_compacted(pv, m):
                def body(i, s):
                    # (s >= 0 always: keeps the compaction in the loop)
                    idx = KK.nonzero_i32(m & (s >= 0), ncap, 0)
                    _o, lb, ub = KK.build_probe(sb, pv[idx] ^ s)
                    return (ub[0] - lb[0]).astype(jnp.int32)

                return lax.fori_loop(0, K, body, jnp.int32(0))

            cell["join_off_ms"] = round(
                per_iter(timed(join_full, pvals)) * 1000, 2)
            cell["join_compacted_rows"] = ncap
            cell["join_compacted_ms"] = round(
                per_iter(timed(join_compacted, pvals, mask)) * 1000, 2)
            shape_out[f"sel{pct}"] = cell
        out[f"probe{nprobe}_build{nbuild}"] = shape_out
    return out


def _anchor(name, sweep):
    """Run ONE sweep of main() alone and print one JSON line, so that an
    anchor can be re-measured without paying for the whole roofline
    (ROOFLINE_K overrides the iteration count)."""
    global K
    K = int(os.environ.get("ROOFLINE_K", K))
    import jax
    import jax.numpy as jnp
    import numpy as np

    import presto_tpu  # noqa: F401  (x64 + compile cache)

    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    rtt = timed(jax.jit(lambda x: x + 1.0), jnp.float32(1.0))

    def per_iter(t):
        return max(t - rtt, 1e-9) / K

    out = {"device": str(dev), "platform": dev.platform, "iters": K,
           "rtt_ms": round(rtt * 1000, 1), name: sweep(per_iter, rng)}
    print(json.dumps(out), flush=True)


def sketch_anchor(nexps):
    """Standalone `--sketch` entry: ONLY the sketch sweep, so the
    docs/PERF.md anchor can be re-measured on a CPU host (the committed
    agg anchor used ROOFLINE_K=5)."""
    _anchor("sketch", lambda per_iter, rng: sketch_sweep(per_iter, rng, nexps))


#: (probe rows, build rows) of sf1_join's filters: orders probed with
#: customer's keys (Q3 df0, Q18 df1), lineitem with orders' (the rest)
DYNFILTER_SF1 = ((1_500_000, 150_000), (6_000_000, 1_500_000))


def dynfilter_anchor():
    """Standalone `dynfilter` entry: ONLY the dynamic-filter sweep, at
    the shapes of sf1_join's filters and the selectivities Q3 sees."""
    _anchor("dynfilter", lambda per_iter, rng: dynfilter_sweep(
        per_iter, rng, DYNFILTER_SF1, pcts=(10, 50)))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    import presto_tpu  # noqa: F401  (x64 + compile cache)
    from presto_tpu.exec import kernels as KK

    dev = jax.devices()[0]
    out = {"device": str(dev), "platform": dev.platform, "iters": K}

    rng = np.random.default_rng(0)
    rtt = timed(jax.jit(lambda x: x + 1.0), jnp.float32(1.0))
    out["rtt_ms"] = round(rtt * 1000, 1)

    def per_iter(t):
        return max(t - rtt, 1e-9) / K

    # --- stream bandwidth: read 2 arrays per iteration ----------------
    n = 1 << 24  # 16M f32 = 64MB per array
    b = jnp.asarray(rng.normal(size=n).astype(np.float32))
    c = jnp.asarray(rng.normal(size=n).astype(np.float32))

    @jax.jit
    def stream(b, c):
        def body(i, acc):
            return acc + jnp.sum(b + c * (1.0 + acc))  # carried dep
        return lax.fori_loop(0, K, body, jnp.float32(0.0))

    t = per_iter(timed(stream, b, c))
    out["stream_read_gbps"] = round(2 * 4 * n / t / 1e9, 1)

    # --- sort throughput (i32 / i64 keys) -----------------------------
    base32 = jnp.asarray(rng.integers(0, 1 << 30, n).astype(np.int32))

    @jax.jit
    def sort_loop(x):
        def body(i, s):
            return jnp.sort(x ^ s)[0]  # dep via s; fresh sort per iter
        return lax.fori_loop(0, K, body, jnp.int32(0))

    t = per_iter(timed(sort_loop, base32))
    out["sort_i32_mrows_s"] = round(n / t / 1e6, 1)
    base64_ = jnp.asarray(rng.integers(0, 1 << 62, n))

    @jax.jit
    def sort_loop64(x):
        def body(i, s):
            return jnp.sort(x ^ s)[0]
        return lax.fori_loop(0, K, body, jnp.int64(0))

    t = per_iter(timed(sort_loop64, base64_))
    out["sort_i64_mrows_s"] = round(n / t / 1e6, 1)

    # --- gather family: random vs sort-order --------------------------
    # Pins the routing constants in exec/gather.py (the crossover where
    # sorted staging beats the flat packed gather).  Swept over
    # index count x row width; each cell is ns/index so the table reads
    # directly against the ~45ns/random-index constant from the round-5
    # profile.
    nsrc = 1 << 23  # 8M source rows, the SF100 chunk shape
    gout = {}
    for width in (1, 2, 4, 8):
        src = jnp.asarray(
            rng.integers(0, 1 << 32, (nsrc, width)).astype(np.uint32))
        for mexp in (20, 22, 23):
            m = 1 << mexp
            ridx = jnp.asarray(rng.integers(0, nsrc, m).astype(np.int32))
            sidx = jnp.sort(ridx)

            @jax.jit
            def rand_loop(src, ridx):
                def body(i, s):
                    return src[(ridx + s) % nsrc][0, 0].astype(jnp.int32)
                return lax.fori_loop(0, K, body, jnp.int32(0))

            @jax.jit
            def sorted_loop(src, sidx):
                def body(i, s):
                    return src[jnp.clip(sidx + s, 0, nsrc - 1)][0, 0] \
                        .astype(jnp.int32)
                return lax.fori_loop(0, K, body, jnp.int32(0))

            cell = {}
            cell["random_ns_per_idx"] = round(
                per_iter(timed(rand_loop, src, ridx)) / m * 1e9, 2)
            cell["sorted_ns_per_idx"] = round(
                per_iter(timed(sorted_loop, src, sidx)) / m * 1e9, 2)
            gout[f"w{width}_m{m >> 20}M"] = cell
    out["gather"] = gout

    # sort-order materialization overhead: the planning sort + the
    # co-sort home, i.e. what request-order staging adds over presorted
    m = 1 << 23
    ridx = jnp.asarray(rng.integers(0, nsrc, m).astype(np.int32))

    @jax.jit
    def plan_loop(ridx):
        def body(i, s):
            sidx, pos = lax.sort(
                (ridx ^ s, jnp.arange(m, dtype=jnp.int32)), num_keys=1)
            return sidx[0] + pos[0]
        return lax.fori_loop(0, K, body, jnp.int32(0))

    out["gather_plan_sort_ms"] = round(
        per_iter(timed(plan_loop, ridx)) * 1000, 1)

    # --- ordering economics: sorted vs unsorted grouping / join build --
    # Anchors the ordering-aware routing (plan/properties.py): what a
    # grouping pass costs when the key arrives presorted (run-boundary
    # scan, no sort, no unpermute) vs the sort path, and what the
    # presorted-build join saves (1 of 3 sorts), per key count.
    oout = {}
    for nexp in (20, 22, 23):
        ng = 1 << nexp
        skey = jnp.asarray(np.sort(rng.integers(0, ng >> 3, ng))
                           .astype(np.int32))
        sel = jnp.ones((ng,), bool)

        @jax.jit
        def grp_sorted_path(k):
            def body(i, s):
                gid, rep, ex, ov = KK.group_ids_static(jnp.abs(k) + s,
                                                       1 << 17)
                return gid[0] + rep[0]
            return lax.fori_loop(0, K, body, jnp.int32(0))

        @jax.jit
        def grp_presorted(k):
            def body(i, s):
                gid, rep, ex, ov, g = KK.group_ids_presorted_static(
                    jnp.abs(k) + s, 1 << 17)
                return gid[0] + rep[0]
            return lax.fori_loop(0, K, body, jnp.int32(0))

        cell = {}
        cell["group_sort_ms"] = round(
            per_iter(timed(grp_sorted_path, skey)) * 1000, 2)
        cell["group_presorted_ms"] = round(
            per_iter(timed(grp_presorted, skey)) * 1000, 2)
        oout[f"n{ng >> 20}M"] = cell
    # presorted-build join at the Q3 shape
    npr_, nb_ = 6_000_000, 1_500_000
    probe_ = jnp.asarray(rng.integers(0, nb_, npr_).astype(np.int32))
    build_ = jnp.asarray(np.arange(nb_, dtype=np.int32))
    ident = jnp.arange(nb_, dtype=jnp.int32)

    @jax.jit
    def bp_presorted_loop(build, probe):
        def body(i, s):
            order, lb, ub = KK.build_probe(build, probe ^ s,
                                           build_order=ident)
            return (ub[0] - lb[0]).astype(jnp.int32)
        return lax.fori_loop(0, K, body, jnp.int32(0))

    oout["build_probe_presorted_q3_ms"] = round(
        per_iter(timed(bp_presorted_loop, build_, probe_)) * 1000, 1)
    out["ordering"] = oout

    # --- aggregation economics: reduction ratio x strategy ------------
    # Anchors plan/agg_strategy.py: what one GROUP BY pass costs under
    # each strategy as the partial stage's reduction ratio (rows /
    # groups) varies.  two_phase = 8 per-chunk partial groupings + a
    # final merge over the partial outputs (the chunked/cluster
    # pipeline); final_only = ONE global grouping pass (what the
    # runtime bypass degenerates to — pass-through rows cost nothing to
    # produce); presorted = the PR-3 run-boundary scan (no sort at
    # all).  The partial_agg_min_reduction default comes from the
    # measured two_phase/final_only crossover: below it the partial
    # stage costs a full grouping pass per chunk and buys back almost
    # nothing in the final stage.
    aout = {}
    crossovers = []
    NCHUNK = 8
    for nexp in (20, 22, 23):  # 1M / 4M / 8M keys
        n = 1 << nexp
        acell = {}
        for red in (1, 2, 10, 100):
            ndv = max(n // red, 1)
            keys = jnp.asarray(rng.integers(0, ndv, n).astype(np.int32))
            skeys = jnp.asarray(np.sort(np.asarray(keys)))
            vals = jnp.asarray(rng.normal(size=n).astype(np.float32))
            gcap = min(1 << max(ndv - 1, 1).bit_length(), n)
            ccap = min(gcap, n // NCHUNK)  # per-chunk groups bound
            rows_c = n // NCHUNK

            @jax.jit
            def two_phase(k, v):
                def body(i, s):
                    pk_parts = []
                    pv_parts = []
                    for c in range(NCHUNK):
                        kc = lax.dynamic_slice(k, (c * rows_c,),
                                               (rows_c,)) + s
                        vc = lax.dynamic_slice(v, (c * rows_c,),
                                               (rows_c,))
                        gid, rep, ex, ov = KK.group_ids_static(kc, ccap)
                        pv_parts.append(KK.segment_sum(vc, gid, ccap))
                        pk_parts.append(kc[rep])
                    pk = jnp.concatenate(pk_parts)
                    pv = jnp.concatenate(pv_parts)
                    gid, rep, ex, ov = KK.group_ids_static(pk, gcap)
                    fin = KK.segment_sum(pv, gid, gcap)
                    # real loop-carried data dependence: XLA cannot
                    # hoist or elide the grouping passes
                    return (rep[0] ^ fin[0].astype(jnp.int32)) & 1
                return lax.fori_loop(0, K, body, jnp.int32(0))

            @jax.jit
            def final_only(k, v):
                def body(i, s):
                    gid, rep, ex, ov = KK.group_ids_static(k + s, gcap)
                    fin = KK.segment_sum(v, gid, gcap)
                    return (rep[0] ^ fin[0].astype(jnp.int32)) & 1
                return lax.fori_loop(0, K, body, jnp.int32(0))

            @jax.jit
            def presorted(k, v):
                def body(i, s):
                    gid, rep, ex, ov, g = KK.group_ids_presorted_static(
                        k + s, gcap)
                    fin = KK.segment_sum(v, gid, gcap)
                    return (rep[0] ^ fin[0].astype(jnp.int32)) & 1
                return lax.fori_loop(0, K, body, jnp.int32(0))

            cell = {
                "two_phase_ms": round(
                    per_iter(timed(two_phase, keys, vals)) * 1000, 2),
                "final_only_ms": round(
                    per_iter(timed(final_only, keys, vals)) * 1000, 2),
                "presorted_ms": round(
                    per_iter(timed(presorted, skeys, vals)) * 1000, 2),
            }
            if cell["final_only_ms"] < cell["two_phase_ms"]:
                crossovers.append(red)
            acell[f"r{red}x"] = cell
        aout[f"n{n >> 20}M"] = acell
    # the largest reduction ratio at which single-phase still beat
    # two-phase: the bypass threshold should sit just above ratio 1
    # (never flip a genuinely reducing partial) but below the smallest
    # measured win — the committed default is 1.3
    aout["single_phase_won_at_ratios"] = sorted(set(crossovers))
    out["agg"] = aout

    # --- sketch economics: exact distinct shuffle vs HLL merge --------
    # (sketch_sweep above; `--sketch` re-measures it standalone)
    out["sketch"] = sketch_sweep(per_iter, rng)

    # --- compile economics: compile-ms vs fragment count x mult -------
    # Frames the exec/compile_cache.py design: what a cold chunked plan
    # pays in XLA compiles (per fragment, per bound-mult variant) and
    # what the persistent disk cache gives back on the next process.
    # Each "fragment" is a filter->group->reduce chain at a distinct
    # static capacity (mult quantizes capacity, so each mult variant is
    # a fresh executable — exactly the chunked runner's key structure).
    from presto_tpu.exec import compile_cache as CC

    # a constant no earlier run compiled: the uncached leg is honestly
    # uncached in whatever directory the persistent cache lives (the
    # engine's default, or JAX_COMPILATION_CACHE_DIR's — never one set
    # here)
    salt = float(time.time_ns() % 1_000_003)

    def fragment_fn(cap):
        def fn(x, key):
            sel = x > 0.0
            gid = jnp.clip(key, 0, 255)
            v = jnp.where(sel, x * 1.0001 + salt, 0.0)
            sums = jax.ops.segment_sum(v, gid, num_segments=256)
            top = jax.lax.top_k(jnp.where(sel, x, -jnp.inf),
                                min(cap, x.shape[0]))[0]
            return sums, top, jnp.sum(sel)
        return fn

    n = 1 << 20
    xa = jnp.asarray(rng.normal(size=n).astype(np.float32))
    ka = jnp.asarray(rng.integers(0, 256, n).astype(np.int32))
    # persist even sub-0.2s compiles so the cached leg measures the
    # disk-served path at this sweep's program sizes
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cout = {}
    for nfrag in (1, 2, 4):
        for mult in (1, 4):
            caps = [1024 * mult + 128 * i for i in range(nfrag)]

            def compile_all():
                t0 = time.perf_counter()
                for cap in caps:
                    CC.build_jit(fragment_fn(cap), example=(xa, ka))
                return (time.perf_counter() - t0) * 1000

            uncached = compile_all()   # fresh HLO: full XLA compile
            jax.clear_caches()         # drop in-memory, keep disk
            # trace again, executable loads from the persistent cache
            cached = compile_all()
            cout[f"f{nfrag}_m{mult}"] = {
                "uncached_ms": round(uncached, 1),
                "cached_ms": round(cached, 1)}
    cout["counters"] = {k: round(v, 1) if isinstance(v, float) else v
                        for k, v in CC.stats().items()}
    out["compile"] = cout

    # --- dynamic filtering: probe selectivity x membership structure --
    # (dynfilter_sweep above; `dynfilter` runs it alone at Q3/Q18 shapes)
    out["dynfilter"] = dynfilter_sweep(per_iter, rng)

    # --- exchange economics: host HTTP shuffle vs in-trace all_to_all --
    # (exchange_sweep above; `--calibrate` fits it into the fusion-cost
    # profile plan/fusion_cost.py loads)
    out["exchange"] = exchange_sweep(per_iter, rng)

    # --- query coalescing: B solo launches vs ONE vmap-batched launch -
    # Anchors the coalescer defaults (server/serving.py coalesce_window_
    # ms / coalesce_max_batch) with measurements instead of guesses:
    # what B separate dispatches of the prepared point-lookup shape
    # (q6-class filter + two reductions with a scalar parameter) cost
    # vs ONE jax.vmap-of-the-same-trace launch at batch B — the solo
    # column pays B launches and host syncs, the batched column one —
    # plus the pow2 padding discipline's waste (wall at
    # the padded bucket vs at the exact batch size).  Honest CPU
    # caveat (docs/PERF.md round 16): on CPU a single reduction
    # already saturates every core and dispatch costs ~40us, so the
    # solo column WINS here — the sweep exists to measure the
    # crossover on real chips, where per-dispatch overhead is ~ms.
    nrow_c = 1 << 16  # the serving bench's point-lookup scan scale
    ckeys = jnp.asarray(rng.integers(0, nrow_c, nrow_c).astype(np.int64))
    cvals = jnp.asarray(rng.normal(size=nrow_c))

    def point_fn(k):
        m = ckeys == k
        return (jnp.sum(m.astype(jnp.int64)),
                jnp.sum(jnp.where(m, cvals, 0.0)))

    solo_j = jax.jit(point_fn)

    def solo_wall(nb):
        ks = [jnp.int64((i * 7919) % nrow_c) for i in range(nb)]
        float(solo_j(ks[0])[0])  # warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for k in ks:
                c_, _s = solo_j(k)
                float(c_)  # force each launch home, like a real EXECUTE
            best = min(best, time.perf_counter() - t0)
        return best

    def batched_wall(nb):
        ks = jnp.asarray([(i * 7919) % nrow_c for i in range(nb)],
                         dtype=jnp.int64)
        f = jax.jit(jax.vmap(point_fn))  # one executable per batch size
        float(f(ks)[0][0])  # warm (the bucket's one-time compile)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            c_, _s = f(ks)
            float(c_[0])
            best = min(best, time.perf_counter() - t0)
        return best

    coout = {}
    for nb in (1, 2, 4, 8, 16, 32):
        sw = solo_wall(nb)
        bw = batched_wall(nb)
        coout[f"b{nb}"] = {"solo_ms": round(sw * 1000, 2),
                           "vmap_ms": round(bw * 1000, 2),
                           "speedup": round(sw / bw, 2)}
    pad = {}
    for nb in (3, 5, 9):
        exact = batched_wall(nb)
        bucket = batched_wall(1 << (nb - 1).bit_length())
        pad[f"b{nb}"] = {"exact_ms": round(exact * 1000, 2),
                         "padded_ms": round(bucket * 1000, 2),
                         "pad_overhead": round(bucket / exact, 2)
                         if exact else None}
    out["coalesce"] = {"rows": nrow_c, "batch": coout, "pad_waste": pad}

    # --- build_probe at TPC-H Q3 shape: 6M probe, 1.5M build ----------
    npr, nb = 6_000_000, 1_500_000
    probe = jnp.asarray(rng.integers(0, nb, npr).astype(np.int32))
    build = jnp.asarray(np.arange(nb, dtype=np.int32))

    @jax.jit
    def bp_loop(build, probe):
        def body(i, s):
            order, lb, ub = KK.build_probe(build, probe ^ s)
            return (ub[0] - lb[0]).astype(jnp.int32)
        return lax.fori_loop(0, K, body, jnp.int32(0))

    t = per_iter(timed(bp_loop, build, probe))
    out["build_probe_q3_shape_ms"] = round(t * 1000, 1)
    out["build_probe_mrows_s"] = round((npr + nb) / t / 1e6, 1)

    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    if "--dcn-child" in sys.argv:
        args = [a for a in sys.argv[1:] if not a.startswith("--")]
        dcn_child(args[0], int(args[1]), int(args[2]), int(args[3]))
    elif "--calibrate" in sys.argv:
        args = [a for a in sys.argv[1:] if not a.startswith("--")]
        calibrate(args[0] if args else None,
                  multiproc="--multiproc" in sys.argv)
    elif "--fleet" in sys.argv:
        args = [a for a in sys.argv[1:] if not a.startswith("--")]
        fleet_sweep(int(args[0]) if args else 4)
    elif "dynfilter" in sys.argv:
        dynfilter_anchor()
    elif "--sketch" in sys.argv:
        args = [a for a in sys.argv[1:] if not a.startswith("--")]
        sketch_anchor(tuple(int(a) for a in args) or (20, 22, 23))
    else:
        main()
