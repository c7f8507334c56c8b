"""The deployment `tpch_sf1_mesh4` at SF0.01 on four of the eight virtual
CPU devices (ISSUE 29): tables born sharded on the mesh, float32 honoured
there, the mesh path under the engine's spans and counters, and a
fallback that does not pass for the mesh's answer.

(a) every table's shards equal the host generator's rows, range by range;
(b) Q1, Q3, Q18 through server -> client under the configuration's own
    session properties equal the benchmark's plain reference, and no scan
    reads a host column;
(c) spans and counters of the mesh path;
(d) a forced Undistributable answers, labelled `compiled`.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

import presto_tpu
from presto_tpu.catalog import TpchTable, tpch_catalog
from presto_tpu.client import StatementClient
from presto_tpu.connectors import tpch as H
from presto_tpu.observe import metrics as M
from presto_tpu.parallel import dist_executor as DX
from presto_tpu.parallel.mesh import make_mesh
from presto_tpu.plan import nodes as P
from presto_tpu.server import PrestoTpuServer
from presto_tpu.server.resource_groups import ResourceGroupManager

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
SF = 0.01
NDEV = 4

with open(os.path.join(BENCH, "configs", "tpch_sf1_mesh4.json")) as f:
    CONFIG = json.load(f)


def bench_file(name):
    with open(os.path.join(BENCH, name)) as f:
        return f.read().strip()


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference", os.path.join(BENCH, "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# (a) born sharded == the host generator, shard by shard
# ---------------------------------------------------------------------------


def decoded(col, data):
    if col.dictionary is not None:
        return np.asarray(col.dictionary.values[np.asarray(data)])
    return np.asarray(data)


@pytest.mark.parametrize("f32", [False, True], ids=["f64", "f32"])
@pytest.mark.parametrize("name", sorted(H.SCHEMAS))
def test_shards_equal_the_host_generator(name, f32):
    table = TpchTable(name, SF)
    cols = [c for c in table.schema if table.device_generable(c)]
    born = bool(cols)
    if not born:    # nation, region: host-fed, laid out by the same ranges
        cols = [c for c, t in table.schema.items() if not t.is_string]
    node = P.TableScan(name, {c: c for c in cols},
                       {c: table.schema[c] for c in cols})
    host = H.generate(name, SF)     # before read() is out of reach
    if born:
        table.read = table._full_table = None   # a call would raise
    batch = DX.sharded_scan(table, node, make_mesh(NDEV), NDEV, f32)

    edges, per = DX._shard_rows(table, NDEV)
    assert edges[0] == 0 and edges[-1] == table.row_count()
    assert len(edges) == NDEV + 1 and per >= max(np.diff(edges))
    if name in ("lineitem", "orders"):  # an order's lines lie with the order
        assert list(table.shard_grid(NDEV).order_edges) == \
            list(TpchTable("orders", SF).shard_grid(NDEV).order_edges)
    sel = np.asarray(batch.sel)
    assert sel.shape == (NDEV * per,)
    want_sel = (np.arange(per)[None, :] < np.diff(edges)[:, None]).reshape(-1)
    assert (sel == want_sel).all()
    assert len({s.device for s in batch.sel.addressable_shards}) == NDEV
    for c in cols:
        col = batch.columns[c]
        double = table.schema[c].name == "DOUBLE"
        assert col.data.dtype == (np.float32 if f32 and double else
                                  np.float64 if double else col.data.dtype)
        assert len({s.device for s in col.data.addressable_shards}) == NDEV
        for i, (a, b) in enumerate(zip(edges[:-1], edges[1:])):
            got = decoded(col, col.data[i * per:i * per + b - a])
            want = np.asarray(host[c][a:b])
            if double:
                # inside a jitted program XLA's CPU compiler turns the
                # generator's `cents / 100.0` into a multiplication: one
                # ulp, on one chip as here (TpchTable.device_columns)
                ulp = np.finfo(np.float32 if f32 else np.float64).eps
                np.testing.assert_allclose(got, want.astype(got.dtype),
                                           rtol=2 * ulp, atol=0,
                                           err_msg=f"{c} shard {i}")
            else:
                assert (got == want).all(), (c, i, got[:3], want[:3])


# ---------------------------------------------------------------------------
# (b)-(d) the served path under the configuration's session properties
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    """The benchmark's build_server, on a catalog whose every host read
    raises."""
    mp = pytest.MonkeyPatch()

    def host_read(self, *a, **kw):
        raise AssertionError(f"host read of {self.name}")

    mp.setattr(TpchTable, "read", host_read)
    mp.setattr(TpchTable, "_full_table", host_read)
    session = presto_tpu.connect(tpch_catalog(SF, cache_dir=None))
    for k, v in CONFIG["session_properties"].items():
        session.set(k, v)
    assert session.properties["mesh_devices"] == NDEV
    rgm = ResourceGroupManager()
    rgm.load_config(CONFIG["server"]["resource_groups"])
    srv = PrestoTpuServer(session, resource_groups=rgm,
                          max_concurrent=CONFIG["server"]["max_concurrent"])
    srv.start()
    try:
        yield session, srv
    finally:
        srv.stop()
        mp.undo()


def serve(session, srv, sql):
    """-> (rows as the client got them, the server's QueryStats row)."""
    rows = [list(r) for r in StatementClient(srv.uri, sql).rows()]
    for st in reversed(session.history_snapshot()):
        if st.sql.strip() == sql:
            return rows, st
    raise AssertionError("no history row")


def queries_total(mode):
    M.ensure_query_metrics()
    return M.REGISTRY.get("presto_tpu_queries_total").value(
        state="FINISHED", mode=mode)


@pytest.mark.parametrize("check", ["tpch_q1", "tpch_q3", "tpch_q18"])
def test_served_answers_equal_the_reference(served, reference, check):
    session, srv = served
    sql = bench_file(f"queries/{check}.sql")
    want = reference.streamed(SF, [check])[check]
    for _ in range(2):      # cold, then from the cached shards
        rows, st = serve(session, srv, sql)
        assert st.execution_mode == "distributed", st.fallback_reason
        assert not st.fallback_reason
        assert reference.rows_equal(rows, want,
                                    CONFIG["guarantees"]["float_rel"]), \
            (rows[:2], want[:2])
    assert st.compiles == 0     # the second pass built nothing
    assert "DYNAMIC" not in session._dist_cache.values()
    # float32 honoured: every DOUBLE the mesh holds is f32
    for t in session.catalog.tables.values():
        for c, col in getattr(t, f"_dist_cols_{NDEV}", {}).items():
            assert c == "__sel__" or t.schema[c].name != "DOUBLE", (t.name, c)
        for col in getattr(t, f"_dist_cols_{NDEV}_f32", {}).values():
            assert col.data.dtype == np.float32


def test_mesh_path_opens_the_engines_spans_and_fills_its_counters(served):
    session, srv = served
    sql = bench_file("queries/tpch_q3.sql")
    M.ensure_query_metrics()
    errors = M.REGISTRY.get(M.TRACE_ERRORS).value()
    on_mesh = queries_total("distributed")
    sql = sql.replace("LIMIT 10", "LIMIT 9")    # a new program: cold
    _, cold = serve(session, srv, sql)
    _, warm = serve(session, srv, sql)
    for st in (cold, warm):
        names = {sp["name"] for sp in st.trace_spans}
        assert {"mesh.feed", "exec.dispatch", "exec.wait_fetch",
                "exec.materialize", "execute"} <= names, names
        assert st.exchange_bytes_collective > 0
        assert st.exchange_bytes_sketch == 0
    assert cold.exchange_bytes_collective == warm.exchange_bytes_collective
    assert cold.compiles >= 1 and "xla_compile" in {
        sp["name"] for sp in cold.trace_spans}
    assert warm.compiles == 0
    assert queries_total("distributed") == on_mesh + 2
    assert M.REGISTRY.get(M.TRACE_ERRORS).value() == errors


def test_a_fallback_is_not_counted_as_the_meshes(served, reference,
                                                 monkeypatch):
    from presto_tpu.plan.distribute import Undistributable

    session, srv = served

    def refuse(*a, **kw):
        raise Undistributable("forced by the test")

    monkeypatch.setattr(DX, "distribute", refuse)
    monkeypatch.setattr(session, "_dist_cache", {})  # Q1 is built anew
    sql = bench_file("queries/tpch_q1.sql")
    on_mesh, compiled = queries_total("distributed"), queries_total("compiled")
    rows, st = serve(session, srv, sql)
    assert reference.rows_equal(
        rows, reference.streamed(SF, ["tpch_q1"])["tpch_q1"],
        CONFIG["guarantees"]["float_rel"])
    assert st.execution_mode == "compiled"
    assert "distributed: Undistributable: forced by the test" \
        in st.fallback_reason
    assert queries_total("compiled") == compiled + 1
    assert queries_total("distributed") == on_mesh
