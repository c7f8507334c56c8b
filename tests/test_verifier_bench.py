"""Verifier tests (reference analog: the presto-verifier unit tests)."""

import presto_tpu
from presto_tpu.verifier import (Verifier, report, row_checksum,
                                 session_runner, sqlite_runner)


def test_row_checksum_order_insensitive():
    a = [(1, "x", 1.5), (2, "y", None)]
    b = [(2, "y", None), (1, "x", 1.5)]
    assert row_checksum(a) == row_checksum(b)
    assert row_checksum(a) != row_checksum([(1, "x", 1.5)])
    # float canonicalization absorbs sub-tolerance noise
    assert row_checksum([(1.00000001,)]) == row_checksum([(1.00000002,)])
    assert row_checksum([(1.0,)]) != row_checksum([(2.0,)])


def test_verifier_match_and_mismatch(tpch_catalog_tiny, tpch_sqlite_tiny):
    s = presto_tpu.connect(tpch_catalog_tiny)
    v = Verifier(sqlite_runner(tpch_sqlite_tiny), session_runner(s))
    results = v.run({
        "counts": "SELECT count(*) FROM nation",
        "joins": "SELECT n_name, count(*) AS c FROM customer, nation "
                 "WHERE c_nationkey = n_nationkey GROUP BY n_name",
        "bad_sql": "SELECT nocol FROM nation",
    })
    by_name = {r.name: r for r in results}
    assert by_name["counts"].state == "MATCH"
    assert by_name["joins"].state == "MATCH"
    # control (sqlite) fails first on bad SQL: CONTROL_FAIL wins
    assert by_name["bad_sql"].state == "CONTROL_FAIL"
    txt = report(results)
    assert "MATCH=2" in txt and "CONTROL_FAIL=1" in txt
    # test-side-only failure
    v2 = Verifier(lambda sql: [(1,)], session_runner(s))
    assert v2.verify_one("t", "SELECT nocol FROM nation").state == "TEST_FAIL"


def test_verifier_detects_difference(tpch_catalog_tiny):
    s = presto_tpu.connect(tpch_catalog_tiny)
    control = lambda sql: [(1,)]
    v = Verifier(control, session_runner(s))
    r = v.verify_one("x", "SELECT 2")
    assert r.state == "MISMATCH"
