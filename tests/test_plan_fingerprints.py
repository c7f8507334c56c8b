"""A query without a grouping set plans to the bytes it planned to before
GROUP BY ROLLUP / CUBE / GROUPING SETS became one plan node (ISSUE 35).

A compiled program is named and keyed by its plan's fingerprint
(`compile_cache.plan_fingerprint`: sha256 of plan/serde over `vars()` of
every node), so the same bytes are the same executables: no new entry in a
deployment's compile cache and nothing compiled again.  The texts are the
benchmark's own (`benchmarks/queries/`), planned under their
configurations' session properties at the tests' scale (TPC-H SF0.01,
TPC-DS SF0.01), the mesh configuration's as the distributed plans its
program is built from; the expected values were computed on the parent
commit (67372f0) by running this file as a script there.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")

#: (configuration, text, the type a `?` binds to) -> fingerprint on 67372f0
PARENT = {
    ("tpch_sf10", "tpch_q1", None):
        "6eaa3279300f0a3b3254e53e9f01adba2a02d7a86ce0d3a08968fa70d9da9633",
    ("tpch_sf10", "tpch_q6", None):
        "73705d6dffcc6894515fe0438929738932de79d42a1cb004cdc54aaa9590537a",
    ("tpch_sf10", "order_point", "INTEGER"):
        "2ff7d60de5d575ec29a82ff81df1b3b4d717fdd01f0b757686c85fae158c9beb",
    ("tpch_sf1", "tpch_q3", None):
        "380424f68c64044f4f2c5473de3a219348a75c9f7349aa142a1fee3d62d0dd0c",
    ("tpch_sf1", "tpch_q18", None):
        "5332cf901cea2f7ac0713cfbbe3a165983708f5ea97314eafc832174aef409ff",
    ("tpch_sf1_mesh4", "tpch_q1", None):
        "eaf2a55c55277ac8295839e59831ed98a3dca4393c317f9e63e173a8c72e5d09",
    ("tpch_sf1_mesh4", "tpch_q3", None):
        "07d4fa6a5a6a64e3e7763cfab881ad0781ae5d547bd44962d86cc4c5e2cc0a26",
    ("tpcds_store", "tpcds_q89", None):
        "3187bd4a81a271855bfea2913d372767f65785b09e72df355e4409831f4f1f05",
}


def fingerprint(config, query, param_type=None):
    import presto_tpu
    from presto_tpu import catalog as C
    from presto_tpu import types as T
    from presto_tpu.exec import compile_cache as CC
    from presto_tpu.exec.executor import plan_statement
    from presto_tpu.plan.distribute import distribute
    from presto_tpu.server.serving import _walk_params
    from presto_tpu.sql.parser import parse

    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        cfg = json.load(f)
    factory = C.tpcds_catalog if config.startswith("tpcds") else C.tpch_catalog
    session = presto_tpu.connect(factory(0.01, cache_dir=None))
    for k, v in cfg["session_properties"].items():
        session.set(k, v)
    with open(os.path.join(BENCH, "queries", query + ".sql")) as f:
        stmt = parse(f.read().strip())
    for p in _walk_params(stmt):        # as the serving tier binds a `?`
        p.type_ = getattr(T, param_type)
    plan = plan_statement(session, stmt)
    if cfg["session_properties"].get("distributed"):   # the mesh's program
        plan = distribute(plan, session,
                          cfg["session_properties"]["mesh_devices"])
    return CC.plan_fingerprint((plan.root, sorted(plan.subplans.items())))


@pytest.mark.parametrize("case", sorted(PARENT, key=str),
                         ids=lambda c: f"{c[0]}-{c[1]}")
def test_plan_without_grouping_set_keeps_its_bytes(case):
    assert fingerprint(*case) == PARENT[case]


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    for case in PARENT:
        print(f"    {case!r}:\n        \"{fingerprint(*case)}\",")
