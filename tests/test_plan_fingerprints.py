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
commit (67372f0; q27 and q36 of `tpcds_store` on f4878b1, ISSUE 36's) by
running this file as a script there.  ISSUE 36 adds the two distributed
plans of the cell `ds100_mesh4_rollup`, which no parent has.
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

#: the same on f4878b1 (PR 35, where q27 and q36 became one GroupingSets
#: node each): the five cells' plans as the parent of ISSUE 36 planned them
PARENT.update({
    ("tpcds_store", "tpcds_q27", None):
        "371aeac883fbc42db9d17c360a2cd2dcda91750c3ce810fc5b0a5c6e62342763",
    ("tpcds_store", "tpcds_q36", None):
        "1f897143677b13ec122e353af447313353e45df635ed0306c13e3c395d61eb8c",
})

#: the cell ds100_mesh4_rollup's two distributed plans, new in ISSUE 36
#: (no parent has them: a change here is a change of the mesh's programs).
#: ISSUE 37 gives a repartitioned merge an estimate; at SF0.01 both
#: classes gather their states, so both pins stay ecc037c's.
MESH_ROLLUP = {
    ("tpcds_store_sf100_mesh4", "tpcds_q27", None):
        "948faf5191e4816f16f526b7af80ca85c9dd55a3dd0e36c14cf05f7821d9b828",
    ("tpcds_store_sf100_mesh4", "tpcds_q36", None):
        "e8b43f43843ec777d82871f6f373e72a2dae6818e38aaf6bbcb01a9474be37b7",
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
    factory = getattr(C, cfg["catalog_factory"].partition(":")[2])
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


@pytest.mark.parametrize("case", sorted(MESH_ROLLUP, key=str),
                         ids=lambda c: f"{c[0]}-{c[1]}")
def test_mesh_rollup_plan_keeps_its_bytes(case):
    assert fingerprint(*case) == MESH_ROLLUP[case]


#: sha256 of the lowered text (no debug info, as JAX's persistent cache
#: hashes it) of every program the TPC-H mesh cell builds at this scale,
#: the generators of its born-sharded scans among them, on f4878b1.  The
#: same text under the same module name is the same compile-cache key in
#: one environment: `sf1_mesh4_join` keeps hitting its entries although
#: `shard_generator` now takes its arguments from the table's grid.
PARENT_MESH_PROGRAMS = {
    "tpch_q1": [
        ["jit_fn_s2_eaf2a55c",
         "87743111648dbd8fd53d762e03b7cad7804a3fe1f95ae06f87078b01c1169c7b"],
        ["jit_shard_s2",
         "5dc7bfdce73633921d937c1e251eb56787f7205416b5220f164105498e33230a"]],
    "tpch_q3": [
        ["jit_fn_s2_07d4fa6a",
         "4181a28768a2902f5c6eca451854f71b47367e95efbe4312ff2b2be41e7db55f"],
        ["jit_shard_s2",
         "5ecbd63be135743a38d17eb14e98c2c56f19cbd3c26867d2a0745fa8012d0b50"],
        ["jit_shard_s2",
         "9089da0dd36ff61a9197f3e726e935b43b2550ab1c41974c9d3b681bd127efae"],
        ["jit_shard_s2",
         "d090539f9e2d83cfa58d69c96fa025459ab82b7d220678ab00e1b9e67792eff0"]],
}


def mesh_program_hashes(query):
    """Sorted (module name, sha256 of the lowered text) of the programs a
    cold mesh session builds for `query` of the cell sf1_mesh4_join."""
    import hashlib
    import re

    import jax

    import presto_tpu
    from presto_tpu import catalog as C
    from presto_tpu.exec import compile_cache as CC

    with open(os.path.join(BENCH, "configs", "tpch_sf1_mesh4.json")) as f:
        cfg = json.load(f)
    session = presto_tpu.connect(C.tpch_catalog(0.01, cache_dir=None))
    for k, v in cfg["session_properties"].items():
        session.set(k, v)
    with open(os.path.join(BENCH, "queries", query + ".sql")) as f:
        text = f.read().strip()
    built, real = [], CC.Executable.aot_compile

    def spy(self, example_args):
        shapes = jax.tree_util.tree_map(CC._shape_struct, example_args)
        lowered = self._jitted.lower(*shapes).as_text()
        name = re.search(r"module @(\S+)", lowered).group(1)
        built.append((name, hashlib.sha256(lowered.encode()).hexdigest()))
        return real(self, example_args)

    CC.clear()
    CC.Executable.aot_compile = spy
    try:
        assert session.sql(text).stats.execution_mode == "distributed"
    finally:
        CC.Executable.aot_compile = real
        CC.clear()
    return sorted(built)


@pytest.mark.parametrize("query", sorted(PARENT_MESH_PROGRAMS))
def test_tpch_mesh_programs_keep_their_compile_cache_keys(query):
    got = mesh_program_hashes(query)
    assert [list(p) for p in got] == PARENT_MESH_PROGRAMS[query]
    assert any(name.startswith("jit_shard_s") for name, _ in got)


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    for query in PARENT_MESH_PROGRAMS:
        print(f'    "{query}": {json.dumps(mesh_program_hashes(query), indent=8)},')
    for case in list(PARENT) + list(MESH_ROLLUP):
        try:
            print(f"    {case!r}:\n        \"{fingerprint(*case)}\",")
        except FileNotFoundError as e:     # a configuration newer than the tree
            print(f"    {case!r}: {e}")
