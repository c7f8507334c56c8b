"""The client protocol server.

Reference parity: server/protocol/StatementResource.java:88-134 —
`POST /v1/statement` returns QueryResults{id, nextUri, columns, data,
stats, error}; the client polls nextUri
(`GET /v1/statement/{queryId}/{token}`) until no nextUri remains;
`DELETE /v1/statement/{queryId}` cancels.  Tokens are cumulative page
sequence numbers: re-fetching a token re-serves the same page
(at-least-once delivery with client dedup, the elasticity seam of
SURVEY.md §2.6).  Also serves the introspection endpoints
(server/QueryResource.java `/v1/query`, ClusterStatsResource
`/v1/cluster`), the Prometheus scrape (`/v1/metrics`,
observe/metrics.py — the primary metrics surface; /v1/info remains as
the JSON compatibility view), per-query chrome traces
(`/v1/query/{id}/trace`, observe/trace.py — loads in Perfetto), node
info/status for the failure detector, and the graceful-shutdown state
machine (server/GracefulShutdownHandler.java).

Execution is in-process on the embedded engine (the coordinator IS the
mesh driver under SPMD — workers are TPU chips, not task servers; the
reference ships plan fragments to worker JVMs, SURVEY.md §3.1).

Fault tolerance (docs/ROBUSTNESS.md): with a fleet attached, in-flight
read queries journal their resumable state (parallel/journal.py) and a
peer coordinator's death triggers ADOPTION on its ring successor — the
adopted query re-runs under its ORIGINAL query id, so a client polling
nextUri through any surviving door completes: the unknown-qid chain
falls through proxied_owner -> journal_lookup, which proxies to the
entry's (re-homed) coordinator or holds the client in RUNNING while
the adoption races.
"""

from __future__ import annotations

import dataclasses
import json
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional

from presto_tpu.observe import trace as TR

PAGE_ROWS = 4096  # rows per protocol page (client re-chunks as needed)

# the ONLY timing constants of the protocol loop (the serving lint rule
# forbids inline timeout literals in this module): first-response grace
# for fast queries, the long-poll bound, and the drain poll period
FIRST_RESPONSE_GRACE_S = 0.05
LONG_POLL_S = 1.0
DRAIN_POLL_S = 0.05
DEFAULT_DRAIN_TIMEOUT_S = 30.0


@dataclasses.dataclass
class _QueryJob:
    query_id: str
    sql: str
    state: str = "QUEUED"  # QUEUED RUNNING FINISHED FAILED CANCELED
    columns: Optional[List[dict]] = None
    rows: Optional[list] = None
    error: Optional[str] = None
    error_code: Optional[str] = None  # e.g. QUEUE_FULL (clean shed)
    resource_group: str = ""
    stats: Optional[dict] = None
    done: threading.Event = dataclasses.field(default_factory=threading.Event)
    cancel: threading.Event = dataclasses.field(default_factory=threading.Event)
    finished_at: float = 0.0  # time.monotonic() at finish(): _prune_done's age
    delivered: bool = False  # last page or terminal error served at least once

    def finish(self) -> None:
        self.finished_at = time.monotonic()
        self.done.set()


class PrestoTpuServer:
    """Embeds a Session behind the REST protocol; queries run on a worker
    thread pool so the HTTP loop never blocks on execution."""

    def __init__(self, session, host: str = "127.0.0.1", port: int = 0,
                 max_concurrent: int = 4, resource_groups=None,
                 authenticator=None, serving=None, fleet=None):
        from presto_tpu.server.serving import ServingTier

        self.session = session
        self.resource_groups = resource_groups  # ResourceGroupManager | None
        # the serving tier (server/serving.py): admission over the
        # resource-group tree + the result cache; every submit routes
        # through it (docs/SERVING.md)
        self.serving = serving if serving is not None else ServingTier(
            session, resource_groups=resource_groups)
        if serving is not None and resource_groups is None:
            self.resource_groups = serving.resource_groups
        # coordinator fleet (server/fleet.FleetMember): the front door
        # routes same-signature EXECUTEs (and cacheable reads) to their
        # ring owner — proxy by default, 307-redirect for clients that
        # follow it — so coalescing batches and cache hits concentrate
        # instead of fragmenting 1/N per coordinator.  `fleet=None` is
        # the single-coordinator path, byte-identical to round 18.
        self.fleet = None
        self._journal = None
        if fleet is not None:
            self.attach_fleet(fleet)
        self._proxied: Dict[str, str] = {}  # proxied query id -> owner uri
        self._proxied_lock = threading.Lock()
        self.fleet_counters = {"proxied": 0, "redirected": 0,
                               "proxy_failures": 0, "journal_writes": 0,
                               "queries_adopted": 0, "adoption_ms": 0}
        # security.PasswordAuthenticator | None — when set, every /v1
        # request must carry HTTP Basic credentials (reference:
        # password authenticators wired through http-server.authentication)
        self.authenticator = authenticator
        self.jobs: Dict[str, _QueryJob] = {}
        self.jobs_lock = threading.Lock()
        self.node_id = f"node_{uuid.uuid4().hex[:8]}"
        from presto_tpu.observe import trace as TR

        self.start_time = TR.wall_s()
        self.shutting_down = threading.Event()
        self.active_queries = 0
        self._sema = threading.Semaphore(max_concurrent)
        handler = _make_handler(self)
        self.httpd = ThreadingHTTPServer((host, port), handler)
        self.port = self.httpd.server_address[1]
        self.host = host
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle -----------------------------------------------------
    def start(self) -> "PrestoTpuServer":
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()

    def graceful_shutdown(self,
                          timeout: float = DEFAULT_DRAIN_TIMEOUT_S) -> None:
        """Drain: refuse new queries, cancel QUEUED (admitted-but-not-
        started) jobs with a terminal CANCELED state their waiting
        clients can read, wait for RUNNING ones, stop (reference:
        GracefulShutdownHandler — worker waits for active tasks before
        exiting; queued queries are failed with SERVER_SHUTTING_DOWN)."""
        self.shutting_down.set()
        # wakes every admission waiter: their jobs turn CANCELED and
        # decrement active_queries, so the drain below only ever waits
        # on genuinely RUNNING queries
        self.serving.drain()
        deadline = time.monotonic() + timeout
        ticker = threading.Event()  # never set: a lint-clean sleep
        while time.monotonic() < deadline:
            with self.jobs_lock:
                if self.active_queries == 0:
                    break
            ticker.wait(timeout=DRAIN_POLL_S)
        self.stop()

    @property
    def uri(self) -> str:
        return f"http://{self.host}:{self.port}"

    # -- query execution ----------------------------------------------
    def submit(self, sql: str) -> _QueryJob:
        if self.shutting_down.is_set():
            raise RuntimeError("server is shutting down")
        job = _QueryJob(query_id=f"qs_{uuid.uuid4().hex[:12]}", sql=sql)
        with self.jobs_lock:
            self.jobs[job.query_id] = job
            self.active_queries += 1
        threading.Thread(target=self._run_job, args=(job,), daemon=True).start()
        return job

    def _run_job(self, job: _QueryJob) -> None:
        from presto_tpu.server.resource_groups import QueryRejected

        slot = None
        try:
            # admission BEFORE the worker semaphore: a query queued on
            # a saturated group must not hold a worker slot (it would
            # starve other groups — head-of-line blocking).  The abort
            # hook drains the wait on client cancel AND on graceful
            # shutdown (queued jobs then end CANCELED, terminally).
            with TR.span("admission.wait", statement_id=job.query_id):
                slot = self.serving.admit(self.session.user,
                                          self.session.source,
                                          abort=job.cancel.is_set)
        except QueryRejected as e:
            if e.code == "SERVER_SHUTTING_DOWN" or job.cancel.is_set():
                job.error = "Query was canceled: server is shutting down"
                job.error_code = e.code
                job.state = "CANCELED"
            else:  # QUEUE_FULL shed / QUEUE_TIMEOUT: a clean query error
                job.error = str(e)
                job.error_code = e.code
                job.state = "FAILED"
            job.finish()
            with self.jobs_lock:
                self.active_queries -= 1
            return
        except Exception as e:  # noqa: BLE001 — rejection is a query error
            job.error = f"{type(e).__name__}: {e}"
            job.state = "FAILED"
            job.finish()
            with self.jobs_lock:
                self.active_queries -= 1
            return
        if slot is not None:
            job.resource_group = slot.group.full_name
        t0 = time.monotonic()
        journaled = False
        with self._sema:
            try:
                if job.cancel.is_set():
                    job.state = "CANCELED"
                    return
                head = job.sql.lstrip().upper()
                if head.startswith(("START", "COMMIT", "ROLLBACK")):
                    # the protocol server multiplexes ONE session across
                    # clients; an explicit transaction here could roll
                    # back another client's acknowledged writes
                    raise RuntimeError(
                        "explicit transactions are not supported over the "
                        "shared protocol server; use an embedded session")
                job.state = "RUNNING"
                if self._journal is not None:
                    first = job.sql.lstrip().split(None, 1)[0].upper()
                    if first in ("SELECT", "WITH", "VALUES", "EXECUTE"):
                        # journal the in-flight query (read statements
                        # only: adoption RE-EXECUTES, so a journaled
                        # write could double-apply) under its protocol
                        # query id — the id the client's nextUri holds
                        from presto_tpu.parallel import journal as _J

                        ent = _J.entry_for(job.query_id, job.sql,
                                           self.fleet.coord_id,
                                           self.session.properties)
                        if self._journal.write(ent):
                            journaled = True
                            self.fleet_counters["journal_writes"] += 1
                            self.fleet.replicate_journal(ent)
                self.session.apply_property_manager()
                cached = self.serving.result_lookup(job.sql)
                if cached is not None:
                    # identical re-submitted query served straight from
                    # the result cache — no parse, no plan, no execution
                    self._finish_cached(job, cached, slot)
                    return
                result = self.session.sql(job.sql)
                if job.cancel.is_set():
                    job.state = "CANCELED"
                    return
                job.columns = [{"name": n, "type": str(t).lower()}
                               for n, t in result.columns]
                with TR.span("result.rows", statement_id=job.query_id):
                    job.rows = [list(r) for r in result.rows]
                st = result.stats  # this query's stats (not last_stats —
                job.stats = {      # concurrent jobs would race)
                    "state": "FINISHED",
                    "elapsedTimeMillis": int((st.total_ns if st else 0) / 1e6),
                    "processedRows": len(job.rows),
                    "peakMemoryBytes": getattr(st, "peak_memory_bytes", 0),
                    "spilledBytes": getattr(st, "spilled_bytes", 0),
                }
                job.state = "FINISHED"
                if st is not None:
                    # admission facts ride the query's own stats object
                    # (already in session.history) for /v1/query/{id}
                    st.resource_group = job.resource_group
                    if slot is not None:
                        st.admission_wait_ms = slot.wait_ms
                if self.serving.result_cache is not None:
                    first = job.sql.lstrip().split(None, 1)[0].upper()
                    if first in ("SELECT", "WITH", "VALUES"):
                        self.serving.result_store(job.sql, job.columns,
                                                  job.rows)
                    elif first in ("INSERT", "DELETE", "UPDATE", "CREATE",
                                   "DROP", "ALTER", "REFRESH"):
                        # write/DDL statement: explicit invalidation on
                        # top of the catalog-version keying, SCOPED to
                        # the written tables when the statement parses
                        # (with a fleet attached this also broadcasts
                        # the same table set to peers)
                        from presto_tpu.server.serving import write_targets

                        self.serving.on_write_statement(
                            tables=write_targets(job.sql))
                if self.fleet is not None and job.sql.lstrip().split(
                        None, 1)[0].upper() == "PREPARE":
                    # best-effort signature replication: an EXECUTE
                    # routed or failed over to any peer should find the
                    # prepared name (a peer it never reached answers
                    # the typed unknown-statement error instead)
                    self.fleet.replicate_prepare(job.sql)
            except Exception as e:  # noqa: BLE001 — protocol reports all errors
                job.error = f"{type(e).__name__}: {e}"
                job.state = "FAILED"
            finally:
                # charge the query's elapsed time as CPU usage for
                # the group's soft/hard CPU limits (reference:
                # per-query cpuUsageMillis charged on completion)
                self.serving.release(slot, cpu_s=time.monotonic() - t0)
                if journaled:
                    # alive to observe the outcome => clean up; only a
                    # coordinator that DIED leaves entries for adoption
                    self._journal.remove(job.query_id)
                job.finish()
                with self.jobs_lock:
                    self.active_queries -= 1

    def _finish_cached(self, job: _QueryJob, cached, slot) -> None:
        """Complete a job from a result-cache entry, recording a history
        stats row so /v1/query shows the (cached) execution."""
        from presto_tpu.observe.stats import QueryMonitor

        columns, rows, _size = cached
        job.columns = columns
        job.rows = rows
        mon = QueryMonitor.begin(self.session, job.sql)
        mon.stats.execution_mode = "cached"
        mon.stats.result_cache_hit = 1
        mon.stats.resource_group = job.resource_group
        if slot is not None:
            mon.stats.admission_wait_ms = slot.wait_ms
        mon.finish(rows)
        job.stats = {"state": "FINISHED", "elapsedTimeMillis": 0,
                     "processedRows": len(rows), "peakMemoryBytes": 0,
                     "spilledBytes": 0, "resultCacheHit": True}
        job.state = "FINISHED"

    # -- fleet front door ---------------------------------------------
    def route_target(self, sql: str) -> Optional[str]:
        """The owning coordinator's URI when this statement belongs to a
        ring peer, else None (execute locally).  Routing is an
        optimization: any error resolves to local execution."""
        if self.fleet is None:
            return None
        mode = str(self.session.properties.get(
            "fleet_affinity", "proxy")).lower()
        if mode == "off":
            return None
        from presto_tpu.server import fleet as FL

        key = FL.affinity_key(sql)
        if key is None:
            return None
        return self.fleet.owner_uri(key)

    def proxy_submit(self, sql: str, owner: str) -> Optional[dict]:
        """Forward a statement to its owning coordinator and re-home the
        payload's URIs so the (dumb) client keeps talking to THIS
        server; follow-up polls forward through the proxied-query map.
        None on any proxy failure — the caller executes locally."""
        import urllib.request

        from presto_tpu.server import fleet as FL

        try:
            req = urllib.request.Request(
                f"{owner}/v1/statement", data=sql.encode(), method="POST")
            with urllib.request.urlopen(
                    req, timeout=FL.PROXY_TIMEOUT_S) as resp:
                payload = json.loads(resp.read().decode())
        except Exception:  # noqa: BLE001 — degrade to local execution
            self.fleet_counters["proxy_failures"] += 1
            return None
        qid = payload.get("id")
        if qid:
            with self._proxied_lock:
                self._proxied[qid] = owner
        self.fleet_counters["proxied"] += 1
        self.fleet.counters["routed_away"] += 1
        return self._rehome(payload, owner)

    def proxy_fetch(self, owner: str, path: str,
                    method: str = "GET") -> Optional[dict]:
        """Forward a follow-up (page poll / cancel) for a proxied query
        to its owner; None when the owner is unreachable."""
        import urllib.request

        from presto_tpu.server import fleet as FL

        from presto_tpu.parallel import faults as F

        if F.client_plan().match("client", "PROXY",
                                 f"{owner}{path}") is not None:
            # scripted coordinator-death-mid-poll: the owner door is
            # unreachable at exactly the nth proxied poll (any action)
            self.fleet_counters["proxy_failures"] += 1
            return None
        try:
            req = urllib.request.Request(f"{owner}{path}", method=method)
            with urllib.request.urlopen(
                    req, timeout=FL.PROXY_TIMEOUT_S) as resp:
                return self._rehome(json.loads(resp.read().decode()),
                                    owner)
        except Exception:  # noqa: BLE001
            self.fleet_counters["proxy_failures"] += 1
            return None

    def _rehome(self, payload: dict, owner: str) -> dict:
        for k in ("nextUri", "infoUri"):
            v = payload.get(k)
            if isinstance(v, str) and v.startswith(owner):
                payload[k] = self.uri + v[len(owner):]
        return payload

    def proxied_owner(self, qid: str) -> Optional[str]:
        with self._proxied_lock:
            return self._proxied.get(qid)

    # -- journaled failover (parallel/journal.py) ----------------------
    def attach_fleet(self, fleet) -> None:
        """Wire a FleetMember into this door: ring-affinity routing in
        the serving tier, query journaling + adoption (with `query_journal`
        not explicitly off, this door journals in-flight read queries
        and adopts a dead peer's journaled queries when discovery/gossip
        declares the death — the ring successor is the deterministic
        adopter), and the peer journal/death subscriptions.  `fleet=None`
        at construction is the single-coordinator path, byte-identical
        to round 18."""
        from presto_tpu.parallel import journal as _J

        self.fleet = fleet
        self.serving.attach_fleet(fleet)
        if _J.enabled(self.session.properties, fleet_attached=True):
            self._journal = _J.QueryJournal(
                _J.root_dir(self.session.properties),
                coord_id=fleet.coord_id)
        fleet.subscribe(on_death=self._on_peer_death,
                        on_journal=self._on_peer_journal)

    def _on_peer_journal(self, entry: dict) -> None:
        """Best-effort replication receive: persist a peer's journal
        entry locally so adoption works even when the journal root is
        not a genuinely shared directory (idempotent when it is)."""
        if self._journal is not None and entry.get("queryId"):
            self._journal.write(dict(entry))

    def _on_peer_death(self, dead_id: str) -> None:
        """Fleet death relay (discovery.watch_fleet -> directory.leave
        -> on_death): the ring SUCCESSOR of the dead coordinator — a
        pure function of the post-leave ring, so every survivor picks
        the same adopter — resumes its journaled in-flight queries."""
        if self._journal is None or self.fleet is None \
                or not self.fleet.should_adopt(dead_id):
            return
        threading.Thread(target=self._adopt_from, args=(dead_id,),
                         daemon=True).start()

    def _adopt_from(self, dead_id: str) -> None:
        t0 = time.monotonic()
        adopted = 0
        for e in self._journal.entries(coord=dead_id):
            qid = str(e.get("queryId", ""))
            sql = str(e.get("sql", ""))
            if not qid or not sql:
                continue
            with self.jobs_lock:
                if qid in self.jobs:
                    continue  # already adopted (or raced a re-submit)
                job = _QueryJob(query_id=qid, sql=sql)
                self.jobs[qid] = job
                self.active_queries += 1
            # re-home the entry FIRST: peers' journal_lookup proxies
            # the client's polls here while the query re-runs
            e["coord"] = self.fleet.coord_id
            if self._journal.write(e):
                self.fleet.replicate_journal(e)
            adopted += 1
            self._run_adopted(job, e)
        if adopted:
            from presto_tpu.observe import metrics as M

            self.fleet_counters["queries_adopted"] += adopted
            self.fleet_counters["adoption_ms"] += max(
                int((time.monotonic() - t0) * 1000.0), 1)
            M.record_recovery("queries_adopted", adopted)

    def _run_adopted(self, job: _QueryJob, entry: dict) -> None:
        """Execute one adopted query under its ORIGINAL query id.  A
        journaled durable-exchange dir routes through the session's
        resume path (completed tasks replay from the durable store);
        otherwise the statement re-executes — reads only, so re-running
        is safe (see the journaling filter in _run_job)."""
        try:
            job.state = "RUNNING"
            if entry.get("ddir") and hasattr(self.session, "resume_sql"):
                result = self.session.resume_sql(
                    job.sql, entry.get("ddir"),
                    int(entry.get("attempt", 0)),
                    query_id=job.query_id)
            else:
                result = self.session.sql(job.sql)
            job.columns = [{"name": n, "type": str(t).lower()}
                           for n, t in result.columns]
            job.rows = [list(r) for r in result.rows]
            job.stats = {"state": "FINISHED",
                         "processedRows": len(job.rows),
                         "adopted": True}
            job.state = "FINISHED"
        except Exception as e:  # noqa: BLE001 — adoption reports all errors
            job.error = f"{type(e).__name__}: {e}"
            job.state = "FAILED"
        finally:
            self._journal.remove(job.query_id)
            job.finish()
            with self.jobs_lock:
                self.active_queries -= 1

    def journal_lookup(self, qid: str, path: str) -> Optional[dict]:
        """Coordinator-death-mid-poll fallback for the unknown-qid
        chain: a query id that appears in the fleet journal is in
        flight SOMEWHERE — proxy the poll to the entry's (re-homed)
        coordinator, then to the dead owner's ring successor, and as a
        last resort hold the client in RUNNING against THIS door while
        the adoption races the poll."""
        if self._journal is None or self.fleet is None:
            return None
        e = self._journal.read(qid)
        if e is None:
            return None
        coord = str(e.get("coord", ""))
        if coord and coord != self.fleet.coord_id:
            target = self.fleet.coordinator_uri(coord)
            if target is not None and target != self.uri:
                got = self.proxy_fetch(target, path)
                if got is not None:
                    return got
            # journaled owner unreachable (it probably just died):
            # its ring successor is the deterministic adopter
            succ = self.fleet.adopter_of(coord)
            if succ and succ != self.fleet.coord_id:
                target = self.fleet.coordinator_uri(succ)
                if target is not None and target != self.uri:
                    got = self.proxy_fetch(target, path)
                    if got is not None:
                        return got
        return {"id": qid,
                "infoUri": f"{self.uri}/v1/query/{qid}",
                "stats": {"state": "RUNNING"},
                "nextUri": f"{self.uri}{path}"}

    # -- protocol payloads --------------------------------------------
    def results_payload(self, job: _QueryJob, token: int) -> dict:
        base = f"{self.uri}/v1/statement/{job.query_id}"
        out = {"id": job.query_id,
               "infoUri": f"{self.uri}/v1/query/{job.query_id}"}
        if job.state in ("QUEUED", "RUNNING"):
            out["stats"] = {"state": job.state}
            out["nextUri"] = f"{base}/{token}"  # poll same token until data
            return out
        if job.state == "FAILED":
            out["error"] = {"message": job.error,
                            "errorCode": job.error_code or "QUERY_FAILED"}
            out["stats"] = {"state": "FAILED"}
            job.delivered = True
            return out
        if job.state == "CANCELED":
            out["stats"] = {"state": "CANCELED"}
            if job.error:  # drained by graceful shutdown: say why
                out["error"] = {"message": job.error,
                                "errorCode": job.error_code or "USER_CANCELED"}
            job.delivered = True
            return out
        start = token * PAGE_ROWS
        page = job.rows[start:start + PAGE_ROWS]
        out["columns"] = job.columns
        if page:
            out["data"] = page
        out["stats"] = job.stats
        if start + PAGE_ROWS < len(job.rows):
            out["nextUri"] = f"{base}/{token + 1}"
        else:
            job.delivered = True
            self._prune_done()
        return out

    MAX_DONE_JOBS = 64
    CLIENT_TIMEOUT_S = 300.0  # the reference's query.client.timeout

    def _prune_done(self) -> None:
        """The one retention rule for finished jobs (reference:
        QueryTracker, execution/QueryTracker.java).  DELIVERED ones (last
        page or terminal error served) are kept to the newest
        MAX_DONE_JOBS, so a recent page stays refetchable
        (at-least-once); UNDELIVERED ones are kept until
        CLIENT_TIMEOUT_S after they finished and then dropped as
        abandoned, so a slow reader never loses rows the server computed.
        The bound: MAX_DONE_JOBS delivered results plus the undelivered
        results of the last CLIENT_TIMEOUT_S, which admission bounds
        (hardConcurrencyLimit + maxQueued; one per closed-loop client)."""
        now = time.monotonic()
        delivered, abandoned = [], []
        with self.jobs_lock:
            for qid, j in self.jobs.items():
                if not j.done.is_set():
                    continue
                if j.delivered:
                    delivered.append(qid)
                elif now - j.finished_at > self.CLIENT_TIMEOUT_S:
                    abandoned.append(qid)
            for qid in delivered[:-self.MAX_DONE_JOBS] + abandoned:
                del self.jobs[qid]

    def query_list_payload(self) -> list:
        out = []
        for st in self.session.history_snapshot():
            out.append({
                "queryId": st.query_id, "query": st.sql, "state": st.state,
                "executionMode": st.execution_mode,
                "elapsedTimeMillis": int(st.total_ns / 1e6),
                "outputRows": st.output_rows, "error": st.error,
                "peakMemoryBytes": st.peak_memory_bytes,
                "createTime": st.create_time, "endTime": st.end_time,
            })
        return out

    def query_detail_payload(self, st) -> dict:
        """Query-detail view for the web UI's plan/stage/timeline panes
        (reference: webapp query.jsx + plan.jsx + stage.jsx consuming
        /v1/query/{id})."""
        plan_text = st.plan_text
        if not plan_text:
            # plans are pure functions of (sql, catalog): render on
            # demand for queries that ran through the fused paths
            try:
                from presto_tpu.exec.executor import explain_text
                from presto_tpu.sql import ast as _ast
                from presto_tpu.sql.parser import parse as _parse

                stmt = _parse(st.sql)
                if isinstance(stmt, _ast.QueryStatement):
                    plan_text = explain_text(self.session, stmt)
            except Exception:
                plan_text = ""
        nodes = []
        for ns in st.node_stats.values():
            nodes.append({"kind": ns.node_kind, "rowsOut": ns.rows_out,
                          "wallMillis": round(ns.wall_ns / 1e6, 2),
                          "invocations": ns.invocations})
        nodes.sort(key=lambda n: -n["wallMillis"])
        return {
            "queryId": st.query_id, "query": st.sql,
            "state": st.state, "error": st.error,
            "executionMode": st.execution_mode,
            "fallbackReason": st.fallback_reason,
            "createTime": st.create_time, "endTime": st.end_time,
            "phaseMillis": {k: v / 1e6 for k, v in st.phase_ns.items()},
            "outputRows": st.output_rows,
            "peakMemoryBytes": st.peak_memory_bytes,
            "spilledBytes": st.spilled_bytes,
            # dynamic filtering (plan/runtime_filters.py): per-query
            # filter economics for the UI's query pane
            "dynamicFilters": {
                "produced": getattr(st, "df_filters_produced", 0),
                "applied": getattr(st, "df_filters_applied", 0),
                "declined": getattr(st, "df_filters_declined", 0),
                "rowsPruned": getattr(st, "df_rows_pruned", 0),
                "chunksPruned": getattr(st, "df_chunks_pruned", 0),
                "splitsPruned": getattr(st, "df_splits_pruned", 0),
                "waitMillis": round(getattr(st, "df_wait_ms", 0.0), 1),
            },
            # fragment fusion (plan/fusion_cost.py): the per-edge
            # fuse-vs-cut economics — edges spliced vs kept on the HTTP
            # path, memo-vs-model disagreements, the pricing wall, and
            # the per-reason skip counts that make a cost-cut edge
            # distinguishable from a kind-filtered or cross-host one
            "fragmentFusion": {
                "fragmentsFused": getattr(st, "fragments_fused", 0),
                "edgesFused": getattr(st, "fusion_edges_fused", 0),
                "edgesCut": getattr(st, "fusion_edges_cut", 0),
                "edgesMispredicted": getattr(
                    st, "fusion_edges_mispredicted", 0),
                "costMillis": round(
                    getattr(st, "fusion_cost_ms", 0.0), 2),
                "skips": dict(getattr(st, "fusion_skips", None) or {}),
                "exchangeBytesHost": getattr(
                    st, "exchange_bytes_host", 0),
                "exchangeBytesCollective": getattr(
                    st, "exchange_bytes_collective", 0),
            },
            # serving tier (server/serving.py): admission + prepared +
            # result-cache facts (reference parity: the query JSON's
            # resourceGroupId and queuedTime)
            "resourceGroupId": getattr(st, "resource_group", "") or None,
            "admissionWaitMillis": round(
                getattr(st, "admission_wait_ms", 0.0), 1),
            "resultCacheHit": bool(getattr(st, "result_cache_hit", 0)),
            "prepared": {
                "binds": getattr(st, "prepared_binds", 0),
                "planHits": getattr(st, "prepared_plan_hits", 0),
                "fallbacks": getattr(st, "prepared_fallbacks", 0),
            },
            # query coalescing (server/serving.QueryCoalescer): how many
            # queries shared this query's XLA launch (0 = solo), the
            # micro-batch window wait the leader paid, and batch
            # memberships abandoned for a solo re-run
            "coalescing": {
                "batchSize": getattr(st, "coalesced_batch_size", 0),
                "windowWaitMillis": round(
                    getattr(st, "coalesce_ms", 0.0), 2),
                "batchesLed": getattr(st, "coalesce_batches", 0),
                "fallbacks": getattr(st, "coalesce_fallbacks", 0),
            },
            # tracing (observe/trace.py): the chrome trace lives at
            # /v1/query/{id}/trace; spanCount hints whether it's worth
            # fetching (0 = tracing was off for this query)
            "traceId": getattr(st, "trace_id", "") or None,
            "traceUri": f"/v1/query/{st.query_id}/trace",
            "spanCount": len(getattr(st, "trace_spans", None) or []),
            "planText": plan_text,
            "nodes": nodes,
        }

    def metrics_payload(self) -> str:
        """GET /v1/metrics: the Prometheus text exposition of the
        process-wide registry (observe/metrics.py), which every
        QueryStats counter / recovery action / serving decision rolls
        into at query completion.  Serving-tier aggregates are exported
        as gauges at scrape time."""
        from presto_tpu.observe import metrics as M

        M.REGISTRY.gauge("presto_tpu_server_active_queries",
                         "Queries admitted and not yet finished") \
            .set(self.active_queries)
        M.REGISTRY.gauge("presto_tpu_serving_admitted_total",
                         "Queries admitted by the serving tier") \
            .set(self.serving.queries_admitted)
        M.REGISTRY.gauge("presto_tpu_serving_shed_total",
                         "Queries shed by admission control") \
            .set(self.serving.queries_shed)
        M.REGISTRY.gauge("presto_tpu_serving_drained_total",
                         "Queued queries drained at shutdown") \
            .set(self.serving.queries_drained)
        M.REGISTRY.gauge("presto_tpu_serving_peak_queue_depth",
                         "Peak admission queue depth") \
            .set(self.serving.peak_queue_depth)
        co = self.serving.coalescer_stats()
        if co is not None:
            import re as _re

            for k, v in co.items():
                if isinstance(v, (int, float)) \
                        and not isinstance(v, bool):
                    snake = _re.sub(r"(?<!^)(?=[A-Z])", "_", k).lower()
                    M.REGISTRY.gauge(
                        f"presto_tpu_coalesce_{snake}",
                        f"Query coalescer {k}").set(v)
        if self.serving.result_cache is not None:
            rc = self.serving.result_cache.stats()
            for k, v in rc.items():
                if isinstance(v, (int, float)) \
                        and not isinstance(v, bool):
                    M.REGISTRY.gauge(
                        f"presto_tpu_result_cache_{k}",
                        f"Result cache {k}").set(v)
        if self.fleet is not None:
            M.set_fleet_gauges({**self.fleet.stats(),
                                **self.fleet_counters})
        return M.render_scrape()

    def trace_payload(self, st) -> dict:
        """GET /v1/query/{id}/trace: the query's chrome trace-event
        JSON (observe/trace.py) — open in Perfetto / chrome://tracing."""
        from presto_tpu.observe import trace as TR

        return TR.chrome_trace(st.trace_spans or [],
                               getattr(st, "trace_id", ""))

    def info_payload(self) -> dict:
        from presto_tpu.observe import trace as TR

        out = {
            "nodeId": self.node_id,
            "uptimeMillis": int((TR.wall_s() - self.start_time) * 1000),
            "state": "SHUTTING_DOWN" if self.shutting_down.is_set()
                     else "ACTIVE",
            "coordinator": True,
        }
        # per-group running/queued/shed counters (reference parity:
        # /v1/resourceGroupState folded into the node info for the
        # serving dashboards) + serving-tier aggregates
        rgm = self.resource_groups
        if rgm is not None:
            out["resourceGroups"] = rgm.info()
        out["serving"] = {
            "admitted": self.serving.queries_admitted,
            "shed": self.serving.queries_shed,
            "drained": self.serving.queries_drained,
            "peakQueueDepth": self.serving.peak_queue_depth,
            "coalescing": self.serving.coalescer_stats(),
            "resultCache": (self.serving.result_cache.stats()
                            if self.serving.result_cache is not None
                            else None),
        }
        if self.fleet is not None:
            out["fleet"] = {**self.fleet.stats(), **self.fleet_counters}
        return out


def _make_handler(server: PrestoTpuServer):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *args):  # silence default stderr noise
            pass

        def _json(self, payload, code: int = 200):
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _authenticate(self) -> bool:
            """HTTP Basic against the configured PasswordAuthenticator;
            True == proceed.  401 + WWW-Authenticate on failure."""
            if server.authenticator is None:
                return True
            import base64 as _b64

            from presto_tpu.security import AuthenticationError

            hdr = self.headers.get("Authorization", "")
            if hdr.startswith("Basic "):
                try:
                    user, _, pw = _b64.b64decode(
                        hdr[6:]).decode("utf-8").partition(":")
                    server.authenticator.authenticate(user, pw)
                    return True
                except (AuthenticationError, ValueError):
                    pass
            # drain a BOUNDED amount of request body so small keep-alive
            # requests can retry cleanly; oversized unauthenticated bodies
            # are not buffered (pre-auth memory safety) — the connection
            # closes instead
            n = int(self.headers.get("Content-Length", 0) or 0)
            drained = 0
            while drained < min(n, 1 << 20):
                chunk = self.rfile.read(min(65536, n - drained))
                if not chunk:
                    break
                drained += len(chunk)
            self.close_connection = True
            self.send_response(401)
            self.send_header("WWW-Authenticate",
                             'Basic realm="presto_tpu"')
            self.send_header("Content-Length", "0")
            self.send_header("Connection", "close")
            self.end_headers()
            return False

        def do_POST(self):
            if not self._authenticate():
                return
            parts = [p for p in self.path.split("/") if p]
            n = int(self.headers.get("Content-Length", 0))
            body = self.rfile.read(n)
            if parts[:2] == ["v1", "fleet"] and len(parts) == 3:
                return self._fleet_post(parts[2], body)
            if self.path != "/v1/statement":
                return self._json({"error": "not found"}, 404)
            if server.shutting_down.is_set():
                return self._json({"error": "shutting down"}, 503)
            sql = body.decode()
            owner = server.route_target(sql)
            if owner is not None:
                mode = str(server.session.properties.get(
                    "fleet_affinity", "proxy")).lower()
                if mode == "redirect":
                    # dumb-LB escape hatch: clients that follow 307
                    # (method+body preserved) talk to the owner directly
                    # from here on — no proxy hop per page
                    server.fleet_counters["redirected"] += 1
                    server.fleet.counters["routed_away"] += 1
                    loc = f"{owner}/v1/statement"
                    payload = json.dumps({"redirect": loc}).encode()
                    self.send_response(307)
                    self.send_header("Location", loc)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(payload)))
                    self.end_headers()
                    self.wfile.write(payload)
                    return
                proxied = server.proxy_submit(sql, owner)
                if proxied is not None:
                    return self._json(proxied)
                # owner unreachable: routing is an optimization — run it
                # here (the version-keyed caches keep this correct)
            with TR.span("http.post"):
                with TR.span("http.submit"):
                    job = server.submit(sql)
                # brief grace so fast queries return data on the first
                # response
                with TR.span("http.grace_wait", statement_id=job.query_id):
                    job.done.wait(timeout=FIRST_RESPONSE_GRACE_S)
                with TR.span("http.encode", statement_id=job.query_id):
                    self._json(server.results_payload(job, 0))

        def _fleet_post(self, action: str, body: bytes):
            """Peer-to-peer fleet bus: invalidation broadcast, health
            gossip, prepared replication (server/fleet.py)."""
            if server.fleet is None:
                return self._json({"error": "no fleet attached"}, 404)
            try:
                payload = json.loads(body.decode() or "{}")
            except ValueError:
                return self._json({"error": "bad fleet payload"}, 400)
            if action == "invalidate":
                tables = payload.get("tables")
                server.fleet.on_invalidate(
                    str(payload.get("origin", "")),
                    str(payload.get("token", "")),
                    int(payload.get("version", 0) or 0),
                    tables=set(tables) if tables else None)
                return self._json({"ok": True})
            if action == "health":
                server.fleet.on_health(
                    str(payload.get("origin", "")),
                    str(payload.get("worker", "")),
                    str(payload.get("verdict", "open")))
                return self._json({"ok": True})
            if action == "prepare":
                try:
                    server.session.sql(str(payload.get("sql", "")))
                except Exception as e:  # noqa: BLE001 — reported to peer
                    return self._json(
                        {"error": f"{type(e).__name__}: {e}"}, 400)
                return self._json({"ok": True})
            if action == "journal":
                server.fleet.on_journal(
                    str(payload.get("origin", "")),
                    payload.get("entry") or {})
                return self._json({"ok": True})
            return self._json({"error": "not found"}, 404)

        def do_GET(self):
            if not self._authenticate():
                return
            parts = [p for p in self.path.split("/") if p]
            if parts[:2] == ["v1", "statement"] and len(parts) == 4:
                with TR.span("http.get", statement_id=parts[2]):
                    job = server.jobs.get(parts[2])
                    if job is None:
                        owner = server.proxied_owner(parts[2])
                        if owner is not None:
                            proxied = server.proxy_fetch(owner, self.path)
                            if proxied is not None:
                                return self._json(proxied)
                        # coordinator-death-mid-poll: an unknown qid that
                        # the fleet journal knows is in flight elsewhere
                        # (or being adopted right here) keeps the client
                        # polling instead of 404ing
                        adopted = server.journal_lookup(parts[2], self.path)
                        if adopted is not None:
                            return self._json(adopted)
                        return self._json({"error": "unknown query"}, 404)
                    try:
                        token = int(parts[3])
                    except ValueError:
                        return self._json({"error": "bad page token"}, 400)
                    if token < 0:
                        return self._json({"error": "bad page token"}, 400)
                    if job.state in ("QUEUED", "RUNNING"):
                        with TR.span("http.long_poll", statement_id=parts[2]):
                            job.done.wait(timeout=LONG_POLL_S)  # long poll
                    with TR.span("http.encode", statement_id=parts[2]):
                        return self._json(server.results_payload(job, token))
            if parts == ["v1", "query"]:
                return self._json(server.query_list_payload())
            if parts[:2] == ["v1", "query"] and len(parts) == 4 \
                    and parts[3] == "trace":
                for st in server.session.history_snapshot():
                    if st.query_id == parts[2]:
                        return self._json(server.trace_payload(st))
                return self._json({"error": "unknown query"}, 404)
            if parts[:2] == ["v1", "query"] and len(parts) == 3:
                for st in server.session.history_snapshot():
                    if st.query_id == parts[2]:
                        return self._json(server.query_detail_payload(st))
                return self._json({"error": "unknown query"}, 404)
            if parts == ["v1", "metrics"]:
                body = server.metrics_payload().encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4; "
                                 "charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            if parts == ["v1", "info"]:
                return self._json(server.info_payload())
            if parts == ["v1", "status"]:  # heartbeat probe target
                return self._json({"nodeId": server.node_id, "alive": True})
            if parts == ["ui"] or parts == []:
                # the web UI (reference: presto-main webapp/); the static
                # page is cached on the server object at first request
                body = getattr(server, "_ui_bytes", None)
                if body is None:
                    import os as _os

                    path = _os.path.join(
                        _os.path.dirname(_os.path.abspath(__file__)),
                        "ui.html")
                    try:
                        with open(path, "rb") as f:
                            body = server._ui_bytes = f.read()
                    except OSError:
                        return self._json({"error": "ui not installed"}, 404)
                self.send_response(200)
                self.send_header("Content-Type", "text/html; charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            if parts == ["v1", "resourceGroupState"]:
                rgm = server.resource_groups
                return self._json(rgm.info() if rgm is not None else [])
            if parts == ["v1", "cluster"]:
                with server.jobs_lock:
                    active = server.active_queries
                return self._json({
                    "runningQueries": active,
                    "totalQueries": len(server.session.history)})
            return self._json({"error": "not found"}, 404)

        def do_DELETE(self):
            if not self._authenticate():
                return
            parts = [p for p in self.path.split("/") if p]
            if parts[:2] == ["v1", "statement"] and len(parts) >= 3:
                job = server.jobs.get(parts[2])
                if job is not None:
                    job.cancel.set()
                    if job.state in ("QUEUED",):
                        job.state = "CANCELED"
                    return self._json({"canceled": True}, 200)
                owner = server.proxied_owner(parts[2])
                if owner is not None:
                    proxied = server.proxy_fetch(owner, self.path,
                                                 method="DELETE")
                    if proxied is not None:
                        return self._json(proxied)
            self._json({"error": "not found"}, 404)

        def do_PUT(self):
            if self.path == "/v1/info/state":
                n = int(self.headers.get("Content-Length", 0))
                body = self.rfile.read(n).decode().strip().strip('"')
                if body == "SHUTTING_DOWN":
                    threading.Thread(target=server.graceful_shutdown,
                                     daemon=True).start()
                    return self._json({"state": "SHUTTING_DOWN"})
            self._json({"error": "bad request"}, 400)

    return Handler
