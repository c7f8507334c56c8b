"""HTTP statement client (stdlib urllib; no external deps).

Reference parity: StatementClientV1 state machine — advance() fetches
the next QueryResults page; duplicate token fetches are safe
(at-least-once + dedup, server/TaskResource.java:244-307 analog).

Fleet failover: `backup_uris` names the OTHER doors of a coordinator
fleet.  When the door this client is polling stops answering, the same
path is retried against each backup — any door resolves a journaled
in-flight query through its proxied/journal_lookup chain
(server/protocol.py), so a coordinator death mid-poll degrades to a
door switch instead of a client error.
"""

from __future__ import annotations

import json
import time
import urllib.error
import urllib.request
from typing import Iterator, List, Optional, Tuple

from presto_tpu.observe import trace as TR


class QueryError(Exception):
    pass


class StatementClient:
    def __init__(self, server_uri: str, sql: str,
                 poll_interval: float = 0.05,
                 backup_uris: Optional[List[str]] = None):
        self.server_uri = server_uri.rstrip("/")
        self.sql = sql
        self.poll_interval = poll_interval
        self.backup_uris = [u.rstrip("/") for u in (backup_uris or [])]
        self.query_id: Optional[str] = None
        self.columns: Optional[List[dict]] = None
        self.stats: dict = {}
        self._next_uri: Optional[str] = None
        self._current_data: list = []
        self._started = False

    # one re-dispatch per request: a fleet front door in redirect mode
    # answers 307 with the owning coordinator's Location, and urllib
    # refuses to auto-follow a redirected POST body — follow it here
    MAX_REDIRECTS = 4

    def _request_once(self, method: str, url: str,
                      body: Optional[bytes] = None):
        for _ in range(self.MAX_REDIRECTS):
            req = urllib.request.Request(url, data=body, method=method)
            try:
                with urllib.request.urlopen(req, timeout=30) as resp:
                    return json.loads(resp.read().decode())
            except urllib.error.HTTPError as e:
                loc = e.headers.get("Location") if e.code in (307, 308) \
                    else None
                if not loc:
                    raise
                url = loc
        raise QueryError(f"redirect loop at {url}")

    def _request(self, method: str, url: str, body: Optional[bytes] = None):
        try:
            return self._request_once(method, url, body)
        except (urllib.error.URLError, ConnectionError, OSError) as e:
            if isinstance(e, urllib.error.HTTPError):
                raise  # the door answered; failover is for dead doors
            last = e
        # the door died (connection refused/reset): replay the SAME
        # path through each backup door — its journal_lookup/proxy
        # chain resolves the query wherever it now lives, and from here
        # on this client polls the door that answered
        prefix_len = len(self.server_uri)
        path = url[prefix_len:] if url.startswith(self.server_uri) else None
        if path is not None:
            for backup in self.backup_uris:
                if backup == self.server_uri:
                    continue
                try:
                    payload = self._request_once(method,
                                                 f"{backup}{path}", body)
                except (urllib.error.HTTPError, QueryError):
                    raise
                except (urllib.error.URLError, ConnectionError, OSError):
                    continue
                self.server_uri = backup
                return payload
        raise last

    def _absorb(self, payload: dict) -> None:
        self.query_id = payload.get("id", self.query_id)
        if payload.get("columns"):
            self.columns = payload["columns"]
        self.stats = payload.get("stats", self.stats)
        self._current_data = payload.get("data", [])
        self._next_uri = payload.get("nextUri")
        err = payload.get("error")
        if err:
            raise QueryError(err.get("message", "query failed"))
        if self.stats.get("state") == "CANCELED":
            # a silent stop would be indistinguishable from completion
            raise QueryError("query was canceled")

    def advance(self) -> bool:
        """Fetch the next page; returns False when the stream is done."""
        if not self._started:
            self._started = True
            with TR.span("client.post"):
                payload = self._request("POST",
                                        f"{self.server_uri}/v1/statement",
                                        self.sql.encode())
            self._absorb(payload)
            return True
        if self._next_uri is None:
            return False
        with TR.span("client.get", statement_id=self.query_id):
            payload = self._request("GET", self._next_uri)
        self._absorb(payload)
        return True

    def rows(self) -> Iterator[tuple]:
        """Stream all result rows, polling while queued/running."""
        while self.advance():
            for r in self._current_data:
                yield tuple(r)
            state = self.stats.get("state")
            if state in ("QUEUED", "RUNNING") and not self._current_data:
                with TR.span("client.poll_sleep",
                             statement_id=self.query_id):
                    time.sleep(self.poll_interval)

    def cancel(self) -> None:
        if self.query_id is not None:
            try:
                self._request(
                    "DELETE",
                    f"{self.server_uri}/v1/statement/{self.query_id}/0")
            except urllib.error.URLError:
                pass


class Cursor:
    """DB-API-flavored convenience over StatementClient (the role the
    JDBC driver plays for the reference; reference: presto-jdbc)."""

    def __init__(self, server_uri: str):
        self.server_uri = server_uri
        self.description: Optional[List[Tuple[str, str]]] = None
        self._rows: list = []
        self._idx = 0

    def execute(self, sql: str) -> "Cursor":
        client = StatementClient(self.server_uri, sql)
        self._rows = list(client.rows())
        self.description = ([(c["name"], c["type"]) for c in client.columns]
                            if client.columns else None)
        self._idx = 0
        self.stats = client.stats
        return self

    def fetchall(self) -> list:
        rows, self._idx = self._rows[self._idx:], len(self._rows)
        return rows

    def fetchone(self):
        if self._idx >= len(self._rows):
            return None
        row = self._rows[self._idx]
        self._idx += 1
        return row


def connect_http(server_uri: str) -> Cursor:
    return Cursor(server_uri)
