"""Python client for the coordinator REST protocol.

Reference blueprint: client/trino-client StatementClientV1.java:75 — POST the
statement, then follow ``nextUri`` (advance():397) until the query drains,
accumulating row batches. Session state (prepared statements, the open
transaction) is CLIENT-held, exactly like the reference: the server mirrors
state changes into X-Trino-Added-Prepare / X-Trino-Started-Transaction-Id /
... response headers and the client re-sends the accumulated state on every
request. Uses stdlib urllib (no extra deps).
"""

from __future__ import annotations

import base64
import json
import time
import urllib.error
import urllib.request
from dataclasses import dataclass, field
from typing import Dict, List, Optional
from urllib.parse import quote, unquote


class ClientError(RuntimeError):
    pass


@dataclass
class StatementResult:
    query_id: str
    columns: List[str]
    rows: List[list]
    stats: dict = field(default_factory=dict)
    # the serving coordinator's /v1/query/{id} URL — in a fleet this names
    # the OWNER host (per-query attribution is fetched from it)
    info_uri: str = ""


class StatementClient:
    def __init__(self, base_url: str, timeout: float = 60.0,
                 user: Optional[str] = None, password: Optional[str] = None,
                 token: Optional[str] = None):
        self.base_url = base_url.rstrip("/")
        self.timeout = timeout
        self.user = user
        self.password = password
        self.token = token  # JWT bearer credential (--access-token analogue)
        # client-held session state (ref: ClientSession.preparedStatements /
        # transactionId): re-sent as headers, updated from response headers
        self._prepared: Dict[str, str] = {}
        self._txn_id: Optional[str] = None

    # ------------------------------------------------------------ low level

    def _auth_headers(self) -> dict:
        if self.token is not None:
            return {"Authorization": f"Bearer {self.token}"}
        if self.user is not None and self.password is not None:
            token = base64.b64encode(
                f"{self.user}:{self.password}".encode()
            ).decode()
            return {"Authorization": f"Basic {token}"}
        if self.user is not None:
            return {"X-Trino-User": self.user}
        return {}

    def _session_headers(self) -> dict:
        headers = dict(self._auth_headers())
        if self._prepared:
            headers["X-Trino-Prepared-Statement"] = ",".join(
                f"{quote(name)}={quote(sql)}"
                for name, sql in self._prepared.items()
            )
        if self._txn_id:
            headers["X-Trino-Transaction-Id"] = self._txn_id
        return headers

    def _absorb_session_updates(self, resp_headers) -> None:
        added = resp_headers.get("X-Trino-Added-Prepare")
        if added and "=" in added:
            name, sql = added.split("=", 1)
            self._prepared[unquote(name)] = unquote(sql)
        dealloc = resp_headers.get("X-Trino-Deallocated-Prepare")
        if dealloc:
            self._prepared.pop(unquote(dealloc), None)
        started = resp_headers.get("X-Trino-Started-Transaction-Id")
        if started:
            self._txn_id = started
        if resp_headers.get("X-Trino-Clear-Transaction-Id"):
            self._txn_id = None

    # coordinator-fleet redirects: a non-owner coordinator answers POST
    # /v1/statement with 307 + the owner's Location. urllib refuses to
    # auto-follow a redirected POST (rightly — it would drop the body), so
    # the client re-issues the SAME method+body itself, with a bounded hop
    # count and loop detection (two coordinators that each believe the
    # other owns the key must surface as a clear error, not a hang).
    MAX_REDIRECT_HOPS = 5

    def _request(self, method: str, url: str, body: Optional[bytes] = None,
                 headers: Optional[dict] = None) -> dict:
        all_headers = dict(headers or {})
        visited = [url]
        for _hop in range(self.MAX_REDIRECT_HOPS + 1):
            req = urllib.request.Request(url, data=body, method=method,
                                         headers=all_headers)
            try:
                with urllib.request.urlopen(req, timeout=self.timeout) as resp:
                    self._absorb_session_updates(resp.headers)
                    return json.loads(resp.read().decode())
            except urllib.error.HTTPError as e:
                if e.code in (307, 308):
                    location = e.headers.get("Location", "")
                    e.read()  # drain so the connection can be reused
                    if not location:
                        raise ClientError(
                            f"HTTP {e.code}: redirect without Location"
                        ) from None
                    if location in visited:
                        raise ClientError(
                            "redirect loop: "
                            + " -> ".join(visited + [location])
                        ) from None
                    visited.append(location)
                    url = location
                    continue
                try:
                    detail = json.loads(e.read().decode())
                except Exception:
                    detail = {"error": str(e)}
                raise ClientError(f"HTTP {e.code}: {detail}") from None
        raise ClientError(
            f"too many redirects ({self.MAX_REDIRECT_HOPS}): "
            + " -> ".join(visited)
        )

    def _fetch_segments(self, segments: list, encoding: str) -> List[list]:
        """Fetch + decode + ack spooled segments (protocol/spooling client).
        Segment requests carry credentials too — the coordinator's spooled
        routes are authenticated like every other route."""
        rows: List[list] = []
        auth = self._auth_headers()
        for seg in segments:
            req = urllib.request.Request(seg["uri"], headers=dict(auth))
            with urllib.request.urlopen(req, timeout=self.timeout) as resp:
                data = resp.read()
            if encoding == "json+lz4":
                from ..native import lz4_decompress

                data = lz4_decompress(data, seg["uncompressedSize"])
            rows.extend(json.loads(data.decode()))
            # acknowledge: the server may free the segment
            ack = urllib.request.Request(
                seg["uri"], method="DELETE", headers=dict(auth)
            )
            try:
                urllib.request.urlopen(ack, timeout=self.timeout)
            except urllib.error.HTTPError:
                pass
        return rows

    # ------------------------------------------------------------ protocol

    def execute(self, sql: str, data_encoding: Optional[str] = None) -> StatementResult:
        headers = self._session_headers()
        if data_encoding:
            headers["X-Trino-Query-Data-Encoding"] = data_encoding
        payload = self._request(
            "POST", f"{self.base_url}/v1/statement", sql.encode(), headers=headers
        )
        columns: List[str] = []
        rows: List[list] = []
        query_id = payload.get("id", "")
        info_uri = payload.get("infoUri", "")
        deadline = time.time() + self.timeout
        while True:
            if "error" in payload:
                err = payload["error"]
                raise ClientError(f"{err.get('errorName')}: {err.get('message')}")
            if "columns" in payload:
                columns = [c["name"] for c in payload["columns"]]
            if "segments" in payload:
                # spooled protocol: fetch each segment out-of-band, then ack
                rows.extend(
                    self._fetch_segments(
                        payload["segments"], payload.get("dataEncoding", "json")
                    )
                )
            rows.extend(payload.get("data", []))
            next_uri = payload.get("nextUri")
            if next_uri is None:
                return StatementResult(
                    query_id=query_id,
                    columns=columns,
                    rows=rows,
                    stats=payload.get("stats", {}),
                    info_uri=info_uri,
                )
            if time.time() > deadline:
                raise ClientError(f"query {query_id} timed out")
            payload = self._request("GET", next_uri, headers=self._auth_headers())

    def query_info(self, query_id: str) -> dict:
        return self._request(
            "GET", f"{self.base_url}/v1/query/{query_id}",
            headers=self._auth_headers(),
        )

    def server_info(self) -> dict:
        return self._request(
            "GET", f"{self.base_url}/v1/info", headers=self._auth_headers()
        )
