"""Coordinator HTTP server: the client protocol + status APIs.

Reference blueprint: the REST surface of SURVEY.md §3.1/§2.6 —
QueuedStatementResource (`POST /v1/statement`, dispatcher/QueuedStatementResource.
java:172), ExecutingStatementResource (`GET /v1/statement/executing/{id}/{slug}/
{token}` with nextUri paging), QueryResource (`/v1/query`), plus /v1/info and
/v1/status. Wire shape follows docs/src/main/sphinx/develop/client-protocol.md:
each response carries columns, data, stats, and a nextUri until the query drains.

Implementation: stdlib ThreadingHTTPServer — the control plane is cold-path
Python by design (SURVEY.md §7: "Python for frontend/planner/coordinator");
pages per response are bounded like Trino's targetResultSize.
"""

from __future__ import annotations

import json
import threading
import datetime
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional
from urllib.parse import urlparse

from .. import __version__
from .. import knobs
from ..runtime.query_manager import QueryManager, QueryState

PAGE_ROWS = 4096  # rows per protocol page (targetResultSize analogue)


class BadSessionHeader(ValueError):
    """A session-state request header failed to parse (-> HTTP 400)."""


def _json_value(v: Any, type_=None) -> Any:
    """Row value -> wire JSON, matching the reference client's decode rules
    (client/trino-client JsonDecodingUtils): dates/timestamps as their SQL
    text forms, decimals as exact-scale strings."""
    if isinstance(v, datetime.datetime):
        return v.isoformat(sep=" ")
    if isinstance(v, datetime.time):
        return v.isoformat()
    if isinstance(v, datetime.date):
        return v.isoformat()
    if v is not None and type_ is not None and getattr(type_, "name", "") == "decimal":
        return f"{v:.{type_.scale}f}"
    if isinstance(v, list):
        el_t = getattr(type_, "element", None)
        return [_json_value(x, el_t) for x in v]
    if isinstance(v, dict):
        kt, vt = getattr(type_, "key", None), getattr(type_, "value", None)
        return {_json_value(k, kt): _json_value(x, vt) for k, x in v.items()}
    if isinstance(v, tuple):
        fts = [ft for _, ft in getattr(type_, "fields", [])] or [None] * len(v)
        return [_json_value(x, ft) for x, ft in zip(v, fts)]
    return v


# the types whose every value `_json_value` gives back as it is (int, float,
# bool, str or None)
_PLAIN_JSON = frozenset(
    {"bigint", "integer", "smallint", "tinyint", "double", "real", "boolean", "varchar"}
)


def _json_rows(rows, types) -> List[list]:
    """Rows -> wire JSON, `_json_value` decided once a column and not once a
    cell: a column of a plain type goes as it is. Per cell, the calls were
    most of the answer's way back for an answer of thousands of rows."""
    convert = [
        None if getattr(t, "name", None) in _PLAIN_JSON
        else (lambda v, t=t: _json_value(v, t))
        for t in types
    ]
    if not any(convert):
        return [list(row) for row in rows]
    return [[v if f is None else f(v) for v, f in zip(row, convert)] for row in rows]


def _type_signature(type_) -> Dict:
    """Our Type -> Trino wire type + ClientTypeSignature
    (ref: client/trino-client ClientTypeSignature / TypeSignature text forms,
    StatementClientV1.java:75 consumers decode by these)."""
    if type_ is None:
        return {
            "type": "varchar",
            "typeSignature": {"rawType": "varchar", "arguments": [
                {"kind": "LONG", "value": 2147483647}
            ]},
        }
    name = type_.name
    args = []
    if name == "array":
        args = [{"kind": "TYPE", "value": _type_signature(type_.element)["typeSignature"]}]
    elif name == "map":
        args = [
            {"kind": "TYPE", "value": _type_signature(type_.key)["typeSignature"]},
            {"kind": "TYPE", "value": _type_signature(type_.value)["typeSignature"]},
        ]
    elif name == "row":
        args = [
            {
                "kind": "NAMED_TYPE",
                "value": {
                    "fieldName": ({"name": n} if n else None),
                    "typeSignature": _type_signature(ft)["typeSignature"],
                },
            }
            for n, ft in type_.fields
        ]
    if name == "decimal":
        args = [
            {"kind": "LONG", "value": type_.precision},
            {"kind": "LONG", "value": type_.scale},
        ]
    elif name == "varchar":
        length = getattr(type_, "length", None)
        args = [{"kind": "LONG", "value": 2147483647 if length is None else length}]
    elif name == "char":
        args = [{"kind": "LONG", "value": type_.length}]
    elif name in ("timestamp", "time", "timestamp with time zone"):
        args = [{"kind": "LONG", "value": type_.precision}]
    display = type_.display()
    if name == "varchar" and getattr(type_, "length", None) is None:
        display = "varchar"
    return {"type": display, "typeSignature": {"rawType": name, "arguments": args}}


class CoordinatorServer:
    """Embeds a query runner behind the REST protocol."""

    def __init__(self, runner, host: str = "127.0.0.1", port: int = 0,
                 resource_groups=None, authenticator=None,
                 jwt_authenticator=None, oauth2_authenticator=None,
                 history_path: Optional[str] = None, ha_lease=None,
                 fleet=None, node_id: Optional[str] = None,
                 front_port: Optional[int] = None):
        import os

        from ..runtime.nodes import InternalNodeManager

        from ..runtime.spool import FileSystemSpoolingManager

        from ..runtime.clusterobs import ClockSync, ClusterMetrics

        self.runner = runner
        self.manager = QueryManager(runner.execute, resource_groups=resource_groups)
        self.nodes = InternalNodeManager()
        # cluster observability plane: per-node clock offsets (heartbeat
        # RTT midpoints) + the federated metric fold. Always constructed
        # (cheap, empty); only announcement riders feed them.
        self.clock_sync = ClockSync()
        self.cluster_metrics = ClusterMetrics()
        # memory arbitration: the ClusterMemoryManager (built by the
        # QueryManager when a pool is configured) reads per-worker pool state
        # off THIS node manager's announcements
        if self.manager.cluster_memory is not None:
            self.manager.cluster_memory.node_manager = self.nodes
        # system catalog wiring: the QueryManager registered itself into the
        # runner's SystemContext at construction; nodes + persistent query
        # history attach here (system.runtime.nodes / query_history)
        self.history = None
        sys_ctx = getattr(runner.metadata, "system_context", None)
        if sys_ctx is not None:
            sys_ctx.node_manager = self.nodes
            sys_ctx.cluster_metrics = self.cluster_metrics
        history_path = history_path or knobs.env_path(
            "TRINO_TPU_QUERY_HISTORY_PATH"
        )
        if history_path:
            from ..runtime.events import QueryHistoryStore

            self.history = QueryHistoryStore(history_path)
            self.manager.add_listener(self.history)
            if sys_ctx is not None:
                sys_ctx.history_store = self.history
        self.authenticator = authenticator  # PasswordAuthenticator or None
        self.jwt_authenticator = jwt_authenticator  # JwtAuthenticator or None
        self.oauth2 = oauth2_authenticator  # OAuth2Authenticator or None
        self.spooling = FileSystemSpoolingManager()
        self._spooled: Dict[str, list] = {}  # query_id -> segment descriptors
        self._spool_lock = threading.Lock()
        self.host = host
        coordinator = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):  # quiet
                pass

            # ---------------------------------------------------------- utils

            def _send(self, code: int, payload: Dict, extra_headers=None) -> int:
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in (extra_headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)
                return len(body)

            def _stream_results(self, q, token: int) -> None:
                """One page of the statement's protocol to the client: the
                `result_stream` span under the statement's root, on this
                HTTP thread. The page without a nextUri is the last: the
                statement's timeline ends with it. Any other opens the span
                `client_turn` as it goes out, until the client asks again."""
                from ..runtime.hostprof import phase_span
                from ..runtime.observability import RECORDER
                from ..runtime.tracing import TRACER

                with TRACER.attach(q.stats.root), phase_span(
                    RECORDER, "result_stream", query_id=q.query_id,
                    token=token,
                ) as sent:
                    payload = coordinator._results_payload(
                        q, token, self._base_uri()
                    )
                    sent["rows"] = len(payload.get("data", ())) + sum(
                        seg["rowCount"] for seg in payload.get("segments", ())
                    )
                    if "nextUri" in payload and q.stats.root is not None:
                        # from here the work waits for the client: until its
                        # request for the next page arrives (do_GET), or the
                        # statement is closed without one (cancel, expiry).
                        # Opened before the page goes out: the next request
                        # cannot arrive before its span is there to end
                        q._client_turn = TRACER.open_span(
                            "client_turn", q.stats.root, cat="protocol",
                            token=token,
                        )
                    sent["bytes"] = self._send(
                        200, payload,
                        extra_headers=coordinator._session_headers(q),
                    )
                if "nextUri" not in payload:
                    coordinator.manager.close_statement(q)

            def _client_context(self):
                """Rebuild the client session from protocol headers — the
                client re-sends its prepared statements and transaction id on
                every request (client-protocol.md: X-Trino-Prepared-Statement
                name=url-encoded-sql, X-Trino-Transaction-Id), so transaction
                and prepared state never depend on which server thread runs
                the statement."""
                from urllib.parse import unquote

                from ..runtime.local import ClientContext
                from ..sql import parse_statement

                ctx = ClientContext()
                header = self.headers.get("X-Trino-Prepared-Statement", "")
                for part in header.split(","):
                    part = part.strip()
                    if not part or "=" not in part:
                        continue
                    name, encoded = part.split("=", 1)
                    try:
                        ctx.prepared[unquote(name)] = parse_statement(
                            unquote(encoded)
                        )
                    except Exception as e:  # noqa: BLE001
                        # a corrupt entry must fail THIS request loudly, not
                        # resurface later as "prepared statement not found"
                        raise BadSessionHeader(
                            f"invalid X-Trino-Prepared-Statement entry "
                            f"{unquote(name)!r}: {e}"
                        ) from None
                txn_id = self.headers.get("X-Trino-Transaction-Id", "")
                if txn_id and txn_id.upper() != "NONE":
                    try:
                        ctx.txn = coordinator.runner.transactions.get(txn_id)
                    except Exception:  # noqa: BLE001 — expired/unknown txn
                        ctx.txn = None
                return ctx

            def _base_uri(self) -> str:
                host = self.headers.get("Host", coordinator.address)
                front = coordinator._front_server
                if front is not None and host.rsplit(":", 1)[-1] == str(
                    front.server_port
                ):
                    # the request came in on the shared SO_REUSEPORT front
                    # port: a nextUri/infoUri echoing that port would let
                    # the kernel hand the follow-up to a SIBLING process
                    # that has never heard of the query — stateful
                    # conversation URIs must pin to THIS process's unique
                    # address
                    return f"http://{coordinator.address}"
                return f"http://{host}"

            def _authenticate(self):
                """Bearer (JWT) then Basic auth, like the reference's
                authenticator chain (server/security/AuthenticationFilter
                tries each configured authenticator in order); returns the
                authenticated user or None after sending a 401. With no
                authenticator configured, trusts X-Trino-User."""
                user_header = self.headers.get("X-Trino-User", "user")
                if (
                    coordinator.authenticator is None
                    and coordinator.jwt_authenticator is None
                    and coordinator.oauth2 is None
                ):
                    return user_header
                import base64

                auth = self.headers.get("Authorization", "")
                if auth.startswith("Bearer ") and coordinator.oauth2:
                    try:
                        return coordinator.oauth2.authenticate_token(auth[7:].strip())
                    except Exception:
                        pass
                if auth.startswith("Bearer ") and coordinator.jwt_authenticator:
                    try:
                        return coordinator.jwt_authenticator.authenticate_token(
                            auth[7:].strip()
                        )
                    except Exception:
                        pass
                if auth.startswith("Basic ") and coordinator.authenticator:
                    try:
                        decoded = base64.b64decode(auth[6:]).decode()
                        user, _, password = decoded.partition(":")
                        coordinator.authenticator.authenticate(user, password)
                        return user
                    except Exception:
                        pass
                self.send_response(401)
                challenge = (
                    'Basic realm="trino-tpu"'
                    if coordinator.authenticator
                    else 'Bearer realm="trino-tpu"'
                )
                self.send_header("WWW-Authenticate", challenge)
                self.send_header("Content-Length", "0")
                self.end_headers()
                return None

            # ---------------------------------------------------------- routes

            def do_PUT(self):
                # worker announcements (node/Announcer.java -> /v1/announcement)
                parts = [p for p in urlparse(self.path).path.split("/") if p]
                if len(parts) == 3 and parts[0] == "v1" and parts[1] == "announcement":
                    length = int(self.headers.get("Content-Length", 0))
                    try:
                        body = json.loads(self.rfile.read(length) or b"{}")
                        if not isinstance(body, dict):
                            raise ValueError("announcement body must be an object")
                    except (ValueError, json.JSONDecodeError) as e:
                        self._send(400, {"error": f"bad announcement body: {e}"})
                        return
                    memory = body.get("memory")
                    coordinator.nodes.announce(
                        parts[2],
                        body.get("uri", ""),
                        coordinator=bool(body.get("coordinator")),
                        location=str(body.get("location", "")),
                        version=str(body.get("version", "")),
                        device=str(body.get("device", "")),
                        memory=memory if isinstance(memory, dict) else None,
                    )
                    # cluster observability riders (payload-driven: only
                    # flag-on workers attach them; the response is the
                    # same either way)
                    clock = body.get("clock")
                    if isinstance(clock, dict):
                        coordinator.clock_sync.observe_announcement(
                            parts[2], clock
                        )
                    metrics = body.get("metrics")
                    if isinstance(metrics, list):
                        coordinator.cluster_metrics.ingest(parts[2], metrics)
                    kc_rows = body.get("kernel_costs")
                    if isinstance(kc_rows, list):
                        from ..runtime import kernelcost

                        kernelcost.ingest_federated(parts[2], kc_rows)
                    self._send(202, {"announced": parts[2]})
                    return
                # admin kill (QueryResource.killQuery / KillQueryProcedure
                # over HTTP): PUT /v1/query/{id}/killed, body = message
                if (
                    len(parts) == 4
                    and parts[0] == "v1"
                    and parts[1] == "query"
                    and parts[3] == "killed"
                ):
                    from ..runtime.query_manager import (
                        CancelResult,
                        QueryNotFound,
                    )

                    if self._authenticate() is None:
                        return
                    length = int(self.headers.get("Content-Length", 0))
                    message = (self.rfile.read(length) or b"").decode()
                    try:
                        result = coordinator.manager.kill(parts[2], message)
                    except QueryNotFound:
                        self._send(404, {"error": "unknown query"})
                        return
                    if result is CancelResult.TERMINAL:
                        self._send(409, {"error": "query already finished"})
                        return
                    self._send(202, {"killed": parts[2]})
                    return
                self._send(404, {"error": "not found"})

            def do_POST(self):
                path = urlparse(self.path).path
                if path == "/v1/statement":
                    # host-path plane: every protocol phase of statement
                    # intake gets a paired flight span (proto_accept wraps
                    # the whole request; auth/parse nest inside) so a slow
                    # submission attributes to a phase, not a guess
                    from ..runtime.hostprof import phase_span
                    from ..runtime.observability import RECORDER

                    with phase_span(
                        RECORDER, "accept", path="/v1/statement"
                    ) as accept_end:
                        with phase_span(RECORDER, "auth"):
                            user = self._authenticate()
                        if user is None:
                            return
                        length = int(self.headers.get("Content-Length", 0))
                        sql = self.rfile.read(length).decode()
                        # coordinator fleet (runtime/fleet.py): partitioned
                        # admission — a non-owner either 307-redirects the
                        # client to the owner or proxies the intake there,
                        # under proto_route/proto_proxy spans; follower-
                        # servable reads short-circuit to local execution
                        if coordinator.fleet is not None:
                            if coordinator._fleet_route(self, sql, user):
                                return
                        try:
                            with phase_span(RECORDER, "parse"):
                                client_ctx = self._client_context()
                        except BadSessionHeader as e:
                            self._send(400, {"error": str(e)})
                            return
                        encodings = [
                            e.strip()
                            for e in self.headers.get(
                                "X-Trino-Query-Data-Encoding", ""
                            ).split(",")
                            if e.strip()
                        ]
                        q = coordinator.manager.submit(
                            sql,
                            user=user,
                            source=self.headers.get("X-Trino-Source", ""),
                            data_encoding=coordinator._pick_encoding(encodings),
                            client_ctx=client_ctx,
                            warm_result=getattr(
                                self, "_fleet_warm_hit", None
                            ),
                        )
                        accept_end["query_id"] = q.query_id
                        wait = coordinator._first_response_wait()
                        if wait > 0:
                            # first-response long-poll (the protocol's
                            # maxWait idea applied to the POST): a query
                            # that finishes within the window — a warm
                            # cache hit above all — drains in ONE round
                            # trip; a slower query falls through to the
                            # usual nextUri sequence when the wait lapses
                            q.wait_done(wait)
                        self._stream_results(q, 0)
                    return
                self._send(404, {"error": f"not found: {path}"})

            def do_GET(self):
                path_q = urlparse(self.path)
                if coordinator.oauth2 is not None and path_q.path == "/oauth2/authorize":
                    # start of the code flow (OAuth2WebUiAuthenticationFilter):
                    # bounce the browser to the IdP with an HMAC'd state
                    import uuid as _uuid

                    state = coordinator.oauth2.sign_state(_uuid.uuid4().hex)
                    redirect = f"{self._base_uri()}/oauth2/callback"
                    url = coordinator.oauth2.authorization_url(redirect, state)
                    self.send_response(302)
                    self.send_header("Location", url)
                    self.send_header("Content-Length", "0")
                    self.end_headers()
                    return
                if coordinator.oauth2 is not None and path_q.path == "/oauth2/callback":
                    from urllib.parse import parse_qs

                    params = parse_qs(path_q.query)
                    state = (params.get("state") or [""])[0]
                    code = (params.get("code") or [""])[0]
                    if not coordinator.oauth2.check_state(state):
                        self._send(401, {"error": "bad oauth2 state"})
                        return
                    try:
                        token = coordinator.oauth2.exchange_code(
                            code, f"{self._base_uri()}/oauth2/callback"
                        )
                    except Exception as e:  # noqa: BLE001 — auth failures -> 401
                        self._send(401, {"error": f"oauth2 exchange failed: {e}"})
                        return
                    self._send(200, {"token": token, "token_type": "Bearer"})
                    return
                if self._authenticate() is None:
                    return
                path = urlparse(self.path).path
                parts = [p for p in path.split("/") if p]
                if path in ("/", "/ui", "/ui/"):
                    # minimal cluster/query overview (core/trino-web-ui's role;
                    # a real SPA is a later round — this reads the same APIs)
                    body = coordinator._ui_html().encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "text/html; charset=utf-8")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if path == "/v1/info":
                    self._send(
                        200,
                        {
                            "nodeVersion": {"version": __version__},
                            "environment": "trino-tpu",
                            "coordinator": True,
                            "starting": False,
                            "uptime": "up",
                        },
                    )
                    return
                if path == "/v1/resourceGroupState":
                    groups = coordinator.manager.resource_groups
                    self._send(200, groups.info() if groups else {})
                    return
                if path == "/v1/memory":
                    # cluster memory pool view (ref: MemoryResource /
                    # ClusterMemoryManager): local pool + per-node heartbeat-
                    # reported reservations
                    cm = coordinator.manager.cluster_memory
                    if cm is not None:
                        self._send(200, cm.cluster_info())
                    else:
                        pool = coordinator.manager.memory_pool
                        self._send(200, pool.snapshot() if pool else {})
                    return
                if path == "/v1/flightrecorder":
                    # the pipeline flight recorder's ring buffer as
                    # Chrome/Perfetto trace-event JSON (load the payload in
                    # ui.perfetto.dev); ?enable=1 / ?disable=1 toggle it,
                    # ?query_id= filters to one query's attribution windows
                    # (the cluster trace assembly's coordinator segment)
                    from urllib.parse import parse_qs

                    from ..runtime.observability import RECORDER

                    params = parse_qs(path_q.query)

                    def flag(name):
                        v = params.get(name, ["0"])[0].lower()
                        return v not in ("", "0", "false", "no")

                    if flag("enable"):
                        RECORDER.enable()
                    if flag("disable"):
                        RECORDER.disable()
                    if flag("clear"):
                        RECORDER.clear()
                    qid = params.get("query_id", [""])[0]
                    if qid:
                        from ..runtime.clusterobs import (
                            local_segment,
                            server_enabled,
                        )

                        # filtering is part of the cluster plane: with the
                        # flag off the param is ignored (unknown params
                        # always were) and the response stays byte-identical
                        if server_enabled():
                            self._send(200, local_segment([qid]))
                            return
                    self._send(200, RECORDER.chrome_trace())
                    return
                if path == "/v1/metrics/cluster":
                    # fleet-wide Prometheus exposition: local registry +
                    # every announced worker's piggybacked snapshot, per-
                    # node labels, HELP preserved, histogram buckets merged
                    from ..runtime.clusterobs import server_enabled

                    if not server_enabled():
                        self._send(404, {"error": "cluster_obs disabled"})
                        return
                    from ..runtime.metrics import REGISTRY

                    body = coordinator.cluster_metrics.render(
                        local_registry=REGISTRY
                    ).encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type", "text/plain; version=0.0.4"
                    )
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if path == "/v1/statshistory":
                    # the statistics feedback plane's history store (the
                    # estimate-vs-actual records HistoryBasedStatsEstimator
                    # overlays; SQL twin: system.optimizer.stats_history)
                    from ..runtime.statstore import history_path, load_history

                    self._send(
                        200,
                        {
                            "path": history_path(),
                            "entries": load_history(),
                        },
                    )
                    return
                if path == "/v1/metrics":
                    from ..runtime.metrics import REGISTRY

                    body = REGISTRY.render().encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type", "text/plain; version=0.0.4"
                    )
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if (
                    len(parts) == 4
                    and parts[0] == "v1"
                    and parts[1] == "query"
                    and parts[3] == "trace"
                ):
                    from urllib.parse import parse_qs

                    from ..runtime.clusterobs import server_enabled
                    from ..runtime.tracing import TRACER

                    params = parse_qs(path_q.query)
                    want_cluster = params.get("cluster", ["0"])[0].lower() \
                        not in ("", "0", "false", "no")
                    if want_cluster and server_enabled():
                        # cross-node trace assembly: pull every node's
                        # segment, skew-align by announced clock offsets,
                        # merge into one Perfetto timeline
                        q = coordinator.manager.get(parts[2])
                        if q is None:
                            self._send(404, {"error": "unknown query"})
                            return
                        self._send(200, coordinator.cluster_trace(q))
                        return
                    q = coordinator.manager.get(parts[2])
                    if q is None or q.trace_id is None:
                        self._send(404, {"error": "no trace for query"})
                        return
                    self._send(
                        200,
                        {"traceId": q.trace_id, "spans": TRACER.trace(q.trace_id)},
                    )
                    return
                if (
                    len(parts) == 4
                    and parts[0] == "v1"
                    and parts[1] == "query"
                    and parts[3] == "profile"
                ):
                    # persisted query profile bundle (cluster obs plane)
                    from ..runtime.clusterobs import (
                        profile_store,
                        server_enabled,
                    )

                    if not server_enabled():
                        self._send(404, {"error": "cluster_obs disabled"})
                        return
                    store = profile_store()
                    profile = store.read(parts[2]) if store else None
                    if profile is None:
                        self._send(404, {"error": "no profile for query"})
                        return
                    self._send(200, profile)
                    return
                if len(parts) == 3 and parts[0] == "v1" and parts[1] == "spooled":
                    data = coordinator.spooling.get_segment(parts[2])
                    if data is None:
                        self._send(404, {"error": "unknown segment"})
                        return
                    self.send_response(200)
                    self.send_header("Content-Type", "application/octet-stream")
                    self.send_header("Content-Length", str(len(data)))
                    self.end_headers()
                    self.wfile.write(data)
                    return
                if path == "/v1/ha":
                    # serving fabric plane: leader lease state (standby
                    # coordinators and operators read the same snapshot)
                    lease = coordinator.ha_lease
                    self._send(
                        200,
                        lease.snapshot() if lease is not None
                        else {"enabled": False},
                    )
                    return
                if path == "/v1/status":
                    queries = coordinator.manager.list_queries()
                    self._send(
                        200,
                        {
                            "nodeCount": 1,
                            "runningQueries": sum(
                                1 for q in queries if not q.state.is_done
                            ),
                            "totalQueries": len(queries),
                        },
                    )
                    return
                if path == "/ui/api/stats":
                    # ClusterStatsResource.java analogue: the numbers the
                    # React UI's landing page renders
                    queries = coordinator.manager.list_queries()
                    by_state: Dict[str, int] = {}
                    for q in queries:
                        by_state[q.state.name] = by_state.get(q.state.name, 0) + 1
                    nodes = coordinator.nodes.all_nodes()
                    self._send(
                        200,
                        {
                            "runningQueries": sum(
                                1 for q in queries if not q.state.is_done
                            ),
                            "queuedQueries": by_state.get("QUEUED", 0),
                            "finishedQueries": by_state.get("FINISHED", 0),
                            "failedQueries": by_state.get("FAILED", 0),
                            "totalQueries": len(queries),
                            "queriesByState": by_state,
                            "activeWorkers": sum(
                                1 for n in nodes if not n.coordinator
                            ),
                            "totalNodes": max(len(nodes), 1),
                        },
                    )
                    return
                if path == "/v1/node":
                    self._send(
                        200,
                        [
                            {
                                "nodeId": n.node_id,
                                "uri": n.uri,
                                "state": n.state.value,
                                "coordinator": n.coordinator,
                                "lastHeartbeat": n.last_heartbeat,
                            }
                            for n in coordinator.nodes.all_nodes()
                        ],
                    )
                    return
                if len(parts) == 2 and parts[:1] == ["v1"] and parts[1] == "query":
                    payload = [
                        coordinator._query_info(q)
                        for q in coordinator.manager.list_queries()
                    ]
                    self._send(200, payload)
                    return
                if len(parts) == 3 and parts[0] == "v1" and parts[1] == "query":
                    q = coordinator.manager.get(parts[2])
                    if q is None:
                        # fleet follower read: any member answers a status
                        # poll for a query it does not own from the board
                        # the owner publishes on lifecycle transitions
                        board = coordinator._fleet_board_status(parts[2])
                        if board is not None:
                            self._send(200, board)
                            return
                        self._send(404, {"error": "unknown query"})
                        return
                    self._send(200, coordinator._query_info_detail(q))
                    return
                if (
                    len(parts) == 5
                    and parts[0] == "v1"
                    and parts[1] == "statement"
                    and parts[2] == "executing"
                ):
                    query_id, token = parts[3], int(parts[4])
                    q = coordinator.manager.get(query_id)
                    if q is None:
                        self._send(404, {"error": "unknown query"})
                        return
                    coordinator.manager.end_client_turn(q)
                    # long-poll-ish: wait briefly for progress (the reference's
                    # ExecutingStatementResource does the same with maxWait)
                    if not q.state.is_done:
                        q.wait_done(timeout=1.0)
                    self._stream_results(q, token)
                    return
                self._send(404, {"error": f"not found: {path}"})

            def do_DELETE(self):
                if self._authenticate() is None:
                    return
                path = urlparse(self.path).path
                parts = [p for p in path.split("/") if p]
                if len(parts) == 3 and parts[0] == "v1" and parts[1] == "spooled":
                    # segment acknowledgement (SpoolingManager.delete)
                    coordinator.spooling.delete_segment(parts[2])
                    self._send(204, {})
                    return
                from ..runtime.query_manager import CancelResult, QueryNotFound

                if len(parts) >= 4 and parts[1] == "statement":
                    # protocol cancel: an already-finished OR history-evicted
                    # query is a client-side race, not an error — a client
                    # closing its statement handle after the bounded ring
                    # dropped the id must still get the no-op 204
                    try:
                        coordinator.manager.cancel(parts[3])
                    except QueryNotFound:
                        pass
                    self._send(204, {})
                    return
                if len(parts) == 3 and parts[0] == "v1" and parts[1] == "query":
                    # admin cancel (QueryResource.cancelQuery): the right
                    # status per outcome — 404 unknown, 409 already terminal
                    try:
                        result = coordinator.manager.cancel(parts[2])
                    except QueryNotFound:
                        self._send(404, {"error": "unknown query"})
                        return
                    if result is CancelResult.TERMINAL:
                        self._send(409, {"error": "query already finished"})
                        return
                    self._send(204, {})
                    return
                self._send(404, {"error": "not found"})

        # stdlib default accept backlog is 5: a concurrent-session storm
        # overflows it and every dropped SYN costs the client a ~1s
        # retransmit. Sizing the listen queue is part of the fleet front
        # plane (runtime/fleet.py main defaults it to 128 per process);
        # the default deployment keeps the shipped listen(5) behavior.
        backlog = knobs.env_int("TRINO_TPU_HTTP_BACKLOG", 0)
        if backlog > 0:
            class _CoordinatorHTTPServer(ThreadingHTTPServer):
                request_queue_size = backlog
        else:
            _CoordinatorHTTPServer = ThreadingHTTPServer

        self._http_server_cls = _CoordinatorHTTPServer
        self._server = _CoordinatorHTTPServer((host, port), Handler)
        self.port = self._server.server_port
        self._thread: Optional[threading.Thread] = None
        # coordinator fleet plane (runtime/fleet.py): membership on the fs
        # substrate when deployed ($TRINO_TPU_FLEET_DIR or an explicit
        # member); plus the optional SO_REUSEPORT front listener so N
        # forked coordinator processes share one client-facing port while
        # membership advertises each process's unique port for routing
        self.fleet = fleet
        if self.fleet is None:
            from ..runtime.fleet import member_from_env

            self.fleet = member_from_env(
                f"http://{host}:{self.port}", node_id=node_id,
                cluster_metrics=self.cluster_metrics,
            )
        self._front_server = None
        self._front_thread: Optional[threading.Thread] = None
        if front_port is None:
            front_port = knobs.env_int("TRINO_TPU_FLEET_FRONT_PORT", 0)
        if front_port:
            import socket

            class _ReusePortServer(self._http_server_cls):
                allow_reuse_address = True

                def server_bind(inner):
                    if hasattr(socket, "SO_REUSEPORT"):
                        inner.socket.setsockopt(
                            socket.SOL_SOCKET, socket.SO_REUSEPORT, 1
                        )
                    ThreadingHTTPServer.server_bind(inner)

            self._front_server = _ReusePortServer((host, front_port), Handler)
        if self.fleet is not None:
            from ..runtime.fleet import FleetStatusListener
            from ..runtime.metrics import REGISTRY

            depth = REGISTRY.gauge(
                "trino_tpu_protocol_queue_depth",
                help="queries waiting on a resource-group concurrency slot",
            )
            self.fleet.queue_depth_fn = lambda: int(depth.value)
            self.manager.add_listener(FleetStatusListener(self.fleet))
        # serving fabric plane (runtime/ha.py): a leader lease on the shared
        # substrate when HA is deployed ($TRINO_TPU_HA_DIR or an explicit
        # lease); the runner's FTE journal appends fence on the same epoch
        self.ha_lease = ha_lease
        if self.ha_lease is None:
            ha_dir = knobs.env_path("TRINO_TPU_HA_DIR")
            if ha_dir:
                from ..runtime.ha import LeaderLease

                self.ha_lease = LeaderLease(
                    ha_dir, node_id=f"coordinator-{os.getpid()}-{self.port}"
                )
        if self.ha_lease is not None and hasattr(runner, "ha_lease"):
            runner.ha_lease = self.ha_lease
        self._ha_stop: Optional[threading.Event] = None
        self._ha_thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------------ api

    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"

    def start(self) -> "CoordinatorServer":
        # named: the hostprof sampler and the deterministic-tid Perfetto
        # contract both group on thread names
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True,
            name=f"coordinator-http-{self.port}",
        )
        self._thread.start()
        if self._front_server is not None:
            # the shared SO_REUSEPORT client-facing listener: the kernel
            # load-balances accepts across the forked sibling processes
            self._front_thread = threading.Thread(
                target=self._front_server.serve_forever, daemon=True,
                name=f"coordinator-front-{self._front_server.server_port}",
            )
            self._front_thread.start()
        if self.fleet is not None:
            self.fleet.start()
        # host-path plane: $TRINO_TPU_HOSTPROF runs the sampling profiler +
        # GIL-contention probe for the process lifetime (no-op when off)
        from ..runtime.hostprof import start_server_profiling

        start_server_profiling()
        # the coordinator is a node too (system.runtime.nodes shows the whole
        # cluster, like the reference's CoordinatorNodeManager)
        from ..connectors.system import device_kind

        pool = self.manager.memory_pool
        self.nodes.announce(
            "coordinator", f"http://{self.address}", coordinator=True,
            version=__version__, device=device_kind(),
            memory=pool.memory_announcement() if pool is not None else None,
        )
        if self.ha_lease is not None:
            # primary grabs the lease; either way the maintenance loop
            # below keeps it honest — the holder renews at ttl/3, a
            # standby keeps watching and takes over when the lease lapses
            self.ha_lease.acquire()
            self._ha_stop = threading.Event()
            self._ha_thread = threading.Thread(
                target=self._ha_loop, daemon=True, name="ha-lease"
            )
            self._ha_thread.start()
        return self

    def _ha_loop(self) -> None:
        """Lease maintenance: renew while leading, re-attempt acquisition
        while standing by. Dies with the process — a crashed coordinator
        stops renewing, which is exactly what lets the standby take over."""
        lease = self.ha_lease
        while not self._ha_stop.wait(max(0.05, lease.ttl / 3.0)):
            try:
                if lease.epoch > 0:
                    lease.renew()
                else:
                    lease.acquire()
            except Exception:  # noqa: BLE001 — maintenance must never die
                pass

    def stop(self, crash: bool = False) -> None:
        """``crash=True`` models a dead process for the fleet plane: the
        membership record is NOT deregistered — it stays until its TTL
        lapses, which is what drives hash-range reassignment."""
        if self._ha_stop is not None:
            self._ha_stop.set()
        if self.fleet is not None:
            self.fleet.stop(deregister=not crash)
        if self._front_server is not None:
            self._front_server.shutdown()
            self._front_server.server_close()
        self._server.shutdown()
        self._server.server_close()
        self.spooling.close()

    # --------------------------------------------------------- fleet routing

    def _fleet_route(self, handler, sql: str, user: str) -> bool:
        """Partitioned-admission routing for one POST /v1/statement under
        the fleet plane. Returns True when a response has been sent (the
        statement was redirected or proxied to its owner); False means
        this coordinator serves it locally — because it owns the key, or
        because the statement is follower-servable (system.*-only, or a
        warm result-cache hit via the PURE ``peek_cached_result`` probe
        against the shared tier)."""
        from ..runtime.fleet import (
            FOLLOWER_READS_HELP,
            ROUTED_HELP,
            _counter,
            is_system_read,
            partition_key,
        )
        from ..runtime.hostprof import phase_span
        from ..runtime.observability import RECORDER

        fleet = self.fleet
        with phase_span(RECORDER, "route") as sp:
            source = handler.headers.get("X-Trino-Source", "")
            if knobs.env_flag("TRINO_TPU_FLEET_FOLLOWER_READS", True):
                if is_system_read(sql):
                    sp["outcome"] = "follower_read"
                    _counter(
                        "trino_tpu_fleet_follower_reads_total",
                        FOLLOWER_READS_HELP,
                    ).inc()
                    return False
                peek = getattr(self.runner, "peek_cached_result", None)
                hit = None
                if peek is not None:
                    try:
                        hit = peek(sql, user=user)
                    except Exception:  # noqa: BLE001 — probe must stay pure
                        hit = None
                if hit is not None:
                    # the owner never sees a warm hit: the local submit
                    # path serves it from the shared tier before the gate.
                    # Hand the peeked result to admission so the serving
                    # path does not repeat the plan/key/lookup work.
                    handler._fleet_warm_hit = hit
                    sp["outcome"] = "warm_hit"
                    _counter(
                        "trino_tpu_fleet_follower_reads_total",
                        FOLLOWER_READS_HELP,
                    ).inc()
                    return False
            group = ""
            if knobs.env_str(
                "TRINO_TPU_FLEET_PARTITION_BY", "session"
            ) == "group" and self.manager.resource_groups is not None:
                try:
                    group = self.manager.resource_groups.group_path(
                        user, source
                    )
                except Exception:  # noqa: BLE001 — no selector match
                    group = ""
            key = partition_key(user, source, group)
            owner = fleet.owner_of(key)
            sp["owner"] = owner.get("node_id", "")
            if owner.get("node_id") == fleet.node_id:
                sp["outcome"] = "self"
                return False
            mode = knobs.env_str("TRINO_TPU_FLEET_ROUTE", "redirect")
            if mode != "proxy":
                sp["outcome"] = "redirect"
                _counter(
                    "trino_tpu_fleet_routed_total", ROUTED_HELP
                ).inc()
                handler._send(
                    307,
                    {"redirect": owner["url"]},
                    extra_headers={
                        "Location": f"{owner['url']}/v1/statement",
                        "X-Trino-Fleet-Owner": owner.get("node_id", ""),
                    },
                )
                return True
            sp["outcome"] = "proxy"
        self._fleet_proxy(handler, sql, owner)
        return True

    def _fleet_proxy(self, handler, sql: str, owner: dict) -> None:
        """Forward the statement intake to the owner and relay its
        response verbatim. Only the intake is proxied: the owner's
        nextUri points at the owner's own address, so result paging goes
        direct (one extra hop per statement, zero per page)."""
        import urllib.error
        import urllib.request

        from ..runtime.fleet import PROXIED_HELP, _counter
        from ..runtime.hostprof import phase_span
        from ..runtime.observability import RECORDER

        with phase_span(
            RECORDER, "proxy", owner=owner.get("node_id", "")
        ):
            fwd_headers = {
                k: v for k, v in handler.headers.items()
                if k.lower().startswith("x-trino")
                or k.lower() == "authorization"
            }
            req = urllib.request.Request(
                f"{owner['url']}/v1/statement", data=sql.encode(),
                method="POST", headers=fwd_headers,
            )
            try:
                with urllib.request.urlopen(req, timeout=60) as resp:
                    status, body = resp.status, resp.read()
                    relay = {
                        k: v for k, v in resp.headers.items()
                        if k.lower().startswith("x-trino")
                    }
            except urllib.error.HTTPError as e:
                status, body, relay = e.code, e.read(), {}
            except (urllib.error.URLError, OSError) as e:
                handler._send(
                    503, {"error": f"fleet owner unreachable: {e}"}
                )
                return
            _counter("trino_tpu_fleet_proxied_total", PROXIED_HELP).inc()
            handler.send_response(status)
            handler.send_header("Content-Type", "application/json")
            handler.send_header("Content-Length", str(len(body)))
            for k, v in relay.items():
                handler.send_header(k, v)
            handler.end_headers()
            handler.wfile.write(body)

    def _fleet_board_status(self, query_id: str) -> Optional[Dict]:
        """Follower status read: the owner-published board record for a
        query this coordinator does not hold (None = not fleet-deployed,
        follower reads off, or no record)."""
        if self.fleet is None or not knobs.env_flag(
            "TRINO_TPU_FLEET_FOLLOWER_READS", True
        ):
            return None
        board = self.fleet.read_status(query_id)
        if board is not None:
            from ..runtime.fleet import FOLLOWER_READS_HELP, _counter

            _counter(
                "trino_tpu_fleet_follower_reads_total", FOLLOWER_READS_HELP
            ).inc()
        return board

    # --------------------------------------------------- cluster observability

    def cluster_trace(self, q) -> Dict:
        """Cross-node trace assembly for one query: the coordinator's own
        flight-recorder segment plus every announced worker's
        ``/v1/flightrecorder?query_id=`` segment, skew-aligned by the clock
        offsets estimated from announcement RTT midpoints and merged into
        one Perfetto timeline (one process lane per node). When the query
        ran under the HA plane, its dispatch-journal records ride along as
        instant markers, stitching both leader epochs of a failover."""
        import os
        import urllib.request

        from ..runtime import clusterobs
        from .worker import SIGNATURE_HEADER, sign

        qids = {q.query_id}
        fte_id = getattr(q, "fte_query_id", None)
        if fte_id:
            qids.add(fte_id)
        segments = {"coordinator": clusterobs.local_segment(qids)}
        # the runner's explicit secret= wins over the env var — workers
        # deployed with a constructor secret would 401 an env-only lookup
        secret = (
            getattr(self.runner, "secret", None)
            or knobs.env_str("TRINO_TPU_INTERNAL_SECRET")
        )
        for n in self.nodes.all_nodes():
            if n.coordinator or not n.uri:
                continue
            rel = "/v1/flightrecorder"
            url = f"{n.uri.rstrip('/')}{rel}?query_id={fte_id or q.query_id}"
            req = urllib.request.Request(url, method="GET")
            sig = sign(secret, "GET", rel)
            if sig:
                req.add_header(SIGNATURE_HEADER, sig)
            try:
                with urllib.request.urlopen(req, timeout=5.0) as resp:
                    payload = json.loads(resp.read())
            except (OSError, ValueError):
                continue  # a dead node costs its lane, never the merge
            trace = payload.get("trace") if isinstance(payload, dict) else None
            if isinstance(trace, dict):
                segments[n.node_id] = trace
        # the journal copy attached to the query's stats bundle survives
        # exchange-directory cleanup; a live (uncleaned) journal file is
        # the fallback for queries still in flight
        journal_records = (getattr(q, "query_stats", None) or {}).get("journal")
        if not journal_records and fte_id:
            mgr = getattr(self.runner, "_fte_manager", None)
            base = getattr(mgr, "base_dir", None)
            if base:
                from ..runtime.ha import DispatchJournal

                path = DispatchJournal.path_for(base, fte_id)
                if os.path.isfile(path):
                    journal_records, _ = DispatchJournal.read(path)
        return clusterobs.assemble_cluster_trace(
            segments,
            offsets=self.clock_sync.offsets(),
            journal_records=journal_records,
        )

    # ------------------------------------------------------------------- ui

    def _ui_html(self) -> str:
        import html as html_mod

        all_queries = self.manager.list_queries()
        running = sum(1 for q in all_queries if not q.state.is_done)
        queries = sorted(
            all_queries, key=lambda q: q.stats.create_time, reverse=True
        )[:50]
        nodes = self.nodes.all_nodes()
        rows = "\n".join(
            f"<tr><td><a href='/v1/query/{q.query_id}'>{q.query_id}</a></td>"
            f"<td>{q.state.value}</td><td>{q.stats.elapsed:.2f}s</td>"
            f"<td>{q.stats.rows}</td>"
            f"<td><code>{html_mod.escape(q.sql[:120])}</code></td></tr>"
            for q in queries
        )
        # node_id/uri arrive from announcements — escape like everything else
        node_rows = "\n".join(
            f"<tr><td>{html_mod.escape(n.node_id)}</td><td>{n.state.value}</td>"
            f"<td>{html_mod.escape(n.uri)}</td></tr>"
            for n in nodes
        )
        return f"""<!doctype html><html><head><title>trino-tpu</title>
<style>body{{font-family:sans-serif;margin:2em}}table{{border-collapse:collapse}}
td,th{{border:1px solid #ccc;padding:4px 8px;text-align:left}}</style></head>
<body><h1>trino-tpu coordinator</h1>
<p>version {__version__} &middot; {running} running &middot; {len(queries)} recent queries
&middot; {len(nodes)} announced workers</p>
<h2>Queries</h2>
<table><tr><th>id</th><th>state</th><th>elapsed</th><th>rows</th><th>query</th></tr>
{rows}</table>
<h2>Workers</h2>
<table><tr><th>node</th><th>state</th><th>uri</th></tr>{node_rows}</table>
</body></html>"""

    # ------------------------------------------------------------- payloads

    def _query_info(self, q) -> Dict:
        return {
            "queryId": q.query_id,
            "state": q.state.value,
            "query": q.sql,
            "elapsedTime": round(q.stats.elapsed, 4),
            "cpuTime": round(q.stats.cpu_time, 4),
            "rows": q.stats.rows,
            "error": q.error,
        }

    def _query_info_detail(self, q) -> Dict:
        """The full query JSON (ref: server/QueryResource.java:59 — the
        reference returns QueryInfo with the stage/task/operator tree; here
        the operator tree comes from the tracing spans the executor already
        records, nested by parent span)."""
        from ..runtime.tracing import TRACER

        info = self._query_info(q)
        info["queryStats"] = {
            "elapsedTime": round(q.stats.elapsed, 4),
            "cpuTime": round(q.stats.cpu_time, 4),
            "rows": q.stats.rows,
            "state": q.state.value,
            # the server's clock split by the statement's spans
            "queuedTime": round(q.stats.queued_secs, 6),
            "executionTime": round(q.stats.exec_secs, 6),
            # warm-path cache plane: which tier served this query
            # ("result" / "fragment" / "plan"), null on a fully cold run —
            # overwritten from the stats snapshot when one exists
            "cacheHitTier": None,
        }
        # observability plane: Trino-parity attribution fields
        # (QueryStats.java naming — device/host/compile time, spill and
        # exchange byte counts) when the runner produced a stats snapshot
        self.manager.join_feedback(q)  # planNodeStats is its to write
        plane = getattr(q, "query_stats", None)
        if plane is not None:
            from ..runtime.observability import query_stats_fields

            info["queryStats"].update(query_stats_fields(plane))
        spans = TRACER.trace(q.trace_id) if q.trace_id else []
        by_id = {}
        roots = []
        for sp in spans:
            entry = {
                "name": sp["name"],
                "durationMs": sp.get("durationMs"),
                "attributes": sp.get("attributes", {}),
                "children": [],
            }
            by_id[sp["spanId"]] = entry
            parent = sp.get("parentSpanId")
            if parent and parent in by_id:
                by_id[parent]["children"].append(entry)
            else:
                roots.append(entry)
        info["operatorTree"] = roots
        return info

    def _session_headers(self, q) -> Dict[str, str]:
        """Session-state response headers mirroring what the statement changed
        (client-protocol.md: the client accumulates these and re-sends the
        state on subsequent requests): X-Trino-Added-Prepare /
        X-Trino-Deallocated-Prepare / X-Trino-Started-Transaction-Id /
        X-Trino-Clear-Transaction-Id."""
        from urllib.parse import quote

        ctx = getattr(q, "client_ctx", None)
        if ctx is None or not q.state.is_done or not ctx.updates:
            return {}
        headers: Dict[str, str] = {}
        added = ctx.updates.get("added_prepare")
        if added is not None:
            name, sql_text = added
            headers["X-Trino-Added-Prepare"] = (
                f"{quote(name)}={quote(sql_text)}"
            )
        if "deallocated_prepare" in ctx.updates:
            headers["X-Trino-Deallocated-Prepare"] = quote(
                ctx.updates["deallocated_prepare"]
            )
        if "started_txn" in ctx.updates:
            headers["X-Trino-Started-Transaction-Id"] = ctx.updates["started_txn"]
        if ctx.updates.get("clear_txn"):
            headers["X-Trino-Clear-Transaction-Id"] = "true"
        if "set_catalog" in ctx.updates:
            headers["X-Trino-Set-Catalog"] = ctx.updates["set_catalog"]
        if "set_schema" in ctx.updates:
            headers["X-Trino-Set-Schema"] = ctx.updates["set_schema"]
        if "set_session" in ctx.updates:
            name, value = ctx.updates["set_session"]
            headers["X-Trino-Set-Session"] = f"{quote(name)}={quote(value)}"
        if "clear_session" in ctx.updates:
            headers["X-Trino-Clear-Session"] = quote(ctx.updates["clear_session"])
        return headers

    def _pick_encoding(self, requested) -> Optional[str]:
        """First supported spooled encoding, or None for inline results
        (protocol/spooling negotiation)."""
        from ..native import native_available

        for enc in requested:
            if enc == "json":
                return enc
            if enc == "json+lz4" and native_available():
                return enc
        return None

    def _spool_results(self, q, base_uri: str) -> list:
        """Write a finished query's rows into spool segments (idempotent).
        Serialization happens OUTSIDE the lock so one huge result can't block
        other clients' first responses; a losing racer deletes its segments."""
        with self._spool_lock:
            segs = self._spooled.get(q.query_id)
            if segs is not None:
                return segs
        types = q.column_types or [None] * len(q.column_names or [])
        rows = q.rows or []
        built = []
        seg_rows = max(PAGE_ROWS * 8, 1)
        for start in range(0, len(rows), seg_rows):
            chunk = rows[start : start + seg_rows]
            data = json.dumps(_json_rows(chunk, types)).encode()
            raw_len = len(data)
            if q.data_encoding == "json+lz4":
                from ..native import lz4_compress

                data = lz4_compress(data)
            handle = self.spooling.create_segment(data, len(chunk))
            built.append(
                {
                    "uri": f"{base_uri}/v1/spooled/{handle.segment_id}",
                    "segmentId": handle.segment_id,
                    "rowCount": handle.rows,
                    "byteSize": handle.size_bytes,
                    "uncompressedSize": raw_len,
                }
            )
        with self._spool_lock:
            segs = self._spooled.get(q.query_id)
            if segs is not None:  # lost the race: free our duplicates
                for s in built:
                    self.spooling.delete_segment(s["segmentId"])
                return segs
            # prune descriptors of queries the tracker has since expired
            for qid in list(self._spooled):
                if self.manager.get(qid) is None:
                    for s in self._spooled.pop(qid):
                        self.spooling.delete_segment(s["segmentId"])
            self._spooled[q.query_id] = built
            return built

    def _first_response_wait(self) -> float:
        """Seconds the initial POST response may block on query completion
        (session prop ``protocol_first_response_wait``, default 0 = the
        classic immediate-nextUri sequence)."""
        session = getattr(self.runner, "session", None)
        if session is None:
            return 0.0
        try:
            return float(session.get("protocol_first_response_wait") or 0.0)
        except (TypeError, ValueError):
            return 0.0

    def _results_payload(self, q, token: int, base_uri: str) -> Dict:
        payload: Dict = {
            "id": q.query_id,
            "infoUri": f"{base_uri}/v1/query/{q.query_id}",
            "stats": {
                "state": q.state.value,
                "elapsedTimeMillis": int(q.stats.elapsed * 1000),
                # Trino's names, read off the statement's spans
                "queuedTimeMillis": int(q.stats.queued_secs * 1000),
                "planningTimeMillis": int(q.stats.planning_secs * 1000),
                "processedRows": q.stats.rows,
            },
        }
        if q.state == QueryState.FAILED:
            payload["error"] = {
                "message": q.error,
                "errorName": q.error_type or "GENERIC_ERROR",
            }
            return payload
        if not q.state.is_done:
            payload["nextUri"] = (
                f"{base_uri}/v1/statement/executing/{q.query_id}/{token}"
            )
            return payload
        if q.data_encoding is not None and token == 0:
            # spooled protocol: all segments described at once; the client
            # fetches them out-of-band and acks with DELETE
            types = q.column_types or [None] * len(q.column_names or [])
            payload["columns"] = [
                {"name": name, **_type_signature(t)}
                for name, t in zip(q.column_names or [], types)
            ]
            payload["dataEncoding"] = q.data_encoding
            payload["segments"] = self._spool_results(q, base_uri)
            return payload
        # finished: page out rows
        start = token * PAGE_ROWS
        rows = q.rows or []
        chunk = rows[start : start + PAGE_ROWS]
        if q.column_names is not None and token == 0 or chunk:
            types = q.column_types or [None] * len(q.column_names or [])
            payload["columns"] = [
                {"name": name, **_type_signature(t)}
                for name, t in zip(q.column_names or [], types)
            ]
        if chunk:
            types = q.column_types or [None] * (len(chunk[0]) if chunk else 0)
            payload["data"] = _json_rows(chunk, types)
        if start + PAGE_ROWS < len(rows):
            payload["nextUri"] = (
                f"{base_uri}/v1/statement/executing/{q.query_id}/{token + 1}"
            )
        return payload
