"""Client-protocol conformance matrix.

Black-box validation of `/v1/statement` against the reference's documented
client protocol, keyed to the sections of
docs/src/main/sphinx/develop/client-protocol.md (no JVM Trino client can
run in this image, so conformance is
asserted against the protocol DOCUMENT, the same contract those clients
implement).

Deviation, declared: session catalog/schema/property state lives
server-side in this engine (the reference carries it client-side via
echoed headers); the response headers mirroring state changes ARE emitted
per the doc, which is what a conforming client consumes.
"""

import json
import urllib.error
import urllib.request

import pytest

from trino_tpu.server import CoordinatorServer


@pytest.fixture(scope="module")
def server(tpch_tiny):
    srv = CoordinatorServer(tpch_tiny).start()
    yield srv
    srv.stop()


def _post(server, sql, headers=None):
    req = urllib.request.Request(
        f"http://{server.address}/v1/statement",
        data=sql.encode(),
        method="POST",
    )
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read()), dict(resp.headers)


def _get(url):
    with urllib.request.urlopen(url, timeout=30) as resp:
        return json.loads(resp.read()), dict(resp.headers)


def _drain(server, sql, headers=None):
    """doc 'Overview of query processing': loop GET nextUri until absent."""
    payload, hdrs = _post(server, sql, headers)
    rows = list(payload.get("data") or [])
    pages = 1
    while "nextUri" in payload:
        payload, h2 = _get(payload["nextUri"])
        hdrs.update(h2)
        rows.extend(payload.get("data") or [])
        pages += 1
        assert pages < 1000, "nextUri loop did not terminate"
    return payload, rows, hdrs


class TestOverviewOfQueryProcessing:
    """doc section 'Overview of query processing'."""

    def test_post_returns_queryresults_and_nexturi_loop_terminates(self, server):
        payload, rows, _ = _drain(server, "SELECT n_nationkey FROM nation ORDER BY 1")
        assert [r[0] for r in rows] == list(range(25))
        assert "nextUri" not in payload  # completed

    def test_success_has_no_error_field(self, server):
        payload, _, _ = _drain(server, "SELECT 1")
        assert payload.get("error") is None

    def test_status_field_is_present_for_humans(self, server):
        payload, _ = _post(server, "SELECT 1")
        assert "stats" in payload and "state" in payload["stats"]

    def test_http_200_even_for_failed_queries(self, server):
        # 'Any HTTP status other than 502/503/504 or 200 means processing
        # failed' — semantic failures still arrive AS QueryResults.error
        payload, _, _ = _drain(server, "SELECT no_such_column FROM nation")
        assert payload.get("error") is not None


class TestQueryResultsAttributes:
    """doc section 'Important QueryResults attributes'."""

    def test_id_columns_data_shapes(self, server):
        payload, rows, _ = _drain(
            server, "SELECT n_name, n_nationkey FROM nation ORDER BY 2 LIMIT 3"
        )
        assert payload["id"]
        cols = payload["columns"]
        assert [c["name"] for c in cols] == ["n_name", "n_nationkey"]
        assert all("type" in c for c in cols)
        assert len(rows) == 3 and len(rows[0]) == 2

    def test_error_is_queryerror_shaped(self, server):
        payload, _, _ = _drain(server, "SELECT bogus FROM nation")
        err = payload["error"]
        assert "message" in err
        assert "errorCode" in err or "errorName" in err

    def test_parse_error_shape(self, server):
        payload, _, _ = _drain(server, "SELEKT 1")
        assert payload["error"] is not None


class TestClientRequestHeaders:
    """doc section 'Client request headers'."""

    def test_user_header_sets_session_user(self, server):
        payload, _, _ = _drain(
            server, "SELECT 1", headers={"X-Trino-User": "alice"}
        )
        assert payload.get("error") is None

    def test_prepared_statement_header_round_trip(self, server):
        from urllib.parse import quote

        # client re-sends prepared statements on every request
        payload, _, hdrs = _drain(server, "PREPARE p1 FROM SELECT count(*) FROM nation")
        assert "X-Trino-Added-Prepare" in hdrs
        name_eq_sql = hdrs["X-Trino-Added-Prepare"]
        payload, rows, _ = _drain(
            server, "EXECUTE p1", headers={"X-Trino-Prepared-Statement": name_eq_sql}
        )
        assert rows == [[25]]

    def test_deallocate_mirrors_header(self, server):
        _, _, h1 = _drain(server, "PREPARE p2 FROM SELECT 1")
        _, _, h2 = _drain(
            server,
            "DEALLOCATE PREPARE p2",
            headers={"X-Trino-Prepared-Statement": h1["X-Trino-Added-Prepare"]},
        )
        assert h2.get("X-Trino-Deallocated-Prepare") == "p2"

    def test_transaction_header_flow(self, server):
        _, _, h1 = _drain(server, "START TRANSACTION")
        txn = h1.get("X-Trino-Started-Transaction-Id")
        assert txn
        _, _, h2 = _drain(
            server, "COMMIT", headers={"X-Trino-Transaction-Id": txn}
        )
        assert h2.get("X-Trino-Clear-Transaction-Id") == "true"


class TestClientResponseHeaders:
    """doc section 'Client response headers'."""

    def test_use_mirrors_set_catalog_and_schema(self, server):
        _, _, hdrs = _drain(server, "USE tpch.tiny")
        assert hdrs.get("X-Trino-Set-Catalog") == "tpch"
        assert hdrs.get("X-Trino-Set-Schema") == "tiny"

    def test_set_session_mirrors_header(self, server):
        _, _, hdrs = _drain(server, "SET SESSION task_concurrency = 2")
        assert hdrs.get("X-Trino-Set-Session") == "task_concurrency=2"

    def test_reset_session_mirrors_clear_header(self, server):
        _drain(server, "SET SESSION task_concurrency = 2")
        _, _, hdrs = _drain(server, "RESET SESSION task_concurrency")
        assert hdrs.get("X-Trino-Clear-Session") == "task_concurrency"


class TestCancellation:
    """doc: 'a client can cancel a query by sending a DELETE to nextUri'."""

    def test_delete_next_uri_cancels(self, server):
        payload, _ = _post(
            server,
            "SELECT count(*) FROM lineitem l1 JOIN lineitem l2 ON l1.l_orderkey = l2.l_orderkey",
        )
        if "nextUri" not in payload:
            pytest.skip("query finished before a cancel point")
        req = urllib.request.Request(payload["nextUri"], method="DELETE")
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.status in (200, 204)
        # the query must terminate (CANCELED/FAILED/FINISHED race is fine;
        # what must NOT happen is an endlessly RUNNING query)
        import time

        qid = payload["id"]
        deadline = time.monotonic() + 30
        state = None
        while time.monotonic() < deadline:
            info, _ = _get(f"http://{server.address}/v1/query/{qid}")
            state = info["state"]
            if state in ("CANCELED", "FAILED", "FINISHED"):
                break
            time.sleep(0.2)
        assert state in ("CANCELED", "FAILED", "FINISHED")
