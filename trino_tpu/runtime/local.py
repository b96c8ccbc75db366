"""LocalQueryRunner — the single-process engine entry point.

Reference blueprint: io.trino.testing.PlanTester (SURVEY.md §4: "a single-process,
no-HTTP mini engine that plans and can locally execute queries") and
LocalQueryRunner in older Trino. This is both the user-facing embedded API and the
fixture every engine test builds on.
"""

from __future__ import annotations

import datetime
import functools
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from ..metadata import CatalogManager, Metadata, Session
from ..sql import parse_statement
from ..sql import tree as t
from ..planner import LogicalPlanner, optimize, format_plan
from ..planner.plan import LogicalPlan
from .executor import PlanExecutor, resolve_actuals


def _exclusive_times(executor, node, s):
    """(own_wall, own_device, own_host, own_compile) for one executed plan
    node. Exclusive time = inclusive minus children's inclusive;
    device_secs is already exclusive (each child is fenced before its
    parent dispatches); compile subtracts children; host is the remainder.
    Shared by EXPLAIN ANALYZE's per-operator annotations and the
    dominant-cost diagnosis line so the two can never disagree."""
    kids = [
        executor.stats[id(c)] for c in node.sources if id(c) in executor.stats
    ]
    own_wall = max(s.wall_secs - sum(k.wall_secs for k in kids), 0.0)
    own_compile = max(s.compile_secs - sum(k.compile_secs for k in kids), 0.0)
    own_device = s.device_secs
    own_host = max(own_wall - own_device - own_compile, 0.0)
    return own_wall, own_device, own_host, own_compile


@dataclass
class QueryResult:
    column_names: List[str]
    rows: List[tuple]
    # output Types, parallel to column_names (None for utility statements —
    # the protocol layer then reports varchar, matching Trino's SHOW output)
    column_types: Optional[List[object]] = None
    # tracing: the query's trace id (runtime.tracing.TRACER holds the spans)
    trace_id: Optional[str] = None
    # observability plane: QueryStatsCollector.snapshot() of this execution
    # (device/host/compile attribution + spill/exchange/prefetch counters)
    query_stats: Optional[dict] = None

    def __iter__(self):
        return iter(self.rows)

    def __len__(self):
        return len(self.rows)

    def to_dicts(self) -> List[dict]:
        return [dict(zip(self.column_names, r)) for r in self.rows]


@dataclass
class ClientContext:
    """Protocol-level client session state (ref: io.trino.Session's
    preparedStatements + transactionId, carried on the wire by the
    X-Trino-Prepared-Statement / X-Trino-Transaction-Id headers,
    client-protocol.md). Prepared statements and the open explicit
    transaction belong to the CLIENT SESSION, not to whichever pool thread
    happens to run the statement — dispatching COMMIT to a different thread
    than START TRANSACTION must still see the same transaction.

    ``updates`` records session-state changes made by the last statement so
    the protocol layer can mirror them to response headers
    (X-Trino-Added-Prepare / X-Trino-Started-Transaction-Id / ...)."""

    prepared: Dict[str, Any] = field(default_factory=dict)
    txn: Optional[Any] = None
    updates: Dict[str, Any] = field(default_factory=dict)


class LocalQueryRunner:
    def __init__(self, session: Optional[Session] = None, access_control=None):
        from ..spi.security import AllowAllAccessControl
        from .transactions import TransactionManager

        self.catalogs = CatalogManager()
        self.metadata = Metadata(self.catalogs)
        self.session = session or Session()
        self.access_control = access_control or AllowAllAccessControl()
        self.transactions = TransactionManager()
        # per-query principal is thread-local: the QueryManager pool runs
        # concurrent queries as different authenticated users. Transaction
        # and prepared-statement state lives in a ClientContext keyed by the
        # protocol session (embedded callers share the runner default).
        import threading

        self._user_tls = threading.local()
        self._ctx_tls = threading.local()

    @property
    def _client(self) -> ClientContext:
        """The active protocol client context, or — for embedded callers that
        pass none — a PER-THREAD default: QueryManager pool threads run
        concurrent queries, and one thread's START TRANSACTION must not
        capture another thread's autocommit writes in its undo log."""
        ctx = getattr(self._ctx_tls, "ctx", None)
        if ctx is not None:
            return ctx
        default = getattr(self._ctx_tls, "default", None)
        if default is None:
            default = ClientContext()
            self._ctx_tls.default = default
        return default

    @property
    def _txn(self):
        return self._client.txn

    @_txn.setter
    def _txn(self, value):
        self._client.txn = value

    @staticmethod
    def tpch(scale: float = 0.01, schema: Optional[str] = None) -> "LocalQueryRunner":
        """Runner with the tpch catalog mounted (the standard test fixture,
        like Trino's TpchQueryRunner). Default schema matches ``scale``."""
        from ..connectors.tpch import TpchConnector

        if schema is None:
            schema = "sf" + f"{scale:g}".replace(".", "_")
        runner = LocalQueryRunner(Session(catalog="tpch", schema=schema))
        runner.register_catalog("tpch", TpchConnector(scale=scale))
        return runner

    def register_catalog(self, name: str, connector) -> None:
        # invalidate only when REPLACING a name in this registry: cached
        # plans may embed the old connector's handles/types. A fresh name
        # (or a brand-new runner mounting its catalogs) cannot alias — plan
        # keys carry this registry's cache_nonce — and wiping on every
        # runner construction would destroy a warm process-wide cache (and
        # truncate the persisted $TRINO_TPU_RESULT_CACHE file) for nothing.
        replacing = self.catalogs.get(name) is not None
        self.catalogs.register(name, connector)
        if replacing:
            from .cachestore import CACHES

            CACHES.on_ddl()

    # ------------------------------------------------------------------ plans

    def plan_sql(self, sql: str) -> LogicalPlan:
        stmt = parse_statement(sql)
        if isinstance(stmt, t.Explain):
            raise ValueError("use explain() for EXPLAIN statements")
        planner = LogicalPlanner(self.metadata, self.session)
        plan = planner.plan(stmt)
        return optimize(plan, self.metadata, self.session)

    def explain(self, sql: str) -> str:
        stmt = parse_statement(sql)
        if isinstance(stmt, t.Explain):
            stmt = stmt.statement
        planner = LogicalPlanner(self.metadata, self.session)
        plan = planner.plan(stmt)
        plan = optimize(plan, self.metadata, self.session)
        return format_plan(plan)

    # ---------------------------------------------------------------- execute

    def execute(
        self,
        sql: str,
        user: Optional[str] = None,
        client: Optional[ClientContext] = None,
    ) -> QueryResult:
        from .tracing import TRACER

        self._user_tls.user = user or self.session.user
        self._ctx_tls.ctx = client  # None -> runner-default embedded context
        self._client.updates.clear()
        try:
            with TRACER.statement(sql):
                self.access_control.check_can_execute_query(self._current_user())
                # warm path tier (c): a textually-identical statement under
                # identical session state skips parse/analysis/optimization —
                # the cached optimized plan goes straight to execution (where
                # the result tier may short-circuit the rest)
                from .cachestore import CACHES

                hit = stmt = None
                with TRACER.span("parse") as parse:
                    if CACHES.plan_enabled(self.session) and self._txn is None:
                        hit = CACHES.plan.lookup(
                            sql, self.session, self.catalogs.cache_nonce
                        )
                    if hit is not None:
                        parse.attributes["cache"] = "plan"
                    else:
                        stmt = parse_statement(sql)
                if hit is not None:
                    return self._execute_query(None, sql, cached=hit)
                if isinstance(stmt, t.QueryStatement):
                    return self._execute_query(stmt, sql, plan_sql=sql)
                return self._dispatch(stmt, sql)
        finally:
            self._ctx_tls.ctx = None

    def peek_cached_result(
        self, sql: str, user: Optional[str] = None
    ) -> Optional[QueryResult]:
        """Cache-aware admission probe (runtime/query_manager._serve_cached):
        a PURE result-cache lookup that never executes anything — a plan
        (via the plan tier when warm, a fresh parse/optimize otherwise),
        the fingerprint+versions key, and the result-tier entry, or None on
        any miss. The QueryManager serves a hit BEFORE the resource-group
        queue gate, so a warm hit returns in ~ms while the group is
        saturated (ROADMAP item 5). Access control still runs: a user who
        may not read the tables gets None here and the real denial on the
        queued path."""
        from .cachestore import CACHES, profile_plan, resolve_versions

        if self._txn is not None or not CACHES.result_enabled(self.session):
            return None
        try:
            if not bool(self.session.get("cache_aware_admission")):
                return None
        except KeyError:
            pass
        prev_user = getattr(self._user_tls, "user", None)
        self._user_tls.user = user or self.session.user
        try:
            self.access_control.check_can_execute_query(self._current_user())
            plan = profile = None
            if CACHES.plan_enabled(self.session):
                hit = CACHES.plan.lookup(
                    sql, self.session, self.catalogs.cache_nonce
                )
                if hit is not None:
                    plan, profile = hit
            if plan is None:
                stmt = parse_statement(sql)
                if not isinstance(stmt, t.QueryStatement):
                    return None
                planner = LogicalPlanner(self.metadata, self.session)
                plan = optimize(planner.plan(stmt), self.metadata, self.session)
            self._check_select_access(plan)
            if profile is None:
                profile = profile_plan(plan)
            versions = resolve_versions(self.metadata, profile.tables)
            rkey = CACHES.result.key_for(
                profile, versions, self.session,
                registry=self.catalogs.cache_nonce,
            )
            if rkey is None:
                return None
            # peek, not lookup: the probe must stay PURE — no hit/miss
            # counters, no LRU touch, and above all no shared-tier
            # single-flight claim for a query that may then sit queued (or
            # be rejected) without ever materializing. The session lets the
            # peek read (never claim) the shared warm tier, so a fleet
            # follower serves another coordinator's published result
            hit = CACHES.result.peek(rkey, session=self.session)
            if hit is not None and hit.unversioned:
                ttl = float(self.session.get("result_cache_ttl") or 0)
                if ttl > 0 and time.time() - hit.created > ttl:
                    hit = None  # expired TTL-fallback entry: let the
                    # queued path take the real lookup's expiry bookkeeping
            if hit is None:
                return None
            result = QueryResult(
                list(hit.names), list(hit.rows),
                list(hit.types) if hit.types is not None else None,
            )
            result.query_stats = {"cacheHitTier": "result"}
            return result
        except Exception:  # noqa: BLE001 — probe only; the queued path decides
            return None
        finally:
            self._user_tls.user = prev_user

    def _dispatch(self, stmt: t.Statement, sql: str) -> QueryResult:
        if isinstance(stmt, t.Prepare):
            # session-scoped prepared statements (ref: execution/PrepareTask —
            # which likewise rejects nested prepared-statement control verbs,
            # closing the EXECUTE-of-EXECUTE recursion hole)
            if isinstance(
                stmt.statement, (t.Prepare, t.ExecuteStmt, t.Deallocate)
            ):
                raise ValueError(
                    "PREPARE body cannot be PREPARE/EXECUTE/DEALLOCATE"
                )
            self._client.prepared[stmt.name] = stmt.statement
            self._client.updates["added_prepare"] = (stmt.name, stmt.body_text)
            return QueryResult(["result"], [(True,)])
        if isinstance(stmt, t.Deallocate):
            if self._client.prepared.pop(stmt.name, None) is None:
                raise ValueError(f"prepared statement not found: {stmt.name}")
            self._client.updates["deallocated_prepare"] = stmt.name
            return QueryResult(["result"], [(True,)])
        if isinstance(stmt, t.ExecuteStmt):
            prepared = self._client.prepared.get(stmt.name)
            if prepared is None:
                raise ValueError(f"prepared statement not found: {stmt.name}")
            n_params = t.count_parameters(prepared)
            if n_params != len(stmt.parameters):
                raise ValueError(
                    f"prepared statement {stmt.name} expects {n_params} "
                    f"parameters, got {len(stmt.parameters)}"
                )
            bound = t.substitute_parameters(prepared, stmt.parameters)
            return self._dispatch(bound, sql)
        if isinstance(stmt, t.DescribeInput):
            prepared = self._client.prepared.get(stmt.name)
            if prepared is None:
                raise ValueError(f"prepared statement not found: {stmt.name}")
            n_params = t.count_parameters(prepared)
            # parameter types are inferred at EXECUTE time; report unknown
            # like the reference does for untyped positions
            return QueryResult(
                ["Position", "Type"],
                [(i, "unknown") for i in range(n_params)],
            )
        if isinstance(stmt, t.DescribeOutput):
            prepared = self._client.prepared.get(stmt.name)
            if prepared is None:
                raise ValueError(f"prepared statement not found: {stmt.name}")
            if not isinstance(prepared, t.QueryStatement):
                return QueryResult(["Column Name", "Type"], [])
            nulls = tuple(
                t.NullLiteral() for _ in range(t.count_parameters(prepared))
            )
            bound = t.substitute_parameters(prepared, nulls)
            planner = LogicalPlanner(self.metadata, self.session)
            plan = planner.plan(bound)
            plan = optimize(plan, self.metadata, self.session)
            out = plan.root
            names = getattr(out, "column_names", None) or out.output_symbols
            syms = getattr(out, "symbols", None) or out.output_symbols
            return QueryResult(
                ["Column Name", "Type"],
                [
                    (name, plan.types[s].display())
                    for name, s in zip(names, syms)
                ],
            )
        if isinstance(stmt, t.StartTransaction):
            from .transactions import TransactionError

            if self._txn is not None:
                raise TransactionError("a transaction is already in progress")
            self._txn = self.transactions.begin(
                read_only=stmt.read_only, isolation=stmt.isolation
            )
            self._client.updates["started_txn"] = self._txn.txn_id
            return QueryResult(["result"], [(True,)])
        if isinstance(stmt, t.Commit):
            from .transactions import TransactionError

            if self._txn is None:
                raise TransactionError("no transaction in progress")
            try:
                self.transactions.commit(self._txn)
            finally:
                # a failed commit (e.g. idle-expired txn) must not wedge the
                # session in transaction mode forever
                self._txn = None
                self._client.updates["clear_txn"] = True
            return QueryResult(["result"], [(True,)])
        if isinstance(stmt, t.Rollback):
            from .transactions import TransactionError

            if self._txn is None:
                raise TransactionError("no transaction in progress")
            try:
                self.transactions.rollback(self._txn)
            finally:
                self._txn = None
                self._client.updates["clear_txn"] = True
            return QueryResult(["result"], [(True,)])
        if isinstance(stmt, t.Explain):
            inner = stmt.statement
            if stmt.analyze:
                text = self._explain_analyze(inner, verbose=stmt.verbose)
            elif stmt.explain_type == "DISTRIBUTED":
                text = self._explain_distributed(inner)
            else:
                text = self.explain_statement(inner)
            return QueryResult(["Query Plan"], [(line,) for line in text.split("\n")])
        if isinstance(stmt, t.CreateCatalog):
            # dynamic catalogs (ref: the reference's CREATE CATALOG task over
            # CatalogStore + ConnectorFactory resolution; StaticCatalogManager
            # becomes registrable at runtime here)
            from .catalog_factories import create_connector

            self._check_catalog_ddl(stmt.name, "create")
            if self.catalogs.get(stmt.name) is not None:
                if stmt.if_not_exists:
                    return QueryResult(["result"], [(True,)])
                raise ValueError(f"catalog already exists: {stmt.name}")
            connector = create_connector(stmt.connector, dict(stmt.properties))
            self.register_catalog(stmt.name, connector)
            return QueryResult(["result"], [(True,)])
        if isinstance(stmt, t.DropCatalog):
            self._check_catalog_ddl(stmt.name, "drop")
            if self.catalogs.get(stmt.name) is None:
                if stmt.if_exists:
                    return QueryResult(["result"], [(True,)])
                raise ValueError(f"catalog not found: {stmt.name}")
            self.catalogs.deregister(stmt.name)
            from .cachestore import CACHES

            CACHES.on_ddl()
            if self.session.catalog == stmt.name:
                # clear the PAIR: a stale schema against no catalog would
                # half-resolve later unqualified names
                self.session.catalog = None
                self.session.schema = None
            return QueryResult(["result"], [(True,)])
        if isinstance(stmt, t.Use):
            if stmt.catalog is not None:
                if self.metadata.connector_by_name(stmt.catalog) is None:
                    raise ValueError(f"catalog not found: {stmt.catalog}")
                self.session.catalog = stmt.catalog
                self._client.updates["set_catalog"] = stmt.catalog
            self.session.schema = stmt.schema
            self._client.updates["set_schema"] = stmt.schema
            return QueryResult(["result"], [(True,)])
        if isinstance(stmt, t.ShowFunctions):
            from ..sql.functions import AGGREGATE_FUNCTIONS, SCALAR_FUNCTIONS

            rows = []
            for name in sorted(SCALAR_FUNCTIONS):
                if not name.startswith("$"):
                    rows.append((name, "scalar"))
            for name in sorted(AGGREGATE_FUNCTIONS):
                rows.append((name, "aggregate"))
            for r in self.metadata.functions.list():
                rows.append((r.name, "sql routine"))
            return QueryResult(["Function", "Kind"], sorted(rows))
        if isinstance(stmt, t.ShowTables):
            return self._show_tables(stmt)
        if isinstance(stmt, t.ShowSchemas):
            return self._show_schemas(stmt)
        if isinstance(stmt, t.ShowCatalogs):
            # metadata listings go through the access control filter hooks
            # (SystemAccessControl.filterCatalogs)
            names = self.access_control.filter_catalogs(
                self._current_user(), self.catalogs.names()
            )
            return QueryResult(["Catalog"], [(c,) for c in names])
        if isinstance(stmt, t.ShowColumns):
            return self._show_columns(stmt)
        if isinstance(stmt, t.ShowSession):
            rows = [
                (name, str(self.session.get(name)), str(default))
                for name, default in sorted(Session.DEFAULTS.items())
            ]
            return QueryResult(["Name", "Value", "Default"], rows)
        if isinstance(stmt, t.SetSession):
            name = str(stmt.name)
            from ..planner.logical_planner import ExpressionTranslator, Scope

            planner = LogicalPlanner(self.metadata, self.session)
            translator = ExpressionTranslator(planner, Scope([], None))
            const = translator.translate(stmt.value)
            value = getattr(const, "value", None)
            self.session.set(name, value)
            self._client.updates["set_session"] = (name, str(value))
            return QueryResult(["result"], [(True,)])
        if isinstance(stmt, t.ResetSession):
            # back to the default (execution/ResetSessionTask analogue)
            name = str(stmt.name)
            if name not in Session.DEFAULTS:
                raise ValueError(f"unknown session property: {name}")
            self.session.properties.pop(name, None)
            self._client.updates["clear_session"] = name
            return QueryResult(["result"], [(True,)])
        if isinstance(stmt, t.CreateView):
            from ..metadata import ViewDefinition

            catalog, schema, vname = self.metadata.resolve_name(
                self.session, stmt.name
            )
            self.access_control.check_can_create_view(
                self._current_user(), catalog, schema, vname
            )
            # validate the body NOW (ref: CreateViewTask analyzes the query
            # before storing) — a view that can't plan should fail at CREATE
            planner = LogicalPlanner(self.metadata, self.session)
            planner.plan(t.QueryStatement(query=stmt.query))
            self.metadata.views.create(
                catalog, schema, vname,
                ViewDefinition(
                    sql=stmt.query_text,
                    catalog=self.session.catalog,
                    schema=self.session.schema,
                    owner=self._current_user(),
                ),
                replace=stmt.replace,
            )
            from .cachestore import CACHES

            CACHES.on_ddl()  # cached plans may inline a replaced view body
            return QueryResult(["result"], [(True,)])
        if isinstance(stmt, (t.Grant, t.Revoke)):
            catalog, st = self._resolve_name(stmt.table)
            privs = tuple(stmt.privileges) or (
                "SELECT", "INSERT", "DELETE", "UPDATE",
            )
            op = (
                self.access_control.grant
                if isinstance(stmt, t.Grant)
                else self.access_control.revoke
            )
            op(self._current_user(), privs, catalog, st.schema, st.table,
               stmt.grantee)
            return QueryResult(["result"], [(True,)])
        if isinstance(stmt, t.CreateFunction):
            from ..metadata import SqlRoutine
            from ..spi.types import parse_type

            fname = stmt.name.parts[-1]
            params = tuple(
                (p, parse_type(ttext)) for p, ttext in stmt.parameters
            )
            routine = SqlRoutine(
                name=fname,
                parameters=params,
                return_type=parse_type(stmt.return_type),
                body=stmt.body,
                body_text=stmt.body_text,
                owner=self._current_user(),
            )
            # validate NOW (CreateFunctionTask analyzes before storing): plan
            # a probe expression over the declared parameter types
            probe = self.metadata.functions.get(fname, len(params))
            self.metadata.functions.create(routine, replace=stmt.replace)
            try:
                planner = LogicalPlanner(self.metadata, self.session)
                args = ", ".join(
                    f"CAST(NULL AS {ttext})" for _, ttext in stmt.parameters
                )
                planner.plan(parse_statement(f"SELECT {fname}({args})"))
            except Exception:
                # roll back the registration on a body that cannot plan
                self.metadata.functions.drop(fname)
                if probe is not None:
                    self.metadata.functions.create(probe, replace=True)
                raise
            from .cachestore import CACHES

            CACHES.on_ddl()  # cached plans inline routine bodies
            return QueryResult(["result"], [(True,)])
        if isinstance(stmt, t.DropFunction):
            dropped = self.metadata.functions.drop(stmt.name.parts[-1])
            if not dropped and not stmt.if_exists:
                raise ValueError(f"function not found: {stmt.name.parts[-1]}")
            if dropped:
                from .cachestore import CACHES

                CACHES.on_ddl()
            return QueryResult(["result"], [(dropped,)])
        if isinstance(stmt, t.DropView):
            catalog, schema, vname = self.metadata.resolve_name(
                self.session, stmt.name
            )
            self.access_control.check_can_drop_view(
                self._current_user(), catalog, schema, vname
            )
            if not self.metadata.views.drop(catalog, schema, vname):
                if stmt.if_exists:
                    return QueryResult(["result"], [(True,)])
                raise ValueError(
                    f"view not found: {catalog}.{schema}.{vname}"
                )
            from .cachestore import CACHES

            CACHES.on_ddl()
            return QueryResult(["result"], [(True,)])
        if isinstance(stmt, t.ShowCreate):
            catalog, schema, oname = self.metadata.resolve_name(
                self.session, stmt.name
            )
            if stmt.kind == "view":
                view = self.metadata.views.get(catalog, schema, oname)
                if view is None:
                    raise ValueError(
                        f"view not found: {catalog}.{schema}.{oname}"
                    )
                text = (
                    f"CREATE VIEW {catalog}.{schema}.{oname} AS\n{view.sql}"
                )
                return QueryResult(["Create View"], [(text,)])
            handle, meta = self.metadata.resolve_table(self.session, stmt.name)
            col_lines = ",\n".join(
                f"   {c.name} {c.type.display()}" for c in meta.columns
            )
            text = (
                f"CREATE TABLE {catalog}.{schema}.{oname} (\n{col_lines}\n)"
            )
            return QueryResult(["Create Table"], [(text,)])
        if isinstance(stmt, (t.CreateTable, t.CreateTableAsSelect, t.InsertInto, t.DropTable)):
            self._pre_mutation(stmt)
            return self._execute_dml(stmt)
        if isinstance(stmt, t.Call):
            # procedure dispatch (execution/CallTask): arguments must fold to
            # constants, like the reference's bound-expression evaluation
            from ..connectors.system import call_procedure
            from ..planner.logical_planner import ExpressionTranslator, Scope

            parts = self.metadata.resolve_name(self.session, stmt.name)
            planner = LogicalPlanner(self.metadata, self.session)
            translator = ExpressionTranslator(planner, Scope([], None))
            args = []
            for expr in stmt.arguments:
                const = translator.translate(expr)
                if not hasattr(const, "value"):
                    raise ValueError(
                        "CALL arguments must be constant expressions"
                    )
                args.append(const.value)
            names, rows = call_procedure(self, parts, args)
            return QueryResult(names, rows)
        if isinstance(stmt, (t.Delete, t.Update, t.Merge)):
            from .dml import execute_delete, execute_merge, execute_update

            self._pre_mutation(stmt)
            if isinstance(stmt, t.Delete):
                n = execute_delete(self, stmt)
            elif isinstance(stmt, t.Update):
                n = execute_update(self, stmt)
            else:
                n = execute_merge(self, stmt)
            from .cachestore import CACHES

            target = stmt.target if isinstance(stmt, t.Merge) else stmt.table
            catalog, st = self._resolve_name(target)
            CACHES.invalidate_table(catalog, st.schema, st.table)
            return QueryResult(["rows"], [(n,)])
        if not isinstance(stmt, t.QueryStatement):
            raise ValueError(f"unsupported statement: {type(stmt).__name__}")
        # EXECUTE'd prepared statements land here carrying the EXECUTE text —
        # never plan-cache under it (parameters vary call to call); the
        # result tier still applies (bound literals ride the fingerprint)
        return self._execute_query(stmt, sql)

    def _execute_query(
        self, stmt: Optional[t.Statement], sql: str,
        cached=None, plan_sql: Optional[str] = None,
    ) -> QueryResult:
        """The SELECT path, warm-path caches wired through it
        (runtime/cachestore.py): ``cached`` is a plan-cache hit
        ``(plan, PlanProfile)`` — parse/analysis/optimization are skipped;
        ``plan_sql`` set means ``stmt`` is the direct parse of that text and
        the optimized plan may be plan-cached under it. The result tier then
        short-circuits execution entirely on a fingerprint+versions hit."""
        from . import observability as obs
        from .cachestore import (
            CACHES,
            ResultEntry,
            encode_result_rows,
            profile_plan,
            resolve_versions,
        )
        from .tracing import TRACER

        def run_once(_sql_unused=None):
            # observability plane: a per-query collector is active for the
            # whole statement — spill/exchange/compile hooks report to it.
            # sync mode (query_stats_sync) fences every operator for exact
            # device/host/compile attribution; async (default) keeps today's
            # dispatch behavior and reports query-level deltas + counters.
            try:
                sync = bool(self.session.get("query_stats_sync"))
            except KeyError:
                sync = False
            # statement-scoped recording (refcounted): one client's property
            # must not leave the process-wide recorder on forever, and a
            # finishing query must not truncate a concurrent one's recording
            recorder_held = False
            try:
                if self.session.get("flight_recorder"):
                    obs.RECORDER.acquire()
                    recorder_held = True
            except KeyError:
                pass
            # host-path plane (runtime/hostprof.py): same refcounted scope —
            # the sampler runs while any host_profile statement executes
            profiler_held = False
            try:
                if self.session.get("host_profile"):
                    from .hostprof import PROFILER

                    PROFILER.acquire()
                    profiler_held = True
            except KeyError:
                pass
            collector = obs.QueryStatsCollector()
            collector.sync_mode = sync
            # span structure mirrors the reference's planning spans
            # (TracingMetadata: "planner"/"optimizer"/per-stage execution),
            # all children of the statement's root
            cache_tier = None
            rkey = versions = None
            try:
                with obs.collecting(collector), obs.compile_window(), \
                        TRACER.statement(sql) as root:
                    if cached is not None:
                        # plan tier hit: parse/analysis/optimization skipped
                        plan, profile = cached
                        cache_tier = "plan"
                    else:
                        profile = None

                        def _plan_once():
                            with TRACER.span("planner") as planning:
                                planner = LogicalPlanner(
                                    self.metadata, self.session
                                )
                                p = planner.plan(stmt)
                                planning.attributes["decorrelated"] = planner.decorrelated
                            with TRACER.span("optimizer", derived_predicates=0):
                                return optimize(
                                    p, self.metadata, self.session
                                )

                        # plan flights only for directly-parsed statements:
                        # EXECUTE text must never key a shared plan — the
                        # same name can be re-PREPAREd with a different
                        # body (the plan cache refuses these for the same
                        # reason: plan_sql is None here)
                        if plan_sql is not None:
                            plan = self._maybe_plan_flight(sql, _plan_once)
                        else:
                            plan = _plan_once()
                    self._check_select_access(plan)
                    # result tier: fingerprint + versions resolved at ONE
                    # point pre-execution (see the mixed-snapshot guard at
                    # the store below); bypass inside explicit transactions
                    rkey = versions = None
                    if CACHES.result_enabled(self.session) and self._txn is None:
                        if profile is None:
                            profile = profile_plan(plan)
                        versions = resolve_versions(self.metadata, profile.tables)
                        rkey = CACHES.result.key_for(
                            profile, versions, self.session,
                            registry=self.catalogs.cache_nonce,
                        )
                    if rkey is not None:
                        hit = CACHES.result.lookup(rkey, self.session)
                        if hit is not None:
                            result = QueryResult(
                                list(hit.names), list(hit.rows),
                                list(hit.types) if hit.types is not None
                                else None,
                            )
                            result.trace_id = root.trace_id
                            root.attributes["rows"] = len(result.rows)
                            root.attributes["cache"] = "result"
                            snap = collector.snapshot()
                            snap["cacheHitTier"] = "result"
                            snap["cacheProvenance"] = (
                                f"result cache HIT @ {hit.provenance}"
                            )
                            result.query_stats = snap
                            return result
                    if (
                        plan_sql is not None
                        and cached is None
                        and self._txn is None
                        and CACHES.plan_enabled(self.session)
                    ):
                        if profile is None:
                            profile = profile_plan(plan)
                        CACHES.plan.store(
                            plan_sql, self.session, plan, profile,
                            registry=self.catalogs.cache_nonce,
                        )
                    import jax as _jax

                    executor = PlanExecutor(
                        plan, self.metadata, self.session, collect_stats=sync
                    )
                    if (
                        CACHES.fragment_enabled(self.session)
                        and self._txn is None
                    ):
                        from .cachestore import FragmentBinding
                        from .statstore import current_query_id

                        executor.fragment_cache = FragmentBinding(
                            CACHES.fragment, self.metadata, self.session,
                            query_id=current_query_id()
                            or root.trace_id or "",
                            registry=self.catalogs.cache_nonce,
                        )
                    # device batching plane: route batchable subtrees
                    # through the scheduler (off by default — attach()
                    # is a no-op leaving the path byte-identical)
                    from .device_scheduler import attach as _attach_batching

                    _attach_batching(
                        executor, self.metadata, self.session,
                        catalogs=self.catalogs,
                    )
                    # cardinality actuals ride every execution (one async
                    # row-count scalar per operator; host reads deferred
                    # past the drain)
                    try:
                        executor.collect_actuals = bool(
                            self.session.get("statistics_feedback")
                        )
                    except KeyError:
                        executor.collect_actuals = True
                    # the operators: host work and dispatch, device work
                    # overlapped (its `op:` and `sync:` children say where)
                    with TRACER.span("execution", cat="query") as dispatched:
                        names, page = executor.execute()
                    # drain = waiting on in-flight device work only; row
                    # conversion below is pure-Python host time and must
                    # NOT be booked as device time
                    with TRACER.span("drain") as drained:
                        _jax.block_until_ready(page.active)
                    with TRACER.span("encode") as encoded:
                        result = QueryResult(
                            names, page.to_pylist(),
                            [c.type for c in page.columns],
                        )
                        # the rows sent beside the padded page walked for them
                        encoded.attributes.update(
                            rows=len(result.rows), capacity=page.capacity,
                            columns=len(page.columns),
                        )
                    result.trace_id = root.trace_id
                    root.attributes["rows"] = len(result.rows)
                    if executor.fragment_cache_hits and cache_tier is None:
                        cache_tier = "fragment"
                    # result tier store, gated on the mixed-snapshot guard:
                    # versions re-resolved AFTER the drain must equal the
                    # pre-execution snapshot — a DML that committed mid-run
                    # (concurrent INSERT) would otherwise record a row set
                    # that is half old snapshot, half new. The raced run
                    # still RETURNS its rows; it just never caches them.
                    if rkey is not None:
                        v_after = resolve_versions(self.metadata, profile.tables)
                        if v_after != versions:
                            # the raced run never publishes: free a claimed
                            # shared-tier flight so peers stop waiting on it
                            CACHES.result.release_flight(rkey, self.session)
                        if v_after == versions:
                            from .statstore import current_query_id

                            nbytes, rows_enc = encode_result_rows(result.rows)
                            entry = ResultEntry(
                                names=list(result.column_names),
                                types=result.column_types,
                                rows=list(result.rows),
                                nbytes=nbytes,
                                rows_encoded=rows_enc,
                                created=time.time(),
                                tables=profile.tables,
                                versions=versions,
                                query_id=current_query_id()
                                or root.trace_id or "",
                                unversioned=any(v is None for v in versions),
                            )
                            CACHES.result.store(rkey, entry, self.session)
                    # statistics feedback plane: fold per-node actuals into
                    # the collector, flag mis-estimates, feed the history
                    # store (runtime/statstore.py). Where the caller offers
                    # a place to run it later (the QueryManager: after
                    # FINISHED, once the statement's root has closed) it is
                    # handed over below and costs the statement nothing;
                    # everywhere else it runs here, post-drain and before
                    # this returns. It holds the executor's actuals, not the
                    # executor; a feedback failure never fails the query.
                    feedback = None
                    if executor.collect_actuals:
                        from . import statstore

                        feedback = statstore.Feedback(
                            plan, self.metadata, self.session, collector,
                            functools.partial(
                                resolve_actuals, executor.actuals,
                                executor.dyn_filters,
                            ),
                            query_id=self._feedback_query_id(root),
                        )
                        if not statstore.feedback_is_deferred():
                            feedback.run()
                            feedback = None
            except BaseException:
                if rkey is not None:
                    # a shared-tier single-flight lease claimed at lookup
                    # time must not outlive a failed/canceled run — free it
                    # now instead of stalling the fleet until the TTL lapses
                    # (end_flight no-ops when this process holds nothing)
                    CACHES.result.release_flight(rkey, self.session)
                raise
            finally:
                if recorder_held:
                    obs.RECORDER.release()
                if profiler_held:
                    from .hostprof import PROFILER

                    PROFILER.release()
            if sync:
                # wall/compile are inclusive of children — convert to
                # EXCLUSIVE before aggregating, or nested operators would
                # double-count (device_secs is already exclusive: each
                # child is fenced before its parent dispatches)
                for s in executor.stats.values():
                    kids = [
                        executor.stats[id(c)]
                        for c in s.node.sources
                        if id(c) in executor.stats
                    ]
                    wall = max(
                        s.wall_secs - sum(k.wall_secs for k in kids), 0.0
                    )
                    comp = max(
                        s.compile_secs - sum(k.compile_secs for k in kids), 0.0
                    )
                    collector.add_operator(
                        type(s.node).__name__,
                        device_secs=s.device_secs,
                        host_secs=max(wall - s.device_secs - comp, 0.0),
                        compile_secs=comp,
                        rows=s.output_rows,
                    )
                collector.add_time(
                    "device_busy_secs",
                    sum(s.device_secs for s in executor.stats.values()),
                )
            else:
                # async attribution, read off the spans: dispatch covers host
                # + overlapped device work, the drain is the last wait for
                # the device and not its busy time (exact splits need
                # query_stats_sync)
                collector.add_time("drain_secs", drained.duration_secs)
                collector.add_time("dispatch_secs", dispatched.duration_secs)
            self._book_planning(collector, root)
            snap = collector.snapshot()
            snap["cacheHitTier"] = cache_tier
            if executor.cache_provenance:
                snap["cacheProvenance"] = sorted(
                    set(executor.cache_provenance.values())
                )
            result.query_stats = snap
            if feedback is not None:
                # the run writes its planNodes into this snapshot
                feedback.defer(snap, hold_recorder=recorder_held)
            return result

        from .failure import execute_with_retry

        return execute_with_retry(
            run_once, sql, retry_policy=str(self.session.get("retry_policy"))
        )

    @staticmethod
    def _book_planning(collector, root) -> None:
        """Planning seconds of the statement, read off its spans: analysis is
        the planner's, planning is parse + planner + optimizer."""
        from .tracing import child_secs

        tree = root._trace or ()
        collector.add_time("analysis_secs", child_secs(tree, root, "planner"))
        collector.add_time(
            "planning_secs",
            child_secs(tree, root, "parse", "planner", "optimizer"),
        )

    def _maybe_plan_flight(self, sql: str, compute):
        """Device batching plane: concurrent identical statements share ONE
        parse/plan/optimize pass (single-flight with the continuous-batching
        linger, runtime/device_scheduler.py) — the wave-of-N planning herd
        that otherwise serializes on the host. Gated exactly like the plan
        cache tier: nondeterministic statement text, history_based_stats
        (replanning is the point there), and open transactions bypass; the
        key carries user/catalog/schema/set-props and the catalog registry
        nonce, so a plan can never cross resolution contexts."""
        try:
            enabled = bool(self.session.get("device_batching"))
        except KeyError:
            enabled = False
        if not enabled or self._txn is not None:
            return compute()
        from .cachestore import session_props_key, sql_mentions_nondeterminism

        if sql_mentions_nondeterminism(sql):
            return compute()
        if bool(self.session.get("history_based_stats")):
            return compute()
        from .device_scheduler import SCHEDULER

        key = (
            "plan", sql, self.session.user,
            getattr(self.catalogs, "cache_nonce", ""),
            session_props_key(self.session),
        )
        return SCHEDULER.plan_flight(key, compute)

    @staticmethod
    def _feedback_query_id(root) -> str:
        """Operator-stats attribution id: the QueryManager's query id when
        one is installed on this thread, else the trace id."""
        from .statstore import current_query_id

        return current_query_id() or root.trace_id or ""

    def _check_catalog_ddl(self, catalog: str, op: str) -> None:
        """Catalog DDL authz (SystemAccessControl checkCanCreateCatalog /
        checkCanDropCatalog): honored when the installed access control
        implements the hooks; the built-in rule-based impl may not."""
        hook = getattr(self.access_control, f"check_can_{op}_catalog", None)
        if hook is not None:
            hook(self._current_user(), catalog)

    def _current_user(self) -> str:
        return getattr(self._user_tls, "user", None) or self.session.user

    def _resolve_name(self, qname):
        """Qualified-name -> (catalog, SchemaTableName) with session defaults
        (the write-target variant of Metadata.resolve_table — the target may
        not exist yet, so this can't go through table resolution)."""
        from ..spi.connector import SchemaTableName

        parts = qname.parts
        if len(parts) == 3:
            return parts[0], SchemaTableName(parts[1], parts[2])
        if self.session.catalog is None:
            raise ValueError(f"no default catalog set for table {qname}")
        if len(parts) == 2:
            return self.session.catalog, SchemaTableName(parts[0], parts[1])
        return self.session.catalog, SchemaTableName(
            self.session.schema or "default", parts[0]
        )

    def _pre_mutation(self, stmt: t.Statement) -> None:
        """Access-control checks + transaction pre-image capture before any
        write statement runs (ref: the checkCanXxx calls in the statement
        tasks, e.g. CreateTableTask/DeleteTask; TransactionManager undo)."""
        ac = self.access_control
        user = self._current_user()
        if isinstance(stmt, (t.CreateTable, t.CreateTableAsSelect)):
            catalog, st = self._resolve_name(stmt.name)
            ac.check_can_create_table(user, catalog, st.schema, st.table)
        elif isinstance(stmt, t.DropTable):
            catalog, st = self._resolve_name(stmt.name)
            ac.check_can_drop_table(user, catalog, st.schema, st.table)
        elif isinstance(stmt, t.InsertInto):
            catalog, st = self._resolve_name(stmt.table)
            ac.check_can_insert(user, catalog, st.schema, st.table)
        elif isinstance(stmt, t.Delete):
            catalog, st = self._resolve_name(stmt.table)
            ac.check_can_delete(user, catalog, st.schema, st.table)
        elif isinstance(stmt, t.Update):
            catalog, st = self._resolve_name(stmt.table)
            ac.check_can_update(user, catalog, st.schema, st.table)
        elif isinstance(stmt, t.Merge):
            catalog, st = self._resolve_name(stmt.target)
            for case in stmt.cases:
                if not case.matched:
                    ac.check_can_insert(user, catalog, st.schema, st.table)
                elif case.operation == "delete":
                    ac.check_can_delete(user, catalog, st.schema, st.table)
                else:
                    ac.check_can_update(user, catalog, st.schema, st.table)
        else:
            return
        if self._txn is not None:
            from .transactions import TransactionError, TxnState

            if self._txn.state is not TxnState.ACTIVE:
                # idle-expired (already rolled back by the manager): leave
                # transaction mode so the session can recover
                self._txn = None
                raise TransactionError(
                    "transaction was idle-expired and rolled back"
                )
            connector = self.catalogs.get(catalog)
            if connector is not None and hasattr(connector, "table"):
                self.transactions.record_pre_image(self._txn, catalog, connector, st)

    def _check_select_access(self, plan) -> None:
        """check_can_select on every scanned table (AccessControl.checkCanSelect
        at analysis time in the reference; post-optimize here so pruned scans
        are not re-checked)."""
        from ..planner.plan import TableScanNode

        user = self._current_user()

        def walk(node):
            if isinstance(node, TableScanNode):
                h = node.table
                self.access_control.check_can_select(
                    user,
                    h.catalog,
                    h.schema_table.schema,
                    h.schema_table.table,
                    [c for _, c in node.assignments],
                )
            for s in node.sources:
                walk(s)

        root = getattr(plan, "root", plan)
        walk(root)

    def _execute_dml(self, stmt: t.Statement) -> QueryResult:
        """DDL/DML statements (ref: execution/CreateTableTask.java et al. — the
        ~70 DataDefinitionTask classes; round 1 covers CTAS/INSERT/DROP against
        writable connectors like memory/blackhole)."""
        from ..spi.connector import ColumnMetadata, SchemaTableName
        from ..planner.plan import OutputNode
        from .executor import PlanExecutor

        resolve = self._resolve_name

        def writable(catalog, op, attr):
            connector = self.catalogs.get(catalog)
            if connector is None:
                raise ValueError(f"catalog not found: {catalog}")
            if not hasattr(connector, attr):
                raise ValueError(f"catalog {catalog} does not support {op}")
            return connector

        from .cachestore import CACHES

        if isinstance(stmt, t.DropTable):
            catalog, st = resolve(stmt.name)
            connector = writable(catalog, "DROP TABLE", "drop_table")
            connector.drop_table(st, if_exists=stmt.if_exists)
            CACHES.on_ddl()
            return QueryResult(["result"], [(True,)])

        if isinstance(stmt, t.CreateTable):
            from ..spi.types import parse_type

            catalog, st = resolve(stmt.name)
            connector = writable(catalog, "CREATE TABLE", "create_table")
            if connector.metadata().get_table_metadata(st) is not None:
                if stmt.if_not_exists:
                    return QueryResult(["result"], [(True,)])
                raise ValueError(f"table already exists: {st}")
            columns = [
                ColumnMetadata(cname, parse_type(ttext))
                for cname, ttext in stmt.columns
            ]
            connector.create_table(st, columns)
            CACHES.on_ddl()
            return QueryResult(["result"], [(True,)])

        # target checks happen BEFORE executing the source query (Trino's
        # CreateTableTask order — don't burn the query on a doomed/no-op DML)
        if isinstance(stmt, t.CreateTableAsSelect):
            catalog, st = resolve(stmt.name)
            connector = writable(catalog, "CREATE TABLE", "create_table")
            if connector.metadata().get_table_metadata(st) is not None:
                if stmt.if_not_exists:
                    return QueryResult(["rows"], [(0,)])
                raise ValueError(f"table already exists: {st}")
        else:
            catalog, st = resolve(stmt.table)
            connector = writable(catalog, "INSERT", "insert")
            if connector.metadata().get_table_metadata(st) is None:
                raise ValueError(f"table not found: {st}")

        query = stmt.query
        planner = LogicalPlanner(self.metadata, self.session)
        plan = planner.plan(t.QueryStatement(query=query))
        plan = optimize(plan, self.metadata, self.session)
        self._check_select_access(plan)
        executor = PlanExecutor(plan, self.metadata, self.session)
        names, page = executor.execute()

        if isinstance(stmt, t.CreateTableAsSelect):
            columns = [
                ColumnMetadata(name, col.type)
                for name, col in zip(names, page.columns)
            ]
            connector.create_table(st, columns)
            n = connector.insert(st, page)
            CACHES.on_ddl()
            return QueryResult(["rows"], [(n,)])

        # INSERT INTO
        meta = connector.metadata().get_table_metadata(st)
        target_cols = list(meta.columns)
        if stmt.columns:
            if list(stmt.columns) != [c.name for c in target_cols]:
                raise ValueError(
                    "INSERT column list must match table columns in order (round 1)"
                )
        if page.num_columns != len(target_cols):
            raise ValueError(
                f"INSERT has {page.num_columns} columns, table has {len(target_cols)}"
            )
        from ..spi.types import (
            ArrayType,
            VectorType,
            common_super_type,
            is_numeric,
        )

        converted = list(page.columns)
        for i, (col, target) in enumerate(zip(page.columns, target_cols)):
            if isinstance(target.type, VectorType) and col.type != target.type:
                # tensor plane ingest: array literals/columns land on the
                # dense vector layout here (host boundary — length
                # mismatches raise loudly, unlike the expression-level CAST)
                from ..spi.types import UnknownType

                if isinstance(col.type, UnknownType):
                    # an all-NULL VALUES column: the NULL vector column
                    import jax.numpy as _jnp

                    from ..spi.page import Column

                    cap = int(col.valid.shape[0])
                    converted[i] = Column(
                        target.type,
                        _jnp.zeros(
                            (cap, target.type.dimension), dtype=_jnp.float64
                        ),
                        _jnp.zeros((cap,), dtype=_jnp.bool_),
                    )
                    continue
                if not (
                    isinstance(col.type, ArrayType)
                    and is_numeric(col.type.element)
                ) and not isinstance(col.type, VectorType):
                    raise ValueError(
                        f"INSERT column {i} ({target.name}): cannot insert "
                        f"{col.type.display()} into {target.type.display()}"
                    )
                from ..ops.tensor import column_to_vector

                try:
                    converted[i] = column_to_vector(col, target.type)
                except ValueError as e:
                    raise ValueError(
                        f"INSERT column {i} ({target.name}): {e}"
                    ) from e
                continue
            if col.type != target.type and common_super_type(col.type, target.type) != target.type:
                raise ValueError(
                    f"INSERT column {i} ({target.name}): cannot insert "
                    f"{col.type.display()} into {target.type.display()}"
                )
        if any(c is not o for c, o in zip(converted, page.columns)):
            page = page.with_columns(converted)
        n = connector.insert(st, page)
        # exact invalidation on the snapshot bump (iceberg-lite commits a new
        # snapshot above; memory tables bump their mutation counter): every
        # warm entry touching the table drops NOW, not at TTL expiry
        CACHES.invalidate_table(catalog, st.schema, st.table)
        return QueryResult(["rows"], [(n,)])

    def explain_statement(self, stmt: t.Statement) -> str:
        planner = LogicalPlanner(self.metadata, self.session)
        plan = planner.plan(stmt)
        plan = optimize(plan, self.metadata, self.session)
        return format_plan(plan, annotate=self._cache_annotator(plan)) \
            if self._caches_on() else format_plan(plan)

    # ------------------------------------------------------- cache provenance

    def _caches_on(self) -> bool:
        from .cachestore import CACHES

        return (
            CACHES.result_enabled(self.session)
            or CACHES.fragment_enabled(self.session)
        )

    def _cache_annotator(self, plan):
        """EXPLAIN per-node + per-query cache provenance (rendered only when
        a cache tier is enabled, so default plans print byte-identically).
        The result-tier line rides the root node; fragment-tier entries
        annotate the subtree they would serve."""
        from .cachestore import (
            CACHES,
            FragmentBinding,
            profile_plan,
            resolve_versions,
            versions_provenance,
        )

        root = plan.root
        lines: Dict[int, str] = {}
        if CACHES.result_enabled(self.session) and self._txn is None:
            profile = profile_plan(plan)
            versions = resolve_versions(self.metadata, profile.tables)
            key = CACHES.result.key_for(
                profile, versions, self.session,
                registry=self.catalogs.cache_nonce,
            )
            hit = CACHES.result.peek(key)
            if hit is not None:
                lines[id(root)] = (
                    f"   [result cache HIT @ {hit.provenance}]"
                )
            elif key is not None:
                lines[id(root)] = (
                    f"   [result cache MISS @ "
                    f"{versions_provenance(profile.tables, versions)}]"
                )
            else:
                lines[id(root)] = "   [result cache BYPASS]"
        if CACHES.fragment_enabled(self.session) and self._txn is None:
            from ..planner.plan import AggregationNode

            binding = FragmentBinding(
                CACHES.fragment, self.metadata, self.session,
                registry=self.catalogs.cache_nonce,
            )

            class _Probe:
                pass  # subtree_cacheable memoizes per-"executor" object

            probe = _Probe()

            def walk(node):
                if isinstance(node, AggregationNode) \
                        and CACHES.fragment.subtree_cacheable(node, probe):
                    e = CACHES.fragment.peek(node, binding)
                    if e is not None:
                        who = e.query_id or "an earlier query"
                        lines[id(node)] = (
                            f"   [fragment reused from query {who}]"
                        )
                for s in node.sources:
                    walk(s)

            walk(root)

        def annotate(node) -> str:
            return lines.get(id(node), "")

        return annotate

    def _explain_distributed(self, stmt: t.Statement) -> str:
        """EXPLAIN (TYPE DISTRIBUTED): the fragmented plan, one section per
        stage with its partitioning (ref: sql/planner/planprinter's
        distributed output + PlanFragmenter)."""
        from ..planner.fragmenter import add_exchanges, create_fragments

        planner = LogicalPlanner(self.metadata, self.session)
        plan = planner.plan(stmt)
        plan = optimize(plan, self.metadata, self.session)
        plan = add_exchanges(plan, self.metadata, self.session)
        sub = create_fragments(plan)
        lines = []
        for frag in sorted(sub.fragments, key=lambda f: f.fragment_id, reverse=True):
            lines.append(
                f"Fragment {frag.fragment_id} [{frag.partitioning.value}] "
                f"<- {sorted(frag.input_fragments)}"
            )
            body = format_plan(LogicalPlan(frag.root, sub.types))
            lines.extend("    " + ln for ln in body.split("\n"))
            lines.append("")
        return "\n".join(lines).rstrip()

    def _explain_analyze(self, stmt: t.Statement, verbose: bool = False) -> str:
        """EXPLAIN ANALYZE: execute with per-operator stats (the
        ExplainAnalyzeOperator path, SURVEY.md §5.1), rendering per-node
        ESTIMATED vs ACTUAL rows with the q-error — the statistics feedback
        plane's primary human surface. VERBOSE adds the observability
        plane's per-operator device/host/compile attribution (stats
        collection fences each operator, so the splits are exact)."""
        from .statstore import q_error

        if not isinstance(stmt, t.QueryStatement):
            raise ValueError("EXPLAIN ANALYZE supports queries only")
        planner = LogicalPlanner(self.metadata, self.session)
        plan = planner.plan(stmt)
        plan = optimize(plan, self.metadata, self.session)
        # EXPLAIN ANALYZE executes the query — same access checks as execute()
        self._check_select_access(plan)
        executor = PlanExecutor(plan, self.metadata, self.session, collect_stats=True)
        executor.collect_actuals = True
        if verbose:
            # VERBOSE is the kernel cost plane's human surface: force
            # attribution on regardless of the kernel_cost session property
            # (stats mode already fences every operator, so the roofline's
            # device_secs denominator is exact)
            executor.kernel_cost_enabled = True
        from .cachestore import CACHES, FragmentBinding

        if CACHES.fragment_enabled(self.session) and self._txn is None:
            from .statstore import current_query_id

            executor.fragment_cache = FragmentBinding(
                CACHES.fragment, self.metadata, self.session,
                query_id=current_query_id() or "",
                registry=self.catalogs.cache_nonce,
            )
        executor.execute()

        from . import observability as obs
        from . import statstore
        from ..planner.stats import make_estimator

        # the estimator must snapshot history BEFORE this run records its
        # own actuals: under history_based_stats the just-recorded rows
        # would otherwise overlay the rendering and every node would show
        # est == actual (q=1.0) — hiding exactly the mis-estimates the
        # est-vs-actual output exists to surface
        estimator = make_estimator(self.metadata, plan.types, self.session)

        # the analyzed run feeds the same history/misestimate plane a plain
        # execution does (Presto HBO records from analyze too); inline
        # whoever calls
        statstore.Feedback(
            plan, self.metadata, self.session,
            obs.current_collector() or obs.QueryStatsCollector(),
            executor.finalize_actuals,
            query_id=statstore.current_query_id() or "",
        ).run()

        def fmt_rows(v) -> str:
            if v is None:
                return "?"
            v = float(v)
            for unit, div in (("G", 1e9), ("M", 1e6), ("K", 1e3)):
                if v >= div:
                    return f"{v / div:.2g}{unit}"
            return f"{v:.0f}"

        def annotate(node) -> str:
            prov = executor.cache_provenance.get(id(node))
            prov_text = f" [{prov}]" if prov else ""
            s = executor.stats.get(id(node))
            if s is None:
                return prov_text
            own_wall, own_device, own_host, own_compile = _exclusive_times(
                executor, node, s
            )
            try:
                est = estimator.rows(node)
            except Exception:  # noqa: BLE001
                est = None
            q = q_error(est, s.output_rows)
            qtext = f" (q={q:.1f})" if q is not None else ""
            base = (
                f"   [rows: est {fmt_rows(est)} -> actual "
                f"{s.output_rows:,}{qtext} capacity={s.output_capacity:,} "
                f"time={own_wall * 1000:.2f}ms"
            )
            if not verbose:
                return base + "]" + prov_text
            kc_text = ""
            kc = executor.kernel_costs.get(id(node))
            if kc and kc.get("programs"):
                from . import kernelcost

                line = kernelcost.render_roofline(
                    kc.get("flops"), kc.get("bytes_accessed"),
                    kc.get("peak_hbm_bytes"),
                    device_secs=own_device if own_device > 0 else None,
                )
                if line:
                    kc_text = f" [kernel: {line}]"
                elif kc.get("unavailable"):
                    kc_text = " [kernel: cost_unavailable]"
            return (
                base
                + f" device={own_device * 1000:.2f}ms"
                + f" host={own_host * 1000:.2f}ms"
                + f" compile={own_compile * 1000:.2f}ms]"
                + prov_text
                + kc_text
            )

        text = format_plan(plan, annotate=annotate)
        if verbose and self._cluster_obs_enabled():
            # cluster observability plane: the dominant-cost diagnosis line
            # ("stage 2: 61% exchange pull" on FTE profiles; here the per-
            # operator device/host/compile split plays the stage role)
            diag = self._dominant_cost_line(plan, executor)
            if diag:
                text += f"\n\ndominant cost — {diag}"
        return text

    def _cluster_obs_enabled(self) -> bool:
        try:
            return bool(self.session.get("cluster_obs"))
        except KeyError:
            return False

    def _dominant_cost_line(self, plan, executor) -> Optional[str]:
        """EXPLAIN ANALYZE VERBOSE's diagnosis: which operator owns the
        query's time and which component (device/host/compile) dominates
        it — the same renderer FTE query profiles use per stage. Splits
        come from the same :func:`_exclusive_times` the per-operator
        annotations render, so the line can never contradict them."""
        from .clusterobs import dominant_cost

        entries = []

        def walk(node) -> None:
            s = executor.stats.get(id(node))
            if s is not None:
                own_wall, own_device, own_host, own_compile = (
                    _exclusive_times(executor, node, s)
                )
                entries.append((
                    type(node).__name__, own_wall,
                    {"device_secs": own_device, "host_secs": own_host,
                     "compile_secs": own_compile},
                ))
            for c in node.sources:
                walk(c)

        walk(plan.root)
        return dominant_cost(entries)

    # ------------------------------------------------------------------ show

    def _show_tables(self, stmt: t.ShowTables) -> QueryResult:
        catalog = self.session.catalog
        schema = self.session.schema
        if stmt.schema is not None:
            parts = stmt.schema.parts
            if len(parts) == 2:
                catalog, schema = parts
            else:
                schema = parts[0]
        connector = self.metadata.connector_by_name(catalog) if catalog else None
        if connector is None:
            raise ValueError(f"catalog not set or not found: {catalog}")
        tables = connector.metadata().list_tables(schema)
        tables = self.access_control.filter_tables(
            self._current_user(), catalog, tables
        )
        return QueryResult(["Table"], [(st.table,) for st in tables])

    def _show_schemas(self, stmt: t.ShowSchemas) -> QueryResult:
        catalog = stmt.catalog or self.session.catalog
        connector = self.metadata.connector_by_name(catalog) if catalog else None
        if connector is None:
            raise ValueError(f"catalog not set or not found: {catalog}")
        schemas = self.access_control.filter_schemas(
            self._current_user(), catalog, connector.metadata().list_schemas()
        )
        return QueryResult(["Schema"], [(s,) for s in schemas])

    def _show_columns(self, stmt: t.ShowColumns) -> QueryResult:
        from ..sql.tree import QualifiedName

        handle, meta = self.metadata.resolve_table(self.session, stmt.table)
        # schema of a fully-denied table must not leak (checkCanShowColumns)
        visible = self.access_control.filter_tables(
            self._current_user(), handle.catalog, [handle.schema_table]
        )
        if not visible:
            from ..spi.security import AccessDeniedError

            raise AccessDeniedError(
                f"Cannot show columns of table {handle.schema_table}"
            )
        return QueryResult(
            ["Column", "Type"],
            [(c.name, c.type.display()) for c in meta.columns],
        )
