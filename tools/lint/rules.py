"""Engine-specific lint rules over the trino_tpu AST.

Each rule encodes an invariant one of the concurrency/observability planes
depends on; ids are stable (they appear in baselines and suppressions):

- ``blocking-call-under-lock``   no sleep / foreign Condition.wait / file or
                                 HTTP I/O / nested lock acquire while holding
                                 a lock (the FTE event loop and the memory
                                 arbiter both assume lock-brief sections)
- ``unpaired-flight-span``       ``RECORDER.span(...)`` must be entered as a
                                 ``with`` context manager so the B always
                                 gets its E (the obs_smoke pairing contract,
                                 enforced at the source instead of per-trace)
- ``metric-help-missing``        REGISTRY.counter/gauge/histogram call sites
                                 always pass a non-empty ``help`` kwarg (the
                                 HELP-registered-family contract; the runtime
                                 half is registry_help_problems below)
- ``env-read-outside-knobs``     ``TRINO_TPU_*`` environment reads go through
                                 the central knob registry (trino_tpu/knobs.py)
- ``bare-except-swallow``        no bare ``except:`` anywhere, and no
                                 ``except ...: pass`` swallow in scheduler/
                                 executor paths (a swallowed failure there
                                 becomes a hang or a wrong answer)
- ``undeclared-session-property`` literal ``session.get("...")`` names must
                                 be declared in the knob registry (catches
                                 typo'd knobs that silently KeyError at
                                 runtime)
- ``unnamed-thread``             every ``threading.Thread(...)`` constructed
                                 in the engine passes ``name=`` — thread
                                 names are the host-profile/cluster-trace
                                 lane identity (clusterobs canonical tids
                                 sort by name; hostprof collapses stacks per
                                 name), so a ``Thread-12`` default makes the
                                 lane unattributable
"""

from __future__ import annotations

import ast
from typing import List, Optional, Sequence

from .engine import Finding

# --------------------------------------------------------------------------- #
# helpers
# --------------------------------------------------------------------------- #


def _attr_chain(node: ast.AST) -> str:
    """Dotted name for Name/Attribute chains ('self._lock', 'time.sleep')."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    elif isinstance(node, ast.Call):
        inner = _attr_chain(node.func)
        parts.append(f"{inner}()")
    return ".".join(reversed(parts))


def _base_obj(chain: str) -> str:
    """'self._cond.wait' -> 'self._cond'; 'pool.acquire' -> 'pool'."""
    return chain.rsplit(".", 1)[0] if "." in chain else chain


def _looks_like_lock(chain: str) -> bool:
    last = chain.rsplit(".", 1)[-1].lower()
    if "io_lock" in last or "iolock" in last:
        # the sanctioned dedicated-I/O-serialization-lock pattern (cachestore
        # persistence, event-listener appends): blocking under it is its ONLY
        # job and no shared state may hide behind it — reviewed by name
        return False
    return "lock" in last or "mutex" in last


def rule(id_: str, description: str):
    def deco(fn):
        fn.id = id_
        fn.description = description
        return fn
    return deco


# --------------------------------------------------------------------------- #
# blocking-call-under-lock
# --------------------------------------------------------------------------- #

_SLEEPS = {"time.sleep", "sleep"}
_IO_CALLS = {
    "open", "urlopen", "urllib.request.urlopen", "requests.get",
    "requests.post", "requests.request",
}
_IO_METHOD_SUFFIXES = ("getresponse", "urlopen")


@rule(
    "blocking-call-under-lock",
    "sleep / foreign Condition.wait / file or HTTP I/O / nested lock acquire "
    "while holding a lock",
)
def blocking_call_under_lock(tree: ast.AST, source_lines: Sequence[str],
                             path: str) -> List[Finding]:
    findings: List[Finding] = []

    class V(ast.NodeVisitor):
        def __init__(self):
            # stack of lock object chains currently held via `with`
            self.held: List[str] = []

        def visit_With(self, node: ast.With):
            locks = []
            for item in node.items:
                ctx = item.context_expr
                chain = _attr_chain(ctx.func) if isinstance(ctx, ast.Call) else _attr_chain(ctx)
                # `with lock:` / `with self._lock:` / `with cond:` — treat
                # Condition objects as locks too (entering one acquires it)
                if _looks_like_lock(chain) or "cond" in chain.rsplit(".", 1)[-1].lower():
                    locks.append(chain)
            self.held.extend(locks)
            self.generic_visit(node)
            for _ in locks:
                self.held.pop()

        # a nested def/lambda runs later, not under the lock
        def visit_FunctionDef(self, node):
            saved, self.held = self.held, []
            self.generic_visit(node)
            self.held = saved

        visit_AsyncFunctionDef = visit_FunctionDef

        def visit_Lambda(self, node):
            saved, self.held = self.held, []
            self.generic_visit(node)
            self.held = saved

        def visit_Call(self, node: ast.Call):
            if self.held:
                chain = _attr_chain(node.func)
                leaf = chain.rsplit(".", 1)[-1]
                base = _base_obj(chain)
                problem: Optional[str] = None
                if chain in _SLEEPS:
                    problem = f"sleep under lock {self.held[-1]!r}"
                elif leaf == "wait" and base not in self.held:
                    # cond.wait() inside `with cond:` releases that lock —
                    # fine; waiting on a DIFFERENT condition while holding
                    # this lock blocks everyone behind it
                    problem = (
                        f"wait on {base!r} while holding {self.held[-1]!r}"
                    )
                elif leaf == "acquire" and base not in self.held:
                    problem = (
                        f"nested acquire of {base!r} while holding "
                        f"{self.held[-1]!r}"
                    )
                elif chain in _IO_CALLS or leaf in _IO_METHOD_SUFFIXES:
                    problem = (
                        f"{chain or leaf}() I/O under lock {self.held[-1]!r}"
                    )
                if problem:
                    findings.append(Finding(
                        path, node.lineno, blocking_call_under_lock.id, problem
                    ))
            self.generic_visit(node)

    V().visit(tree)
    return findings


# --------------------------------------------------------------------------- #
# unpaired-flight-span
# --------------------------------------------------------------------------- #

_SPAN_OWNERS = {"RECORDER", "TRACER"}


@rule(
    "unpaired-flight-span",
    "flight-recorder/tracer span calls must be entered as `with` context "
    "managers so every B event gets its E on all code paths",
)
def unpaired_flight_span(tree: ast.AST, source_lines: Sequence[str],
                         path: str) -> List[Finding]:
    findings: List[Finding] = []
    with_items = set()
    returns = set()

    class Collect(ast.NodeVisitor):
        def visit_With(self, node: ast.With):
            for item in node.items:
                if isinstance(item.context_expr, ast.Call):
                    with_items.add(id(item.context_expr))
            self.generic_visit(node)

        def visit_Return(self, node: ast.Return):
            if isinstance(node.value, ast.Call):
                returns.add(id(node.value))
            self.generic_visit(node)

    Collect().visit(tree)

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if not (isinstance(func, ast.Attribute) and func.attr == "span"):
            continue
        owner = _attr_chain(func.value)
        leaf_owner = owner.rsplit(".", 1)[-1]
        if leaf_owner not in _SPAN_OWNERS:
            continue
        if id(node) in with_items:
            continue
        if id(node) in returns:
            # a helper returning the context manager for its caller to
            # `with` — pairing is the caller's job; flag it so the author
            # must either suppress with a reason or restructure
            findings.append(Finding(
                path, node.lineno, unpaired_flight_span.id,
                f"{owner}.span(...) returned instead of entered — pairing "
                "depends on every caller using `with`",
            ))
        else:
            findings.append(Finding(
                path, node.lineno, unpaired_flight_span.id,
                f"{owner}.span(...) not entered via `with` — the B/E pair "
                "is not guaranteed on all code paths",
            ))
    return findings


# --------------------------------------------------------------------------- #
# metric-help-missing (AST half of the HELP lint; runtime half below)
# --------------------------------------------------------------------------- #

_METRIC_CTORS = {"counter", "gauge", "histogram"}


@rule(
    "metric-help-missing",
    "REGISTRY.counter/gauge/histogram call sites must pass a non-empty help "
    "kwarg (every series exported with HELP text)",
)
def metric_help_missing(tree: ast.AST, source_lines: Sequence[str],
                        path: str) -> List[Finding]:
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if not (
            isinstance(func, ast.Attribute)
            and func.attr in _METRIC_CTORS
            and isinstance(func.value, ast.Name)
            and func.value.id in ("REGISTRY", "registry", "reg")
        ):
            continue
        help_kw = next((k for k in node.keywords if k.arg == "help"), None)
        if help_kw is None:
            # positional help (counter(name, labels, help)): the LAST string
            # constant after the name plays the help role in the registry
            # signature — single-word help is fine, empty is not
            positional = [
                a for a in node.args[1:]
                if isinstance(a, ast.Constant) and isinstance(a.value, str)
            ]
            if positional:
                if not positional[-1].value:
                    findings.append(Finding(
                        path, node.lineno, metric_help_missing.id,
                        f"{func.value.id}.{func.attr}(...) with an EMPTY "
                        "positional help string",
                    ))
                continue
            findings.append(Finding(
                path, node.lineno, metric_help_missing.id,
                f"{func.value.id}.{func.attr}(...) without a help kwarg",
            ))
        elif isinstance(help_kw.value, ast.Constant) and not help_kw.value.value:
            findings.append(Finding(
                path, node.lineno, metric_help_missing.id,
                f"{func.value.id}.{func.attr}(...) with an EMPTY help string",
            ))
    return findings


def registry_help_problems(registry=None, required: Sequence[str] = ()) -> List[str]:
    """Runtime half of the HELP lint (the registry contract): every collected
    series carries HELP text, and every ``required`` family is registered.
    Shared by tools/obs_smoke.py and tests — the single implementation the
    old per-plane copies collapsed into."""
    if registry is None:
        from trino_tpu.runtime.metrics import REGISTRY as registry  # noqa: N813
    problems: List[str] = []
    by_name = {}
    for m in registry.collect():
        by_name.setdefault(m["name"], m)
        if not m["help"]:
            problems.append(f"metric {m['name']} missing HELP text")
    for name in required:
        if name not in by_name:
            problems.append(f"metric {name} not registered")
    return sorted(set(problems))


# --------------------------------------------------------------------------- #
# metric-name-conformance
# --------------------------------------------------------------------------- #


@rule(
    "metric-name-conformance",
    "counter names must end in _total (Prometheus convention) and "
    "histogram registrations must declare their bucket bounds explicitly",
)
def metric_name_conformance(tree: ast.AST, source_lines: Sequence[str],
                            path: str) -> List[Finding]:
    """Two conformance halves of the federated-metrics contract:

    - every COUNTER whose name is a literal ends in ``_total`` — the
      cluster exposition merges per-node series by name, and scrape-side
      rate() math assumes the convention;
    - every ``REGISTRY.histogram(...)`` call declares ``buckets=``
      explicitly — cross-node histogram merging requires agreeing bounds,
      and an implicit default at one call site drifts silently when the
      default changes.

    Counter detection covers both the registry surface (``REGISTRY.counter``)
    and the per-module ``_counter("trino_tpu_...", help)`` wrappers: any
    call whose callee name is/ends with ``counter`` with a literal first
    argument starting ``trino_tpu_`` is a metric registration."""
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        leaf = func.attr if isinstance(func, ast.Attribute) else (
            func.id if isinstance(func, ast.Name) else ""
        )
        registry_owner = (
            isinstance(func, ast.Attribute)
            and isinstance(func.value, ast.Name)
            and func.value.id in ("REGISTRY", "registry", "reg")
        )
        name = None
        if node.args and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            name = node.args[0].value
        if leaf.endswith("counter") and leaf not in ("_get",):
            is_metric = registry_owner or (
                name is not None and name.startswith("trino_tpu_")
            )
            if is_metric and name is not None and not name.endswith("_total"):
                findings.append(Finding(
                    path, node.lineno, metric_name_conformance.id,
                    f"counter {name!r} does not end in _total",
                ))
        elif leaf == "histogram" and registry_owner:
            has_buckets = any(k.arg == "buckets" for k in node.keywords) \
                or len(node.args) >= 4
            if not has_buckets:
                findings.append(Finding(
                    path, node.lineno, metric_name_conformance.id,
                    f"histogram {name or '<dynamic>'!r} does not declare "
                    "buckets= explicitly",
                ))
    return findings


# --------------------------------------------------------------------------- #
# env-read-outside-knobs
# --------------------------------------------------------------------------- #


@rule(
    "env-read-outside-knobs",
    "TRINO_TPU_* environment reads must go through the central knob "
    "registry (trino_tpu/knobs.py)",
)
def env_read_outside_knobs(tree: ast.AST, source_lines: Sequence[str],
                           path: str) -> List[Finding]:
    if path.replace("\\", "/").endswith("trino_tpu/knobs.py"):
        return []
    findings: List[Finding] = []

    def is_environ(node: ast.AST) -> bool:
        chain = _attr_chain(node)
        return chain in ("os.environ", "environ")

    def tpu_name(node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            if node.value.startswith("TRINO_TPU_"):
                return node.value
        return None

    for node in ast.walk(tree):
        name = None
        if isinstance(node, ast.Subscript) and is_environ(node.value):
            name = tpu_name(node.slice)
            # os.environ[SOME_ENV_CONST]: same module-constant resolution
            # as the .get(...) form below
            if name is None and isinstance(node.slice, ast.Name):
                name = _module_env_const(tree, node.slice.id)
        elif isinstance(node, ast.Call):
            chain = _attr_chain(node.func)
            if chain in ("os.environ.get", "environ.get", "os.getenv", "getenv"):
                name = tpu_name(node.args[0]) if node.args else None
                # os.environ.get(SOME_ENV_CONST): resolve simple Name args
                # against module-level "X = 'TRINO_TPU_...'" assignments
                if name is None and node.args and isinstance(node.args[0], ast.Name):
                    name = _module_env_const(tree, node.args[0].id)
        if name:
            findings.append(Finding(
                path, node.lineno, env_read_outside_knobs.id,
                f"direct environment read of {name} — use trino_tpu.knobs",
            ))
    return findings


def _module_env_const(tree: ast.AST, ident: str) -> Optional[str]:
    for stmt in getattr(tree, "body", []):
        if isinstance(stmt, ast.Assign):
            for tgt in stmt.targets:
                if (isinstance(tgt, ast.Name) and tgt.id == ident
                        and isinstance(stmt.value, ast.Constant)
                        and isinstance(stmt.value.value, str)
                        and stmt.value.value.startswith("TRINO_TPU_")):
                    return stmt.value.value
    return None


# --------------------------------------------------------------------------- #
# bare-except-swallow
# --------------------------------------------------------------------------- #

# scheduler/executor paths where a swallowed exception becomes a hang or a
# wrong answer instead of a logged anomaly
_CRITICAL_PATH_PARTS = (
    "runtime/fte_scheduler.py", "runtime/executor.py",
    "runtime/query_manager.py", "parallel/runner.py", "server/worker.py",
    "runtime/fte_plane.py",
)


@rule(
    "bare-except-swallow",
    "no bare `except:`; no `except ...: pass` swallow in scheduler/executor "
    "paths",
)
def bare_except_swallow(tree: ast.AST, source_lines: Sequence[str],
                        path: str) -> List[Finding]:
    findings: List[Finding] = []
    norm = path.replace("\\", "/")
    critical = any(norm.endswith(p) for p in _CRITICAL_PATH_PARTS)
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if node.type is None:
            findings.append(Finding(
                path, node.lineno, bare_except_swallow.id,
                "bare `except:` catches KeyboardInterrupt/SystemExit too",
            ))
            continue
        if not critical:
            continue
        body = node.body
        swallows = all(
            isinstance(s, ast.Pass)
            or (isinstance(s, ast.Expr) and isinstance(s.value, ast.Constant))
            for s in body
        )
        if swallows:
            exc = _attr_chain(node.type) if not isinstance(node.type, ast.Tuple) else "(...)"
            findings.append(Finding(
                path, node.lineno, bare_except_swallow.id,
                f"except {exc}: pass swallows failures on a scheduler/"
                "executor path",
            ))
    return findings


# --------------------------------------------------------------------------- #
# undeclared-session-property
# --------------------------------------------------------------------------- #


@rule(
    "undeclared-session-property",
    "literal session.get()/set() property names must be declared in "
    "trino_tpu.knobs.SESSION_PROPERTIES",
)
def undeclared_session_property(tree: ast.AST, source_lines: Sequence[str],
                                path: str) -> List[Finding]:
    from trino_tpu import knobs

    declared = knobs.session_property_names()
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if not (isinstance(func, ast.Attribute) and func.attr in ("get", "set")):
            continue
        owner = _attr_chain(func.value)
        if not owner.endswith("session"):
            continue
        if not node.args:
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            if arg.value not in declared:
                findings.append(Finding(
                    path, node.lineno, undeclared_session_property.id,
                    f"session property {arg.value!r} is not declared in the "
                    "knob registry",
                ))
    return findings


# --------------------------------------------------------------------------- #
# unnamed-thread
# --------------------------------------------------------------------------- #


@rule(
    "unnamed-thread",
    "threading.Thread construction must pass name= — thread names are the "
    "host-profile and cluster-trace lane identity",
)
def unnamed_thread(tree: ast.AST, source_lines: Sequence[str],
                   path: str) -> List[Finding]:
    """The host-path observability plane keys everything on thread names:
    hostprof collapses sampled stacks per ``threading.Thread.name``, and
    clusterobs assigns canonical trace tids by sorted (name, first-activity).
    A default ``Thread-12`` name is nondeterministic across runs and says
    nothing about the lane, so every ``Thread(...)`` / ``threading.Thread``
    / ``_th.Thread`` construction in the engine must pass ``name=``."""
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        if not (chain == "Thread" or chain.endswith(".Thread")):
            continue
        if any(k.arg == "name" for k in node.keywords):
            continue
        if any(k.arg is None for k in node.keywords):
            # Thread(**kwargs) forwarding — the name may ride the dict;
            # resolving that statically is out of scope, don't flag
            continue
        findings.append(Finding(
            path, node.lineno, unnamed_thread.id,
            f"{chain}(...) without name= — unnamed threads are invisible "
            "to the host-profile/cluster-trace lane contract",
        ))
    return findings


# --------------------------------------------------------------------------- #
# jit-without-cost-hook
# --------------------------------------------------------------------------- #


@rule(
    "jit-without-cost-hook",
    "raw jax.jit call sites bypass the kernel cost plane — use "
    "runtime/kernelcost.jit (same signature) so the program's XLA cost "
    "analysis attributes to the launching plan node",
)
def jit_without_cost_hook(tree: ast.AST, source_lines: Sequence[str],
                          path: str) -> List[Finding]:
    """Every jitted engine program must compile through the
    ``kernelcost.jit`` wrapper: it is a transparent pass-through until a
    recording scope is active, and it is the ONLY place the engine can
    observe a program's FLOPs / HBM bytes / peak device memory. A raw
    ``jax.jit`` — as a call, a decorator, or a ``partial(jax.jit, ...)``
    argument — produces a program the cost plane can never attribute. The
    one sanctioned site is inside kernelcost.CostJit itself (inline
    suppression with reason)."""
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and _attr_chain(node) == "jax.jit":
            findings.append(Finding(
                path, node.lineno, jit_without_cost_hook.id,
                "raw jax.jit bypasses the cost-recording wrapper — use "
                "trino_tpu.runtime.kernelcost.jit",
            ))
    return findings


ALL_RULES = (
    blocking_call_under_lock,
    unpaired_flight_span,
    metric_help_missing,
    metric_name_conformance,
    env_read_outside_knobs,
    bare_except_swallow,
    undeclared_session_property,
    unnamed_thread,
    jit_without_cost_hook,
)
