"""Tensor workload plane: VECTOR columns, MXU similarity kernels, model scoring.

Reference blueprint: "Accelerating ML Queries with Linear Algebra Query
Processing" (arXiv:2306.08367) — compile the vector/ML scalar family into
dense linear algebra — and "Query Processing on Tensor Computation Runtimes"
(arXiv:2203.01877) — the payoff comes from keeping the whole pipeline
on-device. ROADMAP item 3: this is the first workload class where the engine
should beat reference Trino by an order of magnitude instead of matching it,
because the MXU sits idle through every purely relational query.

Three pieces live here; the runtime wiring (fused top-k executor, optimizer
rule, fragmenter split) lives with its planes:

- **Similarity lowering** (:func:`compile_vector_call`): ``dot_product`` /
  ``cosine_similarity`` / ``l2_distance`` / ``vector_norm`` over
  ``VECTOR(n)`` columns. A vector column is one contiguous ``data[rows, n]``
  float64 buffer (spi.types.VectorType — the multi-lane scalar layout, NULL
  on the ordinary row mask), so batched evaluation against a constant query
  vector is literally ``data @ q`` — the ``(rows, n) x (n,)`` matvec the MXU
  exists for. Row-wise vector/vector forms (embedding joins) lower to an
  einsum over the lane axis.

- **Model scoring lowering** (:func:`compile_model_call`): linear models and
  small GBDT ensembles compiled to XLA. The model spec rides the IR as a
  hashable constant (plancodec-encodable, jit-static), features stack into a
  ``(rows, k)`` matrix: linear scoring is one ``(rows, k) @ (k,)`` matmul,
  GBDT traversal is ``depth`` vectorized gather steps over all rows AND all
  trees at once. SQL surface: the ``linear_score`` / ``gbdt_score``
  ConnectorTableFunctions (spi/table_function.py), gated on the
  ``model_scoring`` knob.

- **Observability**: ``trino_tpu_vector_kernel_launches_total`` +
  ``trino_tpu_vector_topk_fallbacks_total{reason}`` counters, and the paired
  ``vector_kernel`` / ``topk_fusion`` flight spans (rows/dim/k on E-args).
  Fallback labels are short stable strings:
  ``unprojected_order_key`` (a fusable ORDER BY similarity whose other sort
  keys are not computed by the scoring projection), ``kernel_error`` (the
  fused program failed at runtime; the serial project+sort pair finished the
  query).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..spi.page import Column
from ..spi.types import VectorType, is_vector
from ..sql.functions import VECTOR_SCALAR_FUNCTIONS
from ..sql.ir import Call, Case, CastExpr, Constant, IrExpr
from .compiler import CVal, CompileError

# IR call names for compiled model scoring (emitted by the table functions,
# lowered by compile_model_call); arg 0 is the static spec constant
LINEAR_MODEL_CALL = "$linear_model"
GBDT_MODEL_CALL = "$gbdt_model"
MODEL_CALLS = frozenset({LINEAR_MODEL_CALL, GBDT_MODEL_CALL})


# --------------------------------------------------------------------------- #
# observability: launch/fallback counters + paired kernel/fusion spans
# --------------------------------------------------------------------------- #


def _launch_counter():
    from ..runtime.metrics import REGISTRY

    return REGISTRY.counter(
        "trino_tpu_vector_kernel_launches_total",
        help="tensor-plane device programs launched (vector similarity "
        "projections, fused score->top-k programs, model-scoring matmuls)",
    )


def _fallback_counter(reason: str):
    from ..runtime.metrics import REGISTRY

    return REGISTRY.counter(
        "trino_tpu_vector_topk_fallbacks_total",
        {"reason": reason},
        help="ORDER BY similarity LIMIT k shapes that fell back from the "
        "fused score->top-k program to the serial project+sort pair, "
        "by reason",
    )


def on_vector_kernel(n: int = 1) -> None:
    _launch_counter().inc(n)


def on_topk_fallback(reason: str) -> None:
    """One query shape declined (or abandoned) the fused top-k path;
    ``reason`` is a short stable label (ARCHITECTURE.md enumerates them)."""
    _fallback_counter(reason).inc()
    from ..runtime.observability import RECORDER

    RECORDER.instant("vector_topk_fallback", "tensor", reason=reason)


def vector_launches() -> float:
    return _launch_counter().value


def topk_fallbacks(reason: str) -> float:
    return _fallback_counter(reason).value


def vector_kernel_span(rows: int, dim: int):
    """Paired ``vector_kernel`` flight span; write rows/dim into the yielded
    dict so they land on the E event (the issue contract: E-args carry the
    shape). Callers: the executor's project path and the fused top-k node."""
    from ..runtime.observability import RECORDER

    return _shaped_span(RECORDER, "vector_kernel", rows=rows, dim=dim)


def topk_fusion_span(rows: int, dim: int, k: int):
    from ..runtime.observability import RECORDER

    return _shaped_span(RECORDER, "topk_fusion", rows=rows, dim=dim, k=k)


class _shaped_span:
    """Context manager stacking a RECORDER span and stamping the shape args
    onto the E event (the span yields a mutable dict for exactly this)."""

    def __init__(self, recorder, name: str, **shape):
        self._cm = recorder.span(name, "tensor")
        self._shape = {k: int(v) for k, v in shape.items()}

    def __enter__(self):
        args = self._cm.__enter__()
        args.update(self._shape)
        return args

    def __exit__(self, *exc):
        return self._cm.__exit__(*exc)


# --------------------------------------------------------------------------- #
# vector serving plane observability: query-matrix batching + ANN index tier
# (runtime wiring: device_scheduler vector lanes, connectors/vector_index.py)
# --------------------------------------------------------------------------- #


def _serving_counter(name: str):
    from ..runtime.metrics import REGISTRY

    helps = {
        "trino_tpu_vector_batched_queries_total":
            "concurrent vector top-k statements served per-lane by one "
            "stacked batched device launch (the query-matrix batching win: "
            "lanes minus launches is the amortization)",
        "trino_tpu_ann_pruned_splits_total":
            "IVF cluster splits pruned by the ANN centroid-distance "
            "pre-pass (ann_mode=approx), the partition-pruning analogue "
            "for vector search",
        "trino_tpu_ann_recall_samples_total":
            "approximate vector top-k executions re-run against the "
            "unpruned exact oracle to measure recall@k "
            "(system.runtime.ann_recall rows)",
        "trino_tpu_ann_oracle_errors_total":
            "recall-oracle sampler runs that raised and were dropped "
            "(monitoring only — the serving query itself already "
            "succeeded; a nonzero rate means recall is under-observed)",
    }
    return REGISTRY.counter(name, help=helps[name])


def on_vector_batched(lanes: int) -> None:
    """One stacked launch served ``lanes`` concurrent vector statements."""
    _serving_counter("trino_tpu_vector_batched_queries_total").inc(lanes)


def on_ann_pruned(splits: int) -> None:
    if splits > 0:
        _serving_counter("trino_tpu_ann_pruned_splits_total").inc(splits)


def vector_batched_queries() -> float:
    return _serving_counter("trino_tpu_vector_batched_queries_total").value


def ann_pruned_splits() -> float:
    return _serving_counter("trino_tpu_ann_pruned_splits_total").value


def ann_recall_samples() -> float:
    return _serving_counter("trino_tpu_ann_recall_samples_total").value


def on_ann_oracle_error() -> None:
    """The recall-oracle sampler raised; the serving query already
    succeeded, so the failure is counted instead of propagated."""
    _serving_counter("trino_tpu_ann_oracle_errors_total").inc()


def vector_batch_launch_span(lanes: int, rows: int, dim: int, k: int):
    """Paired ``vector_batch_launch`` flight span around the one stacked
    device program serving a whole vector lane group."""
    from ..runtime.observability import RECORDER

    return _shaped_span(
        RECORDER, "vector_batch_launch", lanes=lanes, rows=rows, dim=dim, k=k
    )


def ann_probe_span(total: int, nprobe: int):
    """Paired ``ann_probe`` flight span around the centroid-distance
    pre-pass; the split manager stamps probed/pruned onto the E args."""
    from ..runtime.observability import RECORDER

    return _shaped_span(RECORDER, "ann_probe", total=total, nprobe=nprobe)


def register_vector_serving_metrics() -> None:
    """Eager registration (the run_batching_smoke convention): exposition
    and the HELP lint must see the families before the first batched
    launch / ANN probe happens to occur."""
    for name in (
        "trino_tpu_vector_batched_queries_total",
        "trino_tpu_ann_pruned_splits_total",
        "trino_tpu_ann_recall_samples_total",
    ):
        _serving_counter(name)


# bounded ring of measured recall@k samples, served by
# system.runtime.ann_recall: (table, k, nprobe, recall, probed, total)
_ANN_RECALL_MAX = 256
_ANN_RECALL: list = []
_ANN_RECALL_LOCK = None  # created lazily (module import must stay cheap)


def _recall_lock():
    global _ANN_RECALL_LOCK
    if _ANN_RECALL_LOCK is None:
        import threading

        _ANN_RECALL_LOCK = threading.Lock()
    return _ANN_RECALL_LOCK


def record_ann_recall(
    table: str, k: int, nprobe: int, recall: float, probed: int, total: int
) -> None:
    _serving_counter("trino_tpu_ann_recall_samples_total").inc()
    from ..runtime.observability import RECORDER

    RECORDER.instant(
        "ann_recall_sample", "tensor", table=table, k=int(k),
        nprobe=int(nprobe), recall=float(recall),
    )
    with _recall_lock():
        _ANN_RECALL.append(
            (str(table), int(k), int(nprobe), float(recall), int(probed),
             int(total))
        )
        del _ANN_RECALL[:-_ANN_RECALL_MAX]


def ann_recall_rows():
    with _recall_lock():
        return list(_ANN_RECALL)


def reset_ann_recall() -> None:
    global _ANN_SAMPLE_SEQ
    with _recall_lock():
        del _ANN_RECALL[:]
        _ANN_SAMPLE_SEQ = 0


_ANN_SAMPLE_SEQ = 0


def ann_sample_due(rate: float) -> bool:
    """Deterministic recall sampler: the Nth eligible execution samples when
    the cumulative expected sample count crosses an integer — rate=1.0
    samples every execution, rate=0.25 every fourth; no RNG, so tests and
    chaos replays are stable."""
    import math

    global _ANN_SAMPLE_SEQ
    r = min(max(float(rate), 0.0), 1.0)
    with _recall_lock():
        _ANN_SAMPLE_SEQ += 1
        s = _ANN_SAMPLE_SEQ
    return math.floor(s * r) > math.floor((s - 1) * r)


# --------------------------------------------------------------------------- #
# query-matrix batching: lane eligibility + the masked coalescing key
# --------------------------------------------------------------------------- #

# the binary similarity family whose constant-query form is one matvec —
# the shapes the vector lane tier stacks (vector_norm and the model calls
# carry no per-statement query constant; they ride subsumption instead)
BATCHABLE_SIM_FUNCS = frozenset(
    {"dot_product", "cosine_similarity", "l2_distance"}
)


def split_query_constant(expr: IrExpr):
    """``sim(col, CONST q)`` (either operand order) -> ``(name, col_expr,
    const_expr)``; None when the expression is not a constant-query
    similarity call. The score expr must BE the call — a wrapped score
    (CAST, arithmetic) stays on the serial fused path."""
    if not (
        isinstance(expr, Call)
        and expr.name in BATCHABLE_SIM_FUNCS
        and len(expr.args) == 2
    ):
        return None
    a, b = expr.args
    qa, qb = constant_vector_value(a), constant_vector_value(b)
    if qb is not None and qa is None:
        return (expr.name, a, b)
    if qa is not None and qb is None:
        return (expr.name, b, a)
    return None


def broadcast_similarity(expr: IrExpr, broadcast_syms) -> bool:
    """``sim(a.v, b.v)`` where exactly one side is a single-row broadcast
    build vector column (the embedding-JOIN shape _join_relations tags):
    semantically a constant-query lane — the stacked path serves it with
    the lane's own einsum closures, bit-identical to the serial pair."""
    from ..sql.ir import Reference

    if not broadcast_syms:
        return False
    if not (
        isinstance(expr, Call)
        and expr.name in BATCHABLE_SIM_FUNCS
        and len(expr.args) == 2
    ):
        return False
    a, b = expr.args
    if not (isinstance(a, Reference) and isinstance(b, Reference)):
        return False
    return (a.symbol in broadcast_syms) != (b.symbol in broadcast_syms)


def vector_batch_masked_node(node, broadcast_syms=frozenset()):
    """The coalescing key's plan half: the VectorTopNNode with the lead
    score's query constant replaced by a NULL placeholder of the same
    type, so statements differing ONLY in the query vector fingerprint
    identically. Collision-safe: a real NULL-query statement never
    becomes a constant-query lane (constant_vector_value returns None
    for NULL), so the placeholder can't alias a live plan.

    Returns ``(masked_node, kind)`` with kind ``"const"`` / ``"bcast"``,
    or None when the shape is not a stackable lane."""
    import dataclasses

    if node.partial or node.count < 0 or not node.orderings:
        return None
    assigned = dict(node.assignments)
    lead = assigned.get(node.orderings[0].symbol)
    if lead is None:
        return None
    if broadcast_similarity(lead, broadcast_syms):
        # the statement identity (source subtree incl. the build side)
        # already rides the fingerprint — nothing to mask
        return node, "bcast"
    parts = split_query_constant(lead)
    if parts is None:
        return None
    name, _col, const = parts
    placeholder = Constant(_type=const.type, value=None)
    masked_call = Call(
        name=name,
        args=tuple(
            placeholder if a is const else a for a in lead.args
        ),
        _type=lead.type,
    )
    masked_assignments = tuple(
        (s, masked_call if e is lead else e) for s, e in node.assignments
    )
    return dataclasses.replace(node, assignments=masked_assignments), "const"


# --------------------------------------------------------------------------- #
# IR analysis helpers (shared by the analyzer, the optimizer rule, the
# sanity checkers, and the executor's span/counter sites)
# --------------------------------------------------------------------------- #


def constant_vector_value(expr: IrExpr) -> Optional[Tuple[float, ...]]:
    """A non-NULL constant vector's host value, else None."""
    if isinstance(expr, Constant) and is_vector(expr.type) and expr.value is not None:
        return tuple(float(x) for x in expr.value)
    return None


def fold_constant_array(expr: IrExpr) -> Optional[Tuple[Optional[float], ...]]:
    """``ARRAY[...]`` of numeric constants -> host tuple of FLOAT VALUES
    (None per NULL element); None when any element is not a constant.
    Constants carry the *storage* representation, so decimal literals
    (``ARRAY[1.0, 2.5]`` parses as decimal(2,1)) descale here. Casts of
    constants fold at analysis time, so the elements are plain Constants."""
    from ..spi.types import ArrayType, DecimalType, DoubleType, RealType

    if isinstance(expr, CastExpr):
        # fold through a cast ONLY when it is value-preserving for the
        # float fold below (array -> array(double/real)); anything else
        # (array(bigint), narrower decimals) changes values — leave it to
        # the runtime CAST path so fold and execution never disagree
        t = expr.type
        if isinstance(t, ArrayType) and isinstance(
            t.element, (DoubleType, RealType)
        ):
            return fold_constant_array(expr.value)
        return None
    if not (isinstance(expr, Call) and expr.name == "$array"):
        return None
    from ..spi.types import UnknownType, is_numeric

    out = []
    for item in expr.args:
        if not isinstance(item, Constant):
            return None
        if not (is_numeric(item.type) or isinstance(item.type, UnknownType)):
            # strings/booleans/temporals never fold to float lanes — the
            # runtime cast path rejects them, and the fold must agree
            return None
        if item.value is None:
            out.append(None)
        elif isinstance(item.type, DecimalType):
            out.append(float(item.value) / 10**item.type.scale)
        else:
            out.append(float(item.value))
    return tuple(out)


def walk_vector_calls(expr: IrExpr):
    """Yield every tensor-plane Call (similarity family + model scoring)
    inside an IR expression."""
    stack = [expr]
    while stack:
        e = stack.pop()
        if isinstance(e, Call):
            if e.name in VECTOR_SCALAR_FUNCTIONS or e.name in MODEL_CALLS:
                yield e
            stack.extend(e.args)
        elif isinstance(e, CastExpr):
            stack.append(e.value)
        elif isinstance(e, Case):
            for c, r in e.whens:
                stack.append(c)
                stack.append(r)
            if e.default is not None:
                stack.append(e.default)


_ASSIGN_INFO: Dict[tuple, Optional[Tuple[int, int]]] = {}


def assignments_vector_info(assignments) -> Optional[Tuple[int, int]]:
    """(n_tensor_calls, max_dim) over a projection's assignments, or None
    when the projection touches no tensor-plane call. Memoized on the
    (hashable, frozen) assignments tuple — this runs per project execution
    on the hot path, the walk must not."""
    hit = _ASSIGN_INFO.get(assignments, False)
    if hit is not False:
        return hit
    count = 0
    max_dim = 0
    for _, e in assignments:
        for call in walk_vector_calls(e):
            count += 1
            for a in call.args:
                if is_vector(a.type):
                    max_dim = max(max_dim, a.type.dimension)
    info = (count, max_dim) if count else None
    if len(_ASSIGN_INFO) > 4096:  # bound the memo like the compiler cache
        _ASSIGN_INFO.clear()
    _ASSIGN_INFO[assignments] = info
    return info


def vector_dimension_problems(expr: IrExpr):
    """Static shape errors inside an expression, as text — the sanity plane's
    VECTOR-aware check (a dimension mismatch must fail plan validation
    naming the checker, never inside a kernel). Yields messages."""
    for call in walk_vector_calls(expr):
        if call.name in VECTOR_SCALAR_FUNCTIONS:
            dims = []
            for i, a in enumerate(call.args):
                if not is_vector(a.type):
                    yield (
                        f"{call.name} argument {i + 1} has type "
                        f"{a.type.display() if a.type else '?'}, expected vector"
                    )
                else:
                    dims.append(a.type.dimension)
            if len(dims) == 2 and dims[0] != dims[1]:
                yield (
                    f"{call.name}: vector dimensions do not match "
                    f"({dims[0]} vs {dims[1]})"
                )
        elif call.name == LINEAR_MODEL_CALL:
            spec = call.args[0].value if isinstance(call.args[0], Constant) else None
            if spec is not None and len(spec[0]) != len(call.args) - 1:
                yield (
                    f"$linear_model: {len(spec[0])} weights for "
                    f"{len(call.args) - 1} feature arguments"
                )
        elif call.name == GBDT_MODEL_CALL:
            spec = call.args[0].value if isinstance(call.args[0], Constant) else None
            if spec is not None:
                need = model_feature_count(GBDT_MODEL_CALL, spec)
                if need > len(call.args) - 1:
                    yield (
                        f"$gbdt_model: model references feature index "
                        f"{need - 1}, only {len(call.args) - 1} feature "
                        "arguments bound"
                    )


# --------------------------------------------------------------------------- #
# similarity lowering: IR Call -> XLA closure (ops/compiler.py dispatches
# the vector family here)
# --------------------------------------------------------------------------- #


def compile_vector_call(compiler, expr: Call):
    """Lower a vector-family Call. Constant query vectors take the matvec
    form ``data @ q`` — one MXU matmul per page; vector/vector rows (the
    embedding-join shape) lower to a lane-axis einsum. NULL semantics are
    the engine's standard: output valid = AND of input row validities."""
    name = expr.name
    for i, a in enumerate(expr.args):
        if not is_vector(a.type):
            raise CompileError(
                f"{name} argument {i + 1} must be a vector, got "
                f"{a.type.display() if a.type else '?'}"
            )
    if name == "vector_norm":
        inner, _ = compiler.compile(expr.args[0])

        def norm_fn(env) -> CVal:
            v = inner(env)
            data = v.data.astype(jnp.float64)
            return CVal(jnp.sqrt(jnp.sum(data * data, axis=1)), v.valid)

        return norm_fn, None

    a_expr, b_expr = expr.args
    if a_expr.type.dimension != b_expr.type.dimension:
        raise CompileError(
            f"{name}: vector dimensions do not match "
            f"({a_expr.type.dimension} vs {b_expr.type.dimension})"
        )
    # all three binary forms are symmetric: normalize a constant operand to
    # the right so the column side drives the matvec
    if constant_vector_value(a_expr) is not None and constant_vector_value(
        b_expr
    ) is None:
        a_expr, b_expr = b_expr, a_expr
    q = constant_vector_value(b_expr)
    fn_a, _ = compiler.compile(a_expr)

    if q is not None:
        q_np = np.asarray(q, dtype=np.float64)

        def matvec_fn(env) -> CVal:
            v = fn_a(env)
            data = v.data.astype(jnp.float64)
            qd = jnp.asarray(q_np)
            if name == "dot_product":
                out = data @ qd  # (rows, n) @ (n,) — the MXU form
            elif name == "cosine_similarity":
                dot = data @ qd
                na = jnp.sqrt(jnp.sum(data * data, axis=1))
                nq = jnp.sqrt(jnp.sum(qd * qd))
                out = dot / (na * nq)
            else:  # l2_distance — direct form; the expanded
                # ||a||^2 - 2ab + ||b||^2 cancels catastrophically
                diff = data - qd[None, :]
                out = jnp.sqrt(jnp.sum(diff * diff, axis=1))
            return CVal(out, v.valid)

        return matvec_fn, None

    fn_b, _ = compiler.compile(b_expr)

    def rowwise_fn(env) -> CVal:
        va, vb = fn_a(env), fn_b(env)
        a = va.data.astype(jnp.float64)
        b = vb.data.astype(jnp.float64)
        if name == "dot_product":
            out = jnp.einsum("rn,rn->r", a, b)
        elif name == "cosine_similarity":
            dot = jnp.einsum("rn,rn->r", a, b)
            na = jnp.sqrt(jnp.sum(a * a, axis=1))
            nb = jnp.sqrt(jnp.sum(b * b, axis=1))
            out = dot / (na * nb)
        else:
            diff = a - b
            out = jnp.sqrt(jnp.sum(diff * diff, axis=1))
        return CVal(out, va.valid & vb.valid)

    return rowwise_fn, None


# --------------------------------------------------------------------------- #
# model scoring: spec validation + IR lowering
# --------------------------------------------------------------------------- #


def linear_model_spec(weights, bias) -> tuple:
    """Validated hashable spec for ``$linear_model``: (weights, bias)."""
    w = tuple(float(x) for x in weights)
    if not w:
        raise ValueError("linear model needs at least one weight")
    return (w, float(bias))


def gbdt_model_spec(model: dict) -> tuple:
    """Validated hashable spec for ``$gbdt_model``.

    Input shape (the table function parses it from JSON):
    ``{"bias": float, "trees": [{"feature": [...], "threshold": [...],
    "leaf": [...]}, ...]}`` — each tree a FULL binary tree of depth d:
    2**d - 1 internal (feature, threshold) pairs in heap order, 2**d leaf
    values. Trees of differing depth PAD to the ensemble max: every leaf of
    a shallow tree copies its value onto all its padded descendants, so the
    fixed-length vectorized traversal reads the right value no matter how
    the dummy levels route. One uniform depth = one chain of ``depth``
    gather steps over all rows AND all trees at once.
    """
    trees = model.get("trees")
    if not trees:
        raise ValueError("gbdt model has no trees")
    parsed = []
    for i, t in enumerate(trees):
        feat = tuple(int(x) for x in t.get("feature", ()))
        thr = tuple(float(x) for x in t.get("threshold", ()))
        leaf = tuple(float(x) for x in t.get("leaf", ()))
        d = max(len(leaf), 1).bit_length() - 1
        if (1 << d) != len(leaf) or len(feat) != len(leaf) - 1 or len(
            thr
        ) != len(feat) or d < 1:
            raise ValueError(
                f"gbdt tree {i}: need 2**d leaves and 2**d - 1 "
                f"feature/threshold pairs (got {len(leaf)} leaves, "
                f"{len(feat)} features, {len(thr)} thresholds)"
            )
        if min(feat) < 0:
            raise ValueError(f"gbdt tree {i}: negative feature index")
        parsed.append((d, feat, thr, leaf))
    depth = max(d for d, _, _, _ in parsed)
    norm = []
    for d, feat, thr, leaf in parsed:
        if d < depth:
            pad_internal = (1 << depth) - 1 - len(feat)
            feat = feat + (0,) * pad_internal
            thr = thr + (0.0,) * pad_internal
            span = 1 << (depth - d)
            leaf = tuple(v for v in leaf for _ in range(span))
        norm.append((feat, thr, leaf))
    return (float(model.get("bias", 0.0)), tuple(norm))


def model_feature_count(name: str, spec: tuple) -> int:
    if name == LINEAR_MODEL_CALL:
        return len(spec[0])
    return max(f for tree in spec[1] for f in tree[0]) + 1


def compile_model_call(compiler, expr: Call):
    """Lower a ``$linear_model`` / ``$gbdt_model`` Call: features stack into
    one ``(rows, k)`` matrix; linear scoring is a single matvec (MXU), GBDT
    traversal is ``depth`` gather steps vectorized over rows x trees. A row
    with any NULL feature scores NULL (SQL strictness)."""
    spec_arg = expr.args[0]
    if not isinstance(spec_arg, Constant) or spec_arg.value is None:
        raise CompileError(f"{expr.name}: model spec must be a constant")
    spec = spec_arg.value
    feat_fns = [compiler.compile(a)[0] for a in expr.args[1:]]
    k = len(feat_fns)
    if k < model_feature_count(expr.name, spec):
        raise CompileError(
            f"{expr.name}: model references feature "
            f"{model_feature_count(expr.name, spec) - 1}, only {k} "
            "feature arguments bound"
        )

    def features(env):
        vals = [f(env) for f in feat_fns]
        X = jnp.stack([v.data.astype(jnp.float64) for v in vals], axis=1)
        valid = vals[0].valid
        for v in vals[1:]:
            valid = valid & v.valid
        return X, valid

    if expr.name == LINEAR_MODEL_CALL:
        weights, bias = spec
        if len(weights) != k:
            raise CompileError(
                f"$linear_model: {len(weights)} weights for {k} features"
            )
        w_np = np.asarray(weights, dtype=np.float64)

        def linear_fn(env) -> CVal:
            X, valid = features(env)
            out = X @ jnp.asarray(w_np) + jnp.float64(bias)
            return CVal(out, valid)

        return linear_fn, None

    bias, trees = spec
    feat_np = np.asarray([t[0] for t in trees], dtype=np.int32)  # (T, I)
    thr_np = np.asarray([t[1] for t in trees], dtype=np.float64)
    leaf_np = np.asarray([t[2] for t in trees], dtype=np.float64)  # (T, L)
    depth = leaf_np.shape[1].bit_length() - 1
    n_trees = feat_np.shape[0]
    inner = feat_np.shape[1]

    def gbdt_fn(env) -> CVal:
        X, valid = features(env)
        F = jnp.asarray(feat_np)
        TH = jnp.asarray(thr_np)
        LF = jnp.asarray(leaf_np)
        rows = X.shape[0]
        t_ix = jnp.arange(n_trees)[None, :]  # (1, T)
        idx = jnp.zeros((rows, n_trees), dtype=jnp.int32)
        for _ in range(depth):
            node_feat = F[t_ix, idx]  # (rows, T)
            fv = jnp.take_along_axis(X, node_feat, axis=1)
            go_right = (fv > TH[t_ix, idx]).astype(jnp.int32)
            idx = 2 * idx + 1 + go_right
        leaves = LF[t_ix, idx - inner]
        return CVal(jnp.float64(bias) + jnp.sum(leaves, axis=1), valid)

    return gbdt_fn, None


def gbdt_reference_score(spec: tuple, features: np.ndarray) -> np.ndarray:
    """Scalar host oracle for the GBDT lowering (tests): walk each tree with
    plain Python per row."""
    bias, trees = spec
    out = np.full(len(features), float(bias), dtype=np.float64)
    for r, row in enumerate(features):
        for feat, thr, leaf in trees:
            inner = len(feat)
            i = 0
            while i < inner:
                i = 2 * i + 1 + (1 if row[feat[i]] > thr[i] else 0)
            out[r] += leaf[i - inner]
    return out


# --------------------------------------------------------------------------- #
# ingest: array-layout -> dense vector column (INSERT / CTAS conversion)
# --------------------------------------------------------------------------- #


def column_to_vector(col: Column, vtype: VectorType) -> Column:
    """Convert an array-layout column (``data[cap, W]`` + lengths +
    elem_valid) into the dense vector layout. Host-side — this runs at
    ingest boundaries (INSERT INTO a vector column), where a host sync
    already happens. A NULL row stays NULL; a non-NULL row whose array
    length != n is a data error and raises (the dimension is declared on
    the table); a NULL *element* inside a row makes the row NULL — the
    dense layout carries no element mask (same degradation as the
    expression-level CAST, documented in ARCHITECTURE.md)."""
    n = vtype.dimension
    if isinstance(col.type, VectorType):
        if col.type.dimension != n:
            raise ValueError(
                f"cannot store vector({col.type.dimension}) into "
                f"vector({n})"
            )
        return col
    data = np.asarray(col.data)
    valid = np.asarray(col.valid)
    if data.ndim != 2:
        raise ValueError(
            f"cannot convert {col.type.display()} column to {vtype.display()}"
        )
    cap, w = data.shape
    lengths = (
        np.asarray(col.lengths)
        if col.lengths is not None
        else np.full(cap, w, dtype=np.int32)
    )
    bad = valid & (lengths != n)
    if bad.any():
        first = int(np.flatnonzero(bad)[0])
        raise ValueError(
            f"cannot store array of length {int(lengths[first])} into "
            f"{vtype.display()}"
        )
    if w < n:
        # every valid row has length n > W — only possible when all rows
        # are NULL; widen the (empty) lanes
        out = np.zeros((cap, n), dtype=np.float64)
        return Column(vtype, jnp.asarray(out), jnp.asarray(valid & False))
    ev = (
        np.asarray(col.elem_valid)
        if col.elem_valid is not None
        else np.ones((cap, w), dtype=np.bool_)
    )
    new_valid = valid & ev[:, :n].all(axis=1)
    out = np.where(
        new_valid[:, None], data[:, :n].astype(np.float64), 0.0
    )
    return Column(vtype, jnp.asarray(out), jnp.asarray(new_valid))
