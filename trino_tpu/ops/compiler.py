"""Expression compiler: IR -> XLA-traceable column programs.

Reference blueprint: io.trino.sql.gen.PageFunctionCompiler
(PageFunctionCompiler.java:103, compileProjection:170 / compileFilter:385) — Trino's
query-time JVM-bytecode generator, SURVEY.md §2.4: "this entire layer becomes
'IR -> StableHLO/XLA (jax.jit) + Pallas kernels', the single biggest architectural
substitution."

A compiled expression is a host closure ``fn(env) -> CVal`` where ``env`` maps plan
symbols to :class:`CVal` (data array, validity array) pairs; tracing it under
``jax.jit`` produces fused XLA. Compilation is cached per (expression, input layout)
exactly as PageFunctionCompiler caches generated classes per expression.

Null semantics are mask-based three-valued logic:
- arithmetic/comparisons: valid = AND of input validities
- AND/OR: Kleene logic (false dominates AND, true dominates OR)
- CASE: first WHEN whose condition is definitively true

String semantics ride the sorted-dictionary invariant (spi.page.Dictionary):
- col <op> 'literal'  ->  int32 code comparisons (searchsorted for ranges)
- LIKE / IN / functions over strings -> host-evaluated boolean/code LUTs indexed
  by dictionary code on device (InLut nodes and dictionary transforms)
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..spi.page import Dictionary
from ..spi.types import (
    BIGINT,
    BOOLEAN,
    DATE,
    DOUBLE,
    INTERVAL_DAY_TIME,
    INTERVAL_YEAR_MONTH,
    UNKNOWN,
    ArrayType,
    DecimalType,
    IntegralType,
    MapType,
    Type,
    is_floating,
    is_integral,
    is_numeric,
    is_string,
)
from ..sql.functions import HIGHER_ORDER_FUNCTIONS as _HO_FUNCS
from ..sql.functions import VECTOR_SCALAR_FUNCTIONS as _VECTOR_FUNCS
from ..sql.ir import Call, Case, CastExpr, Constant, InLut, IrExpr, Reference
from ..sql.ir import Lambda as IrLambda
from ..sql.ir import references as ir_references


import jax as _jax


@_jax.tree_util.register_pytree_node_class
@dataclass
class CVal:
    """A compiled column value: device data + validity (both full-capacity).
    A pytree, so environments of CVals flow through jit.

    Nested values mirror spi.page.Column's pad-and-mask layout: arrays carry
    ``data[cap, W]`` + ``elem_valid`` + ``lengths``; maps/rows carry child
    CVals in ``children``."""

    data: jnp.ndarray
    valid: jnp.ndarray
    dictionary: Optional[Dictionary] = None
    lengths: Optional[jnp.ndarray] = None
    elem_valid: Optional[jnp.ndarray] = None
    children: tuple = ()

    def tree_flatten(self):
        return (
            (self.data, self.valid, self.lengths, self.elem_valid, self.children),
            self.dictionary,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, valid, lengths, elem_valid, kids = children
        return cls(data, valid, aux, lengths, elem_valid, tuple(kids))


@dataclass(frozen=True)
class ColumnLayout:
    """Static per-symbol input description — part of the compilation cache key.

    ``child_dicts`` mirrors a nested column's children: each entry is either a
    Dictionary/None (scalar or array child) or a nested tuple (map/row child),
    so accessors ($field, map element_at) can resolve the static output
    dictionary string functions compile against."""

    type: Type
    dictionary: Optional[Dictionary] = None
    child_dicts: tuple = ()


class CompileError(ValueError):
    pass


Env = Dict[str, CVal]
Compiled = Callable[[Env], CVal]

_NESTED_FUNCS = frozenset(
    {
        "$array", "$row", "$map", "$field", "$subscript", "element_at",
        "cardinality", "contains", "array_position", "array_min", "array_max",
        "array_sort", "array_distinct", "$array_concat", "slice",
        "map_keys", "map_values", "array_remove", "array_except",
        "array_intersect", "arrays_overlap", "trim_array", "repeat",
        "map_concat", "sequence",
    }
)



def _repeat_cval(v: "CVal", w: int) -> "CVal":
    """Broadcast a [cap]-shaped value to the [cap*w] flattened lane grid."""

    def rep(x):
        return None if x is None else jnp.repeat(x, w, axis=0)

    return CVal(
        rep(v.data), rep(v.valid), v.dictionary, rep(v.lengths),
        rep(v.elem_valid), tuple(_repeat_cval(c, w) for c in v.children),
    )


def _merge_dicts(dicts) -> Dictionary:
    """Merge element dictionaries for string-array construction/concat; every
    contributing value must be dictionary-coded."""
    real = [d for d in dicts if d is not None]
    if len(real) != len(dicts):
        raise CompileError("string array elements must be dictionary-coded")
    if len({d.fingerprint() for d in real}) == 1:
        return real[0]
    merged = sorted(set().union(*[list(d.values) for d in real]))
    return Dictionary(np.asarray(merged, dtype=object))


def _remap_codes(data: jnp.ndarray, from_dict: Dictionary, to_dict: Dictionary):
    """Translate codes between dictionaries via a host LUT (absent -> -1, which
    never equals a valid code)."""
    if from_dict is None or from_dict is to_dict:
        return data
    if from_dict.fingerprint() == to_dict.fingerprint():
        return data
    lut = np.array([to_dict.code_of(s) for s in from_dict.values], dtype=np.int32)
    if len(lut) == 0:
        return jnp.full_like(data, -1)
    return jnp.asarray(lut)[jnp.clip(data, 0, len(lut) - 1)]


def _null_cval(type_: Type, cap: int) -> CVal:
    """An all-NULL CVal of ``type_`` (nested types get empty lanes/children)."""
    from ..spi.types import RowType, VectorType

    invalid = jnp.zeros((cap,), dtype=jnp.bool_)
    if isinstance(type_, VectorType):
        return CVal(
            jnp.zeros((cap, type_.dimension), dtype=jnp.float64), invalid
        )
    if isinstance(type_, ArrayType):
        return CVal(
            jnp.zeros((cap, 1), dtype=_dtype_of(type_.element)), invalid,
            lengths=jnp.zeros((cap,), dtype=jnp.int32),
            elem_valid=jnp.zeros((cap, 1), dtype=jnp.bool_),
        )
    if isinstance(type_, MapType):
        kids = tuple(_null_cval(kt, cap) for kt in type_.child_types())
        return CVal(
            jnp.zeros((cap,), dtype=jnp.int8), invalid,
            lengths=jnp.zeros((cap,), dtype=jnp.int32), children=kids,
        )
    if isinstance(type_, RowType):
        kids = tuple(_null_cval(kt, cap) for kt in type_.child_types())
        return CVal(jnp.zeros((cap,), dtype=jnp.int8), invalid, children=kids)
    return CVal(jnp.zeros((cap,), dtype=_dtype_of(type_)), invalid)


def _lane_equals(a: CVal, x: CVal) -> jnp.ndarray:
    """[cap, W] elementwise equality of array lanes against a scalar column,
    translating dictionary codes when the vocabularies differ. Mixed integral
    widths compare in the promoted int64 domain (never narrowing the needle)."""
    xd = x.data
    if a.dictionary is not None and x.dictionary is not None:
        xd = _remap_codes(xd, x.dictionary, a.dictionary)
    if (
        a.data.dtype != xd.dtype
        and jnp.issubdtype(a.data.dtype, jnp.integer)
        and jnp.issubdtype(xd.dtype, jnp.integer)
    ):
        eq = a.data.astype(jnp.int64) == xd.astype(jnp.int64)[:, None]
    else:
        eq = a.data == xd[:, None].astype(a.data.dtype)
    return eq & a.elem_valid & x.valid[:, None]


def _string_cast_lut(d: Dictionary, dst: Type):
    """(values LUT, ok mask) for a dictionary-string cast to ``dst``, or
    (None, None) when the target type has no string parse."""
    import datetime as _dt

    from ..spi.types import BOOLEAN as _B
    from ..spi.types import DATE as _D
    from ..spi.types import is_floating as _isf
    from ..spi.types import is_integral as _isi
    from ..spi.types import is_long_decimal

    if is_long_decimal(dst):
        return None, None  # two-limb lanes: no scalar LUT shape

    def parse(s: str):
        if dst == _D:
            return (_dt.date.fromisoformat(s.strip()) - _dt.date(1970, 1, 1)).days
        if dst.name.startswith("timestamp"):
            return _iso_timestamp_micros(s.strip())
        if dst == _B:
            u = s.strip().lower()
            if u in ("true", "t", "1"):
                return True
            if u in ("false", "f", "0"):
                return False
            raise ValueError(s)
        if isinstance(dst, DecimalType):
            from decimal import Decimal

            return int(Decimal(s.strip()).scaleb(dst.scale))
        if _isi(dst):
            return int(s.strip())
        if _isf(dst):
            return float(s.strip())
        raise KeyError(dst)

    try:
        parse("1970-01-01" if (dst == _D or dst.name.startswith("timestamp")) else "1")
    except KeyError:
        return None, None
    except Exception:  # noqa: BLE001 — probe value mismatch is fine
        pass
    n = max(len(d.values), 1)
    lut = np.zeros((n,), dtype=dst.storage_dtype)
    ok = np.zeros((n,), dtype=np.bool_)
    for i, s in enumerate(d.values):
        try:
            lut[i] = parse(str(s))
            ok[i] = True
        except Exception:  # noqa: BLE001 — malformed value -> NULL rows
            pass
    return lut, ok


def _lane_present(a: CVal) -> jnp.ndarray:
    return jnp.arange(a.data.shape[1])[None, :] < a.lengths[:, None]


def _lane_member(a: CVal, b: CVal) -> jnp.ndarray:
    """[cap, Wa] bool: a's element is present among b's elements (by VALUE —
    dictionary codes remapped when vocabularies differ); NULL elements of a
    match iff b carries a NULL element (SQL set semantics for except/
    intersect treat NULL as one value)."""
    ad, bd = a.data, b.data
    if (
        a.dictionary is not None
        and b.dictionary is not None
        and a.dictionary is not b.dictionary
    ):
        bd = _remap_codes(bd, b.dictionary, a.dictionary)
    if ad.dtype != bd.dtype:
        ad = ad.astype(jnp.int64)
        bd = bd.astype(jnp.int64)
    pb = _lane_present(b)
    eq = (
        (ad[:, :, None] == bd[:, None, :])
        & a.elem_valid[:, :, None]
        & (b.elem_valid & pb)[:, None, :]
    )
    member = jnp.any(eq, axis=2)
    b_has_null = jnp.any(pb & ~b.elem_valid, axis=1)
    return jnp.where(a.elem_valid, member, b_has_null[:, None])


def _lane_compact(a: CVal, keep: jnp.ndarray, distinct: bool, valid=None) -> CVal:
    """Stable lane compaction to the kept elements; ``distinct`` additionally
    drops later duplicates (value-keyed, NULLs collapse to one)."""
    from . import kernels as K

    if distinct:
        key = jnp.where(
            keep & a.elem_valid,
            K.order_key(a.data),
            jnp.where(keep, jnp.int64(K.INT64_MAX - 1), jnp.int64(K.INT64_MAX)),
        )
        order = jnp.argsort(key, axis=1)
        ks = jnp.take_along_axis(key, order, axis=1)
        keep_s = jnp.take_along_axis(keep, order, axis=1)
        dup_s = jnp.zeros_like(keep_s)
        dup_s = dup_s.at[:, 1:].set(keep_s[:, 1:] & (ks[:, 1:] == ks[:, :-1]))
        inv = jnp.argsort(order, axis=1)
        keep = keep & ~jnp.take_along_axis(dup_s, inv, axis=1)
    korder = jnp.argsort(~keep, axis=1, stable=True)
    data = jnp.take_along_axis(a.data, korder, axis=1)
    ev = jnp.take_along_axis(a.elem_valid, korder, axis=1) & jnp.take_along_axis(
        keep, korder, axis=1
    )
    lengths = jnp.sum(keep, axis=1).astype(jnp.int32)
    return CVal(
        data, a.valid if valid is None else valid, a.dictionary, lengths, ev
    )


def _dtype_of(t: Type) -> np.dtype:
    return t.storage_dtype


def _broadcast_const(value, type_: Type, like: Optional[jnp.ndarray], capacity: int) -> jnp.ndarray:
    from ..spi.types import is_long_decimal

    if is_long_decimal(type_):
        from . import int128 as i128

        limbs = i128.np_from_ints([int(value) if value is not None else 0])
        return jnp.broadcast_to(jnp.asarray(limbs[0]), (capacity, 2))
    dt = _dtype_of(type_)
    return jnp.full((capacity,), value if value is not None else 0, dtype=dt)


# --------------------------------------------------------------------------- #
# public API
# --------------------------------------------------------------------------- #

_CACHE: Dict[tuple, Tuple[Compiled, Optional[Dictionary]]] = {}


def compile_expression(
    expr: IrExpr, layout: Dict[str, ColumnLayout], capacity: int
) -> Tuple[Compiled, Optional[Dictionary]]:
    """Compile IR to a closure over an environment of CVals.

    Returns (fn, output_dictionary). output_dictionary is set when the result is
    a dictionary-coded string column.
    """
    key = (expr, tuple(sorted(layout.items(), key=lambda kv: kv[0])), capacity)
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    c = _Compiler(layout, capacity)
    fn, out_dict = c.compile(expr)
    _CACHE[key] = (fn, out_dict)
    return fn, out_dict


# --------------------------------------------------------------------------- #
# compiler
# --------------------------------------------------------------------------- #


class _Compiler:
    def __init__(self, layout: Dict[str, ColumnLayout], capacity: int):
        self.layout = layout
        self.capacity = capacity
        # per-run subexpression memo: _dict_of peeks at computed string
        # expressions' output dictionaries, and shared subtrees (CASE arms,
        # common operands) must not recompile per use
        self._memo: Dict[int, Tuple[Compiled, Optional[Dictionary]]] = {}

    def compile(self, expr: IrExpr) -> Tuple[Compiled, Optional[Dictionary]]:
        key = id(expr)
        hit = self._memo.get(key)
        if hit is None:
            hit = self._compile_uncached(expr)
            self._memo[key] = hit
        return hit

    def _compile_uncached(self, expr: IrExpr) -> Tuple[Compiled, Optional[Dictionary]]:
        if isinstance(expr, Reference):
            sym = expr.symbol
            lay = self.layout.get(sym)
            d = lay.dictionary if lay else None

            def ref_fn(env: Env, sym=sym, d=d) -> CVal:
                v = env[sym]
                return CVal(
                    v.data, v.valid, v.dictionary or d,
                    v.lengths, v.elem_valid, v.children,
                )

            return ref_fn, d

        if isinstance(expr, Constant):
            type_ = expr.type
            value = expr.value
            if is_string(type_) and isinstance(value, str):
                # A free-standing string constant becomes a 1-entry dictionary col.
                d = Dictionary(np.asarray([value], dtype=object))

                def sconst_fn(env: Env, d=d) -> CVal:
                    data = jnp.zeros((self.capacity,), dtype=jnp.int32)
                    valid = jnp.ones((self.capacity,), dtype=jnp.bool_)
                    return CVal(data, valid, d)

                return sconst_fn, d

            from ..spi.types import is_nested, is_vector as _is_vec

            if _is_vec(type_):
                # vector constant (the ORDER BY similarity query vector):
                # broadcast the host (n,) values to the (cap, n) lane grid —
                # the tensor lowering (ops/tensor.py) reads the HOST value
                # off the Constant for the matvec form, so this path only
                # runs when a vector constant is used as a plain column
                n = type_.dimension
                if value is None:

                    def nullvec_fn(env: Env, type_=type_) -> CVal:
                        return _null_cval(type_, self.capacity)

                    return nullvec_fn, None
                vec_np = np.asarray(value, dtype=np.float64)
                if vec_np.shape != (n,):
                    raise CompileError(
                        f"vector({n}) constant with {vec_np.size} elements"
                    )

                def vec_fn(env: Env, vec_np=vec_np, n=n) -> CVal:
                    data = jnp.broadcast_to(
                        jnp.asarray(vec_np), (self.capacity, n)
                    )
                    return CVal(
                        data, jnp.ones((self.capacity,), dtype=jnp.bool_)
                    )

                return vec_fn, None

            if is_nested(type_):
                if value is not None:
                    raise CompileError(
                        f"non-null {type_.display()} constants are not foldable"
                    )

                def nconst_fn(env: Env, type_=type_) -> CVal:
                    return _null_cval(type_, self.capacity)

                return nconst_fn, None

            def const_fn(env: Env, value=value, type_=type_) -> CVal:
                data = _broadcast_const(value, type_, None, self.capacity)
                valid = jnp.full((self.capacity,), value is not None, dtype=jnp.bool_)
                return CVal(data, valid)

            return const_fn, None

        if isinstance(expr, CastExpr):
            return self._compile_cast(expr)

        if isinstance(expr, Case):
            return self._compile_case(expr)

        if isinstance(expr, InLut):
            inner, _ = self.compile(expr.value)
            # keep host-side; convert inside the closure so cached closures
            # never capture another trace's constants (tracer-leak safe)
            lut_np = np.asarray(expr.lut, dtype=np.bool_)

            def lut_fn(env: Env) -> CVal:
                v = inner(env)
                lut = jnp.asarray(lut_np)
                codes = jnp.clip(v.data, 0, lut.shape[0] - 1)
                return CVal(lut[codes], v.valid)

            return lut_fn, None

        if isinstance(expr, Call):
            return self._compile_call(expr)

        raise CompileError(f"cannot compile {type(expr).__name__}")

    # ------------------------------------------------------------------ casts

    def _compile_cast(self, expr: CastExpr) -> Tuple[Compiled, Optional[Dictionary]]:
        inner, in_dict = self.compile(expr.value)
        src, dst = expr.value.type, expr.type
        cap = self.capacity

        if is_string(src) and is_string(dst):
            return inner, in_dict
        if src == dst:
            return inner, in_dict
        if is_string(src) and in_dict is not None and not is_string(dst):
            # varchar -> date/timestamp/numeric/boolean: one host pass over
            # the dictionary builds a value LUT; malformed values are NULL
            # for THEIR rows (the engine's error channel). ref:
            # scalar/VarcharToDateCast etc. — per-row parsing there,
            # per-dictionary-value here.
            lut_np, ok_np = _string_cast_lut(in_dict, dst)
            if lut_np is not None:

                def dictcast_fn(env: Env) -> CVal:
                    v = inner(env)
                    idx = jnp.clip(v.data, 0, lut_np.shape[0] - 1)
                    return CVal(
                        jnp.asarray(lut_np)[idx],
                        v.valid & jnp.asarray(ok_np)[idx],
                    )

                return dictcast_fn, None

        from ..spi.types import VectorType as _Vec

        if isinstance(dst, _Vec) or isinstance(src, _Vec):
            return self._compile_vector_cast(expr, inner, src, dst)

        def convert(v: CVal) -> CVal:
            from ..spi.types import is_long_decimal

            data = v.data
            if is_long_decimal(src) or is_long_decimal(dst):
                from . import int128 as i128

                if isinstance(src, DecimalType) and isinstance(dst, DecimalType):
                    x = data if is_long_decimal(src) else i128.from_int64(data)
                    diff = dst.scale - src.scale
                    if diff > 0:
                        x = i128.scale_up_pow10(x, diff)
                    elif diff < 0:
                        x = i128.div_round_pow10(x, -diff)
                    if is_long_decimal(dst):
                        return CVal(x, v.valid)
                    # long -> short: low limb (Trino raises on overflow; we
                    # mark out-of-range rows NULL — loud, never silently wrong)
                    return CVal(i128.lo(x), v.valid & i128.fits_int64(x))
                if is_long_decimal(dst):
                    if is_integral(src) or src == BOOLEAN:
                        return CVal(
                            i128.scale_up_pow10(
                                i128.from_int64(data.astype(jnp.int64)), dst.scale
                            ),
                            v.valid,
                        )
                    if is_floating(src):
                        scaled = jnp.round(data.astype(jnp.float64) * float(10**dst.scale))
                        h = jnp.floor(scaled / 2.0**64)
                        l = scaled - h * 2.0**64  # in [0, 2**64): split to
                        # 32-bit halves (a direct int64 cast saturates >= 2**63)
                        lh = jnp.floor(l / 2.0**32)
                        ll = l - lh * 2.0**32
                        lbits = (lh.astype(jnp.int64) << jnp.int64(32)) | ll.astype(
                            jnp.int64
                        )
                        return CVal(
                            i128.make(h.astype(jnp.int64), lbits), v.valid
                        )
                if is_long_decimal(src):
                    if is_floating(dst):
                        return CVal(
                            (i128.to_float64(data) / float(10**src.scale)).astype(
                                _dtype_of(dst)
                            ),
                            v.valid,
                        )
                    if is_integral(dst):
                        x = i128.div_round_pow10(data, src.scale)
                        return CVal(
                            i128.lo(x).astype(_dtype_of(dst)),
                            v.valid & i128.fits_int64(x),
                        )
                raise CompileError(
                    f"cast {src.display()} -> {dst.display()} not supported"
                )
            if isinstance(src, DecimalType) and isinstance(dst, DecimalType):
                diff = dst.scale - src.scale
                if diff > 0:
                    data = data * (10**diff)
                elif diff < 0:
                    data = _div_round(data, 10**-diff)
                return CVal(data.astype(jnp.int64), v.valid)
            if isinstance(dst, DecimalType):
                if is_integral(src):
                    return CVal(data.astype(jnp.int64) * (10**dst.scale), v.valid)
                if is_floating(src):
                    scaled = jnp.round(data * float(10**dst.scale))
                    return CVal(scaled.astype(jnp.int64), v.valid)
                if src == BOOLEAN:
                    return CVal(data.astype(jnp.int64) * (10**dst.scale), v.valid)
            if isinstance(src, DecimalType) and (is_floating(dst)):
                return CVal((data / float(10**src.scale)).astype(_dtype_of(dst)), v.valid)
            if isinstance(src, DecimalType) and is_integral(dst):
                return CVal(
                    _div_round(data, 10**src.scale).astype(_dtype_of(dst)), v.valid
                )
            if is_numeric(src) and is_numeric(dst):
                if is_floating(src) and is_integral(dst):
                    return CVal(jnp.round(data).astype(_dtype_of(dst)), v.valid)
                return CVal(data.astype(_dtype_of(dst)), v.valid)
            if src == BOOLEAN and is_numeric(dst):
                return CVal(data.astype(_dtype_of(dst)), v.valid)
            if is_numeric(src) and dst == BOOLEAN:
                return CVal(data != 0, v.valid)
            from ..spi.types import TimestampWithTimeZoneType as _Ttz
            from ..spi.types import TimeType as _Time
            from ..spi.types import TimeWithTimeZoneType as _Twtz
            from ..spi.types import TimestampType as _Ts

            if isinstance(src, _Twtz) and isinstance(dst, _Time):
                # UTC micros + offset -> local micros-of-day (wrapped)
                local = (data >> 12) + ((data & 0xFFF) - 841) * 60_000_000
                return CVal(jnp.mod(local, 86_400_000_000), v.valid)
            if isinstance(src, _Time) and isinstance(dst, _Twtz):
                # session zone = UTC (matches the TIMESTAMP cast convention)
                return CVal((data.astype(jnp.int64) << 12) | 841, v.valid)

            if isinstance(src, _Ttz) and isinstance(dst, _Ts):
                # instant -> local wall time in the value's zone
                local_millis = (data >> 12) + ((data & 0xFFF) - 841) * 60_000
                return CVal((local_millis * 1000).astype(jnp.int64), v.valid)
            if isinstance(src, _Ts) and isinstance(dst, _Ttz):
                # session zone = UTC (ref: CastFromTimestamp + session zone)
                return CVal(
                    (((data // 1000) << 12) | 841).astype(jnp.int64), v.valid
                )
            if isinstance(src, _Ttz) and dst == DATE:
                return CVal(_days_of(data, src).astype(jnp.int32), v.valid)
            if isinstance(src, (_Ts, _Ttz)) and isinstance(dst, _Time):
                return CVal(_micros_of_day(data, src).astype(jnp.int64), v.valid)
            if isinstance(src, _Time) and isinstance(dst, _Time):
                return CVal(data, v.valid)
            if src == DATE and isinstance(dst, _Ttz):
                millis = data.astype(jnp.int64) * 86_400_000
                return CVal((millis << 12) | 841, v.valid)
            if src == DATE and dst.name.startswith("timestamp"):
                return CVal(data.astype(jnp.int64) * 86_400_000_000, v.valid)
            if src.name.startswith("timestamp") and dst == DATE:
                return CVal(
                    jnp.floor_divide(data, 86_400_000_000).astype(jnp.int32), v.valid
                )
            if src == UNKNOWN:
                return _null_cval(dst, cap)
            raise CompileError(f"unsupported cast {src.display()} -> {dst.display()}")

        def cast_fn(env: Env) -> CVal:
            return convert(inner(env))

        return cast_fn, None

    def _compile_vector_cast(self, expr, inner, src, dst):
        """Casts into/out of the dense VECTOR(n) layout (tensor workload
        plane). array(numeric) -> vector(n): the static lane width is a
        compile-time check; a non-NULL row whose runtime length != n, or one
        carrying a NULL element, degrades to a NULL row (the dense layout
        has no element mask and a traced program has no per-row error
        channel — ingest boundaries raise instead, ops/tensor.py
        column_to_vector). vector(n) -> array(numeric) materializes full
        lanes with length n."""
        from ..spi.types import UnknownType as _Unk
        from ..spi.types import VectorType as _Vec

        cap = self.capacity
        if isinstance(src, _Unk):

            def nullsrc_fn(env: Env) -> CVal:
                return _null_cval(dst, cap)

            return nullsrc_fn, None
        if isinstance(src, _Vec) and isinstance(dst, _Vec):
            raise CompileError(
                f"cannot cast {src.display()} to {dst.display()} "
                "(vector dimensions are fixed)"
            )
        if isinstance(dst, _Vec):
            if not (isinstance(src, ArrayType) and is_numeric(src.element)):
                raise CompileError(
                    f"cannot cast {src.display()} to {dst.display()}"
                )
            n = dst.dimension

            def arr2vec_fn(env: Env) -> CVal:
                v = inner(env)
                data = v.data.astype(jnp.float64)
                w = data.shape[1]
                lengths = (
                    v.lengths
                    if v.lengths is not None
                    else jnp.full((data.shape[0],), w, dtype=jnp.int32)
                )
                ok = v.valid & (lengths == n)
                if w < n:
                    # no row can hold n elements in W < n lanes
                    return CVal(
                        jnp.zeros((data.shape[0], n), dtype=jnp.float64),
                        ok & False,
                    )
                if v.elem_valid is not None:
                    ok = ok & jnp.all(v.elem_valid[:, :n], axis=1)
                out = jnp.where(ok[:, None], data[:, :n], 0.0)
                return CVal(out, ok)

            return arr2vec_fn, None
        # vector -> array(numeric)
        if not (isinstance(dst, ArrayType) and is_numeric(dst.element)):
            raise CompileError(
                f"cannot cast {src.display()} to {dst.display()}"
            )
        n = src.dimension
        el_dt = _dtype_of(dst.element)

        def vec2arr_fn(env: Env) -> CVal:
            v = inner(env)
            data = v.data.astype(el_dt)
            lengths = jnp.where(v.valid, n, 0).astype(jnp.int32)
            ev = jnp.broadcast_to(v.valid[:, None], data.shape)
            return CVal(data, v.valid, None, lengths, ev)

        return vec2arr_fn, None

    # ------------------------------------------------------------------ case

    def _compile_case(self, expr: Case) -> Tuple[Compiled, Optional[Dictionary]]:
        from ..spi.types import is_nested

        if is_nested(expr.type):
            raise CompileError("CASE over array/map/row values not supported yet")
        compiled_whens = [
            (self.compile(c)[0],) + self.compile(r) for c, r in expr.whens
        ]
        default_fn, default_dict = (
            self.compile(expr.default) if expr.default is not None else (None, None)
        )
        dt = _dtype_of(expr.type)

        # string CASE: merge branch dictionaries and remap each branch's codes
        # onto the merged vocabulary (same scheme as $array construction)
        out_dict = None
        if is_string(expr.type):
            branch_dicts = [d for *_rest, d in compiled_whens]
            if default_fn is not None:
                branch_dicts.append(default_dict)
            real = [d for d in branch_dicts if d is not None]
            if real:
                out_dict = _merge_dicts(real)

        def remap(r: CVal, d: Optional[Dictionary]):
            if out_dict is None or d is None:
                return r.data
            return _remap_codes(r.data, d, out_dict)

        from ..spi.types import is_long_decimal

        lanes = is_long_decimal(expr.type)

        def case_fn(env: Env) -> CVal:
            if default_fn is not None:
                acc = default_fn(env)
                acc_data = remap(acc, default_dict).astype(dt)
                acc_valid = acc.valid
            else:
                shape = (self.capacity, 2) if lanes else (self.capacity,)
                acc_data = jnp.zeros(shape, dtype=dt)
                acc_valid = jnp.zeros((self.capacity,), dtype=jnp.bool_)
            # evaluate in reverse: earlier WHENs override later ones
            for cond_fn, res_fn, res_dict in reversed(compiled_whens):
                c = cond_fn(env)
                r = res_fn(env)
                fire = c.valid & c.data.astype(jnp.bool_)
                fire_d = fire[:, None] if lanes else fire
                acc_data = jnp.where(fire_d, remap(r, res_dict).astype(dt), acc_data)
                acc_valid = jnp.where(fire, r.valid, acc_valid)
            return CVal(acc_data, acc_valid, out_dict)

        return case_fn, out_dict

    # ----------------------------------------------------------- nested types

    def _dict_tree(self, expr: IrExpr):
        """Compile-time dictionary info for a (possibly nested) expression:
        a Dictionary/None for scalars and arrays, a tuple of subtrees for
        maps (keys, values) and rows (fields)."""
        if isinstance(expr, Reference):
            lay = self.layout.get(expr.symbol)
            if lay is None:
                return None
            if isinstance(expr.type, (MapType,)) or expr.type.name == "row":
                return lay.child_dicts
            return lay.dictionary
        if isinstance(expr, Call):
            if expr.name == "$row":
                return tuple(self._dict_tree(a) for a in expr.args)
            if expr.name == "$map":
                return (self._dict_tree(expr.args[0]), self._dict_tree(expr.args[1]))
            if expr.name == "$field":
                sub = self._dict_tree(expr.args[0])
                idx = int(expr.args[1].value)
                return sub[idx] if isinstance(sub, tuple) and idx < len(sub) else None
        try:
            return self.compile(expr)[1]
        except CompileError:
            return None

    def _compile_nested(self, expr: Call) -> Tuple[Compiled, Optional[Dictionary]]:
        """ARRAY/MAP/ROW constructors and accessors over the pad-and-mask
        layout (ref: operator/scalar/ArraySubscriptOperator.java, MapSubscript,
        ArrayFunctions — vectorized here as [cap, W] lane ops; no per-row
        loops, everything traces into one fused XLA program)."""
        name = expr.name
        cap = self.capacity
        arg_fns = [self.compile(a)[0] for a in expr.args]
        arg_types = [a.type for a in expr.args]
        out_t = expr.type

        if name == "$array":
            el_t = out_t.element
            merged = None
            if is_string(el_t):
                # NULL elements contribute no vocabulary; every non-null
                # element must be dictionary-coded
                null_arg = [
                    isinstance(a, Constant) and a.value is None for a in expr.args
                ]
                el_dicts = [
                    self.compile(a)[1]
                    for a, isnull in zip(expr.args, null_arg)
                    if not isnull
                ]
                merged = _merge_dicts(el_dicts) if el_dicts else None

            def array_fn(env: Env) -> CVal:
                vals = [f(env) for f in arg_fns]
                datas, valids = [], []
                for v in vals:
                    d = v.data
                    if merged is not None and v.dictionary is not None:
                        d = _remap_codes(d, v.dictionary, merged)
                    datas.append(d)
                    valids.append(v.valid)
                if not vals:
                    data = jnp.zeros((cap, 1), dtype=_dtype_of(el_t))
                    ev = jnp.zeros((cap, 1), dtype=jnp.bool_)
                else:
                    data = jnp.stack(datas, axis=1)
                    ev = jnp.stack(valids, axis=1)
                lengths = jnp.full((cap,), len(vals), dtype=jnp.int32)
                valid = jnp.ones((cap,), dtype=jnp.bool_)
                return CVal(data, valid, merged, lengths, ev)

            return array_fn, merged

        if name == "$row":

            def row_fn(env: Env) -> CVal:
                kids = tuple(f(env) for f in arg_fns)
                return CVal(
                    jnp.zeros((cap,), dtype=jnp.int8),
                    jnp.ones((cap,), dtype=jnp.bool_),
                    children=kids,
                )

            return row_fn, None

        if name == "$map":

            def map_fn(env: Env) -> CVal:
                k, v = arg_fns[0](env), arg_fns[1](env)
                same_len = k.lengths == v.lengths
                valid = k.valid & v.valid & same_len
                return CVal(
                    jnp.zeros((cap,), dtype=jnp.int8), valid,
                    lengths=k.lengths, children=(k, v),
                )

            return map_fn, None

        if name == "$field":
            idx = expr.args[1].value

            def field_fn(env: Env, idx=int(idx)) -> CVal:
                r = arg_fns[0](env)
                c = r.children[idx]
                return CVal(
                    c.data, c.valid & r.valid, c.dictionary,
                    c.lengths, c.elem_valid, c.children,
                )

            d = self._dict_tree(expr)
            return field_fn, d if isinstance(d, Dictionary) else None

        if name in ("$subscript", "element_at") and isinstance(arg_types[0], ArrayType):
            el_t = arg_types[0].element

            def sub_fn(env: Env) -> CVal:
                a, i = arg_fns[0](env), arg_fns[1](env)
                w = a.data.shape[1]
                pos = i.data.astype(jnp.int64) - 1  # SQL arrays are 1-based
                safe = jnp.clip(pos, 0, w - 1)[:, None]
                data = jnp.take_along_axis(a.data, safe, axis=1)[:, 0]
                ev = jnp.take_along_axis(a.elem_valid, safe, axis=1)[:, 0]
                in_range = (pos >= 0) & (pos < a.lengths.astype(jnp.int64))
                valid = a.valid & i.valid & in_range & ev
                return CVal(data, valid, a.dictionary)

            d = self.compile(expr.args[0])[1]
            return sub_fn, d if is_string(el_t) else None

        if name in ("$subscript", "element_at") and isinstance(arg_types[0], MapType):

            def mapsub_fn(env: Env) -> CVal:
                m, k = arg_fns[0](env), arg_fns[1](env)
                keys, vals = m.children
                eq = _lane_equals(keys, k)
                found = jnp.any(eq, axis=1)
                pos = jnp.argmax(eq, axis=1)[:, None]
                data = jnp.take_along_axis(vals.data, pos, axis=1)[:, 0]
                ev = jnp.take_along_axis(vals.elem_valid, pos, axis=1)[:, 0]
                valid = m.valid & k.valid & found & ev
                return CVal(data, valid, vals.dictionary)

            tree = self._dict_tree(expr.args[0])
            vd = tree[1] if isinstance(tree, tuple) and len(tree) == 2 else None
            return mapsub_fn, vd if isinstance(vd, Dictionary) else None

        if name == "cardinality":

            def card_fn(env: Env) -> CVal:
                v = arg_fns[0](env)
                lengths = v.lengths if v.lengths is not None else v.children[0].lengths
                return CVal(lengths.astype(jnp.int64), v.valid)

            return card_fn, None

        if name == "contains":

            def contains_fn(env: Env) -> CVal:
                a, x = arg_fns[0](env), arg_fns[1](env)
                w = a.data.shape[1]
                present = jnp.arange(w)[None, :] < a.lengths[:, None]
                eq = _lane_equals(a, x) & present
                match = jnp.any(eq, axis=1)
                has_null = jnp.any(present & ~a.elem_valid, axis=1)
                valid = a.valid & x.valid & (match | ~has_null)
                return CVal(match, valid)

            return contains_fn, None

        if name == "array_position":

            def pos_fn(env: Env) -> CVal:
                a, x = arg_fns[0](env), arg_fns[1](env)
                w = a.data.shape[1]
                present = jnp.arange(w)[None, :] < a.lengths[:, None]
                eq = _lane_equals(a, x) & present
                found = jnp.any(eq, axis=1)
                first = jnp.argmax(eq, axis=1).astype(jnp.int64) + 1
                return CVal(jnp.where(found, first, 0), a.valid & x.valid)

            return pos_fn, None

        if name in ("array_min", "array_max"):
            el_t = arg_types[0].element

            def minmax_fn(env: Env, is_min=(name == "array_min")) -> CVal:
                a = arg_fns[0](env)
                w = a.data.shape[1]
                present = jnp.arange(w)[None, :] < a.lengths[:, None]
                mask = present & a.elem_valid
                dt = a.data.dtype
                if jnp.issubdtype(dt, jnp.floating):
                    sent = jnp.array(jnp.inf if is_min else -jnp.inf, dtype=dt)
                elif dt == jnp.bool_:
                    sent = jnp.array(is_min, dtype=dt)
                else:
                    info = jnp.iinfo(dt)
                    sent = jnp.array(info.max if is_min else info.min, dtype=dt)
                masked = jnp.where(mask, a.data, sent)
                data = jnp.min(masked, axis=1) if is_min else jnp.max(masked, axis=1)
                has_null = jnp.any(present & ~a.elem_valid, axis=1)
                valid = a.valid & (a.lengths > 0) & ~has_null
                return CVal(data, valid, a.dictionary)

            d = self.compile(expr.args[0])[1]
            return minmax_fn, d if is_string(el_t) else None

        if name in ("array_sort", "array_distinct"):

            def sort_fn(env: Env, distinct=(name == "array_distinct")) -> CVal:
                from . import kernels as K

                a = arg_fns[0](env)
                w = a.data.shape[1]
                present = jnp.arange(w)[None, :] < a.lengths[:, None]
                # sort lanes: value order, nulls last-within-present, absents last
                key = jnp.where(
                    present & a.elem_valid,
                    K.order_key(a.data),
                    jnp.where(present, jnp.int64(K.INT64_MAX - 1), jnp.int64(K.INT64_MAX)),
                )
                order = jnp.argsort(key, axis=1)
                if not distinct:
                    data = jnp.take_along_axis(a.data, order, axis=1)
                    ev = jnp.take_along_axis(a.elem_valid, order, axis=1)
                    return CVal(data, a.valid, a.dictionary, a.lengths, ev)
                # distinct keeps FIRST occurrences in ORIGINAL order (reference
                # semantics): find dups in value order, map the keep mask back
                # through the inverse permutation, then compact stably
                ks = jnp.take_along_axis(key, order, axis=1)
                pres_s = jnp.take_along_axis(present, order, axis=1)
                dup_s = jnp.zeros_like(pres_s)
                dup_s = dup_s.at[:, 1:].set(pres_s[:, 1:] & (ks[:, 1:] == ks[:, :-1]))
                inv = jnp.argsort(order, axis=1)
                keep = present & ~jnp.take_along_axis(dup_s, inv, axis=1)
                korder = jnp.argsort(~keep, axis=1)  # stable: original order kept
                data = jnp.take_along_axis(a.data, korder, axis=1)
                ev = jnp.take_along_axis(a.elem_valid, korder, axis=1) & (
                    jnp.take_along_axis(keep, korder, axis=1)
                )
                lengths = jnp.sum(keep, axis=1).astype(jnp.int32)
                return CVal(data, a.valid, a.dictionary, lengths, ev)

            d = self.compile(expr.args[0])[1]
            return sort_fn, d

        if name == "$array_concat":
            el_t = out_t.element
            d0 = self.compile(expr.args[0])[1]
            d1 = self.compile(expr.args[1])[1]
            merged = _merge_dicts([d0, d1]) if is_string(el_t) else None

            def concat_fn(env: Env) -> CVal:
                a, b = arg_fns[0](env), arg_fns[1](env)
                wa, wb = a.data.shape[1], b.data.shape[1]
                w = wa + wb
                da, db = a.data, b.data
                if merged is not None:
                    da = _remap_codes(da, a.dictionary, merged)
                    db = _remap_codes(db, b.dictionary, merged)
                j = jnp.arange(w)[None, :]
                la = a.lengths[:, None].astype(jnp.int64)
                from_a = j < la
                ia = jnp.clip(j, 0, wa - 1)
                ib = jnp.clip(j - la, 0, wb - 1)
                ia = jnp.broadcast_to(ia, (cap, w))
                ib = jnp.broadcast_to(ib, (cap, w))
                data = jnp.where(
                    from_a,
                    jnp.take_along_axis(da, ia, axis=1),
                    jnp.take_along_axis(db, ib, axis=1),
                )
                ev = jnp.where(
                    from_a,
                    jnp.take_along_axis(a.elem_valid, ia, axis=1),
                    jnp.take_along_axis(b.elem_valid, ib, axis=1),
                )
                lengths = a.lengths + b.lengths
                present = j < lengths[:, None]
                return CVal(data, a.valid & b.valid, merged, lengths, ev & present)

            return concat_fn, merged

        if name == "slice":

            def slice_fn(env: Env) -> CVal:
                a, s, ln = arg_fns[0](env), arg_fns[1](env), arg_fns[2](env)
                w = a.data.shape[1]
                start = s.data.astype(jnp.int64)
                length = jnp.maximum(ln.data.astype(jnp.int64), 0)
                lens = a.lengths.astype(jnp.int64)
                eff = jnp.where(start > 0, start - 1, lens + start)
                j = jnp.arange(w)[None, :]
                src = eff[:, None] + j
                take = (j < length[:, None]) & (src >= 0) & (src < lens[:, None])
                safe = jnp.clip(src, 0, w - 1)
                data = jnp.take_along_axis(a.data, safe, axis=1)
                ev = jnp.take_along_axis(a.elem_valid, safe, axis=1) & take
                new_len = jnp.sum(take, axis=1).astype(jnp.int32)
                valid = a.valid & s.valid & ln.valid & (start != 0)
                return CVal(data, valid, a.dictionary, new_len, ev)

            d = self.compile(expr.args[0])[1]
            return slice_fn, d

        if name in ("map_keys", "map_values"):
            idx = 0 if name == "map_keys" else 1

            def extract_fn(env: Env, idx=idx) -> CVal:
                m = arg_fns[0](env)
                c = m.children[idx]
                return CVal(
                    c.data, m.valid, c.dictionary, c.lengths, c.elem_valid
                )

            tree = self._dict_tree(expr.args[0])
            cd = tree[idx] if isinstance(tree, tuple) and len(tree) == 2 else None
            return extract_fn, cd if isinstance(cd, Dictionary) else None

        if name == "array_remove":

            def remove_fn(env: Env) -> CVal:
                a, x = arg_fns[0](env), arg_fns[1](env)
                keep = _lane_present(a) & ~_lane_equals(a, x)
                return _lane_compact(
                    a, keep, distinct=False, valid=a.valid & x.valid
                )

            return remove_fn, self.compile(expr.args[0])[1]

        if name in ("array_except", "array_intersect"):

            def setop_fn(env: Env, except_=(name == "array_except")) -> CVal:
                a, b = arg_fns[0](env), arg_fns[1](env)
                member = _lane_member(a, b)
                keep = _lane_present(a) & (~member if except_ else member)
                return _lane_compact(
                    a, keep, distinct=True, valid=a.valid & b.valid
                )

            return setop_fn, self.compile(expr.args[0])[1]

        if name == "arrays_overlap":

            def overlap_fn(env: Env) -> CVal:
                a, b = arg_fns[0](env), arg_fns[1](env)
                pa, pb = _lane_present(a), _lane_present(b)
                member = _lane_member(a, b)
                real = jnp.any(pa & a.elem_valid & member, axis=1)
                a_null = jnp.any(pa & ~a.elem_valid, axis=1)
                b_null = jnp.any(pb & ~b.elem_valid, axis=1)
                # a real match decides TRUE; otherwise a NULL element on
                # either side makes the answer unknown (reference semantics)
                valid = a.valid & b.valid & (real | ~(a_null | b_null))
                return CVal(real, valid)

            return overlap_fn, None

        if name == "trim_array":

            def trim_fn(env: Env) -> CVal:
                a, n = arg_fns[0](env), arg_fns[1](env)
                cut = jnp.clip(n.data.astype(jnp.int64), 0, None)
                new_len = jnp.maximum(
                    a.lengths.astype(jnp.int64) - cut, 0
                ).astype(jnp.int32)
                pres = jnp.arange(a.data.shape[1])[None, :] < new_len[:, None]
                # deviation: the reference raises when n exceeds cardinality;
                # we clamp to empty (NULL-free error channel)
                return CVal(
                    a.data, a.valid & n.valid, a.dictionary,
                    new_len, a.elem_valid & pres,
                )

            return trim_fn, self.compile(expr.args[0])[1]

        if name == "sequence":
            if not all(isinstance(a, Constant) for a in expr.args):
                raise CompileError(
                    "sequence: bounds must be literals (static lane width)"
                )
            start = int(expr.args[0].value)
            stop = int(expr.args[1].value)
            step = int(expr.args[2].value) if len(expr.args) > 2 else (
                1 if stop >= start else -1
            )
            if step == 0:
                raise CompileError("sequence: step must not be zero")
            seq = list(range(start, stop + (1 if step > 0 else -1), step))
            wseq = max(len(seq), 1)
            seq_np = np.array(seq or [0], dtype=np.int64)

            def seq_fn(env: Env) -> CVal:
                data = jnp.broadcast_to(jnp.asarray(seq_np)[None, :], (cap, wseq))
                ev = jnp.full((cap, wseq), bool(seq), dtype=jnp.bool_)
                lengths = jnp.full((cap,), len(seq), dtype=jnp.int32)
                return CVal(
                    data, jnp.ones((cap,), dtype=jnp.bool_), None, lengths, ev
                )

            return seq_fn, None

        if name == "repeat":
            cnt = expr.args[1]
            if not isinstance(cnt, Constant):
                raise CompileError(
                    "repeat: count must be a literal (static lane width)"
                )
            if cnt.value is None:  # NULL count null-propagates
                return (lambda env: _null_cval(out_t, cap)), None
            wn = max(int(cnt.value), 0)

            def repeat_fn(env: Env) -> CVal:
                x = arg_fns[0](env)
                w = max(wn, 1)
                data = jnp.broadcast_to(x.data[:, None], (cap, w))
                ev = jnp.broadcast_to(x.valid[:, None], (cap, w))
                lengths = jnp.full((cap,), wn, dtype=jnp.int32)
                return CVal(
                    data, jnp.ones((cap,), dtype=jnp.bool_), x.dictionary,
                    lengths, ev,
                )

            return repeat_fn, self.compile(expr.args[0])[1]

        if name == "map_concat":
            ktrees = [self._dict_tree(a) for a in expr.args]
            kdicts = [t[0] if isinstance(t, tuple) and len(t) == 2 else None for t in ktrees]
            vdicts = [t[1] if isinstance(t, tuple) and len(t) == 2 else None for t in ktrees]
            mk = _merge_dicts([d for d in kdicts if d is not None]) if any(kdicts) else None
            mv = _merge_dicts([d for d in vdicts if d is not None]) if any(vdicts) else None

            def mapcat_fn(env: Env) -> CVal:
                ms = [f(env) for f in arg_fns]
                kds, vds, evs_k, evs_v, press = [], [], [], [], []
                for m, kd_, vd_ in zip(ms, kdicts, vdicts):
                    k, v = m.children
                    kdat, vdat = k.data, v.data
                    if mk is not None:
                        kdat = _remap_codes(kdat, kd_, mk)
                    if mv is not None:
                        vdat = _remap_codes(vdat, vd_, mv)
                    kds.append(kdat)
                    vds.append(vdat)
                    evs_k.append(k.elem_valid)
                    evs_v.append(v.elem_valid)
                    press.append(
                        jnp.arange(kdat.shape[1])[None, :] < m.lengths[:, None]
                    )
                kd = jnp.concatenate(kds, axis=1)
                vd = jnp.concatenate(vds, axis=1)
                kev = jnp.concatenate(evs_k, axis=1)
                vev = jnp.concatenate(evs_v, axis=1)
                pres = jnp.concatenate(press, axis=1)
                from . import kernels as K

                W = kd.shape[1]
                pos = jnp.broadcast_to(jnp.arange(W)[None, :], (cap, W))
                key = jnp.where(
                    pres & kev, K.order_key(kd), jnp.int64(K.INT64_MAX)
                )
                # keep the LAST occurrence of each key (later maps win):
                # sort by (key asc, pos desc), keep first of each run
                order = jnp.lexsort((-pos, key), axis=1)
                ks = jnp.take_along_axis(key, order, axis=1)
                pres_s = jnp.take_along_axis(pres, order, axis=1)
                dup_s = jnp.zeros_like(pres_s)
                dup_s = dup_s.at[:, 1:].set(
                    pres_s[:, 1:] & (ks[:, 1:] == ks[:, :-1])
                )
                inv = jnp.argsort(order, axis=1)
                keep = pres & kev & ~jnp.take_along_axis(dup_s, inv, axis=1)
                korder = jnp.argsort(~keep, axis=1)
                lengths = jnp.sum(keep, axis=1).astype(jnp.int32)
                keep_s2 = jnp.take_along_axis(keep, korder, axis=1)
                kc = CVal(
                    jnp.take_along_axis(kd, korder, axis=1),
                    jnp.ones((cap,), dtype=jnp.bool_), mk, lengths,
                    jnp.take_along_axis(kev, korder, axis=1) & keep_s2,
                )
                vc = CVal(
                    jnp.take_along_axis(vd, korder, axis=1),
                    jnp.ones((cap,), dtype=jnp.bool_), mv, lengths,
                    jnp.take_along_axis(vev, korder, axis=1) & keep_s2,
                )
                valid = ms[0].valid
                for m in ms[1:]:
                    valid = valid & m.valid
                return CVal(
                    jnp.zeros((cap,), dtype=jnp.int8), valid,
                    lengths=lengths, children=(kc, vc),
                )

            return mapcat_fn, (mk, mv)

        raise CompileError(f"nested function {name} not implemented")

    # ---------------------------------------------------------- higher-order

    def _lambda_layout(self, lam: IrLambda, param_dicts) -> Dict[str, ColumnLayout]:
        lay = dict(self.layout)
        for p, pt, pd in zip(lam.params, lam.param_types, param_dicts):
            lay[p] = ColumnLayout(pt, pd)
        return lay

    def _lambda_free_env(self, lam: IrLambda, env: Env, w: int) -> Env:
        """Outer symbols free in the body, repeated onto the lane grid."""
        free = ir_references(lam.body) - set(lam.params)
        return {s: _repeat_cval(env[s], w) for s in free if s in env}

    def _compile_higher_order(self, expr: Call) -> Tuple[Compiled, Optional[Dictionary]]:
        """Lambda-taking array/map functions: the body compiles to its own
        vectorized program over the flattened [cap*W] lane grid (W is a static
        lane width at trace time, so each distinct W compiles once and caches).
        ref: operator/scalar/ArrayTransformFunction.java and friends — there
        the lambda becomes a MethodHandle looped per element; here it becomes
        one fused elementwise program over all rows' lanes at once."""
        from ..spi.types import is_nested

        name = expr.name
        cap = self.capacity
        # scalar lanes only: element CVals flattened onto the lane grid carry
        # no children, and nested lambda results would need [cap, W, ...]
        # layouts — reject cleanly instead of dying inside the trace
        for a in expr.args:
            if isinstance(a, IrLambda):
                if is_nested(a.type) or any(is_nested(p) for p in a.param_types):
                    raise CompileError(
                        f"{name} over nested (array/map/row) elements or with "
                        "a nested-returning lambda is not supported yet"
                    )

        if name in ("transform", "filter", "any_match", "all_match", "none_match"):
            arr_fn, arr_dict = self.compile(expr.args[0])
            lam: IrLambda = expr.args[1]
            lay = self._lambda_layout(lam, (arr_dict,))
            body_dict = compile_expression(lam.body, lay, 1)[1]

            def run_body(env: Env):
                a = arr_fn(env)
                w = a.data.shape[1]
                fenv = self._lambda_free_env(lam, env, w)
                fenv[lam.params[0]] = CVal(
                    a.data.reshape(cap * w), a.elem_valid.reshape(cap * w),
                    a.dictionary,
                )
                bfn, _ = compile_expression(lam.body, lay, cap * w)
                r = bfn(fenv)
                present = jnp.arange(w)[None, :] < a.lengths[:, None]
                return a, w, r, present

            if name == "transform":

                def transform_fn(env: Env) -> CVal:
                    a, w, r, present = run_body(env)
                    return CVal(
                        r.data.reshape(cap, w), a.valid, body_dict, a.lengths,
                        r.valid.reshape(cap, w) & present,
                    )

                return transform_fn, body_dict

            if name == "filter":

                def filter_fn(env: Env) -> CVal:
                    a, w, r, present = run_body(env)
                    keep = (
                        r.data.astype(jnp.bool_) & r.valid
                    ).reshape(cap, w) & present
                    order = jnp.argsort(~keep, axis=1, stable=True)
                    data2 = jnp.take_along_axis(a.data, order, axis=1)
                    ev2 = jnp.take_along_axis(a.elem_valid, order, axis=1)
                    new_len = jnp.sum(keep, axis=1).astype(jnp.int32)
                    pres2 = jnp.arange(w)[None, :] < new_len[:, None]
                    return CVal(data2, a.valid, a.dictionary, new_len, ev2 & pres2)

                return filter_fn, arr_dict

            def match_fn(env: Env, mode=name) -> CVal:
                # 3VL: any_match is TRUE if any true, FALSE if all false,
                # NULL if no true but some null (ref ArrayAnyMatchFunction)
                a, w, r, present = run_body(env)
                bd = r.data.astype(jnp.bool_).reshape(cap, w)
                bv = r.valid.reshape(cap, w)
                any_true = jnp.any(bd & bv & present, axis=1)
                any_false = jnp.any(~bd & bv & present, axis=1)
                any_null = jnp.any(~bv & present, axis=1)
                if mode == "any_match":
                    data, det = any_true, any_true | ~any_null
                elif mode == "all_match":
                    data, det = ~any_false, any_false | ~any_null
                else:  # none_match
                    data, det = ~any_true, any_true | ~any_null
                return CVal(data, a.valid & det)

            return match_fn, None

        if name == "zip_with":
            a_fn, a_dict = self.compile(expr.args[0])
            b_fn, b_dict = self.compile(expr.args[1])
            lam = expr.args[2]
            lay = self._lambda_layout(lam, (a_dict, b_dict))
            body_dict = compile_expression(lam.body, lay, 1)[1]

            def zip_fn(env: Env) -> CVal:
                a, b = a_fn(env), b_fn(env)
                wa, wb = a.data.shape[1], b.data.shape[1]
                w = max(wa, wb)

                def pad(x, width):
                    return x if x.shape[1] == width else jnp.pad(
                        x, ((0, 0), (0, width - x.shape[1]))
                    )

                lane = jnp.arange(w)[None, :]
                lengths = jnp.maximum(a.lengths, b.lengths)
                # the shorter array extends with NULLs (ZipWithFunction)
                ea = pad(a.elem_valid, w) & (lane < a.lengths[:, None])
                eb = pad(b.elem_valid, w) & (lane < b.lengths[:, None])
                fenv = self._lambda_free_env(lam, env, w)
                fenv[lam.params[0]] = CVal(
                    pad(a.data, w).reshape(cap * w), ea.reshape(cap * w), a.dictionary
                )
                fenv[lam.params[1]] = CVal(
                    pad(b.data, w).reshape(cap * w), eb.reshape(cap * w), b.dictionary
                )
                bfn, _ = compile_expression(lam.body, lay, cap * w)
                r = bfn(fenv)
                present = lane < lengths[:, None]
                return CVal(
                    r.data.reshape(cap, w), a.valid & b.valid, body_dict,
                    lengths, r.valid.reshape(cap, w) & present,
                )

            return zip_fn, body_dict

        if name == "reduce":
            arr_fn, arr_dict = self.compile(expr.args[0])
            init_fn, init_dict = self.compile(expr.args[1])
            lam_in: IrLambda = expr.args[2]
            lam_out: IrLambda = expr.args[3]
            state_t = lam_in.param_types[0]
            if is_string(state_t):
                raise CompileError("reduce with a string-typed state is not supported")
            lay_in = self._lambda_layout(lam_in, (None, arr_dict))
            lay_out = self._lambda_layout(lam_out, (None,))
            out_dict = compile_expression(lam_out.body, lay_out, 1)[1]

            def reduce_fn(env: Env) -> CVal:
                a = arr_fn(env)
                w = a.data.shape[1]
                s = init_fn(env)
                bfn, _ = compile_expression(lam_in.body, lay_in, cap)
                free_in = ir_references(lam_in.body) - set(lam_in.params)
                base_env = {k: env[k] for k in free_in if k in env}
                for i in range(w):
                    xi = CVal(a.data[:, i], a.elem_valid[:, i], a.dictionary)
                    env2 = dict(base_env)
                    env2[lam_in.params[0]] = s
                    env2[lam_in.params[1]] = xi
                    s2 = bfn(env2)
                    pres = (i < a.lengths) & a.valid
                    s = CVal(
                        jnp.where(pres, s2.data, s.data),
                        jnp.where(pres, s2.valid, s.valid),
                    )
                ofn, _ = compile_expression(lam_out.body, lay_out, cap)
                free_out = ir_references(lam_out.body) - set(lam_out.params)
                env3 = {k: env[k] for k in free_out if k in env}
                env3[lam_out.params[0]] = s
                r = ofn(env3)
                return CVal(r.data, r.valid & a.valid, out_dict)

            return reduce_fn, out_dict

        if name in ("transform_values", "map_filter"):
            m_fn, _ = self.compile(expr.args[0])
            lam = expr.args[1]
            tree = self._dict_tree(expr.args[0])
            kd, vd = tree if isinstance(tree, tuple) and len(tree) == 2 else (None, None)
            kd = kd if isinstance(kd, Dictionary) else None
            vd = vd if isinstance(vd, Dictionary) else None
            lay = self._lambda_layout(lam, (kd, vd))
            body_dict = compile_expression(lam.body, lay, 1)[1]

            def run_map_body(env: Env):
                m = m_fn(env)
                k, v = m.children
                w = k.data.shape[1]
                present = jnp.arange(w)[None, :] < m.lengths[:, None]
                fenv = self._lambda_free_env(lam, env, w)
                fenv[lam.params[0]] = CVal(
                    k.data.reshape(cap * w), k.elem_valid.reshape(cap * w),
                    k.dictionary,
                )
                fenv[lam.params[1]] = CVal(
                    v.data.reshape(cap * w), v.elem_valid.reshape(cap * w),
                    v.dictionary,
                )
                bfn, _ = compile_expression(lam.body, lay, cap * w)
                return m, k, v, w, bfn(fenv), present

            if name == "transform_values":

                def tv_fn(env: Env) -> CVal:
                    m, k, v, w, r, present = run_map_body(env)
                    nv = CVal(
                        r.data.reshape(cap, w), m.valid, body_dict,
                        k.lengths, r.valid.reshape(cap, w) & present,
                    )
                    return CVal(
                        jnp.zeros((cap,), dtype=jnp.int8), m.valid,
                        lengths=m.lengths, children=(k, nv),
                    )

                return tv_fn, None

            def mf_fn(env: Env) -> CVal:
                m, k, v, w, r, present = run_map_body(env)
                keep = (r.data.astype(jnp.bool_) & r.valid).reshape(cap, w) & present
                order = jnp.argsort(~keep, axis=1, stable=True)
                new_len = jnp.sum(keep, axis=1).astype(jnp.int32)
                pres2 = jnp.arange(w)[None, :] < new_len[:, None]

                def reorder(c: CVal) -> CVal:
                    return CVal(
                        jnp.take_along_axis(c.data, order, axis=1), c.valid,
                        c.dictionary, new_len,
                        jnp.take_along_axis(c.elem_valid, order, axis=1) & pres2,
                    )

                return CVal(
                    jnp.zeros((cap,), dtype=jnp.int8), m.valid,
                    lengths=new_len, children=(reorder(k), reorder(v)),
                )

            return mf_fn, None

        raise CompileError(f"higher-order function {name} not implemented")

    # ------------------------------------------------------------------ calls

    def _compile_call(self, expr: Call) -> Tuple[Compiled, Optional[Dictionary]]:
        name = expr.name
        if name in _HO_FUNCS:
            return self._compile_higher_order(expr)
        if name in _VECTOR_FUNCS or name in ("$linear_model", "$gbdt_model"):
            # tensor workload plane (ops/tensor.py): similarity family ->
            # MXU matmul forms; model calls -> stacked-feature matmul/GBDT
            from . import tensor as _tensor

            if name in _VECTOR_FUNCS:
                return _tensor.compile_vector_call(self, expr)
            return _tensor.compile_model_call(self, expr)
        if name in _NESTED_FUNCS:
            return self._compile_nested(expr)
        # string-aware operators first
        if name in ("$eq", "$ne", "$lt", "$lte", "$gt", "$gte") and any(
            is_string(a.type) for a in expr.args
        ):
            return self._compile_string_comparison(expr)
        if name == "$like":
            return self._compile_like(expr)
        if name in _STRING_FUNCS:
            return self._compile_string_function(expr)

        arg_fns = [self.compile(a)[0] for a in expr.args]
        arg_types = [a.type for a in expr.args]
        out_dt = _dtype_of(expr.type)
        cap = self.capacity

        # logical (Kleene)
        if name == "$and":

            def and_fn(env: Env) -> CVal:
                a, b = arg_fns[0](env), arg_fns[1](env)
                af = a.valid & ~a.data.astype(jnp.bool_)
                bf = b.valid & ~b.data.astype(jnp.bool_)
                res_false = af | bf
                res_true = (a.valid & a.data.astype(jnp.bool_)) & (
                    b.valid & b.data.astype(jnp.bool_)
                )
                return CVal(res_true, res_false | res_true)

            return and_fn, None
        if name == "$or":

            def or_fn(env: Env) -> CVal:
                a, b = arg_fns[0](env), arg_fns[1](env)
                at = a.valid & a.data.astype(jnp.bool_)
                bt = b.valid & b.data.astype(jnp.bool_)
                res_true = at | bt
                res_false = (a.valid & ~a.data.astype(jnp.bool_)) & (
                    b.valid & ~b.data.astype(jnp.bool_)
                )
                return CVal(res_true, res_false | res_true)

            return or_fn, None
        if name == "$not":

            def not_fn(env: Env) -> CVal:
                a = arg_fns[0](env)
                return CVal(~a.data.astype(jnp.bool_), a.valid)

            return not_fn, None
        if name == "$is_null":

            def isnull_fn(env: Env) -> CVal:
                a = arg_fns[0](env)
                return CVal(~a.valid, jnp.ones((cap,), dtype=jnp.bool_))

            return isnull_fn, None
        if name == "$not_null":

            def notnull_fn(env: Env) -> CVal:
                a = arg_fns[0](env)
                return CVal(a.valid, jnp.ones((cap,), dtype=jnp.bool_))

            return notnull_fn, None

        if name == "coalesce":
            if is_string(expr.type):
                # dictionary-coded strings: codes are only comparable within
                # ONE dictionary — merge the argument vocabularies and remap
                # every branch before selecting
                dicts = [self._dict_tree(a) for a in expr.args]
                dicts = [d if isinstance(d, Dictionary) else None for d in dicts]
                merged = _merge_dicts([d for d in dicts if d is not None])

                def coalesce_str_fn(env: Env) -> CVal:
                    vals = [f(env) for f in arg_fns]
                    datas = [
                        _remap_codes(v.data, d, merged) if d is not None else v.data
                        for v, d in zip(vals, dicts)
                    ]
                    data = datas[-1]
                    valid = vals[-1].valid
                    for v, dd in zip(reversed(vals[:-1]), reversed(datas[:-1])):
                        data = jnp.where(v.valid, dd, data)
                        valid = valid | v.valid
                    return CVal(data, valid, merged)

                return coalesce_str_fn, merged

            def coalesce_fn(env: Env) -> CVal:
                vals = [f(env) for f in arg_fns]
                data = vals[-1].data.astype(out_dt)
                valid = vals[-1].valid
                for v in reversed(vals[:-1]):
                    ok = v.valid[:, None] if v.data.ndim == 2 else v.valid
                    data = jnp.where(ok, v.data.astype(out_dt), data)
                    valid = valid | v.valid
                return CVal(data, valid)

            return coalesce_fn, None

        if name == "nullif":

            def nullif_fn(env: Env) -> CVal:
                a, b = arg_fns[0](env), arg_fns[1](env)
                same = (
                    (a.data == b.data).all(axis=-1)
                    if a.data.ndim == 2
                    else (a.data == b.data)
                )
                eq = same & a.valid & b.valid
                return CVal(a.data, a.valid & ~eq)

            return nullif_fn, None

        if name == "value_at_quantile":
            # tdigest lane data: [means..., weights...] per row; walk the
            # cumulative weight to the first centroid covering q (ref:
            # TDigest.valueAt — fully vectorized over rows AND centroids)
            q_type = expr.args[1].type
            out_type_ = expr.type
            from ..spi.types import is_integral as _is_int

            round_out = _is_int(out_type_)
            # digests store VALUE-space means (the aggregate descales decimal
            # inputs); a decimal element rescales back to storage before the
            # generic int64 cast
            out_scale = (
                10 ** out_type_.scale
                if isinstance(out_type_, DecimalType)
                else None
            )

            def vaq_fn(env: Env) -> CVal:
                td, q = arg_fns[0](env), arg_fns[1](env)
                kc = td.data.shape[1] // 2
                means, wts = td.data[:, :kc], td.data[:, kc:]
                total = jnp.sum(wts, axis=-1)
                qv = q.data.astype(jnp.float64)
                if isinstance(q_type, DecimalType):
                    qv = qv / float(10**q_type.scale)  # storage -> value space
                target = jnp.clip(qv, 0.0, 1.0) * total
                cum = jnp.cumsum(wts, axis=-1)
                okb = (cum >= target[:, None]) & (wts > 0)
                idx = jnp.argmax(okb, axis=-1)
                has = jnp.any(okb, axis=-1)
                val = jnp.take_along_axis(means, idx[:, None], axis=-1)[:, 0]
                if out_scale is not None:
                    val = jnp.round(val * out_scale)
                elif round_out:
                    # qdigest(bigint): centroid means round to the element
                    val = jnp.round(val)
                return CVal(val, td.valid & q.valid & has)

            return vaq_fn, None

        if name == "$dec_limb":
            # Int128 -> one of four 32-bit limbs as BIGINT (l3 keeps the
            # sign). The long-decimal aggregation decomposition: sums of
            # limbs are exact int64 for < 2**31 rows/group, so the whole
            # agg/exchange machinery stays scalar int64
            # (planner/rules.py decompose_long_decimal_aggregates).
            idx = expr.args[1].value
            src_t = expr.args[0].type

            def limb_fn(env: Env) -> CVal:
                from ..spi.types import is_long_decimal as _ild

                from . import int128 as i128

                v = arg_fns[0](env)
                x = v.data if _ild(src_t) else i128.from_int64(v.data)
                h, l = i128.hi(x), i128.lo(x)
                m32 = jnp.int64(0xFFFFFFFF)
                if idx == 0:
                    out = l & m32
                elif idx == 1:
                    out = jax.lax.shift_right_logical(l, jnp.int64(32))
                elif idx == 2:
                    out = h & m32
                else:
                    out = h >> jnp.int64(32)  # arithmetic: signed top limb
                return CVal(out, v.valid)

            return limb_fn, None

        if name in ("$i128_recombine", "$i128_avg"):
            nsums = 4

            def recombine_fn(env: Env) -> CVal:
                from . import int128 as i128

                vs = [f(env) for f in arg_fns]
                acc = i128.from_int64(vs[0].data)
                for i in range(1, nsums):
                    term = i128.from_int64(vs[i].data)
                    for _ in range(i):
                        term = i128.mul_int64(term, jnp.int64(1 << 32))
                    acc = i128.add(acc, term)
                valid = vs[0].valid
                for v in vs[1:nsums]:
                    valid = valid & v.valid
                if name == "$i128_avg":
                    cnt = vs[nsums]
                    acc = i128.div_int(acc, jnp.maximum(cnt.data, 1))
                    valid = valid & cnt.valid & (cnt.data > 0)
                return CVal(acc, valid)

            return recombine_fn, None

        if name == "$avg_combine":
            # final-stage avg = total_sum / total_count (fragmenter split);
            # result is NULL when no rows aggregated
            out_type_ = expr.type

            def avgc_fn(env: Env) -> CVal:
                s, c = arg_fns[0](env), arg_fns[1](env)
                cnt = jnp.maximum(c.data, 1)
                if isinstance(out_type_, DecimalType) and isinstance(
                    expr.args[0].type, DecimalType
                ):
                    half = cnt // 2
                    data = jnp.where(
                        s.data >= 0, (s.data + half) // cnt, -((-s.data + half) // cnt)
                    )
                else:
                    data = s.data.astype(jnp.float64) / cnt
                    if isinstance(expr.args[0].type, DecimalType):
                        data = data / float(10 ** expr.args[0].type.scale)
                return CVal(data.astype(out_dt), s.valid & c.valid & (c.data > 0))

            return avgc_fn, None

        if name.endswith("_combine") and name.startswith("$"):
            # $<stddev|variance...>_combine(s1, s2, n)
            stat = name[1:].rsplit("_combine", 1)[0]

            def varc_fn(env: Env) -> CVal:
                s1, s2, cn = (f(env) for f in arg_fns)
                n = jnp.maximum(cn.data, 1).astype(jnp.float64)
                mean = s1.data / n
                var_pop = jnp.maximum(s2.data / n - mean * mean, 0.0)
                if stat in ("var_pop", "stddev_pop"):
                    var = var_pop
                    valid = cn.data > 0
                else:
                    var = var_pop * n / jnp.maximum(n - 1, 1)
                    valid = cn.data > 1
                data = jnp.sqrt(var) if stat.startswith("stddev") else var
                return CVal(data, s1.valid & s2.valid & valid)

            return varc_fn, None

        if name in ("date_trunc", "date_add", "date_diff"):
            return self._compile_datetime_fn(expr)

        if name in ("pi", "e", "nan", "infinity") and not expr.args:
            import math as _math

            constv = {"pi": _math.pi, "e": _math.e, "nan": float("nan"),
                      "infinity": float("inf")}[name]
            cap0 = self.capacity

            def const_fn(env: Env) -> CVal:
                return CVal(
                    jnp.full((cap0,), constv, dtype=jnp.float64),
                    jnp.ones((cap0,), dtype=jnp.bool_),
                )

            return const_fn, None
        if name in ("random", "rand"):
            # per-row uniform via a mixed row index with a per-compilation
            # salt. Deviation, declared: a CACHED program replays its
            # sequence (the reference reseeds per call); fine for sampling.
            import random as _random

            salt = _random.getrandbits(63)
            cap0 = self.capacity
            hi = None
            if expr.args:
                inner_r, _ = self.compile(expr.args[0])
                hi = inner_r

            def random_fn(env: Env) -> CVal:
                from . import kernels as _K

                idx = jnp.arange(cap0, dtype=jnp.int64) + jnp.int64(salt)
                u = (
                    jax.lax.shift_right_logical(_K.splitmix64(idx), jnp.int64(11))
                ).astype(jnp.float64) / float(1 << 53)
                if hi is None:
                    return CVal(u, jnp.ones((cap0,), dtype=jnp.bool_))
                b = hi(env)
                n = jnp.maximum(b.data, 1)
                return CVal(
                    jnp.floor(u * n.astype(jnp.float64)).astype(jnp.int64),
                    b.valid & (b.data > 0),
                )

            return random_fn, None

        impl = _SIMPLE_FUNCS.get(name)
        if impl is None:
            raise CompileError(f"no device lowering for function {name}")

        def call_fn(env: Env) -> CVal:
            vals = [f(env) for f in arg_fns]
            data = impl([v.data for v in vals], arg_types, expr.type)
            valid = None
            for v in vals:
                valid = v.valid if valid is None else (valid & v.valid)
            if valid is None:
                valid = jnp.ones((cap,), dtype=jnp.bool_)
            return CVal(data.astype(out_dt) if data.dtype != out_dt else data, valid)

        return call_fn, None

    def _compile_datetime_fn(self, expr: Call) -> Tuple[Compiled, Optional[Dictionary]]:
        """date_trunc/date_add/date_diff with a constant unit (ref:
        operator/scalar/DateTimeFunctions.java). Calendar math runs on-device
        via the civil-date conversions (_civil_from_days/_days_from_civil)."""
        name = expr.name
        unit_arg = expr.args[0]
        if not isinstance(unit_arg, Constant) or not isinstance(unit_arg.value, str):
            raise CompileError(f"{name}: unit must be a string literal")
        unit = unit_arg.value.lower().rstrip("s")
        out_dt = _dtype_of(expr.type)

        if name == "date_trunc":
            inner, _ = self.compile(expr.args[1])
            src_t = expr.args[1].type

            def trunc_fn(env: Env) -> CVal:
                v = inner(env)
                days = _days_of(v.data, src_t)
                if unit == "day":
                    out_days = days
                elif unit == "week":  # ISO week starts Monday; epoch was a Thursday
                    out_days = days - jnp.remainder(days.astype(jnp.int64) + 3, 7)
                elif unit in ("month", "quarter", "year"):
                    y, m, _d = _civil_from_days(days)
                    if unit == "quarter":
                        m = ((m - 1) // 3) * 3 + 1
                    elif unit == "year":
                        m = jnp.ones_like(m)
                    out_days = _days_from_civil(y, m, jnp.ones_like(m))
                else:
                    raise CompileError(f"date_trunc unit {unit!r} not supported")
                if src_t == DATE:
                    return CVal(out_days.astype(out_dt), v.valid)
                return CVal((out_days * 86_400_000_000).astype(out_dt), v.valid)

            return trunc_fn, None

        if name == "date_add":
            amount_fn, _ = self.compile(expr.args[1])
            inner, _ = self.compile(expr.args[2])
            src_t = expr.args[2].type

            def add_fn(env: Env) -> CVal:
                amt = amount_fn(env)
                v = inner(env)
                days = _days_of(v.data, src_t)
                n = amt.data.astype(jnp.int64)
                if unit == "day":
                    out_days = days.astype(jnp.int64) + n
                elif unit == "week":
                    out_days = days.astype(jnp.int64) + 7 * n
                elif unit in ("month", "year", "quarter"):
                    k = n * {"month": 1, "quarter": 3, "year": 12}[unit]
                    y, m, d = _civil_from_days(days)
                    total = y * 12 + (m - 1) + k
                    ny = jnp.floor_divide(total, 12)
                    nm = jnp.remainder(total, 12) + 1
                    # clamp day to the target month's length
                    month_start = _days_from_civil(ny, nm, jnp.ones_like(nm))
                    next_start = _days_from_civil(
                        ny + (nm == 12), jnp.where(nm == 12, 1, nm + 1), jnp.ones_like(nm)
                    )
                    dim = next_start - month_start
                    out_days = month_start + jnp.minimum(d, dim) - 1
                else:
                    raise CompileError(f"date_add unit {unit!r} not supported")
                if src_t == DATE:
                    return CVal(out_days.astype(out_dt), v.valid & amt.valid)
                return CVal((out_days * 86_400_000_000).astype(out_dt), v.valid & amt.valid)

            return add_fn, None

        # date_diff(unit, a, b) = number of unit boundaries from a to b
        a_fn, _ = self.compile(expr.args[1])
        b_fn, _ = self.compile(expr.args[2])
        at, bt = expr.args[1].type, expr.args[2].type

        def diff_fn(env: Env) -> CVal:
            va, vb = a_fn(env), b_fn(env)
            da = _days_of(va.data, at).astype(jnp.int64)
            db = _days_of(vb.data, bt).astype(jnp.int64)
            if unit == "day":
                out = db - da
            elif unit == "week":
                out = (db - da) // 7
            elif unit in ("month", "quarter", "year"):
                ya, ma, _ = _civil_from_days(da)
                yb, mb, _ = _civil_from_days(db)
                months = (yb * 12 + mb) - (ya * 12 + ma)
                out = months // {"month": 1, "quarter": 3, "year": 12}[unit]
            else:
                raise CompileError(f"date_diff unit {unit!r} not supported")
            return CVal(out.astype(out_dt), va.valid & vb.valid)

        return diff_fn, None

    # ------------------------------------------------ string specializations

    def _dict_of(self, expr: IrExpr) -> Optional[Dictionary]:
        if isinstance(expr, Reference):
            lay = self.layout.get(expr.symbol)
            return lay.dictionary if lay else None
        if isinstance(expr, CastExpr):
            return self._dict_of(expr.value)
        if isinstance(expr, (Call, Case, Constant)):
            # computed string expressions (substr(col, ...), CASE ... END) and
            # string literals carry their output dictionary from compilation
            from ..spi.types import is_string

            if is_string(expr.type):
                _, out_dict = self.compile(expr)
                return out_dict
        return None

    def _compile_string_comparison(self, expr: Call) -> Tuple[Compiled, Optional[Dictionary]]:
        name = expr.name
        a, b = expr.args
        # normalize: column <op> constant
        if isinstance(a, Constant) and not isinstance(b, Constant):
            flip = {"$lt": "$gt", "$lte": "$gte", "$gt": "$lt", "$gte": "$lte"}
            name = flip.get(name, name)
            a, b = b, a
        if isinstance(b, Constant):
            d = self._dict_of(a)
            if d is None:
                raise CompileError("string comparison requires a dictionary column")
            inner, _ = self.compile(a)
            s = b.value
            if name in ("$eq", "$ne"):
                code = d.code_of(s) if s is not None else -1

                def eq_fn(env: Env) -> CVal:
                    v = inner(env)
                    if s is None:
                        return CVal(
                            jnp.zeros((self.capacity,), dtype=jnp.bool_),
                            jnp.zeros((self.capacity,), dtype=jnp.bool_),
                        )
                    res = v.data == code
                    if name == "$ne":
                        res = ~res
                    return CVal(res, v.valid)

                return eq_fn, None
            # range ops via sorted-dictionary searchsorted
            lo_left = d.searchsorted(s, "left")
            lo_right = d.searchsorted(s, "right")

            def range_fn(env: Env) -> CVal:
                v = inner(env)
                if name == "$lt":
                    res = v.data < lo_left
                elif name == "$lte":
                    res = v.data < lo_right
                elif name == "$gt":
                    res = v.data >= lo_right
                else:  # $gte
                    res = v.data >= lo_left
                return CVal(res, v.valid)

            return range_fn, None

        # column vs column
        da, db = self._dict_of(a), self._dict_of(b)
        fa, _ = self.compile(a)
        fb, _ = self.compile(b)
        if da is None or db is None:
            raise CompileError("string comparison requires dictionary columns")
        if da is db:

            def samecmp_fn(env: Env) -> CVal:
                va, vb = fa(env), fb(env)
                res = _compare(name, va.data, vb.data)
                return CVal(res, va.valid & vb.valid)

            return samecmp_fn, None
        if name in ("$eq", "$ne"):
            # translate codes of A into codes of B (exact-match LUT, -1 = no match)
            lut = np.array([db.code_of(s) for s in da.values], dtype=np.int32)

            def xdict_eq_fn(env: Env) -> CVal:
                va, vb = fa(env), fb(env)
                lut_dev = jnp.asarray(lut)
                mapped = lut_dev[jnp.clip(va.data, 0, lut_dev.shape[0] - 1)]
                res = (mapped == vb.data) & (mapped >= 0)
                if name == "$ne":
                    res = ~res
                return CVal(res, va.valid & vb.valid)

            return xdict_eq_fn, None
        raise CompileError(
            "ordering comparison across different dictionaries not supported yet"
        )

    def _compile_like(self, expr: Call) -> Tuple[Compiled, Optional[Dictionary]]:
        value = expr.args[0]
        pattern = expr.args[1]
        escape = expr.args[2].value if len(expr.args) > 2 else None
        if not isinstance(pattern, Constant):
            raise CompileError("LIKE pattern must be constant")
        d = self._dict_of(value)
        if d is None:
            raise CompileError("LIKE requires a dictionary column")
        inner, _ = self.compile(value)
        rx = _like_to_regex(pattern.value, escape)
        lut_np = np.fromiter(
            (rx.fullmatch(s) is not None for s in d.values), dtype=np.bool_, count=len(d)
        )

        def like_fn(env: Env) -> CVal:
            v = inner(env)
            lut_dev = jnp.asarray(lut_np)
            codes = jnp.clip(v.data, 0, lut_dev.shape[0] - 1)
            return CVal(lut_dev[codes], v.valid)

        return like_fn, None

    def _compile_concat(self, expr: Call) -> Tuple[Compiled, Optional[Dictionary]]:
        """String concat over constants + up to two dictionary columns: the
        output vocabulary is the (product) dictionary, computed host-side once;
        the device maps codes through an int LUT (ref operator/scalar/
        ConcatFunction — per-row Slice concat becomes O(|vocab|))."""
        dyn = [i for i, a in enumerate(expr.args) if not isinstance(a, Constant)]
        consts = {
            i: a.value for i, a in enumerate(expr.args) if isinstance(a, Constant)
        }
        if len(dyn) == 0:
            # all-constant: fold here (the planner's constant folder covers
            # arithmetic only)
            if any(v is None for v in consts.values()):
                return self.compile(Constant(expr.type, None))
            folded = "".join(str(consts[i]) for i in range(len(expr.args)))
            return self.compile(Constant(expr.type, folded))
        dicts = {i: self._dict_of(expr.args[i]) for i in dyn}
        if any(d is None for d in dicts.values()):
            raise CompileError("concat requires dictionary-coded string columns")
        if len(dyn) > 2:
            raise CompileError("concat over 3+ non-constant strings not supported yet")
        sizes = [len(dicts[i]) for i in dyn]
        if len(dyn) == 2 and sizes[0] * sizes[1] > 1 << 16:
            raise CompileError(
                f"concat product vocabulary too large ({sizes[0]}x{sizes[1]})"
            )

        def render(codes_vals):  # dict arg-index -> string value
            parts = []
            for i in range(len(expr.args)):
                v = codes_vals.get(i) if i in dicts else consts.get(i)
                if v is None:
                    return None
                parts.append(str(v))
            return "".join(parts)

        if len(dyn) == 1:
            i0 = dyn[0]
            new_values = [render({i0: s}) for s in dicts[i0].values]
        else:
            i0, i1 = dyn
            new_values = [
                render({i0: s0, i1: s1})
                for s0 in dicts[i0].values
                for s1 in dicts[i1].values
            ]
        out_dict, lut_np = _build_code_lut(new_values)
        fns = [self.compile(expr.args[i])[0] for i in dyn]
        n1 = sizes[1] if len(dyn) == 2 else 1

        def concat_fn(env: Env) -> CVal:
            vals = [f(env) for f in fns]
            lut = jnp.asarray(lut_np)
            if len(vals) == 1:
                pair = vals[0].data
                valid = vals[0].valid
            else:
                pair = vals[0].data.astype(jnp.int32) * n1 + vals[1].data
                valid = vals[0].valid & vals[1].valid
            codes = lut[jnp.clip(pair, 0, lut.shape[0] - 1)]
            return CVal(jnp.maximum(codes, 0), valid & (codes >= 0), out_dict)

        return concat_fn, out_dict

    def _compile_string_function(self, expr: Call) -> Tuple[Compiled, Optional[Dictionary]]:
        """String functions via host dictionary transform + device code remap.

        The transform runs once per (function, dictionary) at compile time:
        new_values = f(dict.values); output dictionary is the sorted unique set and
        a code LUT maps old codes -> new codes. (Trino evaluates per row via Slice
        ops — operator/scalar/StringFunctions.java; dictionaries make it O(|dict|).)
        """
        name = expr.name
        if name == "concat":
            return self._compile_concat(expr)
        value = expr.args[0]
        d = self._dict_of(value)
        if name in ("length", "char_length", "character_length") and d is not None:
            inner, _ = self.compile(value)
            lut_np = np.array([len(s) for s in d.values], dtype=np.int64)

            def length_fn(env: Env) -> CVal:
                v = inner(env)
                lut = jnp.asarray(lut_np)
                return CVal(lut[jnp.clip(v.data, 0, lut.shape[0] - 1)], v.valid)

            return length_fn, None
        if name == "codepoint" and d is not None:
            inner, _ = self.compile(value)
            lut_np = np.array(
                [ord(s[0]) if s else 0 for s in d.values], dtype=np.int64
            )

            def codepoint_fn(env: Env) -> CVal:
                v = inner(env)
                lut = jnp.asarray(lut_np)
                return CVal(lut[jnp.clip(v.data, 0, lut.shape[0] - 1)], v.valid)

            return codepoint_fn, None
        if name in _STRING_ARRAY_LUTS and d is not None:
            # string -> array<varchar> via a [vocab, W] code LUT: the parts
            # of every dictionary value are computed once on host, a child
            # dictionary is built from their union, and each row gathers its
            # value's code lanes (ref: StringFunctions.split / regexp family
            # — per-row loops there, one dictionary pass here)
            fn_ = _STRING_ARRAY_LUTS[name]
            cargs = []
            for a in expr.args[1:]:
                if not isinstance(a, Constant):
                    raise CompileError(f"{name}: arguments must be constant")
                cargs.append(a.value)
            parts: List[Optional[List[str]]] = []
            for s in d.values:
                try:
                    parts.append([p for p in fn_(s, *cargs)])
                except Exception:  # noqa: BLE001 — per-value failure -> NULL
                    parts.append(None)
            w = max((len(p) for p in parts if p is not None), default=1) or 1
            vocab = sorted({p for ps in parts if ps is not None for p in ps})
            child = Dictionary(np.asarray(vocab, dtype=object))
            code_of = {s: c for c, s in enumerate(vocab)}
            codes_np = np.zeros((len(parts), w), dtype=np.int32)
            len_np = np.zeros((len(parts),), dtype=np.int32)
            ok_np = np.zeros((len(parts),), dtype=np.bool_)
            for i, ps in enumerate(parts):
                if ps is None:
                    continue
                ok_np[i] = True
                len_np[i] = len(ps)
                for j, p in enumerate(ps):
                    codes_np[i, j] = code_of[p]
            inner, _ = self.compile(value)

            def split_fn(env: Env) -> CVal:
                v = inner(env)
                idx = jnp.clip(v.data, 0, len(parts) - 1)
                data = jnp.asarray(codes_np)[idx]
                lengths = jnp.asarray(len_np)[idx]
                ok = jnp.asarray(ok_np)[idx]
                ev = jnp.arange(w)[None, :] < lengths[:, None]
                return CVal(data, v.valid & ok, child, lengths, ev)

            return split_fn, child
        if name in _STRING_INT_LUTS and d is not None:
            fn_, dtype_ = _STRING_INT_LUTS[name]
            cargs = []
            for a in expr.args[1:]:
                if not isinstance(a, Constant):
                    raise CompileError(f"{name}: non-leading args must be constant")
                cargs.append(a.value)
            vals = []
            for sv in d.values:
                try:
                    vals.append(fn_(sv, *cargs))
                except Exception:  # noqa: BLE001 — per-value failures -> NULL
                    vals.append(None)
            lut_np = np.array(
                [(-1 if v is None else (int(v) if dtype_ != np.bool_ else bool(v)))
                 for v in vals],
                dtype=np.int64 if dtype_ != np.bool_ else np.bool_,
            )
            null_np = np.array([v is None for v in vals], dtype=np.bool_)
            inner, _ = self.compile(value)

            def slut_fn(env: Env) -> CVal:
                v = inner(env)
                codes = jnp.clip(v.data, 0, lut_np.shape[0] - 1)
                out = jnp.asarray(lut_np)[codes]
                bad = jnp.asarray(null_np)[codes]
                return CVal(out, v.valid & ~bad)

            return slut_fn, None
        if name in ("levenshtein_distance", "hamming_distance") and d is not None:
            other = expr.args[1]
            if not isinstance(other, Constant):
                raise CompileError(f"{name}: second argument must be constant")
            ref = other.value or ""

            def lev(a: str, b: str) -> int:
                prev = list(range(len(b) + 1))
                for i, ca in enumerate(a, 1):
                    cur = [i]
                    for j, cb in enumerate(b, 1):
                        cur.append(min(prev[j] + 1, cur[j - 1] + 1,
                                       prev[j - 1] + (ca != cb)))
                    prev = cur
                return prev[-1]

            if name == "hamming_distance":
                vals = [
                    sum(a != b for a, b in zip(s, ref)) if len(s) == len(ref) else -1
                    for s in d.values
                ]
            else:
                vals = [lev(s, ref) for s in d.values]
            lut_np = np.array(vals, dtype=np.int64)
            inner, _ = self.compile(value)

            def dist_fn(env: Env) -> CVal:
                v = inner(env)
                lut = jnp.asarray(lut_np)
                out = lut[jnp.clip(v.data, 0, lut.shape[0] - 1)]
                # hamming over unequal lengths raises in the reference; NULL here
                return CVal(out, v.valid & (out >= 0))

            return dist_fn, None
        if name == "strpos" and d is not None:
            sub = expr.args[1]
            if not isinstance(sub, Constant):
                raise CompileError("strpos needle must be constant")
            inner, _ = self.compile(value)
            lut_np = np.array([s.find(sub.value) + 1 for s in d.values], dtype=np.int64)

            def strpos_fn(env: Env) -> CVal:
                v = inner(env)
                lut = jnp.asarray(lut_np)
                return CVal(lut[jnp.clip(v.data, 0, lut.shape[0] - 1)], v.valid)

            return strpos_fn, None
        if name == "starts_with" and d is not None:
            prefix = expr.args[1]
            if not isinstance(prefix, Constant):
                raise CompileError("starts_with prefix must be constant")
            inner, _ = self.compile(value)
            # prefix predicate == one searchsorted range on the sorted dictionary
            lo = d.searchsorted(prefix.value, "left")
            hi = d.searchsorted(prefix.value + "￿", "right")

            def sw_fn(env: Env) -> CVal:
                v = inner(env)
                return CVal((v.data >= lo) & (v.data < hi), v.valid)

            return sw_fn, None
        if name == "regexp_like" and d is not None:
            # regex predicate as a boolean LUT over the dictionary — the host
            # regex engine runs O(|dict|) once at compile time (ref: Trino's
            # joni matcher runs per ROW; dictionaries collapse that)
            pattern = expr.args[1]
            if not isinstance(pattern, Constant):
                raise CompileError("regexp_like pattern must be constant")
            rx = re.compile(pattern.value)
            inner, _ = self.compile(value)
            lut_np = np.fromiter(
                (rx.search(s) is not None for s in d.values),
                dtype=np.bool_,
                count=len(d),
            )

            def rxlike_fn(env: Env) -> CVal:
                v = inner(env)
                lut = jnp.asarray(lut_np)
                return CVal(lut[jnp.clip(v.data, 0, lut.shape[0] - 1)], v.valid)

            return rxlike_fn, None
        if name in ("json_array_length", "json_size", "json_array_contains") and d is not None:
            # JSON functions with non-string outputs: a typed LUT + a validity
            # LUT (NULL results) computed once over the dictionary
            import json as _json

            cargs = []
            for a in expr.args[1:]:
                if not isinstance(a, Constant):
                    raise CompileError(f"{name}: arguments must be constant")
                val = a.value
                if isinstance(a.type, DecimalType) and val is not None:
                    val = val / 10**a.type.scale
                cargs.append(val)
            if any(v is None for v in cargs):
                return self.compile(Constant(expr.type, None))  # SQL NULL arg

            def compute(s):
                if name == "json_array_length":
                    try:
                        v = _json.loads(s)
                    except (ValueError, TypeError):
                        return None
                    return len(v) if isinstance(v, list) else None
                if name == "json_size":
                    v = _json_eval(s, _parse_json_path(cargs[0]))
                    if v is _MISSING:
                        return None
                    return len(v) if isinstance(v, (dict, list)) else 0
                # json_array_contains — type-strict per the reference: JSON
                # true is not the number 1, and bigints compare exactly
                try:
                    v = _json.loads(s)
                except (ValueError, TypeError):
                    return None
                if not isinstance(v, list):
                    return None
                needle = cargs[0]

                def hit(x):
                    if isinstance(needle, bool):
                        return isinstance(x, bool) and x == needle
                    if isinstance(needle, (int, float)):
                        return (
                            isinstance(x, (int, float))
                            and not isinstance(x, bool)
                            and x == needle
                        )
                    return isinstance(x, str) and x == needle

                return any(hit(x) for x in v)

            results = [compute(s) for s in d.values]
            out_np_t = np.bool_ if name == "json_array_contains" else np.int64
            lut_np = np.array([r if r is not None else 0 for r in results], dtype=out_np_t)
            ok_np = np.array([r is not None for r in results], dtype=np.bool_)
            inner, _ = self.compile(value)

            def jsonlut_fn(env: Env) -> CVal:
                v = inner(env)
                lut = jnp.asarray(lut_np)
                ok = jnp.asarray(ok_np)
                codes = jnp.clip(v.data, 0, lut.shape[0] - 1)
                return CVal(lut[codes], v.valid & ok[codes])

            return jsonlut_fn, None

        if d is None:
            raise CompileError(f"{name} requires a dictionary column")

        transform = _STRING_FUNCS[name]
        args = []
        for a in expr.args[1:]:
            if not isinstance(a, Constant):
                raise CompileError(f"{name}: non-leading arguments must be constant")
            args.append(a.value)
        if any(v is None for v in args):
            return self.compile(Constant(expr.type, None))  # SQL NULL argument
        new_values = [transform(s, *args) for s in d.values]
        # transforms may produce SQL NULL (e.g. regexp_extract with no match):
        # those map to code -1 and invalidate the row
        out_dict, lut_np = _build_code_lut(new_values)
        inner, _ = self.compile(value)

        def transform_fn(env: Env) -> CVal:
            v = inner(env)
            lut = jnp.asarray(lut_np)
            codes = lut[jnp.clip(v.data, 0, lut.shape[0] - 1)]
            return CVal(
                jnp.maximum(codes, 0), v.valid & (codes >= 0), out_dict
            )

        return transform_fn, out_dict


# --------------------------------------------------------------------------- #
# lowering tables
# --------------------------------------------------------------------------- #


def _cmp_norm(x, t: Type):
    """Comparison key: TIMESTAMP WITH TIME ZONE compares by INSTANT — strip
    the packed zone key (the reference's TTZ comparison operators likewise
    operate on unpackMillisUtc)."""
    from ..spi.types import TimestampWithTimeZoneType, TimeWithTimeZoneType

    if isinstance(t, (TimestampWithTimeZoneType, TimeWithTimeZoneType)):
        return x >> 12  # both pack the UTC-normalized instant in the high bits
    return x


def _cmp_op(name: str):
    """Comparison lowering; long decimals (Int128 limbs) compare limb-wise
    (planner coercions guarantee both sides share type + scale)."""

    def impl(datas, arg_types, out_type):
        from ..spi.types import is_long_decimal

        a, b = datas
        at, bt = arg_types
        if is_long_decimal(at) or is_long_decimal(bt):
            from . import int128 as i128

            A = a if is_long_decimal(at) else i128.from_int64(a)
            B = b if is_long_decimal(bt) else i128.from_int64(b)
            return {
                "$eq": lambda: i128.eq(A, B),
                "$ne": lambda: ~i128.eq(A, B),
                "$lt": lambda: i128.lt(A, B),
                "$lte": lambda: i128.lte(A, B),
                "$gt": lambda: i128.lt(B, A),
                "$gte": lambda: i128.lte(B, A),
            }[name]()
        return _compare(name, _cmp_norm(a, at), _cmp_norm(b, bt))

    return impl


def _compare(name: str, a, b):
    return {
        "$eq": lambda: a == b,
        "$ne": lambda: a != b,
        "$lt": lambda: a < b,
        "$lte": lambda: a <= b,
        "$gt": lambda: a > b,
        "$gte": lambda: a >= b,
    }[name]()


def _wilson(d, lower: bool):
    """Wilson score interval bound (ref: scalar/WilsonInterval.java)."""
    n_s = d[0].astype(jnp.float64)
    n = d[1].astype(jnp.float64)
    z = d[2].astype(jnp.float64)
    p = n_s / jnp.maximum(n, 1.0)
    z2 = z * z
    denom = 1.0 + z2 / jnp.maximum(n, 1.0)
    center = p + z2 / (2.0 * jnp.maximum(n, 1.0))
    spread = z * jnp.sqrt(
        (p * (1.0 - p) + z2 / (4.0 * jnp.maximum(n, 1.0))) / jnp.maximum(n, 1.0)
    )
    return (center - spread if lower else center + spread) / denom


def _lane_aware_negate(d, t, o):
    from ..spi.types import is_long_decimal

    if is_long_decimal(t[0]):
        from . import int128 as i128

        return i128.negate(d[0])
    return -d[0]


def _lane_aware_abs(d, t, o):
    from ..spi.types import is_long_decimal

    if is_long_decimal(t[0]):
        from . import int128 as i128

        return i128.abs_(d[0])
    return jnp.abs(d[0])


def _div_round(x, divisor: int):
    """Round-half-up integer division (Trino decimal rescale semantics)."""
    half = divisor // 2
    return jnp.where(x >= 0, (x + half) // divisor, -((-x + half) // divisor))


def _civil_from_days(z):
    """days-since-epoch -> (year, month, day); Howard Hinnant's algorithm,
    branch-free and integer-only (MXU/VPU friendly)."""
    z = z.astype(jnp.int64) + 719468
    era = jnp.floor_divide(z, 146097)
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + jnp.where(mp < 10, 3, -9)
    y = y + (m <= 2)
    return y, m, d


def _arith(name):
    def impl(datas, arg_types, out_type):
        from ..spi.types import is_long_decimal

        a, b = datas
        at, bt = arg_types
        if is_long_decimal(out_type) or is_long_decimal(at) or is_long_decimal(bt):
            from . import int128 as i128

            A = a if is_long_decimal(at) else i128.from_int64(a)
            B = b if is_long_decimal(bt) else i128.from_int64(b)
            if name == "$add":
                return i128.add(A, B)
            if name == "$subtract":
                return i128.sub(A, B)
            if name == "$multiply":
                return i128.mul(A, B)
            raise CompileError(
                f"{name} on DECIMAL(p>18) not supported yet "
                "(ref Int128Math.divideRoundUp)"
            )
        # date/timestamp +- interval
        if at == DATE and bt == INTERVAL_DAY_TIME:
            days = b // 86_400_000_000
            return (a + days if name == "$add" else a - days).astype(jnp.int32)
        if at == DATE and bt == DATE and name == "$subtract":
            return (a.astype(jnp.int64) - b.astype(jnp.int64)) * 86_400_000_000
        if at == DATE and bt == INTERVAL_YEAR_MONTH:
            raise CompileError(
                "date +/- year-month interval over columns not supported yet "
                "(constant-folded when both sides are literals)"
            )
        if name == "$add":
            return a + b
        if name == "$subtract":
            return a - b
        if name == "$multiply":
            out = a * b
            # decimal x decimal already correct: scales add
            return out
        if name == "$divide":
            if is_integral(out_type):
                return jnp.floor_divide(jnp.abs(a), jnp.abs(b).clip(1)) * (
                    jnp.sign(a) * jnp.sign(b)
                )
            return a / b
        if name == "$modulus":
            if isinstance(out_type, DecimalType) or is_integral(out_type):
                m = jnp.remainder(jnp.abs(a), jnp.abs(b).clip(1))
                return m * jnp.sign(a)
            return jnp.remainder(a, b)
        raise CompileError(name)

    return impl


def _binomial_cdf(trials, p, k):
    # P(X <= k) = I_{1-p}(n - k, k + 1)
    n = trials.astype(jnp.float64)
    kk = jnp.clip(jnp.floor(k.astype(jnp.float64)), -1.0, n)
    a = jnp.maximum(n - kk, 1e-12)
    b = kk + 1.0
    out = jax.scipy.special.betainc(a, b, 1.0 - p)
    return jnp.where(kk < 0, 0.0, jnp.where(kk >= n, 1.0, out))


def _f_cdf(df1, df2, x):
    return jax.scipy.special.betainc(
        df1 / 2.0, df2 / 2.0, df1 * x / (df1 * x + df2)
    )


def _laplace_cdf(mean, scale, x):
    z = (x - mean) / scale
    return jnp.where(z < 0, 0.5 * jnp.exp(z), 1.0 - 0.5 * jnp.exp(-z))


def _inverse_laplace_cdf(mean, scale, p):
    return jnp.where(
        p < 0.5,
        mean + scale * jnp.log(2.0 * p),
        mean - scale * jnp.log(2.0 - 2.0 * p),
    )


def _t_cdf(df, x):
    ib = jax.scipy.special.betainc(df / 2.0, 0.5, df / (df + x * x))
    return jnp.where(x < 0, 0.5 * ib, 1.0 - 0.5 * ib)


def _t_pdf(df, x):
    from jax.scipy.special import gammaln

    logc = (
        gammaln((df + 1.0) / 2.0)
        - gammaln(df / 2.0)
        - 0.5 * jnp.log(df * jnp.pi)
    )
    return jnp.exp(logc - ((df + 1.0) / 2.0) * jnp.log1p(x * x / df))


def _inverse_beta_cdf(a, b, p):
    if not hasattr(jax.scipy.special, "betaincinv"):
        raise CompileError(
            "inverse_beta_cdf needs jax.scipy.special.betaincinv "
            "(unavailable in this jax build)"
        )
    return jax.scipy.special.betaincinv(a, b, p)


_SIMPLE_FUNCS: Dict[str, Callable] = {
    "$add": _arith("$add"),
    "$subtract": _arith("$subtract"),
    "$multiply": _arith("$multiply"),
    "$divide": _arith("$divide"),
    "$modulus": _arith("$modulus"),
    "$negate": _lane_aware_negate,
    "$eq": _cmp_op("$eq"),
    "$ne": _cmp_op("$ne"),
    "$lt": _cmp_op("$lt"),
    "$lte": _cmp_op("$lte"),
    "$gt": _cmp_op("$gt"),
    "$gte": _cmp_op("$gte"),
    "abs": _lane_aware_abs,
    "log": lambda d, t, o: jnp.log(_to_f64(d[1], t[1])) / jnp.log(_to_f64(d[0], t[0])),
    "normal_cdf": lambda d, t, o: 0.5 * (
        1.0 + jax.scipy.special.erf(
            (_to_f64(d[2], t[2]) - _to_f64(d[0], t[0]))
            / (_to_f64(d[1], t[1]) * jnp.sqrt(2.0))
        )
    ),
    "inverse_normal_cdf": lambda d, t, o: _to_f64(d[0], t[0]) + _to_f64(d[1], t[1])
    * jax.scipy.special.ndtri(_to_f64(d[2], t[2])),
    "beta_cdf": lambda d, t, o: jax.scipy.special.betainc(
        _to_f64(d[0], t[0]), _to_f64(d[1], t[1]), _to_f64(d[2], t[2])
    ),
    "wilson_interval_lower": lambda d, t, o: _wilson(
        [_to_f64(x, tt) for x, tt in zip(d, t)], lower=True
    ),
    "wilson_interval_upper": lambda d, t, o: _wilson(
        [_to_f64(x, tt) for x, tt in zip(d, t)], lower=False
    ),
    "timezone_hour": lambda d, t, o: jax.lax.div(
        ((d[0] & 0xFFF) - 841).astype(jnp.int64), jnp.int64(60)
    ),
    "timezone_minute": lambda d, t, o: jax.lax.rem(
        ((d[0] & 0xFFF) - 841).astype(jnp.int64), jnp.int64(60)
    ),
    "ceiling": lambda d, t, o: _decimal_ceil(d[0], t[0]) if isinstance(t[0], DecimalType) else jnp.ceil(d[0]),
    "ceil": lambda d, t, o: _decimal_ceil(d[0], t[0]) if isinstance(t[0], DecimalType) else jnp.ceil(d[0]),
    "floor": lambda d, t, o: _decimal_floor(d[0], t[0]) if isinstance(t[0], DecimalType) else jnp.floor(d[0]),
    "round": lambda d, t, o: jnp.round(d[0]) if len(d) == 1 else _round_n(d[0], d[1]),
    "sqrt": lambda d, t, o: jnp.sqrt(_to_f64(d[0], t[0])),
    "cbrt": lambda d, t, o: jnp.cbrt(_to_f64(d[0], t[0])),
    "exp": lambda d, t, o: jnp.exp(_to_f64(d[0], t[0])),
    "ln": lambda d, t, o: jnp.log(_to_f64(d[0], t[0])),
    "log2": lambda d, t, o: jnp.log2(_to_f64(d[0], t[0])),
    "log10": lambda d, t, o: jnp.log10(_to_f64(d[0], t[0])),
    "power": lambda d, t, o: jnp.power(_to_f64(d[0], t[0]), _to_f64(d[1], t[1])),
    "pow": lambda d, t, o: jnp.power(_to_f64(d[0], t[0]), _to_f64(d[1], t[1])),
    "mod": _arith("$modulus"),
    "sign": lambda d, t, o: jnp.sign(d[0]),
    "sin": lambda d, t, o: jnp.sin(_to_f64(d[0], t[0])),
    "cos": lambda d, t, o: jnp.cos(_to_f64(d[0], t[0])),
    "tan": lambda d, t, o: jnp.tan(_to_f64(d[0], t[0])),
    "asin": lambda d, t, o: jnp.arcsin(_to_f64(d[0], t[0])),
    "acos": lambda d, t, o: jnp.arccos(_to_f64(d[0], t[0])),
    "atan": lambda d, t, o: jnp.arctan(_to_f64(d[0], t[0])),
    "atan2": lambda d, t, o: jnp.arctan2(_to_f64(d[0], t[0]), _to_f64(d[1], t[1])),
    "greatest": lambda d, t, o: _nary(jnp.maximum, d),
    "least": lambda d, t, o: _nary(jnp.minimum, d),
    "year": lambda d, t, o: _civil_from_days(_days_of(d[0], t[0]))[0],
    "month": lambda d, t, o: _civil_from_days(_days_of(d[0], t[0]))[1],
    "day": lambda d, t, o: _civil_from_days(_days_of(d[0], t[0]))[2],
    "quarter": lambda d, t, o: (_civil_from_days(_days_of(d[0], t[0]))[1] + 2) // 3,
    "day_of_week": lambda d, t, o: jnp.remainder(_days_of(d[0], t[0]) + 3, 7) + 1,
    "day_of_year": lambda d, t, o: _day_of_year(_days_of(d[0], t[0])),
    "hour": lambda d, t, o: _micros_of_day(d[0], t[0]) // 3_600_000_000,
    "minute": lambda d, t, o: (_micros_of_day(d[0], t[0]) // 60_000_000) % 60,
    "second": lambda d, t, o: (_micros_of_day(d[0], t[0]) // 1_000_000) % 60,
    "millisecond": lambda d, t, o: (_micros_of_day(d[0], t[0]) // 1000) % 1000,
    "hash64": lambda d, t, o: _hash64_combine(d),
    # math long tail (operator/scalar/MathFunctions.java)
    "cot": lambda d, t, o: 1.0 / jnp.tan(_to_f64(d[0], t[0])),
    "bitwise_right_shift_arithmetic": lambda d, t, o: d[0].astype(jnp.int64)
    >> jnp.clip(d[1].astype(jnp.int64), 0, 63),
    "to_milliseconds": lambda d, t, o: d[0].astype(jnp.int64) // 1000,
    "date": lambda d, t, o: _days_of(d[0], t[0]).astype(jnp.int32),
    "from_unixtime_nanos": lambda d, t, o: d[0].astype(jnp.int64) // 1000,
    # try(): the engine's error channel is already NULL-on-failure
    # (division guards, LUT per-value exceptions), so try is a passthrough
    "try": lambda d, t, o: d[0],
    # probability distributions (MathFunctions.java CDF family; closed
    # forms / regularized incomplete gamma+beta via jax.scipy.special)
    "binomial_cdf": lambda d, t, o: _binomial_cdf(
        d[0], _to_f64(d[1], t[1]), d[2]
    ),
    "cauchy_cdf": lambda d, t, o: 0.5
    + jnp.arctan(
        (_to_f64(d[2], t[2]) - _to_f64(d[0], t[0])) / _to_f64(d[1], t[1])
    )
    / jnp.pi,
    "inverse_cauchy_cdf": lambda d, t, o: _to_f64(d[0], t[0])
    + _to_f64(d[1], t[1]) * jnp.tan(jnp.pi * (_to_f64(d[2], t[2]) - 0.5)),
    "chi_squared_cdf": lambda d, t, o: jax.scipy.special.gammainc(
        _to_f64(d[0], t[0]) / 2.0, _to_f64(d[1], t[1]) / 2.0
    ),
    "f_cdf": lambda d, t, o: _f_cdf(
        _to_f64(d[0], t[0]), _to_f64(d[1], t[1]), _to_f64(d[2], t[2])
    ),
    "gamma_cdf": lambda d, t, o: jax.scipy.special.gammainc(
        _to_f64(d[0], t[0]), _to_f64(d[2], t[2]) / _to_f64(d[1], t[1])
    ),
    "laplace_cdf": lambda d, t, o: _laplace_cdf(
        _to_f64(d[0], t[0]), _to_f64(d[1], t[1]), _to_f64(d[2], t[2])
    ),
    "inverse_laplace_cdf": lambda d, t, o: _inverse_laplace_cdf(
        _to_f64(d[0], t[0]), _to_f64(d[1], t[1]), _to_f64(d[2], t[2])
    ),
    "poisson_cdf": lambda d, t, o: jax.scipy.special.gammaincc(
        _to_f64(d[1], t[1]) + 1.0, _to_f64(d[0], t[0])
    ),
    "weibull_cdf": lambda d, t, o: 1.0
    - jnp.exp(
        -jnp.power(
            _to_f64(d[2], t[2]) / _to_f64(d[1], t[1]), _to_f64(d[0], t[0])
        )
    ),
    "inverse_weibull_cdf": lambda d, t, o: _to_f64(d[1], t[1])
    * jnp.power(
        -jnp.log1p(-_to_f64(d[2], t[2])), 1.0 / _to_f64(d[0], t[0])
    ),
    "t_cdf": lambda d, t, o: _t_cdf(_to_f64(d[0], t[0]), _to_f64(d[1], t[1])),
    "t_pdf": lambda d, t, o: _t_pdf(_to_f64(d[0], t[0]), _to_f64(d[1], t[1])),
    "inverse_beta_cdf": lambda d, t, o: _inverse_beta_cdf(
        _to_f64(d[0], t[0]), _to_f64(d[1], t[1]), _to_f64(d[2], t[2])
    ),
    "degrees": lambda d, t, o: jnp.degrees(_to_f64(d[0], t[0])),
    "radians": lambda d, t, o: jnp.radians(_to_f64(d[0], t[0])),
    "cosh": lambda d, t, o: jnp.cosh(_to_f64(d[0], t[0])),
    "sinh": lambda d, t, o: jnp.sinh(_to_f64(d[0], t[0])),
    "tanh": lambda d, t, o: jnp.tanh(_to_f64(d[0], t[0])),
    "is_nan": lambda d, t, o: jnp.isnan(_to_f64(d[0], t[0])),
    "is_finite": lambda d, t, o: jnp.isfinite(_to_f64(d[0], t[0])),
    "is_infinite": lambda d, t, o: jnp.isinf(_to_f64(d[0], t[0])),
    "truncate": lambda d, t, o: jnp.trunc(_to_f64(d[0], t[0])) if len(d) == 1
    else _truncate_n(d[0], d[1], t[0]),
    "width_bucket": lambda d, t, o: _width_bucket(
        _to_f64(d[0], t[0]), _to_f64(d[1], t[1]), _to_f64(d[2], t[2]), d[3]
    ),
    # bitwise family (operator/scalar/BitwiseFunctions.java; two's-complement
    # int64 semantics like the reference)
    "bitwise_and": lambda d, t, o: d[0].astype(jnp.int64) & d[1].astype(jnp.int64),
    "bitwise_or": lambda d, t, o: d[0].astype(jnp.int64) | d[1].astype(jnp.int64),
    "bitwise_xor": lambda d, t, o: d[0].astype(jnp.int64) ^ d[1].astype(jnp.int64),
    "bitwise_not": lambda d, t, o: ~d[0].astype(jnp.int64),
    "bitwise_left_shift": lambda d, t, o: d[0].astype(jnp.int64)
    << jnp.clip(d[1].astype(jnp.int64), 0, 63),
    "bitwise_right_shift": lambda d, t, o: jax.lax.shift_right_logical(
        d[0].astype(jnp.int64), jnp.clip(d[1].astype(jnp.int64), 0, 63)
    ),
    "bit_count": lambda d, t, o: _bit_count(d[0].astype(jnp.int64), d[1] if len(d) > 1 else None),
    # datetime long tail (operator/scalar/DateTimeFunctions.java)
    "day_of_month": lambda d, t, o: _civil_from_days(_days_of(d[0], t[0]))[2],
    "dow": lambda d, t, o: jnp.remainder(_days_of(d[0], t[0]) + 3, 7) + 1,
    "doy": lambda d, t, o: _day_of_year(_days_of(d[0], t[0])),
    "week": lambda d, t, o: _iso_week_year(_days_of(d[0], t[0]))[0],
    "week_of_year": lambda d, t, o: _iso_week_year(_days_of(d[0], t[0]))[0],
    "year_of_week": lambda d, t, o: _iso_week_year(_days_of(d[0], t[0]))[1],
    "yow": lambda d, t, o: _iso_week_year(_days_of(d[0], t[0]))[1],
    "last_day_of_month": lambda d, t, o: _last_day_of_month(_days_of(d[0], t[0])),
}


def _truncate_n(x, n, t):
    scale = jnp.power(10.0, n.astype(jnp.float64))
    return jnp.trunc(_to_f64(x, t) * scale) / scale


def _width_bucket(x, lo, hi, n):
    nb = jnp.maximum(n.astype(jnp.int64), 1)
    frac = (x - lo) / jnp.where(hi != lo, hi - lo, 1.0)
    b = jnp.floor(frac * nb.astype(jnp.float64)).astype(jnp.int64) + 1
    return jnp.clip(b, 0, nb + 1)


def _bit_count(x, bits):
    # popcount via the SWAR ladder (no scalar loop — VPU friendly)
    v = x
    if bits is not None:
        width = jnp.clip(bits.astype(jnp.int64), 2, 64)
        mask = jnp.where(
            width >= 64, jnp.int64(-1), (jnp.int64(1) << width) - 1
        )
        v = v & mask
    c = v - (jax.lax.shift_right_logical(v, jnp.int64(1)) & jnp.int64(0x5555555555555555))
    c = (c & jnp.int64(0x3333333333333333)) + (
        jax.lax.shift_right_logical(c, jnp.int64(2)) & jnp.int64(0x3333333333333333)
    )
    c = (c + jax.lax.shift_right_logical(c, jnp.int64(4))) & jnp.int64(0x0F0F0F0F0F0F0F0F)
    return jax.lax.shift_right_logical(c * jnp.int64(0x0101010101010101), jnp.int64(56))


def _iso_week_year(days):
    """ISO-8601 week number and week-year (WeekOfWeekBasedYear/WeekBasedYear)."""
    y, m, d = _civil_from_days(days)
    doy = _day_of_year(days)
    dow = jnp.remainder(days.astype(jnp.int64) + 3, 7) + 1  # Mon=1..Sun=7
    w = (doy - dow + 10) // 7

    def weeks_in(yy):
        jan1 = _days_from_civil(yy, jnp.ones_like(yy), jnp.ones_like(yy))
        jd = jnp.remainder(jan1 + 3, 7) + 1
        leap = ((yy % 4 == 0) & (yy % 100 != 0)) | (yy % 400 == 0)
        return 52 + ((jd == 4) | (leap & (jd == 3))).astype(jnp.int64)

    week = jnp.where(w < 1, weeks_in(y - 1), jnp.where(w > weeks_in(y), 1, w))
    wyear = jnp.where(w < 1, y - 1, jnp.where(w > weeks_in(y), y + 1, y))
    return week, wyear


def _last_day_of_month(days):
    y, m, _ = _civil_from_days(days)
    ny = jnp.where(m == 12, y + 1, y)
    nm = jnp.where(m == 12, 1, m + 1)
    return (_days_from_civil(ny, nm, jnp.ones_like(nm)) - 1).astype(jnp.int32)


def _to_f64(x, t: Type):
    if isinstance(t, DecimalType):
        return x / float(10**t.scale)
    return x.astype(jnp.float64)


def _days_of(x, t: Type):
    from ..spi.types import TimestampWithTimeZoneType

    if t == DATE:
        return x
    if isinstance(t, TimestampWithTimeZoneType):
        # packed (utc_millis << 12 | zone_key): calendar fields read in the
        # value's own zone (the reference's unpackMillisUtc + zone rules)
        local_millis = (x >> 12) + ((x & 0xFFF) - 841) * 60_000
        return jnp.floor_divide(local_millis, 86_400_000)
    # timestamp micros -> days
    return jnp.floor_divide(x, 86_400_000_000)


def _micros_of_day(x, t: Type):
    from ..spi.types import TimeType, TimestampWithTimeZoneType, TimeWithTimeZoneType

    if isinstance(t, TimeType):
        return x
    if isinstance(t, TimeWithTimeZoneType):
        # packed UTC micros + offset -> LOCAL micros of day
        local = (x >> 12) + ((x & 0xFFF) - 841) * 60_000_000
        return jnp.remainder(local, 86_400_000_000)
    if isinstance(t, TimestampWithTimeZoneType):
        local_millis = (x >> 12) + ((x & 0xFFF) - 841) * 60_000
        return jnp.remainder(local_millis, 86_400_000) * 1000
    return jnp.remainder(x, 86_400_000_000)


def _day_of_year(days):
    y, _, _ = _civil_from_days(days)
    jan1 = _days_from_civil(y, 1, 1)
    return days.astype(jnp.int64) - jan1 + 1


def _days_from_civil(y, m, d):
    y = y - (m <= 2)
    era = jnp.floor_divide(y, 400)
    yoe = y - era * 400
    mp = m + jnp.where(m > 2, -3, 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def _decimal_ceil(x, t: DecimalType):
    f = 10**t.scale
    return jnp.where(x >= 0, (x + f - 1) // f, -((-x) // f)) * f


def _decimal_floor(x, t: DecimalType):
    f = 10**t.scale
    return jnp.where(x >= 0, x // f, -((-x + f - 1) // f)) * f


def _round_n(x, n):
    p = jnp.power(10.0, n.astype(jnp.float64))
    return jnp.round(x * p) / p


def _nary(op, datas):
    out = datas[0]
    for d in datas[1:]:
        out = op(out, d)
    return out


def _hash64_combine(datas):
    """xxhash-style 64-bit mix for partitioning/join keys (the analogue of
    Trino's TypeOperators hash used by FlatHash/PagesHash)."""
    acc = jnp.uint64(0x9E3779B97F4A7C15)
    for d in datas:
        x = d.astype(jnp.uint64)
        x = (x ^ (x >> 33)) * jnp.uint64(0xFF51AFD7ED558CCD)
        x = (x ^ (x >> 33)) * jnp.uint64(0xC4CEB9FE1A85EC53)
        x = x ^ (x >> 33)
        acc = (acc ^ x) * jnp.uint64(0x100000001B3)
    return acc.astype(jnp.int64)


def _java_replacement_to_python(repl: str) -> str:
    """Java-style regex replacement ($N groups, backslash escapes the next
    char) -> Python re.sub template (backslash-group refs, literal backslashes
    doubled). A raw backslash handed to re.sub would raise 'bad escape'."""
    out = []
    i = 0
    while i < len(repl):
        ch = repl[i]
        if ch == "\\" and i + 1 < len(repl):
            nxt = repl[i + 1]
            out.append("\\\\" if nxt == "\\" else nxt)
            i += 2
            continue
        if ch == "$" and i + 1 < len(repl) and repl[i + 1].isdigit():
            j = i + 1
            while j < len(repl) and repl[j].isdigit():
                j += 1
            out.append("\\" + repl[i + 1 : j])
            i = j
            continue
        out.append("\\\\" if ch == "\\" else ch)
        i += 1
    return "".join(out)


# --------------------------------------------------------------------------- #
# JSON (ref: io.trino.operator.scalar.JsonFunctions + io.trino.jsonpath — the
# per-row jsonpath VM becomes a once-per-dictionary host transform here)
# --------------------------------------------------------------------------- #

_MISSING = object()


def _urlparse(s: str):
    from urllib.parse import urlparse

    try:
        return urlparse(s)
    except ValueError:
        return urlparse("")


import functools


@functools.lru_cache(maxsize=1024)
def _parse_json_path(path: str):
    """Parse the supported jsonpath subset: $, .field, ['field'], ["field"],
    [index]. Returns a list of ('field', name) / ('index', i) steps.
    Cached: transforms call this once per dictionary VALUE."""
    if not path.startswith("$"):
        raise CompileError(f"unsupported json path (must start with $): {path!r}")
    steps = []
    rest = path[1:]
    step_rx = re.compile(
        r"""^(?:
              \.(?P<dotted>[A-Za-z_][A-Za-z0-9_]*)
            | \[\s*(?P<index>-?\d+)\s*\]
            | \[\s*'(?P<sq>[^']*)'\s*\]
            | \[\s*"(?P<dq>[^"]*)"\s*\]
        )""",
        re.VERBOSE,
    )
    while rest:
        m = step_rx.match(rest)
        if m is None:
            raise CompileError(f"unsupported json path step at {rest!r}")
        if m.group("index") is not None:
            steps.append(("index", int(m.group("index"))))
        else:
            steps.append(
                ("field", m.group("dotted") or m.group("sq") or m.group("dq"))
            )
        rest = rest[m.end():]
    return tuple(steps)


def _build_code_lut(new_values):
    """Transformed dictionary values -> (output Dictionary, old-code -> new-code
    int32 LUT with -1 for SQL-NULL results). Shared by every dictionary
    transform (string functions, concat)."""
    uniq = sorted({s for s in new_values if s is not None})
    out_dict = Dictionary(np.asarray(uniq, dtype=object))
    code_map = {s: i for i, s in enumerate(uniq)}
    lut_np = np.array(
        [-1 if s is None else code_map[s] for s in new_values], dtype=np.int32
    )
    return out_dict, lut_np


def _json_eval(text, steps):
    """Evaluate parsed jsonpath steps; returns the python value or _MISSING."""
    import json as _json

    try:
        v = _json.loads(text)
    except (ValueError, TypeError):
        return _MISSING
    for kind, arg in steps:
        if kind == "field":
            if not isinstance(v, dict) or arg not in v:
                return _MISSING
            v = v[arg]
        else:
            if not isinstance(v, list):
                return _MISSING
            i = arg if arg >= 0 else len(v) + arg
            if not 0 <= i < len(v):
                return _MISSING
            v = v[i]
    return v


def _json_dumps(v) -> str:
    import json as _json

    return _json.dumps(v, separators=(",", ":"), ensure_ascii=False)


def _json_extract(s, path):
    v = _json_eval(s, _parse_json_path(path))
    return None if v is _MISSING else _json_dumps(v)


def _json_extract_scalar(s, path):
    v = _json_eval(s, _parse_json_path(path))
    if v is _MISSING or v is None or isinstance(v, (dict, list)):
        return None
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, str):
        return v
    return _json_dumps(v)


def _json_parse(s):
    import json as _json

    try:
        return _json_dumps(_json.loads(s))
    except (ValueError, TypeError):
        return None  # deviation: the reference raises on malformed JSON


def _json_array_get(s, idx):
    v = _json_eval(s, [("index", int(idx))])
    return None if v is _MISSING else _json_dumps(v)


def _json_eval_exists(s: str, path: str) -> bool:
    return _json_extract(s, path) is not None


def _null_on_error(fn):
    """Per-dictionary-value transform guard: a malformed value anywhere in
    the column must yield NULL for ITS rows, not abort the query (filtered
    rows still share the dictionary). Strictness note: from_base64 validates
    the alphabet — corrupt input becomes NULL, never silently-decoded data
    (the reference raises; NULL is this engine's documented error channel)."""

    def wrapped(s, *args):
        try:
            return fn(s, *args)
        except Exception:  # noqa: BLE001 — per-value failure -> NULL
            return None

    return wrapped


_STRING_FUNCS: Dict[str, Callable] = {
    "upper": lambda s: s.upper(),
    "lower": lambda s: s.lower(),
    "trim": lambda s: s.strip(),
    "ltrim": lambda s: s.lstrip(),
    "rtrim": lambda s: s.rstrip(),
    "substring": lambda s, start, length=None: (
        s[int(start) - 1 :] if length is None else s[int(start) - 1 : int(start) - 1 + int(length)]
    ),
    "substr": lambda s, start, length=None: (
        s[int(start) - 1 :] if length is None else s[int(start) - 1 : int(start) - 1 + int(length)]
    ),
    "replace": lambda s, find, repl="": s.replace(find, repl),
    "reverse": lambda s: s[::-1],
    "split_part": lambda s, delim, index: (
        (lambda parts, i: parts[i - 1] if 1 <= i <= len(parts) else None)(
            s.split(delim) if delim else [s], int(index)
        )
    ),
    "translate": lambda s, frm, to: s.translate(
        {ord(c): (to[i] if i < len(to) else None) for i, c in enumerate(frm)}
    ),
    "lpad": lambda s, n, fill=" ": (
        (fill * int(n))[: max(int(n) - len(s), 0)] + s if len(s) < int(n) else s[: int(n)]
    ),
    "rpad": lambda s, n, fill=" ": (
        s + (fill * int(n))[: max(int(n) - len(s), 0)] if len(s) < int(n) else s[: int(n)]
    ),
    "regexp_extract": lambda s, pattern, group=0: (
        (lambda m: m.group(int(group)) if m else None)(re.search(pattern, s))
    ),
    "regexp_replace": lambda s, pattern, repl="": re.sub(
        pattern, _java_replacement_to_python(repl), s
    ),
    "url_extract_protocol": lambda s: (_urlparse(s).scheme or None),
    "url_extract_host": lambda s: (_urlparse(s).hostname or None),
    "url_extract_path": lambda s: _urlparse(s).path,
    "url_extract_query": lambda s: (_urlparse(s).query or None),
    "url_extract_fragment": lambda s: (_urlparse(s).fragment or None),
    "url_extract_parameter": lambda s, name: (
        (lambda q: q.get(name, [None])[0])(
            __import__("urllib.parse", fromlist=["parse_qs"]).parse_qs(
                _urlparse(s).query, keep_blank_values=True
            )
        )
    ),
    "url_encode": lambda s: __import__("urllib.parse", fromlist=["quote"]).quote(
        s, safe=""
    ),
    # binary-family functions surface as lowercase-hex strings (the engine
    # has no varbinary lane; documented deviation from the reference's
    # varbinary returns in scalar/VarbinaryFunctions.java)
    "md5": lambda s: __import__("hashlib").md5(s.encode()).hexdigest(),
    "sha1": lambda s: __import__("hashlib").sha1(s.encode()).hexdigest(),
    "sha256": lambda s: __import__("hashlib").sha256(s.encode()).hexdigest(),
    "sha512": lambda s: __import__("hashlib").sha512(s.encode()).hexdigest(),
    "to_hex": lambda s: s.encode().hex().upper(),
    "from_hex": _null_on_error(
        lambda s: bytes.fromhex(s).decode("utf-8", "replace")
    ),
    "to_base64": lambda s: __import__("base64").b64encode(s.encode()).decode(),
    "from_base64": _null_on_error(
        lambda s: __import__("base64").b64decode(s, validate=True).decode(
            "utf-8", "replace"
        )
    ),
    "normalize": lambda s, form="NFC": __import__("unicodedata").normalize(
        str(form).upper(), s
    ),
    "url_decode": lambda s: __import__("urllib.parse", fromlist=["unquote"]).unquote(s),
    "soundex": lambda s: _soundex(s),
    "word_stem": lambda s, lang="en": _word_stem(s),
    "to_utf8": lambda s: s.encode().hex(),   # varbinary-as-hex (documented)
    "from_utf8": _null_on_error(lambda s: bytes.fromhex(s).decode("utf-8", "replace")),
    "xxhash64": lambda s: format(_xxhash64(s.encode()), "016x"),
    "murmur3": lambda s: _murmur3_128_hex(s.encode()),
    "hmac_md5": lambda s, key: __import__("hmac").new(
        str(key).encode(), s.encode(), "md5"
    ).hexdigest(),
    "hmac_sha1": lambda s, key: __import__("hmac").new(
        str(key).encode(), s.encode(), "sha1"
    ).hexdigest(),
    "hmac_sha256": lambda s, key: __import__("hmac").new(
        str(key).encode(), s.encode(), "sha256"
    ).hexdigest(),
    "hmac_sha512": lambda s, key: __import__("hmac").new(
        str(key).encode(), s.encode(), "sha512"
    ).hexdigest(),
    "json_value": _json_extract_scalar,
    "json_extract": _json_extract,
    "json_extract_scalar": _json_extract_scalar,
    "json_parse": _json_parse,
    "json_format": _json_parse,  # canonical re-rendering
    "json_array_get": _json_array_get,
    "concat": None,   # specialized (product-dictionary LUT)
    "length": None,   # specialized
    "char_length": None,       # length alias
    "character_length": None,  # length alias
    "strpos": None,   # specialized
    "ends_with": None,         # LUT (const suffix)
    "strrpos": None,           # LUT (const needle)
    "from_base": None,         # LUT (const radix)
    "date_parse": None,        # LUT (const mysql format) -> timestamp
    "parse_datetime": None,    # LUT (const joda format) -> timestamp
    "from_iso8601_timestamp": None,  # LUT -> timestamp
    "parse_duration": None,    # LUT -> interval micros
    "json_exists": None,       # boolean LUT (const path)
    "is_json_scalar": None,    # boolean LUT
    "split": None,             # array LUT (const delimiter)
    "regexp_split": None,      # array LUT (const pattern)
    "regexp_extract_all": None,  # array LUT (const pattern)
    "json_query": _json_extract,
    "codepoint": None,  # specialized (bigint LUT)
    "levenshtein_distance": None,  # specialized (bigint LUT, const 2nd arg)
    "hamming_distance": None,  # specialized (bigint LUT, const 2nd arg)
    "starts_with": None,  # specialized
    "regexp_like": None,  # specialized (boolean LUT)
    "json_array_length": None,  # specialized (bigint LUT)
    "json_size": None,  # specialized (bigint LUT)
    "json_array_contains": None,  # specialized (boolean LUT)
    "regexp_count": None,   # specialized (generic string->int LUT)
    "regexp_position": None,  # specialized
    "crc32": None,          # specialized
    "luhn_check": None,     # specialized
    "from_iso8601_date": None,  # specialized
}


def _luhn_check(s: str) -> bool:
    digits = [int(c) for c in s if c.isdigit()]
    if len(digits) != len(s) or not digits:
        raise ValueError("non-digit input")
    total = 0
    for i, dgt in enumerate(reversed(digits)):
        if i % 2 == 1:
            dgt *= 2
            if dgt > 9:
                dgt -= 9
        total += dgt
    return total % 10 == 0


def _soundex(s: str) -> str:
    """American Soundex (ref: operator/scalar/StringFunctions soundex)."""
    codes = {
        **dict.fromkeys("BFPV", "1"), **dict.fromkeys("CGJKQSXZ", "2"),
        **dict.fromkeys("DT", "3"), "L": "4", **dict.fromkeys("MN", "5"),
        "R": "6",
    }
    u = [c for c in s.upper() if c.isalpha()]
    if not u:
        return ""
    out = [u[0]]
    prev = codes.get(u[0], "")
    for c in u[1:]:
        code = codes.get(c, "")
        if code and code != prev:
            out.append(code)
        if c not in "HW":
            prev = code
        if len(out) == 4:
            break
    return "".join(out).ljust(4, "0")


def _word_stem(s: str) -> str:
    """Light English suffix stripper (deviation: the reference embeds the
    full Porter stemmer via Lucene; this covers the common inflections)."""
    w = s.lower()
    for suf, repl in (
        ("ies", "y"), ("sses", "ss"), ("ing", ""), ("edly", ""), ("ed", ""),
        ("ly", ""), ("es", ""), ("s", ""),
    ):
        if w.endswith(suf) and len(w) - len(suf) >= 2:
            return w[: len(w) - len(suf)] + repl
    return w


def _xxhash64(data: bytes, seed: int = 0) -> int:
    """Pure-python XXH64 (public algorithm; ref uses airlift XxHash64)."""
    P1, P2, P3, P4, P5 = (
        0x9E3779B185EBCA87, 0xC2B2AE3D27D4EB4F, 0x165667B19E3779F9,
        0x85EBCA77C2B2AE63, 0x27D4EB2F165667C5,
    )
    M = (1 << 64) - 1

    def rotl(x, r):
        return ((x << r) | (x >> (64 - r))) & M

    n = len(data)
    i = 0
    if n >= 32:
        v1 = (seed + P1 + P2) & M
        v2 = (seed + P2) & M
        v3 = seed & M
        v4 = (seed - P1) & M
        while i <= n - 32:
            v1 = (rotl((v1 + int.from_bytes(data[i:i+8], "little") * P2) & M, 31) * P1) & M
            v2 = (rotl((v2 + int.from_bytes(data[i+8:i+16], "little") * P2) & M, 31) * P1) & M
            v3 = (rotl((v3 + int.from_bytes(data[i+16:i+24], "little") * P2) & M, 31) * P1) & M
            v4 = (rotl((v4 + int.from_bytes(data[i+24:i+32], "little") * P2) & M, 31) * P1) & M
            i += 32
        h = (rotl(v1, 1) + rotl(v2, 7) + rotl(v3, 12) + rotl(v4, 18)) & M
        for v in (v1, v2, v3, v4):
            h = ((h ^ (rotl((v * P2) & M, 31) * P1) & M) * P1 + P4) & M
    else:
        h = (seed + P5) & M
    h = (h + n) & M
    while i <= n - 8:
        h = (rotl(h ^ ((rotl((int.from_bytes(data[i:i+8], "little") * P2) & M, 31) * P1) & M), 27) * P1 + P4) & M
        i += 8
    if i <= n - 4:
        h = (rotl(h ^ (int.from_bytes(data[i:i+4], "little") * P1) & M, 23) * P2 + P3) & M
        i += 4
    while i < n:
        h = (rotl(h ^ (data[i] * P5) & M, 11) * P1) & M
        i += 1
    h ^= h >> 33
    h = (h * P2) & M
    h ^= h >> 29
    h = (h * P3) & M
    h ^= h >> 32
    return h


def _murmur3_128_hex(data: bytes, seed: int = 0) -> str:
    """MurmurHash3 x64_128 (public algorithm; ref io.airlift.slice.Murmur3)."""
    M = (1 << 64) - 1
    c1, c2 = 0x87C37B91114253D5, 0x4CF5AD432745937F

    def rotl(x, r):
        return ((x << r) | (x >> (64 - r))) & M

    def fmix(k):
        k ^= k >> 33
        k = (k * 0xFF51AFD7ED558CCD) & M
        k ^= k >> 33
        k = (k * 0xC4CEB9FE1A85EC53) & M
        k ^= k >> 33
        return k

    h1 = h2 = seed
    n = len(data)
    nblocks = n // 16
    for b in range(nblocks):
        k1 = int.from_bytes(data[b*16:b*16+8], "little")
        k2 = int.from_bytes(data[b*16+8:b*16+16], "little")
        k1 = (rotl((k1 * c1) & M, 31) * c2) & M
        h1 = ((rotl(h1 ^ k1, 27) + h2) * 5 + 0x52DCE729) & M
        k2 = (rotl((k2 * c2) & M, 33) * c1) & M
        h2 = ((rotl(h2 ^ k2, 31) + h1) * 5 + 0x38495AB5) & M
    tail = data[nblocks*16:]
    k1 = k2 = 0
    for j in range(len(tail) - 1, 7, -1):
        k2 |= tail[j] << ((j - 8) * 8)
    for j in range(min(len(tail), 8) - 1, -1, -1):
        k1 |= tail[j] << (j * 8)
    if len(tail) > 8:
        k2 = (rotl((k2 * c2) & M, 33) * c1) & M
        h2 ^= k2
    if len(tail) > 0:
        k1 = (rotl((k1 * c1) & M, 31) * c2) & M
        h1 ^= k1
    h1 ^= n
    h2 ^= n
    h1 = (h1 + h2) & M
    h2 = (h2 + h1) & M
    h1 = fmix(h1)
    h2 = fmix(h2)
    h1 = (h1 + h2) & M
    h2 = (h2 + h1) & M
    return h1.to_bytes(8, "little").hex() + h2.to_bytes(8, "little").hex()


_MYSQL_TO_STRPTIME = {
    "%i": "%M", "%s": "%S", "%h": "%I", "%r": "%I:%M:%S %p", "%T": "%H:%M:%S",
    "%e": "%d", "%c": "%m",
}

_JODA_TO_STRPTIME = [
    ("yyyy", "%Y"), ("yy", "%y"), ("MM", "%m"), ("dd", "%d"), ("HH", "%H"),
    ("hh", "%I"), ("mm", "%M"), ("ss", "%S"), ("SSS", "%f"), ("a", "%p"),
]


def _mysql_format(fmt: str) -> str:
    for k, v in _MYSQL_TO_STRPTIME.items():
        fmt = fmt.replace(k, v)
    return fmt


def _joda_format(fmt: str) -> str:
    for k, v in _JODA_TO_STRPTIME:
        fmt = fmt.replace(k, v)
    return fmt


def _strptime_micros(s: str, fmt: str) -> int:
    import datetime as _dt

    d = _dt.datetime.strptime(s, fmt)
    return (d - _dt.datetime(1970, 1, 1)) // _dt.timedelta(microseconds=1)


_DURATION_UNITS = {
    "ns": 1e-3, "us": 1.0, "ms": 1e3, "s": 1e6, "m": 60e6, "h": 3600e6,
    "d": 86400e6,
}


def _parse_duration_micros(s: str) -> int:
    m = re.fullmatch(r"\s*([\d.]+)\s*(ns|us|ms|s|m|h|d)\s*", s)
    if not m:
        raise ValueError(f"bad duration: {s!r}")
    return int(float(m.group(1)) * _DURATION_UNITS[m.group(2)])


def _iso_timestamp_micros(s: str) -> int:
    import datetime as _dt

    d = _dt.datetime.fromisoformat(s)
    if d.tzinfo is not None:
        d = d.astimezone(_dt.timezone.utc).replace(tzinfo=None)
    return (d - _dt.datetime(1970, 1, 1)) // _dt.timedelta(microseconds=1)


def _is_json_scalar(s: str) -> bool:
    import json as _json

    try:
        v = _json.loads(s)
    except (ValueError, TypeError):
        raise ValueError("not json")
    return not isinstance(v, (dict, list))


# string -> array<varchar> dictionary LUTs (trailing args constant)
_STRING_ARRAY_LUTS: Dict[str, Callable] = {
    "split": lambda s, delim, limit=None: (
        s.split(delim, int(limit) - 1) if limit is not None else s.split(delim)
    )
    if delim
    else [s],
    "regexp_split": lambda s, pattern: re.split(pattern, s),
    "regexp_extract_all": lambda s, pattern, group=0: [
        m.group(int(group)) for m in re.finditer(pattern, s)
    ],
}

# string -> numeric/boolean dictionary LUTs (trailing args constant);
# per-value exceptions become NULL
_STRING_INT_LUTS: Dict[str, tuple] = {
    "ends_with": (lambda s, suffix: s.endswith(suffix), np.bool_),
    "strrpos": (lambda s, sub: s.rfind(sub) + 1, np.int64),
    "from_base": (lambda s, radix: int(s, int(radix)), np.int64),
    "date_parse": (
        lambda s, fmt: _strptime_micros(s, _mysql_format(fmt)), np.int64
    ),
    "parse_datetime": (
        lambda s, fmt: _strptime_micros(s, _joda_format(fmt)), np.int64
    ),
    "from_iso8601_timestamp": (_iso_timestamp_micros, np.int64),
    "parse_duration": (_parse_duration_micros, np.int64),
    "json_exists": (
        lambda s, path: _json_eval_exists(s, path), np.bool_
    ),
    "is_json_scalar": (_is_json_scalar, np.bool_),
    "regexp_count": (lambda s, pat: len(re.findall(pat, s)), np.int64),
    "regexp_position": (
        lambda s, pat: (lambda m: m.start() + 1 if m else -1)(re.search(pat, s)),
        np.int64,
    ),
    "crc32": (lambda s: __import__("zlib").crc32(s.encode()), np.int64),
    "luhn_check": (_luhn_check, np.bool_),
    "from_iso8601_date": (
        lambda s: (
            __import__("datetime").date.fromisoformat(s)
            - __import__("datetime").date(1970, 1, 1)
        ).days,
        np.int64,
    ),
}


def _like_to_regex(pattern: str, escape: Optional[str] = None) -> "re.Pattern":
    """SQL LIKE -> compiled regex (ref: io.trino.likematcher; ours runs on the
    host over dictionary values, so a plain regex engine is plenty)."""
    out = []
    i = 0
    while i < len(pattern):
        ch = pattern[i]
        if escape and ch == escape and i + 1 < len(pattern):
            out.append(re.escape(pattern[i + 1]))
            i += 2
            continue
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
        i += 1
    return re.compile("".join(out), re.DOTALL)
