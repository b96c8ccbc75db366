"""Columnar Page/Column substrate — the device-resident analogue of Trino Pages.

Reference blueprint: core/trino-spi/src/main/java/io/trino/spi/Page.java:31 and the
Block hierarchy under spi/block/ (SURVEY.md §2.1). A Trino Page is an ordered list
of Blocks plus a positionCount; a Block is one of 12 physical layouts with validity
("null") masks and dictionary/RLE wrappers.

TPU-first redesign (not a port):

- A :class:`Column` is a fixed-capacity device array (``data``) + a boolean validity
  mask (``valid``). Null handling is mask-based everywhere — there is no sentinel.
- A :class:`Page` is a tuple of equal-capacity Columns plus an ``active`` row mask.
  Because XLA requires static shapes, *filtering never compacts*: a Filter operator
  just ANDs into ``active`` (SURVEY.md §7 "pad-and-mask everywhere; the kernels must
  be oblivious to logical length"). Compaction happens only at exchange boundaries
  and at host materialization.
- VARCHAR columns carry a host-side **sorted dictionary** (strings never touch the
  device); the device sees int32 codes. Sorted means code order == string order, so
  range predicates run on codes. This plays the role of Trino's DictionaryBlock
  (spi/block/DictionaryBlock.java) but as a global, per-column property.
- Pages are JAX pytrees: they flow through jit/shard_map directly, and a Page's
  ``layout()`` (types + capacity) is the compilation cache key, exactly as Trino's
  PageFunctionCompiler caches per (expression, block layout).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .types import Type, DecimalType, VarcharType


class Dictionary:
    """Host-side sorted string dictionary shared by a VARCHAR column.

    Identity-hashed so it can ride in jit static aux data without content hashing;
    connectors create one Dictionary per column at ingest and reuse it, so the jit
    cache stays warm across splits.
    """

    __slots__ = ("values", "_lookup", "_fp", "_value_keys", "_host_bytes")

    def __init__(self, values: np.ndarray):
        # values must be sorted and unique for code-order == string-order.
        self.values = np.asarray(values, dtype=object)
        self._lookup: Optional[dict] = None
        self._fp: Optional[int] = None
        self._value_keys: Optional[np.ndarray] = None
        # memoized host size (runtime.memory.page_bytes): dictionaries are
        # immutable and shared across pages, so sizing sweeps once
        self._host_bytes: Optional[int] = None

    @staticmethod
    def from_strings(strings: Iterable[str]) -> "Dictionary":
        uniq = sorted(set(strings))
        return Dictionary(np.asarray(uniq, dtype=object))

    _empty: Optional["Dictionary"] = None

    @classmethod
    def empty(cls) -> "Dictionary":
        """THE dictionary for zero-row string columns (empty table-scan
        partitions, empty exchange inputs): one "" sentinel value so every
        dictionary-driven compile path (LIKE LUTs, comparison code lookup)
        stays well-formed — a zero-value dictionary breaks the LUT gather.
        All rows of such pages are inactive, so the sentinel never surfaces.
        A process-wide singleton: identity-hashed jit static aux stays warm
        across empty partitions."""
        if cls._empty is None:
            cls._empty = Dictionary(np.asarray([""], dtype=object))
        return cls._empty

    def __len__(self) -> int:
        return len(self.values)

    def code_of(self, s: str) -> int:
        """Exact-match code, or -1 if absent."""
        if self._lookup is None:
            self._lookup = {v: i for i, v in enumerate(self.values)}
        return self._lookup.get(s, -1)

    def searchsorted(self, s: str, side: str = "left") -> int:
        lo, hi = 0, len(self.values)
        while lo < hi:
            mid = (lo + hi) // 2
            v = self.values[mid]
            if v < s or (side == "right" and v == s):
                lo = mid + 1
            else:
                hi = mid
        return lo

    def decode(self, codes: np.ndarray) -> np.ndarray:
        out = np.empty(len(codes), dtype=object)
        in_range = (codes >= 0) & (codes < len(self.values))
        out[in_range] = self.values[codes[in_range]]
        out[~in_range] = None
        return out

    def fingerprint(self) -> int:
        """Content fingerprint (cached): equal vocabularies compare equal even
        across deserialized copies — identity (__eq__/__hash__) stays object-
        based so jit static-aux caching is untouched."""
        if self._fp is None:
            import hashlib

            h = hashlib.blake2b(digest_size=8)
            for v in self.values:
                h.update(str(v).encode())
                h.update(b"\x00")
            self._fp = int.from_bytes(h.digest(), "little", signed=True)
        return self._fp

    def value_keys(self) -> np.ndarray:
        """code -> content-stable int64 key (cached LUT). Lets repartition
        hashing of dictionary columns be consistent across producers whose
        dictionaries differ (codes are only comparable within one dictionary)."""
        if self._value_keys is None:
            import hashlib

            lut = np.empty(len(self.values), dtype=np.int64)
            for i, s in enumerate(self.values):
                d = hashlib.blake2b(str(s).encode(), digest_size=8).digest()
                lut[i] = int.from_bytes(d, "little", signed=True)
            self._value_keys = lut
        return self._value_keys

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other

    def __repr__(self):  # pragma: no cover
        return f"Dictionary(n={len(self.values)})"


@jax.tree_util.register_pytree_node_class
@dataclass
class Column:
    """One column: device data + validity mask + SQL type (+ host dictionary).

    Nested layouts (ref spi/block/ArrayBlock.java, MapBlock.java, RowBlock.java —
    offset-based there; pad-and-mask here, see types.ArrayType):

    - ARRAY:  ``data[cap, W]`` + ``elem_valid[cap, W]`` + ``lengths[cap]``
      (positions 0..len-1 exist; elem_valid marks non-null among them)
    - MAP:    ``children == (keys, values)`` — two array-layout Columns with a
      shared length; parent ``data`` is a dummy int8 lane
    - ROW:    ``children`` holds one scalar-layout Column per field
    """

    type: Type
    data: jnp.ndarray
    valid: jnp.ndarray
    dictionary: Optional[Dictionary] = None
    lengths: Optional[jnp.ndarray] = None  # [cap] int32 (array/map)
    elem_valid: Optional[jnp.ndarray] = None  # [cap, W] (array)
    children: tuple = ()  # nested Columns (map: keys/values; row: fields)

    def tree_flatten(self):
        return (
            (self.data, self.valid, self.lengths, self.elem_valid, self.children),
            (self.type, self.dictionary),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        t, d = aux
        data, valid, lengths, elem_valid, kids = children
        return cls(
            type=t, data=data, valid=valid, dictionary=d,
            lengths=lengths, elem_valid=elem_valid, children=tuple(kids),
        )

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    @staticmethod
    def from_numpy(
        type_: Type,
        values: np.ndarray,
        valid: Optional[np.ndarray] = None,
        capacity: Optional[int] = None,
        dictionary: Optional[Dictionary] = None,
    ) -> "Column":
        values = np.asarray(values)
        n = len(values)
        cap = capacity if capacity is not None else n
        dtype = type_.storage_dtype
        # multi-lane storage (long decimals: (n, 2) int64 limbs) pads on axis 0
        data = np.zeros((cap,) + tuple(values.shape[1:]), dtype=dtype)
        data[:n] = values.astype(dtype, copy=False)
        v = np.zeros(cap, dtype=np.bool_)
        v[:n] = True if valid is None else np.asarray(valid, dtype=np.bool_)
        return Column(type_, jnp.asarray(data), jnp.asarray(v), dictionary)

    @staticmethod
    def from_strings(
        strings: Sequence[Optional[str]],
        type_: Type = None,
        capacity: Optional[int] = None,
        dictionary: Optional[Dictionary] = None,
    ) -> "Column":
        type_ = type_ or VarcharType()
        present = [s for s in strings if s is not None]
        d = dictionary or Dictionary.from_strings(present)
        codes = np.array([d.code_of(s) if s is not None else 0 for s in strings], dtype=np.int32)
        if dictionary is not None and np.any(codes < 0):
            missing = sorted({s for s in present if d.code_of(s) < 0})
            raise ValueError(f"strings absent from supplied dictionary: {missing[:5]}")
        valid = np.array([s is not None for s in strings], dtype=np.bool_)
        return Column.from_numpy(type_, codes, valid, capacity, dictionary=d)

    @staticmethod
    def from_nested(
        type_: Type,
        values: Sequence,
        capacity: Optional[int] = None,
        width: Optional[int] = None,
    ) -> "Column":
        """Build a nested (array/map/row) column from python values (host path,
        used by connectors/tests; the hot paths construct device layouts
        directly)."""
        from .types import ArrayType, MapType, RowType

        n = len(values)
        cap = capacity if capacity is not None else n
        valid = np.array([v is not None for v in values], dtype=np.bool_)
        valid = np.concatenate([valid, np.zeros(cap - n, dtype=np.bool_)])
        if isinstance(type_, ArrayType):
            lists = [list(v) if v is not None else [] for v in values]
            w = width if width is not None else max([len(x) for x in lists] + [1])
            lengths = np.zeros(cap, dtype=np.int32)
            lengths[:n] = [min(len(x), w) for x in lists]
            ev = np.zeros((cap, w), dtype=np.bool_)
            flat = [x[j] if j < len(x) else None for x in lists for j in range(w)]
            for i, x in enumerate(lists):
                for j, e in enumerate(x[:w]):
                    ev[i, j] = e is not None
            if isinstance(type_.element, (ArrayType, MapType, RowType)):
                # nested element: keep a flattened [cap*w] child column and a
                # dummy parent lane (decode reshapes the child back)
                flat += [None] * ((cap - n) * w)
                child = Column.from_nested(type_.element, flat, capacity=cap * w)
                return Column(
                    type_, jnp.zeros((cap, w), dtype=jnp.int8), jnp.asarray(valid),
                    lengths=jnp.asarray(lengths), elem_valid=jnp.asarray(ev),
                    children=(child,),
                )
            ecol = _scalar_from_pylist(type_.element, flat)
            data = np.asarray(ecol.data).reshape(n, w)
            if cap > n:
                data = np.concatenate([data, np.zeros((cap - n, w), dtype=data.dtype)])
            return Column(
                type_, jnp.asarray(data), jnp.asarray(valid), ecol.dictionary,
                lengths=jnp.asarray(lengths), elem_valid=jnp.asarray(ev),
            )
        if isinstance(type_, MapType):
            keys = [list(v.keys()) if v is not None else None for v in values]
            vals = [list(v.values()) if v is not None else None for v in values]
            w = width if width is not None else max(
                [len(k) for k in keys if k is not None] + [1]
            )
            kcol = Column.from_nested(ArrayType(element=type_.key), keys, cap, w)
            vcol = Column.from_nested(ArrayType(element=type_.value), vals, cap, w)
            return Column(
                type_, jnp.zeros(cap, dtype=jnp.int8), jnp.asarray(valid),
                lengths=kcol.lengths, children=(kcol, vcol),
            )
        if isinstance(type_, RowType):
            kids = []
            for i, (_, ft) in enumerate(type_.fields):
                fvals = [v[i] if v is not None else None for v in values]
                kids.append(
                    Column.from_nested(ft, fvals, cap)
                    if isinstance(ft, (ArrayType, MapType, RowType))
                    else _scalar_from_pylist(ft, fvals, cap)
                )
            return Column(
                type_, jnp.zeros(cap, dtype=jnp.int8), jnp.asarray(valid),
                children=tuple(kids),
            )
        return _scalar_from_pylist(type_, list(values), cap)

    def to_numpy(self, active: Optional[np.ndarray] = None) -> np.ndarray:
        """Materialize to host as an object-free array; nulls -> masked separately."""
        data = np.asarray(self.data)
        if active is not None:
            data = data[active]
        return data

    def decode(self, active: Optional[np.ndarray] = None) -> np.ndarray:
        """Host materialization into python-visible values (objects), nulls as None.

        Note: short decimals decode to float where float division is exact
        (every scaled magnitude of the column below 2**53) and to Decimal
        where it is not; long decimals always decode to Decimal.
        """
        from .types import ArrayType, MapType, RowType

        data = np.asarray(self.data)
        valid = np.asarray(self.valid)
        if active is not None:
            data, valid = data[active], valid[active]
        if isinstance(self.type, ArrayType):
            ev = np.asarray(self.elem_valid)
            lengths = np.asarray(self.lengths)
            if self.children:
                # nested element: children[0] is the flattened [cap*w] column
                cap, w = ev.shape
                elems = self.children[0].decode(None).reshape(cap, w)
                if active is not None:
                    ev, lengths = ev[active], lengths[active]
                    elems = elems[active]
                out = np.empty(len(lengths), dtype=object)
                for i in range(len(lengths)):
                    out[i] = list(elems[i, : lengths[i]]) if valid[i] else None
                return out
            if active is not None:
                ev, lengths = ev[active], lengths[active]
            n, w = data.shape
            flat = Column(self.type.element, data.reshape(-1), ev.reshape(-1),
                          self.dictionary).decode(None)
            elems = flat.reshape(n, w)
            out = np.empty(n, dtype=object)
            for i in range(n):
                out[i] = list(elems[i, : lengths[i]]) if valid[i] else None
            return out
        if isinstance(self.type, MapType):
            keys = self.children[0].decode(active)
            vals = self.children[1].decode(active)
            out = np.empty(len(keys), dtype=object)
            for i in range(len(keys)):
                out[i] = (
                    dict(zip(keys[i], vals[i]))
                    if valid[i] and keys[i] is not None
                    else None
                )
            return out
        if isinstance(self.type, RowType):
            fields = [c.decode(active) for c in self.children]
            out = np.empty(len(valid), dtype=object)
            for i in range(len(valid)):
                out[i] = tuple(f[i] for f in fields) if valid[i] else None
            return out
        if self.dictionary is not None:
            out = self.dictionary.decode(data.astype(np.int64))
            out[~valid] = None
            return out
        if self.type.name in ("tdigest", "qdigest"):
            # summary repr (the digest is queried via value_at_quantile;
            # Trino renders an opaque varbinary here)
            out = np.empty(len(data), dtype=object)
            kc = data.shape[1] // 2
            for i, ok in enumerate(valid.tolist()):
                out[i] = (
                    f"{self.type.name}[n={int(data[i, kc:].sum())}]"
                    if ok
                    else None
                )
            return out
        if isinstance(self.type, DecimalType) and self.type.precision > 18:
            # Int128 limbs -> exact python ints; Decimal output (floats would
            # silently destroy the precision that is the type's whole point)
            import decimal as _d

            from ..ops.int128 import np_to_ints

            ints = np_to_ints(data)
            signed = [(x + 2**127) % 2**128 - 2**127 for x in ints]
            out = np.empty(len(data), dtype=object)
            sc = self.type.scale
            for i, (x, ok) in enumerate(zip(signed, valid.tolist())):
                # tuple construction is context-exact (Decimal arithmetic
                # would round to the ambient 28-digit context precision)
                sign = 1 if x < 0 else 0
                digits = tuple(int(ch) for ch in str(abs(x)))
                out[i] = _d.Decimal((sign, digits, -sc)) if ok else None
            return out
        if isinstance(self.type, DecimalType) and self.type.scale > 0:
            out = np.empty(len(data), dtype=object)
            if np.abs(data[valid].astype(np.float64)).max(initial=0.0) >= 2.0**53:
                # float division would round here (a TPC-H q1 sum_charge
                # passes 2**53 at SF1), so the column decodes as long
                # decimals do
                import decimal as _d

                sc = self.type.scale
                convert = lambda x: _d.Decimal(x).scaleb(-sc)  # noqa: E731
            else:
                scale = 10 ** self.type.scale
                convert = lambda x: x / scale  # noqa: E731
            for i, (x, ok) in enumerate(zip(data.tolist(), valid.tolist())):
                out[i] = convert(x) if ok else None
            return out
        if self.type.name == "date":
            import datetime

            epoch = datetime.date(1970, 1, 1)
            out = np.empty(len(data), dtype=object)
            for i, (x, ok) in enumerate(zip(data.tolist(), valid.tolist())):
                out[i] = (epoch + datetime.timedelta(days=x)) if ok else None
            return out
        if self.type.name == "timestamp":
            import datetime

            out = np.empty(len(data), dtype=object)
            for i, (x, ok) in enumerate(zip(data.tolist(), valid.tolist())):
                out[i] = (
                    datetime.datetime(1970, 1, 1) + datetime.timedelta(microseconds=x)
                ) if ok else None
            return out
        if self.type.name == "time":
            import datetime

            out = np.empty(len(data), dtype=object)
            for i, (x, ok) in enumerate(zip(data.tolist(), valid.tolist())):
                if not ok:
                    out[i] = None
                    continue
                s, us = divmod(int(x), 1_000_000)
                h, rem = divmod(s, 3600)
                m, sec = divmod(rem, 60)
                out[i] = datetime.time(h % 24, m, sec, us)
            return out
        if self.type.name == "time with time zone":
            import datetime

            from .types import twtz_unpack

            out = np.empty(len(data), dtype=object)
            for i, (x, ok) in enumerate(zip(data.tolist(), valid.tolist())):
                if not ok:
                    out[i] = None
                    continue
                local, off = twtz_unpack(int(x))
                sec, us = divmod(local, 1_000_000)
                h, rem = divmod(int(sec), 3600)
                m, sc = divmod(rem, 60)
                tz = datetime.timezone(datetime.timedelta(minutes=off))
                out[i] = datetime.time(h % 24, m, sc, int(us), tzinfo=tz)
            return out
        if self.type.name == "timestamp with time zone":
            import datetime

            out = np.empty(len(data), dtype=object)
            for i, (x, ok) in enumerate(zip(data.tolist(), valid.tolist())):
                if not ok:
                    out[i] = None
                    continue
                millis = int(x) >> 12
                off = (int(x) & 0xFFF) - 841
                tz = datetime.timezone(datetime.timedelta(minutes=off))
                out[i] = datetime.datetime.fromtimestamp(
                    millis / 1000, tz=datetime.timezone.utc
                ).astimezone(tz)
            return out
        out = np.empty(len(data), dtype=object)
        lst = data.tolist()
        for i, ok in enumerate(valid.tolist()):
            out[i] = lst[i] if ok else None
        return out


@jax.tree_util.register_pytree_node_class
@dataclass
class Page:
    """A batch of rows: equal-capacity columns + an ``active`` row mask.

    ``active[i]`` means row i logically exists (it is both within the split's row
    count and has survived every filter so far). ref: spi/Page.java:31
    ``getPositionCount`` maps to ``num_rows()`` (a traced reduction, not static).
    """

    columns: tuple
    active: jnp.ndarray

    def tree_flatten(self):
        return (self.columns, self.active), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        cols, active = children
        return cls(columns=tuple(cols), active=active)

    @property
    def capacity(self) -> int:
        return int(self.active.shape[0])

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def num_rows(self) -> jnp.ndarray:
        return jnp.sum(self.active.astype(jnp.int32))

    def column(self, i: int) -> Column:
        return self.columns[i]

    def layout(self) -> tuple:
        """Static compilation cache key (types + dictionaries + shapes —
        nested columns' element width W is part of the physical layout)."""
        return (
            tuple(_column_layout(c) for c in self.columns),
            self.capacity,
        )

    def with_columns(self, columns: Sequence[Column]) -> "Page":
        return Page(tuple(columns), self.active)

    def append_column(self, col: Column) -> "Page":
        # ref: spi/Page.java:160 appendColumn
        return Page(self.columns + (col,), self.active)

    def mask(self, keep: jnp.ndarray) -> "Page":
        """Filter: AND into the active mask (no compaction — static shapes)."""
        return Page(self.columns, self.active & keep)

    @staticmethod
    def from_arrays(
        types: Sequence[Type],
        arrays: Sequence[np.ndarray],
        valids: Optional[Sequence[Optional[np.ndarray]]] = None,
        dictionaries: Optional[Sequence[Optional[Dictionary]]] = None,
        capacity: Optional[int] = None,
    ) -> "Page":
        n = len(arrays[0]) if arrays else 0
        if len(types) != len(arrays):
            raise ValueError(f"{len(types)} types but {len(arrays)} arrays")
        if any(len(a) != n for a in arrays):
            raise ValueError(f"unequal column lengths: {[len(a) for a in arrays]}")
        cap = capacity if capacity is not None else n
        valids = valids or [None] * len(arrays)
        dictionaries = dictionaries or [None] * len(arrays)
        cols = tuple(
            Column.from_numpy(t, a, v, cap, d)
            for t, a, v, d in zip(types, arrays, valids, dictionaries)
        )
        active = np.zeros(cap, dtype=np.bool_)
        active[:n] = True
        return Page(cols, jnp.asarray(active))

    def to_pylist(self) -> list:
        """Host materialization: list of row tuples in storage order (active only)."""
        active = np.asarray(self.active)
        if not self.columns:
            return [()] * int(active.sum())
        # each column decodes to a 1-D array of python objects: zip their lists
        return list(zip(*(c.decode(active).tolist() for c in self.columns)))


def _column_layout(c: Column) -> tuple:
    kids = tuple(_column_layout(k) for k in c.children)
    return (c.type, c.dictionary, tuple(c.data.shape), kids)


def _scalar_from_pylist(
    type_: Type, values: Sequence, capacity: Optional[int] = None
) -> Column:
    """Python scalars -> a scalar-layout Column (strings dictionary-encode,
    decimals scale, dates/timestamps convert to epoch units)."""
    import datetime

    from .types import DecimalType as _Dec

    n = len(values)
    cap = capacity if capacity is not None else n
    if type_.name in ("varchar", "char"):
        return Column.from_strings(list(values) + [None] * (cap - n), type_)
    valid = np.array([v is not None for v in values] + [False] * (cap - n), np.bool_)
    if isinstance(type_, _Dec) and type_.precision > 18:
        import decimal as _d

        from ..ops.int128 import np_from_ints

        with _d.localcontext() as ctx:
            # default context rounds at 28 significant digits — exactly the
            # values this type exists for; widen before scaling
            ctx.prec = 60
            scaled = [
                int(_d.Decimal(str(v)).scaleb(type_.scale).to_integral_value())
                if v is not None
                else 0
                for v in values
            ] + [0] * (cap - n)
        return Column(type_, jnp.asarray(np_from_ints(scaled)), jnp.asarray(valid))
    conv = np.zeros(cap, dtype=type_.storage_dtype)
    for i, v in enumerate(values):
        if v is None:
            continue
        if isinstance(type_, _Dec):
            conv[i] = round(float(v) * 10**type_.scale)
        elif type_.name == "date":
            d = v if isinstance(v, datetime.date) else datetime.date.fromisoformat(v)
            conv[i] = (d - datetime.date(1970, 1, 1)).days
        elif type_.name == "timestamp":
            ts = (
                v
                if isinstance(v, datetime.datetime)
                else datetime.datetime.fromisoformat(v)
            )
            conv[i] = round((ts - datetime.datetime(1970, 1, 1)).total_seconds() * 1e6)
        else:
            conv[i] = v
    return Column(type_, jnp.asarray(conv), jnp.asarray(valid))


def capacity_class(rows: int) -> int:
    """The capacity a stored page of ``rows`` rows takes: a power of two from
    64 up to 2^20, a multiple of 2^20 above it. Pages of different tables then
    share shapes, so a compiled operator program is a cache hit, and a large
    table is not padded to twice its rows."""
    cap = 64
    while cap < rows and cap < (1 << 20):
        cap *= 2
    if cap < rows:
        cap = -(-rows // (1 << 20)) << 20
    return cap


def compact_indices(active: np.ndarray) -> np.ndarray:
    """Host helper: indices of active rows (used at materialization boundaries)."""
    return np.nonzero(np.asarray(active))[0]
