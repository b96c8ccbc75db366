"""Device cost observability plane: XLA cost-model attribution + roofline.

Every BENCH number since round 5 is CPU-labeled, and per-operator *wall*
time has existed since rounds 2/17 — but the engine could not say what a
device program COSTS: FLOPs, HBM bytes, and peak device memory were
invisible, so "memory-bound vs compute-bound" was folklore.  "Query
Processing on Tensor Computation Runtimes" (arXiv:2203.01877) is the
measurement playbook this module implements; arXiv:2606.24647 (GPU-Presto)
motivates the roofline framing that makes offload decisions auditable.

Three pieces:

- :func:`jit` — THE engine-wide ``jax.jit`` wrapper.  It is a transparent
  pass-through (same jitted callable, zero extra dispatch work, byte-
  identical results) until a recording scope is installed; then each call
  attributes the program's XLA ``cost_analysis()`` (flops, bytes accessed)
  and compiled ``memory_analysis()`` (argument/output/temp HBM) to the
  scope's plan node.  Records are keyed like the capstore program cache:
  sha256 of (label, plan-node structural fingerprint, platform, abstract
  arg signature) — stable across processes — and persisted as a sibling
  file of ``$TRINO_TPU_CAP_STORE`` so warm processes (whose jit dispatch
  never lowers: the XLA compile cache hit) still attribute without paying
  a re-trace.  The engine lint rule ``jit-without-cost-hook`` pins every
  ``jax.jit`` call site in ``trino_tpu/`` to this wrapper.
- :func:`attributing` — the per-plan-node recording scope the executor's
  stats path installs (EXPLAIN ANALYZE VERBOSE / kernel_cost session
  property).  Scopes nest like operator evaluation does; a program records
  against the INNERMOST open scope.  Calls made while tracing an enclosing
  program (vmapped lanes, traced subplans) are skipped — the enclosing
  program is the one that launches, so it owns the cost.
- Roofline diagnosis — :func:`classify`/:func:`render_roofline` turn
  (flops, bytes, measured device seconds) into the one-line verdict
  EXPLAIN ANALYZE VERBOSE appends per operator::

      flops 1.2G · hbm 890MB · arith 1.3 flop/B → memory-bound,
      72% of roofline @ cpu

  Peak FLOP/s / bytes/s come from the :data:`PEAKS` table keyed by the
  device_kind JAX reports, or from ``$TRINO_TPU_ROOFLINE_PEAKS``
  (``"cpu=5e10:2e10,TPU v5 lite=1.97e14:8.19e11"``); a device kind in
  neither is an error (:func:`roofline_peaks`).

Availability degrades, never raises: ``cost_analysis``/``memory_analysis``
vary by backend and jax version, Pallas interpret-mode programs may expose
neither, and a mesh/shard_map program may refuse to lower standalone — any
such path records a ``cost_unavailable`` row and ticks
``trino_tpu_kernel_cost_unavailable_total{reason}``.

Cluster-wide surface: every attribution lands in a bounded process ledger
behind ``system.runtime.kernel_costs``; with the round-17 federated plane
on, worker announcements piggyback a bounded ledger snapshot
(:func:`announcement_rows`) that the coordinator folds in
(:func:`ingest_federated`), so the system table shows every node's rows.
Paired ``kernel_cost`` flight spans ride the assembled cluster trace, and
each attribution bumps an ``hbm_watermark`` Perfetto counter track on the
recording thread's lane (the device-lane proxy).
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

import jax

from .. import knobs

# --------------------------------------------------------------------------- #
# roofline peaks
# --------------------------------------------------------------------------- #

# (peak FLOP/s, peak bytes/s) keyed by the device_kind JAX reports, each with
# its source. A device that is not here is an error, not a default: a roofline
# share against another chip's peaks is a wrong number.
PEAKS: Dict[str, Tuple[float, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM
    "TPU v5 lite": (1.97e14, 8.19e11),
    # XLA:CPU, the tier-1 test backend: a single-core host-class placeholder.
    # Only the memory- vs compute-bound CLASSIFICATION is read from it, and
    # that follows arithmetic intensity against the ridge point.
    "cpu": (5.0e10, 2.0e10),
}

ENV_PEAKS = "TRINO_TPU_ROOFLINE_PEAKS"


def roofline_peaks(device_kind: str) -> Tuple[float, float, str]:
    """(peak_flops_per_sec, peak_bytes_per_sec, provenance) for a device kind.

    ``$TRINO_TPU_ROOFLINE_PEAKS`` format: ``device_kind=FLOPS:BYTES`` pairs,
    comma-separated — ``"cpu=5e10:2e10,TPU v5 lite=1.97e14:8.19e11"``.
    Unparseable entries are ignored (a typo'd knob does not take down
    EXPLAIN). Provenance is ``"env"`` or ``"table"`` so the output can say
    whether the pct-of-roofline is against a measured peak. A device kind in
    neither raises LookupError.
    """
    spec = knobs.env_str(ENV_PEAKS) or ""
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry or "=" not in entry:
            continue
        name, _, vals = entry.partition("=")
        if name.strip().lower() != device_kind.lower():
            continue
        fl, _, by = vals.partition(":")
        try:
            pf, pb = float(fl), float(by)
        except ValueError:
            continue
        if pf > 0 and pb > 0:
            return pf, pb, "env"
    for name, (pf, pb) in PEAKS.items():
        if name.lower() == device_kind.lower():
            return pf, pb, "table"
    raise LookupError(
        f"no peaks for device kind {device_kind!r}: add it to "
        f"kernelcost.PEAKS with its source, or set ${ENV_PEAKS}"
    )


def classify(
    flops: Optional[float],
    bytes_accessed: Optional[float],
    device_secs: Optional[float] = None,
    device_kind: Optional[str] = None,
) -> Optional[dict]:
    """Roofline verdict for one program (or one operator's aggregate).

    Returns ``None`` when the cost model gave us nothing to classify.
    ``roofline_pct`` is achieved FLOP/s over the roofline-attainable rate at
    this arithmetic intensity — only computable when a measured
    ``device_secs`` is supplied (EXPLAIN's fenced stats mode), ``None``
    otherwise (the honest answer for unmeasured ledger rows).
    """
    if not flops and not bytes_accessed:
        return None
    device_kind = device_kind or jax.devices()[0].device_kind
    peak_flops, peak_bw, provenance = roofline_peaks(device_kind)
    flops = float(flops or 0.0)
    bytes_accessed = float(bytes_accessed or 0.0)
    ai = flops / bytes_accessed if bytes_accessed > 0 else None
    ridge = peak_flops / peak_bw
    if ai is None:
        bound = "compute-bound" if flops else "memory-bound"
    else:
        bound = "memory-bound" if ai < ridge else "compute-bound"
    attainable = (
        min(peak_flops, ai * peak_bw) if ai is not None else peak_flops
    )
    pct = None
    if device_secs and device_secs > 0 and attainable > 0 and flops > 0:
        pct = min((flops / device_secs) / attainable, 1.0)
    return {
        "device_kind": device_kind,
        "arithmetic_intensity": ai,
        "classification": bound,
        "attainable_flops_per_sec": attainable,
        "roofline_pct": pct,
        "peaks_provenance": provenance,
    }


def _si(v: float) -> str:
    for unit, div in (("T", 1e12), ("G", 1e9), ("M", 1e6), ("K", 1e3)):
        if abs(v) >= div:
            return f"{v / div:.3g}{unit}"
    return f"{v:.3g}"


def _bytes_h(v: float) -> str:
    for unit, div in (("GB", 1 << 30), ("MB", 1 << 20), ("KB", 1 << 10)):
        if abs(v) >= div:
            return f"{v / div:.3g}{unit}"
    return f"{v:.0f}B"


def render_roofline(
    flops: Optional[float],
    bytes_accessed: Optional[float],
    peak_hbm_bytes: Optional[float] = None,
    device_secs: Optional[float] = None,
    device_kind: Optional[str] = None,
) -> Optional[str]:
    """The EXPLAIN ANALYZE VERBOSE one-liner. ``None`` when unclassifiable
    (the caller renders ``cost_unavailable`` instead)."""
    verdict = classify(flops, bytes_accessed, device_secs, device_kind)
    if verdict is None:
        return None
    parts = []
    if flops:
        parts.append(f"flops {_si(float(flops))}")
    if bytes_accessed:
        parts.append(f"hbm {_bytes_h(float(bytes_accessed))}")
    if peak_hbm_bytes:
        parts.append(f"peak {_bytes_h(float(peak_hbm_bytes))}")
    ai = verdict["arithmetic_intensity"]
    if ai is not None:
        parts.append(f"arith {ai:.3g} flop/B")
    tail = verdict["classification"]
    if verdict["roofline_pct"] is not None:
        tail += f", {verdict['roofline_pct'] * 100.0:.0f}% of roofline"
    tail += f" @ {verdict['device_kind']}"
    return " · ".join(parts) + " → " + tail


# --------------------------------------------------------------------------- #
# unavailable accounting
# --------------------------------------------------------------------------- #


def _count_unavailable(reason: str) -> None:
    try:
        from .metrics import REGISTRY

        REGISTRY.counter(
            "trino_tpu_kernel_cost_unavailable_total",
            labels={"reason": reason},
            help="kernel-cost attributions degraded to cost_unavailable",
        ).inc()
    except Exception:  # noqa: BLE001 — observability never fails the query
        pass


# --------------------------------------------------------------------------- #
# persisted record store (sibling of the capstore file)
# --------------------------------------------------------------------------- #

_store_lock = threading.Lock()
_record_cache: Dict[str, dict] = {}  # program key -> record (ok records only)
_persisted_cache: Optional[Dict[str, dict]] = None
_persisted_mtime: Optional[float] = None


def store_path() -> Optional[str]:
    """Persisted kernel-cost records live NEXT TO the capstore file (the
    two stores describe the same compiled programs: capstore the shapes,
    this one the costs), so one deployment knob provisions both."""
    from . import capstore

    base = capstore.store_path()
    return base + ".kernelcost" if base else None


def _read_persisted() -> Dict[str, dict]:
    global _persisted_cache, _persisted_mtime
    path = store_path()
    if path is None:
        return {}
    try:
        mtime = os.stat(path).st_mtime
    except OSError:
        return {}
    if _persisted_cache is not None and _persisted_mtime == mtime:
        return _persisted_cache
    try:
        with open(path, "r") as f:
            data = json.load(f)
        if not isinstance(data, dict):
            data = {}
    except (OSError, ValueError):
        data = {}
    _persisted_cache, _persisted_mtime = data, mtime
    return data


def _persist(key: str, record: dict) -> None:
    global _persisted_cache, _persisted_mtime
    path = store_path()
    if path is None:
        return
    with _store_lock:
        data = dict(_read_persisted())
        data[key] = record
        d = os.path.dirname(os.path.abspath(path)) or "."
        try:
            os.makedirs(d, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=d, prefix=".kernelcost-")
            with os.fdopen(fd, "w") as f:
                json.dump(data, f)
            os.replace(tmp, path)
            _persisted_cache = data
            try:
                _persisted_mtime = os.stat(path).st_mtime
            except OSError:
                _persisted_mtime = None
        except OSError:
            pass


def clear_memory() -> None:
    """Test hook: drop the in-process record cache + persisted-file cache."""
    global _persisted_cache, _persisted_mtime
    with _store_lock:
        _record_cache.clear()
        _persisted_cache = None
        _persisted_mtime = None


# --------------------------------------------------------------------------- #
# attribution scopes
# --------------------------------------------------------------------------- #


class _Scope:
    __slots__ = ("node_key", "node_label", "sink", "query_id", "seen")

    def __init__(self, node_key: str, node_label: str, sink, query_id: str):
        self.node_key = node_key
        self.node_label = node_label
        self.sink = sink
        self.query_id = query_id
        self.seen: set = set()  # program keys already ledgered in this scope


_tls = threading.local()


def _stack() -> List[_Scope]:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = []
        _tls.stack = stack
    return stack


def current_scope() -> Optional[_Scope]:
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


@contextmanager
def attributing(
    node_key: str,
    node_label: str = "",
    sink=None,
    query_id: str = "",
):
    """Install a per-plan-node recording scope on this thread. Programs
    launched while the scope is innermost attribute to it; nested scopes
    (child operators) shadow it exactly like operator evaluation nests."""
    stack = _stack()
    scope = _Scope(node_key, node_label, sink, query_id)
    stack.append(scope)
    try:
        yield scope
    finally:
        stack.pop()


def session_enabled(session) -> bool:
    """The ``kernel_cost`` session property (default off: the wrapper is a
    pass-through and every output byte matches the unrecorded path)."""
    try:
        return bool(session.get("kernel_cost"))
    except KeyError:
        return False


# --------------------------------------------------------------------------- #
# the cost-recording jit wrapper
# --------------------------------------------------------------------------- #


def _static_token(v: Any) -> str:
    """Cross-process-stable token for a static argument. Callables (compiled
    expression closures) reduce to their qualname — the plan-node structural
    fingerprint in the record key is what disambiguates two closures with
    the same qualname (the closures are derived from the node's own
    expressions, which the fingerprint covers)."""
    if callable(v):
        return getattr(v, "__qualname__", None) or type(v).__name__
    if isinstance(v, (tuple, list)):
        return "(" + ",".join(_static_token(x) for x in v) + ")"
    return repr(v)


def _tree_signature(v: Any) -> Optional[str]:
    """Abstract (shape, dtype) signature of a dynamic argument's pytree;
    ``None`` when a leaf is a tracer — we are inside an enclosing program's
    trace, and THAT program owns the launch cost."""
    leaves, treedef = jax.tree_util.tree_flatten(v)
    sig = []
    for leaf in leaves:
        if isinstance(leaf, jax.core.Tracer):
            return None
        sig.append(
            (tuple(getattr(leaf, "shape", ())),
             str(getattr(leaf, "dtype", type(leaf).__name__)))
        )
    return f"{treedef}|{sig}"


class CostJit:
    """A ``jax.jit`` with the cost hook. Transparent: ``__call__`` is the
    jitted callable plus one thread-local read; every other attribute
    (``lower``, ``trace``, ``clear_cache``, ...) proxies to the jit."""

    def __init__(self, fun, label: str, jit_kwargs: dict):
        self._jit = jax.jit(fun, **jit_kwargs)  # lint: disable=jit-without-cost-hook -- the one sanctioned jax.jit: this IS the cost hook
        self.label = label
        static = jit_kwargs.get("static_argnums", ())
        if isinstance(static, int):
            static = (static,)
        self._static = frozenset(static or ())
        self.__wrapped__ = fun

    def __call__(self, *args, **kwargs):
        out = self._jit(*args, **kwargs)
        if current_scope() is not None:
            try:
                self._attribute(args, kwargs)
            except Exception:  # noqa: BLE001 — recording must never fail a query
                _count_unavailable("hook_error")
        return out

    def __getattr__(self, name):
        return getattr(self._jit, name)

    # ------------------------------------------------------------ recording

    def _signature(self, args, kwargs) -> Optional[str]:
        parts: List[str] = []
        for i, a in enumerate(args):
            if i in self._static:
                parts.append("s:" + _static_token(a))
            else:
                sig = _tree_signature(a)
                if sig is None:
                    return None
                parts.append("d:" + sig)
        for k in sorted(kwargs):
            sig = _tree_signature(kwargs[k])
            if sig is None:
                return None
            parts.append(f"k:{k}=" + sig)
        return ";".join(parts)

    def _attribute(self, args, kwargs) -> None:
        scope = current_scope()
        if scope is None:
            return
        sig = self._signature(args, kwargs)
        if sig is None:
            return  # tracing an enclosing program — it owns the cost
        platform = jax.default_backend()
        raw = f"{self.label}|{scope.node_key}|{platform}|{sig}"
        key = hashlib.sha256(raw.encode()).hexdigest()[:24]
        record = _record_cache.get(key)
        source = "memory"
        if record is None:
            persisted = _read_persisted().get(key)
            if isinstance(persisted, dict):
                # warm-process path: the XLA compile cache meant this
                # program never lowered here — attribute from the store
                record = dict(persisted)
                record["source"] = source = "store"
                _record_cache[key] = record
        if record is None:
            source = "computed"
            record = self._compute_record(key, platform, args, kwargs)
            _record_cache[key] = record
            if record.get("status") == "ok":
                _persist(key, {
                    k: v for k, v in record.items() if k != "source"
                })
        self._deliver(scope, key, record, source)

    def _compute_record(self, key, platform, args, kwargs) -> dict:
        from .observability import RECORDER

        record = {
            "label": self.label,
            "key": key,
            "platform": platform,
            "device_kind": jax.devices()[0].device_kind,
            "status": "ok",
            "source": "computed",
            "flops": None,
            "bytes_accessed": None,
            "argument_bytes": None,
            "output_bytes": None,
            "temp_bytes": None,
            "generated_code_bytes": None,
            "peak_hbm_bytes": None,
        }
        with RECORDER.span("kernel_cost", "kernelcost",
                           label=self.label, key=key) as sp:
            try:
                compiled = self._jit.lower(*args, **kwargs).compile()
            except Exception as e:  # noqa: BLE001 — degrade, never raise
                record["status"] = "cost_unavailable"
                record["reason"] = f"lower_failed:{type(e).__name__}"
                _count_unavailable("lower_failed")
                sp["status"] = record["status"]
                return record
            got_any = False
            try:
                ca = compiled.cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0] if ca else None
                if isinstance(ca, dict):
                    flops = float(ca.get("flops", -1.0))
                    nbytes = float(ca.get("bytes accessed", -1.0))
                    if flops >= 0:
                        record["flops"] = flops
                        got_any = True
                    if nbytes >= 0:
                        record["bytes_accessed"] = nbytes
                        got_any = True
            except Exception:  # noqa: BLE001
                pass
            try:
                ma = compiled.memory_analysis()
                total = 0.0
                for attr, field in (
                    ("argument_size_in_bytes", "argument_bytes"),
                    ("output_size_in_bytes", "output_bytes"),
                    ("temp_size_in_bytes", "temp_bytes"),
                    ("generated_code_size_in_bytes", "generated_code_bytes"),
                ):
                    v = getattr(ma, attr, None)
                    if v is not None:
                        record[field] = int(v)
                        if field != "generated_code_bytes":
                            total += float(v)
                        got_any = True
                if got_any:
                    # peak live HBM of one launch: arguments + outputs +
                    # XLA temp allocations (generated code is static)
                    record["peak_hbm_bytes"] = int(total)
            except Exception:  # noqa: BLE001
                pass
            if not got_any:
                record["status"] = "cost_unavailable"
                record["reason"] = "cost_analysis_unavailable"
                _count_unavailable("cost_analysis_unavailable")
            sp["status"] = record["status"]
            if record["flops"] is not None:
                sp["flops"] = record["flops"]
            if record["bytes_accessed"] is not None:
                sp["bytes_accessed"] = record["bytes_accessed"]
        return record

    def _deliver(self, scope: _Scope, key: str, record: dict, source: str) -> None:
        from .observability import RECORDER

        if scope.sink is not None:
            scope.sink(record)
        if key not in scope.seen:
            scope.seen.add(key)
            _ledger_append(scope, record)
        if RECORDER.enabled and record.get("peak_hbm_bytes"):
            # HBM-watermark counter track: one Perfetto "C" series per
            # recording thread (the device-lane proxy) — the assembled
            # cluster trace shows the live watermark under the span lanes
            RECORDER.counter_event(
                "hbm_watermark", "kernelcost",
                hbm_bytes=int(record["peak_hbm_bytes"]),
            )


def jit(fun=None, *, label: Optional[str] = None, **jit_kwargs):
    """Drop-in ``jax.jit`` replacement with the cost hook; supports the
    decorator form (``@jit`` / ``@partial(jit, static_argnums=...)``) and
    the call form (``jit(fn, static_argnums=...)``)."""
    if fun is None:
        def deco(f):
            return jit(f, label=label, **jit_kwargs)
        return deco
    return CostJit(
        fun, label or getattr(fun, "__name__", "jit"), jit_kwargs
    )


# --------------------------------------------------------------------------- #
# process ledger + cluster federation
# --------------------------------------------------------------------------- #

_LEDGER_CAP = 512
_ledger_lock = threading.Lock()
_ledger: deque = deque(maxlen=_LEDGER_CAP)

ANNOUNCE_ROWS_MAX = 64  # bounded rider: announcements must stay heartbeat-sized
_FEDERATED_TTL_SECS = 300.0
_federated: Dict[str, Tuple[float, List[dict]]] = {}


def _ledger_append(scope: _Scope, record: dict) -> None:
    verdict = classify(
        record.get("flops"), record.get("bytes_accessed"),
        device_kind=record.get("device_kind"),
    ) or {}
    row = {
        "ts": time.time(),
        "query_id": scope.query_id,
        "plan_node": scope.node_label,
        "label": record.get("label"),
        "key": record.get("key"),
        "platform": record.get("platform"),
        "flops": record.get("flops"),
        "bytes_accessed": record.get("bytes_accessed"),
        "peak_hbm_bytes": record.get("peak_hbm_bytes"),
        "arithmetic_intensity": verdict.get("arithmetic_intensity"),
        "classification": verdict.get("classification"),
        "status": record.get("status"),
    }
    with _ledger_lock:
        _ledger.append(row)


def ledger_rows() -> List[dict]:
    with _ledger_lock:
        return list(_ledger)


def clear_ledger() -> None:
    """Test hook."""
    with _ledger_lock:
        _ledger.clear()
    with _store_lock:
        _federated.clear()


def announcement_rows(limit: int = ANNOUNCE_ROWS_MAX) -> List[dict]:
    """Bounded latest-rows snapshot a worker announcement piggybacks
    (federated plane rider, same discipline as announcement_metrics)."""
    with _ledger_lock:
        rows = list(_ledger)[-max(int(limit), 0):]
    return rows


def ingest_federated(node_id: str, rows) -> int:
    """Coordinator side: fold a worker's announced kernel-cost rows in.
    Last announcement wins per node; nodes silent past the TTL evict."""
    if not isinstance(rows, list):
        return 0
    clean = [r for r in rows if isinstance(r, dict)][:ANNOUNCE_ROWS_MAX]
    now = time.time()
    with _store_lock:
        _federated[node_id] = (now, clean)
        for nid in [
            n for n, (ts, _) in _federated.items()
            if now - ts > _FEDERATED_TTL_SECS
        ]:
            del _federated[nid]
    return len(clean)


def federated_rows() -> List[Tuple[str, dict]]:
    """(node_id, row) pairs from live announcements (TTL-pruned)."""
    now = time.time()
    out: List[Tuple[str, dict]] = []
    with _store_lock:
        for nid, (ts, rows) in _federated.items():
            if now - ts > _FEDERATED_TTL_SECS:
                continue
            out.extend((nid, r) for r in rows)
    return out
