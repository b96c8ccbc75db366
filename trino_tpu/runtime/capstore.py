"""Cross-query/cross-session persistence of adaptively tuned capacities.

AdaptiveQuery re-tunes per instance, so every new process re-ran the same
grow/shrink compiles. The reference amortizes the analogous cost by
caching generated classes per expression (sql/gen/PageFunctionCompiler.java:103
result cache) and by reusing runtime stats across executions of a prepared
statement; we amortize by persisting the tuned per-node capacities keyed by
a structural plan fingerprint:

- fingerprint = sha256 of the schema'd JSON plan encoding (runtime/plancodec)
  — stable across processes for the same SQL over the same catalog, and it
  changes whenever the plan shape (and therefore the narrowing points)
  changes, so stale vectors can never be mis-applied.
- value = the capacity vector in canonical preorder over the narrowing
  candidates (the same `visit_plan` order `plan_capacities` enumerates).
- capacities are power-of-two bucketed (`_round_capacity`) BEFORE storing,
  so a store hit re-creates byte-identical program shapes and lands in the
  persistent XLA compilation cache (.jax_cache_tpu) — the warm path is one
  cached compile instead of a tuning loop.

The store is a single JSON file written via atomic rename (tempfile +
os.replace); concurrent processes merge-on-write (read latest, update
own key, replace). Lost updates between two simultaneous writers cost a
re-tune later, never corruption. Location: $TRINO_TPU_CAP_STORE, else an
in-process dict (still deduplicates tuning within one session).

An ``object://`` $TRINO_TPU_CAP_STORE runs the same single-object store on
the retrying object backend — merge-on-write becomes an etag CAS loop
(``write_if_match``), which upgrades the local backend's lost-update window
into an actual read-modify-write: concurrent writers on the rename-free
substrate never drop each other's fingerprints.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
from typing import Dict, List, Optional

from .. import knobs

_lock = threading.Lock()
_memory_store: Dict[str, List[Optional[int]]] = {}

ENV_VAR = "TRINO_TPU_CAP_STORE"


def capacity_class(n: int, base: int = 1024) -> int:
    """THE canonical 4x-spaced capacity class (1024, 4096, 16384, ...):
    the smallest class ``>= n`` — varying input sizes collapse into a
    handful of classes, so compiled-program caches key on the CLASS, not
    the row count (OOC bucket loops, the device-batching plane's batch
    keys, v2 serde frame landing).

    Boundary CONTRACT: ``n`` landing exactly on a class edge resolves to
    that class itself — ``capacity_class(4096) == 4096``, and only
    ``4097`` promotes to ``16384``. The function is a pure closed-form of
    ``n`` (no floats, no env, no process state), so two processes — or
    two runs of one process — always agree; a disagreement here would
    silently DOUBLE compiles (each side tracing its own shape) and defeat
    the device scheduler's batch keying, where lanes pack only when their
    inputs share a class. ``n <= 0`` resolves to ``base`` (the smallest
    class; zero-capacity arrays break downstream initializers).
    """
    cap = base
    while cap < n:
        cap *= 4
    return cap


def store_path() -> Optional[str]:
    return knobs.env_path(ENV_VAR)


def plan_fingerprint(plan) -> str:
    """Structural fingerprint of a logical plan (node types, symbols,
    expressions — everything the codec serializes). Delegates to the shared
    plancodec.fingerprint so the capacity store and the statistics history
    store (runtime/statstore.py) key on the SAME notion of plan identity."""
    from .plancodec import fingerprint

    return fingerprint(plan.root)


def _split_object(path: str):
    """(filesystem, key Location) for an ``object://`` store path."""
    from ..fs import Location
    from .objectstore import backend_for_root

    base, _, name = str(path).rstrip("/").rpartition("/")
    fs, _ = backend_for_root(base)
    return fs, Location("object", name)


def _read_file(path: str) -> Dict[str, List[Optional[int]]]:
    from .objectstore import is_object_uri

    if is_object_uri(path):
        fs, loc = _split_object(path)
        try:
            data = json.loads(fs.read(loc).decode())
            if isinstance(data, dict):
                return data
        except (OSError, ValueError):
            pass
        return {}
    try:
        with open(path, "r") as f:
            data = json.load(f)
        if isinstance(data, dict):
            return data
    except (OSError, ValueError):
        pass
    return {}


def _save_object(path: str, fingerprint: str, caps: List[Optional[int]]) -> None:
    """CAS merge-on-write: read latest (with etag), update our key,
    conditional put. A lost CAS re-reads and retries, so concurrent
    writers MERGE instead of clobbering."""
    fs, loc = _split_object(path)
    for _ in range(16):
        try:
            raw, etag = fs.read_with_etag(loc)
            data = json.loads(raw.decode())
            if not isinstance(data, dict):
                data = {}
        except (OSError, ValueError):
            data, etag = {}, None
        data[fingerprint] = list(caps)
        body = json.dumps(data).encode()
        if etag is None:
            if fs.write_if_absent(loc, body):
                return
        elif fs.write_if_match(loc, body, etag) is not None:
            return


def load(fingerprint: str) -> Optional[List[Optional[int]]]:
    if not fingerprint:
        return None
    path = store_path()
    with _lock:
        if path is None:
            vec = _memory_store.get(fingerprint)
        else:
            vec = _read_file(path).get(fingerprint)
    return list(vec) if vec is not None else None


def save(fingerprint: str, caps: List[Optional[int]]) -> None:
    if not fingerprint:
        return
    path = store_path()
    with _lock:
        if path is None:
            _memory_store[fingerprint] = list(caps)
            return
        from .objectstore import is_object_uri

        if is_object_uri(path):
            _save_object(path, fingerprint, caps)
            return
        data = _read_file(path)
        data[fingerprint] = list(caps)
        d = os.path.dirname(os.path.abspath(path)) or "."
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, prefix=".capstore-")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(data, f)
            os.replace(tmp, path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass


def clear_memory() -> None:
    """Test hook: drop the in-process store."""
    with _lock:
        _memory_store.clear()
